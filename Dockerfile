# flyimg-tpu service image.
#
# One container = one serving host (the reference ships nginx+php-fpm in one
# container; here a single asyncio process owns the host's TPU chips, so no
# process supervisor is needed). On TPU VMs, base this on a jax[tpu] image
# instead and drop the jax[cpu] install.

FROM python:3.12-slim AS build

RUN apt-get update && apt-get install -y --no-install-recommends \
        g++ make libjpeg62-turbo-dev libpng-dev libwebp-dev \
    && rm -rf /var/lib/apt/lists/*

WORKDIR /app
COPY flyimg_tpu/codecs/native /app/flyimg_tpu/codecs/native
RUN make -C flyimg_tpu/codecs/native

FROM python:3.12-slim

# ghostscript: the PDF rasterizer (reference Dockerfile:5 — pg_/dnst_
# options 415 without it); ffmpeg: the video frame-extraction fallback;
# opencv-data: the Haar cascade XMLs the face backend evaluates
# (models/haar.py — the reference facedetect's model files)
RUN apt-get update && apt-get install -y --no-install-recommends \
        libjpeg62-turbo libpng16-16 libwebp7 ghostscript ffmpeg opencv-data \
    && rm -rf /var/lib/apt/lists/*

WORKDIR /app
COPY pyproject.toml README.md ./
COPY flyimg_tpu ./flyimg_tpu
# the library is not in git and the loader would build it on first use,
# but this stage has no toolchain: take the one built above (newer than
# the sources just copied, so the loader keeps it)
COPY --from=build /app/flyimg_tpu/codecs/native/libfastcodec.so \
     ./flyimg_tpu/codecs/native/libfastcodec.so

# CPU wheels by default; TPU deployments: pip install 'jax[tpu]' -f
# https://storage.googleapis.com/jax-releases/libtpu_releases.html
RUN pip install --no-cache-dir -e ".[models,video]"

EXPOSE 8080
ENV PYTHONUNBUFFERED=1
CMD ["python", "-m", "flyimg_tpu.service.app", "serve", "--port", "8080"]
