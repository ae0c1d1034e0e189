"""PIL-backed baseline codec (decode/encode), with JPEG DCT prescale.

Replaces the decode/encode halves of the reference's native binaries:
ImageMagick decode, MozJPEG ``cjpeg`` encode (reference
src/Core/Processor/ImageProcessor.php:195-217), ``cwebp``. A native C codec
(codecs/native) overrides the hot JPEG paths when built; this module is the
always-available fallback and the reference implementation for tests.

Decode behavior matching the reference pipeline:
- EXIF auto-orientation is applied (the reference always emits
  ``-auto-orient``, ImageProcessor.php:78).
- Alpha is flattened over white for opaque-only consumers; the alpha channel
  is preserved separately so PNG/WebP outputs keep transparency.
- JPEG sources headed for a big downscale use libjpeg's DCT scaled decode
  (PIL ``draft`` mode): decoding a 4k source at 1/2..1/8 scale before the
  device resample cuts host decode time severalfold — the moral equivalent
  of smartcrop.py's prescale trick (reference python/smartcrop.py:157-172)
  applied at the decode boundary.
"""

from __future__ import annotations

import io
from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np
from PIL import Image, ImageOps

Image.MAX_IMAGE_PIXELS = 512 * 1024 * 1024  # guard decompression bombs at 512MP


def set_max_pixels(limit: int) -> None:
    """Re-bound PIL's decompression-bomb guard from the
    ``mem_max_source_pixels`` server knob (service/app.py make_app).
    <= 0 keeps the module default above rather than disabling the guard:
    an unbounded decoder defeats the memory governor's whole point."""
    if int(limit) > 0:
        Image.MAX_IMAGE_PIXELS = int(limit)


@dataclass
class DecodedImage:
    """Host-side decoded image + metadata the pipeline needs."""

    rgb: np.ndarray                      # [h, w, 3] uint8 (uint16: a 16-bit PNG)
    alpha: Optional[np.ndarray]          # [h, w] uint8 or None
    mime: str
    orig_size: Tuple[int, int]           # (w, h) BEFORE any draft prescale
    n_frames: int = 1
    # ROI decode (docs/host-pipeline.md): when set, ``rgb`` is only the
    # window of the (possibly prescaled) frame starting at this (x, y)
    # offset, and ``frame_size`` is the full (w, h) that frame would have
    # had — the dims the plan must be built against, with the window
    # offset threaded to the device program as a span shift. Both stay
    # None on every full-frame decode path.
    roi_offset: Optional[Tuple[int, int]] = None
    frame_size: Optional[Tuple[int, int]] = None
    # the bits a sample of the source had where the decode gave fewer
    # (a 16-bit PNG read at 8 bits: with alpha, a colour key, or through
    # Pillow); None where the samples kept their depth. The handler counts
    # it (flyimg_depth_narrowed_total{at="decode"}): a narrowing is never
    # silent
    narrowed_from: Optional[int] = None

    @property
    def size(self) -> Tuple[int, int]:
        return (self.rgb.shape[1], self.rgb.shape[0])


def decode(
    data: bytes,
    *,
    target_hint: Optional[Tuple[int, int]] = None,
    frame: int = 0,
) -> DecodedImage:
    """Decode bytes -> RGB array. ``target_hint`` (w, h) enables JPEG DCT
    prescale when the target is much smaller than the source. ``frame``
    selects a GIF frame (reference gif-frame option, ImageProcessor.php:171-186).
    RGB stays RAW (unflattened) for alpha sources; the pipeline flattens
    over the bg_ color only where the alpha channel is actually dropped.
    """
    img = Image.open(io.BytesIO(data))
    mime = Image.MIME.get(img.format or "", "application/octet-stream")
    orig_size = img.size

    n_frames = getattr(img, "n_frames", 1)
    if n_frames > 1 and frame:
        img.seek(min(frame, n_frames - 1))

    if img.format == "JPEG" and target_hint:
        tw, th = target_hint
        if tw * th > 0 and (tw * 3 <= img.size[0] or th * 3 <= img.size[1]):
            # libjpeg scaled decode: draft picks the smallest DCT scale that
            # stays >= 2x the requested size, keeping the device resample the
            # quality-determining step.
            img.draft("RGB", (max(tw * 2, 1), max(th * 2, 1)))

    img = ImageOps.exif_transpose(img)

    alpha = None
    if img.mode in ("RGBA", "LA", "PA") or (
        img.mode == "P" and "transparency" in img.info
    ):
        rgba = img.convert("RGBA")
        arr = np.asarray(rgba)
        alpha = arr[..., 3].copy()
        rgb = arr[..., :3].copy()
    else:
        rgb = np.asarray(img.convert("RGB")).copy()

    return DecodedImage(
        rgb=rgb, alpha=alpha, mime=mime, orig_size=orig_size, n_frames=n_frames,
        # Pillow reads a 16-bit PNG at 8 bits
        narrowed_from=16 if png_bit_depth(data) == 16 else None,
    )


def png_bit_depth(data: bytes) -> Optional[int]:
    """The bit depth in a PNG's IHDR (which the format puts first); None
    for bytes that are no PNG."""
    if len(data) < 26 or data[:8] != b"\x89PNG\r\n\x1a\n" or data[12:16] != b"IHDR":
        return None
    return data[24]


def decode_jpeg_roi(
    data: bytes, scale_num: int, roi: Tuple[int, int, int, int]
) -> Optional[Tuple[np.ndarray, Tuple[int, int], Tuple[int, int]]]:
    """Pure-Python fallback for the native ROI decode: full (draft-
    prescaled) decode, then a host crop to the requested window. Same
    return contract as ``native_codec.jpeg_decode_roi`` — ``(rgb,
    (out_x, out_y), (full_w, full_h))`` — except the window is exactly
    the requested one (a post-decode crop has no iMCU constraint). The
    downstream win (smaller device input, smaller pipeline payload)
    survives even though the decode itself still pays the full frame.

    ``roi`` is ``(x, y, w, h)`` in POST-prescale coordinates:
    ``scale_num``/8 must be the same DCT scale the caller derived the
    window under (``jpeg_batch_scale_num``), and PIL's draft at the
    exact ceil-scaled dims selects exactly that scale.
    """
    img = Image.open(io.BytesIO(data))
    if img.format != "JPEG":
        return None
    if 1 <= scale_num < 8:
        sw = (img.size[0] * scale_num + 7) // 8
        sh = (img.size[1] * scale_num + 7) // 8
        img.draft("RGB", (sw, sh))
    arr = np.asarray(img.convert("RGB"))
    fh, fw = arr.shape[:2]
    x, y, w, h = (int(v) for v in roi)
    if x < 0:
        w += x
        x = 0
    if y < 0:
        h += y
        y = 0
    w = min(w, fw - x)
    h = min(h, fh - y)
    if w <= 0 or h <= 0:
        return None
    window = np.ascontiguousarray(arr[y:y + h, x:x + w])
    return window, (x, y), (fw, fh)


def encode(
    image: np.ndarray,
    fmt: str,
    *,
    quality: int = 90,
    webp_lossless: bool = False,
    mozjpeg: bool = True,
    sampling_factor: str = "1x1",
    strip: bool = True,
    alpha: Optional[np.ndarray] = None,
) -> bytes:
    """Encode [h, w, 3] uint8 (+ optional alpha) to ``fmt`` bytes.

    fmt in {'jpg', 'png', 'webp', 'gif'} — the reference's allowed outputs
    (src/Core/Entity/Image/OutputImage.php:41). ``mozjpeg`` selects the
    high-ratio JPEG path: here (the PIL fallback) that is progressive +
    optimized Huffman only; the native path adds trellis quantization for
    the full cjpeg technique set (reference pipes through cjpeg,
    ImageProcessor.php:204-209; fastcodec.cpp fc_jpeg_encode_trellis).
    """
    quality = max(0, min(int(quality), 100))
    pil = Image.fromarray(image)
    if alpha is not None and fmt in ("png", "webp"):
        pil = pil.convert("RGBA")
        pil.putalpha(Image.fromarray(alpha))
    buf = io.BytesIO()
    if fmt in ("jpg", "jpeg"):
        from flyimg_tpu.codecs import parse_sampling_factor

        h_samp, v_samp = parse_sampling_factor(sampling_factor)
        # PIL exposes only libjpeg's 3 presets; map by chroma data rate
        # (4:4:0/4:1:1 land on the nearest available halving)
        if (h_samp, v_samp) == (1, 1):
            subsampling = 0          # 4:4:4
        elif h_samp * v_samp == 2:
            subsampling = 1          # 4:2:2 (also stands in for 4:4:0)
        else:
            subsampling = 2          # 4:2:0 and coarser
        if mozjpeg:
            # progressive + optimize buffers the WHOLE scan train before
            # emitting; PIL's bufsize estimate undershoots for
            # high-entropy 4:4:4 content and libjpeg dies with
            # "Suspension not allowed here" — give it room. The bump is
            # monotonic (restoring would race concurrent encoder threads)
            # and CAPPED so one giant image can't make every later save
            # in the process allocate a worst-case buffer; beyond the cap
            # such saves fail exactly as they did before the bump.
            from PIL import ImageFile

            needed = min(pil.size[0] * pil.size[1] * 3 * 2, 32 * 1024 * 1024)
            if ImageFile.MAXBLOCK < needed:
                ImageFile.MAXBLOCK = needed
        pil.save(
            buf,
            "JPEG",
            quality=quality,
            optimize=bool(mozjpeg),
            progressive=bool(mozjpeg),
            subsampling=subsampling,
        )
    elif fmt == "png":
        pil.save(buf, "PNG", optimize=True)
    elif fmt == "webp":
        pil.save(
            buf, "WEBP", quality=quality, lossless=bool(webp_lossless), method=4
        )
    elif fmt == "gif":
        pil.save(buf, "GIF")
    else:
        raise ValueError(f"unsupported output format: {fmt}")
    return buf.getvalue()
