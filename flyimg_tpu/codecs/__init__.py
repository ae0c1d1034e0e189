"""Host codec layer facade.

Decode/encode dispatch: the native C codec (codecs/native, libjpeg + libwebp,
built on demand) takes the hot JPEG/WebP paths; PIL covers everything else
(PNG, GIF, alpha-carrying encodes). This layer replaces the reference's codec
binaries (ImageMagick decode, MozJPEG cjpeg, cwebp — reference
src/Core/Processor/Processor.php:15-33) with in-process calls, so image
bytes never cross a process boundary on the way to the device.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

from flyimg_tpu.codecs.sniff import MediaInfo, sniff  # noqa: F401
from flyimg_tpu.codecs import native_codec
from flyimg_tpu.codecs import pil_codec
from flyimg_tpu.codecs.exif import apply_orientation, jpeg_orientation
from flyimg_tpu.codecs.pil_codec import DecodedImage, png_bit_depth

# lazy ref to the host-pool utilization trackers (runtime/metrics.py):
# importing flyimg_tpu.runtime at module scope would drag the whole batch
# runtime (and jax) into every bare codec import
_host_pool_fn = None


def _host_pool(name: str):
    global _host_pool_fn
    if _host_pool_fn is None:
        from flyimg_tpu.runtime.metrics import host_pool as _hp

        _host_pool_fn = _hp
    return _host_pool_fn(name)


def _pool_tracked(pool_name: str):
    """Wrap a codec entry point so its wall time feeds the rolling
    busy-ratio tracker behind ``flyimg_host_pool_busy_ratio{pool=}`` —
    the per-stage host-utilization measurement the codec-overhaul work
    (ROADMAP item 4) gates on. Concurrent callers stack, so a ratio
    above 1.0 reads as an oversubscribed stage."""

    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with _host_pool(pool_name).track():
                return fn(*args, **kwargs)

        return inner

    return wrap


def media_info(data: bytes) -> MediaInfo:
    """Identify media type + dims from leading bytes. Prefers the native
    C probe (fc_probe, the in-process `identify` replacement); the pure-
    Python sniffer is the fallback when the library isn't built."""
    head = data[:65536]
    if native_codec.available():
        probed = native_codec.probe(head)
        if probed is not None:
            mime, width, height, _depth = probed
            return MediaInfo(mime, width or None, height or None)
    return sniff(head)


def _dct_scale_num(src_w: int, src_h: int, hint: Tuple[int, int]) -> int:
    """Smallest libjpeg DCT scale (scale_num/8) that keeps the decoded image
    >= 2x the target box on both axes, so the device resample remains the
    quality-determining step."""
    tw, th = hint
    if not tw or not th or src_w <= 0 or src_h <= 0:
        return 8
    for scale_num in (1, 2, 4, 8):  # 1/8, 1/4, 1/2, 1/1
        if src_w * scale_num >= tw * 2 * 8 and src_h * scale_num >= th * 2 * 8:
            return scale_num
    return 8


@_pool_tracked("decode")
def decode(
    data: bytes,
    *,
    target_hint: Optional[Tuple[int, int]] = None,
    frame: int = 0,
    info: Optional[MediaInfo] = None,
    roi: Optional[Tuple[int, int, int, int]] = None,
    call: Optional[native_codec.PngCall] = None,
) -> DecodedImage:
    """Decode bytes -> DecodedImage. JPEG/WebP/PNG ride the native codec
    when built; everything else (and all animation handling) uses PIL.
    A 16-bit PNG of grey or RGB decodes to ``uint16`` samples as stored
    (``native_codec.png_decode16``); one with alpha or a colour key, or
    read through Pillow, comes out at 8 bits with ``narrowed_from`` 16.
    ``call`` is filled by a native PNG decode (seconds and bits).
    Alpha sources keep RAW rgb + a separate alpha plane; the handler
    flattens over the bg_ color only where alpha is actually dropped.
    Pass ``info`` when the caller already probed the bytes.

    ``roi`` (JPEG only; docs/host-pipeline.md) is a ``(x0, y0, x1, y1)``
    window in POST-prescale coordinates — the same scale
    ``jpeg_batch_scale_num(info, target_hint)`` selects — asking the
    decoder to produce only that window (libjpeg-turbo crop/skip
    scanlines natively; full decode + host crop on the PIL fallback).
    The result then carries ``roi_offset``/``frame_size`` and the caller
    MUST thread the offset to the device program as a span shift. Ignored
    (full decode) for non-JPEG sources, EXIF-rotated sources (the window
    coordinates would not survive the transpose), and any decode
    failure."""
    info = info or media_info(data)
    if roi is not None and info.mime == "image/jpeg" and frame == 0:
        decoded = _decode_jpeg_roi(data, info, target_hint, roi)
        if decoded is not None:
            return decoded
    if native_codec.available():
        if info.mime == "image/jpeg":
            scale_num = jpeg_batch_scale_num(info, target_hint)
            rgb = native_codec.jpeg_decode(data, scale_num)
            if rgb is not None:
                orientation = jpeg_orientation(data)
                rgb = np.ascontiguousarray(apply_orientation(rgb, orientation))
                return DecodedImage(
                    rgb=rgb,
                    alpha=None,
                    mime="image/jpeg",
                    orig_size=(info.width or rgb.shape[1], info.height or rgb.shape[0]),
                )
        elif info.mime == "image/webp" and frame == 0:
            decoded = native_codec.webp_decode_auto(data)
            if decoded is not None:
                return _orient_container(
                    _split_alpha(decoded, "image/webp"), data, "webp"
                )
        elif info.mime == "image/png":
            depth = png_bit_depth(data)
            decoded = None
            if depth == 16 and data[25] in (0, 2):
                # the stored 16-bit samples of grey (spread to RGB) or RGB;
                # the handler's alpha flattening is 8-bit, so colour types
                # with alpha, and a tRNS colour key (png_decode16 refuses
                # it), take the 8-bit read below, counted
                decoded = native_codec.png_decode16(data, call=call)
            if decoded is None:
                decoded = native_codec.png_decode(data, call=call)
            if decoded is not None:
                image = _orient_container(
                    _split_alpha(decoded, "image/png"), data, "png"
                )
                if depth == 16 and image.rgb.dtype == np.uint8:
                    image.narrowed_from = 16
                return image
    # NOTE: no orientation here — the PIL fallback already runs
    # ImageOps.exif_transpose (pil_codec.py:76), which honors PNG eXIf
    # and WebP EXIF; applying it again would double-rotate
    return pil_codec.decode(data, target_hint=target_hint, frame=frame)


def narrow_to_u8(pixels: np.ndarray) -> np.ndarray:
    """16-bit samples -> 8, as ImageMagick scales a Q16 quantum to a char
    (``ScaleQuantumToChar``: about ``round(v / 257)``)."""
    v = pixels.astype(np.uint32) + 128
    return ((v - (v >> 8)) >> 8).astype(np.uint8)


def _decode_jpeg_roi(
    data: bytes, info: MediaInfo, target_hint, roi
) -> Optional[DecodedImage]:
    """One ROI decode attempt: native fc_jpeg_decode_roi when the turbo
    build is loaded, else the PIL decode+crop fallback. None -> the
    caller runs the normal full-frame path (EXIF-rotated sources, both
    decoders failing)."""
    if jpeg_orientation(data) != 1:
        return None
    scale_num = jpeg_batch_scale_num(info, target_hint)
    x0, y0, x1, y1 = (int(v) for v in roi)
    request = (x0, y0, x1 - x0, y1 - y0)
    if request[2] <= 0 or request[3] <= 0:
        return None
    result = None
    if native_codec.roi_supported():
        result = native_codec.jpeg_decode_roi(data, scale_num, request)
    if result is None:
        try:
            result = pil_codec.decode_jpeg_roi(data, scale_num, request)
        except Exception:
            result = None
    if result is None:
        return None
    window, offset, frame_size = result
    return DecodedImage(
        rgb=np.ascontiguousarray(window),
        alpha=None,
        mime="image/jpeg",
        orig_size=(info.width or frame_size[0], info.height or frame_size[1]),
        roi_offset=offset,
        frame_size=frame_size,
    )


def _orient_container(
    decoded: DecodedImage, data: bytes, container: str
) -> DecodedImage:
    """Apply eXIf/EXIF-chunk orientation on the NATIVE decode paths (IM's
    -auto-orient honors orientation in any container; libpng/libwebp
    don't)."""
    from flyimg_tpu.codecs.metadata import png_orientation, webp_orientation

    orientation = (
        png_orientation(data) if container == "png" else webp_orientation(data)
    )
    if orientation == 1:
        return decoded
    rgb = np.ascontiguousarray(apply_orientation(decoded.rgb, orientation))
    alpha = decoded.alpha
    if alpha is not None:
        alpha = np.ascontiguousarray(apply_orientation(alpha, orientation))
    return DecodedImage(
        rgb=rgb, alpha=alpha, mime=decoded.mime, orig_size=decoded.orig_size,
        n_frames=decoded.n_frames, narrowed_from=decoded.narrowed_from,
    )


def _split_alpha(decoded, mime: str) -> DecodedImage:
    """(pixels [h, w, 3|4], channels) -> DecodedImage with RAW rgb + a
    separate alpha plane (the contract every decode path shares)."""
    pixels, channels = decoded
    alpha = pixels[..., 3].copy() if channels == 4 else None
    rgb = np.ascontiguousarray(pixels[..., :3])
    return DecodedImage(
        rgb=rgb,
        alpha=alpha,
        mime=mime,
        orig_size=(rgb.shape[1], rgb.shape[0]),
    )


def jpeg_batch_scale_num(data_info: MediaInfo, target_hint) -> int:
    """The DCT prescale denominator the batch decode path should use for
    one source (mirrors the single-image native path above)."""
    if target_hint and data_info.width and data_info.height:
        return _dct_scale_num(data_info.width, data_info.height, target_hint)
    return 8


@_pool_tracked("decode")
def batch_jpeg_decode(items: list, split=None) -> list:
    """Aux-group runner: decode many JPEGs in ONE native pool call — C
    worker threads run in parallel regardless of Python thread counts.
    ``items`` are ``(bytes, scale_num, roi)`` with a uniform scale (the
    aux group key carries it); ``roi`` is None for a full-frame decode or
    an ``(x0, y0, x1, y1)`` post-prescale window — submitters only set it
    for orientation-1 sources (the handler's gate), so window results
    skip the EXIF transpose. Full entries return oriented RGB arrays;
    ROI entries return ``(rgb, (out_x, out_y), (full_w, full_h))`` with
    the iMCU-actualized window geometry. None = fall back to the
    single-image path. ``split`` (a ``native_codec.LaunchSplit``) is
    filled with the pool launch's two parts and its buffers."""
    pool = native_codec.get_pool()
    if pool is None:
        return [None] * len(items)
    rois = []
    for _, _, roi in items:
        if roi is None:
            rois.append(None)
        else:
            x0, y0, x1, y1 = (int(v) for v in roi)
            rois.append((x0, y0, x1 - x0, y1 - y0))
    outs = pool.decode_batch(
        [d for d, _, _ in items], items[0][1], rois=rois, split=split
    )
    results = []
    for (data, _, roi), decoded in zip(items, outs):
        if decoded is None:
            results.append(None)
        elif isinstance(decoded, tuple):
            window, offset, frame_size = decoded
            results.append((
                np.ascontiguousarray(window), offset, frame_size,
            ))
        else:
            orientation = jpeg_orientation(data)
            results.append(
                np.ascontiguousarray(apply_orientation(decoded, orientation))
            )
    return results


#: IM ratio spellings -> luma (h, v) sampling factors. The geometry form
#: "HxV" is parsed directly; both grammars are what the reference forwards
#: verbatim to `-sampling-factor` (ImageProcessor.php:105, default 1x1 at
#: config/parameters.yml:102).
_SAMPLING_RATIOS = {
    "4:4:4": (1, 1),
    "4:2:2": (2, 1),
    "4:2:0": (2, 2),
    "4:4:0": (1, 2),
    "4:1:1": (4, 1),
    "4:1:0": (4, 2),
}


def parse_sampling_factor(value) -> Tuple[int, int]:
    """IM -sampling-factor grammar -> luma (h, v) factor pair. Accepts the
    geometry form ``HxV`` (1..4 each, h*v <= 8 per the JPEG MCU budget)
    and the ratio form ``4:2:0`` etc. Unparseable values raise — the
    reference would hand them to `convert`, which errors out
    (ExecFailedException); silent coercion to some other subsampling would
    change image content without telling the caller."""
    from flyimg_tpu.exceptions import InvalidArgumentException

    s = str(value if value is not None else "1x1").strip().lower()
    if not s:
        return (1, 1)
    if s in _SAMPLING_RATIOS:
        return _SAMPLING_RATIOS[s]
    parts = s.split("x")
    if len(parts) == 2 and parts[0].isdigit() and parts[1].isdigit():
        h, v = int(parts[0]), int(parts[1])
        if 1 <= h <= 4 and 1 <= v <= 4 and h * v <= 8:
            return (h, v)
    raise InvalidArgumentException(
        f"invalid sampling factor {value!r} (expected HxV with factors "
        "1..4, h*v <= 8, or a ratio like 4:2:0)"
    )


@_pool_tracked("encode")
def batch_jpeg_encode(items: list, split=None) -> list:
    """Aux-group runner: encode many RGB frames to JPEG in ONE native pool
    call — C worker threads run the (expensive) trellis DP in parallel.
    ``items`` are (rgb, quality, sampling, mozjpeg) tuples with uniform
    parameters (the aux group key carries them); returns encoded bytes per
    item (None = fall back to the single-image encode()). moz_0 means a
    BASELINE encode — no trellis, no Huffman optimization, no progressive
    scans — exactly matching the single-image encode(mozjpeg=False) path
    so the pooled and fallback bytes are identical for one cache key.
    ``split`` (a ``native_codec.LaunchSplit``) is filled with the pool
    launch's buffers."""
    pool = native_codec.get_pool()
    if pool is None:
        return [None] * len(items)
    _, quality, sampling, mozjpeg = items[0]
    return pool.encode_batch(
        [frame for frame, _q, _s, _m in items],
        quality,
        trellis=mozjpeg,
        optimize=mozjpeg,
        progressive=mozjpeg,
        sampling=sampling,
        split=split,
    )


@_pool_tracked("encode")
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
    call: Optional[native_codec.PngCall] = None,
) -> bytes:
    """Encode via the native codec where it covers the case (jpg, webp
    without alpha; png with or without); PIL otherwise. ``uint16`` pixels
    are written as a 16-bit PNG and nothing else: no other format holds
    16 bits, and Pillow writes no 16-bit RGB (``narrow_to_u8`` first).
    ``call`` is filled by a native PNG encode (seconds and bits)."""
    if image.dtype == np.uint16:
        blob = None
        if fmt == "png" and alpha is None:
            blob = native_codec.png_encode16(image, call=call)
        if blob is None:
            raise ValueError(
                f"no encoder writes these 16-bit pixels as {fmt!r}"
                f"{' with alpha' if alpha is not None else ''}"
            )
        return blob
    if native_codec.available() and fmt == "png":
        pixels = image
        if alpha is not None:
            pixels = np.dstack([image, alpha])
        blob = native_codec.png_encode(pixels, call=call)
        if blob is not None:
            return blob
    if native_codec.available() and fmt == "webp":
        pixels = image if alpha is None else np.dstack([image, alpha])
        blob = native_codec.webp_encode(
            pixels, quality, lossless=bool(webp_lossless)
        )
        if blob is not None:
            return blob
    if native_codec.available() and alpha is None:
        if fmt in ("jpg", "jpeg"):
            sampling = parse_sampling_factor(sampling_factor)
            if mozjpeg:
                # moz_1 (default): trellis quantization + optimized Huffman
                # + progressive — the cjpeg technique set
                blob = native_codec.jpeg_encode_trellis(
                    image, quality, sampling=sampling
                )
                if blob is not None:
                    return blob
            blob = native_codec.jpeg_encode(
                image,
                quality,
                optimize=bool(mozjpeg),
                progressive=bool(mozjpeg),
                sampling=sampling,
            )
            if blob is not None:
                return blob
    return pil_codec.encode(
        image,
        fmt,
        quality=quality,
        webp_lossless=webp_lossless,
        mozjpeg=mozjpeg,
        sampling_factor=sampling_factor,
        strip=strip,
        alpha=alpha,
    )
