"""ctypes bindings for the native fastcodec library.

Builds libfastcodec.so from the tracked sources on first use (make, g++,
links libjpeg/libpng/libdeflate/libwebp) and exposes decode/encode entry
points with numpy in/out. All calls release the GIL (plain ctypes calls do), so the
fc_pool batch decode genuinely runs decodes in parallel on multi-core hosts.

``available()`` is False when the toolchain or libs are missing; callers
(flyimg_tpu.codecs) then use the PIL paths, and the loader says so once at
WARNING.
"""

from __future__ import annotations

import _ctypes
import ctypes
import dataclasses
import logging
import os
import subprocess
import threading
import time
from typing import List, NamedTuple, Optional, Tuple

import numpy as np

_DIR = os.path.join(os.path.dirname(__file__), "native")
_LIB_PATH = os.path.join(_DIR, "libfastcodec.so")
_lib = None
_lib_lock = threading.Lock()
# The build can honor a ROI window (fc_roi_supported(): libjpeg-turbo
# underneath); a plain-libjpeg build has the same entry points and batch
# layout but decodes full frames only, so ROI requests are not forwarded.
_roi_supported = False
# The newest entry point: a binary without it was built from older sources
# (another batch-item layout, no 16-bit PNG), and is rebuilt like one that
# will not load.
_REQUIRED_SYMBOL = "fc_png_encode16"


class _BatchItem(ctypes.Structure):
    # mirrors fc_batch_item in fastcodec.cpp: roi_w <= 0 = full decode;
    # the actualized window geometry comes back in out_x/out_y/full_w/full_h;
    # frame_cap > 0 marks a buffer that goes back through fc_pool_release
    # (its capacity), and frame_reused one an earlier frame had touched;
    # t_start_ns / t_end_ns are the worker's instants on CLOCK_MONOTONIC
    # (time.perf_counter_ns()'s clock)
    _fields_ = [
        ("data", ctypes.c_char_p),
        ("len", ctypes.c_size_t),
        ("scale_num", ctypes.c_int),
        ("roi_x", ctypes.c_int),
        ("roi_y", ctypes.c_int),
        ("roi_w", ctypes.c_int),
        ("roi_h", ctypes.c_int),
        ("out", ctypes.c_void_p),
        ("width", ctypes.c_int),
        ("height", ctypes.c_int),
        ("out_x", ctypes.c_int),
        ("out_y", ctypes.c_int),
        ("full_w", ctypes.c_int),
        ("full_h", ctypes.c_int),
        ("frame_cap", ctypes.c_size_t),
        ("frame_reused", ctypes.c_int),
        ("t_start_ns", ctypes.c_int64),
        ("t_end_ns", ctypes.c_int64),
    ]


class _EncodeItem(ctypes.Structure):
    _fields_ = [
        ("rgb", ctypes.c_char_p),
        ("width", ctypes.c_int),
        ("height", ctypes.c_int),
        ("quality", ctypes.c_int),
        ("trellis", ctypes.c_int),
        ("optimize", ctypes.c_int),
        ("progressive", ctypes.c_int),
        ("samp_h", ctypes.c_int),
        ("samp_v", ctypes.c_int),
        ("out", ctypes.c_void_p),
        ("out_len", ctypes.c_size_t),
        ("t_start_ns", ctypes.c_int64),
        ("t_end_ns", ctypes.c_int64),
    ]


_SOURCES = ("fastcodec.cpp", "webp_shim.h", "Makefile")


def _build() -> bool:
    try:
        proc = subprocess.run(
            ["make", "-B", "-C", _DIR], capture_output=True, timeout=120
        )
        return proc.returncode == 0 and os.path.exists(_LIB_PATH)
    except (OSError, subprocess.SubprocessError):
        return False


def _stale() -> bool:
    """The library is missing or older than a source it is built from."""
    try:
        built = os.path.getmtime(_LIB_PATH)
    except OSError:
        return True
    return any(
        os.path.getmtime(os.path.join(_DIR, name)) > built
        for name in _SOURCES
    )


def _open_current():
    """``ctypes.CDLL`` of the library if it loads and was built from these
    sources, else None (a binary of older sources is let go, so that a
    rebuild at the same path is loaded afresh)."""
    try:
        lib = ctypes.CDLL(_LIB_PATH)
    except OSError:
        return None
    if hasattr(lib, _REQUIRED_SYMBOL):
        return lib
    _ctypes.dlclose(lib._handle)
    return None


def _open_library():
    """``ctypes.CDLL`` of the library built from the tracked sources, or
    None. The binary is not in git: it is (re)built when missing or older
    than its sources, and again when what is there will not load on this
    machine (a copied-in binary linked against another installation's
    sonames) or lacks an entry point of these sources."""
    if _stale():
        _build()
    lib = _open_current()
    if lib is None and _build():
        lib = _open_current()
    return lib


def _load():
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        lib = _open_library()
        if lib is None:
            logging.getLogger(__name__).warning(
                "native host codec unavailable (could not build or load "
                "%s); every decode/encode runs on PIL", _LIB_PATH,
            )
            _lib = False
            return _lib
        lib.fc_jpeg_decode.restype = ctypes.c_void_p
        lib.fc_jpeg_decode.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_int,
            ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
        ]
        # a plain-libjpeg build (fc_roi_supported() == 0) has callers fall
        # back to full-frame decode + host crop
        global _roi_supported
        lib.fc_jpeg_decode_roi.restype = ctypes.c_void_p
        lib.fc_jpeg_decode_roi.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
        ]
        lib.fc_roi_supported.restype = ctypes.c_int
        lib.fc_roi_supported.argtypes = []
        _roi_supported = bool(lib.fc_roi_supported())
        lib.fc_pool_workers.restype = ctypes.c_int
        lib.fc_pool_workers.argtypes = [ctypes.c_void_p]
        lib.fc_pool_frames.restype = ctypes.c_void_p
        lib.fc_pool_frames.argtypes = [ctypes.c_void_p]
        lib.fc_pool_release.restype = None
        lib.fc_pool_release.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t,
        ]
        lib.fc_pool_set_frame_limits.restype = None
        lib.fc_pool_set_frame_limits.argtypes = [
            ctypes.c_void_p, ctypes.c_size_t, ctypes.c_int64,
        ]
        lib.fc_frame_pool_stats.restype = None
        lib.fc_frame_pool_stats.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_size_t),
        ]
        lib.fc_jpeg_encode.restype = ctypes.c_void_p
        lib.fc_jpeg_encode.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_size_t),
        ]
        lib.fc_jpeg_encode_trellis.restype = ctypes.c_void_p
        lib.fc_jpeg_encode_trellis.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_size_t),
        ]
        lib.fc_png_decode.restype = ctypes.c_void_p
        lib.fc_png_decode.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_int,
            ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_int),
        ]
        lib.fc_png_encode.restype = ctypes.c_void_p
        lib.fc_png_encode.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_size_t),
        ]
        lib.fc_png_decode16.restype = ctypes.c_void_p
        lib.fc_png_decode16.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_size_t),
        ]
        lib.fc_png_encode16.restype = ctypes.c_void_p
        lib.fc_png_encode16.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.POINTER(ctypes.c_size_t),
        ]
        lib.fc_probe.restype = ctypes.c_int
        lib.fc_probe.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_int),
        ]
        lib.fc_webp_decode_auto.restype = ctypes.c_void_p
        lib.fc_webp_decode_auto.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_int),
        ]
        lib.fc_webp_encode.restype = ctypes.c_void_p
        lib.fc_webp_encode.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_float, ctypes.c_int, ctypes.POINTER(ctypes.c_size_t),
        ]
        lib.fc_free.argtypes = [ctypes.c_void_p]
        lib.fc_pool_create.restype = ctypes.c_void_p
        lib.fc_pool_create.argtypes = [ctypes.c_int]
        lib.fc_pool_destroy.argtypes = [ctypes.c_void_p]
        lib.fc_pool_decode_jpeg_batch.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(_BatchItem), ctypes.c_int,
        ]
        lib.fc_pool_encode_jpeg_batch.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(_EncodeItem), ctypes.c_int,
        ]
        _lib = lib
        return _lib


def available() -> bool:
    return bool(_load())


@dataclasses.dataclass
class LaunchSplit:
    """What one pool launch says of itself, for whoever records it (the
    handler, into the registry it was built with: ``DecodePool`` keeps no
    totals). ``native_s`` is the pool call (C workers, GIL released),
    ``handover_s`` the walk over its results on the calling thread (a
    decode launch's; an encode launch leaves it 0); ``buffers`` /
    ``buffer_bytes`` count the native buffers handed over. Of a decode
    launch's frames, ``frames_pooled`` were decoded into a buffer of the
    pool's that an earlier frame had touched and ``frames_fresh`` into one
    it allocated for them (full frames of the pool's size alone; any
    other frame counts in neither). Inside the call, by the workers' own
    instants: ``worker_s`` the seconds they spent on the launch's items,
    ``wait_s`` the seconds its items waited from the call to a worker's
    start, and ``workers`` the pool's size."""

    native_s: float = 0.0
    handover_s: float = 0.0
    buffers: int = 0
    buffer_bytes: int = 0
    frames_pooled: int = 0
    frames_fresh: int = 0
    worker_s: float = 0.0
    wait_s: float = 0.0
    workers: int = 0


class _NativePixels:
    """Owner of one malloc'd buffer of decoded pixels. numpy keeps it alive
    as the end of the ``base`` chain of every view (a ``reshape``'s ``base``
    is the array ``_adopt_pixels`` makes, whose ``base`` is this object), so
    the buffer is released when the last view goes, once, on whichever
    thread drops it: through ``fc_free``, or, for a frame the decode pool
    keeps (``cap`` > 0), back to that pool through ``fc_pool_release``.
    ``__array_interface__`` names the memory by address: no ctypes array
    type is made per byte length. Both functions are held here, so
    ``__del__`` looks nothing up in a module that interpreter exit may
    already have cleared, and the handle it calls through cannot go first
    (the pool's frame handle outlives the pool while a buffer is live)."""

    __slots__ = ("_free", "_release", "_frames", "_cap", "_ptr",
                 "__array_interface__")

    def __init__(self, lib, ptr: int, nbytes: int, frames=None,
                 cap: int = 0) -> None:
        self._free = lib.fc_free
        self._release = lib.fc_pool_release if cap else None
        self._frames = frames
        self._cap = cap
        self._ptr = ptr
        self.__array_interface__ = {
            "version": 3,
            "shape": (nbytes,),
            "typestr": "|u1",
            "data": (ptr, False),
        }

    def __del__(self) -> None:
        ptr, self._ptr = self._ptr, 0
        if not ptr:
            return
        if self._cap:
            self._release(self._frames, ptr, self._cap)
        else:
            self._free(ptr)


def _adopt_pixels(lib, ptr: int, nbytes: int, frames=None,
                  cap: int = 0) -> np.ndarray:
    """Decoded pixels: the native buffer itself as a writable flat ``uint8``
    array that owns it (``_NativePixels``; ``frames`` / ``cap`` for a
    buffer the decode pool keeps). Nothing is copied; a small view kept
    for long pins the whole buffer, as it pinned numpy's copy before."""
    return np.asarray(_NativePixels(lib, ptr, nbytes, frames, cap))


def _copy_bytes(lib, ptr: int, nbytes: int) -> bytes:
    """Encoded output: the one copy the ``bytes`` contract needs, then the
    native buffer is freed."""
    try:
        return ctypes.string_at(ptr, nbytes)
    finally:
        lib.fc_free(ptr)


def jpeg_decode(
    data: bytes, scale_num: int = 8
) -> Optional[np.ndarray]:
    """Decode JPEG -> [h, w, 3] uint8; scale_num/8 is the DCT scale."""
    lib = _load()
    if not lib:
        return None
    w = ctypes.c_int()
    h = ctypes.c_int()
    ptr = lib.fc_jpeg_decode(data, len(data), scale_num, ctypes.byref(w), ctypes.byref(h))
    if not ptr:
        return None
    arr = _adopt_pixels(lib, ptr, w.value * h.value * 3)
    return arr.reshape(h.value, w.value, 3)


def roi_supported() -> bool:
    """True when the loaded library can decode JPEG sub-windows
    (fc_jpeg_decode_roi — needs a libjpeg-turbo build)."""
    return bool(_load()) and _roi_supported


def jpeg_decode_roi(
    data: bytes, scale_num: int, roi: Tuple[int, int, int, int]
) -> Optional[Tuple[np.ndarray, Tuple[int, int], Tuple[int, int]]]:
    """Decode only a window of a JPEG: ``roi`` is ``(x, y, w, h)`` in
    OUTPUT (post-prescale) coordinates. Returns ``(rgb, (out_x, out_y),
    (full_w, full_h))`` where the decoded window may start left of and be
    wider than requested (iMCU alignment) — ``out_x/out_y`` is the actual
    origin and ``full_w/full_h`` the full scaled frame the window belongs
    to. None on failure or when the build lacks the turbo crop API."""
    lib = _load()
    if not lib or not _roi_supported:
        return None
    w = ctypes.c_int()
    h = ctypes.c_int()
    ox = ctypes.c_int()
    oy = ctypes.c_int()
    fw = ctypes.c_int()
    fh = ctypes.c_int()
    ptr = lib.fc_jpeg_decode_roi(
        data, len(data), scale_num,
        int(roi[0]), int(roi[1]), int(roi[2]), int(roi[3]),
        ctypes.byref(w), ctypes.byref(h), ctypes.byref(ox), ctypes.byref(oy),
        ctypes.byref(fw), ctypes.byref(fh),
    )
    if not ptr:
        return None
    arr = _adopt_pixels(lib, ptr, w.value * h.value * 3)
    return (
        arr.reshape(h.value, w.value, 3),
        (ox.value, oy.value),
        (fw.value, fh.value),
    )


def jpeg_encode(
    rgb: np.ndarray,
    quality: int = 90,
    *,
    optimize: bool = True,
    progressive: bool = True,
    sampling: Tuple[int, int] = (1, 1),
) -> Optional[bytes]:
    """``sampling`` is the luma (h, v) factor pair — ImageMagick's
    -sampling-factor HxV geometry: (1,1)=4:4:4, (2,2)=4:2:0, (2,1)=4:2:2."""
    lib = _load()
    if not lib:
        return None
    rgb = np.ascontiguousarray(rgb, dtype=np.uint8)
    h, w = rgb.shape[:2]
    out_len = ctypes.c_size_t()
    ptr = lib.fc_jpeg_encode(
        rgb.tobytes(), w, h, int(quality), int(optimize), int(progressive),
        int(sampling[0]), int(sampling[1]), ctypes.byref(out_len),
    )
    if not ptr:
        return None
    return _copy_bytes(lib, ptr, out_len.value)


def jpeg_encode_trellis(
    rgb: np.ndarray,
    quality: int = 90,
    *,
    progressive: bool = True,
    sampling: Tuple[int, int] = (1, 1),
) -> Optional[bytes]:
    """MozJPEG-technique encode: trellis-quantized coefficients + optimized
    Huffman + progressive scans (fc_jpeg_encode_trellis). ~5-10% smaller
    than the plain optimized encoder at ~equal PSNR on photographic
    content. ``sampling`` as in :func:`jpeg_encode`."""
    lib = _load()
    if not lib:
        return None
    rgb = np.ascontiguousarray(rgb, dtype=np.uint8)
    h, w = rgb.shape[:2]
    out_len = ctypes.c_size_t()
    ptr = lib.fc_jpeg_encode_trellis(
        rgb.tobytes(), w, h, int(quality),
        int(sampling[0]), int(sampling[1]), int(progressive),
        ctypes.byref(out_len),
    )
    if not ptr:
        return None
    return _copy_bytes(lib, ptr, out_len.value)


# fc_probe format codes (keep in sync with enum fc_format in fastcodec.cpp)
PROBE_FORMATS = {
    0: "application/octet-stream",
    1: "image/jpeg",
    2: "image/png",
    3: "image/gif",
    4: "image/webp",
    5: "image/bmp",
    6: "application/pdf",
    7: "video/mp4",
    8: "video/webm",
    9: "video/x-msvideo",
    10: "video/quicktime",
}


def probe(data: bytes) -> Optional[Tuple[str, int, int, int]]:
    """Native header probe -> (mime, width, height, bit_depth); zeros where
    the header does not carry the field. None when the lib is unavailable."""
    lib = _load()
    if not lib:
        return None
    w = ctypes.c_int()
    h = ctypes.c_int()
    depth = ctypes.c_int()
    code = lib.fc_probe(
        data, len(data), ctypes.byref(w), ctypes.byref(h), ctypes.byref(depth)
    )
    return (
        PROBE_FORMATS.get(code, "application/octet-stream"),
        w.value, h.value, depth.value,
    )


@dataclasses.dataclass
class PngCall:
    """What one native PNG call says of itself, for whoever records it (the
    handler, into its registry): the seconds inside the library and the
    bits of a sample it read or wrote. ``bits`` stays 0 where no native
    PNG call finished."""

    seconds: float = 0.0
    bits: int = 0


def _timed(call: Optional[PngCall], bits: int, t0: float) -> None:
    if call is not None:
        call.seconds = time.perf_counter() - t0
        call.bits = bits


def png_decode(
    data: bytes, channels: int = 0, call: Optional[PngCall] = None
) -> Optional[Tuple[np.ndarray, int]]:
    """Decode PNG -> ([h, w, ch] uint8, ch). channels: 0 auto, 3 RGB, 4 RGBA.
    A 16-bit file comes out at 8 bits here (``png_decode16`` keeps 16)."""
    lib = _load()
    if not lib:
        return None
    w = ctypes.c_int()
    h = ctypes.c_int()
    ch = ctypes.c_int()
    t0 = time.perf_counter()
    ptr = lib.fc_png_decode(
        data, len(data), channels,
        ctypes.byref(w), ctypes.byref(h), ctypes.byref(ch),
    )
    if not ptr:
        return None
    _timed(call, 8, t0)
    arr = _adopt_pixels(lib, ptr, w.value * h.value * ch.value)
    return arr.reshape(h.value, w.value, ch.value), ch.value


def png_decode16(
    data: bytes, call: Optional[PngCall] = None
) -> Optional[Tuple[np.ndarray, int]]:
    """Decode a 16-bit PNG -> ([h, w, ch] uint16, ch), ch 3 (RGB; grey is
    spread to three channels) or 4 (with alpha): the stored samples, with
    no gamma or colour transform. The array owns the native buffer, as a
    ``uint16`` view of ``_adopt_pixels``'s bytes: nothing is copied. A
    full frame's buffer is one the process's decode pool keeps
    (``get_pool``), as a batched JPEG frame's is. None for any other
    depth, a palette, a tRNS colour key or a broken file."""
    lib = _load()
    if not lib:
        return None
    pool = get_pool()
    frames = pool._frames if pool is not None else None
    w = ctypes.c_int()
    h = ctypes.c_int()
    ch = ctypes.c_int()
    cap = ctypes.c_size_t()
    t0 = time.perf_counter()
    ptr = lib.fc_png_decode16(
        data, len(data), frames, ctypes.byref(w), ctypes.byref(h),
        ctypes.byref(ch), ctypes.byref(cap),
    )
    if not ptr:
        return None
    _timed(call, 16, t0)
    arr = _adopt_pixels(lib, ptr, w.value * h.value * ch.value * 2, frames,
                        cap.value)
    return arr.view(np.uint16).reshape(h.value, w.value, ch.value), ch.value


def png_encode(pixels: np.ndarray,
               call: Optional[PngCall] = None) -> Optional[bytes]:
    """Encode [h, w, 3|4] uint8 -> PNG bytes."""
    lib = _load()
    if not lib:
        return None
    pixels = np.ascontiguousarray(pixels, dtype=np.uint8)
    h, w = pixels.shape[:2]
    channels = pixels.shape[2] if pixels.ndim == 3 else 1
    if channels not in (3, 4):
        return None
    out_len = ctypes.c_size_t()
    t0 = time.perf_counter()
    ptr = lib.fc_png_encode(
        pixels.tobytes(), w, h, channels, ctypes.byref(out_len)
    )
    if not ptr:
        return None
    _timed(call, 8, t0)
    return _copy_bytes(lib, ptr, out_len.value)


#: deflate level of a 16-bit PNG answer (libdeflate's): zlib's default
#: level, which the 8-bit (simplified API) encode uses too
PNG_LEVEL = 6


def png_encode16(pixels: np.ndarray, level: int = PNG_LEVEL,
                 call: Optional[PngCall] = None) -> Optional[bytes]:
    """Encode [h, w, 3|4] uint16 -> a 16-bit PNG at zlib ``level``, read
    in place (no copy of a C-contiguous array)."""
    lib = _load()
    if not lib:
        return None
    pixels = np.ascontiguousarray(pixels, dtype=np.uint16)
    if pixels.ndim != 3 or pixels.shape[2] not in (3, 4):
        return None
    h, w, channels = pixels.shape
    out_len = ctypes.c_size_t()
    t0 = time.perf_counter()
    ptr = lib.fc_png_encode16(
        pixels.ctypes.data, w, h, channels, int(level),
        ctypes.byref(out_len),
    )
    if not ptr:
        return None
    _timed(call, 16, t0)
    return _copy_bytes(lib, ptr, out_len.value)


def webp_decode_auto(data: bytes) -> Optional[Tuple[np.ndarray, int]]:
    """(pixels, channels) with channels 4 iff the file carries alpha."""
    lib = _load()
    if not lib:
        return None
    w = ctypes.c_int()
    h = ctypes.c_int()
    ch = ctypes.c_int()
    ptr = lib.fc_webp_decode_auto(
        data, len(data), ctypes.byref(w), ctypes.byref(h), ctypes.byref(ch)
    )
    if not ptr:
        return None
    arr = _adopt_pixels(lib, ptr, w.value * h.value * ch.value)
    return arr.reshape(h.value, w.value, ch.value), ch.value


def webp_encode(
    pixels: np.ndarray, quality: int = 90, lossless: bool = False
) -> Optional[bytes]:
    """[h, w, 3|4] uint8 -> WebP; alpha selected by the pixel layout
    (cwebp parity for transparent outputs)."""
    lib = _load()
    if not lib:
        return None
    pixels = np.ascontiguousarray(pixels, dtype=np.uint8)
    h, w = pixels.shape[:2]
    channels = pixels.shape[2]
    out_len = ctypes.c_size_t()
    ptr = lib.fc_webp_encode(
        pixels.tobytes(), w, h, channels, float(quality), int(lossless),
        ctypes.byref(out_len),
    )
    if not ptr:
        return None
    return _copy_bytes(lib, ptr, out_len.value)


class FrameBuffers(NamedTuple):
    """The decode pool's kept frame buffers: how many are idle and live,
    their bytes, and the most bytes that were live at once, which
    ``idle_bytes + live_bytes`` never exceeds."""

    idle: int
    live: int
    idle_bytes: int
    live_bytes: int
    peak_bytes: int


def _frame_buffers_of(lib, frames) -> FrameBuffers:
    """``FrameBuffers`` of a frame pool handle (``fc_pool_frames``), valid
    while its decode pool is open or a buffer it handed out is live."""
    out = (ctypes.c_size_t * 5)()
    lib.fc_frame_pool_stats(frames, out)
    return FrameBuffers(*out)


class DecodePool:
    """Parallel JPEG decode over the native worker pool. A full frame of
    32 MiB or more (``kFramePoolMinBytes`` in ``fastcodec.cpp``: glibc's
    largest mmap threshold) is decoded into a buffer the pool keeps, which
    comes back to it when the frame's last view goes. The pool never holds
    more bytes of such buffers, idle and live, than were live at once,
    hands a frame no buffer over twice its size, and frees a buffer no
    frame took for 20 s. ``_frame_min_bytes`` and ``_frame_idle_s`` lower
    the size and the time (tests decode small frames through the pool,
    and age them, with them)."""

    def __init__(self, n_threads: Optional[int] = None, *,
                 _frame_min_bytes: Optional[int] = None,
                 _frame_idle_s: Optional[float] = None) -> None:
        lib = _load()
        if not lib:
            raise RuntimeError("fastcodec unavailable")
        self._lib = lib
        self._pool = lib.fc_pool_create(n_threads or os.cpu_count() or 1)
        self._workers = lib.fc_pool_workers(self._pool)
        self._frames = lib.fc_pool_frames(self._pool)
        if _frame_min_bytes is not None or _frame_idle_s is not None:
            # 0 and -1: the library's own limit
            lib.fc_pool_set_frame_limits(
                self._pool, _frame_min_bytes or 0,
                -1 if _frame_idle_s is None else round(_frame_idle_s * 1000),
            )

    def frame_buffers(self) -> FrameBuffers:
        """The pool's kept frame buffers (all zero once it is closed)."""
        if self._frames is None:
            return FrameBuffers(0, 0, 0, 0, 0)
        return _frame_buffers_of(self._lib, self._frames)

    def decode_batch(
        self,
        blobs: List[bytes],
        scale_num: int = 8,
        rois: Optional[List[Optional[Tuple[int, int, int, int]]]] = None,
        split: Optional[LaunchSplit] = None,
    ) -> list:
        """Decode many JPEGs in ONE pool call. Plain entries return an
        RGB array (or None on per-image failure). ``rois`` (parallel to
        ``blobs``; entries may be None) requests sub-window decodes in
        OUTPUT coordinates — those entries return ``(rgb, (out_x, out_y),
        (full_w, full_h))`` like :func:`jpeg_decode_roi`, with the same
        iMCU-actualized geometry contract. ``split``, when given, is
        filled with this launch's two parts and its buffers."""
        n = len(blobs)
        if n == 0:
            return []
        items = (_BatchItem * n)()
        keepalive = []
        for i, blob in enumerate(blobs):
            buf = ctypes.create_string_buffer(blob, len(blob))
            keepalive.append(buf)
            items[i].data = ctypes.cast(buf, ctypes.c_char_p)
            items[i].len = len(blob)
            items[i].scale_num = scale_num
            # a plain-libjpeg build decodes the full frame (roi_w 0)
            roi = rois[i] if rois is not None and _roi_supported else None
            if roi is not None:
                items[i].roi_x = int(roi[0])
                items[i].roi_y = int(roi[1])
                items[i].roi_w = int(roi[2])
                items[i].roi_h = int(roi[3])
        self._pool_call(self._lib.fc_pool_decode_jpeg_batch, items, n, split)
        decoded = time.perf_counter()
        out: list = []
        buffers = nbytes = pooled = fresh = 0
        for i in range(n):
            if not items[i].out:
                out.append(None)
                continue
            w, h = items[i].width, items[i].height
            buffers += 1
            nbytes += w * h * 3
            cap = items[i].frame_cap
            if cap and items[i].frame_reused:
                pooled += 1
            elif cap:
                fresh += 1
            arr = _adopt_pixels(
                self._lib, items[i].out, w * h * 3, self._frames, cap)
            rgb = arr.reshape(h, w, 3)
            if items[i].roi_w > 0:
                out.append((
                    rgb,
                    (items[i].out_x, items[i].out_y),
                    (items[i].full_w, items[i].full_h),
                ))
            else:
                out.append(rgb)
        if split is not None:
            split.handover_s = time.perf_counter() - decoded
            split.buffers = buffers
            split.buffer_bytes = nbytes
            split.frames_pooled = pooled
            split.frames_fresh = fresh
        return out

    def encode_batch(
        self,
        frames: List[np.ndarray],
        quality: int = 90,
        *,
        trellis: bool = True,
        optimize: bool = True,
        progressive: bool = True,
        sampling: Tuple[int, int] = (1, 1),
        split: Optional[LaunchSplit] = None,
    ) -> List[Optional[bytes]]:
        """Encode many RGB frames to JPEG in ONE native pool call — the
        encode-side twin of decode_batch. The trellis DP is the expensive
        half of a miss (several ms/image), so bursts must pay it in
        parallel on C worker threads, not serially under one Python
        caller. ``split``, when given, is filled with this launch's
        pool call, its workers and its buffers."""
        n = len(frames)
        if n == 0:
            return []
        items = (_EncodeItem * n)()
        keepalive = []
        for i, frame in enumerate(frames):
            arr = np.ascontiguousarray(frame, dtype=np.uint8)
            keepalive.append(arr)
            h, w = arr.shape[:2]
            items[i].rgb = ctypes.cast(
                arr.ctypes.data_as(ctypes.c_void_p), ctypes.c_char_p
            )
            items[i].width = w
            items[i].height = h
            items[i].quality = int(quality)
            items[i].trellis = int(trellis)
            items[i].optimize = int(optimize)
            items[i].progressive = int(progressive)
            items[i].samp_h = int(sampling[0])
            items[i].samp_v = int(sampling[1])
        self._pool_call(self._lib.fc_pool_encode_jpeg_batch, items, n, split)
        out: List[Optional[bytes]] = []
        for i in range(n):
            if not items[i].out:
                out.append(None)
                continue
            out.append(
                _copy_bytes(self._lib, items[i].out, items[i].out_len)
            )
        if split is not None:
            split.buffers = n - out.count(None)
            split.buffer_bytes = sum(len(blob) for blob in out if blob)
        return out

    def _pool_call(self, call, items, n: int,
                   split: Optional[LaunchSplit]) -> None:
        """One pool call over ``items``, annotated as the launch's ``pool``
        where a batch controller runs this thread's launch
        (``tracing.launch_annotation``); with ``split``, its seconds and
        what its workers did (the items' own instants)."""
        from flyimg_tpu.runtime import tracing

        with tracing.launch_annotation("pool"):
            called_ns = time.perf_counter_ns()
            call(self._pool, items, n)
            returned_ns = time.perf_counter_ns()
        if split is not None:
            split.native_s = (returned_ns - called_ns) * 1e-9
            split.workers = self._workers
            split.worker_s = sum(
                items[i].t_end_ns - items[i].t_start_ns for i in range(n)
            ) * 1e-9
            split.wait_s = sum(
                items[i].t_start_ns - called_ns for i in range(n)
            ) * 1e-9

    def close(self) -> None:
        if self._pool:
            self._lib.fc_pool_destroy(self._pool)
            self._pool = None
            # the handle lives on in the frames still out, not here
            self._frames = None

    def __del__(self) -> None:  # pragma: no cover
        try:
            self.close()
        except Exception:
            pass


_POOL: Optional[DecodePool] = None
_POOL_LOCK = threading.Lock()


def get_pool() -> Optional[DecodePool]:
    """Process-wide native decode pool (lazy; None when fastcodec is not
    built). Serving routes concurrent JPEG cache-misses through it in one
    batch call — C worker threads decode in parallel regardless of how
    many Python threads the HTTP layer runs (SURVEY.md section 7 hard
    part 5)."""
    global _POOL
    if not available():
        return None
    with _POOL_LOCK:
        if _POOL is None:
            _POOL = DecodePool()
        return _POOL
