"""Who owns a native codec buffer (PR 35): decoded pixels are adopted (the
returned array's ``base`` chain ends in the owner, which frees the buffer
once, when the last view goes), encoded bytes are copied once and freed.

The ownership rules run against a stub ``lib`` that counts ``fc_free`` over
memory the test owns, so they hold where the library does not build; the
byte-for-byte cases run against the real library behind a counting proxy."""

import collections
import ctypes
import gc
import io
import sys
import threading

import numpy as np
import pytest
from PIL import Image

from flyimg_tpu.codecs import native_codec
from flyimg_tpu.codecs.exif import apply_orientation

needs_lib = pytest.mark.skipif(
    not native_codec.available(), reason="fastcodec does not build here"
)


class _StubLib:
    """``fc_free`` counts and frees nothing: the test owns the memory."""

    def __init__(self):
        self.freed = []
        self._buffers = []

    def fc_free(self, ptr):
        self.freed.append(ptr)

    def buffer(self, nbytes, fill=None):
        buf = ctypes.create_string_buffer(nbytes)
        if fill is not None:
            ctypes.memmove(buf, bytes(fill), nbytes)
        self._buffers.append(buf)
        return ctypes.addressof(buf)


class _CountingLib:
    """The real library with ``fc_free`` counted before it frees."""

    def __init__(self, real):
        self._real = real
        self.freed = []

    def __getattr__(self, name):
        return getattr(self._real, name)

    def fc_free(self, ptr):
        self.freed.append(ptr)
        self._real.fc_free(ptr)


@pytest.fixture()
def lib(monkeypatch):
    """The module's loaded library replaced by the counting proxy."""
    proxy = _CountingLib(native_codec._load())
    monkeypatch.setattr(native_codec, "_lib", proxy)
    return proxy


@pytest.fixture()
def pool(lib):
    made = native_codec.DecodePool(4)
    yield made
    made.close()


def _photo(w, h, seed=0, channels=3):
    rng = np.random.default_rng(seed)
    ramp = np.linspace(0, 255, w, dtype=np.float32)[None, :, None]
    img = np.clip(ramp + rng.normal(0, 20, (h, w, channels)), 0, 255)
    return img.astype(np.uint8)


def _encoded(pixels, fmt, **kw):
    buf = io.BytesIO()
    Image.fromarray(pixels).save(buf, format=fmt, **kw)
    return buf.getvalue()


def _copied(real, ptr, shape):
    """The hand-over as it was before PR 35: a copy, then the free."""
    n = int(np.prod(shape))
    ref = np.frombuffer(ctypes.string_at(ptr, n), dtype=np.uint8).reshape(shape)
    real.fc_free(ptr)
    return ref


def _ints(n):
    return [ctypes.c_int() for _ in range(n)]


def _assert_adopted(arr):
    assert arr.dtype == np.uint8
    assert arr.flags.writeable and arr.flags.c_contiguous
    base = arr
    while isinstance(base, np.ndarray):
        base = base.base
    assert isinstance(base, native_codec._NativePixels)


# ---------------------------------------------------------------------------
# ownership, on memory the test owns


def test_the_buffer_is_freed_once_and_only_after_the_last_view_goes():
    stub = _StubLib()
    ptr = stub.buffer(4 * 6 * 3, fill=range(72))
    arr = native_codec._adopt_pixels(stub, ptr, 72)
    _assert_adopted(arr)
    assert arr.tolist() == list(range(72))
    arr[0] = 200  # written through to the native buffer, no copy between
    assert ctypes.string_at(ptr, 1) == b"\xc8"
    frame = arr.reshape(4, 6, 3)
    views = {
        "reshape": frame,
        "slice": frame[1:3, 2:, :1],
        "oriented": apply_orientation(frame, 6),
        "contiguous": np.ascontiguousarray(frame),
    }
    assert np.shares_memory(views["contiguous"], arr)
    del arr, frame
    for name in list(views):
        gc.collect()
        assert stub.freed == [], f"freed with the {name} view still held"
        del views[name]
    gc.collect()
    assert stub.freed == [ptr]


def test_a_copy_of_an_adopted_frame_lets_the_buffer_go():
    stub = _StubLib()
    ptr = stub.buffer(48)
    kept = native_codec._adopt_pixels(stub, ptr, 48).reshape(4, 4, 3)[..., 0].copy()
    gc.collect()
    assert stub.freed == [ptr] and kept.flags.owndata


def test_encoded_bytes_are_copied_once_and_the_buffer_freed():
    stub = _StubLib()
    ptr = stub.buffer(5, fill=b"\xff\xd8abc")
    blob = native_codec._copy_bytes(stub, ptr, 5)
    assert type(blob) is bytes and blob == b"\xff\xd8abc"
    assert stub.freed == [ptr]


def test_no_ctypes_type_is_made_per_byte_length():
    stub = _StubLib()
    ptr = stub.buffer(4096)
    gc.collect()
    pointers = len(ctypes._pointer_type_cache)
    for _ in range(2):
        for nbytes in range(1, 101):
            native_codec._adopt_pixels(stub, ptr, nbytes)
            native_codec._copy_bytes(stub, ptr, nbytes)
    assert len(ctypes._pointer_type_cache) == pointers
    assert len(stub.freed) == 400


def _churn(decode_once, threads=36, rounds=25):
    """``threads`` workers decode, take views and drop them at once, with
    the interpreter switching threads every few bytecodes."""
    errors = []
    start = threading.Barrier(threads)

    def work():
        try:
            start.wait(timeout=30)
            for k in range(rounds):
                arr = decode_once(k)
                view = np.ascontiguousarray(arr[1:, :, :2])
                del arr
                assert view.shape[2] == 2
        except Exception as exc:  # noqa: BLE001 - reported below
            errors.append(exc)

    workers = [threading.Thread(target=work) for _ in range(threads)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for worker in workers:
            worker.start()
        for worker in workers:
            worker.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(worker.is_alive() for worker in workers)
    assert errors == []
    gc.collect()
    return threads * rounds


def test_threads_dropping_at_once_free_every_buffer_once():
    stub = _StubLib()
    made = []
    lock = threading.Lock()

    def decode_once(_k):
        with lock:
            ptr = stub.buffer(4 * 4 * 3)
            made.append(ptr)
        return native_codec._adopt_pixels(stub, ptr, 48).reshape(4, 4, 3)

    n = _churn(decode_once)
    assert len(made) == n
    assert collections.Counter(stub.freed) == collections.Counter(made)


# ---------------------------------------------------------------------------
# the real library: the same bytes as the copy gave, and every buffer freed


@needs_lib
def test_jpeg_full_frame_is_the_copy_byte_for_byte(lib):
    data = _encoded(_photo(67, 45), "JPEG", quality=90)
    w, h = _ints(2)
    ptr = lib._real.fc_jpeg_decode(data, len(data), 8, ctypes.byref(w), ctypes.byref(h))
    ref = _copied(lib._real, ptr, (h.value, w.value, 3))
    arr = native_codec.jpeg_decode(data)
    _assert_adopted(arr)
    assert arr.shape == (45, 67, 3) and np.array_equal(arr, ref)
    assert lib.freed == []
    del arr
    gc.collect()
    assert len(lib.freed) == 1


@needs_lib
def test_jpeg_roi_window_is_the_copy_byte_for_byte(lib):
    if not native_codec.roi_supported():
        pytest.skip("no libjpeg-turbo crop API in this build")
    data = _encoded(_photo(160, 120, seed=1), "JPEG", quality=90, subsampling=2)
    w, h, ox, oy, fw, fh = _ints(6)
    ptr = lib._real.fc_jpeg_decode_roi(
        data, len(data), 8, 40, 30, 50, 40, *(ctypes.byref(v) for v in (w, h, ox, oy, fw, fh)))
    ref = _copied(lib._real, ptr, (h.value, w.value, 3))
    window, offset, frame = native_codec.jpeg_decode_roi(data, 8, (40, 30, 50, 40))
    _assert_adopted(window)
    assert np.array_equal(window, ref)
    assert offset == (ox.value, oy.value) and frame == (160, 120)
    del window
    gc.collect()
    assert len(lib.freed) == 1


@needs_lib
def test_png_rgba_is_the_copy_and_its_split_lets_the_buffer_go(lib):
    from flyimg_tpu import codecs

    data = _encoded(_photo(33, 21, seed=2, channels=4), "PNG")
    w, h, ch = _ints(3)
    ptr = lib._real.fc_png_decode(
        data, len(data), 0, ctypes.byref(w), ctypes.byref(h), ctypes.byref(ch))
    ref = _copied(lib._real, ptr, (h.value, w.value, ch.value))
    arr, channels = native_codec.png_decode(data)
    _assert_adopted(arr)
    assert channels == 4 and np.array_equal(arr, ref)
    del arr
    gc.collect()
    assert len(lib.freed) == 1
    # the served path copies rgb and alpha out of the RGBA buffer, which goes
    decoded = codecs.decode(data)
    gc.collect()
    assert len(lib.freed) == 2
    assert np.array_equal(decoded.rgb, ref[..., :3])
    assert np.array_equal(decoded.alpha, ref[..., 3])


@needs_lib
def test_webp_is_the_copy_and_a_kept_rgb_view_pins_the_buffer(lib):
    from flyimg_tpu import codecs

    data = _encoded(_photo(40, 28, seed=3), "WEBP", lossless=True)
    w, h, ch = _ints(3)
    ptr = lib._real.fc_webp_decode_auto(
        data, len(data), ctypes.byref(w), ctypes.byref(h), ctypes.byref(ch))
    if not ptr:
        pytest.skip("no libwebp in this build")
    ref = _copied(lib._real, ptr, (h.value, w.value, ch.value))
    arr, channels = native_codec.webp_decode_auto(data)
    _assert_adopted(arr)
    assert channels == 3 and np.array_equal(arr, ref)
    del arr
    gc.collect()
    assert len(lib.freed) == 1
    # three channels: DecodedImage.rgb is a view of the native buffer
    decoded = codecs.decode(data)
    gc.collect()
    assert len(lib.freed) == 1 and np.array_equal(decoded.rgb, ref)
    del decoded
    gc.collect()
    assert len(lib.freed) == 2


@needs_lib
def test_pool_batch_of_full_and_roi_items_and_one_that_fails(pool, lib):
    blobs = [_encoded(_photo(96 + 16 * k, 64 + 8 * k, seed=k), "JPEG",
                      quality=88, subsampling=2) for k in range(5)]
    blobs.insert(2, b"\xff\xd8 not a jpeg")
    roi_ok = native_codec.roi_supported()
    rois = [None, (16, 8, 40, 30), None, None, (32, 16, 48, 24), None]
    split = native_codec.LaunchSplit()
    outs = pool.decode_batch(blobs, 8, rois=rois, split=split)
    assert outs[2] is None
    assert split.buffers == 5 and split.native_s > 0 and split.handover_s > 0
    expected_bytes = 0
    for blob, roi, out in zip(blobs, rois, outs):
        if out is None:
            continue
        if roi is not None and roi_ok:
            ref, ref_offset, ref_frame = native_codec.jpeg_decode_roi(blob, 8, roi)
            got, offset, frame = out
            assert (offset, frame) == (ref_offset, ref_frame)
        else:
            ref, got = native_codec.jpeg_decode(blob), out
        _assert_adopted(got)
        assert np.array_equal(got, ref.copy())
        expected_bytes += got.nbytes
    assert split.buffer_bytes == expected_bytes
    del ref, got, out
    gc.collect()
    # the references are gone, the batch's five are held: the failed item
    # had no buffer and frees nothing
    assert len(lib.freed) == 5
    del outs
    gc.collect()
    assert len(lib.freed) == 10


@needs_lib
def test_a_hundred_sizes_leave_ctypes_caches_as_they_were(lib):
    blobs = [_encoded(_photo(8 + k, 9, seed=k), "JPEG") for k in range(100)]
    native_codec.jpeg_decode(blobs[0])
    gc.collect()
    pointers = len(ctypes._pointer_type_cache)
    shapes = set()
    for _ in range(2):
        for blob in blobs:
            shapes.add(native_codec.jpeg_decode(blob).shape)
    assert len(shapes) == 100
    assert len(ctypes._pointer_type_cache) == pointers
    gc.collect()
    assert len(lib.freed) == 201


@needs_lib
def test_threads_decoding_and_dropping_at_once_free_every_buffer(lib):
    blobs = [_encoded(_photo(24 + k, 16, seed=k), "JPEG") for k in range(5)]
    n = _churn(lambda k: native_codec.jpeg_decode(blobs[k % 5]), rounds=10)
    assert len(lib.freed) == n


@needs_lib
def test_encoders_return_the_bytes_of_the_double_copy_and_free_once(pool, lib):
    real = lib._real
    rgb = _photo(64, 48, seed=5)
    rgba = _photo(32, 24, seed=6, channels=4)

    def double_copy(ptr, n):
        return _copied(real, ptr, (n,)).tobytes()

    n = ctypes.c_size_t()
    ptr = real.fc_jpeg_encode(rgb.tobytes(), 64, 48, 85, 1, 1, 2, 2, ctypes.byref(n))
    assert native_codec.jpeg_encode(rgb, 85, sampling=(2, 2)) == double_copy(ptr, n.value)
    ptr = real.fc_jpeg_encode_trellis(rgb.tobytes(), 64, 48, 85, 1, 1, 1, ctypes.byref(n))
    trellis = double_copy(ptr, n.value)
    assert native_codec.jpeg_encode_trellis(rgb, 85) == trellis
    ptr = real.fc_png_encode(rgba.tobytes(), 32, 24, 4, ctypes.byref(n))
    assert native_codec.png_encode(rgba) == double_copy(ptr, n.value)
    assert len(lib.freed) == 3
    ptr = real.fc_webp_encode(rgb.tobytes(), 64, 48, 3, 80.0, 0, ctypes.byref(n))
    if ptr:  # libwebp is optional in a build
        assert native_codec.webp_encode(rgb, 80) == double_copy(ptr, n.value)
        assert len(lib.freed) == 4
    freed = len(lib.freed)
    split = native_codec.LaunchSplit()
    blobs = pool.encode_batch([rgb, rgb[:40]], 85, split=split)
    assert blobs[0] == trellis and type(blobs[1]) is bytes
    assert len(lib.freed) == freed + 2
    assert split.buffers == 2 and split.buffer_bytes == len(blobs[0]) + len(blobs[1])
