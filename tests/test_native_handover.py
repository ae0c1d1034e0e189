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
import time

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
    """The real library with ``fc_free`` and ``fc_pool_release`` counted
    before they free or keep."""

    def __init__(self, real):
        self._real = real
        self.freed = []
        self.released = []

    def __getattr__(self, name):
        return getattr(self._real, name)

    def fc_free(self, ptr):
        self.freed.append(ptr)
        self._real.fc_free(ptr)

    def fc_pool_release(self, frames, ptr, cap):
        self.released.append(ptr)
        self._real.fc_pool_release(frames, ptr, cap)


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


@needs_lib
@pytest.mark.parametrize("op", ["decode", "encode"])
def test_each_workers_instants_fall_inside_its_pool_call(pool, lib, op):
    """``fastcodec.cpp`` stamps each item's start and end on
    CLOCK_MONOTONIC, the clock ``time.perf_counter_ns()`` reads: every
    item's pair lies inside the caller's own timing of the pool call, and
    the launch's split sums them."""
    name = f"fc_pool_{op}_jpeg_batch"
    real = getattr(lib._real, name)
    seen = []

    def timed(handle, items, n):
        t0 = time.perf_counter_ns()
        real(handle, items, n)
        t1 = time.perf_counter_ns()
        seen.append((t0, t1, [(items[i].t_start_ns, items[i].t_end_ns)
                              for i in range(n)]))

    setattr(lib, name, timed)
    frames = [_photo(96 + 16 * k, 64 + 8 * k, seed=k) for k in range(6)]
    split = native_codec.LaunchSplit()
    if op == "decode":
        out = pool.decode_batch(
            [_encoded(f, "JPEG", quality=90) for f in frames], split=split)
    else:
        out = pool.encode_batch(frames, 85, split=split)
    assert all(o is not None for o in out)
    (t0, t1, instants), = seen
    assert len(instants) == len(frames)
    for start, end in instants:
        assert t0 <= start <= end <= t1
    assert split.workers == 4
    assert split.worker_s == pytest.approx(
        sum(end - start for start, end in instants) * 1e-9)
    assert split.wait_s >= sum(start - t0 for start, _ in instants) * 1e-9 - 1e-9
    assert 0 < split.worker_s <= split.workers * split.native_s
    assert split.wait_s + split.worker_s <= len(frames) * split.native_s


# ---------------------------------------------------------------------------
# the decode pool's frame buffers: a full frame of the pool's size is decoded
# into a buffer an earlier frame touched, and goes back to the pool. The
# pools below keep frames of 1 KiB and up, so small frames take that path.

SMALL = 1024


def _rgb(w, h):
    return w * h * 3


@pytest.fixture()
def frame_pool(lib):
    made = native_codec.DecodePool(4, _frame_min_bytes=SMALL)
    yield made
    made.close()


def _cmyk_jpeg(w, h, seed):
    buf = io.BytesIO()
    Image.fromarray(_photo(w, h, seed=seed)).convert("CMYK").save(
        buf, format="JPEG", quality=90)
    return buf.getvalue()


def _flat_jpeg(w, h, level):
    return _encoded(np.full((h, w, 3), level, np.uint8), "JPEG", quality=95)


def _held(made):
    """(idle buffers, live ones, the most bytes live at once), with the
    bytes held, idle and live, checked against that most."""
    held = made.frame_buffers()
    assert held.idle_bytes + held.live_bytes <= held.peak_bytes, held
    return held.idle, held.live, held.peak_bytes


@needs_lib
def test_a_frame_in_a_reused_buffer_is_the_fresh_decode_byte_for_byte(frame_pool, lib):
    first = [_encoded(_photo(96, 64, seed=k), "JPEG", quality=90) for k in range(4)]
    # others of each size and kind: a CMYK source, 4:2:0, a smaller frame
    # (into a larger buffer), a grey one
    second = [
        _cmyk_jpeg(96, 64, seed=7),
        _encoded(_photo(96, 64, seed=8), "JPEG", quality=80, subsampling=2),
        _encoded(_photo(80, 56, seed=9), "JPEG", quality=90),
        _encoded(_photo(96, 64, seed=10)[..., 0], "JPEG", quality=90),
    ]
    split = native_codec.LaunchSplit()
    outs = frame_pool.decode_batch(first, 8, split=split)
    assert (split.frames_fresh, split.frames_pooled) == (4, 0)
    assert _held(frame_pool) == (0, 4, 4 * _rgb(96, 64))
    del outs
    gc.collect()
    assert _held(frame_pool) == (4, 0, 4 * _rgb(96, 64))
    assert len(lib.released) == 4 and lib.freed == []
    split = native_codec.LaunchSplit()
    outs = frame_pool.decode_batch(second, 8, split=split)
    assert (split.frames_fresh, split.frames_pooled) == (0, 4)
    assert split.buffers == 4
    for blob, got in zip(second, outs):
        _assert_adopted(got)
        ref = native_codec.jpeg_decode(blob)
        assert got.shape == ref.shape and np.array_equal(got, ref)
    del outs, ref, got
    gc.collect()
    assert len(lib.released) == 8
    # the references went through fc_free, the pool's frames did not
    assert len(lib.freed) == 4


@needs_lib
def test_a_pooled_buffer_goes_back_once_after_its_last_view(frame_pool, lib):
    blob = _encoded(_photo(64, 48, seed=1), "JPEG")
    (frame,) = frame_pool.decode_batch([blob])
    views = [frame[4:9, 1:], apply_orientation(frame, 6),
             np.ascontiguousarray(frame)]
    del frame
    for _ in range(len(views)):
        gc.collect()
        assert lib.released == [] and _held(frame_pool)[1] == 1
        views.pop()
    gc.collect()
    assert len(lib.released) == 1 and lib.freed == []
    assert _held(frame_pool) == (1, 0, _rgb(64, 48))


@needs_lib
def test_threads_dropping_pooled_frames_at_once_give_each_back_once(frame_pool, lib):
    blobs = [_encoded(_photo(40 + 8 * (k % 3), 32, seed=k), "JPEG") for k in range(6)]
    # the byte bound is read under the pool's lock all through the churn
    sampled, stop = [], threading.Event()

    def sample():
        while not stop.is_set():
            sampled.append(_held(frame_pool))

    sampler = threading.Thread(target=sample)
    sampler.start()
    try:
        n = _churn(lambda k: frame_pool.decode_batch([blobs[k % 6]])[0], rounds=10)
    finally:
        stop.set()
        sampler.join(timeout=30)
    assert sampled and len(lib.released) == n and lib.freed == []
    idle, live, peak = _held(frame_pool)
    assert live == 0 and 1 <= idle and peak <= 36 * _rgb(56, 32)


@needs_lib
def test_idle_and_live_never_exceed_the_high_water(frame_pool, lib):
    s, l = _rgb(48, 32), _rgb(96, 80)
    small = [_encoded(_photo(48, 32, seed=k), "JPEG") for k in range(5)]
    large = [_encoded(_photo(96, 80, seed=k), "JPEG") for k in range(3)]
    held = frame_pool.decode_batch(small[:3])
    assert _held(frame_pool) == (0, 3, 3 * s)
    del held
    gc.collect()
    held = frame_pool.decode_batch(small)
    # three reused, two new: the high-water is five frames' bytes
    assert _held(frame_pool) == (0, 5, 5 * s)
    del held[3:]
    gc.collect()
    assert _held(frame_pool) == (2, 3, 5 * s)
    # no idle buffer holds a larger frame: the first new one raises the
    # high-water past idle + live only by giving the idle ones up
    split = native_codec.LaunchSplit()
    big = frame_pool.decode_batch(large, split=split)
    assert (split.frames_fresh, split.frames_pooled) == (3, 0)
    assert _held(frame_pool) == (0, 6, 3 * s + 3 * l)
    del held
    gc.collect()
    assert _held(frame_pool) == (3, 3, 3 * s + 3 * l)
    del big
    gc.collect()
    assert _held(frame_pool) == (6, 0, 3 * s + 3 * l)
    # the smallest buffer that holds a frame is the one taken
    split = native_codec.LaunchSplit()
    again = frame_pool.decode_batch(small[:3] + large[:1], split=split)
    assert split.frames_pooled == 4
    assert _held(frame_pool) == (2, 4, 3 * s + 3 * l)
    assert [a.shape for a in again] == [(32, 48, 3)] * 3 + [(80, 96, 3)]
    del again
    gc.collect()
    assert len(lib.released) == 3 + 5 + 3 + 4 and lib.freed == []


@needs_lib
def test_a_frame_gets_no_buffer_over_twice_its_size_and_bytes_stay_bounded(frame_pool, lib):
    s, m, l = _rgb(48, 32), _rgb(64, 48), _rgb(96, 80)
    big = frame_pool.decode_batch(
        [_encoded(_photo(96, 80, seed=k), "JPEG") for k in range(3)])
    del big
    gc.collect()
    assert _held(frame_pool) == (3, 0, 3 * l)
    # a frame a fifth of the idle buffers' size takes none of them: a new
    # buffer, and the oldest idle one goes to keep the bytes within 3 l
    split = native_codec.LaunchSplit()
    small = frame_pool.decode_batch(
        [_encoded(_photo(48, 32, seed=k), "JPEG") for k in range(2)], split=split)
    assert (split.frames_fresh, split.frames_pooled) == (2, 0)
    held = frame_pool.frame_buffers()
    assert (held.idle, held.live, held.idle_bytes, held.live_bytes) == (2, 2, 2 * l, 2 * s)
    del small
    gc.collect()
    assert _held(frame_pool) == (4, 0, 3 * l)
    # under half of a large buffer, over a small one: new again, and it fits
    # within the bytes the pool may hold
    split = native_codec.LaunchSplit()
    (medium,) = frame_pool.decode_batch(
        [_encoded(_photo(64, 48, seed=9), "JPEG")], split=split)
    assert (split.frames_fresh, split.frames_pooled) == (1, 0) and m * 2 < l
    assert frame_pool.frame_buffers().idle_bytes == 2 * l + 2 * s
    # a large frame takes a large buffer, never a smaller one
    split = native_codec.LaunchSplit()
    (large,) = frame_pool.decode_batch(
        [_encoded(_photo(90, 80, seed=5), "JPEG")], split=split)
    assert split.frames_pooled == 1
    assert frame_pool.frame_buffers().live_bytes == m + l
    assert np.array_equal(large, native_codec.jpeg_decode(
        _encoded(_photo(90, 80, seed=5), "JPEG")))
    del medium, large
    gc.collect()
    assert _held(frame_pool)[:2] == (5, 0)


@needs_lib
def test_roi_and_frames_under_the_size_keep_fc_free(lib):
    made = native_codec.DecodePool(2, _frame_min_bytes=64 * 48 * 3)
    try:
        under = _encoded(_photo(40, 30, seed=1), "JPEG")
        full = _encoded(_photo(64, 48, seed=2), "JPEG")
        roi = (8, 8, 32, 24) if native_codec.roi_supported() else None
        split = native_codec.LaunchSplit()
        outs = made.decode_batch([under, full, full], 8, rois=[None, None, roi],
                                 split=split)
        expect_freed = 2 if roi is not None else 1
        assert (split.frames_fresh, split.frames_pooled) == (3 - expect_freed, 0)
        del outs
        gc.collect()
        assert len(lib.freed) == expect_freed
        assert len(lib.released) == 3 - expect_freed
        # at the default size every frame here is far under it
        default = native_codec.DecodePool(2)
        try:
            split = native_codec.LaunchSplit()
            outs = default.decode_batch([under, full], 8, split=split)
            assert (split.frames_fresh, split.frames_pooled) == (0, 0)
            del outs
            gc.collect()
            assert len(lib.freed) == expect_freed + 2
            assert default.frame_buffers() == (0, 0, 0, 0, 0)
        finally:
            default.close()
    finally:
        made.close()


@needs_lib
def test_destroying_the_pool_frees_its_idle_buffers_and_a_live_one_later(lib):
    made = native_codec.DecodePool(2, _frame_min_bytes=SMALL)
    blobs = [_encoded(_photo(64, 48, seed=k), "JPEG") for k in range(3)]
    outs = made.decode_batch(blobs)
    kept = outs[1]
    del outs
    gc.collect()
    handle = made._frames
    assert _held(made) == (2, 1, 3 * _rgb(64, 48))
    made.close()
    held = native_codec._frame_buffers_of(lib._real, handle)
    assert (held.idle, held.live, held.idle_bytes) == (0, 1, 0)
    assert np.array_equal(kept, native_codec.jpeg_decode(blobs[1]))
    released = len(lib.released)
    del kept
    gc.collect()
    # given back to a closed pool: freed, and the pool's last buffer takes
    # the pool with it
    assert len(lib.released) == released + 1


@needs_lib
@pytest.mark.parametrize("damage", ["truncated", "corrupt", "truncated_progressive",
                                    "corrupt_progressive"])
def test_a_damaged_frame_in_a_reused_buffer_shows_nothing_of_the_last(frame_pool, lib, damage):
    w, h = 96, 64
    source = _encoded(_photo(w, h, seed=4), "JPEG", quality=90,
                      progressive=damage.endswith("progressive"))
    if damage == "corrupt":
        blob = bytearray(source)
        start = len(blob) // 3
        blob[start:start + 64] = bytes(range(64))
        blob = bytes(blob)
    else:
        blob = source[: len(source) // 2]
    got = []
    for level in (250, 5):
        # a frame of one level, then the damaged one in the buffer it left
        (previous,) = frame_pool.decode_batch([_flat_jpeg(w, h, level)])
        del previous
        gc.collect()
        split = native_codec.LaunchSplit()
        (frame,) = frame_pool.decode_batch([blob], split=split)
        if frame is None:
            got.append(None)
            assert _held(frame_pool)[1] == 0
            continue
        assert split.frames_pooled == 1
        got.append(frame.copy())
        del frame
        gc.collect()
    ref = native_codec.jpeg_decode(blob)
    if ref is None:
        assert got == [None, None]
    else:
        # every row written: the same pixels whatever the buffer held
        assert np.array_equal(got[0], got[1]) and np.array_equal(got[0], ref)
    assert _held(frame_pool)[1] == 0


@needs_lib
def test_a_failed_item_gives_its_buffer_back(frame_pool, lib):
    good = _encoded(_photo(64, 48, seed=3), "JPEG")
    outs = frame_pool.decode_batch([good, b"\xff\xd8\xff not a jpeg", good[:200]])
    assert outs[1] is None
    del outs
    gc.collect()
    idle, live, peak = _held(frame_pool)
    assert live == 0


@needs_lib
def test_pooled_frame_share_reads_the_counter_the_handler_keeps(lib, monkeypatch):
    """``perfbench/metrics/pooled_frame_share.json`` through the benchmark's
    own reader, on the handler's registry as the harness scrapes it, with
    the process's decode pool keeping the test's small frames."""
    from perfbench.harness import manifest
    from perfbench.harness.system import parse_prometheus
    from test_launch_phases import _jpeg, _System

    doc = manifest.load_manifest()
    entry = next(m for m in doc["per_layer"] if m["name"] == "pooled_frame_share")
    assert entry["layer"] == "host_decode" and entry["moves"] == "images_per_s"
    # every cell but those whose originals are PNGs: a PNG is decoded on
    # the caller's thread, outside the codec pool's launches, which are
    # what the share counts, so it has nothing to read there
    assert entry["workloads"] == [
        c["name"] for c in doc["workloads"]
        if manifest.load_config(doc, c["config"])["frame"]["format"] != "png"]
    spec = manifest.load_metric("pooled_frame_share")
    read = manifest.load_reader(spec["reader"])
    made = native_codec.DecodePool(2, _frame_min_bytes=SMALL)
    monkeypatch.setattr(native_codec, "_POOL", made)
    system = _System()
    try:
        before = parse_prometheus(system.metrics.render_prometheus())
        system.transform(_jpeg(seed=0))
        first = parse_prometheus(system.metrics.render_prometheus())
        # one frame, into a buffer of its own: nothing reused
        assert first['flyimg_codec_frame_buffers_total{from="fresh"}'] == 1.0
        assert read({"counters_before": before, "counters_after": first},
                    **spec["args"]) == 0.0
        for seed in (1, 2, 3):
            system.transform(_jpeg(seed=seed))
        after = parse_prometheus(system.metrics.render_prometheus())
        assert read({"counters_before": before, "counters_after": after},
                    **spec["args"]) == pytest.approx(75.0)
        assert read({"counters_before": first, "counters_after": after},
                    **spec["args"]) == pytest.approx(100.0)
        # none of it on the codec controller's own registry
        assert "flyimg_codec_frame_buffers_total" not in (
            system.codec.metrics.render_prometheus())
    finally:
        system.close()
        made.close()
    # the parent's program has no such counter: nothing read, nothing raised
    assert read({"counters_before": {}, "counters_after": {
        'flyimg_codec_buffers_total{handover="adopted"}': 4.0}},
        **spec["args"]) is None


@needs_lib
def test_buffers_no_frame_took_for_the_idle_time_go_at_a_release_or_a_launch(lib):
    made = native_codec.DecodePool(2, _frame_min_bytes=_rgb(64, 48), _frame_idle_s=1.0)
    try:
        blobs = [_encoded(_photo(64, 48, seed=k), "JPEG") for k in range(3)]
        first = made.decode_batch(blobs[:2])
        second = made.decode_batch(blobs[2:])
        del first
        gc.collect()
        assert _held(made) == (2, 1, 3 * _rgb(64, 48))
        time.sleep(1.3)
        # the two aged out; the one given back now stays
        del second
        gc.collect()
        assert _held(made)[:2] == (1, 0)
        time.sleep(1.3)
        # a launch of frames under the size begins by freeing what aged out
        small = [_encoded(_photo(16, 16, seed=1), "JPEG")]
        assert made.decode_batch(small)[0].shape == (16, 16, 3)
        assert _held(made) == (0, 0, 3 * _rgb(64, 48))
        assert len(lib.released) == 3 and len(lib.freed) == 1
    finally:
        made.close()


@needs_lib
def test_a_quiet_pool_gives_its_idle_buffers_back(lib):
    made = native_codec.DecodePool(2, _frame_min_bytes=SMALL, _frame_idle_s=0.2)
    try:
        frames = made.decode_batch(
            [_encoded(_photo(64, 48, seed=k), "JPEG") for k in range(2)])
        del frames
        gc.collect()
        assert _held(made)[:2] == (2, 0)
        # no decode, no release: the idle workers free them
        deadline = time.monotonic() + 10
        while made.frame_buffers().idle and time.monotonic() < deadline:
            time.sleep(0.05)
        assert _held(made) == (0, 0, 2 * _rgb(64, 48))
    finally:
        made.close()
