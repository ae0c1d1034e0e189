"""16-bit RGB PNG originals through the normal path at their own depth: the
native 16-bit codec (stored samples in and out, every row filter and a
stream in many chunks, gamma chunks ignored, broken files refused, the
frame in a buffer the process's pool keeps, the 8-bit path byte for byte
as before), the counted narrowing
where 16 bits cannot be kept, the handler's answer by output format, the
batched ``uint16`` program against a float32 reference, its contractions'
precision as lowered, the batcher's keying by dtype, and the benchmark
warmer that refuses a program without 16-bit launches. Small shapes, CPU."""

import hashlib
import os
import struct
import sys
import time
import zlib
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flyimg_tpu import codecs
from flyimg_tpu.appconfig import AppParameters
from flyimg_tpu.codecs import native_codec
from flyimg_tpu.ops.compose import _bucket_dim, plan_layout
from flyimg_tpu.runtime.batcher import BatchController, build_batched_program
from flyimg_tpu.runtime.metrics import MetricsRegistry
from flyimg_tpu.service.handler import ImageHandler
from flyimg_tpu.service.output_image import EXT_TO_MIME, OutputSpec
from flyimg_tpu.spec.options import OptionsBag
from flyimg_tpu.spec.plan import build_plan

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench.harness import manifest  # noqa: E402


def _chunk(kind: bytes, body: bytes) -> bytes:
    return (struct.pack(">I", len(body)) + kind + body
            + struct.pack(">I", zlib.crc32(kind + body) & 0xFFFFFFFF))


def png16(pixels: np.ndarray, extra: bytes = b"") -> bytes:
    """A 16-bit PNG written by hand (no libpng): big-endian samples, filter
    0 on every row, ``extra`` chunks after the header."""
    h, w, c = pixels.shape
    rows = b"".join(b"\x00" + pixels[y].astype(">u2").tobytes() for y in range(h))
    ihdr = struct.pack(">IIBBBBB", w, h, 16, {3: 2, 4: 6}[c], 0, 0, 0)
    return (b"\x89PNG\r\n\x1a\n" + _chunk(b"IHDR", ihdr) + extra
            + _chunk(b"IDAT", zlib.compress(rows, 6)) + _chunk(b"IEND", b""))


def read_png16(data: bytes) -> np.ndarray:
    """The samples of a 16-bit RGB PNG, by zlib and the five row filters
    alone (no libpng): what an independent reader finds in the file."""
    pos, idat, ihdr = 8, b"", None
    while pos < len(data):
        (length,), kind = struct.unpack(">I", data[pos:pos + 4]), data[pos + 4:pos + 8]
        body = data[pos + 8:pos + 8 + length]
        if kind == b"IHDR":
            ihdr = struct.unpack(">IIBBBBB", body)
        elif kind == b"IDAT":
            idat += body
        pos += 12 + length
    w, h, depth, color, _, _, interlace = ihdr
    assert (depth, color, interlace) == (16, 2, 0)
    stride, bpp = w * 6, 6
    raw = zlib.decompress(idat)
    out = np.zeros((h, stride), np.int64)
    prev = np.zeros(stride, np.int64)
    for y in range(h):
        kind, row = raw[y * (stride + 1)], np.frombuffer(
            raw[y * (stride + 1) + 1:(y + 1) * (stride + 1)], np.uint8).astype(np.int64)
        cur = np.zeros(stride, np.int64)
        for x in range(stride):
            a = cur[x - bpp] if x >= bpp else 0
            b = prev[x]
            c = prev[x - bpp] if x >= bpp else 0
            if kind == 0:
                pred = 0
            elif kind == 1:
                pred = a
            elif kind == 2:
                pred = b
            elif kind == 3:
                pred = (a + b) // 2
            else:
                p = a + b - c
                pa, pb, pc = abs(p - a), abs(p - b), abs(p - c)
                pred = a if pa <= pb and pa <= pc else (b if pb <= pc else c)
            cur[x] = (row[x] + pred) & 0xFF
        out[y], prev = cur, cur
    pairs = out.reshape(h, w * 3, 2)
    return (pairs[..., 0] * 256 + pairs[..., 1]).reshape(h, w, 3).astype(np.uint16)


def _not_widened(rng, shape) -> np.ndarray:
    """Samples of which none is a multiple of 257: an 8-bit image widened
    to 16 bits cannot stand in for them."""
    a = rng.integers(0, 65536, size=shape).astype(np.uint16)
    a[a % 257 == 0] += 1
    return a


# ---------------------------------------------------------------------------
# 1. the native codec


def test_native_16bit_png_round_trip_keeps_the_stored_samples():
    rng = np.random.default_rng(16)
    pixels = _not_widened(rng, (23, 37, 3))
    decoded, channels = native_codec.png_decode16(png16(pixels))
    assert channels == 3 and decoded.dtype == np.uint16
    np.testing.assert_array_equal(decoded, pixels)
    # the decode hands the native buffer over: a view, no copy
    assert not decoded.flags.owndata
    call = native_codec.PngCall()
    blob = native_codec.png_encode16(pixels, call=call)
    assert call.bits == 16 and call.seconds > 0
    assert blob[24] == 16 and blob[25] == 2        # IHDR: 16-bit RGB
    np.testing.assert_array_equal(read_png16(blob), pixels)
    np.testing.assert_array_equal(native_codec.png_decode16(blob)[0], pixels)


def test_gamma_and_srgb_chunks_change_no_sample():
    rng = np.random.default_rng(17)
    pixels = _not_widened(rng, (9, 11, 3))
    chunks = (_chunk(b"gAMA", struct.pack(">I", 100000))
              + _chunk(b"sRGB", b"\x00"))
    decoded, _ = native_codec.png_decode16(png16(pixels, chunks))
    np.testing.assert_array_equal(decoded, pixels)


def test_the_8bit_png_path_is_byte_for_byte_what_it_was():
    """The simplified API still writes 8-bit PNGs and reads any PNG at 8
    bits: digests of the library built before 16-bit PNGs existed."""
    rng = np.random.default_rng(20261018)
    u8 = rng.integers(0, 256, size=(41, 67, 3), dtype=np.uint8)
    assert hashlib.sha256(native_codec.png_encode(u8)).hexdigest() == (
        "8141ee7a91a4136978e0efdf400510c02884623c34ede79e9ac51c5778722208")
    rgba = np.dstack([u8, u8[..., :1]])
    assert hashlib.sha256(native_codec.png_encode(rgba)).hexdigest() == (
        "07e97e829591c9ec2cf2595796af9a0fccae8e003c298d461e38500b9849a816")
    u16 = np.random.default_rng(20261018).integers(0, 65536, size=(29, 31, 3)).astype(np.uint16)
    decoded, _ = native_codec.png_decode(png16(u16))
    assert decoded.dtype == np.uint8 and hashlib.sha256(decoded.tobytes()).hexdigest() == (
        "d874bd5129d7b0e81db028f9a8b7debf8a31a0fc09fbc3854e8b1c0e14ec3888")


def test_codecs_decode_keeps_16_bits_and_says_where_it_cannot(monkeypatch):
    rng = np.random.default_rng(18)
    pixels = _not_widened(rng, (12, 10, 3))
    call = native_codec.PngCall()
    image = codecs.decode(png16(pixels), call=call)
    assert image.rgb.dtype == np.uint16 and image.narrowed_from is None
    assert call.bits == 16
    np.testing.assert_array_equal(image.rgb, pixels)
    # with alpha the 8-bit read stands, and says so
    rgba = np.dstack([pixels, pixels[..., :1]])
    image = codecs.decode(png16(rgba))
    assert image.rgb.dtype == np.uint8 and image.alpha is not None
    assert image.narrowed_from == 16
    # the Pillow fallback reads 8 bits, and says so
    monkeypatch.setattr(native_codec, "available", lambda: False)
    image = codecs.decode(png16(pixels))
    assert image.rgb.dtype == np.uint8 and image.narrowed_from == 16


def test_16bit_pixels_encode_as_a_16bit_png_and_nothing_else():
    pixels = _not_widened(np.random.default_rng(19), (8, 8, 3))
    assert codecs.encode(pixels, "png")[24] == 16
    with pytest.raises(ValueError, match="16-bit"):
        codecs.encode(pixels, "jpg")
    narrowed = codecs.narrow_to_u8(np.array([0, 128, 129, 257, 65535], np.uint16))
    np.testing.assert_array_equal(narrowed, [0, 0, 1, 1, 255])


def _filtered_png16(pixels: np.ndarray, kinds, piece: int, depth: int = 16,
                    color=None) -> bytes:
    """A PNG written by hand with row filter ``kinds[y % len(kinds)]`` on
    row y and its zlib stream cut into IDAT chunks of ``piece`` bytes."""
    h, w, c = pixels.shape
    bpp = c * depth // 8
    raw = pixels.astype(">u2" if depth == 16 else np.uint8).reshape(h, -1).view(np.uint8)
    rows, prev = [], np.zeros(raw.shape[1], np.int64)
    for y in range(h):
        cur = raw[y].astype(np.int64)
        a = np.concatenate([np.zeros(bpp, np.int64), cur[:-bpp]])
        b = prev
        cc = np.concatenate([np.zeros(bpp, np.int64), prev[:-bpp]])
        pa, pb, pc = np.abs(b - cc), np.abs(a - cc), np.abs(a + b - 2 * cc)
        paeth = np.where((pa <= pb) & (pa <= pc), a, np.where(pb <= pc, b, cc))
        kind = kinds[y % len(kinds)]
        pred = [0, a, b, (a + b) // 2, paeth][kind]
        rows.append(bytes([kind]) + ((cur - pred) & 0xFF).astype(np.uint8).tobytes())
        prev = cur
    stream = zlib.compress(b"".join(rows), 6)
    if color is None:
        color = {1: 0, 3: 2, 4: 6}[c]
    ihdr = struct.pack(">IIBBBBB", w, h, depth, color, 0, 0, 0)
    return (b"\x89PNG\r\n\x1a\n" + _chunk(b"IHDR", ihdr)
            + b"".join(_chunk(b"IDAT", stream[i:i + piece])
                       for i in range(0, len(stream), piece))
            + _chunk(b"IEND", b""))


@pytest.mark.parametrize("channels", [3, 4])
@pytest.mark.parametrize("kinds", [(0,), (1,), (2,), (3,), (4,), (4, 3, 2, 1, 0)],
                         ids=["none", "sub", "up", "average", "paeth", "mixed"])
def test_each_row_filter_and_a_stream_in_many_chunks_read_as_stored(kinds, channels):
    """The native reader of RGB and RGBA masters: every row filter PNG
    defines, whatever chunks the stream is cut into."""
    rng = np.random.default_rng([21, channels, len(kinds), kinds[0]])
    pixels = _not_widened(rng, (13, 29, channels))
    for piece in (7, 1 << 20):
        decoded, got = native_codec.png_decode16(_filtered_png16(pixels, kinds, piece))
        assert got == channels
        np.testing.assert_array_equal(decoded, pixels)


def test_a_16bit_grey_master_reads_spread_to_rgb():
    grey = _not_widened(np.random.default_rng(22), (9, 14, 1))
    decoded, channels = native_codec.png_decode16(_filtered_png16(grey, (4, 1), 5))
    assert channels == 3
    np.testing.assert_array_equal(decoded, np.repeat(grey, 3, axis=2))


def _corrupt(kind: str) -> bytes:
    pixels = _not_widened(np.random.default_rng(23), (11, 17, 3))
    blob = _filtered_png16(pixels, (4,), 64)
    if kind == "crc":
        at = blob.index(b"IDAT") + 10
        return blob[:at] + bytes([blob[at] ^ 1]) + blob[at + 1:]
    if kind == "truncated":
        return blob[:len(blob) * 2 // 3]
    if kind == "filter_type":
        # a row whose filter byte names no filter PNG defines
        bad = bytearray(zlib.decompress(b"".join(_idat_bodies(blob))))
        bad[3 * (17 * 6 + 1)] = 5
        return _with_stream(blob, zlib.compress(bytes(bad)))
    # a stream one row short
    rows = zlib.decompress(b"".join(_idat_bodies(blob)))
    return _with_stream(blob, zlib.compress(rows[:-(17 * 6 + 1)]))


def _idat_bodies(blob: bytes):
    pos = 8
    while pos < len(blob):
        (length,), kind = struct.unpack(">I", blob[pos:pos + 4]), blob[pos + 4:pos + 8]
        if kind == b"IDAT":
            yield blob[pos + 8:pos + 8 + length]
        pos += 12 + length


def _with_stream(blob: bytes, stream: bytes) -> bytes:
    return blob[:8 + 25] + _chunk(b"IDAT", stream) + _chunk(b"IEND", b"")


@pytest.mark.parametrize("kind", ["crc", "truncated", "filter_type", "short_stream"])
def test_a_broken_16bit_png_is_refused(kind):
    assert native_codec.png_decode16(_corrupt(kind)) is None


def test_the_16bit_encoder_filters_each_row_and_an_independent_reader_reads_it():
    import cv2

    # a smooth ramp with grain over part of it: rows on which different
    # filters win
    y, x = np.mgrid[0:24, 0:40]
    ramp = (y * 1500 + x * 700).astype(np.int64)
    grain = np.random.default_rng(24).integers(-300, 300, size=(24, 40))
    ramp[12:] += grain[12:]
    pixels = np.stack([ramp, ramp[::-1], 60000 - ramp], axis=2).clip(0, 65535).astype(np.uint16)
    blob = native_codec.png_encode16(pixels)
    np.testing.assert_array_equal(read_png16(blob), pixels)
    rows = zlib.decompress(b"".join(_idat_bodies(blob)))
    assert len({rows[r * (40 * 6 + 1)] for r in range(24)}) > 1
    rgba = np.dstack([pixels, pixels[..., :1]])
    back = cv2.imdecode(np.frombuffer(native_codec.png_encode16(rgba), np.uint8),
                        cv2.IMREAD_UNCHANGED)
    np.testing.assert_array_equal(back[..., [2, 1, 0, 3]], rgba)


@pytest.mark.parametrize("piece", [1 << 20, 4096], ids=["one_chunk", "many_chunks"])
def test_a_decoded_16bit_frame_takes_a_buffer_the_process_pool_keeps(monkeypatch, piece):
    """The frame's buffer, and where the stream is in many chunks the
    buffer they are joined in, come from the process's pool and go back to
    it: a second decode takes no new one."""
    pixels = _not_widened(np.random.default_rng(25), (40, 50, 3))
    blob = _filtered_png16(pixels, (4, 3), piece)
    stream = sum(len(body) for body in _idat_bodies(blob))
    joined = stream if piece < stream else 0
    # the rows are inflated behind their filter bytes into the frame's own
    # buffer: one byte a row more than the frame
    frame = 40 * (50 * 6 + 1)
    made = native_codec.DecodePool(1, _frame_min_bytes=1024)
    monkeypatch.setattr(native_codec, "_POOL", made)
    try:
        first = native_codec.png_decode16(blob)[0]
        np.testing.assert_array_equal(first, pixels)
        assert made.frame_buffers() == (
            int(joined > 0), 1, joined, frame, frame + joined)
        del first
        again = native_codec.png_decode16(blob)[0]
        np.testing.assert_array_equal(again, pixels)
        assert made.frame_buffers() == (
            int(joined > 0), 1, joined, frame, frame + joined)
        del again
        assert made.frame_buffers().idle_bytes == frame + joined
    finally:
        made.close()


# ---------------------------------------------------------------------------
# 2. the handler: o_auto (png) at 16 bits, o_jpg at 8


@pytest.fixture()
def handler():
    metrics = MetricsRegistry()
    batcher = BatchController(max_batch=4, deadline_ms=50.0, metrics=metrics)
    yield ImageHandler(storage=None, params=AppParameters({}), batcher=batcher,
                       metrics=metrics), metrics
    batcher.close()


def _render(handler, data, url, ext):
    spec = OutputSpec(name=f"t.{ext}", extension=ext, mime=EXT_TO_MIME[ext])
    return handler.transform_bytes(data, OptionsBag(url), spec, {})


def _smooth16(w, h, seed):
    rng = np.random.default_rng(seed)
    y, x = np.mgrid[0:h, 0:w].astype(np.float64)
    base = 20000.0 + 300.0 * x + 200.0 * y + rng.normal(0.0, 150.0, size=(h, w))
    return np.clip(np.rint(np.stack([base, base[::-1], base[:, ::-1]], -1)), 0, 65535).astype(np.uint16)


def test_handler_answers_a_16bit_png_with_a_16bit_png_and_a_jpeg_at_8(handler):
    ctl, metrics = handler
    src = _smooth16(96, 64, 20)
    out = _render(ctl, png16(src), "w_48,h_48", "png")
    assert out[24] == 16
    answer = read_png16(out)
    assert answer.shape == (32, 48, 3)
    # a 2x downscale of smooth ramps: the 2x2 means, within grain
    expect = src.astype(np.float64).reshape(32, 2, 48, 2, 3).mean(axis=(1, 3))
    assert np.abs(answer[4:-4, 4:-4] - expect[4:-4, 4:-4]).mean() < 150.0
    text = metrics.render_prometheus()
    assert 'flyimg_batch_depth_total{bits="16"} 1' in text
    assert 'flyimg_codec_png_images_total{op="decode",bits="16"} 1' in text
    assert 'flyimg_codec_png_images_total{op="encode",bits="16"} 1' in text
    assert "flyimg_depth_narrowed_total" not in text
    jpeg = _render(ctl, png16(src), "w_48,h_48", "jpg")
    assert jpeg[:3] == b"\xff\xd8\xff"
    text = metrics.render_prometheus()
    assert 'flyimg_depth_narrowed_total{at="output"} 1' in text
    assert 'flyimg_batch_depth_total{bits="8"} 1' in text


# ---------------------------------------------------------------------------
# 3. the batched uint16 program against a float32 reference


def _lanczos_matrix(in_size, out_size):
    """Dense float64 Lanczos3 weights [out, in], ImageMagick's sampling:
    ``(i + 0.5) * scale - 0.5``, kernel stretched by the downscale, taps
    outside the frame dropped and the rest renormalised."""
    scale = in_size / out_size
    stretch = max(scale, 1.0)
    x = np.clip((np.arange(out_size) + 0.5) * scale - 0.5, 0.0, in_size - 1.0)
    d = (np.arange(in_size)[None, :] - x[:, None]) / stretch
    w = np.where(np.abs(d) < 3.0, np.sinc(d) * np.sinc(d / 3.0), 0.0)
    return w / w.sum(axis=1, keepdims=True)


def _reference_fit(frame, out_w, out_h):
    """float32 ``jax.numpy`` at the highest matmul precision, rounded and
    clipped to 16 bits."""
    wy = jnp.asarray(_lanczos_matrix(frame.shape[0], out_h), jnp.float32)
    wx = jnp.asarray(_lanczos_matrix(frame.shape[1], out_w), jnp.float32)
    with jax.default_matmul_precision("highest"):
        tmp = jnp.einsum("oh,hwc->owc", wy, jnp.asarray(frame, jnp.float32))
        out = jnp.einsum("ow,hwc->hoc", wx, tmp)
    return np.asarray(jnp.clip(jnp.round(out), 0, 65535))


def _batched_args(url, w, h, batch, depth):
    plan = build_plan(OptionsBag(url), w, h)
    layout = plan_layout(plan)
    in_shape = (_bucket_dim(h), _bucket_dim(w))
    resample_out = (_bucket_dim(layout.resample_out[0], 64), _bucket_dim(layout.resample_out[1], 64))
    handle = build_batched_program(batch, in_shape, resample_out, None, (0, 0),
                                   plan.device_plan(), None, False, None, depth)
    return plan, layout, in_shape, handle


def test_batched_uint16_program_matches_the_float32_reference():
    w, h, batch = 200, 150, 2
    plan, layout, in_shape, handle = _batched_args("w_90,h_90", w, h, batch, 16)
    rng = np.random.default_rng(21)
    frames = [_not_widened(rng, (h, w, 3)) // 4 + 8000 for _ in range(batch)]
    block = np.zeros((batch, *in_shape, 3), np.uint16)
    for k, frame in enumerate(frames):
        block[k, :h, :w] = frame
    pair = lambda v: np.tile(np.asarray(v, np.float32), (batch, 1))  # noqa: E731
    out = handle.unstage(np.asarray(handle(*handle.stage((
        block, pair((h, w)), pair(layout.span_y), pair(layout.span_x),
        pair(layout.out_true))))))
    assert out.dtype == np.uint16
    oh, ow = layout.out_true
    assert (oh, ow) == (68, 90)                   # 67.5 rounded half up
    for k, frame in enumerate(frames):
        ref = _reference_fit(frame, ow, oh)
        diff = np.abs(out[k, :oh, :ow].astype(np.float64) - ref)
        # rounding on both sides, float32 weights on both: a level at most
        assert diff.max() <= 1.0 and diff.mean() < 0.1, (diff.max(), diff.mean())


def test_a_16bit_program_keeps_16_bits_in_its_contractions_as_lowered():
    """On the CPU every contraction computes float32, so a bfloat16
    regression would not show in the numbers: the StableHLO says what the
    TPU is told. Every ``dot_general`` of the 16-bit program asks for
    HIGHEST (float32, as the configuration states); the 8-bit program's for
    the default (bfloat16 operands on the TPU)."""
    for depth, wanted in ((16, True), (8, False)):
        _, _, in_shape, handle = _batched_args("w_90,h_90", 200, 150, 2, depth)
        specs = (jax.ShapeDtypeStruct((2, *in_shape, 3), np.uint16 if depth == 16 else np.uint8),
                 *(jax.ShapeDtypeStruct((2, 2), np.float32) for _ in range(4)))
        text = handle._jitted.lower(*handle._staged(specs)).as_text()
        dots = [line for line in text.splitlines() if "stablehlo.dot_general" in line]
        assert dots
        for line in dots:
            assert ("precision = [HIGHEST, HIGHEST]" in line) == wanted, line
            assert "precision = [HIGH, HIGH]" not in line, line
        assert ("module @jit_program16" in text) == (depth == 16)


# ---------------------------------------------------------------------------
# 4. the batcher keys groups and blocks by dtype


def test_8bit_and_16bit_members_never_share_a_launch_and_keep_a_block_each():
    metrics = MetricsRegistry()
    ctl = BatchController(max_batch=4, deadline_ms=5000.0, metrics=metrics,
                          lone_flush=False)
    try:
        plan = build_plan(OptionsBag("w_40,h_40"), 80, 60)
        rng = np.random.default_rng(22)
        deep = _not_widened(rng, (60, 80, 3))
        shallow = codecs.narrow_to_u8(deep)
        ctl.pause_launches()
        try:
            futures = [ctl.submit(deep, plan), ctl.submit(shallow, plan),
                       ctl.submit(deep, plan), ctl.submit(shallow, plan)]
            assert len(ctl._groups) == 2
            assert sorted(g.depth for g in ctl._groups.values()) == [8, 16]
            assert sorted(g.block.dtype.name for g in ctl._groups.values()) == ["uint16", "uint8"]
        finally:
            ctl.resume_launches()
        outs = [f.result(timeout=60) for f in futures]
        assert [o.dtype.name for o in outs] == ["uint16", "uint8", "uint16", "uint8"]
        np.testing.assert_array_equal(outs[0], outs[2])
        # the 16-bit answer narrowed agrees with the 8-bit one within a level
        assert np.abs(codecs.narrow_to_u8(outs[0]).astype(int) - outs[1]).max() <= 1
        text = metrics.render_prometheus()
        assert 'flyimg_batch_depth_total{bits="16"} 1' in text
        assert 'flyimg_batch_depth_total{bits="8"} 1' in text
        deadline = time.monotonic() + 10.0
        while len(ctl._spare_blocks) < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert sorted(b.dtype.name for b in ctl._spare_blocks) == ["uint16", "uint8"]
        # each dtype's next group takes its own kept block; two of each at most
        for _ in range(3):
            ctl.submit(deep, plan).result(timeout=60)
        assert 'flyimg_batch_blocks_total{from="kept"} 3' in metrics.render_prometheus()
        assert sorted(b.dtype.name for b in ctl._spare_blocks) == ["uint16", "uint8"]
    finally:
        ctl.close()


# ---------------------------------------------------------------------------
# 5. the benchmark's warmer refuses a program without 16-bit launches


@pytest.mark.parametrize("answers", ["16_bits", "8_bits"])
def test_transform16_warmer_refuses_a_program_that_answers_8_bits(answers):
    """``perfbench/warmers/transform16.py`` halves a 16-bit frame through
    the device controller before it builds anything. A controller that
    narrows it (as every program before 16-bit samples did, here put back
    by hand) is refused with the ``RuntimeError`` that ``run.py`` turns into
    exit code 4; the program as it is goes on to warm."""
    doc = manifest.load_manifest()
    config = manifest.load_config(doc, "archive-png16-lossless")
    manifest.apply_toy(config)
    warm = dict(manifest.bind(doc, "archive-png16-lossless", config).warmers)["transform16"]
    assert config["warm"][0] == "transform16", "refuses in the first seconds: listed first"
    ctl = BatchController(max_batch=8, deadline_ms=3000.0, metrics=MetricsRegistry())
    if answers == "8_bits":
        real = ctl.submit
        ctl.submit = lambda frame, plan, **kw: real(codecs.narrow_to_u8(frame), plan, **kw)
    sut = SimpleNamespace(params=AppParameters(dict(config["parameters"])), batcher=ctl)
    try:
        if answers == "8_bits":
            with pytest.raises(RuntimeError, match="cannot stage a 16-bit launch"):
                warm(sut, config, {"warm_launch_sizes": [1]})
        else:
            built = warm(sut, config, {"warm_launch_sizes": [1, 2]})
            assert built["bits"] == 16 and set(built["seconds_by_launch_size"]) == {"1", "2"}
    finally:
        ctl.close()
