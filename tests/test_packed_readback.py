"""A transform launch read back as 32-bit words: where a row of the batched
program's output holds a whole number of words (``W * 3`` divisible by 4 at
8 bits, by 2 at 16), each piece's step packs the row's samples into
``uint32`` words, little-endian, and ``ProgramHandle.unstage`` hands on the
host's ``uint8`` / ``uint16`` view of them; every other width returns the
samples as before. Pinned here: the word size by shape, the same bytes as
the program without words, the view, the counter and span attribute of the
dtype that crossed the link, the mesh variant, no intermediate whose minor
axis is a word's samples, the byte-order refusal, and the benchmark's
readers of the new counter. Small shapes, CPU."""

import os
import re
import sys

import jax
import numpy as np
import pytest

from flyimg_tpu.ops import compose
from flyimg_tpu.ops.compose import (
    ReadbackByteOrderError,
    make_program_fn,
    readback_word_size,
)
from flyimg_tpu.runtime import tracing
from flyimg_tpu.runtime.batcher import BatchController, build_batched_program
from flyimg_tpu.runtime.metrics import MetricsRegistry
from flyimg_tpu.spec.options import OptionsBag
from flyimg_tpu.spec.plan import build_plan

from test_ops import make_test_image

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class _Parked(BatchController):
    """The executor is parked: the test thread owns the queued group."""

    def _run(self):
        return


def _frame(w, h, depth, seed):
    img = make_test_image(w, h, seed=seed)
    if depth == 8:
        return img
    # 16-bit samples that no 8-bit image widened by 257 gives
    rng = np.random.default_rng(seed)
    return img.astype(np.uint16) * 256 + rng.integers(0, 256, img.shape, dtype=np.uint16)


def _queued(width, depth, n=2, *, mesh=None, metrics=None):
    """A parked controller holding one crop-fill group of ``n`` frames to a
    ``width`` x 90 extent; returns (controller, group, batch, arrays)."""
    ctl = _Parked(max_batch=8, deadline_ms=10_000.0, mesh=mesh,
                  lone_flush=False, metrics=metrics)
    options = f"w_{width},h_90,c_1"
    for seed in range(n):
        w, h = 320 - 6 * seed, 240 - 4 * seed
        ctl.submit(_frame(w, h, depth, seed), build_plan(OptionsBag(options), w, h))
    (group,) = ctl._groups.values()
    batch, arrays = ctl._assemble(group, group.members)
    return ctl, group, batch, arrays


def _close(ctl):
    for group in ctl._groups.values():
        for member in group.members:
            member.future.cancel()
    ctl._groups.clear()
    ctl.close()


def _unpacked(group, arrays):
    """What ``vmap`` of the single-image program gives fed the assembled
    NHWC arrays: the samples the words must hold."""
    inner = make_program_fn(
        group.resample_out, group.pad_canvas, group.pad_offset,
        group.device_plan, rotate_dynamic=group.rotate_dynamic,
        band_taps=group.band_taps, depth=group.depth,
    )
    return np.asarray(jax.jit(jax.vmap(inner))(*arrays))


# (depth, output width, bytes of the unit the output crosses the link in)
_WIDTHS = [
    (8, 120, 4),    # 360 samples: 90 words
    (8, 121, 1),    # 363: no whole words, the samples
    (8, 122, 1),    # 366: two samples over
    (16, 120, 4),
    (16, 121, 2),   # 363 odd: the samples
    (16, 122, 4),   # 366 even: 183 words of two
]
_IDS = [f"u{d}_w{w}" for d, w, _ in _WIDTHS]


@pytest.mark.parametrize("depth,width,word", _WIDTHS, ids=_IDS)
def test_word_size_follows_the_row(depth, width, word):
    assert readback_word_size(width, depth) == word
    # the cells' output widths all pack
    assert readback_word_size(1600, 8) == readback_word_size(832, 8) == 4
    assert readback_word_size(2048, 16) == 4


@pytest.mark.parametrize("depth,width,word", _WIDTHS, ids=_IDS)
def test_words_view_as_the_samples_the_program_gave(depth, width, word):
    """The program's read-back crosses as ``uint32`` words where the row
    allows and as the samples where it does not; ``unstage`` makes of it,
    without a copy, exactly the samples the program without words gives."""
    ctl, group, batch, arrays = _queued(width, depth)
    try:
        assert group.depth == depth
        fn, _ = ctl._program(group, batch)
        sample = np.dtype(f"u{depth // 8}")
        assert fn.samples == sample
        raw = np.asarray(fn(*fn.stage(arrays)))
        assert raw.dtype == np.dtype(f"u{word}")
        assert raw.shape == (batch, 90, width * 3 * sample.itemsize // word)
        assert raw.flags.c_contiguous
        got = fn.unstage(raw)
        want = _unpacked(group, arrays)
        assert got.dtype == sample and got.shape == want.shape == (batch, 90, width, 3)
        assert got.flags.c_contiguous and np.shares_memory(got, raw)
        np.testing.assert_array_equal(got, want)
        assert ("d2h_word" in fn.descriptor) == (word == 4)
    finally:
        _close(ctl)


def _launch_with_a_trace(width, depth):
    """Two members through a live controller, the first under a trace;
    returns (registry text, the launch span's attributes, answers)."""
    metrics = MetricsRegistry()
    ctl = BatchController(max_batch=2, deadline_ms=10_000.0, metrics=metrics,
                          lone_flush=False)
    trace = tracing.Trace()
    try:
        futures = []
        for seed in range(2):
            w, h = 320 - 6 * seed, 240 - 4 * seed
            plan = build_plan(OptionsBag(f"w_{width},h_90,c_1"), w, h)
            if seed == 0:
                with tracing.activate(trace):
                    futures.append(ctl.submit(_frame(w, h, depth, seed), plan))
            else:
                futures.append(ctl.submit(_frame(w, h, depth, seed), plan))
        outs = [f.result(timeout=120) for f in futures]
    finally:
        ctl.close()

    def spans(node):
        yield node
        for child in node["children"]:
            yield from spans(child)

    (span,) = [s for root in trace.as_dict()["spans"] for s in spans(root)
               if s["name"] == "device_execute"]
    return metrics.render_prometheus(), span["attributes"], outs


@pytest.mark.parametrize("depth,width,word", [
    (8, 120, "u32"), (8, 121, "u8"), (16, 122, "u32"), (16, 121, "u16"),
], ids=["u8_packed", "u8_samples", "u16_packed", "u16_samples"])
def test_a_launch_counts_the_dtype_its_read_back_crossed_in(depth, width, word):
    """``flyimg_batch_readback_words_total{word}`` once a launch, beside the
    launch it belongs to, and ``device.d2h_word`` on its shared span; the
    bytes counted are the samples' whatever crossed."""
    text, attrs, outs = _launch_with_a_trace(width, depth)
    assert all(o.dtype == np.dtype(f"u{depth // 8}") and o.shape == (90, width, 3)
               for o in outs)
    assert f'flyimg_batch_readback_words_total{{word="{word}"}} 1' in text, text
    others = {"u32", "u8", "u16"} - {word}
    assert not any(f'word="{o}"' in text for o in others)
    assert "flyimg_batches_total 1" in text
    assert attrs["device.d2h_word"] == word
    assert attrs["device.d2h_bytes"] == 2 * 90 * width * 3 * depth // 8


@pytest.mark.parametrize("width,word", [(120, 4), (121, 1)],
                         ids=["packed", "samples"])
def test_the_mesh_variant_packs_each_shard_rows(width, word):
    """Sharded over the suite's 8 CPU devices along the batch axis, each
    shard packs its own rows: the same samples as the unsharded form."""
    from flyimg_tpu.parallel.mesh import make_mesh

    ctl, group, batch, arrays = _queued(width, 8, n=3, mesh=make_mesh())
    try:
        assert batch == 8
        fn, _ = ctl._program(group, batch)
        staged = fn.stage(arrays)
        assert len(staged[0][0].sharding.device_set) == 8
        out = fn(*staged)
        assert len(out.sharding.device_set) == 8
        raw = np.asarray(out)
        assert raw.dtype == np.dtype(f"u{word}")
        got = fn.unstage(raw)
        assert np.shares_memory(got, raw)
        np.testing.assert_array_equal(got, _unpacked(group, arrays))
    finally:
        _close(ctl)


def _specs(batch, group):
    sample = np.uint16 if group.depth == 16 else np.uint8
    return (jax.ShapeDtypeStruct((batch,) + group.in_shape + (3,), sample),
            *(jax.ShapeDtypeStruct((batch, 2), np.float32) for _ in range(4)))


_BUFFER = re.compile(r"\b(u8|u16|u32)\[([0-9,]*)\]\{([0-9,]*)")


def minor_dims(hlo_text):
    """The size of the minor dimension, by layout, of every ``u8``,
    ``u16`` and ``u32`` array the compiled program holds."""
    sizes = set()
    for _, dims, layout in _BUFFER.findall(hlo_text):
        if dims and layout:
            dims = [int(d) for d in dims.split(",")]
            sizes.add(dims[int(layout.split(",")[0])])
    return sizes


@pytest.mark.parametrize("depth", [8, 16])
def test_the_compiled_program_holds_no_array_of_a_word_samples(depth):
    """No buffer of the compiled program has a word's four (two) samples
    as its minor axis: the words are formed from planes of pixels."""
    ctl, group, batch, arrays = _queued(120, depth)
    try:
        fn, _ = ctl._program(group, batch)
        fn.precompile(_specs(batch, group))
        text = fn._compiled.as_text()
    finally:
        _close(ctl)
    assert "u32[" in text
    sizes = minor_dims(text)
    assert sizes and not sizes & {2, 4}, sizes


@pytest.mark.parametrize("depth", [8, 16])
def test_a_row_of_no_whole_words_lowers_the_program_without_words(depth):
    """Where the row holds no whole number of words the program is the
    one without them: no 32-bit integer and no shift in its StableHLO, the
    samples out."""
    ctl, group, batch, arrays = _queued(121, depth)
    try:
        fn, _ = ctl._program(group, batch)
        text = fn._jitted.lower(*fn._staged(_specs(batch, group))).as_text()
    finally:
        _close(ctl)
    assert "ui32" not in text and "shift_left" not in text
    assert f"-> (tensor<{batch}x90x363xui{depth}>" in text.replace("\n", "")


def test_a_big_endian_host_is_refused_when_the_program_is_built(monkeypatch):
    """The host's byte view of a word holds its samples in order only on a
    little-endian host: elsewhere a program that would pack is refused at
    build, and one that would not is built as before."""
    from flyimg_tpu.runtime import batcher

    plan = build_plan(OptionsBag("w_120,h_90,c_1"), 320, 240)
    layout = compose.plan_layout(plan)
    monkeypatch.setattr(batcher.sys, "byteorder", "big")
    build_batched_program.cache_clear()
    try:
        with pytest.raises(ReadbackByteOrderError):
            build_batched_program(2, (256, 384), (90, 120), layout.pad_canvas,
                                  layout.pad_offset, plan.device_plan())
        plan = build_plan(OptionsBag("w_121,h_90,c_1"), 320, 240)
        layout = compose.plan_layout(plan)
        handle = build_batched_program(
            2, (256, 384), (90, 121), layout.pad_canvas, layout.pad_offset,
            plan.device_plan())
        assert handle.samples == np.uint8
    finally:
        build_batched_program.cache_clear()


class _PlanarReadBack:
    """A handle whose words reach the host in another order than the
    host's: the same values, a transposed array."""

    def __init__(self, handle):
        self._handle = handle

    def __getattr__(self, name):
        return getattr(self._handle, name)

    def __call__(self, *args):
        out = np.asarray(self._handle(*args))
        assert out.dtype == np.uint32
        planar = np.ascontiguousarray(out.transpose(0, 2, 1)).transpose(0, 2, 1)
        assert not planar.flags.c_contiguous
        return planar


def test_strided_words_are_copied_into_the_host_order_then_viewed():
    """Words that arrive in another order are copied into the host's order
    before the byte view (which needs the row's words contiguous), and
    answer the same samples."""
    ctl, group, batch, arrays = _queued(120, 8)
    try:
        fn, _ = ctl._program(group, batch)
        planar = _PlanarReadBack(fn)(*fn.stage(arrays))
        got = fn.unstage(planar)
        assert got.flags.c_contiguous and not np.shares_memory(got, planar)
        np.testing.assert_array_equal(got, _unpacked(group, arrays))
    finally:
        _close(ctl)


def test_readback_packed_share_reads_the_counter_the_controller_keeps():
    """``perfbench/metrics/readback_packed_share.json`` through the
    benchmark's own reader on a controller's registry: 100 where every
    launch crossed as words, nothing read from a program without the
    counter."""
    from perfbench.harness import manifest
    from perfbench.harness.system import parse_prometheus

    doc = manifest.load_manifest()
    entry = next(m for m in doc["per_layer"] if m["name"] == "readback_packed_share")
    assert (entry["layer"], entry["moves"]) == ("transfer", "images_per_s")
    assert sorted(entry["workloads"]) == sorted(c["name"] for c in doc["workloads"])
    spec = manifest.load_metric("readback_packed_share")
    read = manifest.load_reader(spec["reader"])
    metrics = MetricsRegistry()
    before = parse_prometheus(metrics.render_prometheus())
    ctl = BatchController(max_batch=2, deadline_ms=10_000.0, metrics=metrics,
                          lone_flush=False)
    try:
        plan = build_plan(OptionsBag("w_120,h_90,c_1"), 320, 240)
        futures = [ctl.submit(_frame(320, 240, 8, s), plan) for s in range(2)]
        for future in futures:
            future.result(timeout=120)
    finally:
        ctl.close()
    after = parse_prometheus(metrics.render_prometheus())
    assert read({"counters_before": before, "counters_after": after},
                **spec["args"]) == pytest.approx(100.0)
    # the parent's program has no such counter: nothing read, nothing raised
    assert read({"counters_before": {}, "counters_after": {
        "flyimg_batches_total": 4.0}}, **spec["args"]) is None


def test_the_16bit_cell_reads_its_read_back_time_through_a_twin():
    """``d2h_ms_per_image.png16``: the reader and arguments of
    ``d2h_ms_per_image``, in the 16-bit cell alone, moving its rate."""
    from perfbench.harness import manifest

    doc = manifest.load_manifest()
    twin = next(m for m in doc["per_layer"] if m["name"] == "d2h_ms_per_image.png16")
    original = next(m for m in doc["per_layer"] if m["name"] == "d2h_ms_per_image")
    assert twin["workloads"] == ["archive-png16-saturated"]
    assert (twin["layer"], twin["moves"], twin["unit"]) == (
        original["layer"], "images_per_s", original["unit"])
    a, b = manifest.load_metric(twin["name"]), manifest.load_metric(original["name"])
    assert (a["reader"], a["args"]) == (b["reader"], b["args"])
