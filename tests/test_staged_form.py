"""The staged form of a launch's image argument and of its output: the
batcher assembles ``u8[batch, h, w, 3]``, ``ProgramHandle.stage`` hands the
device the same bytes flat and in pieces (``u8[batch / pieces, h, w * 3]``,
views; one piece for every launch of small frames), the batched program
un-flattens and transforms them piece by piece, in a loop over one body,
and returns its output flat, ``u8[batch, H, W * 3]``, which
``ProgramHandle.unstage`` turns into ``[batch, H, W, 3]`` without a copy.
Pinned here: the output bytes do not change, a handle warmed with
image-shaped specs runs staged arrays without a second compile, staging
copies nothing on the host, a member is answered with a view of the
read-back, and the launch's record carries the bytes it moved and the form
they came back in."""

import threading

import jax
import numpy as np
import pytest

from flyimg_tpu.ops import resample
from flyimg_tpu.ops import compose
from flyimg_tpu.ops.compose import flat_pieces, make_program_fn, stage_pieces
from flyimg_tpu.runtime import tracing
from flyimg_tpu.runtime.batcher import (
    BatchController,
    _Launch,
    build_batched_program,
)
from flyimg_tpu.runtime.metrics import MetricsRegistry
from flyimg_tpu.spec.options import OptionsBag
from flyimg_tpu.spec.plan import build_plan

from test_ops import make_test_image


class _Parked(BatchController):
    """The executor is parked: the test thread owns the queued group, so
    ``_assemble`` / ``_program`` are called on exactly what a launch would
    pop, with no timing in it."""

    def _run(self):
        return


def _queued_launch(options, sizes, *, mesh=None):
    """Submit one image per entry of ``sizes`` under ``options`` and return
    (controller, the one queued group, what ``_assemble`` makes of it)."""
    ctl = _Parked(max_batch=8, deadline_ms=10_000.0, mesh=mesh,
                  lone_flush=False)
    for seed, (w, h) in enumerate(sizes):
        ctl.submit(make_test_image(w, h, seed=seed),
                   build_plan(OptionsBag(options), w, h))
    (group,) = ctl._groups.values()
    batch, arrays = ctl._assemble(group, group.members)
    return ctl, group, batch, arrays


def _close(ctl):
    for group in ctl._groups.values():
        for member in group.members:
            member.future.cancel()
    ctl._groups.clear()
    ctl.close()


# every kind of program the batcher builds: (options, source sizes of one
# shared bucket, resample kernel mode)
_PLANS = {
    "crop_fill_dense": ("w_120,h_90,c_1", (320, 240), "dense"),
    "crop_fill_banded": ("w_120,h_90,c_1", (320, 240), "banded"),
    "pixel_ops_edge_padded": ("blr_2x1", (250, 190), "dense"),
    "extent_pad": ("w_100,h_80,ett_160x120,g_SouthEast,bg_red",
                   (320, 240), "dense"),
    "rotate_dynamic": ("w_140,r_15,bg_blue", (320, 240), "dense"),
}


@pytest.fixture()
def piece_a_frame(monkeypatch):
    """Every frame its own piece, as a launch of 24 MP frames has it, at
    test sizes: the bound is set to one frame of the 256 x 384 bucket."""
    build_batched_program.cache_clear()
    monkeypatch.setattr(compose, "STAGE_PIECE_BYTES", 256 * 384 * 3)
    yield
    build_batched_program.cache_clear()


def test_pieces_follow_the_frames_bytes():
    frame_24mp, thumb = (4096, 6016), (256, 384)
    assert compose.STAGE_PIECE_BYTES // (4096 * 6016 * 3) == 1
    assert [stage_pieces(b, frame_24mp) for b in (1, 2, 8, 64)] == [1, 2, 8, 64]
    assert [stage_pieces(b, thumb) for b in (1, 8, 64)] == [1, 1, 1]
    # 12 MP: two frames (75 MB) to a piece; a batch they do not divide
    # falls back to the largest power of two of frames that does
    assert stage_pieces(64, (3072, 4096)) == 32
    assert stage_pieces(6, (3072, 4096)) == 3
    assert stage_pieces(3, (3072, 4096)) == 3


# how a launch is laid out: (members, sharded over the suite's 8 CPU
# devices, every frame its own piece)
_LAUNCHES = {
    "n1": (1, False, False),
    "n4": (4, False, False),
    "n4_piece_a_frame": (4, False, True),
    "n3_mesh": (3, True, False),
}


@pytest.mark.parametrize("launch", sorted(_LAUNCHES))
@pytest.mark.parametrize("case", sorted(_PLANS))
def test_staged_form_gives_the_bytes_nhwc_gave(case, launch, request):
    """The batched program fed what ``stage`` makes of the assembled
    arrays returns its output flat, and read through the handle's
    ``unstage`` (a view of the read-back, C-contiguous) it is byte for byte
    what ``vmap`` of the single-image program returns fed the assembled
    NHWC arrays themselves."""
    options, (w, h), mode = _PLANS[case]
    n, sharded, by_frame = _LAUNCHES[launch]
    if by_frame:
        request.getfixturevalue("piece_a_frame")
    mesh = None
    if sharded:
        from flyimg_tpu.parallel.mesh import make_mesh

        mesh = make_mesh()  # the suite's 8 virtual CPU devices, axis 'data'
    before = resample.kernel_mode()
    resample.set_kernel_mode(mode)
    try:
        # members of one bucket need not share a size
        sizes = [(w - 6 * i, h - 4 * i) for i in range(n)]
        ctl, group, batch, arrays = _queued_launch(options, sizes, mesh=mesh)
    finally:
        resample.set_kernel_mode(before)
    try:
        assert (group.band_taps is not None) == (mode == "banded")
        assert group.rotate_dynamic == (case == "rotate_dynamic")
        assert (group.pad_canvas is not None) == (case == "extent_pad")
        if case == "pixel_ops_edge_padded":
            assert group.resample_out is None and group.in_shape != (h, w)
        assert batch == (8 if sharded else n)
        fn, _ = ctl._program(group, batch)
        assert fn.pieces == (batch if by_frame else 1)
        staged = fn.stage(arrays)
        bh, bw = group.in_shape
        assert [p.shape for p in staged[0]] == (
            [(batch // fn.pieces, bh, bw * 3)] * fn.pieces)
        if sharded:
            assert len(staged[0][0].sharding.device_set) == 8
        raw = np.asarray(fn(*staged))
        inner = make_program_fn(
            group.resample_out, group.pad_canvas, group.pad_offset,
            group.device_plan, rotate_dynamic=group.rotate_dynamic,
            band_taps=group.band_taps,
        )
        want = np.asarray(jax.jit(jax.vmap(inner))(*arrays))
        oh, ow = want.shape[1:3]
        assert raw.dtype == np.uint8 and raw.shape == (batch, oh, ow * 3)
        got = fn.unstage(raw)
        assert got.shape == want.shape and got.flags.c_contiguous
        assert np.shares_memory(got, raw)
        np.testing.assert_array_equal(got, want)
    finally:
        _close(ctl)


def test_pieces_share_one_traced_body(piece_a_frame):
    """The program of a launch in many pieces loops over ONE traced body
    and picks its piece inside it: unrolled, the 64-piece program of a
    24 MP launch compiled for 104-112 s, took 23 s to read back from the
    compile cache and outgrew it (PERF.md section 6, PR 28). So the
    program's text must not grow with the pieces as the bodies would."""
    plan = build_plan(OptionsBag("w_120,h_90,c_1"), 320, 240)
    layout = compose.plan_layout(plan)

    def lowered_text(batch):
        handle = build_batched_program(
            batch, (256, 384), (128, 128), layout.pad_canvas,
            layout.pad_offset, plan.device_plan(), None, False, None,
        )
        assert handle.pieces == batch
        specs = handle._staged((
            jax.ShapeDtypeStruct((batch, 256, 384, 3), np.uint8),
            *(jax.ShapeDtypeStruct((batch, 2), np.float32)
              for _ in range(4)),
        ))
        return handle._jitted.lower(*specs).as_text()

    two, sixteen = lowered_text(2), lowered_text(16)
    assert "stablehlo.while" in sixteen and "stablehlo.case" in sixteen
    # fourteen more pieces add fourteen one-line branches, not bodies
    assert len(sixteen) < 1.5 * len(two)


def _compiles_during(call):
    """Backend compiles JAX makes on this thread while ``call`` runs (the
    event the benchmark's ``compiles_in_window`` counts)."""
    import jax.monitoring
    from jax._src import monitoring as _monitoring

    me = threading.get_ident()
    seen = []

    def listener(event, duration, **_):
        if (event == "/jax/core/compile/backend_compile_duration"
                and threading.get_ident() == me):
            seen.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        result = call()
    finally:
        _monitoring.unregister_event_duration_listener(listener)
    return result, len(seen)


@pytest.mark.parametrize("by_frame", [False, True],
                         ids=["one_piece", "piece_a_frame"])
def test_handle_warmed_with_image_shaped_specs_runs_staged_arrays(
        by_frame, request):
    """``perfbench/harness/system.py`` and ``runtime/warmstart.py`` warm a
    handle positionally, with ``ShapeDtypeStruct((batch, h, w, 3), uint8)``:
    what they leave in it must be the executable that staged arrays run,
    so that a warmed launch compiles nothing."""
    if by_frame:
        request.getfixturevalue("piece_a_frame")
    ctl, group, batch, arrays = _queued_launch(
        "w_44,h_28,c_1", [(212 + by_frame, 148), (212 + by_frame, 148)])
    try:
        handle = build_batched_program(
            batch, group.in_shape, group.resample_out, group.pad_canvas,
            group.pad_offset, group.device_plan, None, False,
            group.band_taps,
        )
        assert not handle.is_compiled
        assert handle.pieces == (2 if by_frame else 1)
        _, cold = _compiles_during(lambda: handle.precompile((
            jax.ShapeDtypeStruct((batch,) + group.in_shape + (3,), np.uint8),
            *(jax.ShapeDtypeStruct((batch, 2), np.float32)
              for _ in range(4)),
        )))
        assert handle.is_compiled and cold >= 1
        fn, compile_hit = ctl._program(group, batch)
        assert fn is handle and compile_hit
        staged = fn.stage(arrays)
        jax.block_until_ready(staged)
        out, warm = _compiles_during(
            lambda: jax.block_until_ready(fn(*staged)))
        assert warm == 0
        assert out.shape == (batch, 28, 44 * 3)
        assert fn.unstage(np.asarray(out)).shape == (batch, 28, 44, 3)
    finally:
        _close(ctl)


def test_stage_copies_nothing_on_the_host(piece_a_frame, monkeypatch):
    """The flat pieces are views of ``_assemble``'s array: the only copy
    of a launch's bytes is the transfer's own."""
    ctl, group, batch, arrays = _queued_launch(
        "w_120,h_90,c_1", [(320, 240)] * 2)
    try:
        images = arrays[0]
        assert images.flags.c_contiguous and batch == 2
        pieces = flat_pieces(images, 2)
        assert len(pieces) == 2
        for k, piece in enumerate(pieces):
            assert piece.shape == (1, 256, 384 * 3)
            assert piece.dtype == np.uint8 and piece.flags.c_contiguous
            assert np.shares_memory(piece, images[k])
            assert piece.tobytes() == images[k].tobytes()
        (whole,) = flat_pieces(images, 1)
        assert np.shares_memory(whole, images)
        assert whole.tobytes() == images.tobytes()
        specs = flat_pieces(
            jax.ShapeDtypeStruct(images.shape, images.dtype), 2)
        assert [(s.shape, s.dtype) for s in specs] == (
            [(p.shape, p.dtype) for p in pieces])

        handed = []
        real_put = compose.jax.device_put

        def recording_put(tree, *args, **kwargs):
            handed.append(tree)
            return real_put(tree, *args, **kwargs)

        fn, _ = ctl._program(group, batch)
        assert fn.pieces == 2
        monkeypatch.setattr(compose.jax, "device_put", recording_put)
        fn.stage(arrays)
        (tree,) = handed
        assert len(tree) == 5 and len(tree[0]) == 2
        for k, piece in enumerate(tree[0]):
            assert np.shares_memory(piece, images[k])
        for given, assembled in zip(tree[1:], arrays[1:]):
            assert given is assembled
    finally:
        _close(ctl)


@pytest.mark.parametrize("path", ["primary", "recovery"])
def test_launch_record_carries_the_bytes_it_moved(path):
    """``flyimg_device_transfer_bytes_total{direction}`` and the shared
    span's ``device.h2d_bytes`` / ``device.d2h_bytes``: the staged bytes
    are the padded batch's ``batch * bh * bw * 3`` and its geometry
    scalars (four ``f32[batch, 2]``), the read-back the program's output."""
    from flyimg_tpu.testing import faults

    metrics = MetricsRegistry()
    ctl = BatchController(max_batch=3, deadline_ms=10_000.0, metrics=metrics,
                          lone_flush=False, batch_retries=1)
    ctl._retry_policy.sleep = lambda _s: None
    w, h = 320, 240
    if path == "recovery":
        faults.install(faults.FaultInjector()).plan(
            "batcher.drain", faults.fail_n_then_succeed(
                1, lambda: ConnectionError("transient device hiccup")))
    try:
        futures, traces = [], []
        for seed in range(3):
            trace = tracing.Trace()
            traces.append(trace)
            with tracing.activate(trace):
                futures.append(ctl.submit(
                    make_test_image(w, h, seed=seed),
                    build_plan(OptionsBag("w_120,h_90,c_1"), w, h)))
        outs = [f.result(timeout=120) for f in futures]
    finally:
        faults.clear()
        ctl.close()
    assert all(out.shape == (90, 120, 3) for out in outs)
    batch, bh, bw = 4, 256, 384       # 3 members pad to 4; 128-px buckets
    h2d = batch * bh * bw * 3 + 4 * batch * 2 * 4
    d2h = batch * 90 * 120 * 3
    text = metrics.render_prometheus()
    assert (f'flyimg_device_transfer_bytes_total{{direction="h2d"}} {h2d}'
            in text), text
    assert (f'flyimg_device_transfer_bytes_total{{direction="d2h"}} {d2h}'
            in text)
    spans = [s for root in traces[0].as_dict()["spans"]
             for s in _spans(root) if s["name"] == "device_execute"]
    assert spans, traces[0].as_dict()
    attrs = spans[-1]["attributes"]
    assert attrs["batch.size"] == batch
    assert attrs["device.h2d_bytes"] == h2d
    if path == "primary":
        assert attrs["device.d2h_bytes"] == d2h
    else:
        # the span is the failed launch's: it staged, and read nothing
        # back; the recovery launch that answered is in the counters
        assert spans[-1]["status"] == "error"
        assert "device.d2h_bytes" not in attrs


def _spans(node):
    yield node
    for child in node["children"]:
        yield from _spans(child)


def _run_parked(ctl, program=None):
    """Pop the parked controller's one group and run it as the executor
    would (``_execute``: staging, dispatch, the drain thread's read-back
    and resolve); ``program`` wraps the handle ``_program`` returns.
    Returns the members' answers."""
    if program is not None:
        real = ctl._program

        def wrapped(group, batch):
            fn, compile_hit = real(group, batch)
            return program(fn), compile_hit

        ctl._program = wrapped
    with ctl._lock:
        group = ctl._pop_ready_group()
    futures = [m.future for m in group.members]
    assert ctl._execute(group)
    return [f.result(timeout=120) for f in futures]


def _readbacks(metrics):
    text = metrics.render_prometheus()
    return {
        layout: f'flyimg_batch_readbacks_total{{layout="{layout}"}} 1' in text
        for layout in ("row_major", "strided")
    }, "flyimg_batches_total 1" in text


def test_an_unsliced_member_is_a_view_of_the_read_back_and_a_sliced_one_a_copy():
    """A crop-fill member (every member the same static extent) is answered
    with its slot of the read-back itself; a fit member whose output is
    bucket-padded gets a C-contiguous copy of its window and nothing of the
    launch's array."""
    for options, sliced in (("w_120,h_90,c_1", False), ("w_100", True)):
        ctl, group, batch, arrays = _queued_launch(
            options, [(320, 240), (300, 236)])
        try:
            assert [m.needs_slice for m in group.members] == [sliced] * 2
            fn, _ = ctl._program(group, batch)
            launch = _Launch(1, group.members)
            launch.open("h2d")
            launch.dev_args = fn.stage(arrays)
            out = ctl._await_launch(launch, fn(*launch.dev_args), fn)
            assert launch.readback == "row_major" and out.flags.c_contiguous
            futures = [m.future for m in group.members]
            ctl._resolve_members(group, group.members, out, launch)
            for i, (future, member) in enumerate(zip(futures, group.members)):
                got = future.result(timeout=0)
                th, tw = member.final_true
                assert got.shape == (th, tw, 3) and got.flags.c_contiguous
                np.testing.assert_array_equal(got, out[i, :th, :tw])
                assert np.shares_memory(got, out) == (not sliced)
        finally:
            _close(ctl)


def test_a_launch_counts_its_read_back_row_major_once():
    """One launch through the controller's own path: the program's flat
    output reads back C-contiguous, and the registry counts it once, beside
    the one launch it belongs to."""
    metrics = MetricsRegistry()
    ctl = _Parked(max_batch=4, deadline_ms=0.0, lone_flush=False,
                  metrics=metrics)
    try:
        for seed, (w, h) in enumerate([(320, 240), (300, 236)]):
            ctl.submit(make_test_image(w, h, seed=seed),
                       build_plan(OptionsBag("w_120,h_90,c_1"), w, h))
        outs = _run_parked(ctl)
        assert [o.shape for o in outs] == [(90, 120, 3)] * 2
        assert _readbacks(metrics) == (
            {"row_major": True, "strided": False}, True)
    finally:
        _close(ctl)


class _PlanarReadBack:
    """A handle whose output reaches the host in another order than the
    host's: the same values, a transposed array (what a program whose
    output the device keeps planar hands numpy)."""

    def __init__(self, handle):
        self._handle = handle

    def __getattr__(self, name):
        return getattr(self._handle, name)

    def __call__(self, *args):
        out = np.asarray(self._handle(*args))
        planar = np.ascontiguousarray(out.transpose(0, 2, 1)).transpose(0, 2, 1)
        assert not planar.flags.c_contiguous
        np.testing.assert_array_equal(planar, out)
        return planar


def test_a_strided_read_back_is_counted_so_and_answers_the_same_bytes():
    """A read-back that does not arrive in the host's order counts
    ``layout="strided"`` and its members are still answered with the
    bytes the row-major read-back gives."""
    answers = {}
    for name, program in (("row_major", None), ("strided", _PlanarReadBack)):
        metrics = MetricsRegistry()
        ctl = _Parked(max_batch=4, deadline_ms=0.0, lone_flush=False,
                      metrics=metrics)
        try:
            for seed, (w, h) in enumerate([(320, 240), (300, 236)]):
                ctl.submit(make_test_image(w, h, seed=seed),
                           build_plan(OptionsBag("w_100"), w, h))
            answers[name] = _run_parked(ctl, program)
            assert _readbacks(metrics) == (
                {"row_major": name == "row_major",
                 "strided": name == "strided"}, True)
        finally:
            _close(ctl)
    for row_major, strided in zip(answers["row_major"], answers["strided"]):
        assert strided.flags.c_contiguous
        assert strided.shape == row_major.shape
        np.testing.assert_array_equal(strided, row_major)


def test_readback_row_major_share_reads_the_counter_the_controller_keeps():
    """``perfbench/metrics/readback_row_major_share.json`` through the
    benchmark's own reader, on a controller's registry as the harness
    scrapes it: 100 where every launch read back row-major, nothing read
    from a program without the counter."""
    from perfbench.harness import manifest
    from perfbench.harness.system import parse_prometheus

    doc = manifest.load_manifest()
    entry = next(m for m in doc["per_layer"]
                 if m["name"] == "readback_row_major_share")
    assert entry["layer"] == "transfer" and entry["moves"] == "images_per_s"
    spec = manifest.load_metric("readback_row_major_share")
    # every cell reads it: those of this entry, and those that came after
    # its list was accepted through a twin of the same counter
    # (`readback_row_major_share.png16` for the 16-bit cell)
    twins = [m for m in doc["per_layer"] if m["name"].startswith("readback_row_major_share.")]
    assert twins
    covered = entry["workloads"] + [c for t in twins for c in t["workloads"]]
    assert sorted(covered) == sorted(c["name"] for c in doc["workloads"])
    for twin in twins:
        assert (twin["layer"], twin["moves"]) == (entry["layer"], entry["moves"])
        twin_spec = manifest.load_metric(twin["name"])
        assert (twin_spec["reader"], twin_spec["args"]) == (spec["reader"], spec["args"])
    read = manifest.load_reader(spec["reader"])
    metrics = MetricsRegistry()
    ctl = _Parked(max_batch=4, deadline_ms=0.0, lone_flush=False,
                  metrics=metrics)
    try:
        before = parse_prometheus(metrics.render_prometheus())
        for seed, (w, h) in enumerate([(320, 240), (300, 236)]):
            ctl.submit(make_test_image(w, h, seed=seed),
                       build_plan(OptionsBag("w_120,h_90,c_1"), w, h))
        _run_parked(ctl)
        after = parse_prometheus(metrics.render_prometheus())
    finally:
        _close(ctl)
    assert read({"counters_before": before, "counters_after": after},
                **spec["args"]) == pytest.approx(100.0)
    # the parent's program has no such counter: nothing read, nothing raised
    assert read({"counters_before": {}, "counters_after": {
        "flyimg_batches_total": 4.0}}, **spec["args"]) is None
