"""The launch cycle as the program itself accounts for it (PR 27): the one
stage helper on the request path, the per-member queue/run split, the one
phase record per launch and its four sinks, the phases' profiler
annotations, ``bulk_process(trace_out=)``, and the benchmark files that
read them. Nothing here asserts an upper bound on a time: sleeps give lower
bounds, identities hold by construction."""

import gc
import io
import json
import logging
import os
import sys
import threading
import time

import numpy as np
import pytest
from PIL import Image

from flyimg_tpu.appconfig import AppParameters
from flyimg_tpu.ops.compose import ProgramHandle
from flyimg_tpu.runtime import batcher as batcher_mod
from flyimg_tpu.runtime import metrics as metrics_mod
from flyimg_tpu.runtime import tracing
from flyimg_tpu.runtime.batcher import BatchController
from flyimg_tpu.runtime.devicegaps import LABELS, GapAccount
from flyimg_tpu.runtime.flightrecorder import PHASE_FIELDS, FlightRecorder
from flyimg_tpu.runtime.metrics import MetricsRegistry
from flyimg_tpu.service.handler import ImageHandler
from flyimg_tpu.service.output_image import EXT_TO_MIME, OutputSpec
from flyimg_tpu.spec.options import OptionsBag
from flyimg_tpu.spec.plan import build_plan
from flyimg_tpu.storage import make_storage

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


# ---------------------------------------------------------------------------
# helpers


def _jpeg(w=320, h=240, seed=0) -> bytes:
    rng = np.random.default_rng(seed)
    ramp = np.linspace(0, 255, w, dtype=np.float32)[None, :, None]
    img = np.clip(ramp + rng.normal(0, 12, (h, w, 3)), 0, 255).astype(np.uint8)
    buf = io.BytesIO()
    Image.fromarray(img).save(buf, format="JPEG", quality=90)
    return buf.getvalue()


def _options(params, text="w_120,h_90,c_1"):
    return OptionsBag(
        text, options_keys=params.by_key("options_keys"),
        default_options=params.by_key("default_options"),
        separator=params.by_key("options_separator", ","),
    )


class _System:
    """Handler + device controller + codec controller, the way bulk.py and
    the benchmark build them."""

    def __init__(self, flight_recorder=None):
        self.params = AppParameters()
        self.metrics = MetricsRegistry()
        self.batcher = BatchController(
            deadline_ms=1.0, metrics=self.metrics,
            flight_recorder=flight_recorder,
        )
        self.codec = BatchController(deadline_ms=1.0, name="codec")
        self.handler = ImageHandler(
            storage=None, params=self.params, batcher=self.batcher,
            codec_batcher=self.codec, metrics=self.metrics,
        )

    def transform(self, data, timings=None, text="w_120,h_90,c_1"):
        spec = OutputSpec(name="t.jpg", extension="jpg",
                          mime=EXT_TO_MIME["jpg"])
        return self.handler.transform_bytes(
            data, _options(self.params, text), spec, timings
        )

    def close(self):
        self.handler.close()
        self.codec.close()
        self.batcher.close()


@pytest.fixture()
def system():
    sut = _System(flight_recorder=FlightRecorder(size=32, dump_dir="/nonexistent"))
    yield sut
    sut.close()


@pytest.fixture()
def span_count(monkeypatch):
    """Counts every ``Span`` the program constructs."""
    made = []

    class CountingSpan(tracing.Span):
        __slots__ = ()

        def __init__(self, *args, **kwargs):
            made.append(args[0] if args else kwargs.get("name"))
            super().__init__(*args, **kwargs)

    monkeypatch.setattr(tracing, "Span", CountingSpan)
    return made


def _stage_count(metrics, stage):
    text = metrics.render_prometheus()
    key = f'flyimg_stage_seconds_count{{stage="{stage}"}}'
    for line in text.splitlines():
        if line.startswith(key):
            return int(float(line.split()[-1]))
    return 0


def _tree(node, out=None):
    """{span name: [child names]} over a Trace.as_dict() span tree."""
    out = {} if out is None else out
    out.setdefault(node["name"], []).extend(c["name"] for c in node["children"])
    for child in node["children"]:
        _tree(child, out)
    return out


def _find(node, name):
    if node["name"] == name:
        return node
    for child in node["children"]:
        hit = _find(child, name)
        if hit is not None:
            return hit
    return None


# ---------------------------------------------------------------------------
# 1. the stage helper


def test_stage_without_a_trace_allocates_no_span(span_count):
    timings, metrics = {}, MetricsRegistry()
    with tracing.stage("decode", timings, metrics) as span_obj:
        assert span_obj is None
    tracing.stage_interval("decode_queue", 1.0, 1.25, timings, metrics,
                           span_name="decode.queue")
    assert span_count == []
    assert timings["decode"] >= 0.0
    assert timings["decode_queue"] == pytest.approx(0.25)
    assert _stage_count(metrics, "decode") == 1
    assert _stage_count(metrics, "decode_queue") == 1


def test_stage_with_a_trace_feeds_span_timings_and_histogram():
    timings, metrics = {}, MetricsRegistry()
    trace = tracing.Trace(name="job")
    with tracing.activate(trace):
        with tracing.stage("device", timings, metrics,
                           span_name="batch_wait", frames=1) as span_obj:
            assert span_obj is not None and span_obj.name == "batch_wait"
            assert tracing.current_span() is span_obj
            now = time.perf_counter()
            tracing.stage_interval("device_queue", now - 0.5, now - 0.25,
                                   timings, metrics, span_name="device.queue")
        assert tracing.current_span() is trace.root
    tree = _tree(trace.as_dict()["spans"][0])
    assert tree["job"] == ["batch_wait"]
    assert tree["batch_wait"] == ["device.queue"]
    wait = _find(trace.as_dict()["spans"][0], "batch_wait")
    assert wait["attributes"]["frames"] == 1
    assert wait["duration_s"] == pytest.approx(timings["device"], abs=1e-3)
    queue = _find(wait, "device.queue")
    # an interval timed elsewhere: placed on both clocks where it happened
    assert queue["duration_s"] == pytest.approx(0.25)
    assert queue["start_mono_ns"] == pytest.approx((now - 0.5) * 1e9, rel=1e-9)
    assert queue["start_s"] == pytest.approx(time.time() - 0.5, abs=0.2)
    assert _stage_count(metrics, "device") == 1
    assert _stage_count(metrics, "device_queue") == 1


def test_stage_that_raises_ends_its_span_as_error_and_records_nothing():
    timings, metrics = {}, MetricsRegistry()
    trace = tracing.Trace()
    with tracing.activate(trace):
        with pytest.raises(ValueError):
            with tracing.stage("encode", timings, metrics):
                raise ValueError("boom")
        assert tracing.current_span() is trace.root  # stack unwound
    span_obj = _find(trace.as_dict()["spans"][0], "encode")
    assert span_obj["status"] == "error"
    assert span_obj["events"][0]["name"] == "exception"
    assert "encode" not in timings
    assert _stage_count(metrics, "encode") == 0


def test_span_carries_its_monotonic_start():
    before = time.perf_counter_ns()
    span_obj = tracing.Span("x")
    after = time.perf_counter_ns()
    assert before <= span_obj.start_mono_ns <= after
    span_obj.end()
    assert span_obj.as_dict()["start_mono_ns"] == span_obj.start_mono_ns
    trace = tracing.Trace()
    copy = trace.attach_shared(span_obj, None)
    assert copy.start_mono_ns == span_obj.start_mono_ns
    assert copy.duration_s == span_obj.duration_s


# ---------------------------------------------------------------------------
# 2. transform_bytes: the same stages with and without a trace

NEW_KEYS = ("decode_queue", "decode_run", "device_copy_in", "device_queue",
            "encode_queue", "encode_run")


def test_transform_bytes_under_a_trace_yields_the_span_tree(system):
    trace = tracing.Trace(name="img0.jpg")
    timings = {}
    with tracing.activate(trace):
        out = system.transform(_jpeg(), timings)
    trace.finish()
    assert Image.open(io.BytesIO(out)).size == (120, 90)
    root = trace.as_dict()["spans"][0]
    tree = _tree(root)
    assert tree["img0.jpg"] == ["decode", "batch_wait", "encode"]
    # the codec controller's shared aux span rides under the stage too
    assert [n for n in tree["decode"] if n != "aux_execute"] == [
        "decode.queue", "decode.run"]
    # the copy into the launch's block was made at submit, on this thread,
    # inside batch_wait and before the fill wait's end
    assert sorted(tree["batch_wait"]) == [
        "batch.copy_in", "device.queue", "device_execute"]
    copy_in = _find(root, "batch.copy_in")
    wait = _find(root, "batch_wait")
    assert wait["start_mono_ns"] <= copy_in["start_mono_ns"]
    assert copy_in["start_mono_ns"] + copy_in["duration_s"] * 1e9 <= \
        _find(root, "device_execute")["start_mono_ns"]
    assert [n for n in tree["encode"] if n != "aux_execute"] == [
        "encode.queue", "encode.run"]
    # the launch's phases ride the shared span
    attrs = _find(root, "device_execute")["attributes"]
    for key in ("batch.queue_wait_s", "batch.assemble_s", "batch.slot_wait_s",
                "device.h2d_s", "device.dispatch_s", "device.run_s",
                "device.sync_s", "device.seconds", "batch.assemble_cpu_s",
                "device.h2d_cpu_s"):
        assert attrs[key] >= 0.0, key
    # each stage's seconds are its span's
    for stage, span_name in (("decode", "decode"), ("device", "batch_wait"),
                             ("encode", "encode"),
                             ("decode_queue", "decode.queue"),
                             ("device_copy_in", "batch.copy_in"),
                             ("encode_run", "encode.run")):
        assert _find(root, span_name)["duration_s"] == pytest.approx(
            timings[stage], abs=2e-3), stage
    # the parts of a stage never exceed it
    assert timings["decode_queue"] + timings["decode_run"] <= timings["decode"] + 1e-3
    assert timings["encode_queue"] + timings["encode_run"] <= timings["encode"] + 1e-3
    assert timings["device_queue"] <= timings["device"] + 1e-3
    assert timings["device_copy_in"] <= timings["device_queue"] + 1e-3
    # the histogram of the moved work and the counter of where it was done
    text = system.metrics.render_prometheus()
    assert "flyimg_batch_member_copy_seconds_count 1" in text
    assert 'flyimg_batch_member_copies_total{at="submit"} 1' in text
    assert 'at="assemble"' not in text


def test_smart_crop_wait_is_split_like_the_codec_waits(system):
    """The smc_1 post-pass: the host prescale (``smartcrop_prepare``), then
    the scorer's aux launch on the device controller, split by the member's
    own instants into ``smartcrop_queue`` / ``smartcrop_run``, all three
    inside ``smartcrop``; the aux launch is observed apart from the
    transform launch."""
    trace = tracing.Trace(name="img0.jpg")
    timings = {}
    with tracing.activate(trace):
        out = system.transform(_jpeg(240, 360), timings, text="w_120,h_120,smc_1")
    trace.finish()
    assert Image.open(io.BytesIO(out)).size[0] in (79, 80)
    root = trace.as_dict()["spans"][0]
    tree = _tree(root)
    assert tree["img0.jpg"] == ["decode", "batch_wait", "smartcrop", "encode"]
    assert [n for n in tree["smartcrop"] if n != "aux_execute"] == [
        "smartcrop.prepare", "smartcrop.queue", "smartcrop.run"]
    assert _find(root, "aux_execute") is not None
    for stage, span_name in (("smartcrop", "smartcrop"),
                             ("smartcrop_prepare", "smartcrop.prepare"),
                             ("smartcrop_queue", "smartcrop.queue"),
                             ("smartcrop_run", "smartcrop.run")):
        assert _find(root, span_name)["duration_s"] == pytest.approx(
            timings[stage], abs=2e-3), stage
        assert _stage_count(system.metrics, stage) == 1, stage
    assert timings["smartcrop_prepare"] + timings["smartcrop_queue"] \
        + timings["smartcrop_run"] <= timings["smartcrop"] + 1e-3
    text = system.metrics.render_prometheus()
    assert 'flyimg_batch_bucket_size_count{controller="device"} 1' in text
    assert 'flyimg_batch_bucket_size_count{controller="device_aux"} 1' in text
    assert "flyimg_aux_items_total 1" in text  # the codec keeps its own registry


def _samples(metrics, prefix):
    out = {}
    for line in metrics.render_prometheus().splitlines():
        if line.startswith(prefix) and "_bucket" not in line:
            name, value = line.rsplit(" ", 1)
            out[name] = float(value)
    return out


def test_codec_launch_split_reaches_the_registry_the_handler_was_given(system):
    """PR 35: the codec controller keeps a registry of its own, so what a
    codec launch says of itself (``native_codec.LaunchSplit``) is recorded
    by the handler's runner into the handler's registry: the two timers,
    one observation a decode launch, and the buffers by how they were
    handed over."""
    from flyimg_tpu.codecs import native_codec

    if native_codec.get_pool() is None:
        pytest.skip("native codec not built")
    frames = 3
    for seed in range(frames):
        timings = {}
        system.transform(_jpeg(seed=seed), timings)
        assert "decode_run" in timings and "encode_run" in timings
    got = _samples(system.metrics, "flyimg_codec_")
    assert got['flyimg_codec_buffers_total{handover="adopted"}'] == frames
    assert got['flyimg_codec_buffer_bytes_total{handover="adopted"}'] == frames * 320 * 240 * 3
    assert got['flyimg_codec_buffers_total{handover="bytes"}'] == frames
    assert got['flyimg_codec_buffer_bytes_total{handover="bytes"}'] > 0
    for timer in ("flyimg_codec_native_seconds", "flyimg_codec_handover_seconds"):
        assert got[timer + "_count"] == frames  # one caller: launches of one
        assert got[timer + "_sum"] > 0
    # the hand-over is inside the member's run, the pool call too
    stage = _samples(system.metrics, 'flyimg_stage_seconds_sum{stage="decode_run"}')
    assert (got["flyimg_codec_native_seconds_sum"]
            + got["flyimg_codec_handover_seconds_sum"]) <= sum(stage.values())
    # and none of it on the codec controller's own registry
    assert _samples(system.codec.metrics, "flyimg_codec_") == {}


def test_resolve_phase_reaches_the_attached_span_and_the_flight_row(system):
    trace = tracing.Trace()
    with tracing.activate(trace):
        system.transform(_jpeg(seed=3))
    # resolve ends after the member was resolved: written late, so wait
    deadline = time.monotonic() + 5.0
    attrs = {}
    while time.monotonic() < deadline:
        attrs = _find(trace.as_dict()["spans"][0], "device_execute")["attributes"]
        rows = [r for r in system.batcher.flight_recorder.snapshot()["records"]
                if r["kind"] == "primary"]
        if "batch.resolve_s" in attrs and rows and rows[0]["resolve_s"] is not None:
            break
        time.sleep(0.01)
    assert attrs["batch.resolve_s"] >= 0.0
    assert rows[0]["resolve_s"] == pytest.approx(attrs["batch.resolve_s"])
    assert set(PHASE_FIELDS) <= set(rows[0])


def test_transform_bytes_without_a_trace_creates_no_span_and_fills_the_same(
        system, span_count):
    timings = {}
    system.transform(_jpeg(seed=1), timings)
    assert span_count == []
    for key in ("decode", "decode_full", "device", "encode") + NEW_KEYS:
        assert timings[key] >= 0.0, key
        assert _stage_count(system.metrics, key) == 1, key


def test_process_image_records_each_stage_exactly_once(tmp_path):
    src = tmp_path / "src.jpg"
    src.write_bytes(_jpeg(seed=2))
    params = AppParameters({
        "upload_dir": str(tmp_path / "up"), "tmp_dir": str(tmp_path / "tmp"),
    })
    metrics = MetricsRegistry()
    device = BatchController(deadline_ms=1.0, metrics=metrics)
    codec = BatchController(deadline_ms=1.0, name="codec")
    try:
        handler = ImageHandler(
            storage=make_storage(params), params=params, batcher=device,
            codec_batcher=codec, metrics=metrics,
        )
        result = handler.process_image("w_100,h_70,c_1,o_jpg", str(src))
        for key in ("fetch", "decode", "device", "encode", "total") + NEW_KEYS:
            assert key in result.timings, key
            assert _stage_count(metrics, key) == 1, key
        # a hit records its own stage and none of the pipeline's again
        handler.process_image("w_100,h_70,c_1,o_jpg", str(src))
        assert _stage_count(metrics, "cache_hit") == 1
        assert _stage_count(metrics, "decode") == 1
        assert _stage_count(metrics, "total") == 1
    finally:
        codec.close()
        device.close()


# ---------------------------------------------------------------------------
# 3. the phase record, with a program whose staging, run and read-back each
#    take a known time


class _Staged:
    """A staged input: the staging call returned at once, the copy takes
    ``seconds`` more."""

    def __init__(self, done_at):
        self.done_at = done_at

    def block_until_ready(self):
        time.sleep(max(self.done_at - time.perf_counter(), 0.0))
        return self


class _Output:
    def __init__(self, program, batch, ready_at):
        self.program, self.batch, self.ready_at = program, batch, ready_at

    def block_until_ready(self):
        time.sleep(max(self.ready_at - time.perf_counter(), 0.0))
        return self

    def __array__(self, dtype=None, copy=None):
        self.block_until_ready()
        self.program.inputs_alive_at_readback.append(
            self.program.launch().dev_args is not None)
        time.sleep(self.program.d2h_s)
        return np.zeros((self.batch, 24, 32 * 3), np.uint8)


class _FakeProgram:
    ledger_key = "fake-program"
    is_compiled = True
    # a batched program's output form: flat, re-shaped by the real handle's
    # own inverse
    pieces = 1
    unstage = ProgramHandle.unstage

    def __init__(self, h2d_s=0.06, run_s=0.04, d2h_s=0.05):
        self.h2d_s, self.run_s, self.d2h_s = h2d_s, run_s, d2h_s
        self.stage_call_s = []
        self.inputs_alive_at_readback = []
        self.launches = []

    def launch(self):
        return self.launches[-1]

    def stage(self, arrays):
        t = time.perf_counter()
        staged = [_Staged(t + self.h2d_s) for _ in arrays]
        self.stage_call_s.append(time.perf_counter() - t)
        return staged

    def __call__(self, *dev_args):
        # runs once its inputs are there, as the device does
        ready = max(a.done_at for a in dev_args) + self.run_s
        return _Output(self, int(self.batch), ready)


@pytest.fixture()
def fake_launches(monkeypatch):
    """A device controller whose program is ``_FakeProgram``; yields
    (controller, program, recorder, registry)."""
    program = _FakeProgram()
    metrics = MetricsRegistry()
    recorder = FlightRecorder(size=64, dump_dir="/nonexistent")
    ctl = BatchController(deadline_ms=1.0, metrics=metrics,
                          flight_recorder=recorder, batch_retries=1)
    ctl._retry_policy.sleep = lambda _s: None

    def program_for(self, group, batch):
        program.batch = batch
        return program, True

    monkeypatch.setattr(BatchController, "_program", program_for)
    real_init = batcher_mod._Launch.__init__

    def remember(self, *args, **kwargs):
        real_init(self, *args, **kwargs)
        if not self.aux:
            program.launches.append(self)

    monkeypatch.setattr(batcher_mod._Launch, "__init__", remember)
    yield ctl, program, recorder, metrics
    ctl.close()


def _submit_one(ctl, w=32, h=24):
    image = np.zeros((h, w, 3), np.uint8)
    plan = build_plan(OptionsBag(f"w_{w},h_{h},c_1"), w, h)
    return ctl.submit(image, plan)


def _rows(recorder, kind):
    return [r for r in recorder.snapshot()["records"] if r["kind"] == kind]


def _check_identity(row):
    """h2d's part after the dispatch began + run + read-back = device_s,
    and every phase is >= 0."""
    for field in ("queue_wait_s", "h2d_s", "dispatch_s", "run_s", "sync_s",
                  "device_s"):
        assert row[field] is not None and row[field] >= 0.0, field
    after_dispatch = row["device_s"] - row["run_s"] - row["sync_s"]
    assert -1e-5 <= after_dispatch <= row["h2d_s"] + 1e-5


def _slow_aux(payloads):
    time.sleep(0.03)
    return [p * 2 for p in payloads]


@pytest.mark.parametrize("path", ["primary", "recovery", "aux"])
def test_phase_record_adds_up_on_every_path(fake_launches, path):
    ctl, program, recorder, metrics = fake_launches
    if path == "aux":
        future = ctl.submit_aux(("k",), 21, _slow_aux)
        assert future.result(timeout=30) == 42
        row = _rows(recorder, "aux")[0]
        assert row["run_s"] >= 0.03 and row["device_s"] == row["run_s"]
        assert row["queue_wait_s"] >= 0.0 and row["h2d_s"] is None
        queued, popped, ready, answered = future.launch_times
        assert queued <= popped and ready - popped >= 0.03
        assert ready <= answered
        return
    if path == "recovery":
        from flyimg_tpu.testing import faults

        faults.install(faults.FaultInjector()).plan(
            "batcher.drain", faults.fail_n_then_succeed(
                1, lambda: ConnectionError("transient device hiccup")))
        try:
            future = _submit_one(ctl)
            future.result(timeout=30)
        finally:
            faults.clear()
        assert _rows(recorder, "primary")[0]["error"] == "ConnectionError"
    else:
        future = _submit_one(ctl)
        future.result(timeout=30)
    row = _rows(recorder, path)[0]
    assert row["error"] is None
    _check_identity(row)
    launch = program.launches[-1]
    assert launch.kind == path
    # exactly: the three laps share their end points
    h2d, run, d2h = (launch.marks[k] for k in ("h2d", "run", "d2h"))
    assert h2d[1] == run[0] and run[1] == d2h[0]
    assert launch.device_s == pytest.approx(
        (h2d[1] - launch.marks["dispatch"][0]) + launch.seconds("run")
        + launch.seconds("d2h"), rel=0.01)
    assert row["assemble_s"] >= 0.0 and row["assemble_cpu_s"] >= 0.0
    assert (row["slot_wait_s"] is None) == (path == "recovery")
    # the primary launch found its member in the block; the recovery launch
    # after it assembled from the member's own array
    text = metrics.render_prometheus()
    assert 'flyimg_batch_member_copies_total{at="submit"} 1' in text
    assert ('flyimg_batch_member_copies_total{at="assemble"} 1' in text) == (
        path == "recovery")
    queued, popped, ready, answered = future.launch_times
    assert queued <= popped <= ready <= answered


def test_h2d_times_the_completed_transfer_not_the_call(fake_launches):
    ctl, program, recorder, metrics = fake_launches
    _submit_one(ctl).result(timeout=30)
    row = _rows(recorder, "primary")[0]
    # the staging call returned at once; the copy took 60 ms more
    assert program.stage_call_s[0] < program.h2d_s / 2
    assert row["h2d_s"] >= program.h2d_s
    assert row["run_s"] >= program.run_s * 0.5
    # the read-back no longer swallows the staging and the run
    assert row["sync_s"] >= program.d2h_s
    assert row["device_s"] >= program.h2d_s + program.run_s + program.d2h_s - 0.01
    assert row["sync_s"] <= row["device_s"] - row["run_s"] + 1e-6
    text = metrics.render_prometheus()
    for series in ('flyimg_device_transfer_seconds_count{direction="h2d"} 1',
                   'flyimg_device_transfer_seconds_count{direction="d2h"} 1',
                   "flyimg_device_run_seconds_count 1",
                   "flyimg_batch_assemble_seconds_count 1",
                   "flyimg_batch_member_copy_seconds_count 1",
                   'flyimg_batch_member_copies_total{at="submit"} 1',
                   "flyimg_batch_slot_wait_seconds_count 1",
                   "flyimg_device_seconds_count 1"):
        assert series in text, series


def test_drain_lets_go_of_the_inputs_before_the_readback(fake_launches):
    ctl, program, recorder, metrics = fake_launches
    _submit_one(ctl).result(timeout=30)
    assert program.inputs_alive_at_readback == [False]
    assert program.launches[-1].dev_args is None


def test_every_phase_is_annotated_for_the_profiler(fake_launches, monkeypatch):
    ctl, program, recorder, metrics = fake_launches
    names = []
    lock = threading.Lock()

    class Recording:
        def __init__(self, name, **_):
            with lock:
                names.append((name, threading.current_thread().name))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(batcher_mod.jax.profiler, "TraceAnnotation", Recording)
    _submit_one(ctl).result(timeout=30)
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline and not any(
            n.endswith(":resolve") for n, _ in names):
        time.sleep(0.01)
    seq = program.launches[-1].seq
    by_name = dict(names)
    executor = {"assemble", "slot_wait", "h2d", "dispatch"}
    drain = {"h2d_wait", "run", "d2h", "resolve"}
    for phase in executor | drain:
        assert f"flyimg:batch:{seq}:{phase}" in by_name, (phase, names)
    assert f"flyimg:batch:{seq}" in by_name  # the dispatch's first name stays
    assert {by_name[f"flyimg:batch:{seq}:{p}"] for p in executor} == {"flyimg-batcher"}
    assert {by_name[f"flyimg:batch:{seq}:{p}"] for p in drain} == {
        "flyimg-batcher-drain"}


# ---------------------------------------------------------------------------
# 4. bulk_process(trace_out=)


def test_bulk_trace_out_writes_one_parseable_line_per_image(tmp_path):
    from flyimg_tpu.bulk import bulk_process

    src = tmp_path / "src"
    src.mkdir()
    for i in range(4):
        (src / f"img{i}.jpg").write_bytes(_jpeg(seed=i))
    path = tmp_path / "spans.jsonl"
    summary = bulk_process(str(src), str(tmp_path / "out"), "w_100,h_75,c_1",
                           workers=4, trace_out=str(path))
    assert summary["images"] == 4 and summary["failed"] == 0
    docs = [json.loads(line) for line in path.read_text().splitlines()]
    assert sorted(d["name"] for d in docs) == [f"img{i}.jpg" for i in range(4)]
    for doc in docs:
        root = doc["spans"][0]
        assert root["name"] == doc["name"] and doc["status"] == "ok"
        names = [c["name"] for c in root["children"]]
        assert names == ["decode", "batch_wait", "encode"]
        assert _find(root, "device_execute")["attributes"]["device.run_s"] >= 0


def test_bulk_without_trace_out_creates_no_trace(tmp_path, span_count):
    from flyimg_tpu.bulk import bulk_process

    src = tmp_path / "src"
    src.mkdir()
    (src / "a.jpg").write_bytes(_jpeg(seed=9))
    summary = bulk_process(str(src), str(tmp_path / "out"), "w_64", workers=1)
    assert summary["images"] == 1
    assert span_count == []


# ---------------------------------------------------------------------------
# 5. the benchmark's new files

# two launches of 64 in the window; the program's timers as Prometheus
# renders them
_BEFORE = {
    "flyimg_images_processed_total": 64.0,
    "flyimg_batch_assemble_seconds_sum": 3.0,
    "flyimg_batch_member_copy_seconds_sum": 4.0,
    "flyimg_batch_resolve_seconds_sum": 0.5,
    'flyimg_device_transfer_seconds_sum{direction="h2d"}': 24.0,
    'flyimg_device_transfer_seconds_sum{direction="d2h"}': 0.25,
    "flyimg_device_run_seconds_sum": 0.25,
}
_AFTER = {
    "flyimg_images_processed_total": 192.0,
    "flyimg_batch_assemble_seconds_sum": 9.4,
    "flyimg_batch_member_copy_seconds_sum": 14.24,
    "flyimg_batch_resolve_seconds_sum": 1.14,
    'flyimg_device_transfer_seconds_sum{direction="h2d"}': 72.0,
    'flyimg_device_transfer_seconds_sum{direction="d2h"}': 0.89,
    "flyimg_device_run_seconds_sum": 0.762,
}
_TIMINGS = [
    {"decode": 4.0, "decode_queue": 2.0, "decode_run": 1.5,
     "encode": 1.0, "encode_queue": 0.25, "encode_run": 0.5},
    {"decode": 5.0, "decode_queue": 3.0, "decode_run": 1.0,
     "encode": 2.0, "encode_queue": 0.75, "encode_run": 1.0},
    {"decode": 1.0},  # a fallback decode: no codec launch carried it
]
# the smc_1 post-pass (PR 31): the second image fell back to the
# single-image scorer after a wedged wait, so no launch carried it
_TIMINGS[0].update(smartcrop=0.4, smartcrop_prepare=0.01,
                   smartcrop_queue=0.25, smartcrop_run=0.125)
_TIMINGS[1].update(smartcrop=0.8, smartcrop_prepare=0.03)
_BEFORE.update({"flyimg_aux_items_total": 10.0, "flyimg_aux_batches_total": 8.0})
_AFTER.update({"flyimg_aux_items_total": 138.0, "flyimg_aux_batches_total": 72.0})
# the codec launches' hand-over (PR 35): four decode launches of 32 in the window
_BEFORE.update({"flyimg_codec_handover_seconds_sum": 2.0,
                "flyimg_codec_handover_seconds_count": 2.0})
_AFTER.update({"flyimg_codec_handover_seconds_sum": 2.064,
               "flyimg_codec_handover_seconds_count": 6.0})


@pytest.mark.parametrize("metric,expected", [
    ("decode_queue_ms", 2500.0),
    ("decode_run_ms", 1250.0),
    ("encode_queue_ms", 500.0),
    ("encode_run_ms", 750.0),
    ("assemble_ms_per_image", 50.0),
    ("member_copy_ms_per_image", 80.0),
    ("resolve_ms_per_image", 5.0),
    ("h2d_ms_per_image", 375.0),
    ("d2h_ms_per_image", 5.0),
    ("device_run_ms_per_image", 4.0),
    ("readback_gap_ms", 150.0),
    ("smartcrop_ms", 600.0),
    ("smartcrop_prepare_ms", 20.0),
    ("smartcrop_queue_ms", 250.0),
    ("smartcrop_run_ms", 125.0),
    ("aux_items_per_launch", 2.0),
    ("decode_handover_ms_per_image", 0.5),
])
def test_new_metric_files_read_the_recorded_fixture(metric, expected):
    from perfbench.harness import manifest

    doc = manifest.load_manifest()
    entry = next(m for m in doc["per_layer"] if m["name"] == metric)
    assert entry["moves"] == (
        "images_per_s" if metric == "aux_items_per_launch" else "latency_p95_ms")
    spec = manifest.load_metric(metric)
    read = manifest.load_reader(spec["reader"])
    planes = manifest.load_json(
        os.path.join(ROOT, "perfbench", "fixtures", "phase_trace.json"))
    ctx = {"counters_before": _BEFORE, "counters_after": _AFTER,
           "timings": _TIMINGS, "images": 3, "trace_planes": planes}
    assert read(ctx, **spec["args"]) == pytest.approx(expected)
    # a program that has no such counter, key or annotation (the parent of
    # this PR): nothing read, nothing raised
    empty = {"counters_before": {}, "counters_after": {},
             "timings": [{"decode": 1.0}], "images": 1, "trace_planes": []}
    assert read(empty, **spec["args"]) is None


def test_scorer_roofline_counts_each_traced_aux_launch_as_one_item():
    """``readers/trace_aux_share.py``: the scorer's two modules, three
    launches of 0.5 ms + 0.25 ms; needed work 81.9 kB an item, 0.1 us at the
    v5e's 819 GB/s; each launch counted as one item: 3 x 0.1 us of 2.25 ms."""
    from perfbench.harness import manifest

    spec = manifest.load_metric("scorer_roofline")
    read = manifest.load_reader(spec["reader"])
    events = []
    for k in range(3):
        events.append(["jit__batched_weighted(11)", 1e9 + k * 1e7, 5e5])
        events.append(["jit__batched_scores(12)", 1e9 + k * 1e7 + 6e5, 2.5e5])
    events.append(["jit_program(7)", 2e9, 4e8])   # the transform launch: not the scorer's
    planes = [{"name": "/device:TPU:0",
               "lines": [{"name": "XLA Modules", "events": events}]}]
    ctx = {"trace_planes": planes, "device": {"kind": "TPU v5 lite"},
           "work_per_image": {"smartcrop_score": {"flops": 2.0e6, "bytes": 81900.0}}}
    assert read(ctx, **spec["args"]) == pytest.approx(100.0 * 3 * 1e-7 / 2.25e-3)
    assert ctx["notes"]["smartcrop_score_traced_launches"] == 3
    # nothing of the scorer in the trace (the parent's program in a cell
    # without smc_1; a CPU run): nothing read, nothing raised
    assert read(dict(ctx, trace_planes=[planes[0] | {"lines": [
        {"name": "XLA Modules", "events": events[-1:]}]}]), **spec["args"]) is None
    assert read(dict(ctx, trace_planes=[]), **spec["args"]) is None
    assert read(dict(ctx, work_per_image={"resample": {}}), **spec["args"]) is None


def test_trace_phase_gap_pairs_each_readback_with_the_module_before_it():
    from perfbench.harness import manifest

    read = manifest.load_reader("trace_phase_gap")
    planes = manifest.load_json(
        os.path.join(ROOT, "perfbench", "fixtures", "phase_trace.json"))
    # module ends at 1.206 s, the d2h annotation at 1.356 s
    assert read({"trace_planes": planes}, "^jit_program",
                r"^flyimg:batch:\d+:d2h$") == pytest.approx(150.0)
    # the resolve annotation ends 30 ms later
    assert read({"trace_planes": planes}, "^jit_program",
                r"^flyimg:batch:\d+:resolve$") == pytest.approx(180.0)
    # an annotation with no module before its end pairs with nothing
    assert read({"trace_planes": planes}, "^jit_program",
                r"^flyimg:batch:\d+:dispatch$") is None
    # the older fixture's program annotates no phases
    old = manifest.load_json(
        os.path.join(ROOT, "perfbench", "fixtures", "small_trace.json"))
    assert read({"trace_planes": old}, "^jit_program",
                r"^flyimg:batch:\d+:d2h$") is None


def test_manifest_with_the_new_metrics_keeps_the_rules():
    from perfbench.harness import manifest

    doc = manifest.load_manifest()
    assert manifest.validate(doc) == []
    names = [m["name"] for m in doc["per_layer"]]
    # appended after what was there, nothing reordered
    assert names[:8] == ["decode_ms", "encode_ms", "host_cpu_ms_per_image",
                         "images_per_launch", "padded_slot_share",
                         "roundtrip_ms_per_image", "resample_roofline",
                         "device_idle_share"]
    # the eighteen as accepted, in their order; what later PRs add comes
    # after them and keeps the manifest's own rules: a list of the cells
    # that report it, each a cell that reports the metric it moves, a
    # metric file, and a reader that loads
    assert names[8:18] == ["decode_queue_ms", "decode_run_ms",
                           "encode_queue_ms", "encode_run_ms",
                           "assemble_ms_per_image", "resolve_ms_per_image",
                           "h2d_ms_per_image", "d2h_ms_per_image",
                           "device_run_ms_per_image", "readback_gap_ms"]
    assert len(set(names)) == len(names)
    assert "decode_handover_ms_per_image" in names[18:]
    cells = {c["name"] for c in doc["workloads"]}
    for metric in doc["per_layer"][8:]:
        assert metric["workloads"] and set(metric["workloads"]) <= cells
        for cell in metric["workloads"]:
            assert metric["moves"] in {
                m["name"] for m in manifest.metrics_for(doc, cell, "end_to_end")}
        assert callable(manifest.load_reader(
            manifest.load_metric(metric["name"])["reader"]))
    for metric in doc["per_layer"][8:18]:
        assert metric["workloads"] == ["dslr-backfill-saturated"]


# ---------------------------------------------------------------------------
# 6. where the host's time goes while the device waits: each member's
#    answer, the callers' wake, the threads' own CPU, the gap split, the
#    collector, and the annotations that name them


class _Recording:
    """``jax.profiler.TraceAnnotation`` stand-in: every name entered, with
    the thread it was made on."""

    names = None

    def __init__(self, name, **_):
        self.names.append((name, threading.current_thread().name))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture()
def annotations(monkeypatch):
    names = []
    monkeypatch.setattr(_Recording, "names", names)
    monkeypatch.setattr(batcher_mod.jax.profiler, "TraceAnnotation", _Recording)
    return names


def _grouped(ctl, n):
    """``n`` transform members of one launch: held until all are queued."""
    ctl.pause_launches()
    try:
        futures = [_submit_one(ctl) for _ in range(n)]
    finally:
        ctl.resume_launches()
    for future in futures:
        future.result(timeout=30)
    return futures


def _settled(ctl, launches):
    """Wait until every launch has written its resolve."""
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline and not all(
            "resolve" in launch.marks for launch in launches):
        time.sleep(0.01)


def test_answered_lies_within_the_resolve_in_member_order(fake_launches,
                                                          annotations):
    ctl, program, recorder, metrics = fake_launches
    futures = _grouped(ctl, 3)
    launch = program.launches[-1]
    _settled(ctl, [launch])
    assert launch.images == 3
    start, end = launch.marks["resolve"]
    times = [f.launch_times for f in futures]
    assert len({t[1] for t in times}) == 1 and len({t[2] for t in times}) == 1
    answered = [t[3] for t in times]
    assert start <= times[0][2] <= answered[0] < answered[1] < answered[2] <= end
    # each member's answer is a child annotation of the resolve, on the
    # drain thread
    answers = [t for n, t in annotations if n == f"flyimg:batch:{launch.seq}:answer"]
    assert answers == ["flyimg-batcher-drain"] * 3
    # the drain thread's own CPU over the resolve, beside its seconds
    got = _samples(metrics, "flyimg_batch_resolve")
    assert 0 <= got["flyimg_batch_resolve_thread_seconds_total"] <= \
        got["flyimg_batch_resolve_seconds_sum"] + 1e-3


def test_the_wake_is_recorded_once_per_transform_member(system):
    frames = 3
    for seed in range(frames):
        timings = {}
        system.transform(_jpeg(seed=seed), timings)
        assert timings["device_answer"] >= 0 and timings["device_wake"] >= 0
        assert timings["device_answer"] + timings["device_wake"] <= \
            timings["device"] + 1e-3
    # the codec launches' members are answered too, but are no transform's
    got = _samples(system.metrics, "flyimg_batch_wake_seconds")
    assert got["flyimg_batch_wake_seconds_count"] == frames
    assert got["flyimg_batch_wake_seconds_sum"] >= 0
    # a smart-crop member of the device controller's aux launch wakes
    # nobody's transform: still one a transform member
    system.transform(_jpeg(240, 360), {}, text="w_120,h_120,smc_1")
    assert _samples(system.metrics, "flyimg_batch_wake_seconds_count") == {
        "flyimg_batch_wake_seconds_count": frames + 1}


def _thread_seconds(metrics):
    out = {}
    for name, value in _samples(metrics, "flyimg_stage_thread_seconds_total").items():
        out[name.split('stage="')[1].split('"')[0]] = value
    return out


def test_thread_cpu_is_no_more_than_wall_time_for_every_stage(system,
                                                              annotations):
    system.transform(_jpeg(seed=4), {})
    system.transform(_jpeg(240, 360), {}, text="w_120,h_120,smc_1")
    threads = _thread_seconds(system.metrics)
    walls = _samples(system.metrics, "flyimg_stage_seconds_sum")
    assert {"decode", "device", "encode", "smartcrop_prepare"} <= set(threads)
    for stage, thread_s in threads.items():
        wall = walls[f'flyimg_stage_seconds_sum{{stage="{stage}"}}']
        assert 0 <= thread_s <= wall + 1e-3, stage
    # a stage the thread computes through reads near its wall time, one it
    # sleeps through near nothing; each is an annotation on its own thread
    metrics = MetricsRegistry()
    with tracing.stage("busy", {}, metrics):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < 0.05:
            pass
    with tracing.stage("idle", {}, metrics):
        time.sleep(0.05)
    threads = _thread_seconds(metrics)
    assert threads["busy"] >= 0.025 and threads["idle"] < 0.025
    me = threading.current_thread().name
    assert ("flyimg:stage:busy", me) in annotations
    assert ("flyimg:stage:idle", me) in annotations
    # the pipeline's stages ran on the caller's thread, annotated there
    assert ("flyimg:stage:decode", me) in annotations
    assert {t for n, t in annotations if n.startswith("flyimg:stage:")} == {me}


def _gap_seconds(metrics):
    """``{during: seconds}`` of ``flyimg_device_gap_seconds_total``."""
    return {name.split('during="')[1].split('"')[0]: value for name, value
            in _samples(metrics, "flyimg_device_gap_seconds_total").items()}


def test_gap_account_takes_the_first_label_that_holds():
    metrics = MetricsRegistry()
    gaps = GapAccount(metrics)
    gaps.queued(True, 0.0)            # before the first run: nothing counted
    gaps.move(None, "launch", 1.0)
    gaps.queued(False, 1.0)
    gaps.move("launch", "staging", 2.0)
    gaps.move("staging", "run", 3.0)  # the first run
    gaps.queued(True, 3.5)            # the next members, during the run
    gaps.move("run", "d2h", 4.0)      # d2h over fill
    gaps.move(None, "launch", 4.5)    # launch over d2h
    gaps.queued(False, 4.5)
    gaps.move("d2h", "resolve", 5.0)  # launch over resolve
    gaps.move("launch", "staging", 5.5)
    gaps.move("staging", "run", 6.0)
    gaps.move("resolve", None, 6.5)   # answered while the next runs
    gaps.move("run", "d2h", 7.0)
    gaps.move("d2h", "resolve", 7.2)
    gaps.aux(+1, 7.3)                 # an aux runner from here
    gaps.move("resolve", None, 7.4)
    gaps.aux(-1, 7.6)
    gaps.queued(True, 7.8)
    gaps.move(None, "launch", 8.0)
    expected = {"staging": 0.5, "launch": 1.0, "d2h": 0.7, "resolve": 0.2,
                "fill": 0.2, "empty": 0.4, "aux_overlap": 0.3}
    assert _gap_seconds(metrics) == pytest.approx(expected)
    assert gaps.run_s == pytest.approx(2.0)
    assert (gaps.first_run, gaps.latest) == (3.0, 8.0)
    assert sum(expected[label] for label in LABELS) + gaps.run_s == \
        pytest.approx(gaps.latest - gaps.first_run)
    # a move read a moment before the last one took the lock adds nothing
    gaps.move("launch", "staging", 7.9)
    assert gaps.latest == 8.0
    assert _gap_seconds(metrics) == pytest.approx(expected)


def test_the_gap_split_adds_up_on_a_controller_driven_with_a_fake_runner(
        fake_launches):
    ctl, program, recorder, metrics = fake_launches
    _submit_one(ctl).result(timeout=30)
    _grouped(ctl, 2)
    assert ctl.submit_aux(("k",), 21, _slow_aux).result(timeout=30) == 42
    _submit_one(ctl).result(timeout=30)
    _settled(ctl, program.launches)
    gaps, seconds = ctl._gaps, _gap_seconds(metrics)
    assert len(program.launches) == 3
    assert set(seconds) == set(LABELS + ("aux_overlap",))
    assert gaps.first_run == program.launches[0].marks["run"][0]
    assert sum(seconds[label] for label in LABELS) + gaps.run_s == \
        pytest.approx(gaps.latest - gaps.first_run, abs=1e-6)
    # one launch at a time: the runs are the launches' own, and the device
    # waited for each later launch's staging and every read-back
    assert gaps.run_s == pytest.approx(
        sum(launch.seconds("run") for launch in program.launches), rel=1e-3)
    assert seconds["staging"] >= 2 * program.h2d_s * 0.9
    assert seconds["d2h"] >= 3 * program.d2h_s * 0.9
    # the aux runner ran in a gap, counted apart; the codec controller
    # keeps no account
    assert seconds["aux_overlap"] >= 0.03
    codec = BatchController(deadline_ms=1.0, name="codec")
    codec.close()
    assert codec._gaps is None


def test_gc_collect_bumps_generation_two_and_close_removes_the_hook(caplog):
    metrics = MetricsRegistry()
    handler = ImageHandler(storage=None, params=AppParameters(), metrics=metrics)
    hook = metrics_mod._gc_hook
    assert hook in gc.callbacks
    caplog.set_level(logging.INFO, logger="flyimg.gc")
    gc.collect()
    got = _samples(metrics, "flyimg_gc_")
    assert got['flyimg_gc_collections_total{generation="2"}'] >= 1
    assert got['flyimg_gc_seconds_total{generation="2"}'] > 0
    # a full collection names the thread it ran on
    me = threading.current_thread().name
    assert any(r.name == "flyimg.gc" and me in r.getMessage()
               for r in caplog.records)
    watch = handler._gc_watch
    handler.close()
    assert not any(ref() is watch for ref in hook._watches)
    gc.collect()
    assert _samples(metrics, "flyimg_gc_") == got
    # the one callback stays only while another handler's watch is open
    assert (hook in gc.callbacks) == any(
        ref() is not None for ref in hook._watches)


def _annotating_aux(payloads):
    with tracing.launch_annotation("pool"):
        return [p + 1 for p in payloads]


def test_aux_annotation_names_carry_their_controller(fake_launches,
                                                     annotations):
    ctl, program, recorder, metrics = fake_launches
    codec = BatchController(deadline_ms=1.0, name="codec")
    try:
        assert ctl.submit_aux(("k",), 1, _annotating_aux).result(timeout=30) == 2
        assert codec.submit_aux(("k",), 1, _annotating_aux).result(timeout=30) == 2
    finally:
        codec.close()
    names = [n for n, _ in annotations if n.startswith("flyimg:aux:")]
    # both controllers count their own launches from 1: the names no longer
    # collide
    for controller in ("device", "codec"):
        for phase in ("run", "pool", "resolve", "answer"):
            assert f"flyimg:aux:{controller}:1:{phase}" in names, (controller, phase)
    assert all(n.split(":")[2] in ("device", "codec") for n in names)
    # outside a launch a runner's annotation is nothing
    assert _annotating_aux([1]) == [2]
    assert not [n for n, _ in annotations if n == "pool"]


# two launches of 64 in the window, as the program renders its counters
_HOST_BEFORE = {
    "flyimg_batches_total": 10.0,
    "flyimg_images_processed_total": 640.0,
    "flyimg_batch_resolve_seconds_sum": 12.0,
    "flyimg_batch_resolve_thread_seconds_total": 1.2,
    "flyimg_batch_wake_seconds_sum": 6.4,
    "flyimg_batch_wake_seconds_count": 640.0,
    'flyimg_device_gap_seconds_total{during="resolve"}': 12.5,
    'flyimg_device_gap_seconds_total{during="fill"}': 5.0,
    'flyimg_codec_worker_seconds_total{op="decode"}': 50.0,
    'flyimg_codec_worker_capacity_seconds_total{op="decode"}': 90.0,
    'flyimg_codec_buffers_total{handover="adopted"}': 640.0,
    'flyimg_stage_thread_seconds_total{stage="faces_prepare"}': 100.0,
    'flyimg_stage_seconds_sum{stage="faces_prepare"}': 500.0,
    'flyimg_face_detect_seconds_total{part="forward"}': 3.0,
    'flyimg_face_detect_seconds_total{part="boxes"}': 1.0,
    'flyimg_gc_seconds_total{generation="2"}': 0.5,
}
_HOST_AFTER = dict(_HOST_BEFORE, **{
    "flyimg_batches_total": 12.0,
    "flyimg_images_processed_total": 768.0,
    "flyimg_batch_resolve_seconds_sum": 14.5,
    "flyimg_batch_resolve_thread_seconds_total": 1.45,
    "flyimg_batch_wake_seconds_sum": 8.96,
    "flyimg_batch_wake_seconds_count": 768.0,
    'flyimg_device_gap_seconds_total{during="resolve"}': 15.0,
    'flyimg_device_gap_seconds_total{during="fill"}': 6.0,
    'flyimg_codec_worker_seconds_total{op="decode"}': 51.28,
    'flyimg_codec_worker_capacity_seconds_total{op="decode"}': 92.56,
    'flyimg_codec_buffers_total{handover="adopted"}': 768.0,
    'flyimg_stage_thread_seconds_total{stage="faces_prepare"}': 130.0,
    'flyimg_stage_seconds_sum{stage="faces_prepare"}': 620.0,
    'flyimg_face_detect_seconds_total{part="forward"}': 3.384,
    'flyimg_face_detect_seconds_total{part="boxes"}': 1.128,
    'flyimg_gc_seconds_total{generation="2"}': 0.5,
})


@pytest.mark.parametrize("metric,expected,moves", [
    ("resolve_own_cpu_share", 10.0, "latency_p95_ms"),
    ("answer_wake_ms", 20.0, "latency_p95_ms"),
    ("gap_resolve_ms_per_launch", 1250.0, "images_per_s"),
    ("gap_fill_ms_per_launch", 500.0, "images_per_s"),
    ("codec_pool_busy_share", 50.0, "images_per_s"),
    ("decode_frame_ms", 10.0, "images_per_s"),
    ("faces_prepare_cpu_share", 25.0, "images_per_s"),
    ("faces_forward_ms_per_image", 3.0, "images_per_s"),
    ("faces_boxes_ms_per_image", 1.0, "images_per_s"),
    ("gc_full_ms_per_launch", 0.0, "images_per_s"),
])
def test_host_attribution_metric_files_read_the_recorded_fixture(
        metric, expected, moves):
    from perfbench.harness import manifest

    doc = manifest.load_manifest()
    entry = next(m for m in doc["per_layer"] if m["name"] == metric)
    assert entry["moves"] == moves and entry["source"] == "program_counter"
    spec = manifest.load_metric(metric)
    read = manifest.load_reader(spec["reader"])
    ctx = {"counters_before": _HOST_BEFORE, "counters_after": _HOST_AFTER}
    assert read(ctx, **spec["args"]) == pytest.approx(expected)
    # the parent of this PR has none of these series: nothing read
    assert read({"counters_before": {}, "counters_after": {}}, **spec["args"]) is None
    # a metric that moves the tail lists only the cells that report it
    cells = {"latency_p95_ms": ["dslr-backfill-saturated",
                                "portrait-smartcrop-saturated"]}
    if moves in cells:
        assert entry["workloads"] == cells[moves]


# ---------------------------------------------------------------------------
# 7. the 16-bit cell's metrics: what each file reads, the cell it
#    lists, and nothing read from a program without 16-bit launches

_DEEP_BEFORE = {
    "flyimg_batches_total": 4.0,
    "flyimg_images_processed_total": 97.0,
    'flyimg_batch_depth_total{bits="16"}': 4.0,
    'flyimg_codec_png_seconds_total{op="decode",bits="16"}': 300.0,
    'flyimg_codec_png_images_total{op="decode",bits="16"}': 97.0,
    'flyimg_codec_png_seconds_total{op="encode",bits="16"}': 190.0,
    'flyimg_codec_png_images_total{op="encode",bits="16"}': 96.0,
}
_DEEP_AFTER = dict(_DEEP_BEFORE, **{
    "flyimg_batches_total": 9.0,
    "flyimg_images_processed_total": 257.0,
    'flyimg_batch_depth_total{bits="16"}': 9.0,
    'flyimg_codec_png_seconds_total{op="decode",bits="16"}': 780.0,
    'flyimg_codec_png_images_total{op="decode",bits="16"}': 257.0,
    'flyimg_codec_png_seconds_total{op="encode",bits="16"}': 510.0,
    'flyimg_codec_png_images_total{op="encode",bits="16"}': 256.0,
})


@pytest.mark.parametrize("metric,expected,layer", [
    ("deep_launch_share", 100.0, "batcher"),
    ("png16_decode_ms_per_image", 3000.0, "host_decode"),
    ("png16_encode_ms_per_image", 2000.0, "host_encode"),
])
def test_16bit_cell_metric_files_read_the_recorded_counters(metric, expected, layer):
    from perfbench.harness import manifest

    doc = manifest.load_manifest()
    entry = next(m for m in doc["per_layer"] if m["name"] == metric)
    assert (entry["layer"], entry["moves"], entry["source"]) == (
        layer, "images_per_s", "program_counter")
    assert entry["workloads"] == ["archive-png16-saturated"]
    spec = manifest.load_metric(metric)
    read = manifest.load_reader(spec["reader"])
    ctx = {"counters_before": _DEEP_BEFORE, "counters_after": _DEEP_AFTER}
    assert read(ctx, **spec["args"]) == pytest.approx(expected)
    # a launch narrowed to 8 bits on the way lowers the share
    mixed = dict(_DEEP_AFTER, **{'flyimg_batch_depth_total{bits="16"}': 8.0})
    if metric == "deep_launch_share":
        assert read(dict(ctx, counters_after=mixed), **spec["args"]) == pytest.approx(80.0)
    # a program without 16-bit launches has none of these series: nothing read
    assert read({"counters_before": {}, "counters_after": {}}, **spec["args"]) is None


def test_resample16_roofline_reads_the_16bit_module_alone():
    """``readers/trace_share.py`` over ``jit_program16``: one full launch
    of 32 of 0.64 s (a lone launch of 1 beside it counts for nothing), the
    needed work memory-bound (1.6e8 bytes an image at the v5e's 819 GB/s);
    an 8-bit ``jit_program`` module is no 16-bit program's."""
    from perfbench.harness import manifest

    entry = next(m for m in manifest.load_manifest()["per_layer"]
                 if m["name"] == "resample16_roofline")
    assert (entry["layer"], entry["moves"], entry["unit"]) == (
        "device_program", "images_per_s", "%")
    spec = manifest.load_metric("resample16_roofline")
    read = manifest.load_reader(spec["reader"])
    work = {"resample": {"flops": 1.15884e9, "bytes": 1.6077312e8}}
    events = [["jit_program16(3)", 1e9, 2e7], ["jit_program16(3)", 2e9, 6.4e8]]
    planes = [{"name": "/device:TPU:0", "lines": [{"name": "XLA Modules", "events": events}]}]
    ctx = {"trace_planes": planes, "device": {"kind": "TPU v5 lite"},
           "work_per_image": work, "launch_sizes": {"1": 1, "32": 1},
           "counters_before": {"flyimg_images_processed_total": 0.0},
           "counters_after": {"flyimg_images_processed_total": 33.0}}
    least = 1.6077312e8 / 819e9
    assert read(ctx, **spec["args"]) == pytest.approx(100.0 * least * 32 / 0.64)
    eight = [{"name": "/device:TPU:0", "lines": [{"name": "XLA Modules",
                                                 "events": [["jit_program(3)", 1e9, 6.4e8]]}]}]
    assert read(dict(ctx, trace_planes=eight), **spec["args"]) is None
    assert read(dict(ctx, trace_planes=[]), **spec["args"]) is None


@pytest.mark.parametrize("twin", [
    "kept_block_share.png16", "readback_row_major_share.png16",
    "device_idle_share.png16", "latency_p95_ms.png16",
    "gap_fill_ms_per_launch.png16", "decode_ms.png16", "encode_ms.png16",
])
def test_16bit_cell_twins_read_as_their_originals(twin):
    """A metric whose list was accepted before the 16-bit cell came is read
    there through a twin (`<name>.png16`, as the face cell's `<name>.faces`):
    the same layer and reader, the one cell, moving the rate it reports.
    The device's idle share reads the 16-bit module alone."""
    from perfbench.harness import manifest

    per_layer = {m["name"]: m for m in manifest.load_manifest()["per_layer"]}
    name = twin.rsplit(".", 1)[0]
    # the tail is end to end where it is bounded: its per-layer reading
    # is the face cell's twin
    first_name = name if name in per_layer else f"{name}.faces"
    entry, original = per_layer[twin], per_layer[first_name]
    assert entry["workloads"] == ["archive-png16-saturated"]
    assert entry["moves"] == "images_per_s"
    assert (entry["layer"], entry["unit"], entry["better"], entry["source"]) == (
        original["layer"], original["unit"], original["better"], original["source"])
    spec, first = manifest.load_metric(twin), manifest.load_metric(first_name)
    assert spec["reader"] == first["reader"]
    if name != "device_idle_share":
        assert spec["args"] == first["args"]
        return
    assert spec["args"] == dict(first["args"], module="^jit_program16")
    read = manifest.load_reader(spec["reader"])
    host = [["flyimg:batch:6:dispatch", 100, 10], ["flyimg:batch:6:d2h", 950, 150]]

    def planes(module):
        return [{"name": "/host:CPU", "lines": [{"name": "annotations", "events": host}]},
                {"name": "/device:TPU:0", "lines": [{"name": "XLA Modules",
                                                     "events": [[module, 300, 250]]}]}]

    # held 100 -> 1100 ns, the module ran 250 of them
    ctx = {"counters_before": {}, "counters_after": {}}
    assert read(dict(ctx, trace_planes=planes("jit_program16(3)")),
                **spec["args"]) == pytest.approx(75.0)
    assert read(dict(ctx, trace_planes=planes("jit_program(3)")), **spec["args"]) is None
