"""Pipelined host stage DAG (runtime/hostpipeline.py;
docs/host-pipeline.md): bounded per-stage pools, admission backpressure,
wedged-worker self-healing, shutdown drain, observability wiring, and
the handler-integration byte-identity pin."""

import io
import threading
import time

import numpy as np
import pytest
from PIL import Image

from flyimg_tpu.exceptions import ServiceUnavailableException
from flyimg_tpu.runtime.hostpipeline import HostPipeline, StagePool
from flyimg_tpu.runtime.metrics import MetricsRegistry

from tests.test_roi_decode import SRC_JPEG, make_handler


# ---------------------------------------------------------------------------
# StagePool unit behavior


def test_stagepool_runs_tasks_and_returns_results():
    pool = StagePool("decode", workers=2, queue_depth=8)
    try:
        # stays within the admission bound (workers + queue_depth = 10)
        futs = [pool.submit(lambda i=i: i * i) for i in range(8)]
        assert [f.result(timeout=10) for f in futs] == [
            i * i for i in range(8)
        ]
        assert pool.pending == 0
    finally:
        pool.close()


def test_stagepool_task_exception_surfaces_to_caller():
    pool = StagePool("decode", workers=1, queue_depth=4)
    try:
        def boom():
            raise ValueError("bad bytes")

        with pytest.raises(ValueError, match="bad bytes"):
            pool.submit(boom).result(timeout=10)
        # the worker survives its task's exception
        assert pool.submit(lambda: 7).result(timeout=10) == 7
    finally:
        pool.close()


def test_backpressure_sheds_typed_503():
    """Pending over workers + queue_depth sheds through the admission
    gate (the same 503 + Retry-After contract as the batch queues) —
    never an invisible unbounded queue."""
    metrics = MetricsRegistry()
    pool = StagePool(
        "decode", workers=1, queue_depth=1, metrics=metrics,
        shed_retry_after_s=3.0,
    )
    gate = threading.Event()
    try:
        running = pool.submit(gate.wait)   # occupies the worker
        queued = pool.submit(lambda: 1)    # fills the queue bound
        with pytest.raises(ServiceUnavailableException) as exc_info:
            pool.submit(lambda: 2)
        assert exc_info.value.retry_after_s == 3
        shed = metrics.counter(
            'flyimg_shed_total{reason="host decode pool"}'
        )
        assert shed.value == 1
        gate.set()
        assert queued.result(timeout=10) == 1
        running.result(timeout=10)
    finally:
        gate.set()
        pool.close()


def test_queue_wait_recorded_in_histogram_and_flightrecorder():
    from flyimg_tpu.runtime.flightrecorder import FlightRecorder

    metrics = MetricsRegistry()
    recorder = FlightRecorder(size=32)
    pool = StagePool(
        "fetch", workers=1, queue_depth=4, metrics=metrics,
        flight_recorder=recorder,
    )
    gate = threading.Event()
    try:
        pool.submit(gate.wait)
        waited = pool.submit(lambda: "ok")  # must queue behind the gate
        time.sleep(0.05)                    # accrue a visible queue wait
        gate.set()
        assert waited.result(timeout=10) == "ok"
        hist = metrics.histogram(
            'flyimg_host_pool_queue_wait_seconds{pool="fetch"}'
        )
        _, _, n = hist.snapshot()
        assert n >= 2
        rows = recorder.snapshot()["records"]
        host_rows = [r for r in rows if r["kind"] == "host_stage"]
        assert host_rows, "a >=5ms queue wait must land in the ring"
        assert host_rows[0]["stage"] == "fetch"
        assert host_rows[0]["queue_wait_s"] >= StagePool.FLIGHT_WAIT_MIN_S
    finally:
        gate.set()
        pool.close()


def test_wedged_worker_detected_and_healed():
    """A worker stuck inside one task past the wedge timeout is
    abandoned and replaced at the next submit — the batcher-executor
    healing contract applied to stage workers."""
    metrics = MetricsRegistry()
    pool = StagePool(
        "decode", workers=1, queue_depth=8, wedge_timeout_s=0.05,
        metrics=metrics,
    )
    gate = threading.Event()
    try:
        wedged = pool.submit(gate.wait)  # wedges the only worker
        time.sleep(0.15)                 # exceed the wedge timeout
        after = pool.submit(lambda: 42)  # submit-time heal respawns
        assert after.result(timeout=10) == 42
        restarts = metrics.counter(
            'flyimg_host_pool_worker_restarts_total'
            '{pool="decode",reason="wedged"}'
        )
        assert restarts.value == 1
        # the abandoned task's future FAILED at heal time (its caller
        # unblocks) and its admission slot was RELEASED — a wedge must
        # not permanently shrink the stage's capacity
        with pytest.raises(TimeoutError):
            wedged.result(timeout=1)
        assert pool.pending == 0
        # the abandoned worker finishing late is harmless (done()-guarded)
        gate.set()
        assert pool.submit(lambda: 1).result(timeout=10) == 1
    finally:
        gate.set()
        pool.close()


def test_dead_worker_respawned_at_submit():
    metrics = MetricsRegistry()
    pool = StagePool("encode", workers=1, queue_depth=4, metrics=metrics)
    try:
        # plant a dead thread in the bookkeeping (a worker killed by a
        # fatal error would look exactly like this at the next submit)
        dead = threading.Thread(target=lambda: None)
        dead.start()
        dead.join()
        with pool._lock:
            pool._busy[dead] = None
        assert pool.submit(lambda: "alive").result(timeout=10) == "alive"
        restarts = metrics.counter(
            'flyimg_host_pool_worker_restarts_total'
            '{pool="encode",reason="dead"}'
        )
        assert restarts.value == 1
        with pool._lock:
            assert dead not in pool._busy
    finally:
        pool.close()


def test_close_drains_queued_tasks():
    pool = StagePool("decode", workers=1, queue_depth=16)
    done = []
    futs = [
        pool.submit(lambda i=i: done.append(i) or i) for i in range(6)
    ]
    pool.close(drain_timeout_s=10.0)
    assert [f.result(timeout=1) for f in futs] == list(range(6))
    assert len(done) == 6
    with pytest.raises(RuntimeError):
        pool.submit(lambda: 1)


def test_close_strands_get_timeout_error():
    """A wedged worker must not hang shutdown: past the drain budget the
    never-ran tasks fail with TimeoutError instead of parking callers
    forever."""
    pool = StagePool("decode", workers=1, queue_depth=8)
    gate = threading.Event()
    pool.submit(gate.wait)
    stranded = pool.submit(lambda: "never")
    pool.close(drain_timeout_s=0.2)
    with pytest.raises(TimeoutError):
        stranded.result(timeout=1)
    gate.set()  # release the abandoned worker


def test_abandoned_worker_never_swallows_a_stop_sentinel():
    """A worker that self-healing abandoned as wedged, and that finished
    late, parks on ``queue.get()`` off the roster. ``close()`` puts one
    stop sentinel per LIVE worker; the abandoned one, parked first, is
    woken first and takes it: it must re-put it, or the live worker it
    was meant for parks for the whole drain budget."""
    pool = StagePool(
        "retiree", workers=1, queue_depth=8, wedge_timeout_s=0.05,
    )
    first, second = threading.Event(), threading.Event()
    try:
        pool.submit(first.wait)          # wedges the only worker
        time.sleep(0.15)                 # exceed the wedge timeout
        held = pool.submit(second.wait)  # heal: abandon it, spawn another
        first.set()                      # the abandoned worker finishes
        time.sleep(0.1)                  # ... and parks on the queue
        second.set()                     # the live worker parks behind it
        assert held.result(timeout=10) is True
        time.sleep(0.1)
        t0 = time.monotonic()
        pool.close(drain_timeout_s=10.0)
        assert time.monotonic() - t0 < 5.0
        leftover = [
            thread for thread in threading.enumerate()
            if thread.name == "flyimg-host-retiree"
        ]
        for thread in leftover:
            thread.join(timeout=5.0)
        assert not any(thread.is_alive() for thread in leftover)
    finally:
        first.set()
        second.set()
        pool.close()


# ---------------------------------------------------------------------------
# HostPipeline wiring


def test_pipeline_disabled_is_inert():
    pipeline = HostPipeline(enabled=False)
    assert not pipeline.enabled
    assert pipeline.pools() == []
    assert pipeline.pressure() == 0.0
    assert pipeline.snapshot() == {}
    pipeline.close()  # no-op


def test_pipeline_pressure_tracks_worst_stage():
    pipeline = HostPipeline(
        enabled=True, fetch_workers=1, decode_workers=1,
        encode_workers=1, queue_depth=1,
    )
    gate = threading.Event()
    try:
        assert pipeline.pressure() == 0.0
        pool = pipeline.pool("decode")
        pool.submit(gate.wait)
        pool.submit(lambda: 1)
        assert pipeline.pressure() == pytest.approx(1.0)  # 2 / (1 + 1)
    finally:
        gate.set()
        pipeline.close()


def test_brownout_consumes_host_stage_pressure():
    from flyimg_tpu.runtime.brownout import BrownoutEngine

    pipeline = HostPipeline(
        enabled=True, fetch_workers=1, decode_workers=1,
        encode_workers=1, queue_depth=1,
    )
    engine = BrownoutEngine(enabled=True)
    engine.attach(host_pipeline=pipeline)
    gate = threading.Event()
    try:
        assert engine._components().get("host_stage", 0.0) == 0.0
        pool = pipeline.pool("encode")
        pool.submit(gate.wait)
        pool.submit(lambda: 1)
        assert engine._components()["host_stage"] == pytest.approx(1.0)
    finally:
        gate.set()
        pipeline.close()


# ---------------------------------------------------------------------------
# handler integration


def test_handler_pipeline_byte_identity(tmp_path):
    """The stage DAG must not change a single output byte — it only
    changes WHERE the stage work runs."""
    h_off, _ = make_handler(tmp_path / "off")
    h_on, pipeline = make_handler(
        tmp_path / "on", host_pipeline_enable=True
    )
    assert pipeline.enabled
    src_off = tmp_path / "off-src.jpg"
    src_off.write_bytes(SRC_JPEG)
    src_on = tmp_path / "on-src.jpg"
    src_on.write_bytes(SRC_JPEG)
    try:
        for opts in (
            "w_200,h_300,c_1,o_jpg",
            "w_300,o_png",
            "e_1,p1x_50,p1y_40,p2x_800,p2y_600,w_150,o_jpg",
        ):
            off = h_off.process_image(opts, str(src_off))
            on = h_on.process_image(opts, str(src_on))
            assert on.content == off.content, opts
    finally:
        pipeline.close()


def test_handler_pipeline_with_roi(tmp_path):
    """Both knobs together: the ROI window decode runs ON the decode
    stage pool and parity holds."""
    h_off, _ = make_handler(tmp_path / "off")
    h_on, pipeline = make_handler(
        tmp_path / "on", host_pipeline_enable=True, decode_roi=True
    )
    src_off = tmp_path / "off-src.jpg"
    src_off.write_bytes(SRC_JPEG)
    src_on = tmp_path / "on-src.jpg"
    src_on.write_bytes(SRC_JPEG)
    try:
        off = h_off.process_image("w_200,h_300,c_1,o_png", str(src_off))
        on = h_on.process_image("w_200,h_300,c_1,o_png", str(src_on))
        a = np.asarray(Image.open(io.BytesIO(off.content))).astype(int)
        b = np.asarray(Image.open(io.BytesIO(on.content))).astype(int)
        assert np.abs(a - b).max() <= 1
        assert "decode_roi" in on.timings
    finally:
        pipeline.close()


def test_handler_wedged_stage_falls_back_inline(tmp_path):
    """A wedged stage pool degrades to running the work inline in the
    request thread (counted as a wedge), not to failing the request —
    the same posture as the wedged-batcher fallbacks."""
    handler, pipeline = make_handler(
        tmp_path, host_pipeline_enable=True,
        host_pipeline_decode_workers=1,
        device_result_timeout_s=0.2,
    )
    gate = threading.Event()
    try:
        pipeline.pool("decode").submit(gate.wait)  # wedge the stage
        out = handler._stage("decode", lambda: "inline", None)
        assert out == "inline"
        wedges = handler.metrics
        assert wedges is None  # direct handler: counter guarded by None
    finally:
        gate.set()
        pipeline.close()


def test_handler_stage_shed_propagates_503(tmp_path):
    handler, pipeline = make_handler(
        tmp_path, host_pipeline_enable=True,
        host_pipeline_fetch_workers=1, host_pipeline_queue_depth=1,
    )
    gate = threading.Event()
    try:
        pool = pipeline.pool("fetch")
        pool.submit(gate.wait)
        pool.submit(lambda: 1)
        with pytest.raises(ServiceUnavailableException):
            handler._stage("fetch", lambda: "x", None,
                           inline_fallback=False)
    finally:
        gate.set()
        pipeline.close()
