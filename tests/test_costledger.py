"""Performance observatory (ISSUE 7): the per-plan XLA cost ledger and
its ProgramHandle compile path (including the backend-returns-nothing /
backend-raises fallbacks), the batch flight recorder (ring, dumps,
rate limit, SLO-breach + brownout-escalation triggers), the on-demand
device profiler (arm/budget/watchdog under a fake jax.profiler), the
device-time split, and the debug-gated HTTP surface
(/debug/plans, /debug/flightrecorder, /debug/profile)."""

import asyncio
import glob
import json
import os
import time

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from flyimg_tpu.appconfig import AppParameters
from flyimg_tpu.codecs import encode
from flyimg_tpu.ops.compose import ProgramHandle
from flyimg_tpu.runtime.costledger import (
    PlanCostLedger,
    get_ledger,
    key_digest,
    normalize_cost_analysis,
)
from flyimg_tpu.runtime.flightrecorder import FlightRecorder
from flyimg_tpu.runtime.metrics import MetricsRegistry, PoolUtilization
from flyimg_tpu.runtime.profiling import DeviceProfiler

# ---------------------------------------------------------------------------
# normalize_cost_analysis: every raw shape the backends produce


def test_normalize_list_of_dicts_merges_totals():
    raw = [{"flops": 100.0, "bytes accessed": 64.0, "utilization0{}": 1.0},
           {"flops": 20.0, "transcendentals": 3.0}]
    out = normalize_cost_analysis(raw)
    assert out == {
        "flops": 120.0, "bytes_accessed": 64.0, "transcendentals": 3.0,
    }


def test_normalize_bare_dict():
    out = normalize_cost_analysis({"flops": 7.0, "bytes accessed": 9.0})
    assert out["flops"] == 7.0 and out["bytes_accessed"] == 9.0


def test_normalize_none_empty_and_junk_return_none():
    assert normalize_cost_analysis(None) is None
    assert normalize_cost_analysis([]) is None
    assert normalize_cost_analysis({}) is None
    assert normalize_cost_analysis({"utilization0{}": 1.0}) is None
    assert normalize_cost_analysis("nonsense") is None


# ---------------------------------------------------------------------------
# ProgramHandle: AOT compile feeds the ledger; fallbacks never crash


class _FakeCompiled:
    def __init__(self, fn, cost_raw, raises=False):
        self._fn = fn
        self._cost_raw = cost_raw
        self._raises = raises

    def __call__(self, *args):
        return self._fn(*args)

    def cost_analysis(self):
        if self._raises:
            raise NotImplementedError("no analysis on this backend")
        return self._cost_raw

    def memory_analysis(self):
        return None


class _FakeJitted:
    """Stands in for a jitted fn: lower().compile() yields a
    _FakeCompiled (or raises), and the plain call path works."""

    def __init__(self, fn, cost_raw=None, cost_raises=False,
                 lower_raises=False):
        self._fn = fn
        self._cost_raw = cost_raw
        self._cost_raises = cost_raises
        self._lower_raises = lower_raises
        self.plain_calls = 0

    def __call__(self, *args):
        self.plain_calls += 1
        return self._fn(*args)

    def lower(self, *args):
        if self._lower_raises:
            raise RuntimeError("the compiler refused this program")
        outer = self

        class _Lowered:
            def compile(self):
                return _FakeCompiled(
                    outer._fn, outer._cost_raw, raises=outer._cost_raises
                )

        return _Lowered()


def _fresh_handle(jitted, key="k"):
    handle = ProgramHandle.__new__(ProgramHandle)
    handle._jitted = jitted
    handle._compiled = None
    handle.in_sharding = None
    import threading

    handle._lock = threading.Lock()
    handle.ledger_key = key_digest((key, "test"))
    handle.descriptor = {"ops": ["test"]}
    return handle


def test_handle_costed_compile_records_ledger_entry():
    jitted = _FakeJitted(
        lambda x: x + 1, cost_raw=[{"flops": 42.0, "bytes accessed": 8.0}]
    )
    handle = _fresh_handle(jitted, key="costed")
    assert not handle.is_compiled
    assert handle(1) == 2
    assert handle.is_compiled
    assert jitted.plain_calls == 0  # execution went through the AOT object
    row = _ledger_row(handle.ledger_key)
    assert row["costed"] and row["flops"] == 42.0
    assert row["bytes_accessed"] == 8.0
    assert row["compile_s"] is not None and row["compile_s"] >= 0


def test_handle_cost_analysis_none_yields_nulled_entry_no_crash():
    """The CPU case ISSUE 7 pins: cost_analysis() returns None -> the
    ledger entry exists with nulled cost fields and the call works."""
    jitted = _FakeJitted(lambda x: x * 2, cost_raw=None)
    handle = _fresh_handle(jitted, key="uncosted-none")
    assert handle(3) == 6
    row = _ledger_row(handle.ledger_key)
    assert row["flops"] is None and row["bytes_accessed"] is None
    assert not row["costed"]
    assert handle(4) == 8  # later calls still served


def test_handle_cost_analysis_raises_yields_nulled_entry_no_crash():
    jitted = _FakeJitted(lambda x: x * 3, cost_raises=True)
    handle = _fresh_handle(jitted, key="uncosted-raise")
    assert handle(2) == 6
    row = _ledger_row(handle.ledger_key)
    assert row["flops"] is None and not row["costed"]


def test_handle_compile_failure_propagates():
    """A program the compiler refuses fails its caller — every call, with
    no jitted-path retry and no ledger row pretending it compiled."""
    jitted = _FakeJitted(lambda x: x - 1, lower_raises=True)
    handle = _fresh_handle(jitted, key="refused")
    for _ in range(2):
        with pytest.raises(RuntimeError):
            handle(10)
    assert not handle.is_compiled
    assert jitted.plain_calls == 0
    assert not [
        r for r in get_ledger().entries() if r["key"] == handle.ledger_key
    ]


def _ledger_row(key):
    rows = [r for r in get_ledger().entries() if r["key"] == key]
    assert rows, f"no ledger entry for {key}"
    return rows[0]


# ---------------------------------------------------------------------------
# PlanCostLedger: accounting + bound


def test_ledger_launches_accumulate_and_survive_missing_compile():
    ledger = PlanCostLedger()
    ledger.record_compile(
        "abc", descriptor={"ops": ["resample"]}, compile_s=0.5,
        cost={"flops": 10.0, "bytes_accessed": 4.0},
        peak_memory_bytes=100.0,
    )
    ledger.record_launch("abc", device_s=0.2, images=8)
    ledger.record_launch("abc", device_s=0.3, images=16)
    # a launch for an evicted/never-compiled key creates an uncosted row
    ledger.record_launch("zzz", device_s=0.1, images=1)
    rows = {r["key"]: r for r in ledger.entries()}
    assert rows["abc"]["launches"] == 2 and rows["abc"]["images"] == 24
    assert rows["abc"]["device_s"] == pytest.approx(0.5)
    assert rows["abc"]["flops_executed"] == pytest.approx(20.0)
    assert rows["zzz"]["flops"] is None and rows["zzz"]["launches"] == 1
    agg = ledger.aggregates()
    assert agg["entries"] == 2.0
    assert agg["flops_executed"] == pytest.approx(20.0)
    assert agg["device_seconds"] == pytest.approx(0.6)
    assert agg["peak_memory_bytes"] == 100.0


def test_ledger_launch_at_capacity_does_not_self_evict():
    """Regression: a launch for an evicted compile record arriving at a
    FULL table used to insert the fresh entry (no launch stamp yet) and
    immediately evict it as 'least recent' — losing the plan's usage
    accounting while mutating an orphan."""
    ledger = PlanCostLedger(max_entries=8)
    for i in range(8):
        ledger.record_compile(f"k{i}", compile_s=0.01, cost={"flops": 1.0})
        ledger.record_launch(f"k{i}", device_s=0.01, images=1)
    ledger.record_launch("fresh", device_s=0.05, images=2)
    rows = {r["key"]: r for r in ledger.entries()}
    assert "fresh" in rows
    assert rows["fresh"]["launches"] == 1 and rows["fresh"]["images"] == 2
    assert len(rows) == 8  # bound still holds (k0 went instead)
    assert "k0" not in rows


def test_ledger_bound_evicts_least_recently_launched():
    ledger = PlanCostLedger(max_entries=8)
    for i in range(12):
        ledger.record_compile(f"k{i}", compile_s=0.01, cost={"flops": 1.0})
        ledger.record_launch(f"k{i}", device_s=0.01, images=1)
    rows = ledger.entries()
    assert len(rows) == 8
    keys = {r["key"] for r in rows}
    assert "k11" in keys and "k0" not in keys
    # since-boot aggregates survive the eviction
    assert ledger.aggregates()["compiles"] == 12.0


# ---------------------------------------------------------------------------
# flight recorder: ring, summary, dump + rate limit


def _record(rec, i=0, **kw):
    defaults = dict(
        controller="device", batch_id=i, plan_key=f"p{i}", occupancy=6,
        capacity=8,
        # the launch's one record as the batcher hands it over
        # (_Launch.fields()): h2d is the completed transfer, run the
        # program, sync the read-back alone; device_s = the part of h2d
        # after the dispatch + run + sync
        phases=dict(
            queue_wait_s=0.004, assemble_s=0.003, slot_wait_s=0.0,
            h2d_s=0.005, dispatch_s=0.001, run_s=0.006, sync_s=0.002,
            device_s=0.013,
        ),
        compile_hit=True, kind="primary",
        trace_id="t" * 32,
    )
    defaults.update(kw)
    rec.record(**defaults)


def test_flightrecorder_ring_is_bounded_and_newest_first():
    rec = FlightRecorder(size=16, dump_dir="/nonexistent")
    for i in range(40):
        _record(rec, i)
    snap = rec.snapshot()
    assert snap["summary"]["records"] == 16
    assert snap["records"][0]["batch_id"] == 39  # newest first
    assert snap["records"][0]["seq"] == 40
    assert snap["summary"]["mean_occupancy"] == pytest.approx(6 / 8)


def test_flightrecorder_dump_writes_artifact_and_rate_limits(tmp_path):
    clock = [1000.0]
    rec = FlightRecorder(
        size=8, dump_dir=str(tmp_path), min_dump_interval_s=30.0,
        clock=lambda: clock[0],
    )
    _record(rec, 1)
    _record(rec, 2, kind="recovery", compile_hit=False)
    path = rec.dump("slo_breach", context={"burn_rate_fast": 20.0})
    assert path is not None and os.path.exists(path)
    with open(path) as fh:
        doc = json.load(fh)
    assert doc["reason"] == "slo_breach"
    assert doc["context"]["burn_rate_fast"] == 20.0
    assert doc["summary"]["records"] == 2
    assert doc["summary"]["recovery_launches"] == 1
    assert doc["summary"]["compile_misses"] == 1
    row = doc["records"][0]
    assert row["h2d_s"] == pytest.approx(0.005)
    assert row["run_s"] == pytest.approx(0.006)
    # every row carries every phase field; one the launch did not reach
    # (resolve ends after the row is written) is null, not missing
    assert row["resolve_s"] is None and row["h2d_cpu_s"] is None
    # rate limit: a second dump inside the interval is suppressed
    assert rec.dump("slo_breach") is None
    clock[0] += 31.0
    assert rec.dump("brownout_escalation") is not None
    names = rec.snapshot()["dumps"]["files"]
    assert len(names) == 2
    assert rec.snapshot()["dumps"]["suppressed_by_rate_limit"] == 1


def test_flightrecorder_empty_ring_dump_does_not_burn_rate_limit(tmp_path):
    """Regression: an evidence-free trigger (breach before any launch)
    used to stamp the rate-limit clock on its way to returning None,
    suppressing the NEXT trigger that actually had records to dump."""
    clock = [1000.0]
    rec = FlightRecorder(
        size=8, dump_dir=str(tmp_path), min_dump_interval_s=30.0,
        clock=lambda: clock[0],
    )
    assert rec.dump("slo_breach") is None  # empty ring: nothing written
    clock[0] += 5.0                        # well inside the interval
    _record(rec, 1)
    path = rec.dump("slo_breach")
    assert path is not None and os.path.exists(path)
    assert rec.snapshot()["dumps"]["suppressed_by_rate_limit"] == 0


def test_flightrecorder_prunes_to_max_dumps(tmp_path):
    clock = [0.0]
    rec = FlightRecorder(
        size=4, dump_dir=str(tmp_path), min_dump_interval_s=0.0,
        max_dumps=3, clock=lambda: clock[0],
    )
    _record(rec)
    for i in range(6):
        clock[0] += 1.0
        # distinct mtimes so prune ordering is deterministic
        path = rec.dump(f"r{i}")
        assert path is not None
        os.utime(path, (i, i))
    files = glob.glob(str(tmp_path / "flightrecorder-*.json"))
    assert len(files) == 3


def test_flightrecorder_record_carries_brownout_level():
    rec = FlightRecorder(size=4, dump_dir="/nonexistent")
    rec.attach(level_fn=lambda: 2)
    _record(rec)
    assert rec.snapshot()["records"][0]["brownout_level"] == 2


# ---------------------------------------------------------------------------
# breach / escalation listeners drive the dump


def test_slo_breach_listener_fires_with_breach_doc():
    from flyimg_tpu.runtime.slo import SloEngine

    eng = SloEngine(
        latency_p99_ms=100.0, availability=99.0, window_fast_s=60.0,
        window_slow_s=600.0, burn_threshold_fast=10.0,
        burn_threshold_slow=2.0, clock=lambda: 1000.0,
    )
    seen = []
    eng.add_breach_listener(seen.append)
    for _ in range(5):
        eng.record(0.01, ok=False)  # 100% errors -> burn 100 > thresholds
    assert len(seen) == 1  # edge-triggered: once per breach edge
    assert seen[0]["event"] == "slo.breach"
    assert seen[0]["burn_rate_fast"] > 10.0


def test_brownout_escalation_listener_fires_outside_lock():
    from flyimg_tpu.runtime.brownout import BrownoutEngine
    from flyimg_tpu.testing import faults

    engine = BrownoutEngine(enabled=True, min_dwell_s=0.0)
    seen = []
    # the listener re-enters the engine (snapshot takes the lock): this
    # deadlocks if notifications fired under the lock
    engine.add_transition_listener(
        lambda info: seen.append((info, engine.snapshot()["level"]))
    )
    injector = faults.FaultInjector()
    injector.plan("brownout.signal", lambda **_: 2.0)  # pressure -> SHED
    faults.install(injector)
    try:
        assert engine.evaluate() == 3
    finally:
        faults.clear()
    assert len(seen) == 1
    info, level_at_cb = seen[0]
    assert info["event"] == "brownout.escalation"
    assert info["to"] == "shed" and level_at_cb == 3


# ---------------------------------------------------------------------------
# profiler: arm/budget/409/watchdog under a fake jax.profiler


class _FakeJaxProfiler:
    def __init__(self):
        self.started = []
        self.stopped = 0

    def start_trace(self, path):
        self.started.append(path)

    def stop_trace(self):
        self.stopped += 1


@pytest.fixture()
def fake_profiler(monkeypatch, tmp_path):
    import jax

    fake = _FakeJaxProfiler()
    monkeypatch.setattr(jax, "profiler", fake)
    prof = DeviceProfiler(
        base_dir=str(tmp_path / "profiles"), max_batches=8,
        max_seconds=30.0,
    )
    return prof, fake


def test_profiler_batch_budget_capture(fake_profiler):
    prof, fake = fake_profiler
    state = prof.arm(2)
    assert state["armed"] and state["remaining_batches"] == 2
    assert prof.busy
    prof.on_batch_start()       # first dispatch starts the trace
    assert fake.started and prof.snapshot()["active"]
    prof.on_batch_start()       # idempotent while active
    assert len(fake.started) == 1
    prof.on_batch_end()
    assert fake.stopped == 0    # budget not yet spent
    prof.on_batch_end()
    assert fake.stopped == 1    # stopped at the budget
    assert not prof.busy
    assert prof.snapshot()["captures_total"] == 1


def test_profiler_single_flight_and_budget_clamp(fake_profiler):
    prof, _ = fake_profiler
    state = prof.arm(10_000)    # clamped to max_batches
    assert state["remaining_batches"] == 8
    with pytest.raises(RuntimeError):
        prof.arm(1)
    # un-arm via the finish path so the fixture ends clean
    prof._finish(prof._capture_id, "test")
    assert not prof.busy


def test_profiler_watchdog_disarms_idle_capture(fake_profiler):
    prof, fake = fake_profiler
    prof.arm(4, max_s=1.0)
    deadline = time.monotonic() + 5.0
    while prof.busy and time.monotonic() < deadline:
        time.sleep(0.05)
    assert not prof.busy        # watchdog disarmed it
    assert fake.started == [] and fake.stopped == 0  # never started
    prof.on_batch_start()       # later batches are untouched
    assert fake.started == []


def test_profiler_capture_path_resolves_listed_names_only(fake_profiler):
    """The download endpoint's resolver: a listed capture resolves, an
    unlisted (or path-traversal) name returns None instead of a path —
    pinned because the dict-vs-attr access here 500'd in a live drive."""
    prof, _ = fake_profiler
    cap = os.path.join(prof.base_dir, "capture-20260803-000000")
    os.makedirs(cap)
    with open(os.path.join(cap, "trace.pb"), "wb") as fh:
        fh.write(b"x" * 32)
    listed = prof.captures()
    assert listed and listed[0]["name"] == "capture-20260803-000000"
    assert listed[0]["bytes"] == 32
    assert prof.capture_path("capture-20260803-000000") == cap
    assert prof.capture_path("capture-nope") is None
    assert prof.capture_path("../../etc") is None


def test_profiler_start_failure_disarms_without_raising(fake_profiler):
    prof, fake = fake_profiler

    def boom(_path):
        raise RuntimeError("profiler already active")

    fake.start_trace = boom
    prof.arm(2)
    prof.on_batch_start()       # must swallow the failure
    assert not prof.busy
    assert prof.snapshot()["last_error"] is not None


# ---------------------------------------------------------------------------
# pool utilization


def test_pool_utilization_busy_ratio_window():
    clock = [100.0]
    pool = PoolUtilization(window_s=10.0, clock=lambda: clock[0])
    with pool.track():
        clock[0] += 2.0         # one 2 s call inside a 10 s window
    assert pool.busy_ratio() == pytest.approx(0.2)
    clock[0] += 20.0            # interval ages out of the window
    assert pool.busy_ratio() == pytest.approx(0.0)


# ---------------------------------------------------------------------------
# HTTP surface: /debug/plans, /debug/flightrecorder, Server-Timing split


def _params(tmp_path, **extra):
    base = {
        "tmp_dir": str(tmp_path / "tmp"),
        "upload_dir": str(tmp_path / "uploads"),
        "batch_deadline_ms": 1.0,
        "debug": True,
    }
    base.update(extra)
    return AppParameters(base)


def _serve(tmp_path, coro_fn, **params_extra):
    from flyimg_tpu.service.app import make_app

    async def go():
        app = make_app(_params(tmp_path, **params_extra))
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            return await coro_fn(client)
        finally:
            await client.close()

    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(go())
    finally:
        loop.close()


@pytest.fixture()
def source_png(tmp_path):
    rng = np.random.default_rng(11)
    img = rng.integers(0, 255, (64, 80, 3), dtype=np.uint8)
    path = tmp_path / "source.png"
    path.write_bytes(encode(img, "png"))
    return str(path)


def test_debug_plans_reports_costed_entry_after_render(
    tmp_path, source_png
):
    """Acceptance: /debug/plans reports per-plan FLOPs / bytes / peak
    memory / compile time / cumulative device seconds on a real render
    (the CPU backend DOES provide cost analysis on this jax)."""

    async def scenario(client):
        resp = await client.get(f"/upload/w_40,h_30,o_png/{source_png}")
        assert resp.status == 200
        return await (await client.get("/debug/plans")).json()

    doc = _serve(tmp_path, scenario)
    launched = [
        row for row in doc["plans"]
        if row["launches"] >= 1 and row["costed"]
        and (row["descriptor"] or {}).get("batch")
    ]
    assert launched, doc["plans"]
    row = launched[0]
    assert row["flops"] > 0 and row["bytes_accessed"] > 0
    assert row["peak_memory_bytes"] > 0
    assert row["compile_s"] is not None and row["compile_s"] > 0
    assert row["device_s"] > 0 and row["images"] >= 1
    assert row["flops_executed"] == pytest.approx(
        row["flops"] * row["launches"]
    )
    assert doc["aggregates"]["entries"] >= 1
    assert doc["program_cache"]["batched"]["entries"] >= 1


def test_debug_flightrecorder_launch_joins_plans_and_split(
    tmp_path, source_png
):
    async def scenario(client):
        resp = await client.get(f"/upload/w_36,h_28,o_png/{source_png}")
        assert resp.status == 200
        fr = await (await client.get("/debug/flightrecorder")).json()
        plans = await (await client.get("/debug/plans")).json()
        return resp.headers.get("Server-Timing", ""), fr, plans

    server_timing, fr, plans = _serve(tmp_path, scenario)
    launches = [
        r for r in fr["records"]
        if r["kind"] == "primary" and r["controller"] == "device"
    ]
    assert launches
    launch = launches[0]
    for field in ("assemble_s", "slot_wait_s", "h2d_s", "dispatch_s",
                  "run_s", "sync_s", "device_s", "assemble_cpu_s",
                  "h2d_cpu_s"):
        assert launch[field] is not None and launch[field] >= 0.0, field
    # device_s is dispatch -> completed read-back: h2d's part after the
    # dispatch call began (at most all of it), the run and the read-back
    assert launch["run_s"] + launch["sync_s"] <= launch["device_s"] + 1e-6
    assert launch["device_s"] <= (
        launch["h2d_s"] + launch["run_s"] + launch["sync_s"] + 1e-6
    )
    assert launch["compile_hit"] in (True, False)
    assert launch["occupancy"] >= 1 and launch["capacity"] >= 1
    # the record's plan key joins the cost ledger
    assert launch["plan_key"] in {row["key"] for row in plans["plans"]}
    # and the split reaches the response's Server-Timing header
    for entry in ("device_h2d;dur=", "device_dispatch;dur=",
                  "device_run;dur=", "device_sync;dur="):
        assert entry in server_timing, server_timing


def test_observatory_endpoints_404_when_debug_off(tmp_path, source_png):
    async def scenario(client):
        resp = await client.get(f"/upload/w_22,o_png/{source_png}")
        assert resp.status == 200
        out = {}
        for path in ("/debug/plans", "/debug/flightrecorder",
                     "/debug/profile"):
            out[path] = (await client.get(path)).status
        out["profile_post"] = (
            await client.post("/debug/profile?batches=1")
        ).status
        return out

    statuses = _serve(tmp_path, scenario, debug=False)
    assert all(status == 404 for status in statuses.values()), statuses


def test_forced_breach_dumps_flightrecorder(tmp_path, source_png):
    """Acceptance: a forced SLO breach produces a flight-recorder dump
    artifact that is retrievable (file on disk + inventory row)."""
    dump_dir = tmp_path / "dumps"

    async def scenario(client):
        resp = await client.get(f"/upload/w_24,h_18,o_png/{source_png}")
        assert resp.status == 200
        return await (await client.get("/debug/flightrecorder")).json()

    doc = _serve(
        tmp_path, scenario,
        # impossible objective: the first (cold-compile) request is
        # "slow", and one slow request in an empty window burns 100x
        # budget in both windows -> edge-triggered breach -> dump
        slo_latency_p99_ms=0.001,
        flightrecorder_dump_dir=str(dump_dir),
    )
    files = glob.glob(str(dump_dir / "flightrecorder-*slo_breach.json"))
    assert files, "breach did not dump the flight recorder"
    with open(files[0]) as fh:
        dump = json.load(fh)
    assert dump["reason"] == "slo_breach"
    assert dump["summary"]["records"] >= 1
    # host_stage rows aside (a fetch or decode that waited 5 ms for its
    # pool worker, under load, lands in the ring before the first launch)
    launches = [
        record for record in dump["records"]
        if record["kind"] != "host_stage"
    ]
    assert launches[0]["controller"] in ("device", "codec")
    assert dump["context"].get("event") == "slo.breach"
    assert files[0].split(os.sep)[-1] in doc["dumps"]["files"]


def test_metrics_carry_observatory_families(tmp_path, source_png):
    async def scenario(client):
        resp = await client.get(f"/upload/w_26,o_png/{source_png}")
        assert resp.status == 200
        return await (await client.get("/metrics")).text()

    text = _serve(tmp_path, scenario)
    for family in (
        "flyimg_plan_entries",
        "flyimg_plan_compile_seconds",
        "flyimg_plan_flops_executed",
        "flyimg_program_cache_entries",
        "flyimg_device_transfer_seconds_bucket",
        "flyimg_device_dispatch_seconds_bucket",
        "flyimg_device_run_seconds_bucket",
        "flyimg_batch_assemble_seconds_bucket",
        "flyimg_batch_slot_wait_seconds_bucket",
        "flyimg_batch_resolve_seconds_bucket",
        "flyimg_host_pool_busy_ratio",
        "flyimg_decode_bytes_total",
        "flyimg_encode_bytes_total",
    ):
        assert family in text, family
    # the transfer family carries both directions
    assert 'flyimg_device_transfer_seconds_bucket{direction="h2d"' in text
    assert 'flyimg_device_transfer_seconds_bucket{direction="d2h"' in text
