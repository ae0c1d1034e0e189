"""SLO engine: window/burn-rate math under a fake clock (breach,
recovery, multi-window agreement), the flyimg_slo_* gauge surface, the
debug-gated /debug/slo + /debug/perf endpoints, and the acceptance
scenario — a fault-forced breach whose burn gauge flips and whose
structured breach log carries a trace id retrievable from /debug/traces
(ISSUE 4)."""

import asyncio
import logging
import math

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from flyimg_tpu.appconfig import AppParameters
from flyimg_tpu.codecs import encode
from flyimg_tpu.runtime.metrics import BUCKET_BOUNDS, MetricsRegistry
from flyimg_tpu.runtime.slo import SLO_LOGGER, SloEngine
from flyimg_tpu.testing import faults


class FakeClock:
    def __init__(self, t: float = 1000.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, seconds: float) -> None:
        self.t += seconds


def _engine(clock, **kw):
    defaults = dict(
        latency_p99_ms=100.0,
        availability=99.0,       # 1% error budget
        latency_quantile=0.99,   # 1% latency budget
        window_fast_s=60.0,
        window_slow_s=600.0,
        burn_threshold_fast=10.0,
        burn_threshold_slow=2.0,
        clock=clock,
    )
    defaults.update(kw)
    return SloEngine(**defaults)


# ---------------------------------------------------------------------------
# unit: burn-rate math under a fake clock


def test_error_burn_rate_matches_hand_computation():
    clk = FakeClock()
    eng = _engine(clk)
    for _ in range(90):
        eng.record(0.010, ok=True)
    for _ in range(10):
        eng.record(0.010, ok=False)
    # 10 bad / 100 total = 0.10 error fraction; budget 0.01 -> burn 10.0
    assert eng.burn_rate("fast") == pytest.approx(10.0)
    assert eng.burn_rate("slow") == pytest.approx(10.0)


def test_latency_burn_rate_counts_slow_requests():
    clk = FakeClock()
    eng = _engine(clk)
    for _ in range(95):
        eng.record(0.010, ok=True)     # under the 100 ms objective
    for _ in range(5):
        eng.record(0.500, ok=True)     # slow but successful
    # 5 slow / 100 = 0.05; latency budget 0.01 -> burn 5.0 (errors: 0)
    assert eng.burn_rate("fast") == pytest.approx(5.0)
    doc = eng.snapshot()["windows"]["fast"]
    assert doc["error_burn"] == pytest.approx(0.0)
    assert doc["latency_burn"] == pytest.approx(5.0)
    assert doc["burn_rate"] == pytest.approx(5.0)


def test_burn_rate_is_worse_of_error_and_latency():
    clk = FakeClock()
    eng = _engine(clk)
    for _ in range(96):
        eng.record(0.010, ok=True)
    for _ in range(2):
        eng.record(0.500, ok=True)    # latency burn 2/98... then errors:
    for _ in range(2):
        eng.record(0.010, ok=False)
    # 100 total: errors 2 -> burn 2.0; slow 2 -> burn 2.0; equal here,
    # add one more slow to tip the latency side
    eng.record(0.500, ok=True)
    doc = eng.snapshot()["windows"]["fast"]
    assert doc["burn_rate"] == pytest.approx(doc["latency_burn"])
    assert doc["latency_burn"] > doc["error_burn"]


def test_window_expiry_recovers_fast_before_slow():
    clk = FakeClock()
    eng = _engine(clk)
    for _ in range(10):
        eng.record(0.010, ok=False)   # 100% errors -> burn 100
    assert eng.burn_rate("fast") == pytest.approx(100.0)
    # past the fast window (+ one slice of slack for bucket granularity):
    # fast burn collapses to 0, slow window still remembers
    clk.advance(60.0 + eng._slice_s)
    for _ in range(100):
        eng.record(0.010, ok=True)
    assert eng.burn_rate("fast") == pytest.approx(0.0)
    assert eng.burn_rate("slow") > 0.0
    # past the slow window too: everything forgotten
    clk.advance(600.0 + eng._slice_s)
    assert eng.burn_rate("slow") == pytest.approx(0.0)


def test_multi_window_agreement_gates_breach(caplog):
    """Fast burn alone must NOT breach (blip suppression); fast AND slow
    over threshold must (and must log exactly one structured line)."""
    clk = FakeClock()
    eng = _engine(clk, burn_threshold_fast=10.0, burn_threshold_slow=50.0)
    with caplog.at_level(logging.ERROR, logger=SLO_LOGGER):
        # 20% errors: fast burn 20 (> 10) but slow threshold is 50 -> no
        for _ in range(80):
            eng.record(0.010, ok=True)
        for _ in range(20):
            eng.record(0.010, ok=False)
        assert eng.burn_rate("fast") == pytest.approx(20.0)
        assert not eng.breached
        assert not caplog.records
        # crank errors until both windows agree
        for _ in range(150):
            eng.record(0.010, ok=False)
    assert eng.breached
    breach_logs = [r for r in caplog.records if r.levelno >= logging.ERROR]
    assert len(breach_logs) == 1  # edge-triggered, not per-request
    assert breach_logs[0].burn_rate_fast > 10.0


def test_breach_recovery_is_edge_triggered(caplog):
    clk = FakeClock()
    eng = _engine(clk)
    with caplog.at_level(logging.INFO, logger=SLO_LOGGER):
        for _ in range(20):
            eng.record(0.010, ok=False)
        assert eng.breached
        clk.advance(700.0)  # everything expires
        eng.record(0.010, ok=True)
        assert not eng.breached
    events = [getattr(r, "event", None) for r in caplog.records]
    assert events.count("slo.breach") == 1
    assert events.count("slo.recovered") == 1
    snap = eng.snapshot()
    assert snap["breaches_total"] == 1
    assert snap["breached"] is False


def test_window_p99_interpolates_like_the_metrics_histogram():
    """All samples at one value: windowed p99 must land inside that
    value's bucket at the interpolated 99% point — the hand-computable
    in-bucket rule runtime/metrics.Histogram also applies."""
    clk = FakeClock()
    eng = _engine(clk)
    value = 0.010
    for _ in range(200):
        eng.record(value, ok=True)
    idx = next(i for i, b in enumerate(BUCKET_BOUNDS) if value <= b)
    lo = BUCKET_BOUNDS[idx - 1] if idx else 0.0
    hi = BUCKET_BOUNDS[idx]
    expected = lo + (hi - lo) * 0.99
    assert eng.window_p99_s("fast") == pytest.approx(expected)
    assert eng.window_p99_s("slow") == pytest.approx(expected)


def test_error_budget_remaining_depletes_and_floors_at_zero():
    clk = FakeClock()
    eng = _engine(clk)
    assert eng.error_budget_remaining() == 1.0
    for _ in range(995):
        eng.record(0.010, ok=True)
    for _ in range(5):
        eng.record(0.010, ok=False)
    # 5/1000 errors against a 1% budget: half the budget consumed
    assert eng.error_budget_remaining() == pytest.approx(0.5)
    for _ in range(10):
        eng.record(0.010, ok=False)
    assert eng.error_budget_remaining() == 0.0


def test_disabled_engine_noops():
    clk = FakeClock()
    eng = _engine(clk, enabled=False)
    eng.record(5.0, ok=False)
    assert eng.burn_rate("fast") == 0.0
    assert eng.snapshot() == {"enabled": False}
    reg = MetricsRegistry()
    eng.register_metrics(reg)
    assert "flyimg_slo_burn_rate_fast" not in reg.render_prometheus()


def test_gauges_render_current_burn_on_scrape():
    clk = FakeClock()
    reg = MetricsRegistry()
    eng = _engine(clk, metrics=reg)
    eng.register_metrics(reg)
    for _ in range(10):
        eng.record(0.010, ok=False)
    text = reg.render_prometheus()
    line = next(
        l for l in text.splitlines()
        if l.startswith("flyimg_slo_burn_rate_fast ")
    )
    assert float(line.split()[1]) == pytest.approx(100.0)
    assert "flyimg_slo_breached 1" in text
    assert 'flyimg_slo_window_p99_ms{window="fast"}' in text
    # breach counter incremented exactly once (edge-triggered)
    assert "flyimg_slo_breaches_total 1" in text
    # the expired state reads back to 0 on the NEXT scrape, no new
    # request needed — the callbacks sample the clock at render time
    clk.advance(700.0)
    text = reg.render_prometheus()
    line = next(
        l for l in text.splitlines()
        if l.startswith("flyimg_slo_burn_rate_fast ")
    )
    assert float(line.split()[1]) == 0.0


def test_breached_reads_live_after_traffic_stops():
    """The breached gauge/debug state must fall back with the windows at
    READ time — not stay latched at the last record()'s verdict when
    traffic ceases (e.g. the LB drained the alerting instance)."""
    clk = FakeClock()
    reg = MetricsRegistry()
    eng = _engine(clk, metrics=reg)
    eng.register_metrics(reg)
    for _ in range(20):
        eng.record(0.010, ok=False)
    assert eng.breached
    clk.advance(700.0)  # windows drain; NO new request arrives
    assert not eng.breached
    assert eng.snapshot()["breached"] is False
    assert "flyimg_slo_breached 0" in reg.render_prometheus()
    assert eng.summary_fields()["breached"] == 0.0


def test_breach_trace_force_kept_past_tail_sampler():
    """The breach log names a trace id; that trace must survive the tail
    sampler at ANY sample rate, even when it is neither an error nor
    'slow' by the tracing threshold (200 ms against a 150 ms objective
    under a 500 ms slow bar)."""
    from flyimg_tpu.runtime.tracing import Trace, Tracer

    clk = FakeClock()
    eng = _engine(clk)
    tracer = Tracer(sample_rate=0.0, slow_threshold_s=30.0)
    trace = Trace()
    # one slow-but-successful sub-threshold request trips the breach
    # (1/1 slow = burn 100) with THIS trace as the trigger
    eng.record(0.200, ok=True, trace=trace)
    assert eng.breached
    assert eng.snapshot()["last_breach"]["trace_id"] == trace.trace_id
    assert tracer.finish(trace, "ok") == "forced"
    assert tracer.get(trace.trace_id) is not None


def test_record_overhead_is_bounded():
    """SLO bookkeeping rides every pipeline request; like the tracing
    no-op guard, the per-record cost must stay far under the <=2%
    cache-hit budget (loose bound — shared CI hosts jitter)."""
    import time as _time

    clk = FakeClock()
    eng = _engine(clk)
    n = 5_000
    t0 = _time.perf_counter()
    for _ in range(n):
        eng.record(0.010, ok=True)
    per_call_us = (_time.perf_counter() - t0) / n * 1e6
    assert per_call_us < 200.0, per_call_us


# ---------------------------------------------------------------------------
# HTTP: /debug/slo, /debug/perf, and the forced-breach acceptance path


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
    img = rng.integers(0, 255, (48, 64, 3), dtype=np.uint8)
    path = tmp_path / "slo-source.png"
    path.write_bytes(encode(img, "png"))
    return str(path)


def test_debug_slo_and_perf_404_when_debug_off(tmp_path):
    async def scenario(client):
        slo = await client.get("/debug/slo")
        perf = await client.get("/debug/perf")
        return slo.status, perf.status

    slo_status, perf_status = _serve(tmp_path, scenario, debug=False)
    assert slo_status == 404 and perf_status == 404


def test_debug_slo_reports_objective_and_windows(tmp_path, source_png):
    async def scenario(client):
        resp = await client.get(f"/upload/w_20,o_png/{source_png}")
        assert resp.status == 200
        return await (await client.get("/debug/slo")).json()

    doc = _serve(tmp_path, scenario)
    assert doc["enabled"] is True
    assert doc["objective"]["latency_p99_ms"] == 150.0
    assert doc["objective"]["availability_pct"] == 99.9
    for window in ("fast", "slow"):
        w = doc["windows"][window]
        assert w["requests"] >= 1
        assert "burn_rate" in w and "p99_ms" in w
    assert 0.0 <= doc["error_budget_remaining"] <= 1.0


def test_debug_perf_reports_controllers_and_stages(tmp_path, source_png):
    async def scenario(client):
        resp = await client.get(f"/upload/w_18,o_png/{source_png}")
        assert resp.status == 200
        return await (await client.get("/debug/perf")).json()

    doc = _serve(tmp_path, scenario)
    dev = doc["controllers"]["device"]
    assert dev["window_batches"] >= 1
    assert 0.0 < dev["mean_occupancy"] <= 1.0
    assert 0.0 <= dev["padding_waste"] < 1.0
    assert 0.0 <= dev["queue_wait_share"] <= 1.0
    assert "decode" in doc["stages"] and "device" in doc["stages"]
    assert doc["device"]["batches"] >= 1


def test_forced_breach_flips_gauge_and_logs_retrievable_trace(
    tmp_path, caplog
):
    """Acceptance: a fault-forced run of 5xx requests pushes
    flyimg_slo_burn_rate_fast above threshold, and the structured breach
    log carries a trace id that /debug/traces can serve."""
    injector = faults.FaultInjector()
    injector.plan(
        "batcher.execute",
        faults.poison_member(
            lambda **_ctx: True, lambda: ValueError("forced-slo-breach")
        ),
    )

    # real local source bytes, so every request reaches the poisoned
    # batcher (and 500s there) instead of dying at fetch as a 404
    rng = np.random.default_rng(3)
    png = encode(rng.integers(0, 255, (32, 40, 3), dtype=np.uint8), "png")
    src = tmp_path / "s.png"
    src.write_bytes(png)

    async def scenario(client):
        statuses = []
        for i in range(4):
            resp = await client.get(f"/upload/w_1{i},o_png/{src}")
            statuses.append(resp.status)
        metrics_text = await (await client.get("/metrics")).text()
        listing = await (await client.get("/debug/traces")).json()
        return statuses, metrics_text, listing

    with caplog.at_level(logging.ERROR, logger=SLO_LOGGER):
        statuses, metrics_text, listing = _serve(
            tmp_path, scenario,
            fault_injector=injector,
            resilience_bisect_enable=False,
            resilience_batch_retries=0,
        )
    assert all(s == 500 for s in statuses), statuses
    burn_line = next(
        l for l in metrics_text.splitlines()
        if l.startswith("flyimg_slo_burn_rate_fast ")
    )
    burn = float(burn_line.split()[1])
    assert burn > 14.4, burn_line  # above the default fast threshold
    assert "flyimg_slo_breached 1" in metrics_text
    breach_logs = [
        r for r in caplog.records
        if getattr(r, "event", None) == "slo.breach"
    ]
    assert breach_logs, "no structured breach log emitted"
    trace_id = breach_logs[0].trace_id
    assert trace_id, "breach log must carry the triggering trace id"
    # the triggering trace is an error: the tail sampler ALWAYS kept it
    kept_ids = {t["trace_id"] for t in listing["traces"]}
    assert trace_id in kept_ids


def test_summary_carries_slo_and_efficiency_fields(tmp_path, source_png):
    """The satellite contract: MetricsRegistry.summary() speaks the same
    efficiency/SLO vocabulary as /debug/perf and /debug/slo."""
    from flyimg_tpu.service import app as app_mod

    async def scenario(client):
        resp = await client.get(f"/upload/w_16,o_png/{source_png}")
        assert resp.status == 200
        registry = client.app[app_mod.METRICS_KEY]
        return registry.summary()

    summary = _serve(tmp_path, scenario)
    assert "slo:burn_rate_fast" in summary
    assert "slo:error_budget_remaining" in summary
    assert "batch_efficiency:device:padding_waste" in summary
    assert "batch_efficiency:device:queue_wait_share" in summary
    assert summary["batch_efficiency:device:padding_waste"] == pytest.approx(
        1.0 - summary["batch_efficiency:device:mean_occupancy"]
    )
    assert not math.isnan(summary["slo:burn_rate_fast"])
