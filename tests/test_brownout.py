"""Graceful-degradation tests: the brownout hysteresis state machine,
stale-while-revalidate coalescing, negative origin caching, hedged
storage reads, and the default-off byte-identity guarantee — all under
the deterministic fault harness (``brownout.signal`` pressure injection,
``storage.read_delay`` latency injection) and injectable clocks; no
sleeping out real dwell windows, no real network.

Acceptance behaviors pinned here (ISSUE 5):
- brownout_enable=false (the default) serves byte-identical responses
  with no new headers,
- the full hysteresis cycle: pressure up -> escalate immediately (gauge +
  events observed), degraded responses carry X-Flyimg-Degraded / stale
  markers, pressure down -> de-escalate one level at a time only after
  the dwell AND under the hysteresis gap (no flapping),
- N concurrent stale hits for one key = N immediate stale responses and
  exactly ONE background re-render,
- a negative-cached origin answers a fast 502 without re-fetching,
- with a slow-primary storage.read_delay fault, hedged cache-hit reads
  stay within ~2x the hedge delay instead of the injected latency.
"""

import asyncio
import os
import threading
import time

import httpx
import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from flyimg_tpu.appconfig import AppParameters
from flyimg_tpu.codecs import encode
from flyimg_tpu.runtime.brownout import (
    BROWNOUT,
    DEGRADED,
    NORMAL,
    SHED,
    BrownoutEngine,
    NegativeCache,
    RefreshQueue,
)
from flyimg_tpu.runtime.metrics import MetricsRegistry
from flyimg_tpu.storage.local import LocalStorage
from flyimg_tpu.testing import faults


@pytest.fixture(autouse=True)
def _clean_faults():
    yield
    faults.clear()


class FakeClock:
    def __init__(self, start: float = 1000.0) -> None:
        self.now = start

    def __call__(self) -> float:
        return self.now

    def advance(self, s: float) -> None:
        self.now += s


def _engine(clock=None, **over) -> BrownoutEngine:
    kw = dict(
        enabled=True, degraded_at=0.6, brownout_at=0.85, shed_at=1.1,
        hysteresis=0.75, min_dwell_s=5.0, eval_interval_s=0.0,
        metrics=MetricsRegistry(),
    )
    kw.update(over)
    return BrownoutEngine(clock=clock or FakeClock(), **kw)


def _inject_pressure(value_box):
    injector = faults.install(faults.FaultInjector())
    injector.plan("brownout.signal", lambda **_: value_box[0])
    return injector


def _png_bytes(w=40, h=30, seed=3) -> bytes:
    rng = np.random.default_rng(seed)
    return encode(rng.integers(0, 255, (h, w, 3), dtype=np.uint8), "png")


# ---------------------------------------------------------------------------
# engine state machine


def test_engine_disabled_never_leaves_normal():
    eng = _engine(enabled=False)
    box = [5.0]
    _inject_pressure(box)
    assert eng.evaluate() == NORMAL
    assert not eng.swr_active()
    assert not eng.plan_degrade_active()
    assert not eng.shed_active()


def test_escalation_is_immediate_and_ordered():
    clock = FakeClock()
    eng = _engine(clock)
    box = [0.0]
    _inject_pressure(box)
    assert eng.evaluate() == NORMAL
    box[0] = 0.7
    assert eng.evaluate() == DEGRADED
    box[0] = 0.9
    assert eng.evaluate() == BROWNOUT
    box[0] = 2.0
    assert eng.evaluate() == SHED
    # straight to the top from NORMAL too
    eng2 = _engine(clock)
    box[0] = 5.0
    assert eng2.evaluate() == SHED


def test_deescalation_needs_dwell_and_hysteresis_gap():
    clock = FakeClock()
    eng = _engine(clock)
    box = [0.9]
    _inject_pressure(box)
    assert eng.evaluate() == BROWNOUT
    # pressure collapses instantly — but the dwell has not elapsed
    box[0] = 0.0
    assert eng.evaluate() == BROWNOUT
    clock.advance(5.1)
    # in the hysteresis gap (brownout_at * 0.75 = 0.6375): must HOLD
    box[0] = 0.7
    assert eng.evaluate() == BROWNOUT
    # clearly under the gap: one level per evaluation, dwell resets
    box[0] = 0.1
    assert eng.evaluate() == DEGRADED
    assert eng.evaluate() == DEGRADED  # dwell at DEGRADED not elapsed
    clock.advance(5.1)
    assert eng.evaluate() == NORMAL
    assert eng.snapshot()["transitions_total"] == 3


def test_idle_gap_walks_level_all_the_way_down():
    """A level must not latch across a quiet period: after an idle gap
    covering several dwell windows, ONE evaluation (a scrape or the
    first returning request) walks the level back to the target instead
    of serving the first post-idle requests degraded."""
    clock = FakeClock()
    eng = _engine(clock)
    box = [2.0]
    _inject_pressure(box)
    assert eng.evaluate() == SHED
    box[0] = 0.0
    clock.advance(3600.0)  # a quiet hour: many dwell windows of credit
    assert eng.evaluate() == NORMAL
    # the /metrics gauge is evaluate-driven, so a scrape alone refreshes
    metrics = MetricsRegistry()
    eng2 = _engine(clock, metrics=metrics)
    eng2.register_metrics(metrics)
    box[0] = 2.0
    eng2.evaluate()
    box[0] = 0.0
    clock.advance(3600.0)
    assert "flyimg_brownout_level 0" in metrics.render_prometheus()


def test_no_flapping_at_a_threshold_boundary():
    """Pressure oscillating tightly around the entry threshold causes ONE
    escalation and no bouncing."""
    clock = FakeClock()
    eng = _engine(clock)
    box = [0.61]
    _inject_pressure(box)
    levels = []
    for i in range(40):
        box[0] = 0.61 if i % 2 == 0 else 0.58  # straddles degraded_at=0.6
        levels.append(eng.evaluate())
        clock.advance(1.0)
    assert levels[0] == DEGRADED
    assert set(levels) == {DEGRADED}  # 0.58 > 0.6*0.75: inside the gap
    assert eng.snapshot()["transitions_total"] == 1


def test_transition_metrics_gauge_and_log(caplog):
    import logging

    clock = FakeClock()
    metrics = MetricsRegistry()
    eng = _engine(clock, metrics=metrics)
    eng.register_metrics(metrics)
    box = [1.5]
    _inject_pressure(box)
    with caplog.at_level(logging.INFO, logger="flyimg.brownout"):
        eng.evaluate()
    text = metrics.render_prometheus()
    assert "flyimg_brownout_level 3" in text
    summary = metrics.summary()
    assert summary['flyimg_brownout_transitions_total{to="shed"}'] == 1
    # the structured transition log line rode along
    records = [
        r for r in caplog.records if r.name == "flyimg.brownout"
    ]
    assert records and records[0].to_level == "shed"
    assert records[0].pressure == 1.5


def test_components_pressure_from_attached_sources():
    class FakeBatcher:
        name = "device"

        class admission:
            pending = 32

    eng = _engine(FakeClock(), queue_ref=64.0)
    eng.attach(batchers=(FakeBatcher(),))
    assert eng.pressure() == pytest.approx(0.5)


def test_inflight_gauge_signal_is_sampled_live():
    """The inflight signal must sample the Gauge at each evaluation (a
    Gauge.value PROPERTY read captured at attach time would freeze the
    signal — or crash — the first time the knob is enabled), and a
    broken source degrades to no-signal, never a per-request error."""
    from flyimg_tpu.runtime.metrics import Gauge

    gauge = Gauge("g", "")
    eng = _engine(FakeClock(), inflight_ref=10.0)
    eng.attach(inflight_fn=lambda: gauge.value)
    assert eng.pressure() == 0.0
    gauge.inc(5)
    assert eng.pressure() == pytest.approx(0.5)

    def broken():
        raise RuntimeError("dead gauge")

    eng.attach(inflight_fn=broken)
    assert eng.pressure() == 0.0  # degraded to no-signal, no raise


# ---------------------------------------------------------------------------
# NegativeCache


def test_negative_cache_ttl_and_keying():
    clock = FakeClock()
    cache = NegativeCache(10.0, clock=clock)
    url = "http://origin.example.com/img.jpg?v=1"
    assert cache.hit(url) is None
    # ORIGIN scope (connect-level failure: nothing reached the host):
    # query strings must not bypass the table; userinfo is stripped
    cache.add(url, "ConnectError")
    assert cache.hit("http://u:p@origin.example.com/img.jpg?v=2") == (
        "ConnectError"
    )
    assert cache.hit("http://origin.example.com/other.jpg") is None
    clock.advance(10.1)
    assert cache.hit(url) is None  # expired
    assert len(cache) == 0


def test_negative_cache_resource_scope_spares_query_siblings():
    """A RESOURCE-level failure (the origin answered: 5xx on one ?id=)
    must not poison every other id on the same host+path endpoint."""
    clock = FakeClock()
    cache = NegativeCache(10.0, clock=clock)
    cache.add(
        "http://cdn.example.com/render?id=broken", "ReadTimeout",
        resource=True,
    )
    assert cache.hit("http://cdn.example.com/render?id=broken") == (
        "ReadTimeout"
    )
    # healthy sibling ids on the same endpoint are untouched
    assert cache.hit("http://cdn.example.com/render?id=healthy") is None
    assert cache.hit("http://cdn.example.com/render") is None
    # an origin-scope entry still covers every query of the path
    cache.add("http://cdn.example.com/render?id=x", "ConnectError")
    assert cache.hit("http://cdn.example.com/render?id=healthy") == (
        "ConnectError"
    )


def test_negative_cache_disabled_and_bounded():
    off = NegativeCache(0.0)
    off.add("http://x/y", "e")
    assert off.hit("http://x/y") is None
    clock = FakeClock()
    cache = NegativeCache(100.0, max_entries=4, clock=clock)
    for i in range(10):
        clock.advance(0.01)
        cache.add(f"http://h{i}/p", "e")
    assert len(cache) <= 4
    # the newest entry survived the oldest-expiry eviction
    assert cache.hit("http://h9/p") == "e"


# ---------------------------------------------------------------------------
# RefreshQueue


def test_refresh_queue_coalesces_and_bounds():
    q = RefreshQueue(max_pending=2)
    gate = threading.Event()
    ran = []

    def slow(key):
        def fn():
            gate.wait(timeout=10)
            ran.append(key)
        return fn

    assert q.submit("a", slow("a"))
    assert not q.submit("a", slow("a"))  # coalesced: key in flight
    assert q.submit("b", slow("b"))
    assert not q.submit("c", slow("c"))  # over the bound: dropped
    gate.set()
    for _ in range(200):
        if len(ran) == 2:
            break
        time.sleep(0.02)
    assert sorted(ran) == ["a", "b"]
    # the key frees after the refresh completes
    for _ in range(200):
        if q.submit("a", lambda: None):
            break
        time.sleep(0.02)
    else:
        pytest.fail("key never freed after refresh")


# ---------------------------------------------------------------------------
# hedged storage reads


def test_hedged_read_bounds_slow_primary(tmp_path):
    params = AppParameters({"upload_dir": str(tmp_path / "u")})
    storage = LocalStorage(params)
    storage.hedge_delay_s = 0.05
    storage.metrics = MetricsRegistry()
    storage.write("key.png", b"payload-bytes")

    injector = faults.install(faults.FaultInjector())
    injector.plan(
        "storage.read_delay",
        lambda attempt=0, **_: time.sleep(0.5) if attempt == 0 else None,
    )
    durations = []
    for _ in range(8):
        t0 = time.perf_counter()
        content, stat = storage.fetch_hedged("key.png")
        durations.append(time.perf_counter() - t0)
        assert content == b"payload-bytes"
        assert stat.mtime is not None
    # every read resolved via the backup in ~hedge_delay, nowhere near
    # the injected 0.5 s primary latency ("p99 within ~2x the delay" —
    # generous headroom for CI thread-start jitter)
    assert max(durations) < 0.3, durations
    summary = storage.metrics.summary()
    assert summary["flyimg_storage_hedges_total"] == 8
    assert (
        summary['flyimg_storage_hedged_reads_total{winner="backup"}'] == 8
    )


def test_hedged_read_primary_wins_without_fault(tmp_path):
    params = AppParameters({"upload_dir": str(tmp_path / "u")})
    storage = LocalStorage(params)
    storage.hedge_delay_s = 0.25
    storage.metrics = MetricsRegistry()
    storage.write("key.png", b"bytes")
    content, _stat = storage.fetch_hedged("key.png")
    assert content == b"bytes"
    assert "flyimg_storage_hedges_total" not in storage.metrics.summary()
    # absent entries still answer None through the hedged path
    assert storage.fetch_hedged("missing.png") is None


def test_hedge_disabled_is_plain_fetch(tmp_path):
    params = AppParameters({"upload_dir": str(tmp_path / "u")})
    storage = LocalStorage(params)
    storage.write("key.png", b"bytes")
    assert storage.hedge_delay_s == 0.0
    content, _stat = storage.fetch_hedged("key.png")
    assert content == b"bytes"


# ---------------------------------------------------------------------------
# HTTP end to end


def _params(tmp_path, **extra):
    base = {
        "tmp_dir": str(tmp_path / "tmp"),
        "upload_dir": str(tmp_path / "uploads"),
        "batch_deadline_ms": 1.0,
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
    path = tmp_path / "source.png"
    path.write_bytes(_png_bytes(80, 64, seed=11))
    return str(path)


BROWNOUT_HEADERS = ("X-Flyimg-Degraded", "Warning")


def test_default_off_is_byte_identical_with_no_new_headers(
    tmp_path, source_png
):
    """The default-off acceptance gate: the same request matrix through
    the default config and through brownout_enable=false under INJECTED
    overload pressure yields byte-identical bodies and the same header
    names — no brownout header ever appears."""
    matrix = [
        f"/upload/w_32,o_png/{source_png}",
        f"/upload/w_24,h_24,c_1,o_jpg,q_85/{source_png}",
        f"/upload/w_20,r_90,o_png/{source_png}",
    ]

    async def scenario(client):
        out = []
        for url in matrix:
            first = await client.get(url)   # miss
            second = await client.get(url)  # hit
            out.append(
                (
                    first.status, await first.read(),
                    tuple(sorted(first.headers)),
                    second.status, await second.read(),
                    tuple(sorted(second.headers)),
                )
            )
        return out

    baseline = _serve(tmp_path / "a", scenario)

    # same matrix, knob explicitly false, pressure slammed to overload:
    # the engine must never engage and nothing may differ
    injector = faults.FaultInjector()
    injector.plan("brownout.signal", lambda **_: 5.0)
    off = _serve(
        tmp_path / "b", scenario,
        brownout_enable=False, fault_injector=injector,
    )
    assert off == baseline
    for row in off:
        for names in (row[2], row[5]):
            for header in BROWNOUT_HEADERS:
                assert header not in names


def test_http_hysteresis_cycle_with_markers(tmp_path, source_png):
    """The full fault-injected overload cycle: escalate (gauge observed),
    stale + degraded markers on responses, de-escalate without flapping
    under the injectable clock."""
    clock = FakeClock()
    box = [0.0]
    injector = faults.FaultInjector()
    injector.plan("brownout.signal", lambda **_: box[0])

    async def scenario(client):
        async def gauge():
            text = await (await client.get("/metrics")).text()
            for line in text.splitlines():
                if line.startswith("flyimg_brownout_level "):
                    return float(line.rsplit(" ", 1)[1])
            return None

        url = f"/upload/w_32,o_png,sh_2/{source_png}"
        # 1) populate the cache under NORMAL
        warm = await client.get(url)
        assert warm.status == 200
        fresh_bytes = await warm.read()
        assert "X-Flyimg-Degraded" not in warm.headers
        assert await gauge() == 0.0

        # 2) age the cached output past the stale TTL
        updir = os.path.join(str(tmp_path), "uploads")
        for name in os.listdir(updir):
            old = time.time() - 3600
            os.utime(os.path.join(updir, name), (old, old))

        # 3) overload: escalate to BROWNOUT; the aged hit serves stale
        box[0] = 0.9
        stale = await client.get(url)
        assert stale.status == 200
        assert await stale.read() == fresh_bytes  # stale = the old bytes
        assert "stale" in stale.headers["X-Flyimg-Degraded"]
        assert stale.headers["Warning"].startswith("110")
        assert await gauge() == 2.0
        # the transition's span event landed on the REQUEST that
        # triggered it (evaluate runs inside the trace activation)
        trace_id = stale.headers["traceparent"].split("-")[1]
        tree = await (
            await client.get(f"/debug/traces/{trace_id}")
        ).json()
        def walk(spans):
            for span in spans:
                yield from (e["name"] for e in span.get("events", []))
                yield from walk(span.get("children", []))

        events = list(walk(tree["spans"]))
        assert "brownout.transition" in events
        assert "brownout.stale_hit" in events

        # 4) a MISS under BROWNOUT renders degraded (plan rewrite tag)
        miss = await client.get(
            f"/upload/w_30,o_jpg,q_90,sh_2/{source_png}"
        )
        assert miss.status == 200
        tags = miss.headers["X-Flyimg-Degraded"].split(",")
        assert "refine" in tags and "quality" in tags
        assert "max-age=60" in miss.headers["Cache-Control"]

        # 5) pressure drops: holds through the dwell, then steps down
        #    one level per elapsed dwell window — never straight to
        #    NORMAL while the credit covers only one step
        box[0] = 0.0
        assert await gauge() == 2.0  # dwell not elapsed: no de-escalation
        clock.advance(6.0)  # one dwell window (5s) of credit
        await client.get(url)
        assert await gauge() == 1.0
        clock.advance(6.0)
        await client.get(url)
        assert await gauge() == 0.0

        # 6) back to NORMAL: fresh-enough hits carry no markers
        normal = await client.get(
            f"/upload/w_30,o_jpg,q_90,sh_2/{source_png}"
        )
        assert "X-Flyimg-Degraded" not in normal.headers
        return True

    assert _serve(
        tmp_path, scenario,
        brownout_enable=True,
        brownout_clock=clock,
        brownout_min_dwell_s=5.0,
        brownout_stale_ttl_s=300.0,
        fault_injector=injector,
        debug=True,  # /debug/traces for the span-event assertion
    )


def test_http_shed_level_rejects_misses_serves_hits(tmp_path, source_png):
    box = [0.0]
    injector = faults.FaultInjector()
    injector.plan("brownout.signal", lambda **_: box[0])

    async def scenario(client):
        url = f"/upload/w_32,o_png/{source_png}"
        warm = await client.get(url)
        assert warm.status == 200
        box[0] = 5.0  # SHED
        hit = await client.get(url)  # fresh cache hit still serves
        assert hit.status == 200
        miss = await client.get(f"/upload/w_33,o_png/{source_png}")
        body = await miss.text()
        return miss.status, dict(miss.headers), body

    status, headers, body = _serve(
        tmp_path, scenario,
        brownout_enable=True,
        brownout_clock=FakeClock(),
        shed_retry_after_s=2.0,
        fault_injector=injector,
    )
    assert status == 503
    assert headers["Retry-After"] == "2"
    assert "brownout" in body


def test_http_negative_cached_origin_fast_502(tmp_path):
    injector = faults.FaultInjector()
    injector.plan(
        "fetch.http",
        lambda **_: (_ for _ in ()).throw(httpx.ConnectError("down")),
    )

    async def scenario(client):
        url = "/upload/w_20,o_png/http://dead.example.com/img.png"
        first = await client.get(url)
        fired_after_first = injector.fired.get("fetch.http", 0)
        t0 = time.perf_counter()
        second = await client.get(url)
        elapsed = time.perf_counter() - t0
        return (
            first.status, second.status, await second.text(), elapsed,
            injector.fired.get("fetch.http", 0) - fired_after_first,
        )

    first_status, second_status, body, elapsed, extra_fetches = _serve(
        tmp_path, scenario,
        negative_cache_ttl_s=60.0,
        retry_max_attempts=1,
    )
    assert first_status == 404  # the failing fetch maps as before
    assert second_status == 502
    assert "OriginUnavailableException" in body
    assert extra_fetches == 0  # short-circuited: no new fetch attempt
    assert elapsed < 1.0


# ---------------------------------------------------------------------------
# stale-while-revalidate coalescing (handler-level for determinism)


def test_swr_coalesces_n_stale_hits_into_one_refresh(tmp_path, source_png):
    from flyimg_tpu.service.handler import ImageHandler

    injector = faults.install(faults.FaultInjector())
    # the harness counts firings only for points with a plan installed —
    # this is the render counter. The plan HOLDS the one refresh until
    # all six hits have been served: a key coalesces while it is queued
    # or refreshing, and on a loaded machine the re-render of this tiny
    # image used to finish (entry rewritten, key released) before the
    # last hit threads had started — a late hit then read a fresh entry,
    # or queued a second refresh (the whole-run flake)
    served = threading.Event()

    def held_refresh(**_):
        served.wait(timeout=60)
        return faults.PASS

    injector.plan("brownout.refresh", held_refresh)
    metrics = MetricsRegistry()
    params = _params(tmp_path)
    engine = BrownoutEngine(
        enabled=True, stale_ttl_s=60.0, metrics=metrics,
        refresh_max_pending=8,
    )
    engine._level = DEGRADED  # pinned: this test is about SWR, not levels
    storage = LocalStorage(params)
    handler = ImageHandler(
        storage, params, metrics=metrics, brownout=engine
    )

    # populate + age the cache entry
    first = handler.process_image("w_32,o_png", source_png)
    assert not first.stale
    old = time.time() - 3600
    path = os.path.join(storage.root, first.spec.name)
    os.utime(path, (old, old))

    results = []
    errors = []

    def hit():
        try:
            results.append(handler.process_image("w_32,o_png", source_png))
        except Exception as exc:  # pragma: no cover - fails the assert
            errors.append(exc)

    threads = [threading.Thread(target=hit) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    served.set()
    assert not errors
    assert len(results) == 6
    # every hit served immediately from the stale entry
    assert all(r.stale and r.from_cache for r in results)
    assert all(r.content == first.content for r in results)
    # ... and exactly ONE background re-render ran
    for _ in range(300):
        if engine.refresh.stats()["pending"] == 0:
            break
        time.sleep(0.02)
    assert injector.fired.get("brownout.refresh", 0) == 1
    # the refresh rewrote the entry: it is fresh again
    after = handler.process_image("w_32,o_png", source_png)
    assert not after.stale
    assert metrics.summary()['flyimg_degraded_total{mode="stale"}'] == 6
