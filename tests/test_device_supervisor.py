"""Backend supervisor (runtime/devicesupervisor.py; docs/resilience.md
"Backend failover"): storm-detection threshold math under an injectable
clock, failover draining without stranding futures, CPU-fallback render
parity, re-promotion hysteresis, readyz/fleet health gating, the
default-off byte identity, and the fleet routing-around-a-down-owner
behavior."""

from __future__ import annotations

import asyncio
import json
import os
import threading
import time

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from flyimg_tpu.appconfig import AppParameters
from flyimg_tpu.codecs import decode, encode
from flyimg_tpu.runtime.batcher import BatchController
from flyimg_tpu.runtime.devicesupervisor import (
    CPU_FALLBACK,
    DEVICE,
    DeviceSupervisor,
)
from flyimg_tpu.runtime.fleet import FleetRouter, rendezvous_owner
from flyimg_tpu.runtime.resilience import POISON, TRANSIENT
from flyimg_tpu.testing import faults


def _run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


class FakeClock:
    def __init__(self, start: float = 1000.0) -> None:
        self.now = start

    def __call__(self) -> float:
        return self.now

    def advance(self, seconds: float) -> None:
        self.now += seconds


class FakeBatcher:
    """Records failover_backend calls; the supervisor must never need
    more of the controller surface than this."""

    def __init__(self) -> None:
        self.calls = []
        self.drains = 0

    def failover_backend(self, mesh, *, drain_timeout_s, reason):
        self.calls.append((mesh, drain_timeout_s, reason))

    def drain_inflight(self, drain_timeout_s):
        # the supervisor drains BEFORE any backend switch (review pin)
        self.drains += 1

    def pause_launches(self):
        self.paused = True

    def resume_launches(self):
        self.paused = False


def _supervisor(clock, *, threshold=3, window_s=10.0, hysteresis=2,
                batcher=None, **kw):
    sup = DeviceSupervisor(
        enabled=True,
        storm_threshold=threshold,
        storm_window_s=window_s,
        probe_hysteresis=hysteresis,
        probe_interval_s=0.05,
        failover_drain_s=0.2,
        clock=clock,
        **kw,
    )
    # run the failover worker inline: the threshold-math tests must
    # observe the post-trip state synchronously
    sup._spawn = lambda target, name="t": target()
    # no background prober either — probes are driven explicitly
    sup._ensure_prober = lambda: None
    sup.attach(batcher=batcher or FakeBatcher(), mesh_factory=lambda: None)
    return sup


# ---------------------------------------------------------------------------
# storm-detection threshold math (injectable clock)


def test_storm_trips_at_threshold_within_window():
    clock = FakeClock()
    batcher = FakeBatcher()
    sup = _supervisor(clock, threshold=3, window_s=10.0, batcher=batcher)
    sup.record_batch_failure(TRANSIENT)
    sup.record_batch_failure(TRANSIENT)
    assert sup.state() == DEVICE  # one short of the threshold
    sup.record_batch_failure(TRANSIENT)
    assert sup.state() == CPU_FALLBACK
    assert sup.cpu_forced()
    # the failover rebuilt the batcher on a None (unsharded CPU) mesh
    assert batcher.calls == [(None, 0.2, "device_failover")]


def test_success_resets_the_consecutive_streak():
    clock = FakeClock()
    sup = _supervisor(clock, threshold=3)
    for _ in range(5):
        sup.record_batch_failure(TRANSIENT)
        sup.record_batch_success()  # a recovering backend is not a storm
    assert sup.state() == DEVICE


def test_failures_spread_past_the_window_do_not_trip():
    clock = FakeClock()
    sup = _supervisor(clock, threshold=3, window_s=10.0)
    sup.record_batch_failure(TRANSIENT)
    clock.advance(11.0)
    sup.record_batch_failure(TRANSIENT)
    clock.advance(11.0)
    # consecutive count says 3, but only ONE failure is inside the
    # window — a slow trickle is per-batch retry's job, not a storm
    sup.record_batch_failure(TRANSIENT)
    assert sup.state() == DEVICE
    # two more inside the window complete a real storm
    sup.record_batch_failure(TRANSIENT)
    sup.record_batch_failure(TRANSIENT)
    assert sup.state() == CPU_FALLBACK


def test_poison_failures_never_count():
    clock = FakeClock()
    sup = _supervisor(clock, threshold=2)
    for _ in range(10):
        sup.record_batch_failure(POISON)  # PR-3's problem, not a storm
    assert sup.state() == DEVICE


def test_disabled_supervisor_records_nothing():
    sup = DeviceSupervisor(enabled=False)
    for _ in range(10):
        sup.record_batch_failure(TRANSIENT)
    assert sup.state() == DEVICE
    assert not sup.cpu_forced()


# ---------------------------------------------------------------------------
# re-promotion hysteresis (scripted probes via the device.backend point)


def _scripted_probes(script):
    """Install a device.backend plan that pops verdicts off ``script``
    (True/False/raise); returns the injector for cleanup."""
    injector = faults.FaultInjector()

    def plan(**_ctx):
        verdict = script.pop(0)
        if isinstance(verdict, BaseException):
            raise verdict
        return verdict

    injector.plan("device.backend", plan)
    return faults.install(injector)


def test_repromotes_after_consecutive_clean_probes():
    clock = FakeClock()
    batcher = FakeBatcher()
    sup = _supervisor(clock, threshold=1, hysteresis=2, batcher=batcher)
    sup.record_batch_failure(TRANSIENT)
    assert sup.cpu_forced()
    _scripted_probes([False, True, True])
    try:
        assert sup.probe_and_handle() is False
        assert sup.cpu_forced()
        assert sup.probe_and_handle() is True
        assert sup.cpu_forced()  # one clean probe is not enough
        assert sup.probe_and_handle() is True
        assert not sup.cpu_forced()
        assert sup.state() == DEVICE
    finally:
        faults.clear()
    # failover + re-promotion each rebuilt the backend
    assert [c[2] for c in batcher.calls] == [
        "device_failover", "device_repromote",
    ]


def test_failed_probe_resets_the_clean_count():
    clock = FakeClock()
    sup = _supervisor(clock, threshold=1, hysteresis=2)
    sup.record_batch_failure(TRANSIENT)
    _scripted_probes([True, False, True, True])
    try:
        sup.probe_and_handle()   # clean 1
        sup.probe_and_handle()   # flap: reset
        sup.probe_and_handle()   # clean 1
        assert sup.cpu_forced()  # a flapping backend must not re-promote
        sup.probe_and_handle()   # clean 2 -> re-promote
        assert not sup.cpu_forced()
    finally:
        faults.clear()


def test_probe_exception_is_a_recorded_outcome_never_a_crash():
    from flyimg_tpu.runtime.metrics import MetricsRegistry

    clock = FakeClock()
    metrics = MetricsRegistry()
    sup = _supervisor(clock, threshold=1, metrics=metrics)
    sup.record_batch_failure(TRANSIENT)
    _scripted_probes([RuntimeError("backend init crashed")])
    try:
        assert sup.probe_and_handle() is False  # no raise
    finally:
        faults.clear()
    assert sup.snapshot()["probe"]["last_outcome"].startswith("error:")
    counter = metrics._counters.get(
        'flyimg_backend_probe_total{outcome="error"}'
    )
    assert counter is not None and counter.value == 1.0


def test_probe_uses_saved_selection_not_the_forced_cpu_env(monkeypatch):
    """Review pin: after a real failover forces JAX_PLATFORMS=cpu, the
    re-probe must test the SAVED selection — trusting the current env
    would read the cpu pin as 'trivially healthy' and re-promote the
    dead backend on the first probe (CPU<->dead-device flapping)."""
    from flyimg_tpu.parallel import mesh as mesh_mod

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")  # the post-failover env
    probed = {}

    def fake_probe(timeout_s, env_overrides=None):
        probed["env"] = env_overrides
        return False  # the device is still dead

    monkeypatch.setattr(mesh_mod, "probe_selected_backend", fake_probe)
    ok, detail = mesh_mod.probe_device_backend(
        5.0, selection={"JAX_PLATFORMS": "tpu", "XLA_FLAGS": None}
    )
    assert (ok, detail) == (False, "down")  # NOT the cpu short-circuit
    assert probed["env"] == {"JAX_PLATFORMS": "tpu", "XLA_FLAGS": None}
    # without a saved selection the env's cpu pin short-circuits as before
    ok, detail = mesh_mod.probe_device_backend(5.0)
    assert (ok, detail) == (True, "cpu")


def test_failover_backend_rejects_bad_mesh_before_mutating():
    """Review pin: a mesh without a 'data' axis must raise BEFORE any
    state mutates — the controller keeps serving afterwards."""
    src = np.random.default_rng(2).integers(
        0, 255, (32, 48, 3), dtype=np.uint8
    )
    from flyimg_tpu.spec.options import OptionsBag
    from flyimg_tpu.spec.plan import build_plan

    plan = build_plan(OptionsBag("w_32,o_png"), 48, 32)

    class BadMesh:
        axis_names = ("model",)

    batcher = BatchController(lone_flush=True, deadline_ms=1.0)
    try:
        with pytest.raises(ValueError):
            batcher.failover_backend(
                BadMesh(), drain_timeout_s=0.1, reason="device_repromote"
            )
        out = batcher.submit(src, plan).result(timeout=60.0)
        assert out.shape[1] == 32
        assert batcher.admission.pending == 0
    finally:
        batcher.close(drain_timeout_s=5.0)


def test_repromote_drains_before_the_backend_switch():
    """Review pin: re-promotion drains healthy in-flight CPU batches
    BEFORE switching backends (clearing backends under live arrays
    would 5xx renders that were about to succeed)."""
    clock = FakeClock()
    batcher = FakeBatcher()
    order = []
    sup = _supervisor(clock, threshold=1, hysteresis=1, batcher=batcher)
    real_switch = sup._switch_backend_to_device
    batcher.drain_inflight = lambda t: order.append("drain")
    sup._switch_backend_to_device = lambda: (
        order.append("switch"), real_switch()
    )
    sup.record_batch_failure(TRANSIENT)
    order.clear()
    _scripted_probes([True])
    try:
        sup.probe_and_handle()
    finally:
        faults.clear()
    assert not sup.cpu_forced()
    assert order[:2] == ["drain", "switch"]


def test_flap_damping_escalates_probe_hysteresis():
    """Review pin: a backend that passes the (small) compute probe but
    storms again under real batches must not cycle forever — a failover
    shortly after a re-promotion doubles the clean probes required
    (capped), and a failover after a long healthy stretch resets it."""
    clock = FakeClock()
    sup = _supervisor(clock, threshold=1, window_s=10.0, hysteresis=1)
    # cycle 1: fail over, one clean probe re-promotes (mult 1)
    sup.record_batch_failure(TRANSIENT)
    _scripted_probes([True])
    try:
        sup.probe_and_handle()
    finally:
        faults.clear()
    assert sup.state() == DEVICE
    # cycle 2: the re-promotion did not stick — the flap doubles the
    # requirement to 2 clean probes
    clock.advance(1.0)
    sup.record_batch_failure(TRANSIENT)
    assert sup.snapshot()["probe"]["hysteresis_mult"] == 2
    _scripted_probes([True, True])
    try:
        sup.probe_and_handle()
        assert sup.cpu_forced()  # one clean probe no longer suffices
        sup.probe_and_handle()
        assert not sup.cpu_forced()
    finally:
        faults.clear()
    # a failover long after the last re-promotion resets the damping
    clock.advance(sup.flap_window_s + 1.0)
    sup.record_batch_failure(TRANSIENT)
    assert sup.snapshot()["probe"]["hysteresis_mult"] == 1


def test_switch_sequences_pause_and_resume_launches():
    """Review pin: both switch directions hold new launches for the
    whole drain+switch+rebuild window and always resume."""
    clock = FakeClock()
    batcher = FakeBatcher()
    sup = _supervisor(clock, threshold=1, hysteresis=1, batcher=batcher)
    states = []
    orig_failover = batcher.failover_backend

    def recording_failover(mesh, **kw):
        states.append(("rebuild", batcher.paused))
        return orig_failover(mesh, **kw)

    batcher.failover_backend = recording_failover
    sup.record_batch_failure(TRANSIENT)
    assert states == [("rebuild", True)]  # rebuilt while paused
    assert batcher.paused is False        # and resumed after
    _scripted_probes([True])
    try:
        sup.probe_and_handle()
    finally:
        faults.clear()
    assert states[-1] == ("rebuild", True)
    assert batcher.paused is False


def test_no_repromote_while_a_new_failover_is_in_flight():
    """Review pin: a clean probe landing while a NEW storm's failover
    worker is mid-switch must not start a concurrent re-promotion (two
    racing backend switches); it re-evaluates once the worker settles."""
    clock = FakeClock()
    sup = _supervisor(clock, threshold=1, hysteresis=1)
    sup.record_batch_failure(TRANSIENT)
    assert sup.cpu_forced()
    with sup._lock:
        sup._failing_over = True  # a new storm's worker is mid-switch
    _scripted_probes([True])
    try:
        sup.probe_and_handle()
    finally:
        faults.clear()
    assert sup.cpu_forced()  # no concurrent re-promotion
    with sup._lock:
        sup._failing_over = False
    _scripted_probes([True])
    try:
        sup.probe_and_handle()
    finally:
        faults.clear()
    assert not sup.cpu_forced()  # settles once the worker is done


def test_probe_child_that_lands_on_cpu_is_down(monkeypatch):
    """The probe asks whether the ACCELERATOR works: a child whose JAX
    fell back to the CPU (no chip, init failed) finishes the matmul fine
    and must still read as down — on every call, so a backend that
    appears later is found without a restart. Real child processes; the
    first sees no non-CPU backend, the second is told cpu counts."""
    from flyimg_tpu.parallel import mesh as mesh_mod

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert mesh_mod.probe_device_backend(120.0) == (False, "down")
    monkeypatch.setattr(
        mesh_mod, "_PROBE_SNIPPET",
        mesh_mod._PROBE_SNIPPET.replace("!= 'cpu'", "== 'cpu'"),
    )
    assert mesh_mod.probe_device_backend(120.0) == (True, "up")


# ---------------------------------------------------------------------------
# failover drains without stranding futures


def test_failover_backend_drains_without_stranding():
    src = np.random.default_rng(0).integers(
        0, 255, (32, 48, 3), dtype=np.uint8
    )
    from flyimg_tpu.spec.options import OptionsBag
    from flyimg_tpu.spec.plan import build_plan

    plan = build_plan(OptionsBag("w_32,o_png"), 48, 32)
    gate = threading.Event()
    injector = faults.FaultInjector()
    injector.plan("batcher.execute", faults.wedge_until(gate))
    faults.install(injector)
    batcher = BatchController(lone_flush=True, deadline_ms=1.0)
    try:
        wedged = batcher.submit(src, plan)
        for _ in range(200):
            if injector.fired.get("batcher.execute"):
                break
            time.sleep(0.01)
        injector.remove("batcher.execute")
        queued = batcher.submit(src, plan)
        # the wedged in-flight batch exceeds the drain budget: it is
        # timeout-stamped, the executor is rebuilt, and the queued
        # submission re-homes and completes — nothing hangs
        batcher.failover_backend(
            None, drain_timeout_s=0.3, reason="device_failover"
        )
        gate.set()
        with pytest.raises(Exception):
            wedged.result(timeout=10.0)
        out = queued.result(timeout=30.0)
        assert out.shape[1] == 32
        assert batcher.admission.pending == 0
    finally:
        gate.set()
        faults.clear()
        batcher.close(drain_timeout_s=5.0)


def test_submit_after_backend_swaps_is_not_lost_to_stale_waiters():
    """Lost-wakeup regression: each backend swap supersedes a healthy
    executor PARKED in the wait loop. submit()'s notify() wakes ONE
    waiter — if a stale thread consumes it and exits without passing it
    on, the live executor sleeps forever with work queued."""
    src = np.random.default_rng(1).integers(
        0, 255, (32, 48, 3), dtype=np.uint8
    )
    from flyimg_tpu.spec.options import OptionsBag
    from flyimg_tpu.spec.plan import build_plan

    plan = build_plan(OptionsBag("w_32,o_png"), 48, 32)
    batcher = BatchController(lone_flush=True, deadline_ms=1.0)
    try:
        for _ in range(5):
            # let each replacement reach its wait before superseding it
            time.sleep(0.05)
            batcher.failover_backend(
                None, drain_timeout_s=0.1, reason="device_repromote"
            )
        time.sleep(0.05)
        out = batcher.submit(src, plan).result(timeout=60.0)
        assert out.shape[1] == 32
    finally:
        batcher.close(drain_timeout_s=5.0)


# ---------------------------------------------------------------------------
# end-to-end: storm -> CPU fallback parity -> readyz -> byte identity


def _write_src(tmp_path):
    rng = np.random.default_rng(11)
    src = tmp_path / "src.png"
    src.write_bytes(
        encode(rng.integers(0, 230, (48, 64, 3), dtype=np.uint8), "png")
    )
    return str(src)


def _app_params(tmp_path, sub, **extra):
    conf = {
        "tmp_dir": str(tmp_path / sub / "t"),
        "upload_dir": str(tmp_path / sub / "u"),
        "batch_deadline_ms": 1.0,
    }
    conf.update(extra)
    return AppParameters(conf)


def test_cpu_fallback_serves_parity_pinned_and_uncached(tmp_path):
    """Misses during CPU failover: 200, tagged cpu-fallback, never
    cached, and pixel-parity ≤1 u8 against a healthy app's render of
    the same request."""
    from flyimg_tpu.service.app import SUPERVISOR_KEY, make_app

    src = _write_src(tmp_path)

    async def go():
        healthy = make_app(_app_params(tmp_path, "healthy"))
        injector = faults.FaultInjector()
        # flag-gated, not count-gated: a stray background render from
        # another test's still-live app must not consume the storm
        # budget (the injector hook is process-global)
        storm = {"on": True}

        def drain_plan(**_ctx):
            if storm["on"]:
                raise ConnectionError("test: device gone")
            return faults.PASS

        injector.plan("batcher.drain", drain_plan)
        injector.plan("device.backend", lambda **_: False)
        downed = make_app(_app_params(
            tmp_path, "downed",
            fault_injector=injector,
            device_supervisor_enable=True,
            device_storm_threshold=2,
            device_probe_interval_s=30.0,  # no prober interference
            device_failover_drain_s=1.0,
            resilience_batch_retries=1,
        ))
        sup = downed[SUPERVISOR_KEY]
        c_h = TestClient(TestServer(healthy))
        c_d = TestClient(TestServer(downed))
        await c_h.start_server()
        await c_d.start_server()
        try:
            # trip the storm on the downed app (every launch fails
            # while the flag holds, so ONE request's launch + retry
            # reaches the threshold; more requests only if needed)
            for w in (31, 30, 29):
                await c_d.get(f"/upload/w_{w},o_png/{src}")
                if sup.cpu_forced():
                    break
            for _ in range(200):
                if sup.cpu_forced():
                    break
                await asyncio.sleep(0.05)
            assert sup.cpu_forced()
            storm["on"] = False  # the device is gone; CPU serves now
            path = f"/upload/w_40,h_30,c_1,o_png/{src}"
            r_d = await c_d.get(path)
            r_h = await c_h.get(path)
            assert r_h.status == 200 and r_d.status == 200
            assert "X-Flyimg-Degraded" not in r_h.headers
            degraded = r_d.headers.get("X-Flyimg-Degraded", "")
            assert "cpu-fallback" in degraded.split(",")
            assert "max-age=60" in r_d.headers.get("Cache-Control", "")
            a = decode(await r_h.read()).rgb.astype(np.int16)
            b = decode(await r_d.read()).rgb.astype(np.int16)
            assert a.shape == b.shape
            assert int(np.abs(a - b).max()) <= 1
            # never cached: the same key degrades again (a cached CPU
            # render would mask re-promotion)
            r_again = await c_d.get(path)
            assert "cpu-fallback" in r_again.headers.get(
                "X-Flyimg-Degraded", ""
            ).split(",")
            # readyz: device down, replica still ready
            ready = json.loads(await (await c_d.get("/readyz")).text())
            assert ready == {"status": "ok", "device": "down"}
        finally:
            await c_h.close()
            await c_d.close()

    _run(go())


def test_trip_mid_render_is_not_cached_at_device_key(tmp_path):
    """Review pin: the breaker tripping MID-render (request admitted
    while healthy, batch re-homed to the rebuilt CPU executor) must
    still tag the response and skip the cache write — the supervisor
    state is rechecked at cache-write time, not only at render start."""
    from flyimg_tpu.service.app import SUPERVISOR_KEY, make_app

    src = _write_src(tmp_path)

    async def go():
        gate = threading.Event()
        injector = faults.FaultInjector()
        injector.plan("batcher.execute", faults.wedge_until(gate))
        app = make_app(_app_params(
            tmp_path, "midtrip",
            fault_injector=injector,
            device_supervisor_enable=True,
        ))
        sup = app[SUPERVISOR_KEY]
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            path = f"/upload/w_36,o_png/{src}"
            pending = asyncio.ensure_future(client.get(path))
            for _ in range(200):
                if injector.fired.get("batcher.execute"):
                    break
                await asyncio.sleep(0.02)
            # the breaker trips while the render is parked at the gate
            # (white-box: the storm path is pinned elsewhere)
            with sup._lock:
                sup._state = CPU_FALLBACK
            injector.remove("batcher.execute")
            gate.set()
            resp = await pending
            assert resp.status == 200
            assert "cpu-fallback" in resp.headers.get(
                "X-Flyimg-Degraded", ""
            ).split(",")
            # nothing was cached: the same key is a (tagged) miss again
            again = await client.get(path)
            assert "cpu-fallback" in again.headers.get(
                "X-Flyimg-Degraded", ""
            ).split(",")
        finally:
            gate.set()
            await client.close()

    _run(go())


def test_default_off_is_byte_identical(tmp_path):
    """Supervisor off (the default): no health metrics, no readyz
    device field, no degraded headers, no supervisor reference on the
    batcher."""
    from flyimg_tpu.service.app import HANDLER_KEY, make_app

    src = _write_src(tmp_path)

    async def go():
        app = make_app(_app_params(tmp_path, "plain"))
        assert app[HANDLER_KEY].batcher.supervisor is None
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            ready = await (await client.get("/readyz")).text()
            assert json.loads(ready) == {"status": "ok"}
            resp = await client.get(f"/upload/w_32,o_png/{src}")
            assert resp.status == 200
            assert "X-Flyimg-Degraded" not in resp.headers
            metrics = await (await client.get("/metrics")).text()
            assert "flyimg_device_health" not in metrics
            assert "flyimg_backend_failovers_total" not in metrics
            assert "flyimg_backend_probe_total" not in metrics
        finally:
            await client.close()

    _run(go())


def test_debug_device_gated_and_snapshots(tmp_path):
    from flyimg_tpu.service.app import make_app

    async def go():
        gated = make_app(_app_params(tmp_path, "gated"))
        on = make_app(_app_params(
            tmp_path, "on", debug=True, device_supervisor_enable=True,
        ))
        c_gated = TestClient(TestServer(gated))
        c_on = TestClient(TestServer(on))
        await c_gated.start_server()
        await c_on.start_server()
        try:
            assert (await c_gated.get("/debug/device")).status == 404
            resp = await c_on.get("/debug/device")
            assert resp.status == 200
            doc = json.loads(await resp.text())
            assert doc["enabled"] is True
            assert doc["state"] == "device"
            assert doc["storm"]["threshold"] == 5
        finally:
            await c_gated.close()
            await c_on.close()

    _run(go())


# ---------------------------------------------------------------------------
# fleet health gating


FLEET = [f"http://10.1.0.{i}:8080" for i in range(1, 4)]


def _key_owned_by(router, owner):
    for i in range(500):
        key = f"key-{i}"
        if rendezvous_owner(FLEET, key) == owner:
            return key
    raise AssertionError("no key landed on the wanted owner")


def test_marked_down_owner_keys_rehome_to_a_healthy_replica():
    """A device-down owner's keys proxy to the next rendezvous choice —
    NOT to everyone, and not forever: HRW re-homes only the down
    replica's keys, and the mark expires."""
    router = FleetRouter(FLEET, FLEET[0], health_ttl_s=0.2)
    down = FLEET[1]
    key = _key_owned_by(router, down)
    healthy_key = _key_owned_by(router, FLEET[2])
    assert router.owner(key) == down
    router.mark_device_down(down)
    rehomed = router.owner(key)
    assert rehomed != down
    assert rehomed == rendezvous_owner(
        [FLEET[0], FLEET[2]], key
    )  # the next-highest replica, deterministically
    # other replicas' keys did not move (HRW minimal disruption)
    assert router.owner(healthy_key) == FLEET[2]
    time.sleep(0.25)
    assert router.owner(key) == down  # the mark expired


def test_self_is_never_marked_down():
    router = FleetRouter(FLEET, FLEET[0], health_ttl_s=5.0)
    router.mark_device_down(FLEET[0])
    key = _key_owned_by(router, FLEET[0])
    assert router.owner(key) == FLEET[0]


def test_health_ttl_zero_disables_the_gate():
    router = FleetRouter(FLEET, FLEET[0], health_ttl_s=0.0)
    down = FLEET[1]
    router.mark_device_down(down)
    key = _key_owned_by(router, down)
    assert router.owner(key) == down


def test_background_readyz_probe_marks_and_skips_device_down_owner(tmp_path):
    """The active half of the gate runs OFF the request path: the first
    proxy to an owner schedules a background /readyz probe and relays
    normally (zero added latency); once the probe sees device:down the
    owner is marked and the NEXT proxy sheds (local fallback + re-homed
    keys)."""
    from aiohttp import web as aioweb

    async def go():
        hits = {"readyz": 0, "upload": 0}

        async def readyz(_request):
            hits["readyz"] += 1
            return aioweb.json_response({"status": "ok", "device": "down"})

        async def catchall(_request):
            hits["upload"] += 1
            return aioweb.Response(body=b"png-bytes", status=200)

        owner_app = aioweb.Application()
        owner_app.router.add_get("/readyz", readyz)
        owner_app.router.add_get("/{tail:.*}", catchall)
        import socket

        sock = socket.socket()
        sock.bind(("127.0.0.1", 0))
        port = sock.getsockname()[1]
        sock.close()
        server = TestClient(
            TestServer(owner_app, host="127.0.0.1", port=port)
        )
        await server.start_server()
        owner_url = f"http://127.0.0.1:{port}"
        router = FleetRouter(
            ["http://self", owner_url], "http://self", health_ttl_s=5.0,
        )
        try:
            # first proxy: relays without waiting on the probe
            relayed = await router.proxy(owner_url, "/upload/x", {})
            assert relayed is not None and relayed[0] == 200
            assert hits["upload"] == 1
            for _ in range(100):  # the background probe lands
                if router._device_down(owner_url):
                    break
                await asyncio.sleep(0.02)
            assert router._device_down(owner_url)
            assert hits["readyz"] == 1
            # second proxy sheds: render locally, keys re-home
            assert await router.proxy(owner_url, "/upload/x", {}) is None
            assert hits["upload"] == 1  # no second hop
        finally:
            await router.aclose()
            await server.close()

    _run(go())


def test_device_down_skip_leaves_the_breaker_untouched():
    """Review pin: the health gate runs BEFORE breaker admission — a
    skip after allow() in HALF_OPEN would consume the probe slot
    without recording an outcome and wedge the breaker forever."""
    from flyimg_tpu.runtime.resilience import BreakerRegistry

    async def go():
        router = FleetRouter(
            ["http://self", "http://o"], "http://self",
            health_ttl_s=5.0,
            breakers=BreakerRegistry(failure_threshold=1, recovery_s=0.0),
        )
        breaker = router.breakers.for_host("http://o")
        breaker.record_failure()  # OPEN; recovery 0 = next allow probes
        router.mark_device_down("http://o")
        try:
            assert await router.proxy("http://o", "/x", {}) is None
            # the skip never consumed the half-open probe slot: the
            # breaker still admits its one probe (a wedged slot raises)
            breaker.allow()
        finally:
            await router.aclose()

    _run(go())


def test_proxy_marks_owner_down_off_relayed_cpu_fallback(tmp_path):
    """The passive half: a relayed response tagged cpu-fallback is
    still served (valid bytes) but marks the owner down."""
    from aiohttp import web as aioweb

    async def go():
        async def readyz(_request):
            return aioweb.json_response({"status": "ok"})

        async def catchall(_request):
            return aioweb.Response(
                body=b"bytes", status=200,
                headers={"X-Flyimg-Degraded": "cpu-fallback"},
            )

        owner_app = aioweb.Application()
        owner_app.router.add_get("/readyz", readyz)
        owner_app.router.add_get("/{tail:.*}", catchall)
        import socket

        sock = socket.socket()
        sock.bind(("127.0.0.1", 0))
        port = sock.getsockname()[1]
        sock.close()
        server = TestClient(
            TestServer(owner_app, host="127.0.0.1", port=port)
        )
        await server.start_server()
        owner_url = f"http://127.0.0.1:{port}"
        router = FleetRouter(
            ["http://self", owner_url], "http://self", health_ttl_s=5.0,
        )
        try:
            relayed = await router.proxy(owner_url, "/upload/x", {})
            assert relayed is not None
            status, headers, body = relayed
            assert status == 200 and body == b"bytes"
            assert router._device_down(owner_url)
        finally:
            await router.aclose()
            await server.close()

    _run(go())


def test_switch_back_resets_config_when_selection_was_default(monkeypatch):
    """Review pin: restoring a DEFAULT selection (JAX_PLATFORMS was
    unset) must reset jax.config.jax_platforms — config beats env, so
    leaving force_cpu_platform's 'cpu' pin in place would re-promote
    onto a backend that is still the CPU (health 1, untagged cached CPU
    renders)."""
    import jax
    from jax.extend import backend as jax_backend

    clock = FakeClock()
    sup = _supervisor(clock, threshold=1)
    sup._saved_selection = {"JAX_PLATFORMS": None, "XLA_FLAGS": None}
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")  # the forced-CPU env
    updates = []
    monkeypatch.setattr(
        jax.config, "update", lambda key, value: updates.append((key, value))
    )
    monkeypatch.setattr(jax_backend, "clear_backends", lambda: None)
    sup._switch_backend_to_device()
    assert os.environ.get("JAX_PLATFORMS") is None  # pin removed
    assert ("jax_platforms", None) in updates       # config RESET


# ---------------------------------------------------------------------------
# evaluate() span-event drain


def test_evaluate_drains_transition_events_onto_the_ambient_trace():
    from flyimg_tpu.runtime import tracing

    clock = FakeClock()
    sup = _supervisor(clock, threshold=1)
    sup.record_batch_failure(TRANSIENT)
    tracer = tracing.Tracer(enabled=True)
    trace = tracer.start(None)
    with tracing.activate(trace):
        sup.evaluate()
    events = [e["name"] for e in trace.root.events]
    assert "device.failover" in events
    # drained: a second evaluation adds nothing
    with tracing.activate(trace):
        sup.evaluate()
    assert [e["name"] for e in trace.root.events].count(
        "device.failover"
    ) == 1
