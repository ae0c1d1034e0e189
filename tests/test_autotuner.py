"""Online policy autotuner tests (ISSUE 14; docs/autotuning.md): the
deterministic decision engine, envelope clamping, revert-on-regression,
the SLO-burn freeze guard rail, live policy application with no torn
reads (batcher policy pair, stage-pool resize), program identity
untouched by tuned thresholds, the bench-history validator, the offline
replay, and the default-off byte-identity guarantee.

Acceptance behaviors pinned here:
- ``autotune_enable`` false (the default) registers no metrics, writes
  no knobs, and serves a disabled /debug/autotune document;
- every adjustment stays inside its declared envelope and moves at most
  one step per period;
- an adjustment whose next window's objective regressed is reverted and
  the knob cools down;
- burn past the brownout thresholds freezes tuning and reverts to
  last-known-good;
- ``BatchController.apply_policy`` swaps (max_batch, deadline) as one
  atomic pair — concurrent readers never observe a torn pair and
  launches under churn all resolve;
- the ``resample_kernel=auto`` threshold steers SELECTION only: a
  tuned fraction never changes the identity of an already-selected
  program;
- ``tools/autotune_replay.py`` runs on the repo's REAL
  bench_history.jsonl + perf_baseline.json and emits a policy proposal
  and a candidate baseline without error.
"""

import asyncio
import json
import os
import threading
import time

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from flyimg_tpu.appconfig import AppParameters
from flyimg_tpu.codecs import encode
from flyimg_tpu.runtime.autotuner import (
    DOWN,
    ENVELOPES,
    UP,
    DecisionEngine,
    Envelope,
    PolicyAutotuner,
    default_envelopes,
)
from flyimg_tpu.runtime.batcher import BatchController, build_batched_program
from flyimg_tpu.runtime.hostpipeline import HostPipeline, StagePool
from flyimg_tpu.runtime.metrics import MetricsRegistry
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


def _ctrl(window=20, occ=0.5, wait=0.0, pad=None, per_miss=10.0):
    return {
        "window_batches": window,
        "mean_occupancy": occ,
        "queue_wait_share": wait,
        "padding_waste": pad if pad is not None else 1.0 - occ,
        "batches_per_compile_miss": per_miss,
    }


DEVICE_POLICY = {
    "device.max_batch": 64.0,
    "device.deadline_ms": 4.0,
    "codec.max_batch": 32.0,
    "codec.deadline_ms": 1.0,
    "host.fetch_workers": 4.0,
    "host.decode_workers": 2.0,
    "host.encode_workers": 2.0,
    "reuse.min_scale": 2.0,
    "resample.auto_band_frac": 1.0,
}


# ---------------------------------------------------------------------------
# envelopes


def test_envelope_clamp_move_and_int_kind():
    env = Envelope(4, 64, 8, "int")
    assert env.clamp(100) == 64
    assert env.clamp(-3) == 4
    assert env.move(60, UP) == 64  # clamped, not 68
    assert env.move(4, DOWN) == 4
    f = Envelope(0.5, 20.0, 1.0)
    assert f.move(4.0, DOWN) == 3.0
    assert f.move(0.9, DOWN) == 0.5


def test_default_envelopes_overrides_and_malformed_fallback():
    envs = default_envelopes({
        "device.deadline_ms": {"lo": 1.0, "hi": 8.0, "step": 0.5},
        "device.max_batch": {"lo": "garbage"},
        "not.a.knob": {"lo": 0, "hi": 1, "step": 1},
    })
    assert envs["device.deadline_ms"] == Envelope(1.0, 8.0, 0.5)
    # malformed override falls back to the pinned envelope
    assert envs["device.max_batch"] == ENVELOPES["device.max_batch"]
    assert "not.a.knob" not in envs


# ---------------------------------------------------------------------------
# decision engine rules (pure, deterministic)


def test_full_batches_grow_max_batch():
    eng = DecisionEngine()
    policy = dict(DEVICE_POLICY, **{"device.max_batch": 32.0})
    got = eng.propose(
        {"controllers": {"device": _ctrl(occ=0.95)}}, policy, ENVELOPES
    )
    assert got.knob == "device.max_batch"
    assert got.direction == UP
    assert got.target == 40.0


def test_queue_wait_dominance_shortens_deadline():
    eng = DecisionEngine()
    got = eng.propose(
        {"controllers": {"device": _ctrl(occ=0.6, wait=0.4)}},
        dict(DEVICE_POLICY), ENVELOPES,
    )
    assert got.knob == "device.deadline_ms"
    assert got.direction == DOWN


def test_sparse_traffic_shortens_deadline():
    eng = DecisionEngine()
    got = eng.propose(
        {"controllers": {"device": _ctrl(occ=0.1, wait=0.0)}},
        dict(DEVICE_POLICY), ENVELOPES,
    )
    assert got == got.__class__(
        "device.deadline_ms", 3.0, DOWN, got.reason
    )


def test_padding_waste_lengthens_deadline():
    eng = DecisionEngine()
    got = eng.propose(
        {"controllers": {"device": _ctrl(occ=0.45, wait=0.0, pad=0.55)}},
        dict(DEVICE_POLICY), ENVELOPES,
    )
    assert got.knob == "device.deadline_ms"
    assert got.direction == UP
    assert got.target == 5.0


def test_thin_window_is_no_evidence():
    eng = DecisionEngine()
    assert eng.propose(
        {"controllers": {"device": _ctrl(window=3, occ=0.95)}},
        dict(DEVICE_POLICY), ENVELOPES,
    ) is None


def test_saturated_pool_gains_a_worker():
    eng = DecisionEngine()
    got = eng.propose(
        {
            "controllers": {},
            "host": {"decode": {"saturation": 0.9, "busy_frac": 1.0}},
        },
        dict(DEVICE_POLICY), ENVELOPES,
    )
    assert got.knob == "host.decode_workers"
    assert got.direction == UP


def test_cold_pool_shed_requires_recent_traffic_evidence():
    eng = DecisionEngine()
    cold = {"host": {"fetch": {"saturation": 0.0, "busy_frac": 0.0}}}
    # idle service: no controller evidence -> never shed workers
    assert eng.propose(
        {"controllers": {}, **cold}, dict(DEVICE_POLICY), ENVELOPES
    ) is None
    # a historical burst still in the (count-based, never-expiring)
    # window but NO launches since the last evaluation: still idle —
    # trickle traffic must not drain the pools to the floor
    stale = _ctrl(occ=0.6, wait=0.1)
    stale["launches_delta"] = 0.0
    assert eng.propose(
        {"controllers": {"device": stale}, **cold},
        dict(DEVICE_POLICY), ENVELOPES,
    ) is None
    # RECENT traffic with a cold pool: shed one
    live = _ctrl(occ=0.6, wait=0.1)
    live["launches_delta"] = 20.0
    got = eng.propose(
        {"controllers": {"device": live}, **cold},
        dict(DEVICE_POLICY), ENVELOPES,
    )
    assert got.knob == "host.fetch_workers"
    assert got.direction == DOWN
    # offline-replay windows carry no delta: window depth is the
    # fallback evidence
    got = eng.propose(
        {"controllers": {"device": _ctrl(occ=0.6, wait=0.1)}, **cold},
        dict(DEVICE_POLICY), ENVELOPES,
    )
    assert got is not None


def test_signal_assembly_stamps_launch_recency():
    metrics = MetricsRegistry()
    tuner = PolicyAutotuner(enabled=True, metrics=metrics)
    tuner.attach_signals(metrics=metrics)
    for _ in range(10):
        metrics.record_batch_launch(
            "device", images=2, capacity=16, queue_wait_s=0.0,
            device_s=0.01, compile_hit=True,
        )
    first = tuner._signals()["controllers"]["device"]
    assert first["launches_delta"] == 0.0  # no previous evaluation yet
    for _ in range(6):
        metrics.record_batch_launch(
            "device", images=2, capacity=16, queue_wait_s=0.0,
            device_s=0.01, compile_hit=True,
        )
    second = tuner._signals()["controllers"]["device"]
    assert second["launches_delta"] == 6.0
    assert tuner._signals()["controllers"]["device"]["launches_delta"] == 0.0


def test_low_reuse_ratio_lowers_min_scale():
    eng = DecisionEngine()
    got = eng.propose(
        {
            "controllers": {},
            "reuse": {"attempts": 100, "hit_ratio": 0.1},
        },
        dict(DEVICE_POLICY), ENVELOPES,
    )
    assert got.knob == "reuse.min_scale"
    assert got.target == 1.75
    # too few attempts = no evidence
    assert eng.propose(
        {"controllers": {}, "reuse": {"attempts": 5, "hit_ratio": 0.0}},
        dict(DEVICE_POLICY), ENVELOPES,
    ) is None


def test_auto_band_frac_follows_compile_amortization():
    eng = DecisionEngine()
    churn = {
        "controllers": {
            "device": _ctrl(occ=0.6, wait=0.1, per_miss=2.0)
        },
        "kernel_mode": "auto",
    }
    got = eng.propose(churn, dict(DEVICE_POLICY), ENVELOPES)
    assert got.knob == "resample.auto_band_frac"
    assert got.direction == DOWN
    warm = {
        "controllers": {
            "device": _ctrl(occ=0.6, wait=0.1, per_miss=64.0)
        },
        "kernel_mode": "auto",
    }
    policy = dict(DEVICE_POLICY, **{"resample.auto_band_frac": 0.5})
    got = eng.propose(warm, policy, ENVELOPES)
    assert got.knob == "resample.auto_band_frac"
    assert got.direction == UP
    # dense/banded modes never touch the auto threshold
    churn_dense = dict(churn, kernel_mode="dense")
    assert eng.propose(
        churn_dense, dict(DEVICE_POLICY), ENVELOPES
    ) is None


def test_pinned_at_bound_proposes_nothing_and_blocked_skips():
    eng = DecisionEngine()
    sparse = {"controllers": {"device": _ctrl(occ=0.1, wait=0.0)}}
    pinned = dict(DEVICE_POLICY, **{"device.deadline_ms": 0.5})
    assert eng.propose(sparse, pinned, ENVELOPES) is None
    assert eng.propose(
        sparse, dict(DEVICE_POLICY), ENVELOPES,
        blocked={"device.deadline_ms"},
    ) is None


def test_freeze_pressure_from_burn_and_brownout_level():
    eng = DecisionEngine()
    assert eng.freeze_pressure({"burn_fast_norm": 1.3}) == 1.3
    assert eng.freeze_pressure(
        {"burn_fast_norm": 0.2, "burn_slow_norm": 0.9}
    ) == 0.9
    assert eng.freeze_pressure({"brownout_level": 2}) >= 1.0
    assert eng.freeze_pressure({"brownout_level": 1}) == 0.0


# ---------------------------------------------------------------------------
# PolicyAutotuner state machine (fake knobs, injected signals + clock)


class _Box:
    def __init__(self, v: float) -> None:
        self.v = float(v)


def _tuner(clock, sig_box, metrics=None, **over):
    kw = dict(
        enabled=True, interval_s=10.0, regression_margin=0.05,
        cooldown_periods=2, freeze_at=1.0, unfreeze_hysteresis=0.75,
        freeze_dwell_s=30.0, metrics=metrics or MetricsRegistry(),
        clock=clock,
    )
    kw.update(over)
    tuner = PolicyAutotuner(**kw)
    tuner._signals = lambda: sig_box[0]  # deterministic signal window
    return tuner


SPARSE = {"controllers": {"device": _ctrl(occ=0.1, wait=0.0)}}


def test_rate_limit_under_injected_clock():
    clock = FakeClock()
    sig = [SPARSE]
    tuner = _tuner(clock, sig)
    box = _Box(4.0)
    tuner.bind(
        "device.deadline_ms", lambda: box.v,
        lambda v: setattr(box, "v", v),
    )
    tuner.evaluate()
    assert box.v == 3.0  # first evaluation tunes
    tuner.evaluate()
    assert box.v == 3.0  # rate-limited: same instant, no second step
    clock.advance(11.0)
    tuner.evaluate()
    assert box.v == 2.0  # next period: pending committed, next step


def test_surviving_adjustment_commits_to_known_good():
    clock = FakeClock()
    sig = [SPARSE]
    tuner = _tuner(clock, sig)
    box = _Box(4.0)
    tuner.bind(
        "device.deadline_ms", lambda: box.v,
        lambda v: setattr(box, "v", v),
    )
    tuner.evaluate()
    assert tuner.snapshot()["known_good"]["device.deadline_ms"] == 4.0
    clock.advance(11.0)
    tuner.evaluate()  # same objective: the 4->3 step survived
    assert tuner.snapshot()["known_good"]["device.deadline_ms"] == 3.0


def test_regression_reverts_and_cools_down():
    clock = FakeClock()
    sig = [SPARSE]
    tuner = _tuner(clock, sig)
    box = _Box(4.0)
    tuner.bind(
        "device.deadline_ms", lambda: box.v,
        lambda v: setattr(box, "v", v),
    )
    tuner.evaluate()
    assert box.v == 3.0
    # next window: objective tanks (occupancy collapsed, waits exploded)
    sig[0] = {"controllers": {"device": _ctrl(occ=0.05, wait=0.6)}}
    clock.advance(11.0)
    tuner.evaluate()
    assert box.v == 4.0  # reverted
    history = tuner.snapshot()["history"]
    assert [h["action"] for h in history] == ["adjust", "revert"]
    # cooldown: the knob sits out the next periods even under clean
    # sparse evidence
    sig[0] = SPARSE
    clock.advance(11.0)
    tuner.evaluate()
    assert box.v == 4.0
    clock.advance(11.0)
    tuner.evaluate()
    assert box.v == 4.0
    clock.advance(11.0)
    tuner.evaluate()
    assert box.v == 3.0  # cooldown expired: tunable again


def test_burn_freeze_reverts_to_known_good_and_dwells():
    clock = FakeClock()
    sig = [SPARSE]
    metrics = MetricsRegistry()
    tuner = _tuner(clock, sig, metrics=metrics)
    tuner.register_metrics(metrics)
    box = _Box(4.0)
    tuner.bind(
        "device.deadline_ms", lambda: box.v,
        lambda v: setattr(box, "v", v),
    )
    tuner.evaluate()
    assert box.v == 3.0
    sig[0] = {"controllers": {}, "burn_fast_norm": 1.5}
    clock.advance(11.0)
    tuner.evaluate()
    assert tuner.frozen
    assert box.v == 4.0  # reverted to known-good (the boot policy)
    assert "flyimg_autotune_frozen 1" in metrics.render_prometheus()
    # frozen = no tuning, whatever the signals say
    sig[0] = dict(SPARSE, burn_fast_norm=1.5)
    clock.advance(11.0)
    tuner.evaluate()
    assert box.v == 4.0 and tuner.frozen
    # burn clears but the dwell holds the freeze
    sig[0] = dict(SPARSE, burn_fast_norm=0.1)
    clock.advance(11.0)
    tuner.evaluate()
    assert tuner.frozen
    # dwell elapsed under clear burn: unfreeze, tuning resumes next period
    clock.advance(31.0)
    tuner.evaluate()
    assert not tuner.frozen
    clock.advance(11.0)
    tuner.evaluate()
    assert box.v == 3.0
    history = [h["action"] for h in tuner.snapshot()["history"]]
    assert history == ["adjust", "freeze", "unfreeze", "adjust"]


def test_adjustment_counter_and_envelope_bound_in_metrics():
    clock = FakeClock()
    sig = [SPARSE]
    metrics = MetricsRegistry()
    tuner = _tuner(clock, sig, metrics=metrics)
    box = _Box(4.0)
    tuner.bind(
        "device.deadline_ms", lambda: box.v,
        lambda v: setattr(box, "v", v),
    )
    for _ in range(50):  # walk to the envelope floor and stay there
        tuner.evaluate()
        clock.advance(11.0)
    assert box.v == ENVELOPES["device.deadline_ms"].lo
    text = metrics.render_prometheus()
    assert (
        'flyimg_autotune_adjustments_total{knob="device.deadline_ms",'
        'direction="down"}'
    ) in text


def test_disabled_tuner_is_inert():
    clock = FakeClock()
    metrics = MetricsRegistry()
    tuner = PolicyAutotuner(enabled=False, metrics=metrics, clock=clock)
    tuner.register_metrics(metrics)
    box = _Box(4.0)
    # bind still validates envelopes, but evaluate never runs
    tuner.bind(
        "device.deadline_ms", lambda: box.v,
        lambda v: setattr(box, "v", v),
    )
    tuner._signals = lambda: SPARSE
    tuner.evaluate()
    assert box.v == 4.0
    assert "flyimg_autotune" not in metrics.render_prometheus()
    assert tuner.snapshot()["enabled"] is False


def test_bind_rejects_envelope_less_knob():
    tuner = PolicyAutotuner(enabled=True)
    with pytest.raises(ValueError):
        tuner.bind("made.up", lambda: 1.0, lambda v: None)


def test_fault_point_overrides_signals_and_rate_limit():
    clock = FakeClock()
    tuner = _tuner(clock, [{"controllers": {}}])
    box = _Box(4.0)
    tuner.bind(
        "device.deadline_ms", lambda: box.v,
        lambda v: setattr(box, "v", v),
    )
    injector = faults.install(faults.FaultInjector())
    injector.plan("autotune.signal", lambda **_: SPARSE)
    tuner.evaluate()
    tuner.evaluate()  # injection bypasses the rate limit entirely
    assert box.v == 2.0


# ---------------------------------------------------------------------------
# live policy application: no torn reads (ISSUE 14 satellite)


def _echo_runner(payloads):
    return list(payloads)


def test_batcher_policy_pair_never_tears_under_churn():
    """apply_policy under live submission load: every concurrent
    policy() read sees one of the two installed (size, timeout) pairs —
    never a half-applied mix — and every launch under churn resolves."""
    ctrl = BatchController(
        max_batch=8, deadline_ms=2.0, lone_flush=False,
        quarantine_ttl_s=0.0,
    )
    pairs = {(8, 0.002), (16, 0.004)}
    torn = []
    stop = threading.Event()

    def writer():
        flip = False
        while not stop.is_set():
            if flip:
                ctrl.apply_policy(max_batch=8, deadline_ms=2.0)
            else:
                ctrl.apply_policy(max_batch=16, deadline_ms=4.0)
            flip = not flip

    def reader():
        while not stop.is_set():
            pair = ctrl.policy()
            if pair not in pairs:
                torn.append(pair)

    threads = [threading.Thread(target=writer)] + [
        threading.Thread(target=reader) for _ in range(3)
    ]
    for t in threads:
        t.start()
    try:
        futures = [
            ctrl.submit_aux(("torn",), i, _echo_runner)
            for i in range(400)
        ]
        results = [f.result(timeout=60) for f in futures]
        assert results == list(range(400))
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
        ctrl.close()
    assert torn == []


def test_apply_policy_clamps_and_notifies():
    ctrl = BatchController(max_batch=8, deadline_ms=2.0, lone_flush=False)
    try:
        assert ctrl.apply_policy(max_batch=10_000) == (64, 0.002)
        assert ctrl.apply_policy(max_batch=0, deadline_ms=-5.0) == (1, 0.0)
        assert ctrl.policy() == (1, 0.0)
        assert ctrl.max_batch == 1 and ctrl.deadline_s == 0.0
    finally:
        ctrl.close()


def test_stagepool_resize_grows_and_shrinks_under_load():
    pool = StagePool(
        "decode", workers=2, queue_depth=4, wedge_timeout_s=0.0,
    )
    try:
        gate = threading.Event()
        blocked = [pool.submit(lambda: (gate.wait(30), "slow")[1])
                   for _ in range(2)]
        # both workers occupied; grow and prove the new capacity is live
        assert pool.resize(4) == 4
        assert pool.stats()["workers"] == 4.0
        assert pool.admission.max_pending == 4 + 4
        fast = [pool.submit(lambda: "fast") for _ in range(2)]
        for f in fast:
            assert f.result(timeout=10) == "fast"
        gate.set()
        for f in blocked:
            assert f.result(timeout=10) == "slow"
        # shrink: roster + admission bound follow immediately, work
        # still completes on the survivor
        assert pool.resize(1) == 1
        assert pool.stats()["workers"] == 1.0
        assert pool.admission.max_pending == 1 + 4
        assert pool.submit(lambda: "after").result(timeout=10) == "after"
        assert pool.resize(0) == 1  # floor: never zero workers
    finally:
        pool.close()


def test_host_pipeline_apply_policy_roundtrip():
    pipeline = HostPipeline(
        enabled=True, fetch_workers=4, decode_workers=2, encode_workers=2,
        queue_depth=4,
    )
    try:
        assert pipeline.policy() == {"fetch": 4, "decode": 2, "encode": 2}
        applied = pipeline.apply_policy({"decode": 3, "nope": 9})
        assert applied == {"decode": 3}
        assert pipeline.policy()["decode"] == 3
    finally:
        pipeline.close()


# ---------------------------------------------------------------------------
# tuned thresholds never change program identity (ISSUE 14 satellite)


def test_auto_band_frac_steers_selection_not_identity():
    from flyimg_tpu.ops.resample import (
        auto_band_frac,
        select_band_taps,
        set_auto_band_frac,
    )

    geometry = dict(
        mode="auto", method="lanczos3", in_hw=(60, 60),
        span_y=(0.0, 60.0), span_x=(0.0, 60.0), out_true_hw=(30.0, 30.0),
    )

    def select():
        return select_band_taps(
            geometry["mode"], geometry["method"], geometry["in_hw"],
            geometry["span_y"], geometry["span_x"],
            geometry["out_true_hw"],
        )

    try:
        assert set_auto_band_frac(1.0) == 1.0
        banded = select()
        assert banded == (16, 16)
        # a tighter worth-it fraction flips this marginal geometry to
        # dense — SELECTION changes...
        assert set_auto_band_frac(0.25) == 0.25
        assert select() is None
        # ...but identity is untouched: the same selected band_taps
        # resolves to the SAME cached program whatever the fraction is
        from flyimg_tpu.spec.options import OptionsBag
        from flyimg_tpu.spec.plan import build_plan

        plan = build_plan(OptionsBag("w_30,h_30,c_1"), 60, 60).device_plan()
        set_auto_band_frac(1.0)
        h1 = build_batched_program(
            1, (60, 60), (30, 30), None, (0, 0), plan, None, False, banded
        )
        set_auto_band_frac(0.5)
        h2 = build_batched_program(
            1, (60, 60), (30, 30), None, (0, 0), plan, None, False, banded
        )
        assert h1 is h2  # one lru entry: the fraction is not in the key
        # the SELECTED band_taps, by contrast, IS identity: a different
        # selection is a different cached program
        h3 = build_batched_program(
            1, (60, 60), (30, 30), None, (0, 0), plan, None, False, None
        )
        assert h3 is not h1
        # clamping: nothing can push the threshold out of [0.1, 1.0]
        # (the tuner's envelope floor, 0.25, is tighter still)
        assert set_auto_band_frac(0.0) == 0.1
        assert set_auto_band_frac(7.0) == 1.0
    finally:
        set_auto_band_frac(1.0)
        assert auto_band_frac() == 1.0


def test_reuse_signal_fn_windows_per_read():
    from flyimg_tpu.runtime.autotuner import reuse_signal_fn

    metrics = MetricsRegistry()

    def bump(outcome, n):
        metrics.counter(
            f'flyimg_reuse_hits_total{{outcome="{outcome}"}}',
            "Derivative-reuse ancestor lookups by outcome",
        ).inc(n)

    read = reuse_signal_fn(metrics)
    # cold-start miss streak
    bump("miss", 40)
    first = read()
    assert first["attempts"] == 40 and first["hit_ratio"] == 0.0
    # the NEXT period is all hits: the windowed ratio must say so (a
    # lifetime ratio would still read 40/80 = 0.5 and keep ratcheting
    # min_scale down on stale evidence)
    bump("hit", 40)
    second = read()
    assert second["attempts"] == 40 and second["hit_ratio"] == 1.0
    # quiet period: no attempts, no evidence
    third = read()
    assert third["attempts"] == 0 and third["hit_ratio"] is None


def test_stagepool_retiree_never_swallows_a_stop_sentinel():
    """A worker retired by resize() can be parked on queue.get() when a
    live worker ate its retirement sentinel; at close() it may grab a
    live worker's STOP sentinel — it must re-put it, or one live worker
    parks for the whole drain budget and shutdown stalls."""
    pool = StagePool("decode", workers=2, queue_depth=4,
                     wedge_timeout_s=0.0)
    assert pool.submit(lambda: "warm").result(timeout=10) == "warm"
    pool.resize(1)
    # let a live worker consume the retirement sentinel first in the
    # racy case; either way close() must finish well under the budget
    time.sleep(0.1)
    t0 = time.monotonic()
    pool.close(drain_timeout_s=10.0)
    assert time.monotonic() - t0 < 5.0


def test_owner_of_emptied_replica_set_is_self_not_valueerror():
    from flyimg_tpu.runtime.fleet import FleetRouter

    router = FleetRouter(["http://a", "http://b"], "http://a")
    key = "abc123"
    assert router.owner(key) in ("http://a", "http://b")
    router.update_replicas([])  # SIGHUP reload to an empty set
    assert router.owner(key) == "http://a"  # local render, no raise
    assert not router.enabled


def test_reuse_min_scale_applier_is_a_plain_store():
    class H:
        reuse_enable = True
        reuse_min_scale = 2.0

    tuner = PolicyAutotuner(enabled=True)
    handler = H()
    tuner.bind(
        "reuse.min_scale",
        lambda: handler.reuse_min_scale,
        lambda v: setattr(handler, "reuse_min_scale", float(v)),
    )
    tuner._knobs["reuse.min_scale"].applier(1.75)
    assert handler.reuse_min_scale == 1.75


# ---------------------------------------------------------------------------
# bench-history validator (ISSUE 14 satellite)


def test_bench_history_tolerant_schema_accepts_every_era():
    from tools.bench_history import check_row

    # PR-4-era row: no kernel/reuse/decode tags — valid
    assert check_row({
        "metric": "images/sec", "value": 47.0, "unit": "images/sec",
        "vs_baseline": 0.038, "backend": "cpu", "ts": 1.0,
    }) == []
    # PR-8-era row with a kernel tag and unknown future columns — valid
    assert check_row({
        "metric": "m", "value": None, "kernel": "banded", "ts": 2.0,
        "brand_new_column": {"x": 1},
    }) == []
    # supervisor failure row — valid (error instead of metric)
    assert check_row({"error": "probe timeout", "ts": 3.0}) == []


def test_bench_history_flags_and_repairs():
    from tools.bench_history import check_row, repair_row

    assert check_row([1, 2]) == ["row is not a JSON object"]
    assert any(
        "ts" in issue for issue in check_row({"metric": "m"})
    )
    assert any(
        "value" in issue
        for issue in check_row({"metric": "m", "value": "47.0", "ts": 1})
    )
    repaired = repair_row({"metric": "m", "value": "47.0", "ts": "1.5"})
    assert repaired["value"] == 47.0 and repaired["ts"] == 1.5
    # wrong-typed era tag is dropped, row kept
    repaired = repair_row({"metric": "m", "ts": 1.0, "kernel": 42})
    assert "kernel" not in repaired
    # unrepairable: neither metric nor error
    assert repair_row({"value": 1.0, "ts": 1.0}) is None


def test_bench_history_validate_exit_codes_and_repair(tmp_path):
    from tools.bench_history import validate

    path = tmp_path / "hist.jsonl"
    path.write_text(
        json.dumps({"metric": "a", "value": 1.0, "ts": 10.0}) + "\n"
        + json.dumps({"metric": "b", "value": "2.0"}) + "\n"  # repairable
        + "not json at all\n"  # dropped
        + json.dumps({"metric": "c", "value": 3.0, "ts": 30.0}) + "\n"
    )
    assert validate(str(path)) == 1  # flagged rows, no repair target
    out = tmp_path / "clean.jsonl"
    assert validate(str(path), repair_to=str(out)) == 1  # one row dropped
    rows = [json.loads(line) for line in out.read_text().splitlines()]
    assert [r["metric"] for r in rows] == ["a", "b", "c"]
    # the repaired middle row got an interpolated timestamp between its
    # stamped neighbors
    assert rows[1]["value"] == 2.0
    assert 10.0 <= rows[1]["ts"] <= 30.0 and rows[1]["_ts_repaired"]
    # a fully valid file is exit 0
    clean = tmp_path / "ok.jsonl"
    clean.write_text(json.dumps({"metric": "a", "ts": 1.0}) + "\n")
    assert validate(str(clean)) == 0


def _bench_row(ts, value, platform="tpu"):
    """A row in the shape bench.py prints today (stamped with the device
    it ran on), plus the timestamp a trajectory file adds."""
    return {
        "metric": "images/sec/chip resize(300x250 crop-fill)+smart-crop",
        "value": value, "unit": "images/sec", "vs_baseline": value / 1250.0,
        "platform": platform, "device_kind": "TPU v5 lite",
        "device_count": 1, "batch": 256, "scan_len": 10, "launches": 6,
        "setup_compile_s": 0.5, "kernel": "dense",
        "peak_rss_bytes": 1 << 30, "ts": ts,
    }


def test_bench_history_validates_rows_in_todays_bench_shape(tmp_path):
    """No trajectory is tracked any more (the old one held CPU runs under
    the chip's metric name and went in PR 21); the tolerant schema must
    still accept what bench.py prints now, device stamp included."""
    from tools.bench_history import check_row, validate

    rows = [_bench_row(1.0, 17000.0), _bench_row(2.0, 17100.5)]
    assert all(check_row(row) == [] for row in rows)
    path = tmp_path / "trajectory.jsonl"
    path.write_text("".join(json.dumps(row) + "\n" for row in rows))
    assert validate(str(path)) == 0


# ---------------------------------------------------------------------------
# offline replay (ISSUE 14 tentpole, offline half)


def test_replay_moves_knobs_on_recorded_evidence():
    from tools.autotune_replay import BOOT_POLICY, replay

    windows = [
        {"controllers": {"device": _ctrl(occ=0.1, wait=0.0)},
         "host": {}, "kernel_mode": "dense",
         "_row": {"metric": "m", "value": 100.0, "ts": 1.0}}
        for _ in range(3)
    ]
    result = replay(windows)
    assert result["windows"] == 3
    # one bounded step per window, never past the envelope
    assert [d["to"] for d in result["decisions"]] == [3.0, 2.0, 1.0]
    assert result["changed_knobs"] == {"device.deadline_ms": 1.0}
    assert result["boot_policy"] == BOOT_POLICY
    assert result["throughput_trend"]["samples"] == 3


def test_replay_flight_recorder_window_math(tmp_path):
    from tools.autotune_replay import _flight_windows

    records = [
        {
            "controller": "device", "occupancy": 2, "capacity": 16,
            "queue_wait_s": 0.0, "device_s": 0.01, "compile_hit": True,
            "kind": "primary",
        }
        for _ in range(20)
    ] + [
        {"controller": "host:fetch", "occupancy": 1, "capacity": 1,
         "queue_wait_s": 0.01, "kind": "host_stage"},
    ]
    dump = tmp_path / "dump.json"
    dump.write_text(json.dumps({"records": records}))
    windows = _flight_windows(str(dump), window=64)
    assert len(windows) == 1
    stats = windows[0]["controllers"]["device"]
    assert stats["window_batches"] == 20  # host_stage rows excluded
    assert stats["mean_occupancy"] == pytest.approx(2 / 16)
    assert stats["queue_wait_share"] == 0.0


def test_replay_e2e_on_real_repo_artifacts(tmp_path):
    """The replay tool on a bench trajectory (rows as bench.py prints
    them) and the repo's real perf baseline emits a policy proposal +
    candidate perf_gate baseline without error."""
    from tools.autotune_replay import main as replay_main

    out_dir = tmp_path / "autotune"
    history = tmp_path / "trajectory.jsonl"
    history.write_text("".join(
        json.dumps(_bench_row(float(i), 17000.0 + i)) + "\n"
        for i in range(3)
    ))
    assert replay_main(
        ["--history", str(history), "--out-dir", str(out_dir)]
    ) == 0
    proposal = json.loads((out_dir / "proposal.json").read_text())
    assert "proposed_policy" in proposal and "decisions" in proposal
    assert "envelopes" in proposal
    candidate = json.loads(
        (out_dir / "perf_baseline_candidate.json").read_text()
    )
    assert "autotune_candidate" in candidate
    assert "proposed_policy" in candidate["autotune_candidate"]
    # the candidate is the real baseline plus the annotation
    real = json.loads(
        open(
            os.path.join(
                os.path.dirname(os.path.dirname(__file__)),
                "benchmarks", "perf_baseline.json",
            )
        ).read()
    )
    assert candidate["schema"] == real.get("schema")


# ---------------------------------------------------------------------------
# HTTP surface: default-off byte identity + /debug/autotune gating


def _serve(tmp_path, coro_fn, **params_extra):
    from flyimg_tpu.service.app import make_app

    async def go():
        params = AppParameters({
            "tmp_dir": str(tmp_path / "tmp"),
            "upload_dir": str(tmp_path / "uploads"),
            **params_extra,
        })
        app = make_app(params)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            await coro_fn(client, app)
        finally:
            await client.close()

    asyncio.new_event_loop().run_until_complete(go())


def _png(tmp_path, name="src.png"):
    rng = np.random.default_rng(5)
    path = tmp_path / name
    path.write_bytes(
        encode(rng.integers(0, 255, (48, 64, 3), dtype=np.uint8), "png")
    )
    return str(path)


def test_default_off_no_metrics_and_debug_document(tmp_path):
    from flyimg_tpu.ops.resample import auto_band_frac, set_auto_band_frac

    src = _png(tmp_path)
    # a previous app's TUNED threshold must not leak into this one:
    # make_app resets it alongside set_kernel_mode
    set_auto_band_frac(0.5)

    async def scenario(client, app):
        assert auto_band_frac() == 1.0
        resp = await client.get(f"/upload/w_32,o_png/{src}")
        assert resp.status == 200
        text = await (await client.get("/metrics")).text()
        assert "flyimg_autotune" not in text
        doc = json.loads(await (await client.get("/debug/autotune")).text())
        assert doc["enabled"] is False
        assert doc["history"] == [] and doc["policy"] == {}

    _serve(tmp_path, scenario, debug=True)


def test_debug_autotune_is_404_without_debug(tmp_path):
    async def scenario(client, app):
        assert (await client.get("/debug/autotune")).status == 404
        assert (
            await client.post(
                "/debug/fleet/replicas", json={"replicas": []}
            )
        ).status == 404

    _serve(tmp_path, scenario, debug=False)


def test_enabled_tuner_binds_live_knobs_in_the_app(tmp_path):
    src = _png(tmp_path)
    clock = FakeClock()

    async def scenario(client, app):
        from flyimg_tpu.service.app import AUTOTUNER_KEY, METRICS_KEY

        resp = await client.get(f"/upload/w_32,o_png/{src}")
        assert resp.status == 200
        doc = json.loads(await (await client.get("/debug/autotune")).text())
        assert doc["enabled"] is True
        # every bound knob family reports a live value inside its envelope
        for name, value in doc["policy"].items():
            env = doc["envelopes"][name]
            assert env["lo"] <= value <= env["hi"], (name, value)
        assert "device.deadline_ms" in doc["policy"]
        assert "host.decode_workers" in doc["policy"]
        # synthetic sparse pressure -> one in-envelope adjustment that
        # the LIVE batcher policy reflects
        metrics = app[METRICS_KEY]
        for _ in range(24):
            metrics.record_batch_launch(
                "device", images=2, capacity=16, queue_wait_s=0.0,
                device_s=0.01, compile_hit=True,
            )
        clock.advance(11.0)
        assert (await client.get(f"/upload/w_32,o_png/{src}")).status == 200
        doc = json.loads(await (await client.get("/debug/autotune")).text())
        assert doc["policy"]["device.deadline_ms"] == 3.0
        assert app[AUTOTUNER_KEY].snapshot()["adjustments_total"] == 1

    _serve(
        tmp_path, scenario, debug=True, autotune_enable=True,
        autotune_interval_s=5.0, autotune_clock=clock,
        slo_latency_p99_ms=60000.0,
    )
