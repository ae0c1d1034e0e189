"""Fleet observatory (runtime/observatory.py, service wiring;
docs/fleet.md "Fleet observatory & autoscaling signal"): signal-digest
marker failure modes under the membership liveness rules (stale
excluded + counted, corrupt/alien counted + skipped, clock-skewed
publishers clamped, IO failures degraded to the previous rollup),
rollup assembly (worst + weighted burn, launch-weighted occupancy,
pressure histogram), the deterministic recommender (hysteresis,
cooldown, min/max bounds), scale-in drain self-selection, and the
off-is-off byte-identity pin."""

from __future__ import annotations

import asyncio
import json
import os

import pytest

from flyimg_tpu.appconfig import AppParameters
from flyimg_tpu.runtime.membership import FleetMembership, member_slug
from flyimg_tpu.runtime.metrics import MetricsRegistry
from flyimg_tpu.runtime.observatory import (
    DIGEST_VERSION,
    AutoscaleRecommender,
    FleetObservatory,
    SignalWindow,
    reuse_signal_fn,
)
from flyimg_tpu.storage.local import LocalStorage
from flyimg_tpu.storage.tiered import DIGEST_SUFFIX, digest_name
from flyimg_tpu.testing import faults


def _store(tmp_path, sub="shared"):
    return LocalStorage(AppParameters({"upload_dir": str(tmp_path / sub)}))


class FakeClock:
    def __init__(self, now=1_000_000.0):
        self.now = float(now)

    def __call__(self):
        return self.now

    def advance(self, dt):
        self.now += float(dt)


class StubRouter:
    def update_replicas(self, replicas, self_id=None, source="manual"):
        return {"replicas": list(replicas)}


def _member(store, url, clock, *, ttl=15.0):
    return FleetMembership(
        store, url, StubRouter(), enabled=True, ttl_s=ttl,
        heartbeat_s=5.0, clock=clock,
    )


def _obs(store, url, clock, *, ttl=15.0, metrics=None, recommender=None,
         drain=False, membership=None, **kw):
    membership = membership or _member(store, url, clock, ttl=ttl)
    return FleetObservatory(
        store, url, enabled=True, ttl_s=ttl, membership=membership,
        metrics=metrics, recommender=recommender, drain_enabled=drain,
        clock=clock, **kw,
    )


def _skips(metrics, reason):
    counter = metrics._counters.get(
        f'flyimg_fleet_digest_skipped_total{{reason="{reason}"}}'
    )
    return counter.value if counter is not None else 0.0


# ---------------------------------------------------------------------------
# digest marker protocol: publish, collect, TTL, skew, failure modes


def test_publish_then_collect_round_trips_both_digests(tmp_path):
    store = _store(tmp_path)
    clock = FakeClock()
    a = _obs(store, "http://a:1", clock)
    b = _obs(store, "http://b:2", clock)
    assert a.publish() and b.publish()
    digests = a.collect()
    assert sorted(digests) == ["http://a:1", "http://b:2"]
    doc = digests["http://b:2"]
    assert doc["v"] == DIGEST_VERSION
    assert doc["status"] == "ready"
    assert doc["signals"]["backend"] == "device"
    # the marker is a distinct family from the member marker: one slug,
    # two suffixes — membership liveness and signal telemetry never
    # collide in the shared tier
    raw = store.read(digest_name(member_slug("http://a:1")))
    assert json.loads(raw.decode())["replica"] == "http://a:1"


def test_stale_digest_excluded_from_rollup_and_counted(tmp_path):
    store = _store(tmp_path)
    clock = FakeClock()
    metrics = MetricsRegistry()
    a = _obs(store, "http://a:1", clock, ttl=10.0, metrics=metrics)
    b = _obs(store, "http://b:2", clock, ttl=10.0)
    a.on_beat()
    b.on_beat()
    a.on_beat()
    assert a.snapshot()["rollup"]["replicas"] == 2
    # b wedges: its digest stops renewing. One TTL later it is stale —
    # excluded from the rollup (counted), while a's own re-publish on
    # the same beat keeps a live.
    clock.advance(11.0)
    a.on_beat()
    snap = a.snapshot()
    assert sorted(snap["digests"]) == ["http://a:1"]
    assert snap["rollup"]["replicas"] == 1
    assert _skips(metrics, "stale") >= 1.0


def test_corrupt_and_alien_digests_counted_and_skipped(tmp_path):
    store = _store(tmp_path)
    clock = FakeClock()
    metrics = MetricsRegistry()
    a = _obs(store, "http://a:1", clock, metrics=metrics)
    a.publish()
    # corrupt: not JSON at all
    store.write(digest_name("b-2"), b"not json")
    # alien: a future schema version this reader does not speak
    store.write(digest_name("c-3"), json.dumps({
        "v": DIGEST_VERSION + 1, "replica": "http://c:3",
        "renewed_at": clock.now, "ttl_s": 15.0, "signals": {},
    }).encode())
    # alien: no replica identity to roll up under
    store.write(digest_name("d-4"), json.dumps({
        "v": DIGEST_VERSION, "replica": "",
        "renewed_at": clock.now, "ttl_s": 15.0, "signals": {},
    }).encode())
    digests = a.collect()
    # the bad markers are skipped, the good one still collected — one
    # peer's corruption never blinds the reader to the rest
    assert sorted(digests) == ["http://a:1"]
    assert _skips(metrics, "corrupt") == 1.0
    assert _skips(metrics, "alien") == 2.0


def test_skewed_future_digest_stays_live_until_it_ages_out(tmp_path):
    """A publisher whose clock runs AHEAD of the reader stamps a
    renewed_at in the reader's future: age clamps to zero, so skew can
    only extend a digest's life — never evict a healthy publisher from
    the rollup (the membership marker rule, verbatim)."""
    store = _store(tmp_path)
    clock = FakeClock()
    metrics = MetricsRegistry()
    a = _obs(store, "http://a:1", clock, ttl=10.0, metrics=metrics)
    a.publish()
    store.write(digest_name("b-2"), json.dumps({
        "v": DIGEST_VERSION, "replica": "http://b:2", "status": "ready",
        "renewed_at": clock.now + 30.0,  # 30s in OUR future
        "ttl_s": 10.0, "signals": {},
    }).encode())
    assert sorted(a.collect()) == ["http://a:1", "http://b:2"]
    # aging only starts once the reader's clock passes the stamp
    clock.advance(35.0)
    a.publish()
    assert "http://b:2" in a.collect()
    clock.advance(11.0)
    a.publish()
    assert "http://b:2" not in a.collect()
    assert _skips(metrics, "stale") == 1.0


def test_publish_failure_counted_and_absorbed(tmp_path):
    store = _store(tmp_path)
    clock = FakeClock()
    metrics = MetricsRegistry()
    a = _obs(store, "http://a:1", clock, metrics=metrics)

    def digest_write_down(**ctx):
        if ctx.get("op") == "digest":
            raise OSError("digest io down")
        return faults.PASS

    faults.install(
        faults.FaultInjector().plan("fleet.member", digest_write_down)
    )
    try:
        assert a.publish() is False
        counter = metrics._counters.get(
            "flyimg_fleet_digest_failures_total"
        )
        assert counter is not None and counter.value == 1.0
        assert a.snapshot()["publish_failures"] == 1
    finally:
        faults.clear()
    # recovery: the next beat writes clean
    assert a.publish() is True


def test_listing_failure_keeps_previous_rollup(tmp_path):
    store = _store(tmp_path)
    clock = FakeClock()
    a = _obs(store, "http://a:1", clock)
    b = _obs(store, "http://b:2", clock)
    b.publish()
    a.on_beat()
    assert a.snapshot()["rollup"]["replicas"] == 2

    def listing_down(**ctx):
        if ctx.get("op") == "digest-list":
            raise OSError("enumeration down")
        return faults.PASS

    faults.install(
        faults.FaultInjector().plan("fleet.member", listing_down)
    )
    try:
        # the beat survives AND the rollup degrades to the last known
        # world instead of an empty fleet
        a.on_beat()
        snap = a.snapshot()
        assert snap["rollup"]["replicas"] == 2
        assert sorted(snap["digests"]) == ["http://a:1", "http://b:2"]
    finally:
        faults.clear()


def test_close_is_token_checked(tmp_path):
    store = _store(tmp_path)
    clock = FakeClock()
    a = _obs(store, "http://a:1", clock)
    a.publish()
    name = digest_name(member_slug("http://a:1"))
    # a foreign process (config error: shared replica id) overwrote the
    # slot — our close must leave THEIR digest for its owner
    store.write(name, json.dumps({
        "v": DIGEST_VERSION, "replica": "http://a:1", "token": "foreign",
        "renewed_at": clock.now, "ttl_s": 15.0, "signals": {},
    }).encode())
    a.close()
    assert store.read(name) is not None
    # our own digest is released
    b = _obs(store, "http://b:2", clock)
    b.publish()
    b.close()
    with pytest.raises(Exception):
        b.storage.read(digest_name(member_slug("http://b:2")))


def test_observatory_requires_membership_substrate(tmp_path):
    store = _store(tmp_path)
    off_member = FleetMembership(
        store, "http://a:1", StubRouter(), enabled=False,
    )
    obs = FleetObservatory(
        store, "http://a:1", enabled=True, membership=off_member,
    )
    assert not obs.enabled
    assert obs.publish() is False and obs.collect() is None


# ---------------------------------------------------------------------------
# rollup assembly


def test_rollup_weighted_aggregates_and_status_counts(tmp_path):
    obs = _obs(_store(tmp_path), "http://a:1", FakeClock())
    rollup = obs._assemble_rollup({
        "http://a:1": {"status": "ready", "signals": {
            "burn_fast_norm": 0.2, "burn_slow_norm": 0.1,
            "window_requests": 100.0, "occupancy": 0.9,
            "launches_delta": 30.0, "brownout_level": 0,
        }},
        "http://b:2": {"status": "degraded", "signals": {
            "burn_fast_norm": 1.5, "burn_slow_norm": 0.4,
            "window_requests": 300.0, "occupancy": 0.3,
            "launches_delta": 10.0, "brownout_level": 2,
        }},
        "http://c:3": {"status": "draining", "signals": {}},
    })
    assert rollup["replicas"] == 3
    assert rollup["by_status"] == {
        "ready": 1, "degraded": 1, "draining": 1,
    }
    # draining members are not routable capacity
    assert rollup["routable"] == 2
    # worst = the max over each digest's max(fast, slow) norm
    assert rollup["burn_worst"] == 1.5
    # request-weighted: the loaded replica's burn dominates, the idle
    # one (weight floor 1.0) cannot wash it out
    assert rollup["burn_weighted"] == round(
        (0.2 * 100 + 1.5 * 300) / 401.0, 4
    )
    # occupancy weighted by recent launches, not by replica count
    assert rollup["occupancy"] == round(
        (0.9 * 30 + 0.3 * 10) / 41.0, 4
    )
    assert rollup["pressure_levels"]["normal"] == 2
    assert rollup["pressure_levels"]["brownout"] == 1
    assert rollup["brownout_worst"] == 2
    assert rollup["ready_members"] == ["http://a:1"]


# ---------------------------------------------------------------------------
# the recommender: pure, deterministic, hysteresis + cooldown + bounds


PRESSURE = {"routable": 2, "burn_worst": 2.0, "occupancy": 0.2,
            "brownout_worst": 0}
QUIET = {"routable": 2, "burn_worst": 0.1, "occupancy": 0.1,
         "brownout_worst": 0}
BETWEEN = {"routable": 2, "burn_worst": 0.7, "occupancy": 0.2,
           "brownout_worst": 0}


def test_recommender_thresholds_and_bounds():
    r = AutoscaleRecommender(min_replicas=1, max_replicas=4)
    out = r.decide(PRESSURE, 0.0)
    assert out["action"] == "scale_out" and out["delta"] == 1
    assert "worst burn" in out["reason"]
    # same pure inputs, same answer on a fresh instance — every
    # replica reaches the fleet's decision with no coordinator
    assert AutoscaleRecommender(
        min_replicas=1, max_replicas=4
    ).decide(PRESSURE, 0.0)["action"] == "scale_out"
    # bounds beat pressure
    capped = AutoscaleRecommender(max_replicas=2).decide(PRESSURE, 0.0)
    assert capped["action"] == "hold" and "max_replicas" in capped["reason"]
    floored = AutoscaleRecommender(min_replicas=2).decide(QUIET, 0.0)
    assert floored["action"] == "hold" and "min_replicas" in floored["reason"]
    # an occupancy or brownout trigger scales out on its own
    assert AutoscaleRecommender().decide(
        {"routable": 2, "burn_worst": 0.0, "occupancy": 0.95,
         "brownout_worst": 0}, 0.0
    )["action"] == "scale_out"
    assert AutoscaleRecommender().decide(
        {"routable": 2, "burn_worst": 0.0, "occupancy": 0.0,
         "brownout_worst": 2}, 0.0
    )["action"] == "scale_out"


def test_recommender_hysteresis_band_holds():
    r = AutoscaleRecommender(burn_out=1.0, burn_in=0.5)
    out = r.decide(BETWEEN, 0.0)
    assert out["action"] == "hold" and "hysteresis" in out["reason"]


def test_recommender_never_scales_on_missing_data():
    out = AutoscaleRecommender().decide({"routable": 0}, 0.0)
    assert out["action"] == "hold"
    assert "no live signal digests" in out["reason"]


def test_recommender_cooldown_gates_flips_not_holds():
    r = AutoscaleRecommender(cooldown_s=60.0)
    assert r.decide(PRESSURE, 0.0)["action"] == "scale_out"
    # a flip straight to the opposite action inside the cooldown is
    # deferred (reported as hold with the dwell remaining)...
    deferred = r.decide(QUIET, 10.0)
    assert deferred["action"] == "hold" and "cooldown" in deferred["reason"]
    # ...and adopted once the dwell passes
    assert r.decide(QUIET, 70.0)["action"] == "scale_in"
    # dropping to hold is IMMEDIATE — a stale scale signal must never
    # outlive its evidence — and restarts the dwell for the next flip
    r2 = AutoscaleRecommender(cooldown_s=60.0)
    assert r2.decide(PRESSURE, 0.0)["action"] == "scale_out"
    assert r2.decide(BETWEEN, 10.0)["action"] == "hold"
    assert r2.decide(PRESSURE, 30.0)["action"] == "hold"  # 40s dwell left
    assert r2.decide(PRESSURE, 71.0)["action"] == "scale_out"


def test_recommendation_is_a_level_not_an_edge():
    """The standing recommendation persists while its evidence does —
    an external scaler polling the gauge at any phase sees it."""
    r = AutoscaleRecommender(cooldown_s=60.0)
    for t in (0.0, 5.0, 10.0, 15.0):
        assert r.decide(PRESSURE, t)["action"] == "scale_out"


# ---------------------------------------------------------------------------
# the full beat: rollup -> recommendation -> drain self-selection


def test_on_beat_flips_recommendation_and_transition_counter(tmp_path):
    store = _store(tmp_path)
    clock = FakeClock()
    metrics = MetricsRegistry()
    recommender = AutoscaleRecommender(
        min_replicas=1, max_replicas=4, cooldown_s=0.0,
    )
    a = _obs(store, "http://a:1", clock, metrics=metrics,
             recommender=recommender)
    # quiet single replica at min bound -> hold (no transition: the
    # initial state is already hold)
    a.on_beat()
    assert a.snapshot()["recommendation"]["action"] == "hold"
    # a peer under fire appears -> scale_out, one edge-triggered count
    store.write(digest_name("b-2"), json.dumps({
        "v": DIGEST_VERSION, "replica": "http://b:2", "status": "ready",
        "renewed_at": clock.now, "ttl_s": 15.0,
        "signals": {"burn_fast_norm": 3.0, "window_requests": 500.0},
    }).encode())
    a.on_beat()
    assert a.snapshot()["recommendation"]["action"] == "scale_out"
    a.on_beat()  # still out: level, not edge — no second count
    flips = metrics._counters.get(
        'flyimg_fleet_autoscale_transitions_total{to="scale_out"}'
    )
    assert flips is not None and flips.value == 1.0


def test_scale_in_drains_exactly_the_last_sorted_ready_member(tmp_path):
    store = _store(tmp_path)
    clock = FakeClock()
    recommenders = {
        url: AutoscaleRecommender(min_replicas=1, cooldown_s=0.0)
        for url in ("http://a:1", "http://b:2", "http://c:3")
    }
    fleet = {
        url: _obs(store, url, clock, drain=True,
                  recommender=recommenders[url])
        for url in recommenders
    }
    for obs in fleet.values():
        obs.publish()
    # every replica evaluates the same quiet rollup; only the last
    # sorted ready member self-selects to drain — no coordinator, no
    # double-drain
    for obs in fleet.values():
        obs.on_beat()
        assert obs.snapshot()["recommendation"]["action"] == "scale_in"
    assert fleet["http://a:1"].membership.current_status() == "ready"
    assert fleet["http://b:2"].membership.current_status() == "ready"
    assert fleet["http://c:3"].membership.current_status() == "draining"


def test_drain_honors_min_replicas_against_ready_members(tmp_path):
    store = _store(tmp_path)
    clock = FakeClock()
    a = _obs(store, "http://a:1", clock, drain=True,
             recommender=AutoscaleRecommender(
                 min_replicas=2, cooldown_s=0.0))
    b = _obs(store, "http://b:2", clock, drain=True,
             recommender=AutoscaleRecommender(
                 min_replicas=2, cooldown_s=0.0))
    a.publish()
    b.publish()
    for obs in (a, b):
        obs.on_beat()
    # scale_in is already suppressed by the routable bound, and even a
    # forced nomination path would refuse: 2 ready <= min_replicas
    assert a.membership.current_status() == "ready"
    assert b.membership.current_status() == "ready"
    a._maybe_drain({"ready_members": ["http://a:1", "http://b:2"]})
    b._maybe_drain({"ready_members": ["http://a:1", "http://b:2"]})
    assert b.membership.current_status() == "ready"


def test_drain_disabled_surfaces_recommendation_only(tmp_path):
    store = _store(tmp_path)
    clock = FakeClock()
    a = _obs(store, "http://a:1", clock, drain=False,
             recommender=AutoscaleRecommender(
                 min_replicas=0, cooldown_s=0.0))
    a.publish()
    a.on_beat()
    assert a.snapshot()["recommendation"]["action"] == "scale_in"
    assert a.membership.current_status() == "ready"


# ---------------------------------------------------------------------------
# signal window: per-consumer recency diffing


def test_signal_window_is_not_shared_between_consumers():
    """assemble() diffs recorded_total per instance — the observatory
    and the telemetry warehouse each own a window, or every
    launches_delta halves."""

    class Stats:
        def __init__(self):
            self.total = 0.0

        def stats(self):
            return {"recorded_total": self.total, "mean_occupancy": 0.5}

    class Registry:
        def __init__(self):
            self.s = Stats()

        def batch_efficiency(self, name):
            return self.s

    registry = Registry()
    w1, w2 = SignalWindow(), SignalWindow()
    w1.attach(metrics=registry)
    w2.attach(metrics=registry)
    w1.assemble()
    w2.assemble()
    registry.s.total = 10.0
    assert w1.assemble()["controllers"]["device"]["launches_delta"] == 10.0
    # the second consumer sees the SAME delta, not the leftovers
    assert w2.assemble()["controllers"]["device"]["launches_delta"] == 10.0


def test_reuse_signal_fn_windows_per_read():
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
    # lifetime ratio would still read 40/80 = 0.5)
    bump("hit", 40)
    second = read()
    assert second["attempts"] == 40 and second["hit_ratio"] == 1.0
    # quiet period: no attempts, no evidence
    third = read()
    assert third["attempts"] == 0 and third["hit_ratio"] is None


# ---------------------------------------------------------------------------
# service wiring: off-is-off, /debug/fleet/status


def _run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


def _app_params(tmp_path, sub, shared, **extra):
    doc = {
        "tmp_dir": str(tmp_path / sub / "tmp"),
        "upload_dir": str(tmp_path / sub / "uploads"),
        "debug": True,
        "l2_enable": True,
        "l2_upload_dir": str(shared),
        "fleet_replica_id": f"http://127.0.0.1:1{hash(sub) % 1000:03d}",
    }
    doc.update(extra)
    return AppParameters(doc)


def test_observatory_off_is_byte_identical_serving(tmp_path):
    """The house rule, pinned: with membership ON but the observatory
    at its default (off), an app writes NO digest markers, registers NO
    flyimg_fleet_* observatory families, and /debug/fleet/status still
    answers (reporting the observatory disabled) for operators."""
    from aiohttp.test_utils import TestClient, TestServer

    from flyimg_tpu.service.app import OBSERVATORY_KEY, make_app

    shared = tmp_path / "shared"

    async def scenario():
        app = make_app(_app_params(
            tmp_path, "off", shared,
            fleet_membership_enable=True,
            fleet_membership_heartbeat_s=30.0,
        ))
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            assert not app[OBSERVATORY_KEY].enabled
            metrics_text = await (await client.get("/metrics")).text()
            for name in ("flyimg_fleet_replicas",
                         "flyimg_fleet_burn_worst",
                         "flyimg_fleet_burn_weighted",
                         "flyimg_fleet_occupancy",
                         "flyimg_fleet_pressure_level",
                         "flyimg_fleet_autoscale_recommendation",
                         "flyimg_fleet_autoscale_delta",
                         "flyimg_fleet_digest_"):
                assert name not in metrics_text
            status = json.loads(
                await (await client.get("/debug/fleet/status")).text()
            )
            assert status["observatory"]["enabled"] is False
            assert status["membership"]["enabled"] is True
        finally:
            await client.close()
        assert not any(
            n.endswith(DIGEST_SUFFIX) for n in os.listdir(shared)
        )

    _run(scenario())


def test_fleet_status_endpoint_joins_digests_rollup_and_membership(
    tmp_path,
):
    from aiohttp.test_utils import TestClient, TestServer

    from flyimg_tpu.service.app import OBSERVATORY_KEY, make_app

    shared = tmp_path / "shared"

    async def scenario():
        app = make_app(_app_params(
            tmp_path, "on", shared,
            fleet_membership_enable=True,
            fleet_membership_heartbeat_s=30.0,  # only the start() beat
            fleet_observatory_enable=True,
        ))
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            observatory = app[OBSERVATORY_KEY]
            assert observatory.enabled
            replica = observatory.replica_id
            status = json.loads(
                await (await client.get("/debug/fleet/status")).text()
            )
            # the first digest publishes WITH the announce: one beat in,
            # the replica already sees itself
            assert replica in status["observatory"]["digests"]
            rollup = status["observatory"]["rollup"]
            assert rollup["replicas"] == 1 and rollup["routable"] == 1
            assert status["observatory"]["recommendation"]["action"] in (
                "hold", "scale_in",
            )
            assert status["membership"]["members"] == [replica]
            assert status["routing"]["replica_id"] == replica
            metrics_text = await (await client.get("/metrics")).text()
            assert 'flyimg_fleet_replicas{status="ready"} 1' in metrics_text
            assert "flyimg_fleet_autoscale_recommendation" in metrics_text
        finally:
            await client.close()
        # cleanup released the digest marker alongside the member one
        assert not any(
            n.endswith(DIGEST_SUFFIX) for n in os.listdir(shared)
        )

    _run(scenario())


def test_autoscale_drain_nomination_flips_readyz(tmp_path):
    """An observatory scale-in nomination calls membership.begin_drain()
    directly — no app shutdown involved — and /readyz must agree
    (503 draining) so the external scaler pulls the nominated replica;
    the drain walk is ready -> draining -> gone whichever initiator
    started it."""
    from aiohttp.test_utils import TestClient, TestServer

    from flyimg_tpu.service.app import MEMBERSHIP_KEY, make_app

    async def scenario():
        app = make_app(_app_params(
            tmp_path, "nominated", tmp_path / "shared",
            fleet_membership_enable=True,
            fleet_membership_heartbeat_s=30.0,
        ))
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            assert (await client.get("/readyz")).status == 200
            # what _maybe_drain does when this replica self-selects
            app[MEMBERSHIP_KEY].begin_drain()
            draining = await client.get("/readyz")
            assert draining.status == 503
            assert json.loads(await draining.text())["status"] == "draining"
        finally:
            await client.close()

    _run(scenario())


def test_fleet_status_endpoint_is_debug_gated(tmp_path):
    from aiohttp.test_utils import TestClient, TestServer

    from flyimg_tpu.service.app import make_app

    async def scenario():
        client = TestClient(TestServer(make_app(_app_params(
            tmp_path, "gated", tmp_path / "shared", debug=False,
        ))))
        await client.start_server()
        try:
            assert (await client.get("/debug/fleet/status")).status == 404
        finally:
            await client.close()

    _run(scenario())
