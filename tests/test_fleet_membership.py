"""Elastic fleet membership + fleet-wide warm start
(runtime/membership.py, runtime/warmstart.py, service wiring;
docs/fleet.md "Membership and elasticity"): marker TTL under skewed
clocks, wedged-replica staleness, crash detection with minimal
re-homing, graceful drain, degraded-not-dead, warm-start digest
validation (recompile-not-execute), the split-brain guard on the
manual escape hatches, and the all-knobs-off byte-identity pin."""

from __future__ import annotations

import asyncio
import json
import logging
import threading
import time

import pytest

from flyimg_tpu.appconfig import AppParameters
from flyimg_tpu.runtime import warmstart as warmstart_mod
from flyimg_tpu.runtime.fleet import rendezvous_owner
from flyimg_tpu.runtime.membership import FleetMembership, member_slug
from flyimg_tpu.runtime.metrics import MetricsRegistry
from flyimg_tpu.runtime.warmstart import (
    PROGRAMS_MANIFEST,
    WarmStartCache,
)
from flyimg_tpu.storage.local import LocalStorage
from flyimg_tpu.storage.tiered import MEMBER_PREFIX, member_name
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
    def __init__(self):
        self.calls = []

    def update_replicas(self, replicas, self_id=None, source="manual"):
        self.calls.append({
            "replicas": list(replicas), "self_id": self_id,
            "source": source,
        })
        return {"replicas": list(replicas)}


def _member(store, url, clock, *, ttl=15.0, beat=5.0, router=None,
            supervisor=None, warmstart=None, metrics=None, enabled=True):
    return FleetMembership(
        store, url, router or StubRouter(), enabled=enabled, ttl_s=ttl,
        heartbeat_s=beat, supervisor=supervisor, warmstart=warmstart,
        metrics=metrics, clock=clock,
    )


# ---------------------------------------------------------------------------
# marker protocol: slug, announce, watch, TTL, skew


def test_member_slug_is_flat_and_filesystem_safe():
    # LocalStorage basenames every object name — a slash in the slug
    # would silently collapse one replica's marker onto another's
    slug = member_slug("http://10.0.0.1:8080/base")
    assert "/" not in slug and ":" not in slug
    assert member_name(slug).startswith(MEMBER_PREFIX)


def test_announce_then_watch_converges_two_members(tmp_path):
    store = _store(tmp_path)
    clock = FakeClock()
    router_a = StubRouter()
    a = _member(store, "http://a:1", clock, router=router_a)
    b = _member(store, "http://b:2", clock)
    a.announce()
    b.announce()
    assert a.watch() == ["http://a:1", "http://b:2"]
    assert b.watch() == ["http://a:1", "http://b:2"]
    applied = router_a.calls[-1]
    assert applied["source"] == "membership"
    assert applied["self_id"] == "http://a:1"


def test_skewed_future_marker_stays_live(tmp_path):
    """A writer whose clock runs AHEAD of the reader produces a
    renewed_at in the reader's future: age clamps to zero, so skew can
    only extend a marker's life — never evict a healthy replica."""
    store = _store(tmp_path)
    clock = FakeClock()
    a = _member(store, "http://a:1", clock, ttl=10.0)
    a.announce()
    store.write(
        member_name("b-2"),
        json.dumps({
            "replica": "http://b:2", "status": "ready", "token": "t",
            "renewed_at": clock.now + 30.0,  # 30s in OUR future
            "ttl_s": 10.0,
        }).encode(),
    )
    assert a.watch() == ["http://a:1", "http://b:2"]
    # even as our clock advances, the marker only starts aging once we
    # pass its (future) renewal stamp
    clock.advance(35.0)
    a._write_marker()
    assert "http://b:2" in a.watch()
    clock.advance(11.0)
    a._write_marker()
    assert "http://b:2" not in a.watch()


def test_stale_but_unexpired_wedged_marker_included_until_ttl(tmp_path):
    """A wedged replica (process alive, beat thread stuck) leaves a
    stale-but-unexpired marker: peers keep it in the set until the TTL
    — liveness is the marker contract, not responsiveness — and drop
    it one TTL after its last renewal, at which point only ITS keys
    re-home."""
    store = _store(tmp_path)
    clock = FakeClock()
    a = _member(store, "http://a:1", clock, ttl=15.0, beat=5.0)
    b = _member(store, "http://b:2", clock, ttl=15.0, beat=5.0)
    a.announce()
    b.announce()
    assert a.watch() == ["http://a:1", "http://b:2"]
    # b wedges: no more heartbeats. One beat later its marker is stale
    # (older than heartbeat_s) but NOT expired — still a member.
    clock.advance(6.0)
    a._write_marker()
    assert "http://b:2" in a.watch()
    snap = a.snapshot()
    b_markers = [m for m in snap["markers"]
                 if m.get("replica") == "http://b:2"]
    assert b_markers and b_markers[0]["expired"] is False
    # past the TTL it ages out with no operator action
    clock.advance(10.0)
    a._write_marker()
    assert a.watch() == ["http://a:1"]


def test_malformed_marker_is_dead(tmp_path):
    store = _store(tmp_path)
    clock = FakeClock()
    a = _member(store, "http://a:1", clock)
    a.announce()
    store.write(member_name("junk"), b"not json")
    store.write(member_name("junk2"), json.dumps(
        {"replica": "http://x:9", "status": "ready",
         "renewed_at": "soon"}).encode())
    assert a.watch() == ["http://a:1"]


# ---------------------------------------------------------------------------
# crash detection: minimal re-homing


def test_sigkilled_replica_drops_within_one_ttl_and_only_its_keys_rehome(
    tmp_path,
):
    store = _store(tmp_path)
    clock = FakeClock()
    router = StubRouter()
    urls = ["http://a:1", "http://b:2", "http://c:3"]
    members = [
        _member(store, url, clock, ttl=15.0, beat=5.0,
                router=router if url == urls[0] else None)
        for url in urls
    ]
    for m in members:
        m.announce()
    assert members[0].watch() == sorted(urls)
    keys = [f"key-{i}" for i in range(200)]
    before = {k: rendezvous_owner(urls, k) for k in keys}
    # c "crashes" (SIGKILL: no drain, no delete) — a and b keep beating
    clock.advance(6.0)
    for m in members[:2]:
        m._write_marker()
    assert members[0].watch() == sorted(urls)  # within TTL: still there
    clock.advance(10.0)  # now > one TTL since c's last beat
    for m in members[:2]:
        m._write_marker()
    live = members[0].watch()
    assert live == ["http://a:1", "http://b:2"]
    after = {k: rendezvous_owner(live, k) for k in keys}
    moved = [k for k in keys if before[k] != after[k]]
    # ONLY the dead replica's keys re-home; every other key stays put
    assert all(before[k] == "http://c:3" for k in moved)
    assert all(after[k] != "http://c:3" for k in keys)
    # and the router swap came from the watcher
    assert router.calls[-1]["source"] == "membership"


def test_join_rehomes_only_new_replicas_keys(tmp_path):
    store = _store(tmp_path)
    clock = FakeClock()
    a = _member(store, "http://a:1", clock)
    b = _member(store, "http://b:2", clock)
    a.announce()
    b.announce()
    two = a.watch()
    keys = [f"key-{i}" for i in range(200)]
    before = {k: rendezvous_owner(two, k) for k in keys}
    c = _member(store, "http://c:3", clock)
    c.announce()
    three = a.watch()
    assert three == ["http://a:1", "http://b:2", "http://c:3"]
    after = {k: rendezvous_owner(three, k) for k in keys}
    moved = [k for k in keys if before[k] != after[k]]
    assert moved, "HRW must hand the joiner a share of keys"
    # the minimal-disruption property: every moved key moved TO the
    # joiner — no key shuffled between the incumbents
    assert all(after[k] == "http://c:3" for k in moved)


# ---------------------------------------------------------------------------
# graceful drain + degraded


def test_drain_leaves_set_immediately_and_close_releases_marker(tmp_path):
    store = _store(tmp_path)
    clock = FakeClock()
    a = _member(store, "http://a:1", clock)
    b = _member(store, "http://b:2", clock)
    a.announce()
    b.announce()
    assert a.watch() == ["http://a:1", "http://b:2"]
    b.begin_drain()
    # peers exclude a draining member on the NEXT watch beat — well
    # before any TTL elapses (clock did not move at all here)
    assert a.watch() == ["http://a:1"]
    # ... and the drainer stops counting itself as routable
    assert b.watch() == ["http://a:1"]
    b.close()
    names = store.list_names(MEMBER_PREFIX)
    assert member_name(member_slug("http://b:2")) not in names


def test_close_leaves_foreign_marker_for_its_owner(tmp_path):
    """Duplicate-replica-id config error: close() must not delete a
    marker another process overwrote (token-checked release, the
    L2Lease discipline)."""
    store = _store(tmp_path)
    clock = FakeClock()
    a1 = _member(store, "http://a:1", clock)
    a1.announce()
    a2 = _member(store, "http://a:1", clock)
    a2.announce()  # overwrites with ITS token
    a1.close()
    assert member_name(member_slug("http://a:1")) in store.list_names(
        MEMBER_PREFIX
    )


def test_duplicate_replica_id_logs_loudly(tmp_path, caplog):
    store = _store(tmp_path)
    clock = FakeClock()
    a1 = _member(store, "http://a:1", clock)
    a1.announce()
    a2 = _member(store, "http://a:1", clock)
    with caplog.at_level(logging.WARNING, logger="flyimg.fleet"):
        a2.announce()
        a1.announce()  # now a1 sees a2's token
    assert any("duplicate" in r.getMessage() for r in caplog.records)


def test_device_down_replica_heartbeats_degraded_not_dead(tmp_path):
    class StubSupervisor:
        def __init__(self):
            self.forced = False

        def cpu_forced(self):
            return self.forced

    store = _store(tmp_path)
    clock = FakeClock()
    sup = StubSupervisor()
    a = _member(store, "http://a:1", clock, supervisor=sup)
    b = _member(store, "http://b:2", clock)
    a.announce()
    b.announce()
    sup.forced = True
    a._write_marker()
    doc = json.loads(store.read(member_name(member_slug("http://a:1"))))
    assert doc["status"] == "degraded"
    # degraded stays IN the membership: the router's per-peer device
    # health gate routes owned keys around it without evicting it
    assert b.watch() == ["http://a:1", "http://b:2"]


# ---------------------------------------------------------------------------
# advisory IO: failures degrade, never break


def test_heartbeat_write_failure_counts_and_watch_failure_keeps_set(
    tmp_path,
):
    store = _store(tmp_path)
    clock = FakeClock()
    metrics = MetricsRegistry()
    a = _member(store, "http://a:1", clock, metrics=metrics)
    b = _member(store, "http://b:2", clock)
    a.announce()
    b.announce()
    assert a.watch() == ["http://a:1", "http://b:2"]
    def marker_io_down(**ctx):
        if ctx.get("op") in ("write", "list"):
            raise OSError("marker io down")
        return faults.PASS

    faults.install(
        faults.FaultInjector().plan("fleet.member", marker_io_down)
    )
    try:
        assert a._write_marker() is False
        assert a._heartbeat_failures == 1
        counter = metrics._counters.get(
            "flyimg_fleet_heartbeat_failures_total"
        )
        assert counter is not None and counter.value == 1.0
        # enumeration down: keep routing against the previous world
        assert a.watch() is None
        assert a.members() == ["http://a:1", "http://b:2"]
    finally:
        faults.clear()
    # recovery: next beat re-lists and the set is intact
    assert a.watch() == ["http://a:1", "http://b:2"]


def test_view_staleness_gauge_and_expired_view(tmp_path):
    """A frozen live view (marker listing failing, or island mode) is
    labeled, not silent: ``view_stale_seconds`` grows from the last
    successful listing and ``expired_view`` flips once the whole view
    could have expired unseen (docs/resilience.md)."""
    store = _store(tmp_path)
    clock = FakeClock()
    metrics = MetricsRegistry()
    a = _member(store, "http://a:1", clock, ttl=15.0, metrics=metrics)
    # before any successful listing, age counts from construction
    clock.advance(3.0)
    assert a.view_stale_seconds() == pytest.approx(3.0)
    a.announce()
    assert a.watch() == ["http://a:1"]
    assert a.view_stale_seconds() == 0.0
    assert a.expired_view() is False
    # listings now fail: the view freezes and its age keeps growing
    def listing_down(**_ctx):
        raise OSError("listing down")

    faults.install(
        faults.FaultInjector().plan("fleet.member", listing_down)
    )
    try:
        clock.advance(10.0)
        assert a.watch() is None
        assert a.view_stale_seconds() == pytest.approx(10.0)
        assert a.expired_view() is False  # still inside the TTL
        clock.advance(6.0)
        assert a.expired_view() is True  # every marker may have expired
        doc = a.snapshot()
        assert doc["view_stale_seconds"] == pytest.approx(16.0)
        assert doc["expired_view"] is True
    finally:
        faults.clear()
    # the gauge is registered (enabled-only) and reads the same age
    gauge = metrics._gauges.get("flyimg_fleet_view_stale_seconds")
    assert gauge is not None
    # recovery resets the age on the next successful listing
    assert a.watch() == ["http://a:1"]
    assert a.view_stale_seconds() == 0.0
    assert a.expired_view() is False
    # disabled: always fresh, never expired (off-is-off)
    off = _member(store, "http://a:1", clock, enabled=False)
    clock.advance(1000.0)
    assert off.view_stale_seconds() == 0.0
    assert off.expired_view() is False


# ---------------------------------------------------------------------------
# warm start: digest validation, seeding, publish merge


def _plan_and_layout():
    from flyimg_tpu.ops import compose
    from flyimg_tpu.spec.options import OptionsBag
    from flyimg_tpu.spec.plan import build_plan

    plan = build_plan(OptionsBag("w_16,h_12"), 64, 48)
    layout = compose.plan_layout(plan)
    return plan.device_plan(), layout


def test_recorder_captures_and_seeding_warms_the_program_cache(tmp_path):
    from flyimg_tpu.ops import compose

    store = _store(tmp_path)
    dp, layout = _plan_and_layout()
    in_shape = (48, 64)
    publisher = WarmStartCache(store, enabled=True)
    publisher.install()
    try:
        compose.invalidate_program_caches()
        compose.build_program(
            in_shape, layout.resample_out, layout.pad_canvas,
            layout.pad_offset, dp, None,
        )
        assert len(publisher.recorder) == 1
        publisher.publish()
    finally:
        warmstart_mod.uninstall()
    manifest = json.loads(store.read(PROGRAMS_MANIFEST))
    assert len(manifest["entries"]) == 1

    # a "fresh replica": empty program cache, seed from the manifest
    compose.invalidate_program_caches()
    seeder = WarmStartCache(store, enabled=True)
    stats = seeder.seed_programs()
    assert stats["seeded"] == 1 and stats["mismatch"] == 0
    info = compose.program_cache_info()
    assert info["single"]["entries"] == 1
    hits_before = compose.build_program.cache_info().hits
    compose.build_program(
        in_shape, layout.resample_out, layout.pad_canvas,
        layout.pad_offset, dp, None,
    )
    after = compose.build_program.cache_info()
    # the real render path lands on the seeded entry: a HIT, no miss
    assert after.hits == hits_before + 1
    compose.invalidate_program_caches()


def test_corrupted_manifest_entry_recompiles_not_executes(tmp_path):
    """The digest gate: a tampered entry is SKIPPED — nothing derived
    from it is compiled (let alone executed); the program it named
    simply compiles on demand at first request."""
    from flyimg_tpu.ops import compose

    store = _store(tmp_path)
    dp, layout = _plan_and_layout()
    publisher = WarmStartCache(store, enabled=True)
    publisher.note_single(
        (48, 64), layout.resample_out, layout.pad_canvas,
        layout.pad_offset, dp, None,
    )
    publisher.publish()
    doc = json.loads(store.read(PROGRAMS_MANIFEST))
    doc["entries"][0]["in_shape"] = [4096, 4096]  # tampered, stale digest
    store.write(PROGRAMS_MANIFEST, json.dumps(doc).encode())

    compose.invalidate_program_caches()
    seeder = WarmStartCache(store, enabled=True)
    stats = seeder.seed_programs()
    assert stats["mismatch"] == 1 and stats["seeded"] == 0
    assert compose.program_cache_info()["single"]["entries"] == 0


def test_unknown_kind_and_unknown_plan_fields_are_skipped(tmp_path):
    from flyimg_tpu.ops import compose
    from flyimg_tpu.runtime.warmstart import _entry_digest

    store = _store(tmp_path)
    alien = {"kind": "single", "in_shape": [8, 8], "resample_out": None,
             "pad_canvas": None, "pad_offset": [0, 0],
             "plan": {"not_a_field": 1}, "band_taps": None}
    alien["digest"] = _entry_digest(alien)
    store.write(PROGRAMS_MANIFEST, json.dumps({
        "version": 1,
        "entries": [{"kind": "mystery", "digest": "x"}, alien],
    }).encode())
    compose.invalidate_program_caches()
    seeder = WarmStartCache(store, enabled=True)
    stats = seeder.seed_programs()
    # the mystery kind is skipped outright; the alien plan field fails
    # reconstruction (a failed compile attempt, never an execution)
    assert stats["skipped"] == 1 and stats["failed"] == 1
    assert stats["seeded"] == 0


def test_publish_merges_by_digest_across_replicas(tmp_path):
    store = _store(tmp_path)
    dp, layout = _plan_and_layout()
    a = WarmStartCache(store, enabled=True)
    a.note_single((48, 64), layout.resample_out, layout.pad_canvas,
                  layout.pad_offset, dp, None)
    a.publish()
    b = WarmStartCache(store, enabled=True)
    b.note_single((96, 128), layout.resample_out, layout.pad_canvas,
                  layout.pad_offset, dp, None)
    # b also re-records a's entry: merge must dedupe by digest
    b.note_single((48, 64), layout.resample_out, layout.pad_canvas,
                  layout.pad_offset, dp, None)
    b.publish()
    manifest = json.loads(store.read(PROGRAMS_MANIFEST))
    assert len(manifest["entries"]) == 2


# ---------------------------------------------------------------------------
# service wiring: off-is-off, split-brain guard, readyz walk


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


def test_membership_off_is_byte_identical_serving(tmp_path):
    """The house rule, pinned: with the new knobs at their defaults an
    L2-armed app writes NO markers, spawns NO membership thread,
    registers NO membership/warm-start metrics, serves NO members
    field, and the manual replica-set endpoint still works."""
    from aiohttp.test_utils import TestClient, TestServer

    from flyimg_tpu.service.app import make_app

    shared = tmp_path / "shared"

    async def scenario():
        client = TestClient(TestServer(make_app(
            _app_params(tmp_path, "off", shared)
        )))
        await client.start_server()
        try:
            ready = await client.get("/readyz")
            assert json.loads(await ready.text()) == {"status": "ok"}
            metrics_text = await (await client.get("/metrics")).text()
            for name in ("flyimg_fleet_members",
                         "flyimg_fleet_heartbeat_failures_total",
                         "flyimg_fleet_membership_transitions_total",
                         "flyimg_warmstart_programs_total"):
                assert name not in metrics_text
            assert not any(
                t.name == "flyimg-membership"
                for t in threading.enumerate()
            )
            manual = await client.post(
                "/debug/fleet/replicas",
                json={"replicas": ["http://x:1", "http://y:2"]},
            )
            assert manual.status == 200
        finally:
            await client.close()
        assert store_names() == []

    def store_names():
        import os

        if not shared.exists():
            return []
        return [n for n in os.listdir(shared)
                if n.endswith(".member") or "warmstart" in n]

    _run(scenario())


def test_membership_on_marks_active_and_guards_escape_hatches(tmp_path):
    from aiohttp.test_utils import TestClient, TestServer

    from flyimg_tpu.service.app import MEMBERSHIP_KEY, make_app

    shared = tmp_path / "shared"

    async def scenario():
        app = make_app(_app_params(
            tmp_path, "on", shared,
            fleet_membership_enable=True,
            fleet_membership_heartbeat_s=30.0,  # no beat during the test
        ))
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            assert app[MEMBERSHIP_KEY].active
            ready = json.loads(await (await client.get("/readyz")).text())
            assert ready == {"status": "ok", "members": 1}
            denied = await client.post(
                "/debug/fleet/replicas",
                json={"replicas": ["http://x:1", "http://y:2"]},
            )
            assert denied.status == 400
            assert "membership" in await denied.text()
            fleet_doc = json.loads(
                await (await client.get("/debug/fleet")).text()
            )
            assert fleet_doc["status"] == "ready"
            assert fleet_doc["members"] == [app[MEMBERSHIP_KEY].replica_id]
            assert fleet_doc["warmstart"]["enabled"] is False
            # the drain walk: on_shutdown flips readiness AND the marker
            await app.shutdown()
            drain = await client.get("/readyz")
            assert drain.status == 503
            assert json.loads(await drain.text())["status"] == "draining"
            marker = json.loads((shared / member_name(
                member_slug(app[MEMBERSHIP_KEY].replica_id)
            )).read_bytes())
            assert marker["status"] == "draining"
        finally:
            await client.close()
        # close() released the marker on cleanup
        assert not any(
            n.endswith(".member")
            for n in __import__("os").listdir(shared)
        )

    _run(scenario())


def test_membership_requires_listing_capable_shared_tier(tmp_path):
    class NoListStorage:
        pass

    m = FleetMembership(
        NoListStorage(), "http://a:1", StubRouter(), enabled=True,
    )
    assert not m.enabled and not m.active
