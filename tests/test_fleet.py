"""Fleet serving tier (runtime/fleet.py + storage/tiered.py +
service wiring; docs/fleet.md): rendezvous routing, owner proxying with
hop/loop protection and owner-down fallback, the handler's cross-replica
lease coalescing (leader / follower / steal / deadline), cross-replica
derivative reuse through shared manifests, replica attribution
(header / span / log), and the all-knobs-off byte-identity pin."""

from __future__ import annotations

import asyncio
import json
import logging
import socket
import threading
import time

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from flyimg_tpu.appconfig import AppParameters
from flyimg_tpu.codecs import encode
from flyimg_tpu.exceptions import (
    DeadlineExceededException,
    ServiceUnavailableException,
)
from flyimg_tpu.runtime.fleet import (
    HOP_HEADER,
    FleetRouter,
    rendezvous_owner,
    route_key,
)
from flyimg_tpu.runtime.metrics import MetricsRegistry
from flyimg_tpu.runtime.resilience import Deadline
from flyimg_tpu.service.handler import ImageHandler
from flyimg_tpu.storage import make_storage
from flyimg_tpu.storage.local import LocalStorage
from flyimg_tpu.storage.tiered import TieredStorage, lease_name


def _gradient(w=192, h=144):
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
    return np.stack(
        [
            xx * (255.0 / max(w - 1, 1)),
            yy * (255.0 / max(h - 1, 1)),
            (xx + yy) * (255.0 / max(w + h - 2, 1)),
        ],
        axis=-1,
    ).astype(np.uint8)


def _counter(metrics, name):
    counter = metrics._counters.get(name)
    return counter.value if counter is not None else 0.0


def _lease_count(metrics, outcome):
    return _counter(
        metrics, f'flyimg_l2_lease_total{{outcome="{outcome}"}}'
    )


def _run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


# ---------------------------------------------------------------------------
# rendezvous routing (pure units)


REPLICAS = [f"http://10.0.0.{i}:8080" for i in range(1, 5)]


def test_rendezvous_owner_deterministic_and_order_free():
    key = route_key("w_200,h_200,c_1", "https://example.com/a.jpg")
    owner = rendezvous_owner(REPLICAS, key)
    assert owner in REPLICAS
    assert rendezvous_owner(list(reversed(REPLICAS)), key) == owner
    assert rendezvous_owner(REPLICAS, key) == owner  # stable across calls


def test_rendezvous_distribution_is_balanced():
    keys = [route_key(f"w_{100 + i}", "https://e.com/a.jpg")
            for i in range(1000)]
    counts = {r: 0 for r in REPLICAS}
    for key in keys:
        counts[rendezvous_owner(REPLICAS, key)] += 1
    for count in counts.values():
        # 1000 keys over 4 replicas: each within a generous band of 250
        assert 150 <= count <= 350, counts


def test_rendezvous_minimal_disruption_on_replica_loss():
    """The HRW property the static-set design banks on: removing one
    replica re-homes ONLY the keys it owned."""
    keys = [route_key(f"w_{i}", "https://e.com/a.jpg") for i in range(400)]
    before = {key: rendezvous_owner(REPLICAS, key) for key in keys}
    survivors = REPLICAS[:-1]
    moved = 0
    for key in keys:
        after = rendezvous_owner(survivors, key)
        if before[key] == REPLICAS[-1]:
            moved += 1
            assert after in survivors
        else:
            assert after == before[key]
    assert moved > 0  # the lost replica did own some keys


def test_route_key_distinct_per_derived_output():
    a = route_key("w_200", "https://e.com/a.jpg")
    b = route_key("w_201", "https://e.com/a.jpg")
    c = route_key("w_200", "https://e.com/b.jpg")
    assert len({a, b, c}) == 3
    assert a == route_key("w_200", "https://e.com/a.jpg")


def test_route_key_plan_affinity_projection():
    """Encode-only options (quality, mozjpeg, sampling, strip, lossless,
    refresh) share a compiled program, so they share an owner — the
    same-plan concentration the batch controller banks on. Token order
    never matters; geometry always does."""
    base = route_key("w_200,h_150,c_1", "https://e.com/a.jpg")
    assert route_key("w_200,h_150,c_1,q_55", "https://e.com/a.jpg") == base
    assert route_key(
        "q_80,moz_0,w_200,h_150,c_1,sf_2x2,st_1,rf_1",
        "https://e.com/a.jpg",
    ) == base
    assert route_key("h_150,c_1,w_200", "https://e.com/a.jpg") == base
    assert route_key("w_201,h_150,c_1", "https://e.com/a.jpg") != base


def test_router_enabled_rules():
    assert not FleetRouter([], "").enabled
    assert not FleetRouter(["http://a"], "http://a").enabled  # one replica
    assert not FleetRouter(["http://a", "http://b"], "").enabled  # no self
    router = FleetRouter(["http://a", "http://b"], "http://a")
    assert router.enabled and router.proxies
    local = FleetRouter(["http://a", "http://b"], "http://a", mode="local")
    assert local.enabled and not local.proxies


def test_router_is_owner_partitions():
    router_a = FleetRouter(["http://a", "http://b"], "http://a")
    router_b = FleetRouter(["http://a", "http://b"], "http://b")
    keys = [route_key(f"w_{i}", "s.jpg") for i in range(64)]
    for key in keys:
        assert router_a.is_owner(key) != router_b.is_owner(key)


# ---------------------------------------------------------------------------
# handler-level cross-replica coalescing (two handlers, one shared L2)


def _replica(tmp_path, sub, shared, replica_id, **over):
    params = AppParameters({
        "tmp_dir": str(tmp_path / sub / "tmp"),
        "upload_dir": str(tmp_path / sub / "uploads"),
        "l2_enable": True,
        "l2_upload_dir": str(shared),
        "fleet_replica_id": replica_id,
        **over,
    })
    metrics = MetricsRegistry()
    storage = make_storage(params, metrics=metrics)
    handler = ImageHandler(storage, params, metrics=metrics)
    return handler, storage, metrics


@pytest.fixture()
def fleet_env(tmp_path):
    """Two lease-armed replicas over one shared L2 dir + the source."""
    src = tmp_path / "src.png"
    src.write_bytes(encode(_gradient(), "png"))
    shared = tmp_path / "shared-l2"
    a = _replica(tmp_path, "a", shared, "replica-a")
    b = _replica(tmp_path, "b", shared, "replica-b")
    return a, b, str(src), shared


OPTS = "w_96,h_72,c_1,o_png"


def test_second_replica_serves_first_replicas_render(fleet_env):
    (ha, _sa, ma), (hb, _sb, mb), src, _shared = fleet_env
    first = ha.process_image(OPTS, src)
    assert not first.from_cache
    assert _lease_count(ma, "lead") == 1.0
    second = hb.process_image(OPTS, src)
    # L2 read-through: a CACHE hit on b, not a render and not a lease
    assert second.from_cache
    assert second.content == first.content
    assert _counter(mb, 'flyimg_cache_total{result="miss"}') == 0.0
    assert _counter(mb, "flyimg_l2_promotions_total") >= 1.0


def test_leader_releases_lease_after_render(fleet_env):
    (ha, sa, _ma), _b, src, _shared = fleet_env
    result = ha.process_image(OPTS, src)
    assert not sa.shared.has(lease_name(result.spec.name))


def test_concurrent_hot_key_renders_once_across_replicas(fleet_env):
    """The FLEET_r01 headline behavior: both replicas miss the same cold
    key concurrently; the lease makes one the leader, the other serves
    the leader's bytes — one device pipeline fleet-wide."""
    (ha, _sa, ma), (hb, _sb, mb), src, _shared = fleet_env
    hb.l2lease.poll_s = 0.02
    # hold a's pipeline open long enough that b's arrival ALWAYS lands
    # inside it (warm program caches would otherwise finish a in
    # milliseconds and hand b a plain cache hit instead of a lease wait)
    original = ha._process_new

    def slow_process(*args, **kwargs):
        time.sleep(0.6)
        return original(*args, **kwargs)

    ha._process_new = slow_process
    results = {}

    def render(name, handler):
        results[name] = handler.process_image(OPTS, src)

    t_a = threading.Thread(target=render, args=("a", ha))
    t_a.start()
    time.sleep(0.15)  # b arrives while a's pipeline is in flight
    t_b = threading.Thread(target=render, args=("b", hb))
    t_b.start()
    t_a.join(timeout=120)
    t_b.join(timeout=120)
    assert results["a"].content == results["b"].content
    renders = _counter(ma, 'flyimg_cache_total{result="miss"}') + _counter(
        mb, 'flyimg_cache_total{result="miss"}'
    )
    assert renders == 1.0
    assert (
        _lease_count(ma, "coalesced") + _lease_count(mb, "coalesced") == 1.0
    )


def test_follower_coalesces_on_live_foreign_lease(fleet_env):
    (ha, _sa, _ma), (hb, sb, mb), src, _shared = fleet_env
    # learn the artifact name + bytes from a's isolated render, then
    # reset the world so b faces a cold key under a foreign lease
    reference = ha.process_image(OPTS, src)
    name = reference.spec.name
    sb.delete(name)
    foreign = hb.l2lease.__class__(
        sb.shared, "replica-x", ttl_s=30.0, poll_s=0.01
    )
    token = foreign.acquire(name)
    assert token is not None
    hb.l2lease.poll_s = 0.02

    def publish():
        time.sleep(0.2)
        sb.shared.write(name, reference.content)
        foreign.release(name, token)

    publisher = threading.Thread(target=publish)
    publisher.start()
    result = hb.process_image(OPTS, src)
    publisher.join()
    assert result.from_cache
    assert result.content == reference.content
    assert _lease_count(mb, "coalesced") == 1.0
    assert _counter(mb, 'flyimg_cache_total{result="miss"}') == 0.0


def test_crashed_leader_lease_expires_and_is_stolen(fleet_env):
    """Leader crash before write: the follower polls out the TTL, steals
    the lease, and renders — a dead leader never wedges the key."""
    (ha, _sa, _ma), (hb, sb, mb), src, _shared = fleet_env
    reference = ha.process_image(OPTS, src)
    name = reference.spec.name
    sb.delete(name)
    # a "crashed leader": live marker with a short TTL and no artifact
    sb.shared.write(
        lease_name(name),
        json.dumps({
            "owner": "replica-dead", "token": "t0",
            "acquired_at": time.time(), "ttl_s": 0.3,
        }).encode(),
    )
    hb.l2lease.poll_s = 0.02
    result = hb.process_image(OPTS, src)
    assert not result.from_cache  # b rendered it
    assert result.content == reference.content
    assert _lease_count(mb, "steal") == 1.0
    assert not sb.shared.has(lease_name(name))  # released after render


def test_lease_wait_exceeding_deadline_is_504_not_hang(fleet_env):
    (ha, _sa, _ma), (hb, sb, _mb), src, _shared = fleet_env
    reference = ha.process_image(OPTS, src)
    name = reference.spec.name
    sb.delete(name)
    sb.shared.write(
        lease_name(name),
        json.dumps({
            "owner": "replica-slow", "token": "t1",
            "acquired_at": time.time(), "ttl_s": 60.0,
        }).encode(),
    )
    hb.l2lease.poll_s = 0.02
    t0 = time.monotonic()
    with pytest.raises(DeadlineExceededException):
        hb.process_image(OPTS, src, deadline=Deadline(0.3))
    assert time.monotonic() - t0 < 5.0


def test_lease_wait_cap_sheds_503_without_deadline(fleet_env):
    (ha, _sa, _ma), (hb, sb, mb), src, _shared = fleet_env
    reference = ha.process_image(OPTS, src)
    name = reference.spec.name
    sb.delete(name)
    sb.shared.write(
        lease_name(name),
        json.dumps({
            "owner": "replica-slow", "token": "t2",
            "acquired_at": time.time(), "ttl_s": 60.0,
        }).encode(),
    )
    hb.l2lease.poll_s = 0.02
    hb.l2lease.wait_cap_s = 0.2
    with pytest.raises(ServiceUnavailableException):
        hb.process_image(OPTS, src)
    assert _lease_count(mb, "timeout") == 1.0


def test_torn_l2_artifact_under_active_lease_rerenders(fleet_env):
    """A valid-magic garbage-body artifact published under a live lease
    is sniff-discarded from BOTH tiers; once the lease frees, the
    follower steals it and re-renders clean bytes."""
    (ha, _sa, _ma), (hb, sb, mb), src, _shared = fleet_env
    reference = ha.process_image(OPTS, src)
    name = reference.spec.name
    sb.delete(name)
    foreign = hb.l2lease.__class__(
        sb.shared, "replica-x", ttl_s=30.0, poll_s=0.01
    )
    token = foreign.acquire(name)
    # wrong leading magic: exactly what the read-time sniff catches (a
    # torn valid-magic body is the REUSE layer's decode-time concern,
    # pinned in tests/test_reuse.py)
    torn = b"not-a-png-at-all" * 8

    def publish_torn():
        time.sleep(0.15)
        sb.shared.write(name, torn)
        time.sleep(0.25)
        foreign.release(name, token)

    publisher = threading.Thread(target=publish_torn)
    publisher.start()
    hb.l2lease.poll_s = 0.02
    result = hb.process_image(OPTS, src)
    publisher.join()
    assert result.content == reference.content
    assert not result.from_cache  # re-rendered, not served torn
    assert _counter(mb, "flyimg_cache_corrupt_total") >= 1.0
    assert _lease_count(mb, "steal") == 1.0
    # the torn blob is gone from the shared tier, replaced by the render
    assert sb.shared.read(name) == reference.content


def test_refresh_bypasses_lease_wait_but_writes_through(fleet_env):
    (ha, _sa, _ma), (hb, sb, _mb), src, _shared = fleet_env
    reference = ha.process_image(OPTS, src)
    name = reference.spec.name
    # a foreign lease exists; rf_1 must re-render NOW, not wait on it
    sb.shared.write(
        lease_name(name),
        json.dumps({
            "owner": "replica-x", "token": "t3",
            "acquired_at": time.time(), "ttl_s": 60.0,
        }).encode(),
    )
    result = hb.process_image(OPTS + ",rf_1", src)
    assert not result.from_cache
    assert sb.shared.read(name) == result.content


def test_cross_replica_derivative_reuse_via_shared_manifest(tmp_path):
    """PR 10's variant index goes fleet-wide through the shared tier: a
    cold replica's lookup rebuilds from the manifest replica a wrote,
    and serves a small rendition from a's cached large one with the
    ORIGIN GONE — no fetch, no origin dependency."""
    src = tmp_path / "src.png"
    src.write_bytes(encode(_gradient(256, 192), "png"))
    shared = tmp_path / "shared-l2"
    ha, _sa, _ma = _replica(
        tmp_path, "a", shared, "replica-a", reuse_enable=True
    )
    hb, _sb, mb = _replica(
        tmp_path, "b", shared, "replica-b", reuse_enable=True
    )
    seeded = ha.process_image("w_128,o_png", str(src))
    assert seeded.reused_from is None
    src.unlink()  # the origin is gone; only a's rendition can serve this
    result = hb.process_image("w_48,h_36,c_1,o_png", str(src))
    assert result.reused_from == seeded.spec.name
    assert (
        _counter(mb, 'flyimg_reuse_hits_total{outcome="hit"}') == 1.0
    )


def test_off_is_off_byte_identity_and_no_markers(tmp_path):
    """All fleet knobs at their defaults: plain single-tier storage, no
    lease object, no marker writes, and the served bytes are identical
    to an L2-armed replica's render of the same request."""
    src = tmp_path / "src.png"
    src.write_bytes(encode(_gradient(), "png"))
    params = AppParameters({
        "tmp_dir": str(tmp_path / "off" / "tmp"),
        "upload_dir": str(tmp_path / "off" / "uploads"),
    })
    storage = make_storage(params)
    handler = ImageHandler(storage, params, metrics=MetricsRegistry())
    assert isinstance(storage, LocalStorage)
    assert handler.l2lease is None
    off = handler.process_image(OPTS, str(src))
    shared = tmp_path / "shared-l2"
    on_handler, on_storage, _ = _replica(tmp_path, "on", shared, "r1")
    assert isinstance(on_storage, TieredStorage)
    on = on_handler.process_image(OPTS, str(src))
    assert off.content == on.content
    # no lease markers survive anywhere, and the off store has no L2 dir
    assert not any(
        name.endswith(".lease")
        for name in __import__("os").listdir(str(shared))
    )


# ---------------------------------------------------------------------------
# HTTP: owner proxying, hop protection, fallback, attribution


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _fleet_params(tmp_path, sub, replicas, self_url, shared, **extra):
    base = {
        "tmp_dir": str(tmp_path / sub / "tmp"),
        "upload_dir": str(tmp_path / sub / "uploads"),
        "debug": True,
        "batch_deadline_ms": 1.0,
        "fleet_replicas": replicas,
        "fleet_replica_id": self_url,
        "l2_enable": True,
        "l2_upload_dir": str(shared),
    }
    base.update(extra)
    return AppParameters(base)


async def _two_replica_fleet(tmp_path, mode="proxy", owner_dead=False):
    """Two real HTTP replicas on fixed local ports (+ optionally a dead
    third owner candidate). Returns (clients, urls, src)."""
    from flyimg_tpu.service.app import make_app

    ports = [_free_port(), _free_port()]
    urls = [f"http://127.0.0.1:{p}" for p in ports]
    replicas = list(urls)
    if owner_dead:
        replicas.append(f"http://127.0.0.1:{_free_port()}")
    shared = tmp_path / "shared-l2"
    clients = []
    for i, (port, url) in enumerate(zip(ports, urls)):
        app = make_app(_fleet_params(
            tmp_path, f"r{i}", replicas, url, shared, fleet_route=mode,
        ))
        client = TestClient(
            TestServer(app, host="127.0.0.1", port=port)
        )
        await client.start_server()
        clients.append(client)
    src = tmp_path / "src.png"
    src.write_bytes(encode(_gradient(), "png"))
    return clients, urls, replicas, str(src)


def _owned_request(replicas, owner_url, src):
    """An /upload path whose route key rendezvous-maps to ``owner_url``.
    Candidates vary GEOMETRY (w_), because the routing key deliberately
    ignores encode-only options (plan affinity, runtime/fleet.py)."""
    for w in range(40, 100):
        options = f"w_{w},h_48,c_1,o_jpg"
        if rendezvous_owner(replicas, route_key(options, src)) == owner_url:
            return f"/upload/{options}/{src}", options
    raise AssertionError("no candidate key landed on the wanted owner")


async def _metric(client, name):
    text = await (await client.get("/metrics")).text()
    for line in text.splitlines():
        if line.startswith(name + " "):
            return float(line.rsplit(" ", 1)[1])
    return 0.0


def test_proxy_routes_to_owner_and_attributes_renderer(tmp_path):
    async def go():
        clients, urls, replicas, src = await _two_replica_fleet(tmp_path)
        try:
            path, _ = _owned_request(replicas, urls[1], src)
            resp = await clients[0].get(path)
            assert resp.status == 200
            body = await resp.read()
            assert len(body) > 0
            # the RENDERING replica's id survives the proxy hop
            assert resp.headers.get("X-Flyimg-Replica") == urls[1]
            proxied = await _metric(
                clients[0],
                'flyimg_fleet_routed_total{outcome="proxied"}',
            )
            assert proxied == 1.0
            hopped = await _metric(
                clients[1], 'flyimg_fleet_routed_total{outcome="hop"}'
            )
            assert hopped == 1.0
            # replica 0 ran no pipeline for it
            assert await _metric(
                clients[0], 'flyimg_cache_total{result="miss"}'
            ) == 0.0
        finally:
            for client in clients:
                await client.close()

    _run(go())


def test_self_owned_key_renders_locally(tmp_path):
    async def go():
        clients, urls, replicas, src = await _two_replica_fleet(tmp_path)
        try:
            path, _ = _owned_request(replicas, urls[0], src)
            resp = await clients[0].get(path)
            assert resp.status == 200
            assert resp.headers.get("X-Flyimg-Replica") == urls[0]
            assert await _metric(
                clients[0], 'flyimg_fleet_routed_total{outcome="self"}'
            ) == 1.0
        finally:
            for client in clients:
                await client.close()

    _run(go())


def test_hop_header_prevents_proxy_loops(tmp_path):
    async def go():
        clients, urls, replicas, src = await _two_replica_fleet(tmp_path)
        try:
            path, _ = _owned_request(replicas, urls[1], src)
            resp = await clients[0].get(
                path, headers={HOP_HEADER: "somewhere"}
            )
            assert resp.status == 200
            # rendered HERE despite foreign ownership: no second hop
            assert resp.headers.get("X-Flyimg-Replica") == urls[0]
            assert await _metric(
                clients[0], 'flyimg_fleet_routed_total{outcome="hop"}'
            ) == 1.0
        finally:
            for client in clients:
                await client.close()

    _run(go())


def test_owner_down_falls_back_to_local_render(tmp_path):
    async def go():
        clients, urls, replicas, src = await _two_replica_fleet(
            tmp_path, owner_dead=True
        )
        try:
            dead = replicas[-1]
            path, _ = _owned_request(replicas, dead, src)
            resp = await clients[0].get(path)
            assert resp.status == 200  # served, not 502
            assert resp.headers.get("X-Flyimg-Replica") == urls[0]
            assert await _metric(
                clients[0],
                'flyimg_fleet_routed_total{outcome="fallback"}',
            ) == 1.0
        finally:
            for client in clients:
                await client.close()

    _run(go())


def test_owner_5xx_falls_back_to_local_render(tmp_path):
    """An overloaded owner (503) must never become a user-visible error
    the single-replica tier would not have produced: the non-owner
    records the breaker failure and renders locally."""

    async def go():
        from aiohttp import web as aioweb

        from flyimg_tpu.service.app import make_app

        # a fake "owner" that sheds everything as 503
        async def always_503(_request):
            return aioweb.Response(status=503, text="shedding")

        sick_port = _free_port()
        sick_app = aioweb.Application()
        sick_app.router.add_get("/{tail:.*}", always_503)
        sick = TestClient(
            TestServer(sick_app, host="127.0.0.1", port=sick_port)
        )
        await sick.start_server()
        sick_url = f"http://127.0.0.1:{sick_port}"

        port = _free_port()
        url = f"http://127.0.0.1:{port}"
        replicas = [url, sick_url]
        shared = tmp_path / "shared-l2"
        app = make_app(_fleet_params(
            tmp_path, "r0", replicas, url, shared, fleet_route="proxy",
        ))
        client = TestClient(TestServer(app, host="127.0.0.1", port=port))
        await client.start_server()
        try:
            src = tmp_path / "src.png"
            src.write_bytes(encode(_gradient(), "png"))
            path, _ = _owned_request(replicas, sick_url, str(src))
            resp = await client.get(path)
            assert resp.status == 200  # rendered HERE, not relayed 503
            assert resp.headers.get("X-Flyimg-Replica") == url
            assert await _metric(
                client, 'flyimg_fleet_routed_total{outcome="fallback"}'
            ) == 1.0
        finally:
            await client.close()
            await sick.close()

    _run(go())


def test_local_mode_renders_and_shares_through_l2(tmp_path):
    async def go():
        clients, urls, replicas, src = await _two_replica_fleet(
            tmp_path, mode="local"
        )
        try:
            path, _ = _owned_request(replicas, urls[1], src)
            resp = await clients[0].get(path)
            assert resp.status == 200
            assert resp.headers.get("X-Flyimg-Replica") == urls[0]
            assert await _metric(
                clients[0], 'flyimg_fleet_routed_total{outcome="local"}'
            ) == 1.0
            # the render is fleet-visible: replica 1 serves it as a HIT
            resp2 = await clients[1].get(path)
            assert resp2.status == 200
            assert await _metric(
                clients[1], 'flyimg_cache_total{result="hit"}'
            ) == 1.0
            assert await _metric(
                clients[1], 'flyimg_cache_total{result="miss"}'
            ) == 0.0
        finally:
            for client in clients:
                await client.close()

    _run(go())


def test_proxied_owner_4xx_relays_without_local_render(tmp_path):
    async def go():
        clients, urls, replicas, src = await _two_replica_fleet(tmp_path)
        try:
            # an invalid sampling factor 400s deterministically at the
            # owner on every jpg path (spec/options grammar)
            for w in range(40, 100):
                options = f"w_{w},sf_bogus,o_jpg"
                if rendezvous_owner(
                    replicas, route_key(options, src)
                ) == urls[1]:
                    break
            resp = await clients[0].get(f"/upload/{options}/{src}")
            assert resp.status == 400
            assert await _metric(
                clients[0],
                'flyimg_fleet_routed_total{outcome="proxied"}',
            ) == 1.0
            assert await _metric(
                clients[0], 'flyimg_cache_total{result="miss"}'
            ) == 0.0
        finally:
            for client in clients:
                await client.close()

    _run(go())


def test_fleet_route_span_lands_on_proxying_trace(tmp_path):
    async def go():
        clients, urls, replicas, src = await _two_replica_fleet(tmp_path)
        try:
            path, _ = _owned_request(replicas, urls[1], src)
            resp = await clients[0].get(path)
            traceparent = resp.headers.get("traceparent", "")
            trace_id = (
                traceparent.split("-")[1] if "-" in traceparent else ""
            )
            assert trace_id
            tree = json.loads(
                await (
                    await clients[0].get(f"/debug/traces/{trace_id}")
                ).text()
            )

            def walk(node, out):
                out.append(node)
                for child in node.get("children", ()):
                    walk(child, out)
                return out

            spans = []
            for root in tree["spans"]:
                walk(root, spans)
            names = [s["name"] for s in spans]
            assert "fleet.route" in names
            route = next(s for s in spans if s["name"] == "fleet.route")
            assert route["attributes"]["fleet.outcome"] == "proxied"
            assert route["attributes"]["fleet.owner"] == urls[1]
            assert tree["spans"][0]["attributes"].get(
                "fleet.replica_id"
            ) == urls[0]
        finally:
            for client in clients:
                await client.close()

    _run(go())


def test_proxy_hop_joins_callers_trace_under_fleet_route_span(tmp_path):
    """The proxy hop forwards a traceparent minted under the caller's
    ``fleet.route`` span (runtime/fleet.py proxy(), overriding any
    inbound header), so the owner's spans land in the SAME trace as
    CHILDREN of fleet.route — one distributed tree, not two sibling
    traces that only share timestamps."""

    async def go():
        clients, urls, replicas, src = await _two_replica_fleet(tmp_path)
        try:
            path, _ = _owned_request(replicas, urls[1], src)
            resp = await clients[0].get(path)
            assert resp.status == 200
            trace_id = resp.headers.get("traceparent", "").split("-")[1]
            assert trace_id

            def walk(node, out):
                out.append(node)
                for child in node.get("children", ()):
                    walk(child, out)
                return out

            async def spans_of(client):
                tree = json.loads(await (
                    await client.get(f"/debug/traces/{trace_id}")
                ).text())
                spans = []
                for root in tree["spans"]:
                    walk(root, spans)
                return spans

            # the caller's side of the hop
            caller = await spans_of(clients[0])
            route = next(s for s in caller if s["name"] == "fleet.route")
            assert route["attributes"]["fleet.outcome"] == "proxied"
            # the owner kept a trace under the CALLER's id — adopted
            # from the forwarded traceparent, not minted fresh
            owner = await spans_of(clients[1])
            owner_root = owner[0]
            assert owner_root["name"] == "request"
            # ...and its root is parented under the caller's
            # fleet.route span: the cross-replica tree joins on span
            # ids, so a trace viewer nests the owner's whole pipeline
            # (fetch/decode/device/encode) inside the proxy hop
            assert owner_root["parent_id"] == route["span_id"]
            owner_names = [s["name"] for s in owner]
            assert "device_execute" in owner_names
            # both replicas tagged their spans with their own identity
            assert owner_root["attributes"]["fleet.replica_id"] == urls[1]
        finally:
            for client in clients:
                await client.close()

    _run(go())


def test_debug_off_hides_replica_header(tmp_path):
    async def go():
        from flyimg_tpu.service.app import make_app

        shared = tmp_path / "shared-l2"
        app = make_app(AppParameters({
            "tmp_dir": str(tmp_path / "tmp"),
            "upload_dir": str(tmp_path / "uploads"),
            "fleet_replica_id": "r1",
            "l2_enable": True,
            "l2_upload_dir": str(shared),
        }))
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            src = tmp_path / "src.png"
            src.write_bytes(encode(_gradient(), "png"))
            resp = await client.get(f"/upload/w_64,o_png/{src}")
            assert resp.status == 200
            assert "X-Flyimg-Replica" not in resp.headers
        finally:
            await client.close()

    _run(go())


def test_fleet_off_app_has_no_fleet_surface(tmp_path):
    async def go():
        from flyimg_tpu.service.app import make_app

        app = make_app(AppParameters({
            "tmp_dir": str(tmp_path / "tmp"),
            "upload_dir": str(tmp_path / "uploads"),
            "debug": True,
        }))
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            src = tmp_path / "src.png"
            src.write_bytes(encode(_gradient(), "png"))
            resp = await client.get(f"/upload/w_64,o_png/{src}")
            assert resp.status == 200
            assert "X-Flyimg-Replica" not in resp.headers
            metrics_text = await (await client.get("/metrics")).text()
            assert "flyimg_fleet_routed_total" not in metrics_text
            assert "flyimg_l2_lease_total" not in metrics_text
            perf = json.loads(await (await client.get("/debug/perf")).text())
            assert perf["fleet"] is None
        finally:
            await client.close()

    _run(go())


def test_debug_perf_carries_fleet_identity(tmp_path):
    async def go():
        clients, urls, _replicas, _src = await _two_replica_fleet(tmp_path)
        try:
            perf = json.loads(
                await (await clients[0].get("/debug/perf")).text()
            )
            assert perf["fleet"]["replica_id"] == urls[0]
            assert perf["fleet"]["mode"] == "proxy"
            assert urls[1] in perf["fleet"]["replicas"]
        finally:
            for client in clients:
                await client.close()

    _run(go())


# ---------------------------------------------------------------------------
# replica attribution in structured logs


def test_access_log_carries_replica(caplog):
    from flyimg_tpu.runtime.logging import ACCESS_LOGGER, access_log

    with caplog.at_level(logging.INFO, logger=ACCESS_LOGGER):
        access_log(
            method="GET", path="/upload/x/y", route="upload", status=200,
            duration_s=0.01, replica="replica-9",
        )
    record = caplog.records[-1]
    assert record.replica == "replica-9"


def test_configured_logging_stamps_replica_on_every_line():
    import io

    from flyimg_tpu.runtime.logging import configure_logging

    stream = io.StringIO()
    params = AppParameters({
        "fleet_replica_id": "replica-3", "log_format": "json",
    })
    # configure_logging mutates the process-wide "flyimg" logger
    # (handler + propagate=False); restore EVERYTHING afterwards or
    # every later caplog-based test in the session goes blind
    logger = logging.getLogger("flyimg")
    prev_handlers = list(logger.handlers)
    prev_propagate = logger.propagate
    prev_level = logger.level
    try:
        configure_logging(params, stream=stream)
        logging.getLogger("flyimg.fleet").warning("something happened")
        line = stream.getvalue().strip().splitlines()[-1]
        doc = json.loads(line)
        assert doc["replica"] == "replica-3"
    finally:
        for installed in list(logger.handlers):
            if installed not in prev_handlers:
                logger.removeHandler(installed)
        for missing in prev_handlers:
            if missing not in logger.handlers:
                logger.addHandler(missing)
        logger.propagate = prev_propagate
        logger.setLevel(prev_level)


# ---------------------------------------------------------------------------
# dynamic replica-set reload (ISSUE 14 satellite; docs/fleet.md
# "Dynamic replica sets")


def test_update_replicas_rehomes_only_changed_keys():
    router = FleetRouter(REPLICAS, REPLICAS[0])
    keys = [route_key(f"w_{i}", "https://e.com/a.jpg") for i in range(400)]
    before = {key: router.owner(key) for key in keys}
    applied = router.update_replicas(REPLICAS[:-1])
    assert applied["replicas"] == REPLICAS[:-1]
    assert applied["enabled"] is True
    moved = 0
    for key in keys:
        after = router.owner(key)
        if before[key] == REPLICAS[-1]:
            moved += 1
            assert after in REPLICAS[:-1]
        else:
            assert after == before[key]  # HRW minimal disruption, live
    assert moved > 0


def test_update_replicas_toggles_enabled_and_self_id():
    router = FleetRouter([], "")
    assert not router.enabled
    applied = router.update_replicas(
        ["http://a/", "http://b"], self_id="http://a"
    )
    assert router.enabled
    assert applied["replica_id"] == "http://a"
    assert router.replicas == ["http://a", "http://b"]  # normalized
    router.update_replicas(["http://a"])
    assert not router.enabled  # one replica = routing off
    # self_id untouched when not passed
    assert router.self_id == "http://a"


def test_owner_of_emptied_replica_set_is_self_not_valueerror():
    router = FleetRouter(["http://a", "http://b"], "http://a")
    key = "abc123"
    assert router.owner(key) in ("http://a", "http://b")
    router.update_replicas([])  # SIGHUP reload to an empty set
    assert router.owner(key) == "http://a"  # local render, no raise
    assert not router.enabled


def test_debug_fleet_replicas_endpoint_applies_and_validates(tmp_path):
    from flyimg_tpu.service.app import FLEET_KEY, make_app

    async def go():
        params = AppParameters({
            "tmp_dir": str(tmp_path / "tmp"),
            "upload_dir": str(tmp_path / "uploads"),
            "debug": True,
            "fleet_replicas": ["http://r1", "http://r2"],
            "fleet_replica_id": "http://r1",
            "fleet_route": "local",
        })
        app = make_app(params)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            resp = await client.post(
                "/debug/fleet/replicas",
                json={"replicas": ["http://r1", "http://r2", "http://r3"]},
            )
            assert resp.status == 200
            doc = json.loads(await resp.text())
            assert doc["replicas"] == [
                "http://r1", "http://r2", "http://r3"
            ]
            assert app[FLEET_KEY].replicas == doc["replicas"]
            # /debug/perf's fleet section reflects the LIVE set
            perf = json.loads(await (await client.get("/debug/perf")).text())
            assert perf["fleet"]["replicas"] == doc["replicas"]
            # replica_id swap rides the same endpoint
            resp = await client.post(
                "/debug/fleet/replicas",
                json={
                    "replicas": ["http://r2", "http://r3"],
                    "replica_id": "http://r2",
                },
            )
            assert json.loads(await resp.text())["replica_id"] == "http://r2"
            # malformed bodies are 400s, never applied
            assert (
                await client.post(
                    "/debug/fleet/replicas", json={"replicas": "x"}
                )
            ).status == 400
            assert (
                await client.post(
                    "/debug/fleet/replicas", json={"replicas": [1, 2]}
                )
            ).status == 400
            assert (
                await client.post(
                    "/debug/fleet/replicas", data=b"not json"
                )
            ).status == 400
            assert (
                await client.post(
                    "/debug/fleet/replicas",
                    json={"replicas": ["http://a"], "replica_id": 7},
                )
            ).status == 400
            assert app[FLEET_KEY].replicas == ["http://r2", "http://r3"]
        finally:
            await client.close()

    _run(go())


def test_debug_fleet_replicas_404_without_debug(tmp_path):
    from flyimg_tpu.service.app import make_app

    async def go():
        params = AppParameters({
            "tmp_dir": str(tmp_path / "tmp"),
            "upload_dir": str(tmp_path / "uploads"),
            "debug": False,
        })
        app = make_app(params)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            resp = await client.post(
                "/debug/fleet/replicas", json={"replicas": []}
            )
            assert resp.status == 404
        finally:
            await client.close()

    _run(go())


# ---------------------------------------------------------------------------
# lease-aware brownout (ISSUE 14 satellite; docs/degradation.md
# "Lease-aware pressure"): a follower blocked behind a stalled leader
# counts toward brownout pressure instead of looking idle


def test_stalled_leader_follower_counts_toward_brownout(fleet_env):
    from flyimg_tpu.runtime.brownout import DEGRADED, BrownoutEngine

    (ha, _sa, _ma), (hb, sb, _mb), src, _shared = fleet_env
    reference = ha.process_image(OPTS, src)
    name = reference.spec.name
    sb.delete(name)
    # a STALLED leader: live foreign marker, artifact never arriving
    foreign = hb.l2lease.__class__(
        sb.shared, "replica-stalled", ttl_s=30.0, poll_s=0.01
    )
    token = foreign.acquire(name)
    assert token is not None
    hb.l2lease.poll_s = 0.02
    engine = BrownoutEngine(
        enabled=True, degraded_at=0.4, lease_ref=2.0, eval_interval_s=0.0,
        metrics=MetricsRegistry(),
    )
    engine.attach(lease_waiters_fn=lambda: float(hb.l2lease.waiters))
    assert engine.evaluate() == 0  # nobody waiting yet

    done = threading.Event()

    def follower():
        try:
            hb.process_image(OPTS, src)
        finally:
            done.set()

    thread = threading.Thread(target=follower)
    thread.start()
    try:
        deadline = time.monotonic() + 10.0
        while hb.l2lease.waiters == 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert hb.l2lease.waiters == 1
        level = engine.evaluate()
        snap = engine.snapshot()
        # 1 waiter / lease_ref 2.0 = 0.5 pressure -> DEGRADED
        assert snap["components"]["l2_lease"] == 0.5
        assert level >= DEGRADED
    finally:
        # unstall: publish the artifact and free the lease
        sb.shared.write(name, reference.content)
        foreign.release(name, token)
        done.wait(timeout=30)
        thread.join(timeout=30)
    assert hb.l2lease.waiters == 0  # accounting always unwinds


def test_lease_component_absent_without_source_or_ref():
    from flyimg_tpu.runtime.brownout import BrownoutEngine

    engine = BrownoutEngine(
        enabled=True, eval_interval_s=0.0, metrics=MetricsRegistry(),
    )
    assert "l2_lease" not in engine._components()
    engine.attach(lease_waiters_fn=lambda: 5.0)
    assert engine._components()["l2_lease"] == 5.0 / 8.0  # default ref
    zero_ref = BrownoutEngine(
        enabled=True, lease_ref=0.0, eval_interval_s=0.0,
        metrics=MetricsRegistry(),
    )
    zero_ref.attach(lease_waiters_fn=lambda: 5.0)
    assert "l2_lease" not in zero_ref._components()
