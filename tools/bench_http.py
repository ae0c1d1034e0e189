"""HTTP serving benchmark — the reference `benchmark.sh` analog.

The reference's published numbers are a vegeta run: 50 req/s for 10 s
against one image for three option sets (crop / resize / rotate), measuring
the cache-hit serving path after the first miss (README.md:548-587,
BASELINE.md). This harness reproduces that methodology against the live
service, plus an uncapped burst mode that reports max sustained cache-hit
throughput.

Usage:
    python tools/bench_http.py [--base http://host:port] [--rate 50]
                               [--duration 10] [--burst 2000]

With --base, benchmarks that already-running service. Without it (or with
--spawn), starts the service on a free port and shuts it down after; the
two flags together are contradictory and rejected. Prints one human table
and one JSON line per scenario.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import signal
import socket
import subprocess
import sys
import time

import httpx
import numpy as np

SCENARIOS = [
    ("crop", "w_200,h_200,c_1"),
    ("resize", "w_200,h_200,rz_1"),
    ("rotate", "r_-45,w_400,h_400"),
]


def _make_source(path: str, seed: int = 42) -> str:
    from PIL import Image

    if not os.path.exists(path):
        rng = np.random.default_rng(seed)
        arr = rng.integers(0, 256, size=(768, 1024, 3), dtype=np.uint8)
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        Image.fromarray(arr).save(path, "JPEG", quality=92)
    return path


async def _rated_run(client: httpx.AsyncClient, urls: list, rate: float):
    """Fire one GET per URL on a fixed-rate schedule (vegeta-style
    open-loop), regardless of completions; gather latencies. Cache-hit
    scenarios pass the same URL repeated; the rated-miss sweep passes
    distinct uncached keys — a rate the host can't sustain shows up as
    p99 growing with elapsed time (queueing), which is the knee the
    sweep looks for."""
    latencies: list = []
    failures = 0
    tasks = []

    async def one(url):
        nonlocal failures
        t0 = time.perf_counter()
        try:
            resp = await client.get(url)
            ok = resp.status_code == 200 and len(resp.content) > 0
        except httpx.HTTPError:
            ok = False
        if ok:
            latencies.append(time.perf_counter() - t0)
        else:
            failures += 1

    start = time.perf_counter()
    for i, url in enumerate(urls):
        target = start + i / rate
        delay = target - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)
        tasks.append(asyncio.ensure_future(one(url)))
    await asyncio.gather(*tasks)
    elapsed = time.perf_counter() - start
    return latencies, failures, elapsed


async def _burst_run(client: httpx.AsyncClient, url: str, total: int, conc: int):
    """Closed-loop max throughput: `conc` in-flight workers, `total` reqs."""
    latencies: list = []
    failures = 0
    remaining = [total]

    async def worker():
        nonlocal failures
        while True:
            if remaining[0] <= 0:
                return
            remaining[0] -= 1
            t0 = time.perf_counter()
            try:
                resp = await client.get(url)
                ok = resp.status_code == 200
            except httpx.HTTPError:
                ok = False
            if ok:
                latencies.append(time.perf_counter() - t0)
            else:
                failures += 1

    start = time.perf_counter()
    await asyncio.gather(*[worker() for _ in range(conc)])
    elapsed = time.perf_counter() - start
    return latencies, failures, elapsed


async def _miss_run(
    client: httpx.AsyncClient, urls: list, conc: int
):
    """Cache-MISS path: every URL is a distinct uncached output, requested
    exactly once by `conc` closed-loop workers — each request runs the full
    fetch/decode/device/encode pipeline (concurrent misses batch in the
    runtime; none coalesce, the keys are all different)."""
    latencies: list = []
    failures = 0
    it = iter(urls)

    async def worker():
        nonlocal failures
        while True:
            url = next(it, None)
            if url is None:
                return
            t0 = time.perf_counter()
            try:
                resp = await client.get(url)
                ok = resp.status_code == 200 and len(resp.content) > 0
            except httpx.HTTPError:
                ok = False
            if ok:
                latencies.append(time.perf_counter() - t0)
            else:
                failures += 1

    start = time.perf_counter()
    await asyncio.gather(*[worker() for _ in range(conc)])
    elapsed = time.perf_counter() - start
    return latencies, failures, elapsed


# resample-kernel variant tag for the A/B legs (--kernel): stamped into
# every result row so sweep artifacts can tell dense and banded curves
# apart; None (no --kernel) omits the field
_KERNEL_TAG = None

# derivative-reuse tag (--reuse): stamped into every result row exactly
# like _KERNEL_TAG, so multisize A/B artifacts carry which rewriter
# setting produced each curve; None (no --reuse) omits the field
_REUSE_TAG = None

# host-codec-overhaul tags (--decode-roi / --pipeline): stamped into
# every result row like _KERNEL_TAG so the thumbnail/cropzoom A/B
# artifacts carry which knobs produced each curve (docs/host-pipeline.md)
_ROI_TAG = None
_PIPELINE_TAG = None


def _zipf_weights(n: int, s: float = 1.1) -> list:
    """Zipf-ish popularity over ladder ranks: rank r gets 1/(r+1)^s.
    Real multi-size traffic concentrates on a few small renditions with
    a long tail of odd sizes — exactly the distribution the variant
    index is built for."""
    raw = [1.0 / ((rank + 1) ** s) for rank in range(n)]
    total = sum(raw)
    return [w / total for w in raw]


async def _multisize_run(
    client: httpx.AsyncClient, urls: list, conc: int
):
    """Closed-loop run over distinct-key multisize URLs; every request
    records (latency, reused) where ``reused`` comes from the
    debug-gated X-Flyimg-Reuse header (docs/caching.md) — the split the
    hit/miss rows are built from."""
    samples: list = []
    failures = 0
    it = iter(urls)

    async def worker():
        nonlocal failures
        while True:
            url = next(it, None)
            if url is None:
                return
            t0 = time.perf_counter()
            try:
                resp = await client.get(url)
                ok = resp.status_code == 200 and len(resp.content) > 0
            except httpx.HTTPError:
                ok = False
                resp = None
            if ok:
                samples.append(
                    (
                        time.perf_counter() - t0,
                        "X-Flyimg-Reuse" in resp.headers,
                    )
                )
            else:
                failures += 1

    start = time.perf_counter()
    await asyncio.gather(*[worker() for _ in range(conc)])
    elapsed = time.perf_counter() - start
    return samples, failures, elapsed


def _report(name: str, mode: str, lat, failures: int, elapsed: float,
            extra: dict | None = None):
    """``extra`` fields merge into the row BEFORE it is printed, so the
    JSON line an artifact consumer scrapes carries them (the multisize
    rows stamp reuse=hit|miss + ancestor_hit_ratio this way)."""
    if not lat:
        # all-failed legs are the MOST important rows of an overload
        # sweep (they mark the saturation knee): emit the same schema as
        # success rows — explicit null latency fields plus a
        # "saturated" flag — so artifact consumers handle them
        # deterministically instead of KeyError-ing on the data point
        # that matters
        row = {
            "scenario": name,
            "mode": mode,
            "requests": failures,
            "success_rate": 0.0,
            "throughput_rps": 0.0,
            "saturated": True,
            "latency_ms": {
                "mean": None, "p50": None, "p95": None, "p99": None,
                "max": None,
            },
        }
        if _KERNEL_TAG is not None:
            row["kernel"] = _KERNEL_TAG
        if _REUSE_TAG is not None:
            row["reuse_enable"] = _REUSE_TAG == "on"
        if _ROI_TAG is not None:
            row["decode_roi"] = _ROI_TAG == "on"
        if _PIPELINE_TAG is not None:
            row["host_pipeline"] = _PIPELINE_TAG == "on"
        if extra:
            row.update(extra)
        print(f"{name:8s} {mode:6s}  ALL {failures} REQUESTS FAILED "
              "(saturated)")
        print(json.dumps(row))
        return row
    arr = np.asarray(lat) * 1000.0
    row = {
        "scenario": name,
        "mode": mode,
        "requests": len(lat) + failures,
        "success_rate": round(len(lat) / (len(lat) + failures), 4),
        "throughput_rps": round(len(lat) / elapsed, 1),
        "saturated": False,
        "latency_ms": {
            "mean": round(float(arr.mean()), 2),
            "p50": round(float(np.percentile(arr, 50)), 2),
            "p95": round(float(np.percentile(arr, 95)), 2),
            "p99": round(float(np.percentile(arr, 99)), 2),
            "max": round(float(arr.max()), 2),
        },
    }
    if _KERNEL_TAG is not None:
        row["kernel"] = _KERNEL_TAG
    if _REUSE_TAG is not None:
        row["reuse_enable"] = _REUSE_TAG == "on"
    if _ROI_TAG is not None:
        row["decode_roi"] = _ROI_TAG == "on"
    if _PIPELINE_TAG is not None:
        row["host_pipeline"] = _PIPELINE_TAG == "on"
    if extra:
        row.update(extra)
    # extra may null throughput/success (the multisize split legs share
    # one wall clock, so per-leg rates cannot be measured honestly)
    tp = row["throughput_rps"]
    ok_rate = row["success_rate"]
    print(
        f"{name:8s} {mode:6s}  "
        + (f"{tp:8.1f} req/s   " if tp is not None else "     n/a req/s   ")
        + f"mean {row['latency_ms']['mean']:7.2f}  p50 {row['latency_ms']['p50']:7.2f}  "
        f"p95 {row['latency_ms']['p95']:7.2f}  p99 {row['latency_ms']['p99']:7.2f}  "
        f"max {row['latency_ms']['max']:8.2f} ms   "
        + (f"ok {ok_rate * 100:.1f}%" if ok_rate is not None else "ok n/a")
    )
    print(json.dumps(row))
    return row


def _make_source_4k(path: str, seed: int = 77) -> str:
    """ONE smooth 4k JPEG (seeded noise upscaled bilinearly compresses
    sanely and decodes realistically) — the source the thumbnail and
    cropzoom mixes hammer."""
    from PIL import Image

    if not os.path.exists(path):
        rng = np.random.default_rng(seed)
        arr = rng.integers(0, 256, size=(135, 240, 3), dtype=np.uint8)
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        Image.fromarray(arr).resize(
            (3840, 2160), Image.BILINEAR
        ).save(path, "JPEG", quality=90)
    return path


async def _decode_split(client: httpx.AsyncClient, base: str):
    """Decode-stage latency split by decode mode (full | prescale | roi)
    from /debug/perf's stage quantiles — the headline figures of the
    host-codec-overhaul A/B (docs/host-pipeline.md). None when the
    target serves 404 (debug off)."""
    try:
        resp = await client.get(f"{base}/debug/perf")
        if resp.status_code != 200:
            return None
        stages = resp.json().get("stages", {})
    except (httpx.HTTPError, ValueError):
        return None
    return {
        name: doc for name, doc in stages.items()
        if name == "decode" or name.startswith("decode_")
    } or None


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------------------
# multi-replica fleet A/B (--replicas N; docs/fleet.md "Measurement")


#: churn-leg membership timing — short enough that one bench run sees
#: crash detection and re-homing, long enough to stay off the fast path
CHURN_TTL_S = 3.0
CHURN_BEAT_S = 0.5


def _spawn_replica(i: int, port: int, root: str, urls: list, *,
                   fleet_on: bool, mode: str, membership: bool = False,
                   warmstart: bool = False):
    """One fleet member process. Split out of _spawn_fleet so the churn
    leg can restart a killed replica on its original port with warm
    start toggled per restart."""
    url = f"http://127.0.0.1:{port}"
    shared = os.path.join(root, "shared-l2")
    replica_root = os.path.join(root, f"replica-{i}")
    os.makedirs(replica_root, exist_ok=True)
    params_path = os.path.join(replica_root, "params.yml")
    with open(params_path, "w") as fh:
        fh.write("debug: true\n")
        fh.write("reuse_enable: true\n")
        fh.write(f"upload_dir: {os.path.join(replica_root, 'out')}\n")
        fh.write(f"tmp_dir: {os.path.join(replica_root, 'tmp')}\n")
        fh.write(f"fleet_replica_id: {url}\n")
        if fleet_on:
            fh.write(f"fleet_replicas: {json.dumps(urls)}\n")
            fh.write(f"fleet_route: {mode}\n")
            fh.write("l2_enable: true\n")
            fh.write(f"l2_upload_dir: {shared}\n")
        if membership:
            fh.write("fleet_membership_enable: true\n")
            fh.write(f"fleet_membership_ttl_s: {CHURN_TTL_S}\n")
            fh.write(f"fleet_membership_heartbeat_s: {CHURN_BEAT_S}\n")
        if warmstart:
            fh.write("warmstart_enable: true\n")
    return subprocess.Popen(
        [
            sys.executable, "-m", "flyimg_tpu.service.app", "serve",
            "--port", str(port), "--params", params_path,
        ],
        stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
    )


def _spawn_fleet(n: int, root: str, *, fleet_on: bool, mode: str = "proxy",
                 membership: bool = False):
    """Spawn N app processes as one fleet. ``fleet_on`` arms rendezvous
    routing + the shared L2 + lease; off = N isolated replicas behind a
    dumb round-robin (today's load-balancer story, the control leg).
    ``membership`` (the --churn prerequisite) arms heartbeat markers +
    warm start on top. Returns (procs, urls)."""
    ports = [_free_port() for _ in range(n)]
    urls = [f"http://127.0.0.1:{p}" for p in ports]
    procs = [
        _spawn_replica(
            i, port, root, urls, fleet_on=fleet_on, mode=mode,
            membership=membership and fleet_on,
            warmstart=membership and fleet_on,
        )
        for i, port in enumerate(ports)
    ]
    return procs, urls


async def _wait_healthy(client: httpx.AsyncClient, urls: list) -> bool:
    for url in urls:
        for _ in range(120):
            try:
                r = await client.get(f"{url}/healthz")
                if r.status_code == 200:
                    break
            except httpx.HTTPError:
                pass
            await asyncio.sleep(1.0)
        else:
            return False
    return True


def _metric_from_text(text: str, name: str) -> float:
    for line in text.splitlines():
        if line.startswith(name + " "):
            try:
                return float(line.rsplit(" ", 1)[1])
            except ValueError:
                pass
    return 0.0


async def _replica_metric(client, url: str, name: str) -> float:
    try:
        text = (await client.get(f"{url}/metrics")).text
    except httpx.HTTPError:
        return 0.0
    return _metric_from_text(text, name)


async def _replica_snapshot(client, url: str) -> dict:
    """Per-replica attribution: render counts, lease outcomes, batch
    mean-occupancy/compile amortization, distinct compiled programs —
    ONE /metrics scrape per replica, parsed locally for every counter
    (a per-counter round trip would perturb the system under test)."""
    try:
        text = (await client.get(f"{url}/metrics")).text
    except httpx.HTTPError:
        text = ""
    doc = {
        "renders": _metric_from_text(
            text, 'flyimg_cache_total{result="miss"}'
        ),
        "cache_hits": _metric_from_text(
            text, 'flyimg_cache_total{result="hit"}'
        ),
        "lease": {
            outcome: _metric_from_text(
                text, f'flyimg_l2_lease_total{{outcome="{outcome}"}}'
            )
            for outcome in ("lead", "coalesced", "steal", "timeout")
        },
        "routed": {
            outcome: _metric_from_text(
                text, f'flyimg_fleet_routed_total{{outcome="{outcome}"}}'
            )
            for outcome in ("self", "hop", "proxied", "fallback", "local")
        },
    }
    batches = _metric_from_text(text, "flyimg_batches_total")
    images = _metric_from_text(text, "flyimg_images_processed_total")
    compile_misses = _metric_from_text(
        text, 'flyimg_compile_events_total{result="miss"}'
    )
    doc["launches"] = {
        "batches": batches,
        "images": images,
        # the affinity headline: owner routing concentrates one plan's
        # stream on one replica, so launches carry more images each and
        # each compiled program amortizes over more launches
        "mean_batch_size": round(images / batches, 3) if batches else None,
        "compile_misses": compile_misses,
        "images_per_compile_miss": (
            round(images / compile_misses, 2) if compile_misses else None
        ),
    }
    try:
        perf = (await client.get(f"{url}/debug/perf")).json()
        device = (perf.get("controllers") or {}).get("device") or {}
        doc["batch"] = {
            "mean_occupancy": device.get("mean_occupancy"),
            "batches_per_compile_miss": device.get(
                "batches_per_compile_miss"
            ),
            "window_batches": device.get("window_batches"),
        }
    except (httpx.HTTPError, ValueError):
        doc["batch"] = None
    try:
        plans = (await client.get(f"{url}/debug/plans")).json()
        doc["distinct_programs"] = len(plans.get("plans", []))
    except (httpx.HTTPError, ValueError):
        doc["distinct_programs"] = None
    return doc


async def _fleet_hot_key_leg(client, urls: list, src: str, conc: int):
    """ONE cold derived key, ``conc`` concurrent requests round-robin
    across the fleet — the duplicate-render probe. Returns the leg doc
    with per-replica render deltas (off: every replica renders it; on:
    the lease + owner routing hold it to one render fleet-wide)."""
    before = [
        await _replica_metric(client, u, 'flyimg_cache_total{result="miss"}')
        for u in urls
    ]
    options = "w_321,h_241,c_1,o_jpg"
    t0 = time.perf_counter()

    async def one(i: int):
        url = f"{urls[i % len(urls)]}/upload/{options}/{src}"
        try:
            resp = await client.get(url)
            return resp.status_code == 200
        except httpx.HTTPError:
            return False

    ok = sum(await asyncio.gather(*[one(i) for i in range(conc)]))
    elapsed = time.perf_counter() - t0
    after = [
        await _replica_metric(client, u, 'flyimg_cache_total{result="miss"}')
        for u in urls
    ]
    renders = [a - b for a, b in zip(after, before)]
    return {
        "leg": "hot_key",
        "requests": conc,
        "ok": ok,
        "elapsed_s": round(elapsed, 3),
        "renders_per_replica": renders,
        "duplicate_renders": sum(renders),
    }


async def _fleet_multisize_leg(client, urls: list, src: str,
                               requests: int, conc: int):
    """The multisize Zipf mix round-robined across the fleet: distinct
    derived keys (q varies), same plan ladder — measures the
    cross-replica ancestor-hit ratio (X-Flyimg-Replica/-Reuse headers)
    and feeds the per-replica occupancy scrape."""
    anc = await client.get(f"{urls[0]}/upload/w_800,o_jpg/{src}")
    if anc.status_code != 200:
        return {"leg": "multisize", "error": "ancestor warm failed"}
    ladder = [100, 128, 160, 200, 256, 320, 400, 512, 640]
    weights = _zipf_weights(len(ladder))
    rng = np.random.default_rng(20260803)
    counts = {size: 0 for size in ladder}
    reqs = []
    for _ in range(requests):
        size = int(rng.choice(ladder, p=weights))
        q = 89 - counts[size]
        if q < 2:
            continue
        counts[size] += 1
        h = int(size * 3 / 4)
        reqs.append(f"w_{size},h_{h},c_1,q_{q},o_jpg")
    samples: list = []
    failures = [0]
    it = iter(enumerate(reqs))

    async def worker():
        while True:
            item = next(it, None)
            if item is None:
                return
            i, options = item
            url = f"{urls[i % len(urls)]}/upload/{options}/{src}"
            t0 = time.perf_counter()
            try:
                resp = await client.get(url)
                ok = resp.status_code == 200 and len(resp.content) > 0
            except httpx.HTTPError:
                ok = False
                resp = None
            if ok:
                samples.append((
                    time.perf_counter() - t0,
                    "X-Flyimg-Reuse" in resp.headers,
                    resp.headers.get("X-Flyimg-Replica", ""),
                ))
            else:
                failures[0] += 1

    t0 = time.perf_counter()
    await asyncio.gather(*[worker() for _ in range(conc)])
    elapsed = time.perf_counter() - t0
    lat = np.asarray([s[0] for s in samples]) * 1000.0
    hits = sum(1 for s in samples if s[1])
    by_renderer: dict = {}
    for _, _, renderer in samples:
        if renderer:
            by_renderer[renderer] = by_renderer.get(renderer, 0) + 1
    return {
        "leg": "multisize",
        "requests": len(reqs),
        "ok": len(samples),
        "failures": failures[0],
        "elapsed_s": round(elapsed, 3),
        "throughput_rps": round(len(samples) / elapsed, 1) if elapsed else 0,
        "ancestor_hit_ratio": (
            round(hits / len(samples), 4) if samples else 0.0
        ),
        "latency_ms": {
            "p50": round(float(np.percentile(lat, 50)), 2),
            "p99": round(float(np.percentile(lat, 99)), 2),
        } if len(lat) else None,
        "served_by": by_renderer,
    }


async def _fleet_churn_leg(client, urls, procs, root) -> dict:
    """Kill + rejoin mid-run (docs/fleet.md "Membership and
    elasticity"): SIGKILL the last replica while hammering the
    survivors, measure the error count and the re-home disruption
    (fraction of a probe keyset whose rendezvous owner changed — the
    minimal-disruption bar is the victim's own 1/N share), then restart
    it twice on the same port — once warm-start-off, once on — and
    compare first-render latency and compile misses. Requires
    membership (the --churn spawn arms it), so re-homing is the
    watcher's doing, not a config push."""
    # bench_http otherwise never imports the package in-process; the
    # probe keyset check reuses the REAL HRW implementation
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo_root not in sys.path:
        sys.path.insert(0, repo_root)
    from flyimg_tpu.runtime.fleet import rendezvous_owner

    n = len(urls)
    victim = n - 1
    victim_url = urls[victim]
    victim_port = int(victim_url.rsplit(":", 1)[1])
    survivors = urls[:victim]
    shared = os.path.join(root, "shared-l2")
    # distinct PROGRAMS (blur/rotate change the device plan; pure w/h
    # variants can share one size-bucketed program) — rendered now so
    # the heartbeat publishes their identities before the kill
    mix = ("w_201,h_151,o_jpg", "w_202,blr_2,o_png",
           "w_203,h_140,r_90,o_jpg")
    src_seed = _make_source(os.path.join(root, "churn-seed.jpg"), seed=11)
    # same dims, different pixels: fresh cache keys over the SAME
    # programs, so the restart probes render instead of hitting L2
    src_cold = _make_source(os.path.join(root, "churn-cold.jpg"), seed=12)
    src_warm = _make_source(os.path.join(root, "churn-warm.jpg"), seed=13)

    async def members_of(url):
        try:
            resp = await client.get(f"{url}/debug/fleet")
            return sorted(resp.json().get("members", []))
        except (httpx.HTTPError, ValueError):
            return None

    async def wait_members(url, want, timeout_s):
        want = sorted(want)
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if await members_of(url) == want:
                return time.monotonic()
            await asyncio.sleep(CHURN_BEAT_S / 2)
        return None

    async def first_render_probe(url, src):
        """Latency + compile-miss cost of this replica's first renders
        (the full mix, sequentially — the scale-out cold-start tax)."""
        miss = 'flyimg_compile_events_total{result="miss"}'
        before = await _replica_metric(client, url, miss)
        t0 = time.monotonic()
        ok = 0
        for options in mix:
            resp = await client.get(f"{url}/upload/{options}/{src}")
            ok += 1 if resp.status_code == 200 else 0
        latency_ms = (time.monotonic() - t0) * 1000.0
        return {
            "first_render_ms": round(latency_ms, 1),
            "compile_misses": await _replica_metric(client, url, miss)
            - before,
            "ok": ok,
        }

    # membership must have converged before the kill means anything
    assembled = await wait_members(urls[0], urls, CHURN_TTL_S * 6)
    seeded_renders = 0
    for url in urls:
        for options in mix:
            resp = await client.get(f"{url}/upload/{options}/{src_seed}")
            seeded_renders += 1 if resp.status_code == 200 else 0
    manifest = os.path.join(root, "shared-l2",
                            "warmstart-programs.manifest")
    deadline = time.monotonic() + 15.0
    while time.monotonic() < deadline and not os.path.exists(manifest):
        await asyncio.sleep(CHURN_BEAT_S)

    probe_keys = [f"churn-probe-{i}" for i in range(512)]
    owners_before = {k: rendezvous_owner(list(urls), k)
                     for k in probe_keys}

    procs[victim].kill()
    procs[victim].wait()
    kill_t = time.monotonic()
    errors = 0
    requests = 0
    detected_s = None
    while time.monotonic() - kill_t < CHURN_TTL_S * 3:
        for url in survivors:
            for options in mix:
                requests += 1
                try:
                    resp = await client.get(
                        f"{url}/upload/{options}/{src_seed}"
                    )
                    errors += 0 if resp.status_code == 200 else 1
                except httpx.HTTPError:
                    errors += 1
        if detected_s is None:
            if await members_of(urls[0]) == sorted(survivors):
                detected_s = time.monotonic() - kill_t
    owners_after = {k: rendezvous_owner(list(survivors), k)
                    for k in probe_keys}
    moved = [k for k in probe_keys
             if owners_before[k] != owners_after[k]]
    moved_from_victim = [k for k in moved
                         if owners_before[k] == victim_url]

    # rejoin A (cold control): same port, warm start off. Both rejoins
    # run fleet_route=local — under proxy mode the probe's keys would
    # route to the already-warm survivors and measure nothing
    procs[victim] = _spawn_replica(
        victim, victim_port, root, urls, fleet_on=True, mode="local",
        membership=True, warmstart=False,
    )
    if not await _wait_healthy(client, [victim_url]):
        return {"error": "cold rejoin never became healthy"}
    cold = await first_render_probe(victim_url, src_cold)
    procs[victim].send_signal(signal.SIGTERM)
    procs[victim].wait()

    # rejoin B (the real thing): warm start seeds the program cache
    # from the fleet manifest before the port opens
    procs[victim] = _spawn_replica(
        victim, victim_port, root, urls, fleet_on=True, mode="local",
        membership=True, warmstart=True,
    )
    if not await _wait_healthy(client, [victim_url]):
        return {"error": "warm rejoin never became healthy"}
    rejoin_t = time.monotonic()
    converged = await wait_members(urls[0], urls, CHURN_TTL_S * 6)
    warm = await first_render_probe(victim_url, src_warm)

    return {
        "ttl_s": CHURN_TTL_S,
        "heartbeat_s": CHURN_BEAT_S,
        "assembled_before_kill": assembled is not None,
        "kill": {
            "victim": victim_url,
            "requests_during_outage": requests,
            "errors_during_outage": errors,
            "detected_after_s": (
                round(detected_s, 2) if detected_s is not None else None
            ),
            "probe_keys": len(probe_keys),
            "keys_moved": len(moved),
            "keys_moved_from_victim": len(moved_from_victim),
            "rehome_fraction": round(len(moved) / len(probe_keys), 3),
            "minimal_disruption": len(moved) == len(moved_from_victim),
        },
        "rejoin": {
            "cold": cold,
            "warm": warm,
            "warm_vs_cold_latency": (
                round(warm["first_render_ms"] / cold["first_render_ms"], 3)
                if cold["first_render_ms"] else None
            ),
            "converge_after_s": (
                round(converged - rejoin_t, 2)
                if converged is not None else None
            ),
        },
    }


async def _fleet_ab(args) -> int:
    """The --replicas A/B: one fleet with routing+L2+lease on, one
    control fleet of isolated replicas, same legs, one artifact
    (benchmarks/FLEET_r01.json)."""
    import shutil
    import tempfile

    n = args.replicas
    configs = [("fleet_on", True), ("fleet_off", False)]
    results = {}
    for name, fleet_on in configs:
        root = tempfile.mkdtemp(prefix=f"flyimg-fleet-{name}-")
        procs, urls = _spawn_fleet(
            n, root, fleet_on=fleet_on, mode=args.fleet_route,
            membership=args.churn,
        )
        try:
            async with httpx.AsyncClient(
                timeout=120.0, limits=httpx.Limits(max_connections=256)
            ) as client:
                if not await _wait_healthy(client, urls):
                    print(f"{name}: fleet never became healthy",
                          file=sys.stderr)
                    return 1
                src = _make_source(args.source)
                # the multisize leg gets its OWN source: the hot-key leg
                # already ran index lookups on the first one, and the
                # variant index's short negative-lookup memo
                # (runtime/variantindex.py NEGATIVE_TTL_S) would
                # honestly suppress reuse on it for up to 30 s
                src_multi = _make_source(
                    os.path.join(
                        os.path.dirname(args.source) or ".",
                        "bench-fleet-multisize.jpg",
                    ),
                    seed=4242,
                )
                print(f"== {name}: {n} replicas "
                      f"({'routing+L2+lease' if fleet_on else 'isolated'})")
                hot = await _fleet_hot_key_leg(
                    client, urls, src, conc=4 * n
                )
                print(
                    f"  hot key: {hot['duplicate_renders']:.0f} renders "
                    f"for {hot['requests']} concurrent requests "
                    f"(per replica {hot['renders_per_replica']})"
                )
                multi = await _fleet_multisize_leg(
                    client, urls, src_multi, args.mix_requests, args.conc
                )
                print(
                    f"  multisize: ratio {multi.get('ancestor_hit_ratio')} "
                    f"rps {multi.get('throughput_rps')} "
                    f"p50 {(multi.get('latency_ms') or {}).get('p50')}ms "
                    f"served_by {multi.get('served_by')}"
                )
                replicas = {
                    url: await _replica_snapshot(client, url)
                    for url in urls
                }
                for url, snap in replicas.items():
                    batch = snap.get("batch") or {}
                    launches = snap.get("launches") or {}
                    print(
                        f"    {url}: renders {snap['renders']:.0f} "
                        f"occupancy {batch.get('mean_occupancy')} "
                        f"batch_size {launches.get('mean_batch_size')} "
                        f"programs {snap.get('distinct_programs')} "
                        f"img/compile {launches.get('images_per_compile_miss')}"
                    )
                results[name] = {
                    "replicas": n,
                    "mode": args.fleet_route if fleet_on else None,
                    "hot_key": hot,
                    "multisize": multi,
                    "per_replica": replicas,
                }
                if args.churn and fleet_on:
                    churn = await _fleet_churn_leg(
                        client, urls, procs, root
                    )
                    results[name]["churn"] = churn
                    kill = churn.get("kill") or {}
                    rejoin = churn.get("rejoin") or {}
                    print(
                        f"  churn: {kill.get('errors_during_outage')} "
                        f"errors/{kill.get('requests_during_outage')} "
                        f"requests, detected "
                        f"{kill.get('detected_after_s')}s, re-home "
                        f"{kill.get('rehome_fraction')} (minimal "
                        f"{kill.get('minimal_disruption')}), first "
                        f"render warm "
                        f"{(rejoin.get('warm') or {}).get('first_render_ms')}ms"
                        f" vs cold "
                        f"{(rejoin.get('cold') or {}).get('first_render_ms')}ms"
                    )
        finally:
            for proc in procs:
                proc.send_signal(signal.SIGTERM)
            for proc in procs:
                try:
                    proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    proc.kill()
            shutil.rmtree(root, ignore_errors=True)

    def _occupancies(doc):
        return [
            (snap.get("batch") or {}).get("mean_occupancy")
            for snap in doc["per_replica"].values()
        ]

    artifact = {
        "what": (
            "Multi-replica fleet A/B (docs/fleet.md): rendezvous routing "
            "+ shared L2 + cross-replica lease vs N isolated replicas "
            "behind round-robin — duplicate renders of one hot key, "
            "cross-replica ancestor-hit ratio on the multisize Zipf mix, "
            "and per-replica batch occupancy / distinct compiled programs"
        ),
        "method": (
            f"bench_http --replicas {n} --fleet-route {args.fleet_route} "
            f"--mix-requests {args.mix_requests} --conc {args.conc}; "
            "every replica a spawned process on this host; client "
            "round-robins requests across replicas"
        ),
        "backend": os.environ.get("JAX_PLATFORMS", "default"),
        "legs": results,
        "summary": {
            "hot_key_renders_on": results["fleet_on"]["hot_key"][
                "duplicate_renders"
            ],
            "hot_key_renders_off": results["fleet_off"]["hot_key"][
                "duplicate_renders"
            ],
            "ancestor_hit_ratio_on": results["fleet_on"]["multisize"].get(
                "ancestor_hit_ratio"
            ),
            "ancestor_hit_ratio_off": results["fleet_off"][
                "multisize"
            ].get("ancestor_hit_ratio"),
            "mean_occupancy_on": _occupancies(results["fleet_on"]),
            "mean_occupancy_off": _occupancies(results["fleet_off"]),
            "mean_batch_size_on": [
                (snap.get("launches") or {}).get("mean_batch_size")
                for snap in results["fleet_on"]["per_replica"].values()
            ],
            "mean_batch_size_off": [
                (snap.get("launches") or {}).get("mean_batch_size")
                for snap in results["fleet_off"]["per_replica"].values()
            ],
            "distinct_programs_on": [
                snap.get("distinct_programs")
                for snap in results["fleet_on"]["per_replica"].values()
            ],
            "distinct_programs_off": [
                snap.get("distinct_programs")
                for snap in results["fleet_off"]["per_replica"].values()
            ],
            "images_per_compile_miss_on": [
                (snap.get("launches") or {}).get("images_per_compile_miss")
                for snap in results["fleet_on"]["per_replica"].values()
            ],
            "images_per_compile_miss_off": [
                (snap.get("launches") or {}).get("images_per_compile_miss")
                for snap in results["fleet_off"]["per_replica"].values()
            ],
        },
    }
    churn = results["fleet_on"].get("churn")
    if churn is not None:
        kill = churn.get("kill") or {}
        rejoin = churn.get("rejoin") or {}
        artifact["summary"]["churn"] = {
            "errors_during_outage": kill.get("errors_during_outage"),
            "rehome_fraction": kill.get("rehome_fraction"),
            "minimal_disruption": kill.get("minimal_disruption"),
            "detected_after_s": kill.get("detected_after_s"),
            "first_render_cold_ms": (
                (rejoin.get("cold") or {}).get("first_render_ms")
            ),
            "first_render_warm_ms": (
                (rejoin.get("warm") or {}).get("first_render_ms")
            ),
            "compile_misses_cold": (
                (rejoin.get("cold") or {}).get("compile_misses")
            ),
            "compile_misses_warm": (
                (rejoin.get("warm") or {}).get("compile_misses")
            ),
        }
    print(json.dumps(artifact["summary"]))
    if args.fleet_out:
        with open(args.fleet_out, "w") as fh:
            json.dump(artifact, fh, indent=1)
            fh.write("\n")
        print(f"wrote {args.fleet_out}")
    return 0


async def _scrape_observability(client: httpx.AsyncClient, base: str):
    """End-of-run attribution scrape: batch efficiency (/debug/perf),
    the per-plan cost ledger (/debug/plans), and the flight-recorder
    summary (/debug/flightrecorder) — so bench artifacts carry
    per-plan FLOP/byte/occupancy attribution next to throughput, not
    just throughput. Returns None per section when the target serves
    404 (debug off — e.g. --base against a production config)."""

    async def _get(path):
        try:
            resp = await client.get(f"{base}{path}")
            if resp.status_code != 200:
                return None
            return resp.json()
        except (httpx.HTTPError, ValueError):
            return None

    perf = await _get("/debug/perf")
    plans = await _get("/debug/plans")
    recorder = await _get("/debug/flightrecorder")
    # telemetry warehouse (runtime/telemetry.py): the adopted traffic-mix
    # label + archive segment count, compact — None when the endpoint
    # 404s (debug off) or the warehouse is disabled
    telemetry_doc = await _get("/debug/telemetry")
    telemetry = None
    if isinstance(telemetry_doc, dict) and telemetry_doc.get("enabled"):
        telemetry = {
            "mix": (telemetry_doc.get("mix") or {}).get("label"),
            "segments": len(
                (telemetry_doc.get("archive") or {}).get("segments") or []
            ),
        }
    # memory governor (runtime/memgovernor.py): pre-split/OOM counts
    # and the target's peak RSS, so capacity rows carry the memory
    # footprint next to the throughput — None when the endpoint 404s
    # (debug off) or the governor never registered
    memory_doc = await _get("/debug/memory")
    memory = None
    if isinstance(memory_doc, dict):
        memory = {
            "presplits_total": (
                (memory_doc.get("governor") or {}).get("presplits_total")
            ),
            "oom_launches_total": (
                (memory_doc.get("governor") or {}).get("oom_launches_total")
            ),
            "peak_rss_bytes": (memory_doc.get("rss") or {}).get("peak_bytes"),
        }
    plan_costs = None
    if plans is not None:
        rows = plans.get("plans", [])
        plan_costs = {
            "aggregates": plans.get("aggregates"),
            # the top device-time consumers, compact: enough to attribute
            # a sweep without embedding the whole ledger per row
            "top_plans": [
                {
                    "key": row["key"],
                    "ops": (row.get("descriptor") or {}).get("ops"),
                    "batch": (row.get("descriptor") or {}).get("batch"),
                    "flops": row.get("flops"),
                    "bytes_accessed": row.get("bytes_accessed"),
                    "launches": row.get("launches"),
                    "device_s": row.get("device_s"),
                }
                for row in rows[:8]
            ],
        }
    return {
        "batch_efficiency": (
            (perf or {}).get("controllers") if perf is not None else None
        ),
        "device": (perf or {}).get("device") if perf is not None else None,
        "plan_costs": plan_costs,
        "flightrecorder": (
            recorder.get("summary") if recorder is not None else None
        ),
        "telemetry": telemetry,
        "memory": memory,
    }


async def _sample_signals(client: httpx.AsyncClient, base: str,
                          interval_s: float, stop: asyncio.Event):
    """Background sampler behind the report's ``signal_timeline``: one
    joined reading of /debug/slo (burn rates) and /debug/fleet/status
    (the standing autoscale recommendation + fleet rollup) every
    ``interval_s``, timestamped from the run start — so a bench
    artifact shows not just the latency the load produced but the
    control-plane signals it drove (when did burn cross the threshold,
    when did the recommendation flip). Endpoints serving 404 (debug or
    observatory off) contribute nothing; an all-404 run yields an
    empty timeline, not an error."""
    samples = []
    t0 = time.monotonic()
    while True:
        sample: dict = {"t": round(time.monotonic() - t0, 2)}
        try:
            resp = await client.get(f"{base}/debug/slo")
            if resp.status_code == 200:
                windows = resp.json().get("windows") or {}
                sample["burn_fast"] = (
                    (windows.get("fast") or {}).get("burn_rate")
                )
                sample["burn_slow"] = (
                    (windows.get("slow") or {}).get("burn_rate")
                )
        except (httpx.HTTPError, ValueError):
            pass
        try:
            resp = await client.get(f"{base}/debug/fleet/status")
            if resp.status_code == 200:
                observatory = resp.json().get("observatory") or {}
                rec = observatory.get("recommendation") or {}
                if rec:
                    sample["recommendation"] = rec.get("action")
                    sample["delta"] = rec.get("delta")
                rollup = observatory.get("rollup") or {}
                if rollup:
                    sample["fleet_burn_worst"] = rollup.get("burn_worst")
                    sample["fleet_routable"] = rollup.get("routable")
        except (httpx.HTTPError, ValueError):
            pass
        if len(sample) > 1:
            samples.append(sample)
        if stop.is_set():
            return samples
        try:
            await asyncio.wait_for(stop.wait(), timeout=interval_s)
        except asyncio.TimeoutError:
            pass


async def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--base", default=None, help="base URL of a running service")
    ap.add_argument("--rate", type=float, default=50.0)
    ap.add_argument("--duration", type=float, default=10.0)
    ap.add_argument("--burst", type=int, default=2000, help="burst request count (0=skip)")
    ap.add_argument("--conc", type=int, default=32, help="burst concurrency")
    ap.add_argument(
        "--signal-sample-s", type=float, default=1.0,
        help="sampling period for the SLO-burn / autoscale-recommendation "
             "timeline embedded in report rows (reads /debug/slo and "
             "/debug/fleet/status; 0 = off)",
    )
    ap.add_argument(
        "--miss", type=int, default=0,
        help="cache-miss scenario: N distinct sources, each a fresh "
             "full-pipeline request (0=skip)",
    )
    ap.add_argument(
        "--miss-warm", type=int, default=64,
        help="throwaway miss requests first, so the batch-size ladder's "
             "programs are compiled before measurement",
    )
    ap.add_argument(
        "--miss-rates", default=None,
        help="comma list of req/s for a RATED miss sweep (each rate runs "
             "--duration s of distinct-key misses; the p99-vs-rate curve "
             "locates the miss-path knee)")
    ap.add_argument(
        "--miss-out", default=None,
        help="write the rated-miss sweep rows to this JSON artifact")
    ap.add_argument(
        "--fresh-storage", action="store_true",
        help="spawn the service with a throwaway output-cache dir. "
             "REQUIRED for honest miss measurements: a persistent "
             "web/uploads populated by earlier runs silently turns "
             "'misses' into 4 ms cache hits (found the hard way, round 5)")
    ap.add_argument("--spawn", action="store_true", help="start the service here")
    ap.add_argument("--source", default="var/tmp/bench-source.jpg")
    ap.add_argument(
        "--kernel", default=None, choices=("dense", "banded", "auto"),
        help="resample-kernel variant for the A/B legs (docs/kernels.md): "
             "written into the spawned service's params and stamped into "
             "every result row. With --base it only stamps the rows — the "
             "target's own config decides what actually runs")
    ap.add_argument(
        "--mix", default=None,
        choices=("multisize", "thumbnail", "cropzoom"),
        help="traffic-mix scenario: 'multisize' = ONE source requested "
             "at a Zipf-distributed ladder of crop sizes, every request "
             "a distinct uncached key — the derivative-reuse pattern "
             "(docs/caching.md). Reports ancestor-hit ratio and the "
             "p50/p99 split between reuse=hit and reuse=miss rows. "
             "'thumbnail' = ONE 4k source, a Zipf ladder of small "
             "fit-resize outputs (the decode-dominated firehose); "
             "'cropzoom' = overlapping extract windows on the 4k source "
             "(pan/zoom traffic). Both report the decode-stage p50/p99 "
             "split by decode mode (full | prescale | roi) scraped from "
             "/debug/perf — the host-codec-overhaul A/B artifact "
             "(docs/host-pipeline.md)")
    ap.add_argument(
        "--mix-requests", type=int, default=300,
        help="requests in the --mix leg")
    ap.add_argument(
        "--reuse", default=None, choices=("on", "off"),
        help="derivative-reuse rewriter for the spawned service "
             "(reuse_enable; docs/caching.md), stamped into every result "
             "row as reuse_enable. With --base it only stamps the rows")
    ap.add_argument(
        "--decode-roi", default=None, choices=("on", "off"),
        help="ROI JPEG decode for the spawned service (decode_roi; "
             "docs/host-pipeline.md), stamped into every result row. "
             "With --base it only stamps the rows")
    ap.add_argument(
        "--pipeline", default=None, choices=("on", "off"),
        help="host stage DAG for the spawned service "
             "(host_pipeline_enable; docs/host-pipeline.md), stamped "
             "into every result row. With --base it only stamps the rows")
    ap.add_argument(
        "--replicas", type=int, default=0,
        help="multi-replica fleet A/B (docs/fleet.md): spawn N app "
             "processes behind a round-robin client, once with "
             "rendezvous routing + shared L2 + cross-replica lease and "
             "once isolated (the control), measuring hot-key duplicate "
             "renders, cross-replica ancestor-hit ratio, and per-replica "
             "batch occupancy. Replaces the standard scenarios")
    ap.add_argument(
        "--fleet-route", default="proxy", choices=("proxy", "local"),
        help="non-owner behavior in the fleet-on leg (fleet_route knob)")
    ap.add_argument(
        "--fleet-out", default=None,
        help="write the fleet A/B artifact to this JSON path "
             "(e.g. benchmarks/FLEET_r01.json)")
    ap.add_argument(
        "--churn", action="store_true",
        help="add a kill+rejoin leg to the fleet-on A/B run (requires "
             "--replicas): arms fleet membership + warm start on every "
             "replica, SIGKILLs one mid-run (error count + re-home "
             "disruption vs the minimal 1/N bar), then restarts it "
             "cold and warm to compare first-render latency and "
             "compile misses")
    args = ap.parse_args()

    if args.replicas:
        if args.base:
            print("--replicas spawns its own fleet; --base conflicts",
                  file=sys.stderr)
            return 2
        return await _fleet_ab(args)

    if args.base and args.spawn:
        print("--base and --spawn are mutually exclusive", file=sys.stderr)
        return 2

    global _KERNEL_TAG, _REUSE_TAG, _ROI_TAG, _PIPELINE_TAG
    _KERNEL_TAG = args.kernel
    _REUSE_TAG = args.reuse
    _ROI_TAG = args.decode_roi
    _PIPELINE_TAG = args.pipeline

    proc = None
    store = None
    base = args.base
    if base is None:
        import tempfile

        port = _free_port()
        base = f"http://127.0.0.1:{port}"
        spawn_cmd = [
            sys.executable, "-m", "flyimg_tpu.service.app", "serve",
            "--port", str(port),
        ]
        if args.fresh_storage:
            store = tempfile.mkdtemp(prefix="flyimg-bench-store-")
            params_dir = store
        else:
            params_dir = tempfile.mkdtemp(prefix="flyimg-bench-params-")
        # spawned services always run with debug on: the end-of-run
        # attribution scrape (/debug/perf, /debug/plans,
        # /debug/flightrecorder) is the point of a bench artifact
        params_path = os.path.join(params_dir, "params.yml")
        with open(params_path, "w") as fh:
            fh.write("debug: true\n")
            if args.kernel is not None:
                fh.write(f"resample_kernel: {args.kernel}\n")
            if args.reuse is not None:
                fh.write(
                    f"reuse_enable: {'true' if args.reuse == 'on' else 'false'}\n"
                )
            if args.decode_roi is not None:
                fh.write(
                    "decode_roi: "
                    f"{'true' if args.decode_roi == 'on' else 'false'}\n"
                )
            if args.pipeline is not None:
                fh.write(
                    "host_pipeline_enable: "
                    f"{'true' if args.pipeline == 'on' else 'false'}\n"
                )
            if store is not None:
                fh.write(f"upload_dir: {os.path.join(store, 'out')}\n")
        spawn_cmd += ["--params", params_path]
        proc = subprocess.Popen(
            spawn_cmd,
            stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL,
        )

    src = _make_source(args.source)
    rc = 0
    try:
        async with httpx.AsyncClient(
            timeout=60.0, limits=httpx.Limits(max_connections=256)
        ) as client:
            # wait for readiness
            for _ in range(120):
                try:
                    r = await client.get(f"{base}/healthz")
                    if r.status_code == 200:
                        break
                except httpx.HTTPError:
                    pass
                await asyncio.sleep(1.0)
            else:
                print("service never became healthy", file=sys.stderr)
                return 1

            print(f"target {base}  rate {args.rate} req/s x {args.duration}s "
                  f"+ burst {args.burst} @ conc {args.conc}")
            stop_signals = asyncio.Event()
            signal_task = (
                asyncio.create_task(_sample_signals(
                    client, base, args.signal_sample_s, stop_signals,
                ))
                if args.signal_sample_s > 0 else None
            )
            all_rows = []
            for name, options in SCENARIOS:
                url = f"{base}/upload/{options}/{src}"
                warm = await client.get(url)   # first miss computes
                if warm.status_code != 200:
                    print(f"{name}: warmup failed ({warm.status_code})")
                    if args.base and "://" not in args.source:
                        print(
                            "  note: with --base, --source is resolved by "
                            "the TARGET service (relative to its cwd); pass "
                            "a URL or a path that exists on the service host",
                            file=sys.stderr,
                        )
                    rc = 1
                    continue
                lat, fails, elapsed = await _rated_run(
                    client, [url] * int(args.rate * args.duration), args.rate
                )
                all_rows.append(_report(name, "rated", lat, fails, elapsed))
                if args.burst:
                    lat, fails, elapsed = await _burst_run(
                        client, url, args.burst, args.conc
                    )
                    all_rows.append(
                        _report(name, "burst", lat, fails, elapsed)
                    )

            if args.miss:
                # distinct sources (same dims -> one shape bucket) so every
                # request is an uncoalescible cache miss; seed 1000+ avoids
                # colliding with the shared cache-hit source
                src_dir = os.path.dirname(args.source) or "."
                miss_srcs = [
                    _make_source(
                        os.path.join(src_dir, f"bench-miss-{i}.jpg"),
                        seed=1000 + i,
                    )
                    for i in range(args.miss_warm + args.miss)
                ]
                options = SCENARIOS[0][1]  # crop, the reference's headline
                urls = [
                    f"{base}/upload/{options}/{s}" for s in miss_srcs
                ]
                if args.miss_warm:
                    await _miss_run(client, urls[: args.miss_warm], args.conc)
                lat, fails, elapsed = await _miss_run(
                    client, urls[args.miss_warm:], args.conc
                )
                all_rows.append(
                    _report("miss", "burst", lat, fails, elapsed)
                )

            if args.miss_rates:
                rates = [float(r) for r in args.miss_rates.split(",")]
                src_dir = os.path.dirname(args.source) or "."
                # a reusable pool of distinct sources; distinct CACHE KEYS
                # come from source x quality so the pool stays modest while
                # every request is still an uncoalescible miss
                pool = [
                    _make_source(
                        os.path.join(src_dir, f"bench-miss-{i}.jpg"),
                        seed=1000 + i,
                    )
                    for i in range(320)
                ]
                # q_90 canonicalizes to the SAME cache key as no-q (the
                # default quality), so start below it or the first leg's
                # "misses" can hit outputs cached by a plain-options run
                key_seq = iter(
                    (s, q) for q in range(89, 1, -1) for s in pool
                )
                available = len(pool) * len(range(89, 1, -1))
                needed = 16 + 2 * sum(
                    max(int(r * args.duration), 1) for r in rates
                )
                if needed > available:
                    print(
                        f"miss sweep needs {needed} distinct keys, only "
                        f"{available} available — lower the rates/duration",
                        file=sys.stderr,
                    )
                    return 1

                def next_urls(options, n):
                    out = []
                    for _ in range(n):
                        s, q = next(key_seq)
                        out.append(f"{base}/upload/{options},q_{q}/{s}")
                    return out

                # warm the batch ladder + program cache once, off-record
                await _miss_run(
                    client, next_urls(SCENARIOS[0][1], 16), 8
                )
                sweep = []
                for vname, vopts in (
                    ("moz_1", SCENARIOS[0][1]),
                    ("moz_0", SCENARIOS[0][1] + ",moz_0"),
                ):
                    for rate in rates:
                        n = max(int(rate * args.duration), 1)
                        lat, fails, elapsed = await _rated_run(
                            client, next_urls(vopts, n), rate
                        )
                        row = _report(
                            f"miss-{vname}", f"rated@{rate:g}", lat, fails,
                            elapsed,
                        )
                        row["offered_rate_rps"] = rate
                        row["options"] = vopts
                        sweep.append(row)
                        all_rows.append(row)

            if args.mix == "multisize":
                # ONE source, Zipf-distributed crop-size ladder, every
                # request a distinct uncached key (q_ varies the derived
                # name): the derivative-reuse traffic pattern. The w_800
                # warm render seeds the pure ancestor; sizes <= half of
                # it are reuse-eligible, larger ones exercise the
                # unsafe->full-pipeline fallback (docs/caching.md).
                anc = await client.get(f"{base}/upload/w_800,o_jpg/{src}")
                if anc.status_code != 200:
                    print(
                        f"multisize: ancestor warm failed "
                        f"({anc.status_code})", file=sys.stderr,
                    )
                    rc = 1
                else:
                    ladder = [100, 128, 160, 200, 256, 320, 400, 512, 640]
                    weights = _zipf_weights(len(ladder))
                    rng = np.random.default_rng(20260803)
                    counts = {size: 0 for size in ladder}
                    urls = []
                    for _ in range(args.mix_requests):
                        size = int(
                            rng.choice(ladder, p=weights)
                        )
                        q = 89 - counts[size]
                        if q < 2:
                            continue  # that size's key space is spent
                        counts[size] += 1
                        h = int(size * 3 / 4)
                        urls.append(
                            f"{base}/upload/w_{size},h_{h},c_1,q_{q},"
                            f"o_jpg/{src}"
                        )
                    samples, fails, elapsed = await _multisize_run(
                        client, urls, args.conc
                    )
                    hits = [lat for lat, reused in samples if reused]
                    misses = [lat for lat, reused in samples if not reused]
                    ratio = (
                        round(len(hits) / len(samples), 4) if samples else 0.0
                    )
                    print(
                        f"multisize: {len(samples)} ok / {fails} failed, "
                        f"ancestor-hit ratio {ratio}"
                    )
                    for leg, lat in (("hit", hits), ("miss", misses)):
                        if not lat:
                            # an empty leg (e.g. no hits with --reuse
                            # off) is an absent curve, NOT a saturated
                            # run — _report's all-failed row would read
                            # as an overload knee to artifact consumers
                            print(f"multisize reuse-{leg}: no samples")
                            continue
                        row = _report(
                            "multisize", f"reuse-{leg}", lat, 0,
                            max(elapsed, 1e-9),
                            extra={
                                "reuse": leg,
                                "ancestor_hit_ratio": ratio,
                                # the legs interleave in ONE closed
                                # loop: the wall clock is shared and a
                                # failed request carries no reuse
                                # header, so per-leg throughput/success
                                # cannot be attributed honestly — the
                                # split rows carry latency only, with
                                # run-level figures alongside
                                "throughput_rps": None,
                                "success_rate": None,
                                "run_failures": fails,
                                "run_elapsed_s": round(elapsed, 3),
                            },
                        )
                        all_rows.append(row)

            if args.mix in ("thumbnail", "cropzoom"):
                # host-codec-overhaul mixes (docs/host-pipeline.md): ONE
                # 4k source; every request a distinct uncached key so the
                # full miss pipeline runs. 'thumbnail' is a Zipf ladder
                # of SQUARE crop thumbnails (crop-dominant on a 16:9
                # frame: prescale + ROI both engage); 'cropzoom' is
                # overlapping e_ extract windows at three zoom levels
                # (pan/zoom traffic — full-scale decode, ROI-dominant).
                src4k = _make_source_4k(
                    os.path.join(
                        os.path.dirname(args.source) or ".", "bench-4k.jpg"
                    )
                )
                rng = np.random.default_rng(20260803)
                urls = []
                warm_urls = []
                dropped_keyspace = 0
                if args.mix == "thumbnail":
                    ladder = [64, 96, 128, 160, 200, 256, 320, 400, 512]
                    weights = _zipf_weights(len(ladder))
                    counts = {size: 0 for size in ladder}
                    warm_urls = [
                        f"{base}/upload/w_{s},h_{s},c_1,q_90,o_jpg/{src4k}"
                        for s in ladder
                    ]
                    for _ in range(args.mix_requests):
                        size = int(rng.choice(ladder, p=weights))
                        q = 89 - counts[size]
                        if q < 2:
                            # that size's quality-derived key space is
                            # spent; COUNTED and stamped into the row —
                            # a silently smaller request set would
                            # misrepresent the measured mix
                            dropped_keyspace += 1
                            continue
                        counts[size] += 1
                        urls.append(
                            f"{base}/upload/w_{size},h_{size},c_1,q_{q},"
                            f"o_jpg/{src4k}"
                        )
                else:
                    zooms = [(960, 540), (1280, 720), (1920, 1080)]
                    warm_urls = [
                        f"{base}/upload/e_1,p1x_0,p1y_0,p2x_{zw},p2y_{zh},"
                        f"w_320,q_90,o_jpg/{src4k}"
                        for zw, zh in zooms
                    ]
                    for i in range(args.mix_requests):
                        zw, zh = zooms[i % len(zooms)]
                        x = int(rng.integers(0, (3840 - zw) // 16 + 1)) * 16
                        y = int(rng.integers(0, (2160 - zh) // 16 + 1)) * 16
                        q = 88 - (i % 80)
                        urls.append(
                            f"{base}/upload/e_1,p1x_{x},p1y_{y},"
                            f"p2x_{x + zw},p2y_{y + zh},w_320,q_{q},"
                            f"o_jpg/{src4k}"
                        )
                if dropped_keyspace:
                    print(
                        f"{args.mix}: {dropped_keyspace} of "
                        f"{args.mix_requests} requests dropped (Zipf-top "
                        "rung key space spent) — raise the ladder or "
                        "lower --mix-requests",
                        file=sys.stderr,
                    )
                # warm pass compiles the ladder's program shapes
                # off-record (one request per distinct geometry)
                await _miss_run(client, warm_urls, min(args.conc, 4))
                lat, fails, elapsed = await _miss_run(
                    client, urls, args.conc
                )
                split = await _decode_split(client, base)
                extra = {"decode_stages": split}
                if dropped_keyspace:
                    extra["requests_dropped_keyspace"] = dropped_keyspace
                all_rows.append(
                    _report(
                        args.mix, "miss", lat, fails, elapsed,
                        extra=extra,
                    )
                )
                if split:
                    for mode, doc in sorted(split.items()):
                        print(
                            f"  {mode:16s} n={doc['count']:<5} "
                            f"p50={doc['p50_ms']}ms p99={doc['p99_ms']}ms"
                        )

            # end-of-run attribution: batch efficiency + per-plan cost +
            # flight-recorder summary embedded in every row (and the
            # sweep artifact), so the artifact carries attribution, not
            # just throughput. None sections = target served 404
            # (debug off).
            # the control-plane timeline rides every row next to the
            # latency it explains (empty when the target's debug
            # endpoints answered 404 throughout)
            if signal_task is not None:
                stop_signals.set()
                timeline = await signal_task
                if timeline:
                    for row in all_rows:
                        row["signal_timeline"] = timeline
                    print(json.dumps({"signal_timeline": {
                        "samples": len(timeline),
                        "last": timeline[-1],
                    }}))

            obs = await _scrape_observability(client, base)
            if obs is not None and any(v is not None for v in obs.values()):
                for row in all_rows:
                    row["batch_efficiency"] = obs["batch_efficiency"]
                    row["plan_costs"] = obs["plan_costs"]
                    row["flightrecorder"] = obs["flightrecorder"]
                    if obs.get("telemetry") is not None:
                        # traffic-shape attribution (ISSUE 19): which
                        # mix label the warehouse adopted for this run
                        row["traffic_mix"] = obs["telemetry"]["mix"]
                        row["telemetry_segments"] = (
                            obs["telemetry"]["segments"]
                        )
                    if obs.get("memory") is not None:
                        # memory-footprint attribution: the target's
                        # peak RSS and governor interventions
                        row["peak_rss_bytes"] = (
                            obs["memory"]["peak_rss_bytes"]
                        )
                        row["mem_presplits_total"] = (
                            obs["memory"]["presplits_total"]
                        )
                print(json.dumps({"observability": obs}))
            elif args.base:
                print(
                    "note: target serves no /debug endpoints (debug off) — "
                    "rows carry no batch-efficiency/plan-cost attribution",
                    file=sys.stderr,
                )

            if args.miss_rates and args.miss_out:
                with open(args.miss_out, "w") as fh:
                    json.dump({
                        "what": (
                            "RATED (open-loop) cache-MISS latency vs "
                            "offered rate; every request is a distinct "
                            "uncoalescible key through the full "
                            "fetch/decode/device/encode miss pipeline"
                        ),
                        "method": (
                            f"{args.duration}s per rate per encoder "
                            "variant; vegeta-style fixed schedule; "
                            "service and client share this host"
                        ),
                        "backend": os.environ.get(
                            "JAX_PLATFORMS", "default"
                        ),
                        "kernel": args.kernel,
                        "rows": sweep,
                    }, fh, indent=1)
                    fh.write("\n")
                print(f"wrote {args.miss_out}")
    finally:
        if proc is not None:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
        if store is not None:
            # the throwaway cache holds thousands of miss outputs per sweep
            import shutil

            shutil.rmtree(store, ignore_errors=True)
    return rc


if __name__ == "__main__":
    raise SystemExit(asyncio.run(main()))
