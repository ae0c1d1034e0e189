"""CI device-failover smoke: boot the app with the backend supervisor
on under an injected persistent device-fault storm, and prove the
replica degrades and re-joins instead of wedging (docs/resilience.md
"Backend failover"):

1. a key is seeded while the backend is healthy (`flyimg_device_health`
   reads 1);
2. the injected storm kills device launches — the storm-trigger request
   burns its bounded retries, the backend breaker trips, and the gauge
   walks to 0;
3. while failed over: the seeded CACHE HIT stays 200 and untagged,
   misses serve within the deadline as `X-Flyimg-Degraded:
   cpu-fallback` with `Cache-Control: max-age=60` (never cached — the
   same key misses again), and `/readyz` reports `device: down` while
   staying 200 so peers route around the replica without a load
   balancer pulling it;
4. the injected fault clears, the background prober's consecutive clean
   probes re-promote WITHOUT a restart: the gauge walks back to 1,
   misses lose the tag and cache normally, and the failover counters
   read exactly one `to="cpu"` + one `to="device"`.

    JAX_PLATFORMS=cpu python tools/smoke_device_failover.py

Exit code 0 = every assertion held. The behavioral matrix (storm
threshold math, drain bounds, parity, fleet gating) lives in
tests/test_device_supervisor.py; this script proves the wired-together
service end to end.
"""

from __future__ import annotations

import asyncio
import os
import sys
import tempfile

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

REQUEST_TIMEOUT_S = 120.0


def _require(cond: bool, what: str) -> None:
    if not cond:
        print(f"FAIL: {what}", file=sys.stderr)
        raise SystemExit(1)


def _metric_value(text: str, name: str) -> float:
    for line in text.splitlines():
        if line.startswith(name) and not line.startswith("#"):
            try:
                return float(line.rsplit(" ", 1)[1])
            except ValueError:
                continue
    return 0.0


async def main() -> int:
    import numpy as np
    from aiohttp.test_utils import TestClient, TestServer

    from flyimg_tpu.appconfig import AppParameters
    from flyimg_tpu.codecs import encode
    from flyimg_tpu.service.app import SUPERVISOR_KEY, make_app
    from flyimg_tpu.testing import faults

    tmp = tempfile.mkdtemp(prefix="flyimg-devfail-")
    rng = np.random.default_rng(3)
    src = os.path.join(tmp, "src.png")
    with open(src, "wb") as fh:
        fh.write(
            encode(rng.integers(0, 220, (48, 64, 3), dtype=np.uint8), "png")
        )

    # the scripted outage: while `storm` holds, every device readback
    # raises a transient transport error (a dying device's signature);
    # while `dead` holds, every backend probe reports the device gone.
    # Clearing `storm` models "the device is unreachable, CPU serves";
    # clearing `dead` models "the device is back".
    storm = {"on": False}
    dead = {"on": True}
    injector = faults.FaultInjector()

    def drain_plan(**_ctx):
        if storm["on"]:
            raise ConnectionError("smoke: device transport gone")
        return faults.PASS

    injector.plan("batcher.drain", drain_plan)
    injector.plan("device.backend", lambda **_: not dead["on"])

    params = AppParameters({
        "tmp_dir": os.path.join(tmp, "t"),
        "upload_dir": os.path.join(tmp, "u"),
        "fault_injector": injector,
        "device_supervisor_enable": True,
        "device_storm_threshold": 2,
        "device_storm_window_s": 60.0,
        "device_probe_interval_s": 0.2,
        "device_probe_hysteresis": 2,
        "device_failover_drain_s": 2.0,
        "resilience_batch_retries": 1,
        "request_deadline_s": REQUEST_TIMEOUT_S - 30.0,
        "batch_deadline_ms": 2.0,
    })
    app = make_app(params)
    supervisor = app[SUPERVISOR_KEY]
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        async def bounded_get(path):
            return await asyncio.wait_for(
                client.get(path), timeout=REQUEST_TIMEOUT_S
            )

        async def metrics_text():
            return await (await client.get("/metrics")).text()

        # phase 1: healthy — seed the hit key, gauge reads 1
        seed = await bounded_get(f"/upload/w_40,o_png/{src}")
        _require(seed.status == 200, f"healthy seed 200 (got {seed.status})")
        _require(
            _metric_value(await metrics_text(), "flyimg_device_health")
            == 1.0,
            "flyimg_device_health starts at 1",
        )

        # phase 2: the storm — the trigger request exhausts its retries
        # against the dead transport (its 5xx IS the outage surfacing),
        # the breaker trips, health walks to 0
        storm["on"] = True
        trigger = await bounded_get(f"/upload/w_41,o_png/{src}")
        _require(
            trigger.status >= 500 or trigger.status == 200,
            f"storm trigger mapped (got {trigger.status})",
        )
        for _ in range(200):
            if supervisor.cpu_forced():
                break
            await asyncio.sleep(0.05)
        _require(supervisor.cpu_forced(), "backend breaker tripped")
        storm["on"] = False  # the device is gone; CPU launches work
        _require(
            _metric_value(await metrics_text(), "flyimg_device_health")
            == 0.0,
            "flyimg_device_health walked to 0",
        )

        # phase 3: degraded serving — hits clean, misses tagged CPU
        hit = await bounded_get(f"/upload/w_40,o_png/{src}")
        _require(hit.status == 200, f"cache hit 200 (got {hit.status})")
        _require(
            "X-Flyimg-Degraded" not in hit.headers,
            "cache hit carries no degraded tag",
        )
        miss = await bounded_get(f"/upload/w_42,o_png/{src}")
        _require(miss.status == 200, f"CPU miss 200 (got {miss.status})")
        _require(
            "cpu-fallback"
            in miss.headers.get("X-Flyimg-Degraded", "").split(","),
            f"miss tagged cpu-fallback "
            f"(got {miss.headers.get('X-Flyimg-Degraded')!r})",
        )
        _require(
            "max-age=60" in miss.headers.get("Cache-Control", ""),
            "CPU miss short-cached",
        )
        again = await bounded_get(f"/upload/w_42,o_png/{src}")
        _require(
            "cpu-fallback"
            in again.headers.get("X-Flyimg-Degraded", "").split(","),
            "CPU render was never cached (same key degrades again)",
        )
        ready = await (await client.get("/readyz")).json()
        _require(
            ready.get("device") == "down" and ready.get("status") == "ok",
            f"/readyz reports device down while staying ready ({ready})",
        )

        # phase 4: the fault clears — clean probes re-promote, no restart
        dead["on"] = False
        for _ in range(300):
            if not supervisor.cpu_forced():
                break
            await asyncio.sleep(0.05)
        _require(not supervisor.cpu_forced(), "clean probes re-promoted")
        text = await metrics_text()
        _require(
            _metric_value(text, "flyimg_device_health") == 1.0,
            "flyimg_device_health walked back to 1",
        )
        _require(
            _metric_value(
                text, 'flyimg_backend_failovers_total{to="cpu"}'
            ) == 1.0
            and _metric_value(
                text, 'flyimg_backend_failovers_total{to="device"}'
            ) == 1.0,
            "exactly one failover each way",
        )
        _require(
            _metric_value(
                text, 'flyimg_backend_probe_total{outcome="ok"}'
            ) >= 2.0,
            "clean probes counted",
        )
        healed = await bounded_get(f"/upload/w_42,o_png/{src}")
        _require(
            healed.status == 200
            and "X-Flyimg-Degraded" not in healed.headers,
            "post-re-promotion miss serves untagged",
        )
        cached = await bounded_get(f"/upload/w_42,o_png/{src}")
        _require(
            cached.status == 200
            and "X-Flyimg-Degraded" not in cached.headers,
            "post-re-promotion render was cached normally",
        )
        print(
            "device failover smoke OK: health 1->0->1, hits clean, "
            "misses cpu-fallback-tagged and uncached, auto re-promotion"
        )
        return 0
    finally:
        await client.close()


if __name__ == "__main__":
    raise SystemExit(asyncio.run(main()))
