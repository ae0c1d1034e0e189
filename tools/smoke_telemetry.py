"""CI telemetry smoke: boot the real app with the telemetry warehouse on
(injectable clock), drive a thumbnail burst then a cropzoom burst, and
assert the full loop end to end (docs/observability.md "Telemetry
warehouse & traffic-mix classifier"):

- the traffic-mix gauge flips thumbnail -> cropzoom WITH hysteresis
  (the first cropzoom beat proposes, the second adopts), visible in
  /debug/telemetry, the flyimg_traffic_mix gauges, AND the
  flyimg_traffic_mix_transitions_total counter;
- archive segments rotate under the injected clock and the window +
  launch records land on disk;
- ``tools/telemetry_query.py mix-report`` reproduces every stored label
  from the segment files alone (the live process gone), and ``export``
  writes every archived window into one JSONL stream;
- a default-off app is byte-clean: no flyimg_telemetry_* /
  flyimg_traffic_mix metrics, no archive directory, a disabled
  /debug/telemetry document.

    JAX_PLATFORMS=cpu python tools/smoke_telemetry.py

Exit code 0 = every assertion held. The behavioral matrix (durability
edges, centroid math, schema validation) lives in
tests/test_telemetry.py; this script proves the assembled service —
middleware beat, handler outcome recording, archive, metrics, debug
surface, offline tools — warehouses as one system.
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import tempfile

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)


def _require(cond: bool, what: str) -> None:
    if not cond:
        print(f"FAIL: {what}", file=sys.stderr)
        raise SystemExit(1)


def _metric_value(text: str, prefix: str) -> float:
    for line in text.splitlines():
        if line.startswith(prefix):
            try:
                return float(line.rsplit(" ", 1)[1])
            except ValueError:
                continue
    return float("nan")


class _Clock:
    def __init__(self) -> None:
        self.now = 1000.0

    def __call__(self) -> float:
        return self.now


async def main() -> int:
    import numpy as np
    from aiohttp.test_utils import TestClient, TestServer

    from flyimg_tpu.appconfig import AppParameters
    from flyimg_tpu.codecs import encode
    from flyimg_tpu.service.app import TELEMETRY_KEY, make_app

    tmp = tempfile.mkdtemp(prefix="flyimg-telemetry-")
    rng = np.random.default_rng(7)
    src = os.path.join(tmp, "src.png")
    with open(src, "wb") as fh:
        fh.write(
            encode(rng.integers(0, 230, (640, 800, 3), dtype=np.uint8), "png")
        )

    clock = _Clock()
    tel_dir = os.path.join(tmp, "warehouse")
    params = AppParameters(
        {
            "tmp_dir": os.path.join(tmp, "t"),
            "upload_dir": os.path.join(tmp, "u"),
            "debug": True,
            "telemetry_enable": True,
            "telemetry_dir": tel_dir,
            "telemetry_clock": clock,
            "telemetry_snapshot_interval_s": 5.0,
            "telemetry_segment_max_age_s": 10.0,
            "telemetry_mix_window": 16,
            "telemetry_mix_min_samples": 4,
            "telemetry_mix_hysteresis": 2,
            # keep the REAL burn signal calm on the slow CI first-render
            "slo_latency_p99_ms": 60000.0,
        }
    )
    app = make_app(params)
    _require(app[TELEMETRY_KEY].enabled, "telemetry pipeline armed")
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        async def snap() -> dict:
            return json.loads(
                await (await client.get("/debug/telemetry")).text()
            )

        async def beat(url: str) -> None:
            # past the interval: the NEXT request's middleware hook
            # writes one window record (and ages the active segment)
            clock.now += 6.0
            resp = await client.get(url)
            _require(resp.status == 200, f"beat render 200 ({resp.status})")

        thumb = f"/upload/w_32,o_png/{src}"
        crop = f"/upload/c_1,w_520,h_400,o_png/{src}"

        # 1) thumbnail burst, two beats -> adopted label thumbnail
        for _ in range(10):
            resp = await client.get(thumb)
            _require(resp.status == 200, f"thumbnail 200 ({resp.status})")
        await beat(thumb)
        await beat(thumb)
        doc = await snap()
        _require(doc["enabled"] is True, "enabled /debug/telemetry")
        _require(
            doc["mix"]["label"] == "thumbnail",
            f"thumbnail adopted after two beats (got {doc['mix']})",
        )
        text = await (await client.get("/metrics")).text()
        _require(
            _metric_value(text, 'flyimg_traffic_mix{mix="thumbnail"}') == 1.0,
            "thumbnail gauge reads 1",
        )

        # 2) cropzoom burst displaces the classifier window; the FIRST
        #    beat only PROPOSES (hysteresis), the second adopts
        for _ in range(18):
            resp = await client.get(crop)
            _require(resp.status == 200, f"cropzoom 200 ({resp.status})")
        await beat(crop)
        doc = await snap()
        _require(
            doc["mix"]["label"] == "thumbnail"
            and doc["mix"]["raw"] == "cropzoom",
            f"hysteresis holds one odd beat (got {doc['mix']})",
        )
        await beat(crop)
        doc = await snap()
        _require(
            doc["mix"]["label"] == "cropzoom",
            f"cropzoom adopted on the second beat (got {doc['mix']})",
        )
        _require(
            doc["mix"]["transitions"] == 2,
            f"two adopted flips: mixed->thumbnail->cropzoom (got "
            f"{doc['mix']['transitions']})",
        )
        text = await (await client.get("/metrics")).text()
        _require(
            _metric_value(text, 'flyimg_traffic_mix{mix="cropzoom"}') == 1.0
            and _metric_value(
                text, 'flyimg_traffic_mix{mix="thumbnail"}') == 0.0,
            "mix gauge flipped to cropzoom",
        )
        _require(
            _metric_value(
                text,
                'flyimg_traffic_mix_transitions_total{to="cropzoom"}',
            ) == 1.0,
            "transition counter carries the flip",
        )

        # 3) segments rotated under the injected clock (age bound 10 s,
        #    each beat advances 6 s) and the records are on disk
        _require(
            doc["archive"]["rotations"] >= 1
            and len(doc["archive"]["segments"]) >= 2,
            f"segments rotated (got {doc['archive']})",
        )
        _require(
            doc["archive"]["records_written"].get("window", 0) >= 4
            and doc["archive"]["records_written"].get("launch", 0) >= 1,
            f"window + launch records written (got "
            f"{doc['archive']['records_written']})",
        )
    finally:
        await client.close()  # on_cleanup runs the final telemetry beat

    # 4) the offline half: labels reproduce from segment files ALONE
    from flyimg_tpu.runtime.telemetry import read_archive
    from tools import telemetry_query

    offline = read_archive(tel_dir)
    windows = [r for r in offline["records"] if r["kind"] == "window"]
    labels = {w["mix"] for w in windows}
    _require(
        {"thumbnail", "cropzoom"} <= labels,
        f"both adopted labels persisted ({sorted(labels)})",
    )
    _require(
        telemetry_query.main(["mix-report", tel_dir, "--json"]) == 0,
        "mix-report reproduces every stored label from disk",
    )
    export = os.path.join(tmp, "export.jsonl")
    _require(
        telemetry_query.main(
            ["export", tel_dir, "--kind", "window", "--out", export]
        ) == 0,
        "telemetry_query export",
    )
    with open(export, encoding="utf-8") as fh:
        exported = [json.loads(line) for line in fh if line.strip()]
    _require(
        len(exported) == len(windows),
        f"export holds every archived window (got {len(exported)}"
        f" of {len(windows)})",
    )

    # 5) default-off cleanliness: no metrics, no directory, disabled doc
    params_off = AppParameters(
        {
            "tmp_dir": os.path.join(tmp, "t2"),
            "upload_dir": os.path.join(tmp, "u2"),
            "debug": True,
        }
    )
    app_off = make_app(params_off)
    client_off = TestClient(TestServer(app_off))
    await client_off.start_server()
    try:
        resp = await client_off.get(f"/upload/w_40,o_jpg,q_85/{src}")
        _require(resp.status == 200, "off-app render 200")
        text = await (await client_off.get("/metrics")).text()
        _require(
            "flyimg_telemetry" not in text and "flyimg_traffic_mix" not in text,
            "no telemetry metrics with telemetry_enable off",
        )
        doc = json.loads(
            await (await client_off.get("/debug/telemetry")).text()
        )
        _require(doc == {"enabled": False}, "disabled /debug/telemetry")
    finally:
        await client_off.close()
    _require(
        not os.path.exists(os.path.join(tmp, "t2", "telemetry")),
        "no archive directory with telemetry_enable off",
    )

    print(
        "telemetry smoke OK: thumbnail -> cropzoom flip with hysteresis, "
        f"{len(windows)} windows across {len(offline['segments'])} rotated "
        "segments, mix-report + export reproduce from disk, "
        "default-off clean"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(asyncio.run(main()))
