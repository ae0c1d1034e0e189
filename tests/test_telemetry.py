"""Telemetry warehouse (runtime/telemetry.py; docs/observability.md
"Telemetry warehouse & traffic-mix classifier"): archive durability
edges (torn-tail recovery, rotation under an injectable clock,
oldest-first retention eviction, reader-clock skew), emit-time schema
validation, the traffic-mix classifier's centroids and hysteresis, the
assembled pipeline end to end through the real app, the offline round
trip (telemetry_query from segments alone), the
unified dump-retention override, and the default-off byte identity."""

from __future__ import annotations

import asyncio
import json
import os

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from flyimg_tpu.appconfig import AppParameters
from flyimg_tpu.codecs import encode
from flyimg_tpu.runtime.telemetry import (
    MIX_CENTROIDS,
    MIX_FEATURES,
    RECORD_SCHEMAS,
    SCHEMA_VERSION,
    TelemetryArchive,
    TelemetryPipeline,
    TrafficMixClassifier,
    read_archive,
    request_features,
)


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


def _archive(tmp_path, clock=None, **kw):
    kw.setdefault("segment_max_bytes", 4096)
    kw.setdefault("segment_max_age_s", 1000.0)
    return TelemetryArchive(
        str(tmp_path / "telemetry"), clock=clock or FakeClock(), **kw
    )


def _fill_segment(archive, payload_bytes=900, kind="launch"):
    """Append launch records until the active segment rotates once."""
    start = archive.rotations
    while archive.rotations == start:
        archive.append(kind, {"controller": "device",
                              "plan_key": "x" * payload_bytes})


# ---------------------------------------------------------------------------
# request_features: the per-request fingerprint input
# ---------------------------------------------------------------------------


class _Opts(dict):
    def get(self, key, default=None):  # OptionsBag-compatible read
        return dict.get(self, key, default)


def test_request_features_resize_vs_crop_and_buckets():
    thumb = request_features(_Opts(width=120, height=80), "src-a")
    assert thumb["family"] == "resize"
    assert thumb["bucket"] == 7  # 120 -> bit_length 7 (<=512 => small)
    assert thumb["source"] == "src-a"

    crop = request_features(_Opts({"width": 600, "crop": 1}), "src-b")
    assert crop["family"] == "crop"
    assert crop["bucket"] == 10  # 600px: outside the small ladder

    extract = request_features(
        _Opts({"extract": "1", "extract-top-x": 10, "extract-top-y": 20,
               "extract-bottom-x": 110, "extract-bottom-y": 120}),
        "src-c",
    )
    assert extract["family"] == "crop"
    assert extract["sig"].endswith("10,20,110,120")

    bare = request_features(_Opts(), None)
    assert bare["bucket"] == 0 and bare["source"] == ""


def test_request_features_never_raises_on_exotic_options():
    class Hostile:
        def get(self, key, default=None):
            raise RuntimeError("no")

    feats = request_features(Hostile(), "s")
    assert feats["family"] == "resize" and feats["bucket"] == 0


# ---------------------------------------------------------------------------
# TrafficMixClassifier: centroids, sample floor, hysteresis
# ---------------------------------------------------------------------------


def _feed(clf, n, *, family="resize", bucket=6, sig=None, source="s",
          outcome="hit"):
    for i in range(n):
        clf.record({"family": family, "bucket": bucket,
                    "sig": sig or f"{family}:{bucket}:",
                    "source": source}, outcome)


def test_classifier_below_sample_floor_stays_mixed():
    clf = TrafficMixClassifier(min_samples=8, hysteresis=1)
    _feed(clf, 7)
    assert clf.fingerprint() is None
    beat = clf.classify()
    assert beat["raw"] is None and beat["label"] == "mixed"
    assert beat["changed"] is False and clf.transitions == 0


def test_classifier_centroids_label_shaped_traffic():
    # thumbnail: small resizes, one shape per source, cache-hot
    thumb = TrafficMixClassifier(min_samples=8, hysteresis=1)
    _feed(thumb, 32, family="resize", bucket=6, outcome="hit")
    assert thumb.classify()["raw"] == "thumbnail"

    # cropzoom: crop-dominant at medium size, low fan-out
    crop = TrafficMixClassifier(min_samples=8, hysteresis=1)
    _feed(crop, 32, family="crop", bucket=10, outcome="miss")
    assert crop.classify()["raw"] == "cropzoom"

    # multisize: the same sources at MANY sizes (srcset ladder)
    multi = TrafficMixClassifier(min_samples=8, hysteresis=1)
    for s in range(3):
        for bucket in range(5, 11):
            multi.record({"family": "resize", "bucket": bucket,
                          "sig": f"resize:{bucket}:",
                          "source": f"s{s}"}, "miss")
    assert multi.classify()["raw"] == "multisize"

    # panzoom: repeated extracts panning across the same sources
    pan = TrafficMixClassifier(min_samples=8, hysteresis=1)
    for i in range(36):
        pan.record({"family": "crop", "bucket": 10,
                    "sig": f"crop:10:{i % 8},0,100,100",
                    "source": f"s{i % 3}"}, "hit" if i % 2 else "miss")
    assert pan.classify()["raw"] == "panzoom"


def test_classifier_far_from_every_centroid_is_mixed():
    # a feature vector outside MIX_RADIUS of every centroid
    label, dist = TrafficMixClassifier.nearest(
        {"crop_share": 0.5, "small_share": 0.0, "bucket_spread": 1.0,
         "source_fanout": 0.0, "hit_ratio": 1.0}
    )
    assert label == "mixed" and dist > 0.55


def test_nearest_is_exact_on_the_centroids_themselves():
    for label, centroid in MIX_CENTROIDS.items():
        got, dist = TrafficMixClassifier.nearest(
            dict(zip(MIX_FEATURES, centroid))
        )
        assert got == label and dist == pytest.approx(0.0)


def test_classifier_hysteresis_needs_consecutive_agreement():
    clf = TrafficMixClassifier(window=32, min_samples=8, hysteresis=2)
    _feed(clf, 32, family="resize", bucket=6, outcome="hit")
    # beat 1 proposes thumbnail, does not adopt
    beat = clf.classify()
    assert beat["raw"] == "thumbnail" and beat["label"] == "mixed"
    assert beat["changed"] is False
    # beat 2 agrees -> adopted, edge-triggered changed
    beat = clf.classify()
    assert beat["label"] == "thumbnail" and beat["changed"] is True
    assert beat["previous"] == "mixed"
    assert clf.transitions == 1
    # one odd window (crop burst) proposes but cannot flip alone
    _feed(clf, 32, family="crop", bucket=10, outcome="miss")
    beat = clf.classify()
    assert beat["raw"] == "cropzoom" and beat["label"] == "thumbnail"
    # back to thumbnail traffic: the streak resets, no flip ever lands
    _feed(clf, 32, family="resize", bucket=6, outcome="hit")
    assert clf.classify()["label"] == "thumbnail"
    _feed(clf, 32, family="crop", bucket=10, outcome="miss")
    clf.classify()
    beat = clf.classify()
    assert beat["label"] == "cropzoom" and clf.transitions == 2


# ---------------------------------------------------------------------------
# TelemetryArchive: durability edges
# ---------------------------------------------------------------------------


def test_archive_append_validates_schema(tmp_path):
    archive = _archive(tmp_path)
    assert archive.append("nonsense", {"x": 1}) is False
    assert archive.append(
        "boot", {"segment": "telemetry-00000001.jsonl", "bogus_field": 7}
    ) is True
    assert archive.dropped_fields == 1  # unknown field dropped + counted
    archive.close()
    doc = read_archive(str(tmp_path / "telemetry"))
    assert len(doc["records"]) == 1
    rec = doc["records"][0]
    assert rec["schema"] == SCHEMA_VERSION and rec["kind"] == "boot"
    assert "bogus_field" not in rec  # never reached disk


def test_archive_recovers_unterminated_torn_tail(tmp_path):
    archive = _archive(tmp_path)
    archive.append("launch", {"controller": "device", "launch_seq": 1})
    archive.append("launch", {"controller": "device", "launch_seq": 2})
    path = os.path.join(archive.directory, archive._segment_name)
    archive.close()
    # mid-write crash: a final line with no terminator
    with open(path, "a", encoding="utf-8") as fh:
        fh.write('{"schema":1,"kind":"launch","controller":"dev')
    # a reader skips (and counts) it without the writer's help
    doc = read_archive(archive.directory)
    assert len(doc["records"]) == 2 and doc["torn"] == 1
    # the next open truncates exactly that line — never a boot failure
    archive2 = _archive(tmp_path)
    assert archive2.torn_recovered == 1
    archive2.append("launch", {"controller": "device", "launch_seq": 3})
    archive2.close()
    doc = read_archive(archive.directory)
    assert [r["launch_seq"] for r in doc["records"]] == [1, 2, 3]
    assert doc["torn"] == 0  # the damage is gone from disk


def test_archive_recovers_terminated_garbage_tail(tmp_path):
    archive = _archive(tmp_path)
    archive.append("launch", {"controller": "device", "launch_seq": 1})
    path = os.path.join(archive.directory, archive._segment_name)
    archive.close()
    # a torn overwrite can leave a terminated-but-unparseable line
    with open(path, "a", encoding="utf-8") as fh:
        fh.write('{"kind":"launch",GARBAGE}\n')
    archive2 = _archive(tmp_path)
    assert archive2.torn_recovered == 1
    archive2.close()
    doc = read_archive(archive.directory)
    assert len(doc["records"]) == 1 and doc["torn"] == 0


def test_archive_rotates_by_size(tmp_path):
    clock = FakeClock()
    archive = _archive(tmp_path, clock)
    _fill_segment(archive)
    assert archive.rotations == 1
    inv = archive.inventory()
    assert len(inv["segments"]) == 2
    assert inv["active_segment"] == "telemetry-00000002.jsonl"
    archive.close()


def test_archive_rotates_by_age_under_injected_clock(tmp_path):
    clock = FakeClock()
    archive = _archive(tmp_path, clock, segment_max_age_s=60.0)
    archive.append("launch", {"controller": "device"})
    clock.advance(59.0)
    archive.append("launch", {"controller": "device"})
    assert archive.rotations == 0  # still inside the age bound
    clock.advance(2.0)
    archive.append("launch", {"controller": "device"})
    assert archive.rotations == 1
    assert archive.inventory()["active_segment"] == "telemetry-00000002.jsonl"
    archive.close()


def test_archive_reopen_continues_partial_segment(tmp_path):
    clock = FakeClock()
    archive = _archive(tmp_path, clock)
    archive.append("launch", {"controller": "device", "launch_seq": 1})
    archive.close()
    archive2 = _archive(tmp_path, clock)
    assert archive2.inventory()["active_segment"] == "telemetry-00000001.jsonl"
    archive2.append("launch", {"controller": "device", "launch_seq": 2})
    archive2.close()
    doc = read_archive(archive.directory)
    assert [r["launch_seq"] for r in doc["records"]] == [1, 2]
    assert doc["segments"] == ["telemetry-00000001.jsonl"]


def test_archive_retention_evicts_oldest_closed_first(tmp_path):
    clock = FakeClock()
    archive = _archive(tmp_path, clock, retention_max_segments=3)
    for _ in range(6):
        _fill_segment(archive)
    inv = archive.inventory()
    # the count bound holds, the WRITABLE segment never evicts, and the
    # survivors are exactly the newest seqs
    assert len(inv["segments"]) == 3
    assert inv["active_segment"] in inv["segments"]
    seqs = [int(n.split("-")[1].split(".")[0]) for n in inv["segments"]]
    assert seqs == sorted(seqs)
    assert max(seqs) == TelemetryArchive._segment_seq(inv["active_segment"])
    assert archive.evicted_segments == 4  # 7 created, 3 retained
    archive.close()


def test_archive_retention_byte_bound(tmp_path):
    clock = FakeClock()
    archive = _archive(tmp_path, clock,
                       retention_max_bytes=3 * 4096,
                       retention_max_segments=64)
    for _ in range(5):
        _fill_segment(archive)
    assert archive.total_bytes() <= 3 * 4096 + archive.segment_max_bytes
    assert archive.evicted_segments > 0
    archive.close()


def test_reader_orders_by_segment_and_line_not_timestamp(tmp_path):
    # a writer whose wall clock jumps BACKWARDS must not reorder the
    # timeline for readers: read_archive returns write order, always
    clock = FakeClock(5000.0)
    archive = _archive(tmp_path, clock)
    archive.append("launch", {"controller": "device", "launch_seq": 1})
    clock.now = 100.0  # massive backwards skew (NTP step, VM migration)
    archive.append("launch", {"controller": "device", "launch_seq": 2})
    clock.now = 9000.0
    archive.append("launch", {"controller": "device", "launch_seq": 3})
    archive.close()
    doc = read_archive(archive.directory)
    assert [r["launch_seq"] for r in doc["records"]] == [1, 2, 3]
    stamps = [r["at_s"] for r in doc["records"]]
    assert stamps != sorted(stamps)  # the skew really happened


def test_schema_doc_and_code_agree_on_field_count():
    # the flylint parity rule enforces this statically; keep a cheap
    # runtime canary so a schema edit that skips the docs fails HERE too
    pairs = {(kind, field) for kind, fields in RECORD_SCHEMAS.items()
             for field in fields}
    assert len(pairs) == 63  # 57 + the six launch-phase fields of PR 27
    for kind in ("boot", "window", "launch"):
        assert {"schema", "kind", "at_s"} <= set(RECORD_SCHEMAS[kind])


# ---------------------------------------------------------------------------
# the assembled pipeline through the real app
# ---------------------------------------------------------------------------


def _write_src(tmp_path):
    rng = np.random.default_rng(7)
    src = tmp_path / "src.png"
    src.write_bytes(
        encode(rng.integers(0, 230, (640, 800, 3), dtype=np.uint8), "png")
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


def test_default_off_is_byte_identical(tmp_path):
    """telemetry_enable unset: handler holds None, no directory, no
    metric families, /debug/telemetry 404s with debug off and reports
    disabled with debug on."""
    from flyimg_tpu.service.app import HANDLER_KEY, TELEMETRY_KEY, make_app

    src = _write_src(tmp_path)

    async def go():
        app = make_app(_app_params(tmp_path, "plain"))
        assert app[HANDLER_KEY].telemetry is None
        assert app[TELEMETRY_KEY].enabled is False
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            resp = await client.get(f"/upload/w_32,o_png/{src}")
            assert resp.status == 200
            metrics = await (await client.get("/metrics")).text()
            assert "flyimg_telemetry" not in metrics
            assert "flyimg_traffic_mix" not in metrics
            assert (await client.get("/debug/telemetry")).status == 404
        finally:
            await client.close()
        assert not os.path.exists(str(tmp_path / "plain" / "t" / "telemetry"))

        gated = make_app(_app_params(tmp_path, "dbg", debug=True))
        c = TestClient(TestServer(gated))
        await c.start_server()
        try:
            doc = json.loads(await (await c.get("/debug/telemetry")).text())
            assert doc == {"enabled": False}
        finally:
            await c.close()

    _run(go())


def test_pipeline_end_to_end_mix_flip_and_round_trip(tmp_path):
    """The full loop: thumbnail burst then cropzoom burst through the
    real app under an injected clock -> the adopted label flips with
    hysteresis, window + launch records land in segments, the gauge and
    transition counter move, and the offline half (telemetry_query)
    reproduces everything from disk alone after the process state is
    gone."""
    from flyimg_tpu.service.app import TELEMETRY_KEY, make_app

    src = _write_src(tmp_path)
    clock = FakeClock()
    tel_dir = str(tmp_path / "warehouse")
    params = _app_params(
        tmp_path, "on",
        debug=True,
        telemetry_enable=True,
        telemetry_dir=tel_dir,
        telemetry_clock=clock,
        telemetry_snapshot_interval_s=5.0,
        telemetry_mix_window=16,
        telemetry_mix_min_samples=4,
        telemetry_mix_hysteresis=2,
    )

    async def go():
        app = make_app(params)
        telemetry = app[TELEMETRY_KEY]
        assert telemetry.enabled
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            async def beat():
                # advancing past the interval makes the NEXT request's
                # middleware hook write one window record
                clock.advance(6.0)
                assert (await client.get(
                    f"/upload/w_32,o_png/{src}")).status == 200

            # boot record is on disk before any traffic
            doc = read_archive(tel_dir)
            kinds = [r["kind"] for r in doc["records"]]
            assert kinds == ["boot"]

            # thumbnail burst (one miss then cache hits) + two beats
            for _ in range(10):
                assert (await client.get(
                    f"/upload/w_32,o_png/{src}")).status == 200
            await beat()
            await beat()
            snap = json.loads(
                await (await client.get("/debug/telemetry")).text()
            )
            assert snap["mix"]["label"] == "thumbnail"
            assert snap["mix"]["transitions"] == 1

            # cropzoom burst displaces the 16-sample window + two beats
            for _ in range(18):
                assert (await client.get(
                    f"/upload/c_1,w_520,h_400,o_png/{src}")).status == 200
            await beat()
            await beat()
            snap = json.loads(
                await (await client.get("/debug/telemetry")).text()
            )
            assert snap["mix"]["label"] == "cropzoom"
            assert snap["mix"]["transitions"] == 2
            # the artifact index rides the same document (satellite 1)
            assert "artifacts" in snap and "dumps" in snap["artifacts"]

            metrics = await (await client.get("/metrics")).text()
            assert 'flyimg_traffic_mix{mix="cropzoom"} 1' in metrics
            assert 'flyimg_traffic_mix{mix="thumbnail"} 0' in metrics
            assert ('flyimg_traffic_mix_transitions_total{to="cropzoom"} 1'
                    in metrics)
            assert 'flyimg_telemetry_records_total{kind="window"}' in metrics
            assert "flyimg_telemetry_segments 1" in metrics
        finally:
            await client.close()

    _run(go())

    # ---- offline half: everything below reads segments from disk only
    doc = read_archive(tel_dir)
    kinds = [r["kind"] for r in doc["records"]]
    assert kinds.count("boot") == 1
    windows = [r for r in doc["records"] if r["kind"] == "window"]
    assert len(windows) >= 5  # 4 beats + the shutdown window
    launches = [r for r in doc["records"] if r["kind"] == "launch"]
    assert launches, "real renders must drain launch records"
    # the ring's kind/seq are renamed so they cannot collide with the
    # archive envelope's own kind field
    assert all(r["kind"] == "launch" and r.get("launch_kind")
               for r in launches)
    seqs = [r["launch_seq"] for r in launches]
    assert seqs == sorted(seqs)  # drained strictly by seq, no repeats
    assert len(set(seqs)) == len(seqs)
    labels = [w["mix"] for w in windows]
    assert "thumbnail" in labels and "cropzoom" in labels

    from tools import telemetry_query

    # mix-report exits 0 ONLY when every stored feature vector re-maps
    # to its stored raw label through the shipped centroid table
    assert telemetry_query.main(["mix-report", tel_dir, "--json"]) == 0
    assert telemetry_query.main(["burn-timeline", tel_dir]) == 0
    assert telemetry_query.main(["windows", tel_dir]) == 0
    out = str(tmp_path / "export.jsonl")
    assert telemetry_query.main(
        ["export", tel_dir, "--kind", "window", "--out", out]
    ) == 0
    exported = [json.loads(line) for line in
                open(out, encoding="utf-8") if line.strip()]
    assert len(exported) == len(windows)


def test_mix_report_flags_tampered_labels(tmp_path):
    """The reproducibility check is real: a stored raw label that the
    shipped centroid table cannot reproduce fails the report."""
    clock = FakeClock()
    archive = _archive(tmp_path, clock)
    features = dict(zip(MIX_FEATURES, MIX_CENTROIDS["thumbnail"]))
    archive.append("window", {
        "window_s": 5.0, "mix": "cropzoom", "mix_raw": "cropzoom",
        "mix_features": features, "mix_samples": 32,
    })
    archive.close()
    from tools import telemetry_query

    assert telemetry_query.main(
        ["mix-report", archive.directory, "--json"]
    ) == 1


def test_telemetry_query_empty_dir_exits_2(tmp_path):
    from tools import telemetry_query

    empty = tmp_path / "empty"
    empty.mkdir()
    with pytest.raises(SystemExit) as exc:
        telemetry_query.main(["windows", str(empty)])
    assert exc.value.code == 2


# ---------------------------------------------------------------------------
# direct pipeline units (no HTTP)
# ---------------------------------------------------------------------------


def _pipeline(tmp_path, clock, **extra):
    conf = {
        "tmp_dir": str(tmp_path / "t"),
        "telemetry_enable": True,
        "telemetry_clock": clock,
        "telemetry_snapshot_interval_s": 5.0,
        "telemetry_mix_min_samples": 4,
    }
    conf.update(extra)
    return TelemetryPipeline.from_params(AppParameters(conf))


def test_pipeline_beat_is_rate_limited(tmp_path):
    clock = FakeClock()
    pipe = _pipeline(tmp_path, clock)
    pipe.attach()
    assert pipe.evaluate() is True  # first beat always fires
    assert pipe.evaluate() is False  # inside the interval: one compare
    clock.advance(6.0)
    assert pipe.evaluate() is True
    pipe.close()
    doc = read_archive(pipe.directory, kinds=("window",))
    assert len(doc["records"]) == 3  # 2 beats + the forced shutdown beat


def test_pipeline_default_dir_is_under_tmp_dir(tmp_path):
    pipe = _pipeline(tmp_path, FakeClock())
    assert pipe.directory == str(tmp_path / "t" / "telemetry")
    pipe.close()


def test_pipeline_window_counts_beat_outcomes(tmp_path):
    clock = FakeClock()
    pipe = _pipeline(tmp_path, clock)
    pipe.attach()
    assert pipe.evaluate() is True  # beat 1: opens the delta window
    opts = _Opts(width=64)
    for outcome in ("hit", "hit", "stale", "coalesced", "miss", "reuse",
                    "degraded", "shed"):
        pipe.record_request(options=opts, source_key="s", outcome=outcome)
    clock.advance(6.0)
    assert pipe.evaluate() is True  # beat 2 carries the outcome deltas
    pipe.close()
    windows = read_archive(pipe.directory, kinds=("window",))["records"]
    rec = windows[1]
    assert rec["hits_delta"] == 4      # hit + stale + coalesced
    assert rec["misses_delta"] == 2    # miss + reuse
    assert rec["degraded_delta"] == 2  # degraded + shed
    assert rec["window_s"] == pytest.approx(6.0)
    # the shutdown beat starts a fresh (empty) delta window
    assert windows[-1]["hits_delta"] == 0


def test_adopt_dump_retention_overrides_recorder_bound(tmp_path):
    from flyimg_tpu.runtime.flightrecorder import FlightRecorder

    dump_dir = str(tmp_path / "dumps")
    recorder = FlightRecorder(
        dump_dir=dump_dir, min_dump_interval_s=0.0, max_dumps=16
    )
    for i in range(5):
        recorder.record(controller="device", batch_id=i, plan_key="p",
                        occupancy=1, capacity=1, queue_wait_s=0.0)
        assert recorder.dump(f"r{i}") is not None
    assert len(recorder.dump_files()) == 5

    pipe = _pipeline(tmp_path, FakeClock())
    pipe.adopt_dump_retention(recorder, 2)
    assert recorder.max_dumps == 2
    assert len(recorder.dump_files()) == 2  # pruned immediately, oldest out
    snap = pipe.snapshot()
    assert snap["artifacts"]["max_dumps"] == 2
    assert snap["artifacts"]["dumps"] == recorder.dump_files()
    pipe.close()

    # 0 = keep the legacy flightrecorder_max_dumps bound (the alias)
    pipe2 = _pipeline(tmp_path, FakeClock())
    recorder.max_dumps = 16
    pipe2.adopt_dump_retention(recorder, 0)
    assert recorder.max_dumps == 16
    pipe2.close()


def test_disabled_pipeline_is_fully_inert(tmp_path):
    pipe = TelemetryPipeline.from_params(
        AppParameters({"tmp_dir": str(tmp_path / "t")})
    )
    assert pipe.enabled is False and pipe.archive is None
    pipe.attach()          # all no-ops, no directory ever created
    assert pipe.evaluate() is False
    pipe.record_request(options=_Opts(), source_key=None, outcome="hit")
    assert pipe.snapshot() == {"enabled": False}
    pipe.close()
    assert not os.path.exists(str(tmp_path / "t"))
