"""Metrics registry: counters, histogram quantiles, Prometheus rendering,
and the handler/batcher wiring."""

import numpy as np

from flyimg_tpu.runtime.metrics import (
    BUCKET_BOUNDS,
    Histogram,
    MetricsRegistry,
)


def test_counter_and_labels():
    reg = MetricsRegistry()
    reg.record_request("upload", 200)
    reg.record_request("upload", 200)
    reg.record_request("upload", 404)
    text = reg.render_prometheus()
    assert 'flyimg_requests_total{route="upload",status="200"} 2' in text
    assert 'flyimg_requests_total{route="upload",status="404"} 1' in text


def test_histogram_quantiles_bracket_samples():
    h = Histogram("t")
    rng = np.random.default_rng(0)
    samples = rng.uniform(0.001, 0.1, 1000)
    for s in samples:
        h.observe(float(s))
    p50 = h.quantile(0.5)
    p99 = h.quantile(0.99)
    # in-bucket interpolation: estimates sit within one bucket factor of
    # truth on EITHER side (the old upper-bound rule forced >= truth and
    # over-reported by up to 1.8x at bucket edges)
    assert np.quantile(samples, 0.5) / 1.9 <= p50 <= np.quantile(samples, 0.5) * 1.9
    assert np.quantile(samples, 0.99) / 1.9 <= p99 <= np.quantile(samples, 0.99) * 1.9


def test_histogram_quantile_interpolates_within_bucket():
    """All mass in one bucket: q must move THROUGH the bucket instead of
    pinning to its upper bound (the old behavior over-reported p50 by up
    to 1.8x for tightly clustered latencies)."""
    from flyimg_tpu.runtime.metrics import BUCKET_BOUNDS as B

    h = Histogram("t")
    mid = (B[4] + B[5]) / 2.0
    for _ in range(1000):
        h.observe(mid)  # every sample lands in bucket 5 (le = B[5])
    p10, p50, p90 = h.quantile(0.1), h.quantile(0.5), h.quantile(0.9)
    assert B[4] < p10 < p50 < p90 < B[5]
    # p50 sits at the bucket midpoint under uniform-in-bucket assumption
    assert abs(p50 - (B[4] + B[5]) / 2.0) < (B[5] - B[4]) * 0.02


def test_gauge_set_inc_dec_and_callback():
    reg = MetricsRegistry()
    g = reg.gauge("flyimg_test_gauge", "help me")
    g.set(5)
    g.inc(2)
    g.dec()
    assert g.value == 6
    # callback gauges sample at render time
    state = {"v": 3}
    reg.gauge("flyimg_test_cb_gauge", "cb", fn=lambda: state["v"])
    text = reg.render_prometheus()
    assert "# TYPE flyimg_test_gauge gauge" in text
    assert "flyimg_test_gauge 6" in text
    assert "flyimg_test_cb_gauge 3" in text
    state["v"] = 9
    assert "flyimg_test_cb_gauge 9" in reg.render_prometheus()


def test_label_values_escaped_in_request_and_stage():
    """A crafted route/stage value must not corrupt the exposition format
    (same escaping record_breaker applies to host)."""
    reg = MetricsRegistry()
    evil = 'up"load}\nx\\y'
    reg.record_request(evil, 200)
    reg.record_stage(evil, 0.01)
    text = reg.render_prometheus()
    for line in text.splitlines():
        assert "\r" not in line
        if line.startswith("flyimg_requests_total"):
            # raw quote/newline/backslash must appear only escaped
            inner = line[line.index("{") + 1 : line.rindex("}")]
            assert '\\"' in inner and "\\n" in inner and "\\\\" in inner


def test_histogram_overflow_bucket():
    h = Histogram("t")
    h.observe(BUCKET_BOUNDS[-1] * 10)
    assert h.quantile(0.5) == float("inf")
    counts, total, n = h.snapshot()
    assert counts[-1] == 1 and n == 1


def test_prometheus_histogram_rendering():
    reg = MetricsRegistry()
    reg.record_stage("decode", 0.004)
    reg.record_stage("decode", 0.008)
    text = reg.render_prometheus()
    assert 'flyimg_stage_seconds_count{stage="decode"} 2' in text
    assert 'le="+Inf"' in text
    assert "flyimg_uptime_seconds" in text


def test_prometheus_one_type_line_per_family():
    reg = MetricsRegistry()
    reg.record_request("upload", 200)
    reg.record_request("upload", 404)
    reg.record_stage("decode", 0.01)
    reg.record_stage("device", 0.02)
    text = reg.render_prometheus()
    assert text.count("# TYPE flyimg_requests_total counter") == 1
    assert text.count("# TYPE flyimg_stage_seconds histogram") == 1
    # family samples stay contiguous: no TYPE line interleaves its samples
    lines = text.splitlines()
    first = next(
        i for i, l in enumerate(lines)
        if l.startswith("flyimg_requests_total")
    )
    last = max(
        i for i, l in enumerate(lines)
        if l.startswith("flyimg_requests_total")
    )
    assert not any(
        l.startswith("# TYPE") for l in lines[first : last + 1]
    )


def test_batch_occupancy_summary():
    reg = MetricsRegistry()
    reg.record_batch(images=3, capacity=4)
    reg.record_batch(images=4, capacity=4)
    summary = reg.summary()
    assert summary["flyimg_images_processed_total"] == 7
    assert summary["flyimg_batches_total"] == 2
    # occupancy is images over padded slots, from the two counters (the
    # benchmark's padded_slot_share and the batcher's stats() read them)
    assert summary["flyimg_batch_slots_total"] == 8
    occupancy = (
        summary["flyimg_images_processed_total"]
        / summary["flyimg_batch_slots_total"]
    )
    assert abs(occupancy - 7 / 8) < 1e-9
    # the derived copies nobody read are gone (PERF.md, series audit)
    assert "flyimg_batch_occupancy" not in summary
    assert "flyimg_batch_padding_waste" not in summary


def test_handler_records_cache_and_stages(tmp_path):
    from flyimg_tpu.appconfig import AppParameters
    from flyimg_tpu.codecs import encode
    from flyimg_tpu.service.handler import ImageHandler
    from flyimg_tpu.storage.local import LocalStorage

    rng = np.random.default_rng(1)
    img = rng.integers(0, 255, (32, 48, 3), dtype=np.uint8)
    src = tmp_path / "in.png"
    src.write_bytes(encode(img, "png"))

    reg = MetricsRegistry()
    params = AppParameters(
        {"tmp_dir": str(tmp_path / "tmp"), "upload_dir": str(tmp_path / "up")}
    )
    handler = ImageHandler(LocalStorage(params), params, metrics=reg)
    handler.process_image("w_16,h_16,o_png", str(src))
    summary = reg.summary()
    assert summary['flyimg_cache_total{result="miss"}'] == 1
    assert 'flyimg_stage_seconds{stage="device"}:p50' in summary

    handler.process_image("w_16,h_16,o_png", str(src))
    assert reg.summary()['flyimg_cache_total{result="hit"}'] == 1
