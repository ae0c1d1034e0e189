"""bench.py's contract: one process, chip or fail. No chip and no explicit
cpu pin means a non-zero exit and no number; under the pin the one JSON
line is stamped ``platform: cpu`` and does not carry the device metric's
name. (The bench_http report-row tests live here too.)"""

import importlib
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

bench = importlib.import_module("bench")


def _run_bench(jax_platforms):
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    if jax_platforms is not None:
        env["JAX_PLATFORMS"] = jax_platforms
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
    )


def test_bench_without_chip_or_pin_exits_nonzero_with_no_number():
    proc = _run_bench(None)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no accelerator" in proc.stderr


def test_bench_cpu_pin_line_is_stamped_and_not_a_device_metric():
    proc = _run_bench("cpu")
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    assert len(lines) == 1  # exactly ONE JSON line
    record = json.loads(lines[0])
    assert record["platform"] == "cpu"
    assert record["device_kind"] and record["device_count"] >= 1
    assert record["metric"] == bench.CPU_SMOKE_METRIC
    assert record["metric"] != bench.DEVICE_METRIC
    assert "images/sec/chip" not in record["metric"]
    assert record["vs_baseline"] is None
    assert record["value"] > 0
    # every progress line names the device too
    notes = [ln for ln in proc.stderr.splitlines() if ln.startswith("# bench")]
    assert notes and all("[cpu " in ln for ln in notes)


def test_bench_refuses_an_accelerator_selection_that_fell_back_to_cpu(
    monkeypatch, capsys
):
    """``tpu,cpu`` (what the chip machine exports) is not a cpu pin: when
    JAX lands on the CPU under it, there is no demotion and no number."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    assert bench.main() == 2
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "no accelerator" in captured.err


def test_bench_ops_refuses_without_chip_or_pin(monkeypatch, capsys):
    """The per-operator harness follows the same rule in process: no
    probe child, no CPU document under the device's name."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_ops", os.path.join(REPO, "benchmarks", "bench_ops.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(sys, "argv", ["bench_ops.py"])
    assert mod.main() == 2
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "no accelerator" in captured.err


def test_bench_is_one_process_with_no_fallback_switches():
    """No supervisor, no probe child, no demotion: the module starts no
    process and reads none of the old hunt switches."""
    with open(os.path.join(REPO, "bench.py"), encoding="utf-8") as fh:
        source = fh.read()
    for gone in ("subprocess", "Popen", "FLYIMG_BENCH_CHILD",
                 "FLYIMG_BENCH_FORCE_CPU", "force_cpu_platform"):
        assert gone not in source, gone
    assert not hasattr(bench, "_supervise")


def _load_bench_http():
    import importlib.util

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools", "bench_http.py",
    )
    spec = importlib.util.spec_from_file_location("bench_http", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_http_report_all_failed_row_is_schema_complete(capsys):
    # an all-failed rated leg is the saturation knee — the row artifact
    # consumers care about MOST. It must carry the same schema as
    # success rows (explicit null latency fields + saturated flag), not
    # a truncated dict that KeyErrors every consumer (ISSUE 5 satellite)
    mod = _load_bench_http()
    row = mod._report("miss", "rated@500", [], 123, 10.0)
    assert row["saturated"] is True
    assert row["requests"] == 123
    assert row["success_rate"] == 0.0
    assert row["throughput_rps"] == 0.0
    assert set(row["latency_ms"]) == {"mean", "p50", "p95", "p99", "max"}
    assert all(v is None for v in row["latency_ms"].values())
    out = capsys.readouterr().out
    assert "saturated" in out

    ok = mod._report("miss", "rated@10", [0.01, 0.02], 0, 1.0)
    assert ok["saturated"] is False
    assert ok["latency_ms"]["p99"] is not None


def test_bench_http_rows_carry_kernel_tag_for_ab_legs():
    """--kernel legs (a dense-vs-banded A/B) stamp the variant
    into every row — success AND saturated — so sweep artifacts can tell
    the two rated-miss curves apart; without --kernel the field is
    absent (an untagged --base target's variant is unknown)."""
    mod = _load_bench_http()
    assert "kernel" not in mod._report("miss", "rated@10", [0.01], 0, 1.0)
    mod._KERNEL_TAG = "banded"
    try:
        assert mod._report(
            "miss", "rated@10", [0.01], 0, 1.0
        )["kernel"] == "banded"
        assert mod._report(
            "miss", "rated@500", [], 9, 1.0
        )["kernel"] == "banded"
    finally:
        mod._KERNEL_TAG = None
