"""chip_smoke.py under the explicit CPU pin: every step of the smoke runs
here at the same code path (serve child, real-size traffic, restart against
the same compile cache), and the device check is its ONLY failure. On the
chip the same script must pass outright; that run is the builder's and the
driver's, through the chip tool."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_under_cpu_pin_fails_only_its_device_check():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # the smoke must work with the cache wherever it is placed from
    # outside; leave the default (<checkout>/var/cache/xla) in force
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900,
    )
    tail = proc.stderr[-3000:]
    assert proc.returncode == 1, tail
    assert proc.stdout.strip() == "", proc.stdout  # no result without a chip
    # the reason is printed last
    assert proc.stderr.strip().splitlines()[-1].startswith(
        "chip_smoke: FAILED: device_is_tpu"
    ), tail
    with open(
        os.path.join(REPO, "chiprun_out", "chip_smoke", "report.json"),
        encoding="utf-8",
    ) as fh:
        report = json.load(fh)
    assert report["ok"] is False
    assert report["platform"] == "cpu"
    assert report["device"] == {
        "platform": "cpu", "kind": "cpu", "count": report["device_count"],
    }
    failed = sorted(k for k, ok in report["checks"].items() if not ok)
    assert failed == ["device_is_tpu"], (failed, report["failures"])
    assert len(report["failures"]) == 1
    # every phase ran: the checks that only later phases record are there
    for name in (
        "native_codec", "responses_ok", "batches_formed", "lossless_psnr",
        "repeat_is_cache_hit", "no_fallbacks", "no_5xx",
        "programs_compiled", "clean_exit", "compile_cache_reused",
    ):
        assert report["checks"][name] is True, name
    assert report["programs_compiled"] > 0
    assert report["compile_cache_entries_gained_run2"] == 0
    assert report["compile_cache_dir"] == os.path.join(
        REPO, "var", "cache", "xla"
    )


def test_result_line_holds_the_contract_keys_and_no_others():
    """The last stdout line of a passing run is read by the driver: exactly
    ``ok`` and ``device`` with ``platform``, ``kind`` (text) and ``count``
    (a whole number). The full report is the line before it."""
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    assert "jax" not in chip_smoke.__dict__  # the parent never imports jax
    doc = json.loads(chip_smoke.result_line(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    ))
    assert doc == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }
    assert isinstance(doc["device"]["count"], int)


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    """The script without the program next to it exits non-zero and
    prints no result."""
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path,
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
