"""Every configuration and cell of ``BENCHMARK.json``, held on the CPU to what
the benchmark's own tests hold it to (``perfbench/tests/``, which the tier-1
command does not run): what a configuration names loads and keeps the
interface, its reference's ``NUMBERS`` are its limits, a rule broken in its
file fails at load, an option its reference does not render ends the command
before ``flyimg_tpu`` or ``jax`` is imported, and its cells read ``correct``
at their toy size. The cases are those of ``perfbench/tests/test_seams.py``,
taken from that file; each runs under a time limit of its own.

Then the smart-crop reference (``perfbench/references/smartcrop_lanczos.py``)
against the program's scorer, and the faults its comparison has to see."""

import importlib.util
import json
import os
import signal
import subprocess
import sys
from contextlib import contextmanager

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench.harness import compare, corpus, manifest, plain  # noqa: E402

BENCH_TESTS = os.path.join(ROOT, "perfbench", "tests")


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _load_seams():
    """``perfbench/tests/test_seams.py`` with ITS ``conftest`` (this
    directory has one of the same name)."""
    bench_conftest = _load("perfbench_tests_conftest", os.path.join(BENCH_TESTS, "conftest.py"))
    ours = sys.modules.get("conftest")
    sys.modules["conftest"] = bench_conftest
    try:
        return bench_conftest, _load("perfbench_tests_seams", os.path.join(BENCH_TESTS, "test_seams.py"))
    finally:
        if ours is not None:
            sys.modules["conftest"] = ours
        else:
            del sys.modules["conftest"]


BENCH_CONFTEST, SEAMS = _load_seams()
DOC = manifest.load_manifest()
CONFIGS = [c["name"] for c in DOC["configs"]]
CELLS = [c["name"] for c in DOC["workloads"]]
BREAKS = next(m.args[1] for m in SEAMS.test_a_configuration_that_breaks_a_rule_fails_at_load.pytestmark
              if m.args[0] == "break_it,says")


@contextmanager
def limit(seconds):
    """Fail the case, and only it, when it runs longer than ``seconds``."""
    def late(signum, frame):
        raise TimeoutError(f"the case ran over its {seconds} s")

    before = signal.signal(signal.SIGALRM, late)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, before)


@pytest.mark.parametrize("name", CONFIGS)
def test_what_a_configuration_names_binds_and_its_numbers_are_its_limits(name):
    with limit(60):
        SEAMS.test_what_a_configuration_names_loads_and_exposes_the_interface("benchmark", name)
        config = manifest.load_config(DOC, name)
        bound = manifest.bind(DOC, name, config)
        assert sorted(bound.reference.NUMBERS) == sorted(config["limits"])
        entry = next(c for c in DOC["configs"] if c["name"] == name)
        assert entry["source"] == config["source"] and sorted(entry["reduced"]) == sorted(config["reduced"])


@pytest.mark.parametrize("case", range(len(BREAKS)), ids=[says.replace(" ", "_") for _, says in BREAKS])
@pytest.mark.parametrize("name", CONFIGS)
def test_a_configuration_that_breaks_a_rule_fails_at_load(name, case):
    with limit(60):
        SEAMS.test_a_configuration_that_breaks_a_rule_fails_at_load("benchmark", name, *BREAKS[case])


@pytest.mark.parametrize("name", CELLS)
def test_refused_option_ends_the_command_before_the_program_is_imported(name, tmp_path):
    """``run.py`` on the cell with an option its reference does not render:
    exit 3 with the reference's message, and neither ``flyimg_tpu`` nor
    ``jax`` was imported to get there."""
    doc = json.loads(json.dumps(DOC))
    cell = manifest.workload(doc, name)
    entry = next(c for c in doc["configs"] if c["name"] == cell["config"])
    config = manifest.load_config(doc, cell["config"])
    config["options"]["url"] += ",zz_1"
    # beside the refused file, the plugs it names: the loader looks there first
    (tmp_path / "configs").mkdir()
    (tmp_path / "configs" / "refused.json").write_text(json.dumps(config))
    for kind in ("references", "corpora", "warmers"):
        os.symlink(os.path.join(ROOT, "perfbench", kind), tmp_path / kind)
    entry["file"] = str(tmp_path / "configs" / "refused.json")
    probe = (
        "import sys, json; sys.argv = ['run.py', '--workload', %r, '--seed', '1', '--seconds', '1']\n"
        "sys.path.insert(0, %r)\n"
        "from perfbench import run\n"
        "from perfbench.harness import manifest\n"
        "manifest.load_manifest = lambda path=None: json.loads(%r)\n"
        "code = run.main()\n"
        "print(code, sorted(m for m in ('jax', 'flyimg_tpu') if m in sys.modules))\n"
    ) % (name, ROOT, json.dumps(doc))
    proc = subprocess.run([sys.executable, "-c", probe], capture_output=True, text=True, cwd=ROOT,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=60)
    assert proc.stdout.strip() == "3 []", (proc.stdout, proc.stderr)
    assert "perfbench:" in proc.stderr and "zz_1" in proc.stderr


TOY_RUN = (
    "import sys, json, time\n"
    "t = time.perf_counter()\n"
    "sys.path.insert(0, %r)\n"
    "from perfbench.harness import cell, manifest\n"
    "result = cell.run_cell(manifest.load_manifest(), %r, %d, 3.0, True, t_process=t, toy=True,\n"
    "                       require_chip=False)\n"
    "print(json.dumps(result), flush=True)\n"
    "import os; os._exit(0)\n"
)


@pytest.mark.parametrize("name", CELLS)
def test_toy_cell_reads_correct_on_the_cpu(name):
    """The whole cell at its toy size, in a process of its own (the harness
    sets the process's compile cache) under its own time limit: ``correct``,
    nothing failed, nothing built inside the window, the host metrics printed
    and no device metric."""
    proc = subprocess.run([sys.executable, "-c", TOY_RUN % (ROOT, name, 2**31 + 29)],
                          capture_output=True, text=True, cwd=ROOT, timeout=420,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["compared"]["compiles_in_window"]["value"] == 0
    reported = set(result["metrics"])
    for metric in manifest.metrics_for(DOC, name, "per_layer"):
        assert (metric["name"] in reported) == (metric["source"] != "device_trace"), metric["name"]
    assert "busy_s" not in result["device"]


# ---------------------------------------------------------------------------
# the smart-crop reference against the program's scorer

SMC = "portrait-smartcrop-24mp"


@pytest.fixture(scope="module")
def smc():
    config, bound = BENCH_CONFTEST.toy_config("benchmark", SMC)
    return config, bound


def _program_answer(rendition):
    from flyimg_tpu.models import smartcrop

    item = smartcrop.prepare_work(rendition)
    crop = smartcrop.find_best_crops_batched([item])[0]
    return smartcrop.apply_crop(rendition, crop)


def _noise_image(seed, w, h):
    rng = np.random.default_rng(seed)
    coarse = rng.integers(0, 256, size=(h // 16 + 1, w // 16 + 1, 3)).astype(np.uint8)
    from PIL import Image

    big = np.asarray(Image.fromarray(coarse).resize((w, h), Image.BICUBIC)).astype(np.int16)
    return np.clip(big + rng.integers(-20, 21, size=(h, w, 3)), 0, 255).astype(np.uint8)


@pytest.mark.parametrize("kind,seed,size", [
    ("portrait", 11, (800, 1200)), ("portrait", 2**31 + 5, (800, 1200)), ("portrait", 12, (1200, 800)),
    ("portrait", 13, (400, 600)), ("noise", 21, (640, 480)), ("noise", 22, (300, 900)),
    ("noise", 23, (111, 166)), ("noise", 24, (90, 70)),
])
def test_program_scorer_cuts_a_window_the_reference_scores_as_its_best(smc, kind, seed, size):
    """``find_best_crops_batched`` + ``apply_crop`` on a rendition against the
    plain scorer on the same pixels: the program's cut has the size of one
    of the reference's candidate windows, its pixels are that window's, and
    the reference scores that window as its best or (random images have
    near-ties) within a hundredth of the spread of its candidates."""
    _, bound = smc
    ref = bound.reference
    w, h = size
    rendition = bound.make_image(seed, 0, w, h) if kind == "portrait" else _noise_image(seed, w, h)
    answer = _program_answer(rendition)
    windows, chosen = ref.score_rendition(rendition)
    numbers = ref.judge_answer(answer, rendition.astype(np.float32), windows)
    assert numbers["dims_gap"] == 0 and numbers["block_err"] == 0, numbers
    assert numbers["score_gap"] <= (0.0 if kind == "portrait" else 0.01), numbers
    if kind == "portrait":
        x0, y0, x1, y1 = chosen["box"]
        np.testing.assert_array_equal(answer, rendition[y0:y1, x0:x1])


def test_reference_work_counts_the_scorer_by_hand(smc):
    """111x166 prescaled pixels: 54 + 1 flops a pixel for the maps and the
    total, then 7 windows of 111x111 and 18 of 100x100 (99.9 rounded up) at
    3 flops a pixel under the window."""
    _, bound = smc
    config = manifest.load_config(DOC, SMC)
    kernels = bound.reference.work(config)
    assert set(kernels) == {"resample", "smartcrop_score"}
    pixels = 111 * 166
    assert kernels["smartcrop_score"]["flops"] == 55.0 * pixels + 3.0 * (7 * 111 * 111 + 18 * 100 * 100)
    assert kernels["smartcrop_score"]["bytes"] == 3.0 * pixels + 4.0 * (111 * 111 + 100 * 100) + 4.0 * 25
    assert kernels["resample"]["bytes"] == 3.0 * (4000 * 6000 + 800 * 1200)


def _judge_with(bound, originals, alter):
    """The reference put in the program's place (its own render, encoded by
    its own encoder), with ``alter(window list, chosen) -> box`` moving the
    cut: what a faulty scorer would answer."""
    ref = bound.reference
    answers = {}
    for i, data in enumerate(originals):
        frame = ref.render_fit(data, bound.options)
        windows, chosen = ref.score_rendition(plain.to_u8(frame))
        x0, y0, x1, y1 = alter(i, windows, chosen)
        answers[(i, "x")] = plain.encode_jpeg(plain.to_u8(frame[y0:y1, x0:x1]), 90)
    return compare.Judge(bound, originals).judge(answers)


def _one_stride_off(i, windows, chosen):
    near = [w for w in windows if w["width"] == chosen["width"]
            and abs(w["x"] - chosen["x"]) + abs(w["y"] - chosen["y"]) == 8]
    return max(near, key=lambda w: (w["y"], w["x"]))["box"]


@pytest.fixture(scope="module")
def smc_originals(smc):
    config, bound = smc
    return corpus.make_corpus(bound.make_image, 97, config["frame"], 4)


@pytest.mark.parametrize("fault,over", [
    ("sound", None),
    ("window_one_stride_off", "score_gap"),
    ("skin_term_dropped", "score_gap"),
    ("another_images_answer", "block_err"),
])
def test_planted_scorer_faults_read_not_correct(smc, smc_originals, fault, over):
    _, bound = smc
    ref = bound.reference
    if fault == "sound":
        verdict = _judge_with(bound, smc_originals, lambda i, windows, chosen: chosen["box"])
    elif fault == "window_one_stride_off":
        verdict = _judge_with(bound, smc_originals, _one_stride_off)
    elif fault == "skin_term_dropped":
        answers = {(i, "x"): plain.encode_jpeg(plain.to_u8(ref.render(data, bound.options, scorer="no_skin")), 90)
                   for i, data in enumerate(smc_originals)}
        verdict = compare.Judge(bound, smc_originals).judge(answers)
    else:
        first = plain.encode_jpeg(plain.to_u8(ref.render(smc_originals[0], bound.options)), 90)
        verdict = compare.Judge(bound, smc_originals).judge({(i, "x"): first for i in range(len(smc_originals))})
    numbers = verdict["numbers"]
    failing = [k for k, n in numbers.items() if n["value"] > n["limit"]]
    if over is None:
        assert verdict["correct"] and not failing, numbers
    else:
        assert not verdict["correct"] and over in failing, numbers


# ---------------------------------------------------------------------------
# the scorer's warmer refuses a program whose aux launches it cannot tell
# from transform launches


@pytest.mark.parametrize("observed_as,refused", [("own_label", False), ("transform_label", True)])
def test_smartcrop_warmer_refuses_a_program_that_counts_aux_launches_as_transform_launches(
        smc, observed_as, refused):
    """``warmers/smartcrop_aux.py`` sends one scoring item through the device
    controller before it builds anything. A program that observes that launch
    in ``controller="device"`` (the parent of PR 31 did; here the label is
    put back by hand) is refused with the ``RuntimeError`` that ``run.py``
    turns into exit code 4; the program as it is goes on to warm."""
    from types import SimpleNamespace

    from flyimg_tpu.appconfig import AppParameters
    from flyimg_tpu.runtime.batcher import BatchController
    from flyimg_tpu.runtime.metrics import MetricsRegistry
    from perfbench.harness import system

    config, bound = smc
    warm = dict(bound.warmers)["smartcrop_aux"]
    assert next(iter(dict(bound.warmers))) == "smartcrop_aux", "refuses in the first seconds: listed first"
    metrics = MetricsRegistry()
    batcher = BatchController(max_batch=8, deadline_ms=3000.0, metrics=metrics)
    if observed_as == "transform_label":
        batcher.aux_name = batcher.name
    sut = SimpleNamespace(params=AppParameters(dict(config["parameters"])), batcher=batcher,
                          counters=lambda: system.parse_prometheus(metrics.render_prometheus()))
    try:
        with limit(120):
            if refused:
                with pytest.raises(RuntimeError, match="aux .* launch in the transform launches' series"):
                    warm(sut, config, {})
            else:
                assert set(warm(sut, config, {})["seconds_by_batch_size"]) == {"1", "2", "4", "8"}
    finally:
        batcher.close()
