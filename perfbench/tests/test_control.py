"""The control of ``correct``: the reference put in the program's place and
computed in the nearest precision below the one the configurations state
(float8_e4m3fn operands for bfloat16) has to come out as not correct, and the
reference itself, in float32 or bfloat16, as correct. At a size a test run
holds; the readings at the cells' own size are in PERF.md."""

import copy

import pytest

from perfbench.control import answers_for
from perfbench.harness import compare, corpus, manifest
from perfbench.harness.cell import apply_toy

CONFIGS = ["dslr-backfill-24mp"]


def _toy(doc, config_name):
    config = copy.deepcopy(manifest.load_config(doc, config_name))
    apply_toy(config)
    return config


@pytest.mark.parametrize("config_name", CONFIGS)
@pytest.mark.parametrize("seed", [5, 2**31 + 7, 123456789])
def test_fp8_control_fails_and_reference_passes(doc, config_name, seed):
    config = _toy(doc, config_name)
    originals = corpus.make_corpus(seed, config["frame"], 3)
    judge = compare.Judge(config, originals)
    for operands in ("float32", "bfloat16"):
        verdict = judge.judge(answers_for(config, originals, operands))
        assert verdict["correct"], (operands, verdict["numbers"])
    control = judge.judge(answers_for(config, originals, "float8_e4m3fn"))
    assert not control["correct"], control["numbers"]
    assert control["numbers"]["block_err"]["value"] > control["numbers"]["block_err"]["limit"]
