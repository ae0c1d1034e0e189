"""The control of ``correct``: each configuration's reference put in the
program's place and computed in the nearest precision below the one the
configuration states (float8_e4m3fn operands for bfloat16) has to come out
as not correct, and the reference itself, in float32 or the stated one, as
correct.
At a size a test run holds; the readings at the cells' own size are in
PERF.md."""

import pytest
from conftest import every, toy_config

from perfbench.control import BELOW, answers_for
from perfbench.harness import compare, corpus


@pytest.mark.parametrize("which,config_name", every("configs"))
@pytest.mark.parametrize("seed", [5, 2**31 + 7, 123456789])
def test_control_below_the_stated_precision_fails_and_reference_passes(which, config_name, seed):
    config, bound = toy_config(which, config_name)
    stated = config["guarantees"]["precision"]
    originals = corpus.make_corpus(bound.make_image, seed, config["frame"], 3)
    judge = compare.Judge(bound, originals)
    for operands in ("float32", stated):
        verdict = judge.judge(answers_for(bound, originals, operands))
        assert verdict["correct"], (operands, verdict["numbers"])
    control = judge.judge(answers_for(bound, originals, BELOW[stated]))
    assert not control["correct"], control["numbers"]
    over = [k for k, n in control["numbers"].items() if n["value"] > n["limit"]]
    assert over and set(over) <= set(bound.reference.NUMBERS), control["numbers"]
