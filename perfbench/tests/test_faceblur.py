"""The face-blur configurations' own faults, at a toy size: the reference
put in the program's place with its face pass broken has to come out as not
correct, by the number that is there for that fault, and sound as correct.
Beside ``test_faults.py`` (a whole run with the timed path's answers altered)
and ``test_control.py`` (the resample below its stated precision), which run
over every configuration. The controls at the configuration's own size are
``control_faces.py``'s; their readings are in PERF.md section 2."""

import pytest
from conftest import MANIFESTS, toy_config

from perfbench.control_faces import controlled
from perfbench.harness import compare, corpus, plain

FACE_CONFIGS = [c["name"] for c in MANIFESTS["benchmark"]["configs"]
                if "face_gap" in toy_config("benchmark", c["name"])[0]["limits"]]


@pytest.fixture(scope="module", params=FACE_CONFIGS)
def deployment(request):
    config, bound = toy_config("benchmark", request.param)
    return bound, corpus.make_corpus(bound.make_image, 2**31 + 11, config["frame"], 3)


@pytest.mark.parametrize("fault,over", [
    ("sound", None), ("detector_bf16", None),
    ("not_pixelated", "face_gap"), ("shifted", "block_err"), ("another_images_answer", "block_err"),
])
def test_face_fault_is_refused_by_its_number(deployment, fault, over):
    bound, originals = deployment
    ref = bound.reference
    answers = {}
    for i, data in enumerate(originals):
        kind = "sound" if fault == "another_images_answer" else fault
        out = controlled(ref, ref.render_fill(data, bound.options), kind)
        answers[(i, fault)] = plain.encode_jpeg(out, 90)
    if fault == "another_images_answer":
        answers = {key: answers[(0, fault)] for key in answers}
    verdict = compare.Judge(bound, originals).judge(answers)
    failing = [k for k, n in verdict["numbers"].items() if n["value"] > n["limit"]]
    if over is None:
        assert verdict["correct"] and not failing, verdict["numbers"]
    else:
        assert not verdict["correct"] and over in failing, verdict["numbers"]


def test_every_frame_of_the_corpus_shows_four_faces_the_reference_is_sure_of(deployment):
    bound, originals = deployment
    ref = bound.reference
    for data in originals:
        found = ref.detect(plain.to_u8(ref.render_fill(data, bound.options)))
        assert sum(k["score"] >= ref.THRESHOLD + ref.MARGIN for k in found) >= 4
