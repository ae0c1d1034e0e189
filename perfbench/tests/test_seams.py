"""The seams a configuration plugs into: its reference, its corpus kind and
its warmers are files it names, loaded by name and held to their rules before
the program or the backend is imported. Over every configuration of
``BENCHMARK.json`` and of the fixture deployment. None takes a chip."""

import hashlib
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest
from conftest import HERE, MANIFESTS, ROOT, every, toy_config

from perfbench.control import answers_for
from perfbench.harness import compare, corpus, manifest, plain

# Each configuration's pins, ``pins/<config>.json`` beside its own ``configs/``
# (the first of ``manifest.plug_roots``): the sha256 of its toy corpus at the
# seed ``SAME_SEED`` and its reference's needed work at full size, compared
# with ``==``. A pin is what these tests expect and nothing a run reads, so it
# is a file of its own and not a key of the configuration. The JPEG
# configurations' are as the harness made them before it was taught a second
# sample depth, so that teaching it moved no byte of the 8-bit originals and
# no digit of a roofline's work.
SAME_SEED = 2**31 + 3


def load_pin(doc, name):
    """``{"toy_corpus_sha256", "work"}`` of configuration ``name``; a test
    that finds no pin file fails naming the file and how to make it."""
    path = os.path.join(manifest.plug_roots(doc, name)[0], "pins", name + ".json")
    if not os.path.exists(path):
        pytest.fail(
            f"configuration {name} has no pin file {path}: write it as "
            '{"toy_corpus_sha256": corpus.digest(<its toy corpus at the seed 2**31 + 3>), '
            '"work": <its reference.work(config) at full size>}', pytrace=False)
    return manifest.load_json(path)


def _same_bytes_and_work(doc, name):
    """The toy corpus at ``SAME_SEED``: its digest as pinned, every original
    read back by ``plain.decode`` at the frame's depth (a PNG's sample for
    sample as made); the reference's needed work as pinned."""
    pin = load_pin(doc, name)
    config, bound = toy_config(doc, name)
    made = corpus.make_corpus(bound.make_image, SAME_SEED, config["frame"], config["corpus"]["images"])
    assert corpus.digest(made) == hashlib.sha256(b"".join(made)).hexdigest() == pin["toy_corpus_sha256"]
    for index, data in enumerate(made[:2]):
        back = plain.decode(data)
        assert back.dtype == plain.DTYPES[bound.depth] and back.shape == (
            config["frame"]["height"], config["frame"]["width"], 3)
        if config["frame"]["format"] == "png":
            np.testing.assert_array_equal(
                back, bound.make_image(SAME_SEED, index, config["frame"]["width"], config["frame"]["height"]))
    full = manifest.load_config(doc, name)
    assert manifest.bind(doc, name, full).reference.work(full) == pin["work"]


def _loads_and_exposes(doc, name):
    assert manifest.validate(doc) == []
    config = manifest.load_config(doc, name)
    for sized, bound in ((config, manifest.bind(doc, name, config)), toy_config(doc, name)):
        assert all(callable(getattr(bound.reference, f)) for f in ("parse", "render", "judge_original", "work"))
        assert set(bound.reference.NUMBERS) == set(sized["limits"])
        assert callable(bound.make_image) and [w for w, _ in bound.warmers] == sized["warm"]
        kernels = bound.reference.work(sized)
        assert kernels and all(set(w) == {"flops", "bytes"} and min(w.values()) > 0 for w in kernels.values())
    _same_bytes_and_work(doc, name)


@pytest.mark.parametrize("which,name", every("configs"))
def test_what_a_configuration_names_loads_and_exposes_the_interface(which, name):
    _loads_and_exposes(MANIFESTS[which], name)


def _size(c):
    return {"width": c["frame"]["width"], "height": c["frame"]["height"]}


def _png_of_the_other_depth(c):
    """Frame and toy frame as PNGs of the depth the corpus kind does not make."""
    other = 24 - c["frame"].get("bit_depth", 8)
    c["frame"] = dict(_size(c), format="png", bit_depth=other, compression=6)
    c["toy"]["frame"] = dict(c["frame"])


# the frame's rules (manifest.frame_depth): each breach, of any configuration
FRAME_BREAKS = [
    (lambda c: c.update(frame=dict(_size(c), format="png", compression=6)), r"png frame lacks \['bit_depth'\]"),
    (lambda c: c.update(frame=dict(_size(c), format="jpeg", subsampling="4:2:0", quality=90, bit_depth=16)),
     r"jpeg frame lacks \[\] and takes no \['bit_depth'\]"),
    (lambda c: c.update(frame=dict(_size(c), format="png", bit_depth=8, compression=6, quality=90)),
     r"png frame lacks \[\] and takes no \['quality'\]"),
    (lambda c: c.update(frame=dict(_size(c), format="png", bit_depth=12, compression=6)), "bit_depth is 8 or 16"),
    (lambda c: c.update(frame=dict(_size(c), format="png", bit_depth=16, compression=10)), "compression is a zlib level"),
    (lambda c: c["frame"].update(format="webp"), "format 'webp' is 'jpeg' or 'png'"),
    (lambda c: c["toy"]["frame"].update(format="tiff"), "toy.frame: format 'tiff'"),
    (_png_of_the_other_depth, r"corpus kind \w+ makes \d+-bit samples"),
]


BREAKS = [
    (lambda c: c["options"].update(url=c["options"]["url"] + ",smc_1"), "smc_1"),
    (lambda c: c["limits"].pop(sorted(c["limits"])[0]), "every number has a limit"),
    (lambda c: c["limits"].update(sharpness=1.0), "every limit a number"),
    (lambda c: c.pop("reference"), "no default stands in"),
    (lambda c: c.update(reference="no_such_reference"), "no references/no_such_reference.py"),
    (lambda c: c["corpus"].pop("kind"), "bad name None under corpora/"),
    (lambda c: c.update(warm=["../transform"]), "bad name"),
] + FRAME_BREAKS


def _breaks_at_load(doc, name, break_it, says):
    config = manifest.load_json(manifest.config_file(doc, name))
    break_it(config)
    with pytest.raises(manifest.ManifestError, match=says):
        manifest.bind(doc, name, config)


@pytest.mark.parametrize("which,name", every("configs"))
@pytest.mark.parametrize("break_it,says", BREAKS)
def test_a_configuration_that_breaks_a_rule_fails_at_load(which, name, break_it, says):
    _breaks_at_load(MANIFESTS[which], name, break_it, says)


def _added_as_files(tmp_path, pinned):
    """A copy of the fixture's 16-bit configuration under a new name, added
    as files alone in a tree of its own: its configuration file, its pin
    file where ``pinned``, the plugs of its deployment linked beside them,
    and a manifest that names it and one cell of it."""
    fixture, source, name = MANIFESTS["fixture"], "png16-fit-416", "png16-fit-416-added"
    config = dict(manifest.load_config(fixture, source), name=name)
    (tmp_path / "configs").mkdir()
    (tmp_path / "configs" / (name + ".json")).write_text(json.dumps(config))
    for kind in ("references", "corpora", "warmers"):
        if os.path.isdir(os.path.join(HERE, "fixtures", kind)):
            os.symlink(os.path.join(HERE, "fixtures", kind), tmp_path / kind)
    if pinned:
        (tmp_path / "pins").mkdir()
        (tmp_path / "pins" / (name + ".json")).write_text(json.dumps(load_pin(fixture, source)))
    doc = json.loads(json.dumps(fixture))
    entry = next(c for c in doc["configs"] if c["name"] == source)
    cell = next(w for w in doc["workloads"] if w["config"] == source)
    doc["paths"] = [str(tmp_path)]
    doc["configs"] = [dict(entry, name=name, file=str(tmp_path / "configs" / (name + ".json")))]
    doc["workloads"] = [dict(cell, name=name + "-saturated", config=name)]
    return doc, name


def test_a_configuration_added_as_files_alone_keeps_every_rule(tmp_path):
    """Files and a manifest entry are all a configuration needs: the
    interface, its pins and every break case, with no edit to this file."""
    doc, name = _added_as_files(tmp_path, pinned=True)
    _loads_and_exposes(doc, name)
    for break_it, says in BREAKS:
        _breaks_at_load(doc, name, break_it, says)


def test_a_configuration_without_its_pin_file_fails_naming_the_file(tmp_path):
    doc, name = _added_as_files(tmp_path, pinned=False)
    with pytest.raises(pytest.fail.Exception) as failed:
        _loads_and_exposes(doc, name)
    says = str(failed.value)
    assert str(tmp_path / "pins" / (name + ".json")) in says, says
    assert "corpus.digest" in says and "2**31 + 3" in says and "reference.work(config)" in says, says


@pytest.mark.parametrize("break_it,says", [
    (lambda c: c["options"].update(url=c["options"]["url"] + ",smc_1"), "smc_1")] + FRAME_BREAKS)
def test_refused_options_end_the_command_before_the_program_is_imported(tmp_path, break_it, says):
    """``run.py`` on the fixture manifest with an ``smc_1`` options string,
    or a frame that breaks its rules: exit 3 with the rule's message, and
    neither ``flyimg_tpu`` nor ``jax`` was imported to get there."""
    fixture = json.loads(json.dumps(MANIFESTS["fixture"]))
    config = manifest.load_json(manifest.config_file(fixture, fixture["configs"][0]["name"]))
    break_it(config)
    root = tmp_path / "configs"
    root.mkdir()
    (root / "refused.json").write_text(json.dumps(config))
    os.symlink(os.path.join(HERE, "fixtures", "references"), tmp_path / "references")
    fixture["configs"][0]["file"] = str(root / "refused.json")
    probe = (
        "import sys, json; sys.argv = ['run.py', '--workload', %r, '--seed', '1', '--seconds', '1']\n"
        "sys.path.insert(0, %r)\n"
        "from perfbench import run\n"
        "from perfbench.harness import manifest\n"
        "manifest.load_manifest = lambda path=None: json.loads(%r)\n"
        "code = run.main()\n"
        "print(code, sorted(m for m in ('jax', 'flyimg_tpu') if m in sys.modules))\n"
    ) % (fixture["workloads"][0]["name"], ROOT, json.dumps(fixture))
    proc = subprocess.run([sys.executable, "-c", probe], capture_output=True, text=True, cwd=ROOT,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.stdout.strip() == "3 []", (proc.stdout, proc.stderr)
    assert "perfbench:" in proc.stderr and re.search(says, proc.stderr), proc.stderr


PINNED = {
    # sha256 over the encoded toy corpus (4 x 1536x1024, 4:2:0, q90) of kind
    # photo, as the parent commit's harness/corpus.py made it
    1: "9a354251c3ae7f65b6eee95cd40595899ada053c81c70ae92b967afc7226a9b6",
    2: "6dc121530a9adf54761ea26be1dd4bcd215f1fe98a777ff46066fdfd383ebe06",
    3: "f02de01b44d7fa317ef237b205aa22f5cc671bef65ad0b67b992bd74b5dd0565",
}


@pytest.mark.parametrize("seed", sorted(PINNED))
def test_photo_corpus_is_the_same_bytes_for_the_same_seed(seed):
    config, bound = toy_config("benchmark", MANIFESTS["benchmark"]["configs"][0]["name"])
    assert config["corpus"]["kind"] == "photo"
    made = corpus.make_corpus(bound.make_image, seed, config["frame"], config["corpus"]["images"])
    assert hashlib.sha256(b"".join(made)).hexdigest() == PINNED[seed]


def test_no_file_of_the_harness_names_a_plug():
    """References, corpus kinds and warmers are reached through the loader
    alone: no ``import`` in ``harness/`` or in the scripts names one."""
    bench = os.path.dirname(HERE)
    plugs = {os.path.splitext(f)[0] for kind in ("references", "corpora", "warmers")
             for f in os.listdir(os.path.join(bench, kind)) if f.endswith(".py")}
    plugs |= {"references", "corpora", "warmers"}
    files = [os.path.join(bench, f) for f in os.listdir(bench) if f.endswith(".py")]
    files += [os.path.join(bench, "harness", f) for f in os.listdir(os.path.join(bench, "harness"))]
    for path in files:
        if not path.endswith(".py"):
            continue
        with open(path, encoding="utf-8") as fh:
            for line in fh:
                words = line.replace(",", " ").replace(".", " ").split()
                if words[:1] in (["import"], ["from"]):
                    assert not plugs & set(words), (path, line)


SIXTEEN = [(which, name) for which, name in every("configs")
           if manifest.load_config(MANIFESTS[which], name)["frame"].get("bit_depth") == 16]


@pytest.fixture(scope="module", params=SIXTEEN, ids=[name for _, name in SIXTEEN])
def sixteen(request):
    config, bound = toy_config(*request.param)
    return bound, corpus.make_corpus(bound.make_image, 2**31 + 17, config["frame"], 2)


def _widened_from_8_bits(bound, data):
    """The render cut to 8 bits and widened back: what an 8-bit path that
    answers in a 16-bit PNG gives."""
    frame = bound.reference.render(data, bound.options)
    return plain.to_u8(frame / 257.0).astype(np.uint16) * np.uint16(257)


def _shifted(bound, data):
    return np.roll(plain.to_depth(bound.reference.render(data, bound.options), 16), 1, axis=1)


@pytest.mark.parametrize("fault,over", [
    ("sound", None), ("widened_from_8_bits", "block_err"), ("bfloat16_operands", "block_err"),
    ("shifted_one_pixel", "block_err"),
])
def test_sixteen_bit_answers_are_judged_at_their_own_depth(sixteen, fault, over):
    """The reference's render in the program's place, as a lossless 16-bit
    PNG, reads correct; kept to 8 bits, resampled with the program's
    bfloat16 operands of today, or shifted by a pixel, it does not.
    Readings: PERF.md section 6 (PR 39)."""
    bound, originals = sixteen
    if fault == "sound":
        answers = answers_for(bound, originals, "float32")
    elif fault == "bfloat16_operands":
        answers = answers_for(bound, originals, "bfloat16")
    else:
        alter = _widened_from_8_bits if fault == "widened_from_8_bits" else _shifted
        answers = {(i, fault): plain.encode_png(alter(bound, data)) for i, data in enumerate(originals)}
    verdict = compare.Judge(bound, originals).judge(answers)
    failing = [k for k, n in verdict["numbers"].items() if n["value"] > n["limit"]]
    if over is None:
        assert verdict["correct"] and not failing, verdict["numbers"]
    else:
        assert not verdict["correct"] and over in failing, verdict["numbers"]


@pytest.mark.parametrize("colour,channels", [("grey", 1), ("alpha", 4)])
def test_a_sixteen_bit_png_of_grey_or_alpha_is_an_answer_that_would_not_decode(sixteen, colour, channels):
    """``plain.decode`` reads the depth and colour type from the IHDR: a
    16-bit PNG that is not RGB raises, and the judge counts it unanswered,
    as it does damaged bytes."""
    bound, originals = sixteen
    sound = answers_for(bound, originals, "float32")
    rgb = plain.decode(sound[(0, "float32")])
    import cv2

    shaped = rgb[..., :1] if channels == 1 else np.concatenate([rgb, rgb[..., :1]], axis=2)
    ok, buf = cv2.imencode(".png", np.ascontiguousarray(shaped))
    assert ok and plain.png_depth(buf.tobytes()) == (16, 0 if channels == 1 else 6)
    with pytest.raises(ValueError, match="colour type"):
        plain.decode(buf.tobytes())
    sound[(0, "float32")] = buf.tobytes()
    verdict = compare.Judge(bound, originals).judge(sound)
    assert not verdict["correct"] and verdict["numbers"]["unanswered"]["value"] == 1.0, verdict["numbers"]
