"""The seams a configuration plugs into: its reference, its corpus kind and
its warmers are files it names, loaded by name and held to their rules before
the program or the backend is imported. Over every configuration of
``BENCHMARK.json`` and of the fixture deployment. None takes a chip."""

import hashlib
import json
import os
import subprocess
import sys

import pytest
from conftest import HERE, MANIFESTS, ROOT, every, toy_config

from perfbench.harness import corpus, manifest


@pytest.mark.parametrize("which,name", every("configs"))
def test_what_a_configuration_names_loads_and_exposes_the_interface(which, name):
    doc = MANIFESTS[which]
    assert manifest.validate(doc) == []
    config = manifest.load_config(doc, name)
    for sized, bound in ((config, manifest.bind(doc, name, config)), toy_config(which, name)):
        assert all(callable(getattr(bound.reference, f)) for f in ("parse", "render", "judge_original", "work"))
        assert set(bound.reference.NUMBERS) == set(sized["limits"])
        assert callable(bound.make_image) and [w for w, _ in bound.warmers] == sized["warm"]
        kernels = bound.reference.work(sized)
        assert kernels and all(set(w) == {"flops", "bytes"} and min(w.values()) > 0 for w in kernels.values())


@pytest.mark.parametrize("which,name", every("configs"))
@pytest.mark.parametrize("break_it,says", [
    (lambda c: c["options"].update(url=c["options"]["url"] + ",smc_1"), "smc_1"),
    (lambda c: c["limits"].pop(sorted(c["limits"])[0]), "every number has a limit"),
    (lambda c: c["limits"].update(sharpness=1.0), "every limit a number"),
    (lambda c: c.pop("reference"), "no default stands in"),
    (lambda c: c.update(reference="no_such_reference"), "no references/no_such_reference.py"),
    (lambda c: c["corpus"].pop("kind"), "bad name None under corpora/"),
    (lambda c: c.update(warm=["../transform"]), "bad name"),
])
def test_a_configuration_that_breaks_a_rule_fails_at_load(which, name, break_it, says):
    doc = MANIFESTS[which]
    config = manifest.load_json(manifest.config_file(doc, name))
    break_it(config)
    with pytest.raises(manifest.ManifestError, match=says):
        manifest.bind(doc, name, config)


def test_refused_options_end_the_command_before_the_program_is_imported(tmp_path):
    """``run.py`` on the fixture manifest with an ``smc_1`` options string:
    exit 3 with the reference's message, and neither ``flyimg_tpu`` nor
    ``jax`` was imported to get there."""
    fixture = json.loads(json.dumps(MANIFESTS["fixture"]))
    config = manifest.load_json(manifest.config_file(fixture, fixture["configs"][0]["name"]))
    config["options"]["url"] += ",smc_1"
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
    assert "perfbench:" in proc.stderr and "smc_1" in proc.stderr


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
