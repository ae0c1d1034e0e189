import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from perfbench.harness import manifest  # noqa: E402

# the benchmark's manifest, and the fixture deployment's: other semantics
# (an aspect-preserving resize), kept under fixtures/ as files alone
MANIFESTS = {"benchmark": manifest.load_manifest(),
             "fixture": manifest.load_manifest(os.path.join(HERE, "fixtures", "manifest.json"))}


def every(kind):
    """``(which manifest, name)`` of every configuration or cell of both."""
    return [(which, entry["name"]) for which, doc in MANIFESTS.items() for entry in doc[kind]]


def toy_config(which, name):
    """Configuration ``name`` at its toy size, with what it names loaded;
    ``which`` is a key of ``MANIFESTS`` or a manifest of its own."""
    doc = MANIFESTS[which] if isinstance(which, str) else which
    config = manifest.load_json(manifest.config_file(doc, name))
    manifest.apply_toy(config)
    return config, manifest.bind(doc, name, config)
