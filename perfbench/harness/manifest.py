"""BENCHMARK.json and the files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name in the manifest or
in the configuration's file:

    configs/<config>.json     sizes, options, parameters, limits of `correct`,
                              and, by name, its reference, corpus kind and warmers
    traffic/<traffic>.json    parameters of the one general load generator
    metrics/<metric>.json     which reader reads the metric, and its arguments
    readers/<reader>.py       the reader's code (``read(ctx, **args)``)
    references/<name>.py      a configuration's ``"reference"``: the plain
                              render of its semantics and the numbers that
                              decide `correct` (``REFERENCE_INTERFACE``)
    corpora/<kind>.py         its ``"corpus": {"kind"}``: ``make_image``
    warmers/<name>.py         each of its ``"warm"``: ``warm(sut, config, mix)``

A plug is a Python file loaded by its name (``load_plug``); no file of the
harness imports one. A configuration's plugs are looked for beside its own
file first (``<dir of configs/>/references/`` ...), then here, so that a
deployment kept elsewhere (``tests/fixtures/``) brings its own. Adding a
cell, a metric whose reader exists, or a configuration of other semantics
adds files and entries and edits nothing that is there.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from types import ModuleType, SimpleNamespace
from typing import Any, Callable, Dict, List, Optional, Sequence

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TRAFFIC_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")
REFERENCE_INTERFACE = ("NUMBERS", "parse", "render", "judge_original", "work")


class ManifestError(ValueError):
    """The manifest or one of its data files breaks a rule."""


def load_json(path: str) -> Any:
    with open(path, "r", encoding="utf-8") as fh:
        return json.load(fh)


def load_manifest(path: Optional[str] = None) -> Dict[str, Any]:
    return load_json(path or os.path.join(ROOT, "BENCHMARK.json"))


def workload(manifest: Dict[str, Any], name: str) -> Dict[str, Any]:
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return cell
    known = ", ".join(c["name"] for c in manifest["workloads"])
    raise ManifestError(f"unknown workload {name!r}; the manifest has: {known}")


def config_file(manifest: Dict[str, Any], name: str) -> str:
    for cfg in manifest["configs"]:
        if cfg["name"] == name:
            return os.path.join(ROOT, cfg["file"])
    raise ManifestError(f"no configuration {name!r} in the manifest")


def load_config(manifest: Dict[str, Any], name: str) -> Dict[str, Any]:
    return load_json(config_file(manifest, name))


def load_traffic(name: str) -> Dict[str, Any]:
    path = os.path.join(BENCH_DIR, "traffic", name + ".json")
    if not os.path.exists(path):
        raise ManifestError(f"no traffic file {path}")
    return load_json(path)


def load_metric(name: str) -> Dict[str, Any]:
    path = os.path.join(BENCH_DIR, "metrics", name + ".json")
    if not os.path.exists(path):
        raise ManifestError(f"no metric file {path}")
    return load_json(path)


def load_plug(kind: str, name: str, needs: Sequence[str],
              roots: Sequence[str] = (BENCH_DIR,)) -> ModuleType:
    """``<root>/<kind>/<name>.py`` of the first root that has it, as a module
    that has every attribute of ``needs``."""
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise ManifestError(f"bad name {name!r} under {kind}/")
    for root in roots:
        path = os.path.join(root, kind, name + ".py")
        if os.path.exists(path):
            break
    else:
        raise ManifestError(f"no {kind}/{name}.py under {' or '.join(roots)}")
    spec = importlib.util.spec_from_file_location(f"perfbench_{kind}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    missing = [attr for attr in needs if not hasattr(module, attr)]
    if missing:
        raise ManifestError(f"{path} lacks {', '.join(missing)}")
    return module


def load_reader(name: str) -> Callable[..., Optional[float]]:
    """``readers/<name>.py`` -> its ``read(ctx, **args)`` function."""
    return load_plug("readers", name, ("read",)).read


def plug_roots(manifest: Dict[str, Any], name: str) -> Sequence[str]:
    """Where the plugs of configuration ``name`` are looked for: beside its
    own file (``configs/`` and ``references/`` ... are siblings), then here."""
    return (os.path.dirname(os.path.dirname(config_file(manifest, name))), BENCH_DIR)


def bind(manifest: Dict[str, Any], name: str, config: Dict[str, Any]) -> SimpleNamespace:
    """What the configuration ``name`` names, loaded and held to its rules
    before anything of the program or of JAX is imported (a warmer imports
    the program inside ``warm``, not as it is loaded): its reference with its
    reading of the options, its corpus kind and its warmers. An option the
    reference does not render, a judged number without a limit and a limit
    without a number are errors here, not after a run. ``config`` is the
    configuration as it will be run (at its toy size, where that is what
    runs)."""
    roots = plug_roots(manifest, name)
    for key in ("reference", "corpus", "warm", "limits"):
        if key not in config:
            raise ManifestError(f"config {name}: names no {key!r}; no default stands in")
    reference = load_plug("references", config["reference"], REFERENCE_INTERFACE, roots)
    try:
        options = reference.parse(config)
    except (KeyError, ValueError) as exc:
        raise ManifestError(f"config {name}, reference {config['reference']}: {exc}") from exc
    if set(reference.NUMBERS) != set(config["limits"]):
        raise ManifestError(
            f"config {name}: its reference judges {sorted(reference.NUMBERS)} and its limits "
            f"are for {sorted(config['limits'])}; every number has a limit and every limit a number")
    kind = load_plug("corpora", config["corpus"].get("kind"), ("make_image",), roots)
    warmers = [(w, load_plug("warmers", w, ("warm",), roots).warm) for w in config["warm"]]
    return SimpleNamespace(reference=reference, options=options, limits=dict(config["limits"]),
                           make_image=kind.make_image, warmers=warmers)


def apply_toy(config: Dict[str, Any], mix: Optional[Dict[str, Any]] = None) -> None:
    """The configuration's toy size, for rehearsals and tests on the CPU."""
    toy = config["toy"]
    config["frame"], config["options"] = toy["frame"], toy["options"]
    config["corpus"] = dict(config["corpus"], **toy["corpus"])
    config["parameters"] = toy.get("parameters", config.get("parameters"))
    for key in ("in_flight", "preroll_images", "warm_launch_sizes"):
        if mix is not None:
            mix[key] = toy[key]


def cells_of(manifest: Dict[str, Any], metric: Dict[str, Any]) -> List[str]:
    """The cells a metric is reported in: its ``workloads`` list, or all."""
    return list(metric.get("workloads") or [c["name"] for c in manifest["workloads"]])


def metrics_for(manifest: Dict[str, Any], cell: str, kind: str) -> List[Dict[str, Any]]:
    return [m for m in manifest[kind] if cell in cells_of(manifest, m)]


def _line(text: Any, what: str, errors: List[str], limit: int = 200) -> None:
    if not isinstance(text, str) or not 1 <= len(text) <= limit \
            or "\n" in text or "\t" in text:
        errors.append(f"{what}: 1 to {limit} characters on one line, no tab")


def validate(manifest: Dict[str, Any]) -> List[str]:
    """The contract's rules on names, units, layers and files, as far as
    they can be checked without the driver. Returns the list of breaches."""
    errors: List[str] = []
    keys = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(manifest) != keys:
        errors.append(f"top-level keys must be exactly {sorted(keys)}")
        return errors
    paths = manifest["paths"]
    if not 1 <= len(paths) <= 16:
        errors.append("paths: 1 to 16 directories")
    for word in manifest["command"]:
        _line(word, f"command word {word!r}", errors)
    if not isinstance(manifest["run_seconds"], int) or not 1 <= manifest["run_seconds"] <= 51:
        errors.append("run_seconds: a whole number from 1 to 51")

    def under_paths(rel: str) -> bool:
        return any(rel == p or rel.startswith(p.rstrip("/") + "/") for p in paths)

    seen: Dict[str, set] = {k: set() for k in ("configs", "workloads", "metrics")}
    files = set()
    for cfg in manifest["configs"]:
        if set(cfg) != {"name", "source", "file", "reduced", "why"}:
            errors.append(f"config {cfg.get('name')}: keys must be name, source, file, reduced, why")
            continue
        if not NAME_RE.match(cfg["name"]):
            errors.append(f"config name {cfg['name']!r}")
        if cfg["name"] in seen["configs"]:
            errors.append(f"config {cfg['name']} appears twice")
        seen["configs"].add(cfg["name"])
        _line(cfg["source"], f"config {cfg['name']} source", errors)
        _line(cfg["why"], f"config {cfg['name']} why", errors)
        if not under_paths(cfg["file"]) or cfg["file"] in files:
            errors.append(f"config {cfg['name']}: file must lie under paths and be its own")
        files.add(cfg["file"])
        if not os.path.exists(os.path.join(ROOT, cfg["file"])):
            errors.append(f"config {cfg['name']}: {cfg['file']} does not exist")
        if len(cfg["reduced"]) > 16 or not all(NAME_RE.match(k) for k in cfg["reduced"]):
            errors.append(f"config {cfg['name']}: reduced holds at most 16 names")
    pairs = set()
    four = 0
    for cell in manifest["workloads"]:
        if set(cell) != {"name", "config", "traffic", "chips", "why"}:
            errors.append(f"workload {cell.get('name')}: keys must be name, config, traffic, chips, why")
            continue
        for key in ("name", "config", "traffic"):
            if not NAME_RE.match(cell[key]):
                errors.append(f"workload {cell['name']}: bad {key} {cell[key]!r}")
        if cell["name"] in seen["workloads"]:
            errors.append(f"workload {cell['name']} appears twice")
        seen["workloads"].add(cell["name"])
        if cell["config"] not in seen["configs"]:
            errors.append(f"workload {cell['name']}: unknown config {cell['config']}")
        if (cell["config"], cell["traffic"]) in pairs:
            errors.append(f"workload {cell['name']}: its config and traffic appear twice")
        pairs.add((cell["config"], cell["traffic"]))
        if cell["chips"] not in (1, 4):
            errors.append(f"workload {cell['name']}: chips is 1 or 4")
        four += cell["chips"] == 4
        _line(cell["why"], f"workload {cell['name']} why", errors)
        traffic = os.path.join(BENCH_DIR, "traffic", cell["traffic"] + ".json")
        if not os.path.exists(traffic):
            errors.append(f"workload {cell['name']}: no traffic file {traffic}")
    if four > max(1, len(manifest["workloads"]) // 4):
        errors.append("too many four-chip cells")
    used = {c["config"] for c in manifest["workloads"] if "config" in c}
    for name in seen["configs"] - used:
        errors.append(f"config {name} is used by no cell")
    e2e_names = set()
    for kind, allowed in (("end_to_end", {"name", "unit", "better", "bound", "source"}),
                          ("per_layer", {"name", "unit", "better", "source", "layer", "moves"})):
        for metric in manifest[kind]:
            name = metric.get("name")
            if set(metric) - {"workloads"} != allowed:
                errors.append(f"{kind} metric {name}: keys must be {sorted(allowed)} and may add workloads")
                continue
            if not NAME_RE.match(name):
                errors.append(f"metric name {name!r}")
            if name in seen["metrics"]:
                errors.append(f"metric {name} appears twice")
            seen["metrics"].add(name)
            if not UNIT_RE.match(metric["unit"]):
                errors.append(f"metric {name}: unit {metric['unit']!r}")
            if metric["better"] not in ("lower", "higher"):
                errors.append(f"metric {name}: better is lower or higher")
            if metric["source"] not in SOURCES:
                errors.append(f"metric {name}: source {metric['source']!r}")
            for cell in metric.get("workloads", []):
                if cell not in seen["workloads"]:
                    errors.append(f"metric {name}: unknown workload {cell}")
            if kind == "end_to_end":
                e2e_names.add(name)
                if metric["source"] not in ("host_clock", "device_trace"):
                    errors.append(f"metric {name}: an end-to-end metric is host_clock or device_trace")
                if not 0.01 <= metric["bound"] <= 0.1:
                    errors.append(f"metric {name}: bound from 0.01 to 0.1")
            else:
                # PR 22 was refused for a layer with a space in it
                if not NAME_RE.match(metric["layer"]):
                    errors.append(f"metric {name}: layer {metric['layer']!r} must be one token")
                if metric["moves"] not in e2e_names:
                    errors.append(f"metric {name}: moves {metric['moves']!r} is no end-to-end metric")
                if name.endswith("_roofline") and metric["unit"] != "%":
                    errors.append(f"metric {name}: a roofline share has the unit %")
                if not os.path.exists(os.path.join(BENCH_DIR, "metrics", name + ".json")):
                    errors.append(f"metric {name}: no metrics/{name}.json")
    if "setup_s" not in e2e_names:
        errors.append("end_to_end lacks setup_s")
    if not 1 <= len(manifest["end_to_end"]) <= 16 or not 1 <= len(manifest["per_layer"]) <= 128:
        errors.append("1 to 16 end-to-end metrics and 1 to 128 per-layer metrics")
    for cell in seen["workloads"]:
        if len(metrics_for(manifest, cell, "end_to_end")) < 2:
            errors.append(f"workload {cell}: reports setup_s and one more end-to-end metric")
        if not metrics_for(manifest, cell, "per_layer"):
            errors.append(f"workload {cell}: reports no per-layer metric")
    if len(json.dumps(manifest)) > 64 * 1024:
        errors.append("the manifest is over 64 KiB")
    return errors
