#!/usr/bin/env python3
"""The benchmark's check of itself, with no chip:

    JAX_PLATFORMS=cpu python3 perfbench/selfcheck.py [--no-run]

1. loads the manifest and every file it names (each configuration's
   reference, corpus kind and warmers with it), and holds every name, unit
   and layer to the contract's character rules;
2. checks the needed-work function on hand-computed shapes, and that an
   unknown device kind is an error;
3. checks the trace reduction on the small trace in ``fixtures/``;
4. runs one cell at its toy size on the CPU, traced, and sees that no device
   metric is printed; and that the command itself refuses to run there.
"""

import json
import math
import os
import subprocess
import sys
import time

T_PROCESS = time.perf_counter()
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from perfbench.harness import manifest, trace, work  # noqa: E402


def check(cond, what):
    print(("ok   " if cond else "FAIL ") + what)
    if not cond:
        check.failed += 1


check.failed = 0


def files() -> None:
    doc = manifest.load_manifest()
    errors = manifest.validate(doc)
    check(not errors, f"manifest keeps the contract's rules {errors or ''}")
    for cfg in doc["configs"]:
        config = manifest.load_config(doc, cfg["name"])
        check(config["name"] == cfg["name"] and config["source"] == cfg["source"],
              f"config {cfg['name']}: file and manifest agree on name and source")
        check(sorted(config["reduced"]) == sorted(cfg["reduced"]),
              f"config {cfg['name']}: reduced agrees")
        for key in ("frame", "corpus", "options", "reference", "warm", "output", "guarantees",
                    "parameters", "source_defines", "assumed", "limits", "toy"):
            check(key in config, f"config {cfg['name']}: has {key}")
        for toy in (False, True):
            sized = json.loads(json.dumps(config))
            if toy:
                manifest.apply_toy(sized)
            try:
                bound = manifest.bind(doc, cfg["name"], sized)
                kernels = bound.reference.work(sized)
                check(all(set(w) == {"flops", "bytes"} for w in kernels.values()),
                      f"config {cfg['name']}{' (toy)' if toy else ''}: reference {sized['reference']}, corpus kind "
                      f"{sized['corpus']['kind']} and warmers {sized['warm']} load; needed work for {sorted(kernels)}")
            except manifest.ManifestError as exc:
                check(False, f"config {cfg['name']}: {exc}")
        refused = dict(config, options={"url": config["options"]["url"] + ",zz_1"})
        try:
            manifest.bind(doc, cfg["name"], refused)
            check(False, f"config {cfg['name']}: an option its reference does not render is refused at load")
        except manifest.ManifestError:
            check(True, f"config {cfg['name']}: an option its reference does not render is refused at load")
    for cell in doc["workloads"]:
        mix = manifest.load_traffic(cell["traffic"])
        check(mix["loop"] == "closed" and mix["in_flight"] > 0,
              f"traffic {cell['traffic']}: a mix the generator knows")
    for metric in doc["per_layer"]:
        spec = manifest.load_metric(metric["name"])
        check(callable(manifest.load_reader(spec["reader"])) and isinstance(spec["args"], dict),
              f"metric {metric['name']}: reader {spec['reader']} loads")
    bad = dict(doc, per_layer=[dict(doc["per_layer"][0], layer="host decode")] + doc["per_layer"][1:])
    check(any("one token" in e for e in manifest.validate(bad)),
          "a layer with a space in it is refused (PR 22's fault)")
    bad = dict(doc, end_to_end=[dict(doc["end_to_end"][0], unit="images per second")] + doc["end_to_end"][1:])
    check(any("unit" in e for e in manifest.validate(bad)), "a unit with spaces is refused")


def needed_work() -> None:
    # 6000x4000 -> 1600x1066 from the rows [0, 3996.25): 3.75 source samples
    # to an output sample on both axes, 22.5 taps
    w = work.resize_work(6000, 4000, 6000.0, 4000.0 * 1066 / 1067, 1600, 1066)
    tx = 2 * 3.0 * 6000 / 1600
    ty = 2 * 3.0 * (4000.0 * 1066 / 1067) / 1066
    rows_first = 1066 * 6000 * ty + 1066 * 1600 * tx
    cols_first = (4000.0 * 1066 / 1067) * 1600 * tx + 1066 * 1600 * ty
    check(math.isclose(w["flops"], 2 * 3 * min(rows_first, cols_first), rel_tol=1e-12),
          f"resize_work flops by hand: {w['flops']:.4g}")
    check(w["bytes"] == 3 * (6000 * 4000 + 1600 * 1066), f"resize_work bytes by hand: {w['bytes']:.4g}")
    up = work.resize_work(100, 100, 100, 100, 200, 200)
    check(up["flops"] == 2 * 3 * min(200 * 100 * 6 + 200 * 200 * 6, 100 * 200 * 6 + 200 * 200 * 6),
          "an enlargement counts 6 taps an axis")
    peak = work.peaks("TPU v5 lite")
    least = work.least_seconds(w, peak)
    check(least["bound"] == "memory" and math.isclose(least["seconds"], w["bytes"] / 819e9),
          f"24 MP to 1.7 MP is memory-bound: {least['seconds'] * 1e6:.1f} us an image")
    try:
        work.peaks("TPU v9 imaginary")
        check(False, "an unknown device kind is an error")
    except KeyError:
        check(True, "an unknown device kind is an error")


def trace_reduction() -> None:
    planes = manifest.load_json(os.path.join(HERE, "fixtures", "small_trace.json"))
    dev = trace.device_planes(planes)
    check(len(dev) == 1, "one device plane found")
    check(math.isclose(trace.busy_seconds(dev[0]), 1.1), "busy union: 0.4 + 0.1 + 0.6 = 1.1 s")
    seconds, count = trace.module_seconds(dev[0], "^jit_program")
    check(math.isclose(seconds, 1.0) and count == 2, "module time of jit_program: 1.0 s in 2 launches")
    top = trace.top_ops(dev)
    check(top[0][0] == "fusion.1" and math.isclose(top[0][1], 0.65), "heaviest op: fusion.1, 0.65 s")
    gaps = trace.idle_gaps(dev[0])
    check([(round(a / 1e9, 3), round(b / 1e9, 3)) for a, b in gaps] == [(1.4, 1.5), (1.6, 2.0)],
          "idle gaps: 1.4-1.5 and 1.6-2.0")
    marks = [e for p in planes if p not in dev for l in p["lines"] for e in l["events"]]
    named = dict((n, s) for n, s in trace.attribute_gaps(gaps, marks, "in", "out"))
    check(math.isclose(named["all gaps, in"], 0.3) and math.isclose(named["all gaps, out"], 0.2),
          "gap attribution: 0.3 s inside a dispatch, 0.2 s between")
    from perfbench.harness import cell

    timers = {"flyimg_device_seconds": [1, 25.0], "flyimg_x_seconds": [2, 7.0],
              'flyimg_stage_seconds{stage="device"}': [64, 1700.0]}
    timers.update({f"flyimg_phase_{i}_seconds": [1, float(i)] for i in range(7)})
    timers.update({f'flyimg_stage_seconds{{stage="s{i}"}}': [64, 100.0 * i] for i in range(4)})
    busy, breakdown = cell.device_report(planes, 3.0, 51.0, timers)
    idle = breakdown["idle_gaps"]
    check(math.isclose(busy, 1.1) and len(idle) == 10
          and idle[0] == ["window outside the traced slice (profiler off)", 48.0]
          and idle[1][0] == "traced slice, no device op running" and math.isclose(idle[1][1], 1.9)
          and idle[2] == ["flyimg_device_seconds x1", 25.0] and idle[3] == ["flyimg_x_seconds x2", 7.0]
          and [n for n, _ in idle[4:9]] == [f"flyimg_phase_{i}_seconds x1" for i in (6, 5, 4, 3, 2)]
          and idle[9] == ['flyimg_stage_seconds{stage="device"} x64', 1700.0]
          and all(len(n) <= 64 for n, _ in idle),
          "traced run's report: busy 1.1 s of a 3 s slice in a 51 s window; seven per-launch timers by "
          "seconds, then the heaviest per-image stage, every name within 64 characters")
    read = manifest.load_reader("trace_share")
    ctx = {"trace_planes": planes, "counters_before": {"flyimg_device_seconds_sum": 2.0},
           "counters_after": {"flyimg_device_seconds_sum": 12.0, "flyimg_batches_total": 2.0,
                              "flyimg_images_processed_total": 4.0},
           "launch_sizes": {"2": 2}, "device": {"kind": "TPU v5 lite"},
           "work_per_image": {"resample": {"flops": 0.0, "bytes": 819e9 / 100}}}
    check(read(ctx, "launch_idle", "^jit_program") is None,
          "launch_idle: a trace whose program annotates no phases has no hold to read: nothing read")
    phased = dict(ctx, trace_planes=manifest.load_json(os.path.join(HERE, "fixtures", "phase_trace.json")))
    check(math.isclose(read(phased, "launch_idle", "^jit_program"), 100.0 * (1.0 - 0.206 / 0.456)),
          "launch_idle: the module ran 0.206 s of the 0.456 s from the launch's dispatch to the end of its "
          "read-back, whatever the window's counters say: 54.8%")
    kernel = {"work": "resample", "images": "flyimg_images_processed_total"}
    check(math.isclose(read(ctx, "roofline", "^jit_program", **kernel), 100.0 * 0.01 * 2 / 0.6),
          "roofline: of 2 traced launches of 2 images the longer alone, 20 ms needed in 0.6 s of module time: 3.33%")
    lone = dict(ctx, launch_sizes={"1": 1, "2": 2},
                counters_after=dict(ctx["counters_after"], flyimg_images_processed_total=5.0))
    check(math.isclose(read(lone, "roofline", "^jit_program", **kernel), 100.0 * 0.01 * 2 / 0.6),
          "roofline: a lone launch of 1 in the window, traced or not, does not change it")
    check(read(dict(ctx, trace_planes=[]), "roofline", "^jit_program", **kernel) is None,
          "no device plane: the reader reads nothing")
    check(read(ctx, "roofline", "^jit_program", work="scores", images="flyimg_aux_items_total") is None,
          "a kernel the configuration's reference gives no work for: the reader reads nothing")
    recorded = os.path.join(HERE, "fixtures", "recorded_trace.json")
    if os.path.exists(recorded):
        doc = manifest.load_json(recorded)
        dev = trace.device_planes(doc["planes"])
        exp = doc["expected"]
        check(math.isclose(trace.busy_seconds(dev[0]), exp["busy_s"], rel_tol=1e-9),
              f"recorded trace: busy {exp['busy_s']:.6f} s")
        seconds, count = trace.module_seconds(dev[0], exp["module"])
        check(math.isclose(seconds, exp["module_s"], rel_tol=1e-9) and count == exp["module_count"],
              f"recorded trace: {count} launches of {exp['module']}, {exp['module_s']:.6f} s")


def toy_run() -> None:
    from perfbench.harness import cell

    doc = manifest.load_manifest()
    name = doc["workloads"][0]["name"]
    result = cell.run_cell(doc, name, 2**31 + 11, 3.0, True, t_process=T_PROCESS,
                           toy=True, require_chip=False)
    check(result["correct"] and result["failed"] == 0 and result["attempted"] > 0,
          f"toy run of {name} on the CPU is correct: {json.dumps(result['compared'])}")
    device_metrics = [m["name"] for m in doc["per_layer"] if m["source"] == "device_trace"]
    check(not any(m in result["metrics"] for m in device_metrics) and "busy_s" not in result["device"],
          "the CPU run prints no device metric")
    check(all(m in result["metrics"] for m in ("decode_ms", "encode_ms", "images_per_launch")),
          "the CPU run prints the host and counter metrics")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", name, "--seed", "1",
         "--seconds", "1", "--trace", "0"], capture_output=True, text=True, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    check(proc.returncode != 0 and not proc.stdout.strip(),
          "the command refuses to run without an accelerator and prints no result")


def main(argv) -> int:
    files()
    needed_work()
    trace_reduction()
    if "--no-run" not in argv:
        toy_run()
    print(f"{check.failed} check(s) failed")
    return 1 if check.failed else 0


if __name__ == "__main__":
    code = main(sys.argv[1:])
    sys.stdout.flush()
    os._exit(code)
