#!/usr/bin/env python3
"""Several runs of one cell in one call, each a process of its own, with the
spread of every metric as the contract measures it (distance between the
first and third quartile by ``statistics.quantiles(n=4)``, as a share of the
median).

    python3 perfbench/prove.py --workload W --seeds 11,12,13 [--sets 2]
        [--seconds S] [--trace 0|1] [--out chiprun_out/W]

This parent never imports JAX: a chip belongs to one process at a time.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def spread(values):
    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q3 - q1) / med if med else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--sets", type=int, default=1, help="repeat the seeds this many times")
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", default=None)
    ns = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        doc = json.load(fh)
    seconds = ns.seconds if ns.seconds is not None else doc["run_seconds"]
    out_dir = os.path.join(ROOT, ns.out or os.path.join("chiprun_out", ns.workload))
    os.makedirs(out_dir, exist_ok=True)
    seeds = [int(s) for s in ns.seeds.split(",")]
    sets = []
    bad = 0
    for set_no in range(ns.sets):
        rows = []
        for seed in seeds:
            cmd = doc["command"] + ["--workload", ns.workload, "--seed", str(seed),
                                    "--seconds", str(seconds), "--trace", str(ns.trace)]
            t = time.time()
            proc = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
            wall = time.time() - t
            tag = f"set{set_no}_seed{seed}_trace{ns.trace}"
            with open(os.path.join(out_dir, tag + ".out"), "w") as fh:
                fh.write(proc.stdout)
            with open(os.path.join(out_dir, tag + ".err"), "w") as fh:
                fh.write(proc.stderr)
            lines = [l for l in proc.stdout.splitlines() if l.strip()]
            result = None
            if proc.returncode == 0 and lines:
                try:
                    result = json.loads(lines[-1])
                except ValueError:
                    result = None
            notes = [l for l in proc.stderr.splitlines() if l.startswith("#")]
            print(f"== {tag}: rc {proc.returncode}, {wall:.0f} s wall")
            for l in notes:
                print("   ", l)
            if result is None:
                bad += 1
                print("    NO RESULT; stderr tail:", proc.stderr[-1500:])
                continue
            if not result["correct"] or result["failed"]:
                bad += 1
            print("    correct", result["correct"], "attempted", result["attempted"],
                  "failed", result["failed"], "device", json.dumps(result["device"]))
            print("    metrics", json.dumps({k: v["value"] for k, v in result["metrics"].items()}))
            if "breakdown" in result:
                print("    breakdown", json.dumps(result["breakdown"]))
            rows.append(result)
        sets.append(rows)
    names = sorted({k for rows in sets for r in rows for k in r["metrics"]})
    print("\nmetric: per set median, spread (IQR/median); first runs of set 0 included")
    for name in names:
        parts = []
        for rows in sets:
            vals = [r["metrics"][name]["value"] for r in rows if name in r["metrics"]]
            sp = spread(vals)
            parts.append(f"median {statistics.median(vals):.6g} spread "
                         f"{'n/a' if sp is None else format(100 * sp, '.2f') + '%'} "
                         f"[{', '.join(format(v, '.5g') for v in vals)}]" if vals else "none")
        print(f"  {name}: " + " | ".join(parts))
    print("runs not correct or without result:", bad)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
