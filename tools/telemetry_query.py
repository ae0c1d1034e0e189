#!/usr/bin/env python3
"""Query the telemetry warehouse from disk alone.

The archive (runtime/telemetry.py; docs/observability.md "Telemetry
warehouse & traffic-mix classifier") is append-only JSONL — this tool
is the offline half of the round trip: everything it prints is
reconstructed purely from segment files, with no live process, so a
restarted (or dead) replica's telemetry is still fully queryable.

Subcommands:

- ``windows``       — the window-record timeline (one line per snapshot
                      beat: mix label, burn, brownout level, deltas)
- ``mix-report``    — traffic-mix dwell report: which labels the
                      classifier adopted, for how many windows, plus a
                      re-classification of each stored feature vector
                      through the SAME centroid table the live process
                      used (proving labels are reproducible from disk)
- ``burn-timeline`` — SLO burn-rate timeline (fast/slow normalized
                      burn + brownout level per window) for incident
                      reconstruction
- ``export``        — concatenate segments into one JSONL stream
                      (optionally filtered by --kind)

Usage:
    python tools/telemetry_query.py windows var/tmp/telemetry
    python tools/telemetry_query.py mix-report var/tmp/telemetry --json
    python tools/telemetry_query.py burn-timeline var/tmp/telemetry
    python tools/telemetry_query.py export var/tmp/telemetry \\
        --kind window --out /tmp/archive.jsonl
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

from flyimg_tpu.runtime.telemetry import (  # noqa: E402
    TrafficMixClassifier,
    read_archive,
)


def _load(directory: str, kinds=None) -> Dict:
    doc = read_archive(directory, kinds=kinds)
    if not doc["segments"]:
        print(f"no telemetry segments under {directory}", file=sys.stderr)
        raise SystemExit(2)
    return doc


def _fmt(value, width: int = 7) -> str:
    if value is None:
        return "-".rjust(width)
    if isinstance(value, float):
        return f"{value:.3f}".rjust(width)
    return str(value).rjust(width)


def cmd_windows(args) -> int:
    doc = _load(args.directory, kinds=("window",))
    rows = doc["records"]
    if args.json:
        print(json.dumps({"windows": rows, "torn": doc["torn"],
                          "segments": doc["segments"]}, indent=1))
        return 0
    print(f"{len(rows)} windows across {len(doc['segments'])} segments"
          f" ({doc['torn']} torn lines skipped)")
    header = (f"{'at_s':>12} {'mix':>10} {'raw':>10} {'burn_f':>7} "
              f"{'burn_s':>7} {'lvl':>4} {'req':>6} {'hit':>5} "
              f"{'miss':>5} {'degr':>5}")
    print(header)
    for rec in rows:
        print(f"{_fmt(rec.get('at_s'), 12)} "
              f"{str(rec.get('mix') or '-'):>10} "
              f"{str(rec.get('mix_raw') or '-'):>10} "
              f"{_fmt(rec.get('burn_fast_norm'))} "
              f"{_fmt(rec.get('burn_slow_norm'))} "
              f"{_fmt(rec.get('brownout_level'), 4)} "
              f"{_fmt(rec.get('requests_delta'), 6)} "
              f"{_fmt(rec.get('hits_delta'), 5)} "
              f"{_fmt(rec.get('misses_delta'), 5)} "
              f"{_fmt(rec.get('degraded_delta'), 5)}")
    return 0


def cmd_mix_report(args) -> int:
    doc = _load(args.directory, kinds=("window",))
    rows = doc["records"]
    dwell: Dict[str, int] = {}
    flips: List[Dict] = []
    reclassified = 0
    mismatches = 0
    previous = None
    for rec in rows:
        label = rec.get("mix")
        if label:
            dwell[label] = dwell.get(label, 0) + 1
            if previous is not None and label != previous:
                flips.append({"at_s": rec.get("at_s"),
                              "from": previous, "to": label})
            previous = label
        # reproducibility proof: the stored feature vector must map to
        # the stored RAW label through the shipped centroid table
        features = rec.get("mix_features")
        raw = rec.get("mix_raw")
        if features and raw:
            reclassified += 1
            label2, _dist = TrafficMixClassifier.nearest(features)
            if label2 != raw:
                mismatches += 1
    report = {
        "windows": len(rows),
        "dwell_windows": dwell,
        "transitions": flips,
        "reclassified": reclassified,
        "reclassify_mismatches": mismatches,
        "labels_seen": sorted(dwell),
        "torn": doc["torn"],
    }
    if args.json:
        print(json.dumps(report, indent=1))
        return 0 if mismatches == 0 else 1
    print(f"{len(rows)} windows, labels adopted: "
          + (", ".join(f"{k}×{v}" for k, v in sorted(dwell.items()))
             or "(none)"))
    for flip in flips:
        print(f"  flip @ {flip['at_s']}: {flip['from']} -> {flip['to']}")
    print(f"centroid reproducibility: {reclassified - mismatches}/"
          f"{reclassified} stored feature vectors re-map to their "
          f"stored raw label")
    return 0 if mismatches == 0 else 1


def cmd_burn_timeline(args) -> int:
    doc = _load(args.directory, kinds=("window",))
    rows = [
        {
            "at_s": rec.get("at_s"),
            "burn_fast_norm": rec.get("burn_fast_norm"),
            "burn_slow_norm": rec.get("burn_slow_norm"),
            "brownout_level": rec.get("brownout_level"),
            "mix": rec.get("mix"),
            "slo": rec.get("slo"),
        }
        for rec in doc["records"]
    ]
    if args.json:
        print(json.dumps({"timeline": rows}, indent=1))
        return 0
    print(f"{'at_s':>12} {'burn_fast':>9} {'burn_slow':>9} "
          f"{'level':>5}  mix")
    for rec in rows:
        print(f"{_fmt(rec['at_s'], 12)} {_fmt(rec['burn_fast_norm'], 9)} "
              f"{_fmt(rec['burn_slow_norm'], 9)} "
              f"{_fmt(rec['brownout_level'], 5)}  {rec['mix'] or '-'}")
    return 0


def cmd_export(args) -> int:
    kinds = tuple(args.kind) if args.kind else None
    doc = _load(args.directory, kinds=kinds)
    out = open(args.out, "w", encoding="utf-8") if args.out else sys.stdout
    try:
        for rec in doc["records"]:
            out.write(json.dumps(rec, separators=(",", ":")) + "\n")
    finally:
        if args.out:
            out.close()
    print(f"exported {len(doc['records'])} records "
          f"({doc['torn']} torn lines skipped) from "
          f"{len(doc['segments'])} segments"
          + (f" -> {args.out}" if args.out else ""),
          file=sys.stderr)
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    sub = parser.add_subparsers(dest="command", required=True)

    p_windows = sub.add_parser(
        "windows", help="window-record timeline (one line per beat)"
    )
    p_windows.set_defaults(fn=cmd_windows)
    p_mix = sub.add_parser(
        "mix-report",
        help="traffic-mix dwell/transition report + centroid "
             "reproducibility check",
    )
    p_mix.set_defaults(fn=cmd_mix_report)
    p_burn = sub.add_parser(
        "burn-timeline", help="SLO burn + brownout level per window"
    )
    p_burn.set_defaults(fn=cmd_burn_timeline)
    p_export = sub.add_parser(
        "export",
        help="concatenate segments to one JSONL stream",
    )
    p_export.add_argument(
        "--kind", action="append",
        choices=["boot", "window", "launch"],
        help="only these record kinds (repeatable; default all)",
    )
    p_export.add_argument("--out", help="output path (default stdout)")
    p_export.set_defaults(fn=cmd_export)

    for p in (p_windows, p_mix, p_burn, p_export):
        p.add_argument("directory", help="telemetry archive directory")
    for p in (p_windows, p_mix, p_burn):
        p.add_argument("--json", action="store_true",
                       help="machine-readable output")

    args = parser.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    raise SystemExit(main())
