"""Offline autotuner replay: propose a policy table from recorded
trajectories without touching a live process (docs/autotuning.md
"Offline replay").

Feeds recorded signal windows through the SAME ``DecisionEngine`` the
online tuner runs (``flyimg_tpu/runtime/autotuner.py`` — pure,
clock-free, deterministic), so the proposals here are exactly the
adjustments a live process would have made on that traffic:

    python -m tools.autotune_replay --history rows.jsonl  # bench rows
    python -m tools.autotune_replay --flightrecorder dump.json
    python -m tools.autotune_replay --telemetry var/tmp/telemetry
    python -m tools.autotune_replay --out-dir /tmp/autotune

Inputs:

- a bench trajectory (``--history``, one bench JSON row per line; none
  is tracked in the repo, so the default path only exists if you keep
  one there): rows are loaded through the tolerant trajectory schema
  (``tools/bench_history.py``). Rows that embed ``batch_efficiency`` columns
  (bench_http rows, PR 7+) drive controller decisions directly;
  headline-only rows contribute to the throughput trend.
- a flight-recorder dump (``--flightrecorder``): per-launch records are
  re-aggregated into rolling per-controller windows with the same math
  as ``BatchEfficiency.stats``, then replayed window by window.
- a telemetry archive (``--telemetry``, a segment directory or a
  ``telemetry_query export`` JSONL file; runtime/telemetry.py): window
  records embed the live SignalWindow's ``controllers``/``host``/
  ``kernel_mode`` verbatim, so they replay with full fidelity — the
  ROADMAP item-4 planner input. Archives with only launch records fall
  back to the flight-recorder re-aggregation math.

Outputs (``--out-dir``, default ``var/tmp/autotune`` — never a tracked
file):

- ``proposal.json``: the proposed policy table (boot policy, proposed
  values, per-decision audit trail mirroring /debug/autotune history).
- ``perf_baseline_candidate.json``: the current
  ``benchmarks/perf_baseline.json`` annotated with the proposal and the
  replayed throughput trend — a CANDIDATE an operator reviews and
  promotes via ``tools/perf_gate.py --update``, never an automatic
  baseline swap.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

from flyimg_tpu.runtime.autotuner import (  # noqa: E402
    ENVELOPES,
    DecisionEngine,
    default_envelopes,
)
from tools.bench_history import (  # noqa: E402
    DEFAULT_PATH as HISTORY_PATH,
    check_row,
    load_rows,
    repair_row,
)

from flyimg_tpu.appconfig import SERVER_DEFAULTS  # noqa: E402

#: the replayed boot policy, READ from the appconfig defaults (the one
#: source of truth) so a default flip shows up in replay proposals
#: immediately instead of silently desynchronizing
BOOT_POLICY: Dict[str, float] = {
    "device.max_batch": float(SERVER_DEFAULTS["batch_max_size"]),
    "device.deadline_ms": float(SERVER_DEFAULTS["batch_deadline_ms"]),
    "codec.max_batch": float(SERVER_DEFAULTS["decode_batch_max"]),
    "codec.deadline_ms": float(SERVER_DEFAULTS["decode_deadline_ms"]),
    "host.fetch_workers": float(
        SERVER_DEFAULTS["host_pipeline_fetch_workers"]
    ),
    "host.decode_workers": float(
        SERVER_DEFAULTS["host_pipeline_decode_workers"]
    ),
    "host.encode_workers": float(
        SERVER_DEFAULTS["host_pipeline_encode_workers"]
    ),
    "reuse.min_scale": float(SERVER_DEFAULTS["reuse_min_scale"]),
    # the auto threshold's default is the module's shipped 1.0 (it has
    # no appconfig knob: the autotuner is its only writer)
    "resample.auto_band_frac": 1.0,
}


def _history_windows(path: str) -> List[Dict]:
    """Signal windows from the bench trajectory. Every valid-or-repaired
    row yields one window; rows embedding batch_efficiency columns give
    the engine controller evidence, the rest replay as neutral windows
    (no evidence -> no adjustment, exactly like a quiet live period)."""
    windows: List[Dict] = []
    for _lineno, row, parse_error in load_rows(path):
        if parse_error is not None:
            continue
        if check_row(row):
            row = repair_row(row) if isinstance(row, dict) else None
            if row is None:
                continue
        assert isinstance(row, dict)
        signals: Dict = {"controllers": {}, "host": {}}
        eff = row.get("batch_efficiency")
        if isinstance(eff, dict):
            for ctrl, stats in eff.items():
                if isinstance(stats, dict):
                    signals["controllers"][str(ctrl)] = stats
        signals["kernel_mode"] = (
            "auto" if row.get("kernel") == "auto" else
            str(row.get("kernel") or "dense")
        )
        signals["_row"] = {
            "metric": row.get("metric") or row.get("error"),
            "value": row.get("value"),
            "ts": row.get("ts"),
        }
        windows.append(signals)
    return windows


def _flight_windows(path: str, window: int = 64) -> List[Dict]:
    """Signal windows from a flight-recorder dump: chunk the launch
    records and re-aggregate each chunk per controller with the
    BatchEfficiency math (occupancy, queue-wait share, compile
    amortization)."""
    with open(path, "r", encoding="utf-8") as fh:
        doc = json.load(fh)
    records = [
        r for r in doc.get("records", [])
        if isinstance(r, dict) and r.get("kind") != "host_stage"
    ]
    return _aggregate_launch_windows(records, window=window)


def _aggregate_launch_windows(records: List[Dict],
                              window: int = 64) -> List[Dict]:
    windows: List[Dict] = []
    for start in range(0, len(records), max(window, 1)):
        chunk = records[start:start + window]
        per_ctrl: Dict[str, List[dict]] = {}
        for rec in chunk:
            per_ctrl.setdefault(str(rec.get("controller")), []).append(rec)
        controllers: Dict[str, Dict] = {}
        for ctrl, rows in per_ctrl.items():
            images = sum(int(r.get("occupancy") or 0) for r in rows)
            slots = sum(int(r.get("capacity") or 0) for r in rows)
            queue = sum(float(r.get("queue_wait_s") or 0.0) for r in rows)
            device = sum(float(r.get("device_s") or 0.0) for r in rows)
            compiled = [
                r.get("compile_hit") for r in rows
                if r.get("compile_hit") is not None
            ]
            misses = sum(1 for hit in compiled if not hit)
            occupancy = images / slots if slots else 0.0
            controllers[ctrl] = {
                "window_batches": len(rows),
                "mean_occupancy": occupancy,
                "padding_waste": 1.0 - occupancy if slots else 0.0,
                "queue_wait_share": (
                    queue / (queue + device) if (queue + device) > 0
                    else 0.0
                ),
                "batches_per_compile_miss": (
                    len(compiled) / misses if misses
                    else float(len(compiled))
                ),
            }
        windows.append({
            "controllers": controllers,
            "host": {},
            "kernel_mode": "dense",
        })
    return windows


def _telemetry_windows(path: str) -> List[Dict]:
    """Signal windows from a telemetry archive (runtime/telemetry.py):
    ``path`` is a segment directory or an exported JSONL file
    (``tools/telemetry_query.py export``). Archive WINDOW records embed
    the live SignalWindow assembly verbatim and replay with full
    fidelity (mix label carried through to the audit trail); an archive
    holding only LAUNCH records re-aggregates them with the
    flight-recorder math above."""
    from flyimg_tpu.runtime.telemetry import read_archive

    if os.path.isdir(path):
        records = read_archive(path)["records"]
    else:
        records = []
        with open(path, "r", encoding="utf-8") as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if isinstance(rec, dict):
                    records.append(rec)
    windows: List[Dict] = []
    for rec in records:
        if rec.get("kind") != "window":
            continue
        controllers = rec.get("controllers")
        signals: Dict = {
            "controllers": controllers if isinstance(controllers, dict)
            else {},
            "host": rec.get("host") if isinstance(rec.get("host"), dict)
            else {},
            "kernel_mode": str(rec.get("kernel_mode") or "dense"),
            "burn_fast_norm": rec.get("burn_fast_norm"),
            "burn_slow_norm": rec.get("burn_slow_norm"),
            "_row": {
                "metric": f"telemetry_window:{rec.get('mix') or 'mixed'}",
                "value": None,
                "ts": rec.get("at_s"),
            },
        }
        windows.append(signals)
    if windows:
        return windows
    launches = [
        r for r in records
        if r.get("kind") == "launch" and r.get("launch_kind") != "host_stage"
    ]
    return _aggregate_launch_windows(launches)


def replay(windows: List[Dict],
           envelopes=None) -> Dict[str, object]:
    """Run the decision engine over the windows, maintaining the policy
    table the way the live tuner would (one bounded adjustment per
    window; no freeze/revert — the replay proposes, the operator
    judges)."""
    engine = DecisionEngine()
    envelopes = envelopes or dict(ENVELOPES)
    policy = dict(BOOT_POLICY)
    decisions: List[Dict] = []
    throughput: List[float] = []
    for i, signals in enumerate(windows):
        row = signals.get("_row") or {}
        value = row.get("value")
        if isinstance(value, (int, float)) and not isinstance(value, bool):
            throughput.append(float(value))
        proposal = engine.propose(signals, policy, envelopes)
        if proposal is None:
            continue
        frm = policy[proposal.knob]
        policy[proposal.knob] = proposal.target
        decisions.append({
            "window": i,
            "knob": proposal.knob,
            "from": frm,
            "to": proposal.target,
            "direction": proposal.direction,
            "reason": proposal.reason,
        })
    proposed = {
        knob: value for knob, value in policy.items()
        if value != BOOT_POLICY[knob]
    }
    return {
        "windows": len(windows),
        "decisions": decisions,
        "boot_policy": dict(BOOT_POLICY),
        "proposed_policy": policy,
        "changed_knobs": proposed,
        "throughput_trend": {
            "samples": len(throughput),
            "first": throughput[0] if throughput else None,
            "last": throughput[-1] if throughput else None,
            "best": max(throughput) if throughput else None,
        },
    }


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(prog="autotune_replay")
    parser.add_argument(
        "--history", default=HISTORY_PATH,
        help="bench_history.jsonl trajectory to replay",
    )
    parser.add_argument(
        "--flightrecorder", default=None,
        help="replay a flight-recorder dump instead of the bench history",
    )
    parser.add_argument(
        "--telemetry", default=None,
        help="replay a telemetry archive (segment directory or exported "
             "JSONL) instead of the bench history",
    )
    parser.add_argument(
        "--baseline",
        default=os.path.join(REPO_ROOT, "benchmarks", "perf_baseline.json"),
    )
    parser.add_argument(
        "--out-dir",
        default=os.path.join(REPO_ROOT, "var", "tmp", "autotune"),
    )
    args = parser.parse_args(argv)

    if args.telemetry:
        windows = _telemetry_windows(args.telemetry)
        source = args.telemetry
    elif args.flightrecorder:
        windows = _flight_windows(args.flightrecorder)
        source = args.flightrecorder
    else:
        windows = _history_windows(args.history)
        source = args.history
    result = replay(windows)
    result["source"] = source
    result["envelopes"] = {
        name: {"lo": env.lo, "hi": env.hi, "step": env.step}
        for name, env in default_envelopes().items()
    }

    os.makedirs(args.out_dir, exist_ok=True)
    proposal_path = os.path.join(args.out_dir, "proposal.json")
    with open(proposal_path, "w", encoding="utf-8") as fh:
        json.dump(result, fh, indent=1)
        fh.write("\n")

    candidate_path = os.path.join(
        args.out_dir, "perf_baseline_candidate.json"
    )
    try:
        with open(args.baseline, "r", encoding="utf-8") as fh:
            baseline = json.load(fh)
    except (OSError, ValueError) as exc:
        print(f"cannot read baseline {args.baseline}: {exc}",
              file=sys.stderr)
        return 1
    baseline["autotune_candidate"] = {
        "source": source,
        "windows": result["windows"],
        "proposed_policy": result["proposed_policy"],
        "changed_knobs": result["changed_knobs"],
        "throughput_trend": result["throughput_trend"],
        "note": (
            "CANDIDATE only — review the proposal, apply the knobs to "
            "the serving params, re-measure, then refresh the real "
            "baseline via tools/perf_gate.py --update "
            "(benchmarks/README.md refresh policy)"
        ),
    }
    with open(candidate_path, "w", encoding="utf-8") as fh:
        json.dump(baseline, fh, indent=1)
        fh.write("\n")

    print(
        f"replayed {result['windows']} windows from {source}: "
        f"{len(result['decisions'])} in-envelope adjustments, "
        f"{len(result['changed_knobs'])} knobs moved"
    )
    print(f"proposal: {proposal_path}")
    print(f"candidate baseline: {candidate_path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
