"""bench_history.jsonl validation: a tolerant schema for a heterogeneous
trajectory (ISSUE 14 satellite; docs/autotuning.md "Offline replay").

A bench trajectory holds one JSON line per bench run (``bench.py`` /
``tools/bench_http.py`` rows plus a ``ts``); none is tracked in the repo
since PR 21, so pass ``--path``. Rows from different eras carry different
columns: old rows have no ``kernel`` tag, no ``reuse_enable``, no
decode-mode columns, ``backend`` where newer ones stamp ``platform`` /
``device_kind`` / ``device_count``, and failure rows carry ``error``
with a null ``value``. Anything consuming a WHOLE trajectory (the autotuner's
offline replay, future dashboards) needs one contract for what a row
may look like; this tool is that contract, machine-checked:

    python -m tools.bench_history validate
    python -m tools.bench_history validate --repair-to /tmp/clean.jsonl

**Schema (tolerant by design):** a row must be a JSON object with
- ``ts``: number (epoch seconds) — repairable when missing (monotonic
  interpolation from neighbors, flagged);
- at least one of ``metric`` (str) or ``error`` (str) — which run this
  was, or why it failed;
- ``value``: number or null when present;
- era tags OPTIONAL with pinned types when present: ``kernel`` (str),
  ``backend`` (str|null), ``unit`` (str), ``vs_baseline`` (number|null),
  ``reuse_enable`` (bool), ``saturated`` (bool).
Unknown extra fields are always allowed (future eras add columns).

**Repair-or-flag:** ``--repair-to`` writes a cleaned trajectory —
numeric strings coerced, missing ``ts`` interpolated, rows beyond
repair DROPPED and flagged on stderr. Exit code 0 = every row valid or
repaired; 1 = at least one unrepairable row (without --repair-to, any
invalid row exits 1).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_PATH = os.path.join(REPO_ROOT, "benchmarks", "bench_history.jsonl")

#: optional fields with pinned types WHEN PRESENT (None in the tuple =
#: null allowed). Absence is always fine — that's what "tolerant" means
#: for a trajectory spanning eras.
OPTIONAL_FIELDS: Dict[str, Tuple[type, ...]] = {
    "unit": (str,),
    "backend": (str, type(None)),
    "kernel": (str,),
    "vs_baseline": (int, float, type(None)),
    "reuse_enable": (bool,),
    "saturated": (bool,),
}


def _coerce_number(value) -> Optional[float]:
    """Repair path: a numeric string becomes its number; anything else
    non-numeric is unrepairable (returns None for null-like inputs)."""
    if value is None or isinstance(value, bool):
        return None
    if isinstance(value, (int, float)):
        return float(value)
    if isinstance(value, str):
        try:
            return float(value)
        except ValueError:
            return None
    return None


def check_row(row: object) -> List[str]:
    """Issues with one parsed row under the tolerant schema (empty list
    = valid). Pure — the replay tool and tests call this directly."""
    issues: List[str] = []
    if not isinstance(row, dict):
        return ["row is not a JSON object"]
    metric = row.get("metric")
    error = row.get("error")
    if not isinstance(metric, str) and not isinstance(error, str):
        issues.append("neither `metric` (str) nor `error` (str) present")
    ts = row.get("ts")
    if ts is None:
        issues.append("missing `ts` (repairable: interpolated)")
    elif not isinstance(ts, (int, float)) or isinstance(ts, bool):
        issues.append(f"`ts` must be a number, got {type(ts).__name__}")
    if "value" in row:
        value = row["value"]
        if value is not None and (
            isinstance(value, bool) or not isinstance(value, (int, float))
        ):
            issues.append(
                f"`value` must be a number or null, got "
                f"{type(value).__name__}"
            )
    for field, types in OPTIONAL_FIELDS.items():
        if field in row and not isinstance(row[field], types):
            issues.append(
                f"`{field}` has type {type(row[field]).__name__} "
                f"(expected {'/'.join(t.__name__ for t in types)})"
            )
    return issues


def repair_row(row: dict) -> Optional[dict]:
    """Best-effort repair of one object row; None when unrepairable.
    Repairs: numeric-string ``value``/``vs_baseline``/``ts`` coerced;
    a missing ``ts`` left for the caller's interpolation pass (marked
    with ``_ts_repaired``)."""
    out = dict(row)
    metric = out.get("metric")
    error = out.get("error")
    if not isinstance(metric, str) and not isinstance(error, str):
        return None
    for field in ("value", "vs_baseline"):
        if field in out and not (
            out[field] is None
            or (
                isinstance(out[field], (int, float))
                and not isinstance(out[field], bool)
            )
        ):
            coerced = _coerce_number(out[field])
            if coerced is None and out[field] is not None:
                return None
            out[field] = coerced
    ts = out.get("ts")
    if ts is not None and (
        isinstance(ts, bool) or not isinstance(ts, (int, float))
    ):
        coerced = _coerce_number(ts)
        if coerced is None:
            out.pop("ts", None)
        else:
            out["ts"] = coerced
    if out.get("ts") is None:
        out.pop("ts", None)
        out["_ts_repaired"] = True
    for field, types in OPTIONAL_FIELDS.items():
        if field in out and not isinstance(out[field], types):
            # wrong-typed era tag: drop the tag, keep the row (the tag
            # is optional; a lying tag is worse than an absent one)
            out.pop(field)
    return out


def load_rows(path: str) -> List[Tuple[int, object, Optional[str]]]:
    """(line_number, parsed-or-None, parse-error) per non-empty line."""
    out: List[Tuple[int, object, Optional[str]]] = []
    with open(path, "r", encoding="utf-8") as fh:
        for lineno, line in enumerate(fh, 1):
            line = line.strip()
            if not line:
                continue
            try:
                out.append((lineno, json.loads(line), None))
            except ValueError as exc:
                out.append((lineno, None, f"not JSON: {exc}"))
    return out


def validate(path: str, repair_to: Optional[str] = None,
             as_json: bool = False) -> int:
    try:
        rows = load_rows(path)
    except OSError as exc:
        print(f"cannot read {path}: {exc}", file=sys.stderr)
        return 1
    valid: List[dict] = []
    flagged: List[Dict] = []
    dropped = 0
    for lineno, row, parse_error in rows:
        if parse_error is not None:
            flagged.append({"line": lineno, "issues": [parse_error]})
            dropped += 1
            continue
        issues = check_row(row)
        if not issues:
            valid.append(row)  # type: ignore[arg-type]
            continue
        flagged.append({"line": lineno, "issues": issues})
        repaired = repair_row(row) if isinstance(row, dict) else None
        if repaired is not None:
            valid.append(repaired)
        else:
            dropped += 1
    # interpolate missing timestamps from the nearest stamped neighbors
    # (the trajectory is append-only, so file order IS time order)
    stamped = [r.get("ts") for r in valid]
    known = [
        (i, t) for i, t in enumerate(stamped)
        if isinstance(t, (int, float))
    ]
    for i, row in enumerate(valid):
        if isinstance(row.get("ts"), (int, float)):
            continue
        before = [t for j, t in known if j < i]
        after = [t for j, t in known if j > i]
        if before and after:
            row["ts"] = round((before[-1] + after[0]) / 2.0, 3)
        elif before:
            row["ts"] = before[-1]
        elif after:
            row["ts"] = after[0]
        else:
            row["ts"] = 0.0
    summary = {
        "path": path,
        "rows": len(rows),
        "valid": len(rows) - len(flagged),
        "repaired": len(flagged) - dropped,
        "dropped": dropped,
        "flagged": flagged,
    }
    if as_json:
        print(json.dumps(summary, indent=1))
    else:
        print(
            f"{path}: {summary['rows']} rows — {summary['valid']} valid, "
            f"{summary['repaired']} repaired, {dropped} dropped"
        )
        for item in flagged:
            for issue in item["issues"]:
                print(f"  line {item['line']}: {issue}", file=sys.stderr)
    if repair_to is not None:
        os.makedirs(
            os.path.dirname(os.path.abspath(repair_to)), exist_ok=True
        )
        with open(repair_to, "w", encoding="utf-8") as fh:
            for row in valid:
                fh.write(json.dumps(row) + "\n")
        print(f"repaired trajectory written to {repair_to}")
        return 0 if dropped == 0 else 1
    return 0 if not flagged else 1


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(prog="bench_history")
    sub = parser.add_subparsers(dest="cmd")
    val = sub.add_parser(
        "validate", help="check rows against the tolerant trajectory schema"
    )
    val.add_argument("--path", default=DEFAULT_PATH)
    val.add_argument(
        "--repair-to", default=None,
        help="write a repaired trajectory here (drops unrepairable rows)",
    )
    val.add_argument("--json", action="store_true", dest="as_json")
    args = parser.parse_args(argv)
    if args.cmd != "validate":
        parser.print_help()
        return 2
    return validate(args.path, repair_to=args.repair_to,
                    as_json=args.as_json)


if __name__ == "__main__":
    sys.exit(main())
