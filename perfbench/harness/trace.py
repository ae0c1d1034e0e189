"""Reduction of a profiler trace to numbers: device-busy union, device time
of named XLA modules, the heaviest device operations, and the longest idle
gaps by what the host was doing in them.

The functions work on a plain structure, so that they can be checked on a
small recorded trace kept as JSON (``fixtures/``):

    [{"name": plane, "lines": [{"name": line, "events": [[name, start_ns, dur_ns], ...]}]}]

``load_xplane`` makes that structure from the ``.xplane.pb`` the JAX
profiler writes, with nothing but JAX.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

Plane = Dict[str, Any]
Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:[A-Za-z]+:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
LAYOUT = re.compile(r"\{[^{}]*\}")


def find_xplane(log_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load_xplane(path: str, keep_host: Optional[re.Pattern] = None) -> List[Plane]:
    """``.xplane.pb`` -> planes. Device planes are kept whole; of the host's
    threads only events whose name matches ``keep_host`` are kept (a traced
    window holds millions of host events and the reduction needs few)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes: List[Plane] = []
    for plane in data.planes:
        is_device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            events = []
            for ev in line.events:
                if is_device or (keep_host is not None and keep_host.search(ev.name)):
                    events.append([ev.name, float(ev.start_ns), float(ev.duration_ns)])
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return planes


def device_planes(planes: Sequence[Plane]) -> List[Plane]:
    return [p for p in planes if DEVICE_PLANE.match(p["name"])]


def _line(plane: Plane, name: str) -> List[List[Any]]:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def op_events(plane: Plane) -> List[List[Any]]:
    """The device's operations: the ops line, or the modules where a backend
    writes no ops line."""
    return _line(plane, OPS_LINE) or _line(plane, MODULES_LINE)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def busy_seconds(plane: Plane) -> float:
    """Seconds in which an operation ran on the device: the union of the
    operations' intervals, so that overlapping events count once."""
    spans = union((e[1], e[1] + e[2]) for e in op_events(plane))
    return sum(b - a for a, b in spans) / 1e9


def module_seconds(plane: Plane, pattern: str) -> Tuple[float, int]:
    """Total device seconds, and the count, of the XLA modules whose name
    matches ``pattern``."""
    rx = re.compile(pattern)
    hits = [e for e in _line(plane, MODULES_LINE) if rx.search(e[0])]
    return sum(e[2] for e in hits) / 1e9, len(hits)


def top_ops(planes: Sequence[Plane], n: int = 10) -> List[List[Any]]:
    total: Dict[str, float] = {}
    for plane in planes:
        for name, _, dur in op_events(plane):
            # an HLO op's event is named by its whole instruction; its name
            # and result type are enough to find it again, and without the
            # layouts both are whole within what the ledger keeps of a name
            name = LAYOUT.sub("", name.split(" fusion(")[0].split(" copy(")[0])[:96]
            total[name] = total.get(name, 0.0) + dur / 1e9
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, seconds] for name, seconds in ranked]


def idle_gaps(plane: Plane, span: Optional[Interval] = None) -> List[Interval]:
    """The intervals in which no operation ran, within ``span`` (or between
    the first and the last operation)."""
    busy = union((e[1], e[1] + e[2]) for e in op_events(plane))
    if not busy and span is None:
        return []
    lo, hi = span if span is not None else (busy[0][0], busy[-1][1])
    gaps: List[Interval] = []
    cursor = lo
    for a, b in busy:
        if a > cursor:
            gaps.append((cursor, min(a, hi)))
        cursor = max(cursor, b)
    if cursor < hi:
        gaps.append((cursor, hi))
    return [(a, b) for a, b in gaps if b > a]


def attribute_gaps(gaps: Sequence[Interval], host_events: Sequence[Sequence[Any]],
                   inside: str, outside: str, n: int = 10) -> List[List[Any]]:
    """Split each idle gap by whether a host event (a launch's dispatch
    annotation) covers it, and sum the seconds under the two names; the
    longest single gaps follow, each named by where most of it fell."""
    marks = union((e[1], e[1] + e[2]) for e in host_events)
    totals = {inside: 0.0, outside: 0.0}
    singles: List[Tuple[float, str]] = []
    for a, b in gaps:
        covered = sum(max(0.0, min(b, y) - max(a, x)) for x, y in marks)
        totals[inside] += covered / 1e9
        totals[outside] += (b - a - covered) / 1e9
        label = inside if covered * 2 > (b - a) else outside
        singles.append(((b - a) / 1e9, label))
    out = [[f"all gaps, {name}", seconds] for name, seconds in totals.items()]
    singles.sort(reverse=True)
    for i, (seconds, label) in enumerate(singles[: max(n - len(out), 0)]):
        out.append([f"gap {i + 1}, {label}", seconds])
    return out
