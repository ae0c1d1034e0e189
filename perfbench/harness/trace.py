"""Reduction of a profiler trace to numbers: device-busy union, device time
of named XLA modules, the heaviest device operations, the longest idle gaps
by what the host was doing in them, and the launches the program annotates
on the host, each with its hold of the device and its place in the slice.

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
# the program opens every phase of a transform launch as a host annotation of
# this name (``runtime/batcher.py`` ``_Launch.annotate``); the harness opens
# one of its own over the traced slice (``cell.trace_one_launch``)
LAUNCH_PHASE = re.compile(r"^flyimg:batch:(\d+):([a-z0-9_]+)$")
SLICE_MARK = "perfbench:slice"


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


def modules(plane: Plane, pattern: str) -> List[List[Any]]:
    """The XLA modules that ran on this device and whose name matches
    ``pattern``, one event a run."""
    rx = re.compile(pattern)
    return [e for e in _line(plane, MODULES_LINE) if rx.search(e[0])]


def module_seconds(plane: Plane, pattern: str) -> Tuple[float, int]:
    """Total device seconds, and the count, of the XLA modules whose name
    matches ``pattern``."""
    hits = modules(plane, pattern)
    return sum(e[2] for e in hits) / 1e9, len(hits)


def longest_module(plane: Plane, pattern: str) -> Optional[List[Any]]:
    """The XLA module matching ``pattern`` that ran longest on this device:
    of the launches a slice holds, the full one's."""
    return max(modules(plane, pattern), key=lambda e: e[2], default=None)


def host_events(planes: Sequence[Plane]) -> List[List[Any]]:
    """Every event kept of the planes that are no device's."""
    return [e for p in planes if not DEVICE_PLANE.match(p["name"])
            for line in p["lines"] for e in line["events"]]


def launch_phases(planes: Sequence[Plane]) -> Dict[int, Dict[str, Interval]]:
    """The phases the program annotated on the host, by launch:
    ``{seq: {phase: (start_ns, end_ns)}}``. An annotation that began before
    the profiler was on is not in the trace."""
    out: Dict[int, Dict[str, Interval]] = {}
    for name, start, duration in host_events(planes):
        m = LAUNCH_PHASE.match(name)
        if m:
            out.setdefault(int(m.group(1)), {})[m.group(2)] = (start, start + duration)
    return out


def launch_holds(planes: Sequence[Plane]) -> Dict[int, Interval]:
    """For each launch the trace holds whole, the interval in which the
    program held the device for it: the start of its ``dispatch`` annotation
    to the end of its ``d2h``, which is what the program's own
    ``flyimg_device_seconds`` spans (``batcher._Launch.device_s``)."""
    return {seq: (phases["dispatch"][0], phases["d2h"][1])
            for seq, phases in launch_phases(planes).items()
            if "dispatch" in phases and "d2h" in phases}


def slice_margins(planes: Sequence[Plane]) -> List[Dict[str, Any]]:
    """Where each launch sits in the traced slice, on the trace's own clock:
    seconds from the slice's opening (the start of the harness's
    ``SLICE_MARK`` annotation) to the start of the launch's staging call
    (``h2d``), seconds from the end of its read-back (``d2h``) to the slice's
    end, and its hold. The launch held longest, the full one, comes first.
    A launch whose staging began before the profiler was on has no ``h2d``
    in the trace and reads None there. No mark in the trace: nothing."""
    mark = next(((s, s + d) for name, s, d in host_events(planes) if name == SLICE_MARK), None)
    if mark is None:
        return []
    phases = launch_phases(planes)
    rows = []
    for seq, (a, b) in launch_holds(planes).items():
        staged = phases[seq].get("h2d")
        rows.append({"seq": seq, "hold_s": (b - a) / 1e9,
                     "staged_after_open_s": None if staged is None else (staged[0] - mark[0]) / 1e9,
                     "readback_before_end_s": (mark[1] - b) / 1e9})
    return sorted(rows, key=lambda r: -r["hold_s"])


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
