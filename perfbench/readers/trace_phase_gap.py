"""Milliseconds, on the profiler's clock, from the end of a launch's work on
the device to the end of a phase the program annotates on the host.

The program opens each phase of a launch as a profiler annotation named
``flyimg:batch:<seq>:<phase>`` on the thread that runs it, over the same
interval as the phase's span and timer (``runtime/batcher.py`` ``_Launch``),
so the profiler's trace carries the program's phases on the device trace's
clock. This reader takes the host events whose name matches ``host_event``
and, for each, the traced XLA module matching ``module`` that ended last
before the event did; it reports the mean of (end of the event - end of that
module). With ``host_event`` the read-back's annotation, that is the
read-back as the profiler has it, and it should agree with the program's own
d2h timer: the cross-check that the two clocks are bridged.

Nothing to read (no device plane, no such module, no such annotation: a CPU
run, or a program that annotates no phases): nothing read."""

import re

from perfbench.harness import trace


def read(ctx, module, host_event, scale=1e-6):
    planes = ctx.get("trace_planes") or []
    device = trace.device_planes(planes)
    module_rx, host_rx = re.compile(module), re.compile(host_event)
    module_ends = sorted(
        start + duration
        for plane in device for line in plane["lines"]
        if line["name"] == trace.MODULES_LINE
        for name, start, duration in line["events"] if module_rx.search(name))
    phase_ends = [
        start + duration
        for plane in planes if plane not in device for line in plane["lines"]
        for name, start, duration in line["events"] if host_rx.search(name)]
    gaps = []
    for end in phase_ends:
        before = [m for m in module_ends if m <= end]
        if before:
            gaps.append(end - before[-1])
    if not gaps:
        return None
    return scale * sum(gaps) / len(gaps)
