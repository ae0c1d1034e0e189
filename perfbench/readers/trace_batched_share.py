"""The share of its roofline at which a batched aux program ran (the face
detector's forward, the face pixelation), for the items its traced launches
really carried.

An aux program pads its batch up a power-of-two ladder, and a padded slot is
no work. What the slice's launches carried is read in two steps, neither of
them "one a launch":

- the **padded batch** of each traced run of the program, from the trace
  itself: the XLA module matching ``module`` ran once a call, and the
  operations inside its interval carry their result and operand types in
  their names. ``shapes`` are regular expressions, each with one group that
  is the batch dimension, tried in order until one matches an operation of
  the run: first the type of the program's own batched parameter where an
  operation names it (``f32[B,128,128,3]{...} %images``; the compiler may
  consume a parameter through a free bitcast, and then none does), then a
  looser one over activations. Of the values one expression finds, ``pick``
  takes the ``commonest`` or the ``largest`` (a program that walks its
  batch in pieces names the pieces more often than the whole). A run whose
  batch no expression finds is left out, its device time with it;
- the **share of those slots that were real**, from the program's counters
  over the window: ``real`` over ``slots`` (sample names of its Prometheus
  exposition: items run and padded items run).

Needed work is that of the kernel ``work`` as the configuration's reference
gives it for ONE item (``references/<name>.py`` ``work``: a view for the
detector, an image for the pixelation), times the slots of the slice, times
the real share; over the device time of the matching modules in the slice.

No device plane, no such module, no operation whose type says the batch, no
such counters (a CPU run; a program or a cell without the path): nothing
read. Never 0."""

import re
from collections import Counter

from perfbench.harness import trace, work as work_mod


def _padded_batch(plane, start, end, shape_rxs, pick):
    names = [name for name, op_start, _ in trace.op_events(plane) if start <= op_start < end]
    for rx in shape_rxs:
        seen = Counter(int(match.group(1)) for name in names for match in rx.finditer(name))
        if seen:
            return max(seen) if pick == "largest" else seen.most_common(1)[0][0]
    return None


def read(ctx, module, shapes, work, real, slots, pick="commonest"):
    planes = trace.device_planes(ctx.get("trace_planes") or [])
    before, after = ctx["counters_before"], ctx["counters_after"]
    if not planes or work not in ctx.get("work_per_image", {}) or real not in after or slots not in after:
        return None
    ran = after[slots] - before.get(slots, 0.0)
    if ran <= 0:
        return None
    real_share = (after[real] - before.get(real, 0.0)) / ran
    shape_rxs = [re.compile(shape) for shape in shapes]
    seconds, traced_slots, runs, unread = 0.0, 0, 0, 0
    for plane in planes:
        for _, start, duration in trace.modules(plane, module):
            batch = _padded_batch(plane, start, start + duration, shape_rxs, pick)
            if batch is None:
                unread += 1
                continue
            seconds += duration / 1e9
            traced_slots += batch
            runs += 1
    if seconds <= 0 or traced_slots == 0:
        return None
    least = work_mod.least_seconds(ctx["work_per_image"][work], work_mod.peaks(ctx["device"]["kind"]))
    ctx.setdefault("notes", {}).update({
        f"{work}_roofline_bound": least["bound"], f"{work}_traced_runs": runs,
        f"{work}_traced_runs_unread": unread,
        f"{work}_traced_slots": traced_slots, f"{work}_real_share_of_slots": real_share,
        f"{work}_traced_module_seconds": seconds})
    return 100.0 * least["seconds"] * traced_slots * real_share / seconds
