"""The share of its roofline at which an aux program ran, read from the
profiler's trace alone (the traced run puts the profiler around one cycle's
device work: ``cell.trace_one_launch``).

An aux launch (``BatchController.submit_aux``) is counted by none of the
counters ``trace_share`` goes by, and the window's counters say nothing of
which launches the traced slice held. So this reader takes everything from
the slice: the device time of the XLA modules matching ``modules``, and the
launches among them, one for each module matching ``counted`` (a launch runs
that module once). A launch holds at least one item, and how many more the
device's plane does not say, so each launch is counted as ONE item: the
count errs low, and the share with it (the host annotation that could say,
``flyimg:aux:<seq>:run``, is not among the host events the harness keeps of a
trace: PERF.md section 7).

Needed work is that of the kernel ``work`` as the configuration's reference
gives it for one item (``references/<name>.py`` ``work``). No device plane,
no such module (a CPU run; a program or a cell without the aux path): nothing
read. Never 0."""

from perfbench.harness import trace, work as work_mod


def read(ctx, modules, counted, work):
    planes = trace.device_planes(ctx.get("trace_planes") or [])
    if not planes or work not in ctx.get("work_per_image", {}):
        return None
    seconds, launches = 0.0, 0
    for plane in planes:
        seconds += trace.module_seconds(plane, modules)[0]
        launches += trace.module_seconds(plane, counted)[1]
    if seconds <= 0 or launches == 0:
        return None
    least = work_mod.least_seconds(ctx["work_per_image"][work], work_mod.peaks(ctx["device"]["kind"]))
    ctx.setdefault("notes", {}).update({
        f"{work}_roofline_bound": least["bound"], f"{work}_traced_launches": launches,
        f"{work}_traced_module_seconds": seconds})
    return 100.0 * least["seconds"] * launches / seconds
