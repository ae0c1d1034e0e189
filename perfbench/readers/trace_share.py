"""A share read from the profiler's trace of one launch (the traced run puts
the profiler around one full launch's execution: ``cell.trace_one_launch``).

``what``:
  ``roofline``     least time the chip could take for the needed work of the
                   launches traced, over the device time of the XLA modules
                   matching ``module``. Needed work is that of the kernel
                   ``work`` as the configuration's reference gives it for
                   one image (``references/<name>.py`` ``work``, through
                   ``harness/work.py``, from the configuration's true
                   sizes); ``images`` names the program's counter of the
                   images that kernel has done. The traced launches are the
                   window's largest (the profiler is put on a full one);
                   their images are the window's images less the padded
                   sizes of its other launches (program counters), which is
                   exact where those hold 1 or 2 images and never too many.
  ``launch_idle``  of the seconds the program held its launches between
                   dispatch and completed read-back (the histogram ``timer``
                   of the program, summed over the window), the share in
                   which none of the traced modules ran. The trace holds one
                   launch; where the window read back more, the share reads
                   high by the device time of the others.

No device plane in the trace (a CPU run): nothing read. Never 0 for a share
of a roofline."""

from perfbench.harness import trace, work as work_mod


def read(ctx, what, module, timer=None, work=None, images=None):
    planes = trace.device_planes(ctx.get("trace_planes") or [])
    seconds = count = 0
    for plane in planes:
        s, c = trace.module_seconds(plane, module)
        seconds, count = seconds + s, count + c
    if seconds <= 0 or count == 0:
        return None
    before, after = ctx["counters_before"], ctx["counters_after"]

    def delta(key):
        return after.get(key, 0.0) - before.get(key, 0.0)

    if what == "roofline":
        sizes = sorted((int(size) for size, n in ctx["launch_sizes"].items() for _ in range(n)),
                       reverse=True)
        done = delta(images) - sum(sizes[count:])
        if done <= 0 or work not in ctx["work_per_image"]:
            return None
        least = work_mod.least_seconds(ctx["work_per_image"][work],
                                       work_mod.peaks(ctx["device"]["kind"]))
        ctx.setdefault("notes", {})[f"{work}_roofline_bound"] = least["bound"]
        return 100.0 * least["seconds"] * done / seconds
    if what == "launch_idle":
        held = delta(timer + "_sum")
        if held <= seconds:
            return None
        return 100.0 * (1.0 - seconds / held)
    raise ValueError(f"unknown share {what!r}")
