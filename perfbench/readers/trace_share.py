"""A share read from the profiler's trace of one launch (the traced run puts
the profiler around one cycle's device work: ``cell.trace_one_launch``).

Both shares are read of ONE launch, the full one: on each device, the XLA
module matching ``module`` that ran longest. A slice may hold a lone launch
of 1 beside the full one (its module runs a sixtieth as long), and neither
share may depend on whether it does.

``what``:
  ``roofline``     least time the chip could take for the needed work of
                   that launch, over its module's device time. Needed work
                   is that of the kernel ``work`` as the configuration's
                   reference gives it for one image (``references/<name>.py``
                   ``work``, through ``harness/work.py``, from the
                   configuration's true sizes); ``images`` names the
                   program's counter of the images that kernel has done. The
                   launch is one of the window's largest; its images are the
                   window's images less the padded sizes of every other
                   launch (program counters), which is exact where the
                   window's other launches are full or hold 1 image, and
                   never too many.
  ``launch_idle``  of the seconds the program held the device for that
                   launch, the share in which its modules did not run. The
                   hold is read from the same trace: the start of the
                   launch's ``flyimg:batch:<seq>:dispatch`` annotation to the
                   end of its ``:d2h`` (``trace.launch_holds``), which is
                   what the program's ``flyimg_device_seconds`` spans. So the
                   share does not move with the number of launches a window
                   holds (before PR 34 the divisor was the window's sum of
                   that timer, and the share rose with every launch more).
                   A program that annotates no phases, or a slice that opened
                   after the dispatch began: nothing read.

No device plane in the trace (a CPU run): nothing read. Never 0 for a share
of a roofline."""

from perfbench.harness import trace, work as work_mod


def read(ctx, what, module, work=None, images=None):
    planes = ctx.get("trace_planes") or []
    longest = [m for m in (trace.longest_module(p, module) for p in trace.device_planes(planes)) if m]
    seconds = sum(m[2] for m in longest) / 1e9
    if seconds <= 0:
        return None
    before, after = ctx["counters_before"], ctx["counters_after"]
    if what == "roofline":
        sizes = [int(size) for size, n in ctx["launch_sizes"].items() for _ in range(n)]
        done = after.get(images, 0.0) - before.get(images, 0.0) - (sum(sizes) - max(sizes, default=0))
        if done <= 0 or work not in ctx["work_per_image"]:
            return None
        least = work_mod.least_seconds(ctx["work_per_image"][work],
                                       work_mod.peaks(ctx["device"]["kind"]))
        ctx.setdefault("notes", {})[f"{work}_roofline_bound"] = least["bound"]
        return 100.0 * least["seconds"] * done / seconds
    if what == "launch_idle":
        start = max(longest, key=lambda m: m[2])[1]
        hold = next(((a, b) for a, b in trace.launch_holds(planes).values() if a <= start <= b), None)
        if hold is None:
            return None
        # the launch's modules, a device: the mean over the devices it ran on
        ran = [sum(m[2] for m in trace.modules(p, module) if hold[0] <= m[1] <= hold[1])
               for p in trace.device_planes(planes)]
        ran = [r for r in ran if r > 0]
        held = hold[1] - hold[0]
        busy = sum(ran) / len(ran)
        if busy >= held:
            return None
        ctx.setdefault("notes", {})["traced_launch_hold_s"] = held / 1e9
        return 100.0 * (1.0 - busy / held)
    raise ValueError(f"unknown share {what!r}")
