"""One run of one cell: set-up, pre-roll, the measured window, the drain,
the comparison with the reference, and the result line."""

from __future__ import annotations

import copy
import hashlib
import json
import os
import re
import resource
import shutil
import statistics
import sys
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from . import compare, corpus as corpus_mod, manifest as mf, trace as trace_mod
from .traffic import ClosedLoop

HOST_KEEP = re.compile(rf"^(flyimg:batch:|{re.escape(trace_mod.SLICE_MARK)}$)")


def log(*parts: Any) -> None:
    print(*parts, file=sys.stderr, flush=True)


def rss_bytes() -> int:
    """Resident set of this process now (``/proc/self/statm``)."""
    try:
        with open("/proc/self/statm", "r", encoding="ascii") as fh:
            return int(fh.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError, IndexError):
        return 0


class RssWatch(threading.Thread):
    """Samples the resident set every few seconds; the cell holds tens of
    GB of decoded frames and assembled launches on the host, and the machine
    ends a process that passes its limit."""

    def __init__(self, every: float = 2.0) -> None:
        super().__init__(name="bench-rss", daemon=True)
        self.every, self.samples, self._halt = every, [], threading.Event()
        self._t0 = time.perf_counter()

    def run(self) -> None:
        while not self._halt.wait(self.every):
            self.samples.append((round(time.perf_counter() - self._t0, 1),
                                 round(rss_bytes() / 2**30, 2)))

    def stop(self) -> None:
        self._halt.set()


# a cycle with a lone launch in it is longer than the others: the full launch
# waits for the lone launch's caller to be answered and its next frame to be
# decoded, which is up to half a cycle more
SLICE_CAP_CYCLES = 1.5


def trace_one_launch(loop: ClosedLoop, t_burst: float, cycle: float, t_close: float,
                     burst_gap: float) -> Tuple[str, float, Dict[str, float]]:
    """Put the profiler around one cycle's device work: every launch from the
    window's opening to the first burst of answers, whatever programs it
    runs. Placed by the opening and the answers alone, so that no gain on the
    host can move the launch out of the slice.

    It opens with the window, at the last answer of the pre-roll's burst
    (``t_burst``): all the callers have just been answered and are out again,
    so no launch of the new cycle can have been staged yet, however short the
    fill becomes. It ends a fixed 0.5 s (the read-back's own tail) after the
    SECOND answer of the new cycle: the first may be a lone launch's (the
    first frame decoded can reach the idle executor alone, and its one answer
    is followed by seconds of nothing), while the callers of a full launch
    wake 20 ms apart, so the second answer says that the full launch has been
    read back and is being resolved. An answer counts for the new cycle where
    its call was sent since the pre-roll's burst began (``burst_gap`` tells
    bursts apart, as for the closing rule): after a lone launch in the
    pre-roll, its caller's second call rides the pre-roll's full launch and
    can answer a moment after ``t_burst``. Where the two answers do not come
    the slice ends ``SLICE_CAP_CYCLES`` cycles of the pre-roll's after it
    opened, and never later than 1 s before the time is up.

    On the slice's own clock the harness opens the annotation
    ``trace.SLICE_MARK`` for as long as the slice lasts, from which
    ``trace.slice_margins`` reads how far inside it the launch sits. Returns
    the trace's directory, the slice's seconds, and what the profiler's two
    calls took."""
    import jax

    trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    began = loop.burst_began(t_burst, burst_gap)
    t_call = time.perf_counter()
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    t_on = time.perf_counter()
    last = max(t_close - 1.0, t_on)
    with jax.profiler.TraceAnnotation(trace_mod.SLICE_MARK):
        answered = loop.wait_answers(2, t_on, began, min(t_on + SLICE_CAP_CYCLES * cycle, last) - t_on)
        if answered is not None:
            time.sleep(min(0.5, max(last - time.perf_counter(), 0.0)))
        t_off = time.perf_counter()
    jax.profiler.stop_trace()
    took = {"start_trace_s": t_on - t_call, "stop_trace_s": time.perf_counter() - t_off}
    log(f"# trace: cycle of the pre-roll {cycle:.2f} s; start_trace called {t_call - t_burst:.2f} s after its burst, "
        f"returned after {took['start_trace_s']:.2f} s; {t_off - t_on:.2f} s traced until "
        f"{'0.5 s after the second answer of the new cycle' if answered is not None else 'the cap (no two answers came)'}; "
        f"stopped in {took['stop_trace_s']:.2f} s")
    return trace_dir, t_off - t_on, took


def trim_heap() -> None:
    """Hand the allocator's free memory back to the machine. Seven compiles
    at once leave several GB in glibc's arenas, which a cold run would
    otherwise carry through its window beside 64 decoded 24 MP frames."""
    try:
        import ctypes

        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except (OSError, AttributeError):
        pass


def cpu_seconds() -> float:
    usage = resource.getrusage(resource.RUSAGE_SELF)
    return usage.ru_utime + usage.ru_stime


def launch_sizes(before: Dict[str, float], after: Dict[str, float]) -> Dict[str, int]:
    """Launches of each padded size in the window, from the program's
    ``flyimg_batch_bucket_size`` histogram of the device controller."""
    rx = re.compile(r'^flyimg_batch_bucket_size_bucket\{(.*)\}$')
    cumulative: List[Tuple[float, float]] = []
    for key, value in after.items():
        m = rx.match(key)
        if not m or 'controller="device"' not in m.group(1):
            continue
        le = re.search(r'le="([^"]+)"', m.group(1))
        if le and le.group(1) != "+Inf":
            cumulative.append((float(le.group(1)), value - before.get(key, 0.0)))
    sizes: Dict[str, int] = {}
    last = 0.0
    for bound, count in sorted(cumulative):
        if count - last > 0:
            sizes[str(int(bound))] = int(round(count - last))
        last = count
    return sizes


def window_timers(before: Dict[str, float], after: Dict[str, float]) -> Dict[str, List[float]]:
    """Every histogram of seconds the program keeps, as ``[count, summed
    seconds]`` over the window."""
    out: Dict[str, List[float]] = {}
    for key, value in after.items():
        if "_seconds_sum" not in key:
            continue
        count_key = key.replace("_sum", "_count", 1)
        n = after.get(count_key, 0.0) - before.get(count_key, 0.0)
        if n > 0:
            out[key.replace("_sum", "", 1)] = [int(n), value - before.get(key, 0.0)]
    return out


PER_IMAGE_TIMER = "flyimg_stage_seconds"
LAUNCH_TIMERS_KEPT = 7


def device_report(planes: List[Dict[str, Any]], slice_s: float, window_s: float,
                  timers: Dict[str, List[float]]) -> Tuple[float, Dict[str, Any]]:
    """``busy_s`` and the ``breakdown`` of a traced run. The profiler ran for
    a slice of the window around one cycle's device work
    (``trace_one_launch``), so the busy seconds are those of the slice: a
    lower bound on the window's where it holds a second cycle. The idle
    seconds are the window's: the part the profiler was off for, the slice's
    own, and then what the host was doing meanwhile, by the program's own
    timers summed over the window (``<series> x<calls>``): the per-launch
    ones first, by seconds, then the per-image stage series in what room is
    left, so that the ten entries name the launch's phases and the heaviest
    stages both. Every name is whole within the 64 characters the ledger
    keeps of it."""
    dev_planes = trace_mod.device_planes(planes)
    busy = sum(trace_mod.busy_seconds(p) for p in dev_planes) / len(dev_planes)
    host_marks = [e for e in trace_mod.host_events(planes) if e[0] != trace_mod.SLICE_MARK]
    log("# trace: gaps between the slice's device ops, seconds:", json.dumps(trace_mod.attribute_gaps(
        trace_mod.idle_gaps(dev_planes[0]), host_marks,
        "host inside a flyimg:batch dispatch", "host between dispatches")[:4]))
    idle = [["window outside the traced slice (profiler off)", window_s - slice_s],
            ["traced slice, no device op running", slice_s - busy]]
    ranked = sorted(timers.items(), key=lambda kv: -kv[1][1])
    per_launch = [kv for kv in ranked if not kv[0].startswith(PER_IMAGE_TIMER)][:LAUNCH_TIMERS_KEPT]
    per_image = [kv for kv in ranked if kv[0].startswith(PER_IMAGE_TIMER)]
    idle += [[f"{key} x{n}", seconds]
             for key, (n, seconds) in (per_launch + per_image)[:10 - len(idle)]]
    return busy, {"device_ops": trace_mod.top_ops(dev_planes), "idle_gaps": idle}


def bursts(records: List[Any], t_open: float, gap: float) -> List[List[float]]:
    """The answers of the window grouped where the gap between two reaches
    ``gap``: the closing rule's own reading of a burst."""
    out: List[List[float]] = []
    last = None
    for done in sorted(r.done for r in records):
        if last is None or done - last >= gap:
            out.append([round(done - t_open, 2), 0.0, 0])
        out[-1][1], out[-1][2] = round(done - t_open - out[-1][0], 2), out[-1][2] + 1
        last = done
    return out


def percentile(values: List[float], q: float) -> float:
    ordered = sorted(values)
    if not ordered:
        raise ValueError("no values")
    pos = q * (len(ordered) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(ordered) - 1)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (pos - lo)


def run_cell(manifest: Dict[str, Any], name: str, seed: int, seconds: float,
             traced: bool, *, t_process: float, toy: bool = False,
             require_chip: bool = True) -> Dict[str, Any]:
    from . import system as system_mod

    cell = mf.workload(manifest, name)
    config = copy.deepcopy(mf.load_config(manifest, cell["config"]))
    mix = copy.deepcopy(mf.load_traffic(cell["traffic"]))
    if toy:
        mf.apply_toy(config, mix)
    bound = mf.bind(manifest, cell["config"], config)
    device = system_mod.device_info(cell["chips"], require_chip)
    on_chip = device["platform"] != "cpu"
    phases: Dict[str, float] = {"import_and_backend": time.perf_counter() - t_process}

    # -- set-up: corpus on threads while the programs compile or load -------
    frame = config["frame"]
    made: Dict[str, Any] = {}
    watch = RssWatch()
    watch.start()

    def build_corpus() -> None:
        t = time.perf_counter()
        made["corpus"] = corpus_mod.make_corpus(
            bound.make_image, seed, frame, int(config["corpus"]["images"]))
        made["seconds"] = time.perf_counter() - t

    builder = threading.Thread(target=build_corpus, name="bench-corpus")
    builder.start()
    t = time.perf_counter()
    sut = system_mod.System(config)
    warmed = {warmer: warm(sut, config, mix) for warmer, warm in bound.warmers}
    phases["warm_programs"] = time.perf_counter() - t
    trim_heap()
    compiles_in_warm = sut.compiles.count
    builder.join()
    corpus = made["corpus"]
    phases["corpus"] = made["seconds"]
    log(f"# set-up: corpus {len(corpus)} x {frame['width']}x{frame['height']} "
        f"({sum(map(len, corpus)) / 1e6:.1f} MB, {config['corpus']['kind']}) in {made['seconds']:.1f} s; "
        f"programs of {list(warmed)} in {phases['warm_programs']:.1f} s ({compiles_in_warm} built, "
        f"{sut.compiles.hits} of them read from the cache {sut.cache_dir})")
    for warmer, info in warmed.items():
        log(f"# warmer {warmer}:", json.dumps(info))

    # -- the loop ---------------------------------------------------------------
    answers: Dict[Tuple[int, str], bytes] = {}
    answers_lock = threading.Lock()

    def call(item: int):
        out, timings = sut.transform(corpus[item])
        digest = hashlib.blake2b(out, digest_size=8).hexdigest()
        with answers_lock:
            answers.setdefault((item, digest), out)
        return timings, digest

    loop = ClosedLoop(mix, len(corpus), seed, call)
    t = time.perf_counter()
    loop.start()
    # the pre-roll ends when the first wave of calls has all answered: the
    # same amount of work from the seed in every run. The callers move in
    # step with a launch, so that is the end of a burst of answers, and the
    # window opens there as it closes: on the last answer of a burst, so
    # that it holds whole cycles
    t_burst = loop.wait_first_sent(int(mix["preroll_images"]),
                                   float(mix.get("preroll_timeout_seconds", 900)))
    cycle = t_burst - t
    t_open = time.perf_counter()
    phases["preroll"] = t_open - t
    compiles_in_preroll = sut.compiles.count - compiles_in_warm
    counters_before, cpu_before = sut.counters(), cpu_seconds()
    compiles_before = sut.compiles.count
    setup_s = t_open - t_process
    # when the time is up nothing more is sent; all that was sent is waited
    # for and counts, and the clock is read after that wait (traffic.py)
    t_up = t_open + seconds
    burst_gap = float(mix["burst_gap_cycle_share"]) * cycle
    loop.close_at(t_up, burst_gap, float(mix["burst_cap_cycle_share"]) * cycle)
    trace_dir = slice_s = None
    if traced:
        trace_dir, slice_s, notes = trace_one_launch(loop, t_burst, cycle, t_up, burst_gap)
    time.sleep(max(t_up - time.perf_counter(), 0.0))
    unanswered = loop.drain(float(mix["drain_seconds"]))
    t_close = time.perf_counter()
    loop.stop()
    counters_after, cpu_after = sut.counters(), cpu_seconds()
    compiles_in_window = sut.compiles.count - compiles_before
    answered = loop.window(t_open, t_close)
    peak = system_mod.memory_peak_bytes()
    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    after_up = sum(r.sent > t_up for r in answered)
    sut.close()
    watch.stop()
    log("# host RSS GiB by second since set-up began:",
        " ".join(f"{t:.0f}:{g}" for t, g in watch.samples))

    done = [r for r in answered if r.ok]
    failed = [r for r in answered if not r.ok]
    for r in failed[:5]:
        log("# failed:", r.error)
    sizes = launch_sizes(counters_before, counters_after)
    wedged = counters_after.get("flyimg_wedged_fallbacks_total", 0.0) - \
        counters_before.get("flyimg_wedged_fallbacks_total", 0.0)
    log(f"# launches in the window by padded size: {json.dumps(sizes)}; programs built in warm-up "
        f"{compiles_in_warm}, pre-roll {compiles_in_preroll}, window {compiles_in_window}; "
        f"wedged fallbacks in window {wedged:.0f}")
    log(f"# process peak RSS {rss / 2**30:.2f} GiB; device peak "
        f"{(peak or 0) / 2**30:.2f} GiB; sent after the time was up (a burst that straddled it): "
        f"{after_up}; never answered: {unanswered}; set-up phases {json.dumps({k: round(v, 2) for k, v in phases.items()})}")

    window_s = t_close - t_open
    log(f"# window: {window_s:.2f} s, of which {t_close - t_up:.2f} s after the time was up; bursts of answers "
        f"[seconds after the opening, seconds long, answers]: {json.dumps(bursts(answered, t_open, burst_gap))}")
    timers = window_timers(counters_before, counters_after)
    log("# program timers in the window [count, summed seconds]:",
        json.dumps({k: [n, round(v, 3)] for k, (n, v) in timers.items()}))
    if done:
        keys = sorted({k for r in done for k in r.info[0]})
        log("# mean timings per image in the window, ms:", json.dumps({
            k: round(1000 * statistics.fmean(r.info[0][k] for r in done if k in r.info[0]), 1)
            for k in keys}))

    # -- metrics ------------------------------------------------------------------
    values: Dict[str, Optional[float]] = {}
    breakdown = None
    device_out = {"platform": device["platform"], "kind": device["kind"],
                  "count": device["count"], "memory_peak_bytes": peak}
    if not traced:
        # what the harness can time itself; a cell reports those of them
        # that the manifest lists for it
        lat = [r.done - r.sent for r in done]
        measured = {
            "latency_p95_ms": 1000.0 * percentile(lat, 0.95) if lat else None,
            "images_per_s": len(done) / window_s,
            "setup_s": setup_s,
        }
        for metric in mf.metrics_for(manifest, name, "end_to_end"):
            values[metric["name"]] = measured[metric["name"]]
        if lat:
            log(f"# in the window: {len(done)} images answered in {window_s:.2f} s; latency p50 "
                f"{1000 * percentile(lat, 0.5):.0f} ms, p95 {1000 * percentile(lat, 0.95):.0f} ms")
    else:
        planes: List[Dict[str, Any]] = []
        xplane = trace_mod.find_xplane(trace_dir) if trace_dir else None
        if xplane:
            planes = trace_mod.load_xplane(xplane, HOST_KEEP)
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
        ctx: Dict[str, Any] = {
            "counters_before": counters_before, "counters_after": counters_after,
            "timings": [r.info[0] for r in done], "images": len(done),
            "cpu_before": cpu_before, "cpu_after": cpu_after,
            "trace_planes": planes, "trace_slice_s": slice_s, "launch_sizes": sizes,
            "device": device,
            "work_per_image": bound.reference.work(config),
            "notes": notes,
        }
        margins = trace_mod.slice_margins(planes)
        if margins:
            # the full launch's place in the slice; a lone launch beside it is
            # in the log alone
            notes.update({k: margins[0][k] for k in ("staged_after_open_s", "readback_before_end_s")})
            log("# trace: launches in the slice [seq, staging began s after the opening, read-back ended s "
                "before the end, held s], the one held longest first:", json.dumps(
                    [[m["seq"], m["staged_after_open_s"], m["readback_before_end_s"], m["hold_s"]]
                     for m in margins]))
        else:
            log("# trace: no launch of the program is whole in the slice (dispatch to read-back)")
        for metric in mf.metrics_for(manifest, name, "per_layer"):
            spec = mf.load_metric(metric["name"])
            if metric["source"] == "device_trace" and not on_chip:
                continue  # a CPU run never prints a device metric
            values[metric["name"]] = mf.load_reader(spec["reader"])(ctx, **spec["args"])
        dev_planes = trace_mod.device_planes(planes)
        if dev_planes and on_chip:
            busy, breakdown = device_report(planes, slice_s, window_s, timers)
            device_out["busy_s"], device_out["window_s"] = busy, window_s
            log(f"# trace: device busy {busy:.4f} s of the {slice_s:.2f} s slice; "
                f"window {window_s:.2f} s")
        log("# notes:", json.dumps(notes))

    units = {m["name"]: m["unit"] for kind in ("end_to_end", "per_layer") for m in manifest[kind]}
    metrics = {k: {"value": v, "unit": units[k]} for k, v in values.items() if v is not None}

    # -- correct: after the window, the memory reading and the program's close --
    t = time.perf_counter()
    verdict = compare.Judge(bound, corpus).judge(answers, unanswered)
    numbers = verdict["numbers"]
    numbers["compiles_in_window"] = {"value": float(compiles_in_window), "limit": 0.0}
    numbers["wedged_fallbacks"] = {"value": float(wedged), "limit": 0.0}
    correct = all(n["value"] <= n["limit"] for n in numbers.values())
    log(f"# reference: {verdict['answers']} distinct answers of {len(corpus)} originals "
        f"judged in {time.perf_counter() - t:.1f} s; rms_err {verdict['rms_err_not_compared']:.4g} (not compared)")
    log("# compared:", compare.format_numbers(numbers))

    result: Dict[str, Any] = {
        "correct": bool(correct),
        "attempted": len(done) + len(failed),
        "failed": len(failed),
        "metrics": metrics,
        "device": device_out,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["workload"] = name
    result["seed"] = seed
    result["launch_sizes"] = sizes
    result["host_peak_rss_bytes"] = rss
    if traced:
        result["notes"] = notes
    result["compared"] = numbers
    return result
