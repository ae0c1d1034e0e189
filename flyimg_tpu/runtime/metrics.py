"""Service metrics: counters + gauges + latency histograms + Prometheus
rendering.

The reference has no metrics at all (SURVEY.md section 5 "Metrics /
logging": exceptions to stdout and nginx access logs are the whole story).
A batched TPU serving tier is not operable blind, so this subsystem provides
the counters the baseline targets are phrased in — images/sec, batch
occupancy, per-stage latency p50/p99 — exposed in Prometheus text format by
the `/metrics` route (flyimg_tpu/service/app.py).

Design notes:
- Histograms use fixed log-spaced buckets (120 us .. ~2 min) so quantile
  estimates need no per-sample storage and merging across threads is just
  integer adds — the standard Prometheus histogram design.
- Everything is guarded by one lock per registry; recording is a few dict
  ops, far off any hot path (the hot path is the device, ~ms per batch).
"""

from __future__ import annotations

import re
import threading
import time
from collections import deque
from typing import Callable, Dict, Iterable, List, Optional, Tuple

# log-spaced latency buckets in seconds: 23 buckets, x1.8 apart,
# 120us .. ~113s — covers device-batch latencies through cold compiles.
_BUCKET_BASE = 0.00012
_BUCKET_FACTOR = 1.8
_N_BUCKETS = 23
BUCKET_BOUNDS: Tuple[float, ...] = tuple(
    _BUCKET_BASE * _BUCKET_FACTOR ** i for i in range(_N_BUCKETS)
)

# batch-efficiency histogram bounds (docs/observability.md "Batch
# efficiency"): occupancy is a ratio in (0, 1], bucket sizes ride the
# power-of-two ladder — latency bounds would be meaningless for either
OCCUPANCY_BOUNDS: Tuple[float, ...] = (
    0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0
)
BATCH_SIZE_BOUNDS: Tuple[float, ...] = (1, 2, 4, 8, 16, 32, 64)

# cached module ref for exemplar trace-id lookup (lazy: metrics must stay
# importable/fast without dragging the tracing module in at import time)
_tracing_mod = None


def _ambient_trace_id() -> Optional[str]:
    """Trace id of the ambient request trace, for OpenMetrics exemplars.
    No active trace (or tracing not yet imported by anything) -> None in
    a few instructions — this sits on the record_stage hot path."""
    global _tracing_mod
    if _tracing_mod is None:
        from flyimg_tpu.runtime import tracing as _t

        _tracing_mod = _t
    trace = _tracing_mod.current_trace()
    return trace.trace_id if trace is not None else None


def bucket_index(value: float, bounds: Tuple[float, ...]) -> int:
    """Index of the bucket ``value`` lands in (len(bounds) = overflow).
    THE bucketing rule — Histogram.observe and the SLO engine's window
    slices must agree or their quantiles drift apart."""
    for i, bound in enumerate(bounds):
        if value <= bound:
            return i
    return len(bounds)


def quantile_from_counts(counts: List[int], bounds: Tuple[float, ...],
                         q: float) -> float:
    """In-bucket linearly interpolated q-quantile over bucket counts (the
    histogram_quantile() rule). ONE copy shared by Histogram.quantile and
    the SLO engine's windowed p99 — the PR-2 interpolation fix showed why
    this math must not fork. Overflow-bucket quantiles are +inf (no upper
    bound to interpolate toward); empty counts -> 0."""
    n = sum(counts)
    if n == 0:
        return 0.0
    target = q * n
    acc = 0
    for i, c in enumerate(counts):
        prev = acc
        acc += c
        if acc >= target and c > 0:
            if i >= len(bounds):
                return float("inf")
            lo = bounds[i - 1] if i > 0 else 0.0
            hi = bounds[i]
            return lo + (hi - lo) * ((target - prev) / c)
    return float("inf")


def escape_label_value(value: str) -> str:
    """Prometheus label-value escaping (exposition format allows \\\\ \\"
    \\n only). EVERY label whose value is not a literal in this module
    goes through here — route/stage/point/reason strings reach the
    registry from request paths and a crafted value must not corrupt the
    exposition format."""
    return (
        str(value)
        .replace("\\", r"\\")
        .replace('"', r"\"")
        .replace("\n", r"\n")
    )


class Counter:
    """Monotonic counter with optional labels baked into the name."""

    def __init__(self, name: str, help_text: str = "") -> None:
        self.name = name
        self.help = help_text
        self._value = 0.0
        self._lock = threading.Lock()

    def inc(self, amount: float = 1.0) -> None:
        with self._lock:
            self._value += amount

    @property
    def value(self) -> float:
        with self._lock:
            return self._value


class Gauge:
    """Point-in-time value: settable, inc/dec-able, or backed by a
    callback (``fn``) sampled at render time — the right shape for
    in-flight request counts, queue depths, and open-breaker counts,
    which are states, not monotonic totals."""

    def __init__(self, name: str, help_text: str = "",
                 fn: Optional[Callable[[], float]] = None) -> None:
        self.name = name
        self.help = help_text
        self._fn = fn
        self._value = 0.0
        self._lock = threading.Lock()

    def set(self, value: float) -> None:
        with self._lock:
            self._value = float(value)

    def inc(self, amount: float = 1.0) -> None:
        with self._lock:
            self._value += amount

    def dec(self, amount: float = 1.0) -> None:
        with self._lock:
            self._value -= amount

    @property
    def value(self) -> float:
        if self._fn is not None:
            try:
                return float(self._fn())
            except Exception:
                # a dead callback must not take /metrics down with it
                return float("nan")
        with self._lock:
            return self._value


class Histogram:
    """Fixed-bucket histogram with quantile estimation and optional
    OpenMetrics exemplars. Default bounds are the log-spaced latency
    ladder; ``bounds`` overrides them for non-latency distributions
    (occupancy ratios, batch-size buckets)."""

    def __init__(self, name: str, help_text: str = "",
                 bounds: Optional[Tuple[float, ...]] = None) -> None:
        self.name = name
        self.help = help_text
        self.bounds: Tuple[float, ...] = (
            BUCKET_BOUNDS if bounds is None else tuple(bounds)
        )
        self._counts = [0] * (len(self.bounds) + 1)  # +1 overflow bucket
        self._sum = 0.0
        self._n = 0
        # per-bucket exemplar: (observed value, trace_id, unix ts) — the
        # OpenMetrics hook that links a latency bucket to one concrete
        # trace in the ring (last observation wins, the standard policy)
        self._exemplars: Dict[int, Tuple[float, str, float]] = {}
        self._lock = threading.Lock()

    def observe(self, seconds: float, trace_id: Optional[str] = None) -> None:
        idx = bucket_index(seconds, self.bounds)
        with self._lock:
            self._counts[idx] += 1
            self._sum += seconds
            self._n += 1
            if trace_id:
                self._exemplars[idx] = (seconds, trace_id, time.time())

    def quantile(self, q: float) -> float:
        """Estimate of the q-quantile (0 < q <= 1), interpolated linearly
        within the winning bucket (the histogram_quantile() rule):
        returning the bucket's upper bound over-reported p50/p99 by up to
        one bucket factor (1.8x) whenever the mass sat near a bucket's
        lower edge. Overflow-bucket quantiles stay +inf — there is no
        upper bound to interpolate toward."""
        with self._lock:
            counts = list(self._counts)
        return quantile_from_counts(counts, self.bounds, q)

    def snapshot(self) -> Tuple[List[int], float, int]:
        with self._lock:
            return list(self._counts), self._sum, self._n

    def exemplars(self) -> Dict[int, Tuple[float, str, float]]:
        with self._lock:
            return dict(self._exemplars)


class BatchEfficiency:
    """Rolling batch-efficiency window for ONE controller: the last
    ``window`` launches' occupancy, padded-slot waste, queue-wait vs
    device-time share, and compile amortization. Counters answer
    "since boot"; operators tuning ``batch_deadline_ms``/``batch_max_size``
    need "lately" — this is the object behind ``/debug/perf`` and the
    batcher's ``stats()``."""

    def __init__(self, window: int = 256) -> None:
        self._lock = threading.Lock()
        # (images, capacity, queue_wait_s, device_s, compile_hit|None)
        self._entries: deque = deque(maxlen=max(1, int(window)))
        # monotone launches-ever-recorded counter: the rolling window
        # itself never expires by time, so consumers that need RECENCY
        # (the signal window's since-last-assembly launch delta) diff this
        self._recorded_total = 0

    def record(self, *, images: int, capacity: int, queue_wait_s: float,
               device_s: Optional[float],
               compile_hit: Optional[bool]) -> None:
        with self._lock:
            self._recorded_total += 1
            self._entries.append((
                int(images), int(capacity), max(float(queue_wait_s), 0.0),
                float(device_s) if device_s is not None else 0.0,
                compile_hit,
            ))

    def stats(self) -> Dict[str, float]:
        with self._lock:
            entries = list(self._entries)
            recorded_total = self._recorded_total
        if not entries:
            return {
                "window_batches": 0, "mean_occupancy": 0.0,
                "padding_waste": 0.0, "queue_wait_share": 0.0,
                "batches_per_compile_miss": 0.0,
                "mean_queue_wait_ms": 0.0, "mean_device_ms": 0.0,
                "recorded_total": 0,
            }
        images = sum(e[0] for e in entries)
        slots = sum(e[1] for e in entries)
        queue_wait = sum(e[2] for e in entries)
        device = sum(e[3] for e in entries)
        # compile amortization counts only launches where a compile COULD
        # have happened (compile_hit is None for aux/host-codec launches);
        # zero misses in the window reports the window length — a floor,
        # not an exact amortization (documented in docs/observability.md)
        compiled = [e[4] for e in entries if e[4] is not None]
        misses = sum(1 for hit in compiled if not hit)
        occupancy = images / slots if slots else 0.0
        return {
            "window_batches": len(entries),
            "mean_occupancy": occupancy,
            "padding_waste": 1.0 - occupancy if slots else 0.0,
            "queue_wait_share": (
                queue_wait / (queue_wait + device)
                if (queue_wait + device) > 0 else 0.0
            ),
            "batches_per_compile_miss": (
                len(compiled) / misses if misses
                else float(len(compiled))
            ),
            "mean_queue_wait_ms": queue_wait / len(entries) * 1000.0,
            "mean_device_ms": device / len(entries) * 1000.0,
            "recorded_total": recorded_total,
        }


class PoolUtilization:
    """Rolling busy-ratio tracker for one host worker pool (the decode /
    encode codec pools). ``track()`` wraps each pool call; the gauge
    callback reads ``busy_ratio()`` — summed busy time overlapping the
    trailing window, divided by the window. Concurrent callers stack, so
    a ratio above 1.0 means the pool is oversubscribed (more wall-clock
    demand than one serial pool can supply) — exactly the saturation
    signal the host-codec pipelined-DAG work (ROADMAP item 4) needs to
    start from a measurement instead of a guess."""

    def __init__(self, window_s: float = 30.0,
                 clock: Callable[[], float] = time.monotonic) -> None:
        self.window_s = max(float(window_s), 0.1)
        self._clock = clock
        self._lock = threading.Lock()
        self._intervals: deque = deque()  # (start, end) monotonic pairs

    def track(self):
        """Context manager around ONE pool call."""
        return _PoolTrack(self)

    def _record(self, start: float, end: float) -> None:
        with self._lock:
            self._intervals.append((start, end))
            self._prune_locked(end)

    def _prune_locked(self, now: float) -> None:
        horizon = now - self.window_s
        while self._intervals and self._intervals[0][1] < horizon:
            self._intervals.popleft()

    def busy_ratio(self) -> float:
        now = self._clock()
        horizon = now - self.window_s
        with self._lock:
            self._prune_locked(now)
            busy = sum(
                min(end, now) - max(start, horizon)
                for start, end in self._intervals
            )
        return max(busy, 0.0) / self.window_s


class _PoolTrack:
    __slots__ = ("_pool", "_t0")

    def __init__(self, pool: PoolUtilization) -> None:
        self._pool = pool

    def __enter__(self):
        self._t0 = self._pool._clock()
        return self

    def __exit__(self, *exc):
        self._pool._record(self._t0, self._pool._clock())
        return False


# process-wide host-pool trackers (like the native pools they watch —
# one decode pool per process, whatever the app count); apps export them
# through flyimg_host_pool_busy_ratio gauge callbacks (service/app.py)
_host_pools: Dict[str, PoolUtilization] = {}
_host_pools_lock = threading.Lock()


def host_pool(name: str) -> PoolUtilization:
    """Get-or-create the utilization tracker for one host pool
    ('decode' / 'encode'; flyimg_tpu/codecs wraps its pool calls)."""
    with _host_pools_lock:
        pool = _host_pools.get(name)
        if pool is None:
            pool = PoolUtilization()
            _host_pools[name] = pool
        return pool


class MetricsRegistry:
    """Named metric store; one per app."""

    def __init__(self, *, exemplars: bool = True) -> None:
        self._lock = threading.Lock()
        self._counters: Dict[str, Counter] = {}
        self._gauges: Dict[str, Gauge] = {}
        self._histograms: Dict[str, Histogram] = {}
        # OpenMetrics exemplars on latency-histogram buckets (the
        # `metrics_exemplars` appconfig knob): each bucket remembers the
        # last traced observation that landed in it, so an SLO breach
        # links straight from /metrics to /debug/traces/{id}
        self.exemplars_enabled = bool(exemplars)
        # rolling per-controller batch-efficiency windows (runtime/batcher)
        self._batch_eff: Dict[str, BatchEfficiency] = {}
        # SLO engine attached by the app (runtime/slo.py) so summary()
        # speaks the same vocabulary as /debug/slo
        self._slo = None
        self.started_at = time.time()

    def _exemplar_trace_id(self) -> Optional[str]:
        if not self.exemplars_enabled:
            return None
        return _ambient_trace_id()

    def counter(self, name: str, help_text: str = "") -> Counter:
        with self._lock:
            metric = self._counters.get(name)
            if metric is None:
                metric = Counter(name, help_text)
                self._counters[name] = metric
            return metric

    def gauge(self, name: str, help_text: str = "",
              fn: Optional[Callable[[], float]] = None) -> Gauge:
        """Get-or-create a gauge; ``fn`` (sampled at render time) wins on
        first creation and is re-armed on later calls that pass one — so
        wiring code can idempotently re-register a callback."""
        with self._lock:
            metric = self._gauges.get(name)
            if metric is None:
                metric = Gauge(name, help_text, fn=fn)
                self._gauges[name] = metric
            elif fn is not None:
                metric._fn = fn
            return metric

    def histogram(self, name: str, help_text: str = "",
                  bounds: Optional[Tuple[float, ...]] = None) -> Histogram:
        with self._lock:
            metric = self._histograms.get(name)
            if metric is None:
                metric = Histogram(name, help_text, bounds=bounds)
                self._histograms[name] = metric
            return metric

    def batch_efficiency(self, controller: str) -> BatchEfficiency:
        """Get-or-create the rolling efficiency window for one batch
        controller (keyed by its name: 'device', 'codec', ...)."""
        with self._lock:
            eff = self._batch_eff.get(controller)
            if eff is None:
                eff = BatchEfficiency()
                self._batch_eff[controller] = eff
            return eff

    def attach_slo(self, engine) -> None:
        """Attach the app's SLO engine so summary() carries its burn
        rates/budget alongside the batch-efficiency fields."""
        self._slo = engine

    def family_total(self, family: str) -> float:
        """Sum of every sample in one counter/gauge family across all
        label sets — the fleet observatory's digest fields (shed and
        deadline totals, queue depths; runtime/observatory.py) without
        each caller re-parsing exposition names. Dead gauge callbacks
        (NaN) are skipped, like the renderer tolerates them."""
        with self._lock:
            samples = list(self._counters.values()) + list(
                self._gauges.values()
            )
        total = 0.0
        for metric in samples:
            if _bare(metric.name) != family:
                continue
            value = metric.value
            if value == value:  # skip NaN
                total += float(value)
        return total

    # -- recording helpers used by the serving path ------------------------

    def record_request(self, route: str, status: int) -> None:
        # route can derive from a client-controlled path segment: escape
        # it like record_breaker escapes host, or a crafted segment could
        # corrupt the exposition format
        safe = escape_label_value(route)
        self.counter(
            f'flyimg_requests_total{{route="{safe}",status="{int(status)}"}}',
            "HTTP requests by route and status",
        ).inc()

    def record_stage(self, stage: str, seconds: float) -> None:
        self.histogram(
            f'flyimg_stage_seconds{{stage="{escape_label_value(stage)}"}}',
            "Per-stage pipeline latency",
        ).observe(seconds, trace_id=self._exemplar_trace_id())

    def record_stage_thread(self, stage: str, seconds: float) -> None:
        """The calling thread's own CPU seconds over one stage
        (``tracing.stage``), beside its wall seconds in
        ``flyimg_stage_seconds``: over the sum of those, the share of the
        stage the thread computed rather than waited."""
        self.counter(
            "flyimg_stage_thread_seconds_total"
            f'{{stage="{escape_label_value(stage)}"}}',
            "Per-stage CPU seconds of the thread that ran the stage "
            "(time.thread_time())",
        ).inc(max(float(seconds), 0.0))

    def record_device_batch_seconds(
        self, seconds: float, trace_id: Optional[str] = None
    ) -> None:
        """Wall time of one device batch from dispatch to completed
        device->host readback (runtime/batcher.py profiling hook).
        ``trace_id`` is a member request's trace for the bucket exemplar —
        drain threads have no ambient trace, so the batcher passes one."""
        self.histogram(
            "flyimg_device_seconds",
            "Per-batch device time, dispatch to completed readback",
        ).observe(
            seconds,
            trace_id=trace_id if self.exemplars_enabled else None,
        )

    def record_launch(self, controller: str, launch,
                      trace_id: Optional[str] = None) -> None:
        """THE histogram sink of one completed launch
        (runtime/batcher.py ``_Launch``: the launch's one record, read
        here by ``seconds(phase)``, ``device_s``, ``queue_wait_s``,
        ``images``, ``capacity``, ``compile_hit``, ``aux``,
        ``transfer_bytes``, ``readback``, ``d2h_word``, ``depth``). A
        transform launch
        observes ``flyimg_device_seconds`` (dispatch to completed
        read-back, as ever), one histogram per phase, the bytes it moved
        each way (``flyimg_device_transfer_bytes_total``), the bits of its
        samples (``flyimg_batch_depth_total``), the form its output
        was read back in (``flyimg_batch_readbacks_total``) and the dtype
        it crossed the link in (``flyimg_batch_readback_words_total``);
        every launch,
        aux included, feeds the per-controller efficiency record
        (``record_batch_launch``) under the label its controller gives
        it: the transform controller's aux launches go under
        ``<name>_aux`` (``BatchController.aux_name``), so the series of
        ``controller="device"`` hold transform launches alone.
        ``resolve`` ends after the sinks are
        fed and arrives through ``record_launch_resolve``."""
        exemplar = trace_id if self.exemplars_enabled else None
        if not launch.aux:
            device_s = launch.device_s
            if device_s is not None:
                # dispatch -> completed readback: what the batch actually
                # held the device (and its members) for; the exemplar
                # links this bucket to one member's retrievable trace
                self.record_device_batch_seconds(device_s, trace_id=trace_id)
            for phase, name, help_text in _LAUNCH_PHASE_HISTOGRAMS:
                seconds = launch.seconds(phase)
                if seconds is not None:
                    self.histogram(name, help_text).observe(
                        max(float(seconds), 0.0), trace_id=exemplar
                    )
            for direction, nbytes in launch.transfer_bytes.items():
                self.counter(
                    "flyimg_device_transfer_bytes_total"
                    f'{{direction="{direction}"}}',
                    "Bytes transform launches staged to the device (h2d: "
                    "every argument of the launch) and read back from it "
                    "(d2h: the output); over the sum of "
                    "flyimg_device_transfer_seconds, the link's rate",
                ).inc(nbytes)
            self.counter(
                f'flyimg_batch_depth_total{{bits="{launch.depth}"}}',
                "Transform launches by the bits of a sample they carried: 8 "
                "(uint8 blocks and programs) or 16 (uint16, 16-bit PNG "
                "originals answered at their depth)",
            ).inc()
            if launch.readback is not None:
                self.counter(
                    f'flyimg_batch_readbacks_total{{layout="{launch.readback}"}}',
                    "Transform launches by the form their output was read "
                    "back in: row_major (a C-contiguous host array, each "
                    "member a view of it) or strided (a view in another "
                    "order, such as the device's planar one: each member "
                    "copied out of it on the drain thread)",
                ).inc()
            if launch.d2h_word is not None:
                self.counter(
                    "flyimg_batch_readback_words_total"
                    f'{{word="{launch.d2h_word}"}}',
                    "Transform launches by the dtype their output crossed "
                    "the link in: u32 (the samples packed four, or two of "
                    "16 bits, to a word, where a row holds whole words) or "
                    "the samples themselves (u8, u16)",
                ).inc()
        self.record_batch_launch(
            controller, images=launch.images, capacity=launch.capacity,
            queue_wait_s=launch.queue_wait_s, device_s=launch.device_s,
            compile_hit=launch.compile_hit, trace_id=trace_id,
            aux=launch.aux,
        )

    def record_launch_resolve(self, seconds: float,
                              thread_s: Optional[float] = None) -> None:
        """A transform launch's ``resolve``: its seconds, and the drain
        thread's own CPU seconds over them (``time.thread_time()``)."""
        self.histogram(
            "flyimg_batch_resolve_seconds",
            "Per transform launch: slicing and copying each member's "
            "output out of the batch and resolving its future",
        ).observe(max(float(seconds), 0.0))
        if thread_s is not None:
            self.counter(
                "flyimg_batch_resolve_thread_seconds_total",
                "CPU seconds of the drain thread over transform launches' "
                "resolve: over flyimg_batch_resolve_seconds_sum, the share "
                "of the resolve it computed rather than waited",
            ).inc(max(float(thread_s), 0.0))

    def record_member_wake(self, seconds: float) -> None:
        self.histogram(
            "flyimg_batch_wake_seconds",
            "Per member of a transform launch: its future's set_result on "
            "the drain thread until its caller's result() returned",
        ).observe(max(float(seconds), 0.0))

    def record_member_copy(self, seconds: float) -> None:
        self.histogram(
            "flyimg_batch_member_copy_seconds",
            "Per member of a transform launch: the copy of its frame into "
            "its slot of the launch's padded host block, at submit, on the "
            "caller's thread, while the launch is still filling",
        ).observe(max(float(seconds), 0.0))

    def record_member_copies(self, at: str, members: int) -> None:
        self.counter(
            f'flyimg_batch_member_copies_total{{at="{at}"}}',
            "Members of transform launches by where their pixels reached "
            "the launch's block: at submit (early, on the callers' "
            "threads) or at assemble (the copying path, on the launching "
            "thread: recovery sub-launches, a pre-split's remainder, "
            "members beyond the block)",
        ).inc(members)

    def record_block(self, origin: str) -> None:
        self.counter(
            f'flyimg_batch_blocks_total{{from="{origin}"}}',
            "Host blocks transform groups were made with, by where the "
            "block came from: kept (the controller's spare, a block whose "
            "launch has run: its pages are there) or fresh (np.zeros: "
            "every page a copy writes is touched for the first time)",
        ).inc()

    def record_codec_decode_launch(self, split) -> None:
        """One decode launch of the host codec's pool
        (``codecs.native_codec.LaunchSplit``, carried back by the
        handler's runner): its two parts, one observation a launch, the
        frames it handed over, and of its full frames of the pool's size
        how many reused a buffer the pool kept."""
        self.histogram(
            "flyimg_codec_native_seconds",
            "Per decode launch of the native codec pool: the pool call "
            "(C workers decode, GIL released)",
        ).observe(max(float(split.native_s), 0.0))
        self.histogram(
            "flyimg_codec_handover_seconds",
            "Per decode launch of the native codec pool: the walk over "
            "its results on the calling thread, each native buffer of "
            "pixels handed to the array that is returned",
        ).observe(max(float(split.handover_s), 0.0))
        self.record_codec_buffers(
            "adopted", split.buffers, split.buffer_bytes
        )
        for origin, frames in (("pooled", split.frames_pooled),
                               ("fresh", split.frames_fresh)):
            self.counter(
                f'flyimg_codec_frame_buffers_total{{from="{origin}"}}',
                "Full frames of 32 MiB and up that the codec pool's decode "
                "launches decoded, by where the buffer came from: pooled "
                "(one an earlier frame had touched, kept by the pool) or "
                "fresh (allocated for this frame: every page faulted in "
                "as the decoder writes it)",
            ).inc(frames)

    def record_codec_workers(self, op: str, split) -> None:
        """What one pool launch's workers did (``LaunchSplit``): the seconds
        they spent on its items, the seconds its items waited from the
        call to a worker's start, and the workers the call held for its
        length (the pool's size times the call's seconds)."""
        self.counter(
            f'flyimg_codec_worker_seconds_total{{op="{op}"}}',
            "Seconds the native codec pool's workers spent on items",
        ).inc(max(float(split.worker_s), 0.0))
        self.counter(
            f'flyimg_codec_worker_wait_seconds_total{{op="{op}"}}',
            "Seconds the native codec pool's items waited from the pool "
            "call to a worker's start",
        ).inc(max(float(split.wait_s), 0.0))
        self.counter(
            f'flyimg_codec_worker_capacity_seconds_total{{op="{op}"}}',
            "Worker-seconds the native codec pool's calls held: workers "
            "times the call's seconds",
        ).inc(max(float(split.workers * split.native_s), 0.0))

    def record_codec_buffers(self, handover: str, buffers: int,
                             nbytes: int) -> None:
        """Native buffers the codec pool's launches handed over, by how:
        ``adopted`` (decoded pixels: the returned array owns the buffer,
        nothing copied) or ``bytes`` (encoded output: copied once into
        ``bytes``, then freed)."""
        self.counter(
            f'flyimg_codec_buffers_total{{handover="{handover}"}}',
            "Native buffers handed over by the codec pool's launches",
        ).inc(buffers)
        self.counter(
            f'flyimg_codec_buffer_bytes_total{{handover="{handover}"}}',
            "Bytes of the native buffers handed over by the codec "
            "pool's launches",
        ).inc(nbytes)

    def record_face_detect_launch(self, stats: dict, boxes: int) -> None:
        """One face-detection aux launch (handler ``_face_detect_launch``):
        the network inputs the convnet detector ran, real (``views``) and
        padded up its batch ladder (``slots``), its forward launches, and
        the boxes kept after NMS. A detector without a forward pass
        (facefind) counts boxes alone."""
        for key, name, text in (
            ("views", "flyimg_face_views_total",
             "Network inputs (views of images) the face detector ran"),
            ("slots", "flyimg_face_view_slots_total",
             "Padded network inputs the face detector's forwards ran"),
            ("forwards", "flyimg_face_forwards_total",
             "Forward launches of the face detector"),
        ):
            if stats.get(key):
                self.counter(name, text).inc(stats[key])
        self.counter(
            "flyimg_face_boxes_total",
            "Face boxes kept after NMS",
        ).inc(boxes)
        for part in FACE_DETECT_PARTS:
            seconds = stats.get(f"{part}_s")
            if seconds is not None:
                self.counter(
                    f'flyimg_face_detect_seconds_total{{part="{part}"}}',
                    "Seconds of face-detection launches by part: stack "
                    "(the views into one padded array), forward (each "
                    "chunk's transfer, forward and read-back) and boxes "
                    "(view boxes mapped back, NMS an image)",
                ).inc(max(float(seconds), 0.0))

    def record_face_pixelate_launch(self, stats: dict) -> None:
        """One ``fb_1`` pixelation aux launch: its images, the padded
        batches they ran in, and the program calls that carried them (a
        chunk a call: ops/pixelate.py)."""
        self.counter(
            "flyimg_face_pixelate_images_total",
            "Images through the batched face-pixelation program",
        ).inc(stats.get("images", 0))
        self.counter(
            "flyimg_face_pixelate_slots_total",
            "Padded image slots the face-pixelation program's calls ran",
        ).inc(stats.get("slots", 0))
        self.counter(
            "flyimg_face_pixelate_launches_total",
            "Calls of the batched face-pixelation program",
        ).inc(stats.get("launches", 0))

    def record_device_gap(self, during: str, seconds: float) -> None:
        self.counter(
            f'flyimg_device_gap_seconds_total{{during="{during}"}}',
            "Seconds since the device controller's first run in which no "
            "transform launch ran, by what the controller was doing "
            "(runtime/devicegaps.py); aux_overlap: of those, the seconds "
            "an aux runner ran",
        ).inc(max(float(seconds), 0.0))

    def record_compile_event(self, cache_hit: bool) -> None:
        """Batched-program compile cache outcome per device batch."""
        result = "hit" if cache_hit else "miss"
        self.counter(
            f'flyimg_compile_events_total{{result="{result}"}}',
            "Device-program compile cache outcomes per batch",
        ).inc()

    def record_cache(self, hit: bool) -> None:
        self.counter(
            f'flyimg_cache_total{{result="{"hit" if hit else "miss"}"}}',
            "Output-cache lookups",
        ).inc()

    # -- resilience counters (runtime/resilience.py) -----------------------

    def record_retry(self, point: str) -> None:
        self.counter(
            f'flyimg_retries_total{{point="{escape_label_value(point)}"}}',
            "Transient-failure retries by pipeline point",
        ).inc()

    def record_breaker(self, host: str, state: str) -> None:
        # host derives from a client-controlled URL: escape it so a crafted
        # value cannot break the exposition format
        safe = escape_label_value(host)
        self.counter(
            f'flyimg_breaker_transitions_total{{host="{safe}",to="{state}"}}',
            "Circuit-breaker state transitions by upstream host",
        ).inc()

    def record_shed(self, reason: str) -> None:
        self.counter(
            f'flyimg_shed_total{{reason="{escape_label_value(reason)}"}}',
            "Requests shed by admission control / open circuits",
        ).inc()

    def record_deadline_hit(self, stage: str) -> None:
        self.counter(
            "flyimg_deadline_exceeded_total"
            f'{{stage="{escape_label_value(stage)}"}}',
            "Requests that exhausted their latency budget, by stage",
        ).inc()

    # -- batch failure-containment counters (runtime/batcher.py;
    # docs/resilience.md) --------------------------------------------------

    def record_batch_retry(self) -> None:
        self.counter(
            "flyimg_batch_retries_total",
            "Whole-batch re-executions after transient device failures",
        ).inc()

    def record_poison_isolated(self) -> None:
        self.counter(
            "flyimg_poison_isolated_total",
            "Poison batch members isolated by bisection (innocents saved)",
        ).inc()

    def record_quarantine_hit(self) -> None:
        self.counter(
            "flyimg_quarantine_hits_total",
            "Submissions short-circuited by the poison quarantine table",
        ).inc()

    def record_executor_restart(self, reason: str) -> None:
        self.counter(
            "flyimg_executor_restarts_total"
            f'{{reason="{escape_label_value(reason)}"}}',
            "Batch executor threads replaced by self-healing (dead/wedged)",
        ).inc()

    def record_cache_corrupt(self) -> None:
        self.counter(
            "flyimg_cache_corrupt_total",
            "Cached outputs that failed read-time integrity validation",
        ).inc()

    def record_batch(self, images: int, capacity: int) -> None:
        self.counter(
            "flyimg_batches_total", "Device batches executed"
        ).inc()
        self.counter(
            "flyimg_images_processed_total", "Images through the device"
        ).inc(images)
        self.counter(
            "flyimg_batch_slots_total", "Padded batch slots (occupancy denom)"
        ).inc(capacity)

    def record_batch_launch(
        self,
        controller: str,
        *,
        images: int,
        capacity: int,
        queue_wait_s: float,
        device_s: Optional[float] = None,
        compile_hit: Optional[bool] = None,
        trace_id: Optional[str] = None,
        aux: bool = False,
    ) -> None:
        """THE per-launch efficiency record (runtime/batcher.py, primary
        and recovery launches alike): feeds the global batch counters
        (transform launches only — aux items are counted by their own
        family), the per-controller occupancy/bucket/queue-wait
        histograms, and the rolling efficiency window behind
        ``/debug/perf``. ``compile_hit`` is None for launches with no
        compile step (aux runners)."""
        if not aux:
            self.record_batch(images, capacity)
        safe = escape_label_value(controller)
        self.histogram(
            f'flyimg_batch_occupancy_ratio{{controller="{safe}"}}',
            "Per-launch batch occupancy (images / padded slots)",
            bounds=OCCUPANCY_BOUNDS,
        ).observe(images / capacity if capacity else 0.0)
        self.histogram(
            f'flyimg_batch_bucket_size{{controller="{safe}"}}',
            "Padded batch-bucket sizes actually launched",
            bounds=BATCH_SIZE_BOUNDS,
        ).observe(float(capacity))
        self.histogram(
            f'flyimg_batch_queue_wait_seconds{{controller="{safe}"}}',
            "Oldest-member queue wait at launch time",
        ).observe(
            max(float(queue_wait_s), 0.0),
            trace_id=trace_id if self.exemplars_enabled else None,
        )
        self.batch_efficiency(controller).record(
            images=images, capacity=capacity, queue_wait_s=queue_wait_s,
            device_s=device_s, compile_hit=compile_hit,
        )

    # -- rendering ---------------------------------------------------------

    def render_prometheus(self, openmetrics: bool = False) -> str:
        """Prometheus text exposition. Metric objects are stored per
        label-set, so rendering groups them into families (one HELP/TYPE
        block per bare metric name, all samples contiguous) as the
        exposition format requires.

        ``openmetrics=True`` (the Accept-negotiated scrape) additionally
        emits bucket exemplars and the ``# EOF`` terminator. The default
        text/plain rendering stays pure 0.0.4: the classic format has NO
        exemplar syntax, and a stock Prometheus text parser aborts the
        whole scrape on a trailing ``# {...}`` token — exemplars must
        only reach clients that negotiated for them (service/app.py)."""
        lines: List[str] = []
        with self._lock:
            counters = list(self._counters.values())
            gauges = list(self._gauges.values())
            histograms = list(self._histograms.values())

        for family in _families(counters):
            head = family[0]
            if head.help:
                lines.append(f"# HELP {_bare(head.name)} {head.help}")
                lines.append(f"# TYPE {_bare(head.name)} counter")
            for c in family:
                lines.append(f"{c.name} {_fmt(c.value)}")

        for family in _families(gauges):
            head = family[0]
            if head.help:
                lines.append(f"# HELP {_bare(head.name)} {head.help}")
                lines.append(f"# TYPE {_bare(head.name)} gauge")
            for g in family:
                lines.append(f"{g.name} {_fmt(g.value)}")

        for family in _families(histograms):
            head = family[0]
            bare = _bare(head.name)
            if head.help:
                lines.append(f"# HELP {bare} {head.help}")
                lines.append(f"# TYPE {bare} histogram")
            for h in family:
                counts, total, n = h.snapshot()
                exemplars = (
                    h.exemplars()
                    if openmetrics and self.exemplars_enabled else {}
                )
                acc = 0
                for i, count in enumerate(counts):
                    acc += count
                    le = (
                        f"{h.bounds[i]:.6f}" if i < len(h.bounds) else "+Inf"
                    )
                    line = (
                        f'{_with_label(h.name, "le", le, suffix="_bucket")} '
                        f"{acc}"
                    )
                    ex = exemplars.get(i)
                    if ex is not None:
                        # OpenMetrics exemplar: ` # {labels} value ts` —
                        # bucket lines ONLY (the conformance test pins
                        # this); links the bucket to one kept trace
                        value, trace_id, ts = ex
                        line += (
                            f' # {{trace_id="{escape_label_value(trace_id)}"'
                            f"}} {_fmt(value)} {ts:.3f}"
                        )
                    lines.append(line)
                lines.append(f"{_suffixed(h.name, '_sum')} {_fmt(total)}")
                lines.append(f"{_suffixed(h.name, '_count')} {n}")
        lines.append("# HELP flyimg_uptime_seconds Process uptime")
        lines.append("# TYPE flyimg_uptime_seconds gauge")
        lines.append(
            f"flyimg_uptime_seconds {_fmt(time.time() - self.started_at)}"
        )
        if openmetrics:
            lines.append("# EOF")
        return "\n".join(lines) + "\n"

    def summary(self) -> Dict[str, float]:
        """Human/JSON view: key counters + p50/p99 per stage, plus the
        rolling batch-efficiency windows and (when an SLO engine is
        attached) the burn rates and budget — one vocabulary shared by
        bulk sweeps, /debug/perf, and /debug/slo."""
        out: Dict[str, float] = {}
        with self._lock:
            counters = dict(self._counters)
            histograms = dict(self._histograms)
            batch_eff = dict(self._batch_eff)
            slo = self._slo
        for name, c in counters.items():
            out[name] = c.value
        for name, h in histograms.items():
            out[f"{name}:p50"] = h.quantile(0.5)
            out[f"{name}:p99"] = h.quantile(0.99)
        for name, eff in batch_eff.items():
            stats = eff.stats()
            for key in (
                "mean_occupancy", "padding_waste", "queue_wait_share",
                "batches_per_compile_miss",
            ):
                out[f"batch_efficiency:{name}:{key}"] = stats[key]
        if slo is not None and getattr(slo, "enabled", False):
            for key, value in slo.summary_fields().items():
                out[f"slo:{key}"] = value
        # per-plan cost ledger aggregates (runtime/costledger.py): the
        # same attribution vocabulary /debug/plans serves, folded in so
        # bulk sweeps and bench artifacts carry FLOP/byte accounting
        try:
            from flyimg_tpu.runtime.costledger import get_ledger

            for key, value in get_ledger().aggregates().items():
                out[f"plan_ledger:{key}"] = value
        except Exception:
            pass  # accounting must never fail a summary
        return out

    def perf_snapshot(self) -> Dict[str, object]:
        """The /debug/perf JSON document: per-controller rolling batch
        efficiency plus per-stage and device-time quantiles — the answers
        "Beyond Inference" says dominate vision-serving latency (queueing,
        padding, host codec), in one operator-readable place."""
        with self._lock:
            histograms = dict(self._histograms)
            batch_eff = dict(self._batch_eff)

        def _ms(seconds: float) -> Optional[float]:
            if seconds != seconds or seconds == float("inf"):
                return None  # overflow-bucket quantile: no upper bound
            return round(seconds * 1000.0, 3)

        stages: Dict[str, Dict[str, object]] = {}
        for name, h in histograms.items():
            match = re.match(r'flyimg_stage_seconds\{stage="([^"]*)"\}', name)
            if match is None:
                continue
            _, _, n = h.snapshot()
            stages[match.group(1)] = {
                "count": n,
                "p50_ms": _ms(h.quantile(0.5)),
                "p99_ms": _ms(h.quantile(0.99)),
            }
        device = histograms.get("flyimg_device_seconds")
        device_doc = None
        if device is not None:
            _, _, n = device.snapshot()
            device_doc = {
                "batches": n,
                "p50_ms": _ms(device.quantile(0.5)),
                "p99_ms": _ms(device.quantile(0.99)),
            }
        controllers = {}
        for name, eff in batch_eff.items():
            stats = eff.stats()
            controllers[name] = {
                key: (round(value, 4) if isinstance(value, float) else value)
                for key, value in stats.items()
            }
        return {
            "controllers": controllers,
            "stages": stages,
            "device": device_doc,
        }


class GcWatch:
    """The process's garbage collections into one registry, while open:
    ``flyimg_gc_seconds_total{generation}`` and
    ``flyimg_gc_collections_total{generation}``, and a log line naming the
    thread each full (generation 2) collection ran on. One ``gc.callbacks``
    entry serves every open watch and is removed with the last. The
    collector calls it with the lock of whatever the thread was doing held,
    a registry's among them, so the counters are made when the watch opens
    and the callback takes no registry lock."""

    def __init__(self, registry: "MetricsRegistry") -> None:
        self._counters = {}
        for generation in range(3):
            self._counters[generation] = (
                registry.counter(
                    f'flyimg_gc_seconds_total{{generation="{generation}"}}',
                    "Seconds the process spent in the garbage collector, "
                    "by the generation collected",
                ),
                registry.counter(
                    "flyimg_gc_collections_total"
                    f'{{generation="{generation}"}}',
                    "Garbage collections, by the generation collected",
                ),
            )
        _gc_hook.add(self)

    def record(self, generation: int, seconds: float) -> None:
        counters = self._counters.get(generation)
        if counters is not None:
            counters[0].inc(seconds)
            counters[1].inc()

    def close(self) -> None:
        _gc_hook.discard(self)


class _GcHook:
    """The one ``gc.callbacks`` entry behind every open ``GcWatch``. The
    collector runs one collection at a time and calls ``start`` and
    ``stop`` on the thread that triggered it."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._watches: Tuple = ()
        self._started: Optional[float] = None

    def add(self, watch: GcWatch) -> None:
        import gc
        import weakref

        with self._lock:
            if self not in gc.callbacks:
                gc.callbacks.append(self)
            self._watches = tuple(
                ref for ref in self._watches if ref() is not None
            ) + (weakref.ref(watch),)

    def discard(self, watch: GcWatch) -> None:
        import gc

        with self._lock:
            kept = tuple(
                ref for ref in self._watches
                if ref() is not None and ref() is not watch
            )
            if not kept and self in gc.callbacks:
                gc.callbacks.remove(self)
            self._watches = kept

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._started = time.perf_counter()
            return
        started, self._started = self._started, None
        if started is None:
            return
        seconds = time.perf_counter() - started
        generation = info.get("generation", 0)
        for ref in self._watches:
            watch = ref()
            if watch is not None:
                watch.record(generation, seconds)
        if generation == 2:
            import logging

            logging.getLogger("flyimg.gc").info(
                "gc.full %.6f s on thread %s", seconds,
                threading.current_thread().name,
            )


_gc_hook = _GcHook()


#: the parts of a face-detection launch (models/blazeface.py
#: ``detect_prepared``: ``stats["<part>_s"]``)
FACE_DETECT_PARTS = ("stack", "forward", "boxes")


# one histogram per phase of a transform launch (runtime/batcher.py
# _Launch; docs/observability.md "Launch phases"). The fill wait is
# flyimg_batch_queue_wait_seconds{controller=} (record_batch_launch).
_TRANSFER_HELP = (
    "Host<->device transfer time per batch launch, by direction: h2d "
    "from the start of the staging call until the staged inputs are on "
    "the device, d2h from the output being ready until it is on the host"
)
_LAUNCH_PHASE_HISTOGRAMS = (
    ("assemble", "flyimg_batch_assemble_seconds",
     "Per transform launch: the phase around _assemble on the launching "
     "thread: the per-member scalars and the pad slots, and the zero-fill "
     "and copy of every frame only where submit had not already copied "
     "them into the launch's block (flyimg_batch_member_copy_seconds)"),
    ("slot_wait", "flyimg_batch_slot_wait_seconds",
     "Per transform launch: waiting for a pipeline slot "
     "(batch_pipeline_depth launches between dispatch and read-back)"),
    ("h2d", 'flyimg_device_transfer_seconds{direction="h2d"}',
     _TRANSFER_HELP),
    ("dispatch", "flyimg_device_dispatch_seconds",
     "Asynchronous dispatch (launch enqueue) time per batch; "
     "includes the synchronous XLA compile on a miss"),
    ("run", "flyimg_device_run_seconds",
     "Per transform launch: staged inputs on the device until the output "
     "is ready"),
    ("d2h", 'flyimg_device_transfer_seconds{direction="d2h"}',
     _TRANSFER_HELP),
)


def _families(metrics: Iterable) -> List[List]:
    """Group metric objects by bare family name, preserving first-seen
    order of families and of members within a family."""
    grouped: Dict[str, List] = {}
    for metric in metrics:
        grouped.setdefault(_bare(metric.name), []).append(metric)
    return list(grouped.values())


def _bare(name: str) -> str:
    return name.split("{", 1)[0]


def _suffixed(name: str, suffix: str) -> str:
    if "{" in name:
        head, rest = name.split("{", 1)
        return f"{head}{suffix}{{{rest}"
    return name + suffix


def _with_label(name: str, key: str, value: str, suffix: str = "") -> str:
    if "{" in name:
        head, rest = name.split("{", 1)
        rest = rest.rstrip("}")
        return f'{head}{suffix}{{{rest},{key}="{value}"}}'
    return f'{name}{suffix}{{{key}="{value}"}}'


def _fmt(value: float) -> str:
    if value != value:  # NaN (a dead gauge callback): int() would raise
        return "NaN"
    if value in (float("inf"), float("-inf")):
        return "+Inf" if value > 0 else "-Inf"
    if value == int(value) and abs(value) < 1e15:
        return str(int(value))
    return repr(value)
