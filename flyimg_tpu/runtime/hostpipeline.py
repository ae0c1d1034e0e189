"""Host pipeline: explicit bounded per-stage worker pools for the miss
path's host work (fetch I/O, decode, encode), with backpressure between
stages.

Why ("Beyond Inference", arXiv 2403.12981; docs/host-pipeline.md): host
overheads — not the accelerator — dominate CV serving, and the naive
shape runs every miss's fetch -> decode -> batch -> device -> encode
sequentially inside one HTTP worker thread. With N server threads, N
concurrent misses run N concurrent native decodes: CPU-bound codec work
oversubscribes the host while the device sits idle, and nothing bounds
or even measures the queueing. This module is the Bi-criteria Pipeline
Mapping shape (arXiv 0801.1772): each stage gets its OWN bounded worker
pool, so

- decode of request N overlaps device execution of request N-1 whatever
  the HTTP thread count (the request thread parks on a stage future
  while stage workers run the CPU-bound work at a bounded parallelism),
- concurrent decode-stage tasks land in the codec batcher together and
  coalesce into ONE native-pool ``batch_jpeg_decode`` call,
- saturation is explicit: each stage queue is bounded and sheds through
  the SAME AdmissionGate the batch controllers use (503 + Retry-After,
  ``flyimg_shed_total{reason=}``) instead of silently queueing, and
- the observatory sees it: ``flyimg_host_pool_queue_depth{pool=}``
  gauges, per-stage queue-wait histograms, span events, flight-recorder
  ``host_stage`` records for tasks that actually waited, and the
  brownout engine consumes stage queue depth as a pressure signal.

Self-healing mirrors the batch executor (runtime/batcher.py): a DEAD
worker thread is replaced at the next submit, and a WEDGED one (inside a
task longer than ``wedge_timeout_s`` — e.g. a native decode hung on
hostile bytes) is abandoned and replaced so the stage keeps its
parallelism; the wedged task's caller is bounded by its own deadline.

Everything is inert with ``host_pipeline_enable`` off: the handler runs
stages inline exactly as before (byte-identical serving, pinned by
tests/test_host_pipeline.py).
"""

from __future__ import annotations

import queue
import threading
import time
from concurrent.futures import Future
from typing import Callable, Dict, List, Optional, Tuple

from flyimg_tpu.runtime import tracing
from flyimg_tpu.runtime.resilience import AdmissionGate

__all__ = ["StagePool", "HostPipeline", "STAGES"]

#: the miss path's host stages, in pipeline order
STAGES = ("fetch", "decode", "encode")


class _Task:
    __slots__ = ("fn", "future", "enqueued_at", "trace")

    def __init__(self, fn: Callable, trace) -> None:
        self.fn = fn
        self.future: Future = Future()
        self.enqueued_at = time.monotonic()
        self.trace = trace


class StagePool:
    """One bounded worker pool for one host pipeline stage.

    ``submit`` admits through an :class:`AdmissionGate` bounded at
    ``workers + queue_depth`` pending tasks — over that it sheds with a
    typed 503 (the existing load-shedding contract) rather than growing
    an invisible queue. Each task's queue wait (submit -> worker pickup)
    feeds ``flyimg_host_pool_queue_wait_seconds{pool=}`` and, when the
    task actually waited (>= ``FLIGHT_WAIT_MIN_S``), one ``host_stage``
    flight-recorder record — the backpressure evidence an operator wants
    next to the device launches in the same ring.
    """

    #: only queue waits at least this long are worth a flight-recorder
    #: row: sub-millisecond pickups are the healthy steady state and
    #: would drown the launch records the ring exists for
    FLIGHT_WAIT_MIN_S = 0.005

    def __init__(
        self,
        name: str,
        *,
        workers: int = 2,
        queue_depth: int = 16,
        wedge_timeout_s: float = 60.0,
        shed_retry_after_s: float = 1.0,
        metrics=None,
        flight_recorder=None,
    ) -> None:
        self.name = name
        self.workers = max(1, int(workers))
        self.queue_depth = max(1, int(queue_depth))
        self.wedge_timeout_s = max(float(wedge_timeout_s), 0.0)
        self.metrics = metrics
        self.flight_recorder = flight_recorder
        self.admission = AdmissionGate(
            max_pending=self.workers + self.queue_depth,
            retry_after_s=shed_retry_after_s,
            name=f"host {name} pool",
            metrics=metrics,
        )
        self._queue: "queue.Queue[Optional[_Task]]" = queue.Queue()
        self._lock = threading.Lock()
        self._stop = False
        # worker bookkeeping for self-healing: thread -> (busy-since
        # monotonic time, running task), or None when idle. A replaced/
        # wedged thread is dropped from the dict; it notices on its next
        # loop turn and exits (or stays wedged, abandoned, until process
        # exit). The running task rides along so abandoning a wedged
        # worker can FAIL its future — the caller unblocks AND the
        # admission slot frees (the done-callback releases it); a wedge
        # must shrink neither the stage's capacity nor its pressure
        # accounting forever.
        self._busy: Dict[
            threading.Thread, Optional[Tuple[float, _Task]]
        ] = {}
        for _ in range(self.workers):
            self._spawn_worker()

    def _spawn_worker(self) -> threading.Thread:
        thread = threading.Thread(
            target=self._run, name=f"flyimg-host-{self.name}", daemon=True
        )
        with self._lock:
            self._busy[thread] = None
        thread.start()
        return thread

    # -- worker loop -------------------------------------------------------

    def _run(self) -> None:
        me = threading.current_thread()
        while True:
            task = self._queue.get()
            superseded = False
            with self._lock:
                if me not in self._busy:
                    superseded = True
                elif task is None:  # close()'s stop sentinel
                    self._busy.pop(me, None)
                    return
                else:
                    self._busy[me] = (time.monotonic(), task)
            if superseded:
                # superseded by self-healing: hand the task to a live
                # worker (outside the lock; the queue is unbounded but
                # the lock-held-blocking-call discipline still applies)
                # and exit. A ``None`` is one of close()'s per-LIVE-worker
                # stop sentinels — re-put it too, or the live worker it
                # was meant for parks for the whole drain budget.
                self._queue.put(task)
                return
            wait_s = time.monotonic() - task.enqueued_at
            self._record_wait(task, wait_s)
            try:
                with tracing.activate(task.trace):
                    result = task.fn()
            except BaseException as exc:
                if not task.future.done():
                    task.future.set_exception(exc)
            else:
                if not task.future.done():
                    task.future.set_result(result)
            finally:
                with self._lock:
                    if me in self._busy:
                        self._busy[me] = None

    def _record_wait(self, task: _Task, wait_s: float) -> None:
        if self.metrics is not None:
            from flyimg_tpu.runtime.metrics import escape_label_value

            self.metrics.histogram(
                "flyimg_host_pool_queue_wait_seconds"
                f'{{pool="{escape_label_value(self.name)}"}}',
                "Host stage-pool queue wait, task submit to worker pickup",
            ).observe(
                max(wait_s, 0.0),
                trace_id=(
                    task.trace.trace_id if task.trace is not None else None
                ),
            )
        if (
            self.flight_recorder is not None
            and wait_s >= self.FLIGHT_WAIT_MIN_S
        ):
            # backpressure evidence only: healthy sub-ms pickups stay out
            # of the ring (it exists for the launches around an incident)
            self.flight_recorder.record(
                controller=f"host:{self.name}",
                batch_id=None,
                plan_key=None,
                occupancy=1,
                capacity=1,
                queue_wait_s=wait_s,
                kind="host_stage",
                stage=self.name,
                trace_id=(
                    task.trace.trace_id if task.trace is not None else None
                ),
            )

    # -- submission --------------------------------------------------------

    def submit(self, fn: Callable) -> Future:
        """Queue ``fn`` for a stage worker; returns its Future. Sheds a
        typed 503 through the admission gate when the stage is saturated;
        heals dead/wedged workers first so a sick pool cannot strand the
        queue."""
        with self._lock:
            if self._stop:
                raise RuntimeError(f"host {self.name} pool is closed")
        self._heal_workers()
        self.admission.acquire()
        task = _Task(fn, tracing.current_trace())
        task.future.add_done_callback(lambda _f: self.admission.release())
        try:
            self._queue.put(task)
        except BaseException:
            if not task.future.done():
                self.admission.release()
            raise
        return task.future

    def _heal_workers(self) -> None:
        """Replace dead workers, abandon + replace wedged ones (inside a
        task longer than ``wedge_timeout_s``). Checked at submit time
        like the batch executor's heal — no watchdog thread to leak."""
        now = time.monotonic()
        respawn = 0
        wedged_tasks: List[_Task] = []
        with self._lock:
            if self._stop:
                return
            for thread in list(self._busy):
                entry = self._busy[thread]
                reason = None
                if not thread.is_alive():
                    reason = "dead"
                elif (
                    self.wedge_timeout_s > 0
                    and entry is not None
                    and now - entry[0] > self.wedge_timeout_s
                ):
                    reason = "wedged"
                if reason is None:
                    continue
                # abandon: the thread no longer counts toward the pool;
                # a wedged one that eventually finishes sees itself gone
                # from _busy and exits
                self._busy.pop(thread, None)
                respawn += 1
                if reason == "wedged" and entry is not None:
                    wedged_tasks.append(entry[1])
                if self.metrics is not None:
                    from flyimg_tpu.runtime.metrics import (
                        escape_label_value,
                    )

                    self.metrics.counter(
                        "flyimg_host_pool_worker_restarts_total"
                        f'{{pool="{escape_label_value(self.name)}",'
                        f'reason="{reason}"}}',
                        "Host stage-pool workers replaced by self-healing",
                    ).inc()
                tracing.add_event(
                    "host_pool.worker_restart", pool=self.name,
                    reason=reason,
                )
        for task in wedged_tasks:
            # fail the wedged task's future (outside the lock: future
            # callbacks run inline) so its caller unblocks with a typed
            # error and the done-callback RELEASES its admission slot —
            # otherwise every wedge permanently consumed one slot until
            # the stage shed everything. The abandoned worker finishing
            # late is harmless: its resolution paths are done()-guarded.
            if not task.future.done():
                task.future.set_exception(
                    TimeoutError(
                        f"host {self.name} pool worker wedged; task "
                        "abandoned"
                    )
                )
        for _ in range(respawn):
            self._spawn_worker()

    # -- introspection -----------------------------------------------------

    @property
    def pending(self) -> int:
        """Admitted-and-unresolved tasks (queued or executing) — the
        queue-depth gauge and the brownout pressure signal."""
        return self.admission.pending

    def stats(self) -> Dict[str, float]:
        with self._lock:
            busy = sum(
                1 for entry in self._busy.values() if entry is not None
            )
            workers = len(self._busy)
        return {
            "workers": float(workers),
            "busy": float(busy),
            "pending": float(self.pending),
            "bound": float(self.workers + self.queue_depth),
        }

    def close(self, drain_timeout_s: float = 10.0) -> None:
        """Stop accepting work and drain: queued tasks complete (bounded
        by the drain budget), then workers exit on their stop sentinel.
        Stranded tasks (wedged worker, budget exhausted) get a typed
        TimeoutError instead of hanging their callers forever."""
        with self._lock:
            if self._stop:
                return
            self._stop = True
            workers = list(self._busy)
        for _ in workers:
            self._queue.put(None)  # one stop sentinel per worker
        deadline = time.monotonic() + max(drain_timeout_s, 0.0)
        for thread in workers:
            thread.join(timeout=max(deadline - time.monotonic(), 0.0))
        # fail whatever never ran (the queue may still hold tasks if
        # workers were wedged or the budget ran out)
        while True:
            try:
                task = self._queue.get_nowait()
            except queue.Empty:
                break
            if task is not None and not task.future.done():
                task.future.set_exception(
                    TimeoutError(
                        f"host {self.name} pool closed before the task ran"
                    )
                )


class HostPipeline:
    """The miss path's stage pools (fetch / decode / encode) as one
    wired object. ``enabled`` False means the handler never touches the
    pools — the off state is the exact pre-pipeline behavior."""

    def __init__(
        self,
        *,
        enabled: bool = False,
        fetch_workers: int = 4,
        decode_workers: int = 2,
        encode_workers: int = 2,
        queue_depth: int = 16,
        wedge_timeout_s: float = 60.0,
        shed_retry_after_s: float = 1.0,
        metrics=None,
        flight_recorder=None,
    ) -> None:
        self.enabled = bool(enabled)
        self._pools: Dict[str, StagePool] = {}
        if not self.enabled:
            return
        for name, workers in (
            ("fetch", fetch_workers),
            ("decode", decode_workers),
            ("encode", encode_workers),
        ):
            self._pools[name] = StagePool(
                name,
                workers=workers,
                queue_depth=queue_depth,
                wedge_timeout_s=wedge_timeout_s,
                shed_retry_after_s=shed_retry_after_s,
                metrics=metrics,
                flight_recorder=flight_recorder,
            )

    @classmethod
    def from_params(cls, params, *, metrics=None,
                    flight_recorder=None) -> "HostPipeline":
        return cls(
            enabled=bool(params.by_key("host_pipeline_enable", False)),
            fetch_workers=int(
                params.by_key("host_pipeline_fetch_workers", 4)
            ),
            decode_workers=int(
                params.by_key("host_pipeline_decode_workers", 2)
            ),
            encode_workers=int(
                params.by_key("host_pipeline_encode_workers", 2)
            ),
            queue_depth=int(params.by_key("host_pipeline_queue_depth", 16)),
            wedge_timeout_s=float(
                params.by_key("host_pipeline_wedge_timeout_s", 60.0)
            ),
            shed_retry_after_s=float(
                params.by_key("shed_retry_after_s", 1.0)
            ),
            metrics=metrics,
            flight_recorder=flight_recorder,
        )

    def pool(self, stage: str) -> Optional[StagePool]:
        return self._pools.get(stage)

    def pools(self) -> List[Tuple[str, StagePool]]:
        return list(self._pools.items())

    def pressure(self) -> float:
        """Max stage saturation in [0, ...]: pending / bound per pool —
        the brownout engine's host-stage pressure component (1.0 = some
        stage is at its admission bound)."""
        worst = 0.0
        for pool in self._pools.values():
            bound = pool.workers + pool.queue_depth
            if bound > 0:
                worst = max(worst, pool.pending / bound)
        return worst

    def run(self, stage: str, fn: Callable, *, timeout: Optional[float]):
        """Run ``fn`` on the stage's pool and wait (bounded) for the
        result — the handler's one call site per stage. Falls through to
        an inline call when the pipeline is off or the stage is unknown.
        A timeout surfaces as ``concurrent.futures.TimeoutError`` for
        the caller's deadline/wedge handling (the task itself keeps its
        worker until it finishes; the heal path replaces the worker if
        it never does)."""
        pool = self._pools.get(stage)
        if pool is None:
            return fn()
        future = pool.submit(fn)
        tracing.add_event(
            "host_pipeline.staged", stage=stage, pending=pool.pending,
        )
        return future.result(timeout=timeout)

    def close(self, drain_timeout_s: float = 10.0) -> None:
        for pool in self._pools.values():
            pool.close(drain_timeout_s)

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        return {name: pool.stats() for name, pool in self._pools.items()}
