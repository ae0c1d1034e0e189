"""Backend supervisor: device-loss detection, CPU failover, re-promotion.

The stack already contains poison inputs (PR 3, per-batch bisection),
overload (PR 5, brownout), and replica faults (PR 12, fleet fallback) —
but not the accelerator backend dying mid-serve.
``classify_batch_error`` labels the individual XLA transients,
and the batcher retries each batch, but nothing acts on a *storm* of
them: a dead libtpu keeps every miss burning ``batch_retries`` ×
backoff before failing, forever, until an operator restarts the
process. A TPU-native server that bricks when the device resets is not
production-scale; orchestrated serving (AlpaServe-style SLO-aware
tiers, the PATCHEDSERVE patch-management framing — PAPERS.md) assumes
replicas *degrade and re-join* rather than wedge.

``DeviceSupervisor`` is the missing layer between PR 3's per-batch
containment and PR 12's per-replica fallback:

- **Storm detection.** The batcher's existing launch/recovery
  resolution sites feed it outcomes: each classified-TRANSIENT batch
  failure counts, each successful launch resets. When
  ``device_storm_threshold`` consecutive transient failures land within
  ``device_storm_window_s`` (both conditions — a slow trickle over
  hours is the per-batch retry's job, not a storm), the **backend
  breaker** trips. Distinct from per-batch retry, which PR 3 owns: the
  supervisor never re-executes anything, it decides the *backend* is
  sick.
- **Failover.** A worker thread (never a request thread) drains the
  in-flight device batches (bounded by ``device_failover_drain_s``;
  leftovers are timeout-stamped like a shutdown drain), switches the
  process backend to CPU where a real accelerator was selected
  (no-op when the default backend already is the CPU — the test
  topology), rebuilds the batcher's executor against the new backend
  (mesh swapped, fresh pipeline semaphore, queued groups re-homed), and
  invalidates BOTH program caches so no executable compiled against the
  dead backend is ever called again. Misses keep serving — on CPU,
  tagged ``X-Flyimg-Degraded: cpu-fallback`` and never cached at the
  device-quality keys (a cached CPU render would mask re-promotion);
  cache hits never notice.
- **Re-promotion.** A background prober re-attempts device init every
  ``device_probe_interval_s`` in a disposable child process
  (``parallel/mesh.probe_device_backend`` — the child must finish a
  computation on a non-CPU backend; a probe exception is a recorded
  outcome, never a crash). The child needs the chip to itself, so the
  probe can only pass once the failover dropped this process's own
  accelerator backend. ``device_probe_hysteresis`` consecutive
  clean probes re-promote atomically: backend restored, mesh rebuilt,
  program caches invalidated again (re-promotion compiles are a named,
  expected family — repeating known key values is clean under the
  retrace sentinel).

Health is exported end to end: the ``flyimg_device_health`` gauge
(1 → 0 → 1), ``flyimg_backend_failovers_total{to=cpu|device}``,
``flyimg_backend_probe_total{outcome=}``, ``device.failover`` /
``device.repromote`` span events (drained onto the next evaluated
request, like brownout transitions), ``/readyz``'s ``device`` field and
the debug-gated ``/debug/device`` snapshot; ``FleetRouter`` skips
owners whose health endpoint reports device-down (runtime/fleet.py),
and the brownout engine gains a ``device_health`` pressure component so
degradation reacts to the slower render path (docs/degradation.md).

Default OFF (``device_supervisor_enable: false``): disabled, the
batcher carries no supervisor reference, no metrics register, no
threads exist, and serving is byte-identical (pinned by
tests/test_device_supervisor.py).
"""

from __future__ import annotations

import collections
import logging
import threading
import time
from typing import Callable, Deque, Dict, List, Optional

from flyimg_tpu.runtime import tracing
from flyimg_tpu.runtime.resilience import TRANSIENT

__all__ = ["DeviceSupervisor", "DEVICE", "CPU_FALLBACK"]

SUPERVISOR_LOGGER = "flyimg.device"

#: supervisor states: the backend serving device batches right now
DEVICE, CPU_FALLBACK = "device", "cpu-fallback"


class DeviceSupervisor:
    """The backend breaker + failover/re-promotion state machine."""

    def __init__(
        self,
        *,
        enabled: bool = False,
        storm_threshold: int = 5,
        storm_window_s: float = 30.0,
        probe_interval_s: float = 5.0,
        probe_timeout_s: float = 75.0,
        probe_hysteresis: int = 2,
        failover_drain_s: float = 10.0,
        metrics=None,
        clock: Callable[[], float] = time.monotonic,
    ) -> None:
        self.enabled = bool(enabled)
        self.storm_threshold = max(1, int(storm_threshold))
        self.storm_window_s = max(float(storm_window_s), 0.001)
        self.probe_interval_s = max(float(probe_interval_s), 0.05)
        self.probe_timeout_s = float(probe_timeout_s)
        self.probe_hysteresis = max(1, int(probe_hysteresis))
        self.failover_drain_s = max(float(failover_drain_s), 0.0)
        self._metrics = metrics
        self._clock = clock
        self._lock = threading.Lock()
        self._state = DEVICE
        self._state_since = clock()
        # storm bookkeeping: consecutive transient failures (reset by any
        # success) AND their timestamps (the rate half — the threshold
        # failures must fall inside the window)
        self._consecutive = 0
        self._window: Deque[float] = collections.deque()
        self._failing_over = False
        self._repromoting = False
        # probe bookkeeping
        self._clean_probes = 0
        self._last_probe_at: Optional[float] = None
        self._last_probe_outcome: Optional[str] = None
        self._probes_total = 0
        self._failovers = 0
        self._repromotions = 0
        # flap damping: a backend that passes the (small) compute probe
        # but storms again under real batches would otherwise cycle
        # failover<->re-promotion forever, paying a full program-cache
        # recompile every ~2 probes. A failover landing within
        # ``flap_window_s`` of the last re-promotion doubles the clean
        # probes required for the NEXT re-promotion (capped 8x); a
        # failover after a long healthy stretch resets the multiplier.
        self.flap_window_s = self.storm_window_s * 10.0
        self._hysteresis_mult = 1
        self._last_repromote_at: Optional[float] = None
        # span events queued by worker/prober threads (no ambient trace
        # there), drained onto the next evaluated request — the same
        # discipline as brownout transition notifications
        self._pending_events: List[Dict[str, object]] = []
        # wiring (attach()): the device batch controller and the factory
        # that rebuilds its data-parallel mesh after re-promotion
        self._batcher = None
        self._mesh_factory: Optional[Callable[[], object]] = None
        # prober thread state
        self._prober: Optional[threading.Thread] = None
        self._wake = threading.Event()
        self._closed = False
        # real-hardware backend switch bookkeeping: the JAX_PLATFORMS /
        # XLA_FLAGS selection saved before a forced-CPU swap, restored
        # at re-promotion (None = never switched — the CPU test topology)
        self._saved_selection: Optional[Dict[str, Optional[str]]] = None

    @classmethod
    def from_params(cls, params, *, metrics=None) -> "DeviceSupervisor":
        clock = params.by_key("device_supervisor_clock") or time.monotonic
        return cls(
            enabled=bool(params.by_key("device_supervisor_enable", False)),
            storm_threshold=int(params.by_key("device_storm_threshold", 5)),
            storm_window_s=float(
                params.by_key("device_storm_window_s", 30.0)
            ),
            probe_interval_s=float(
                params.by_key("device_probe_interval_s", 5.0)
            ),
            probe_hysteresis=int(
                params.by_key("device_probe_hysteresis", 2)
            ),
            failover_drain_s=float(
                params.by_key("device_failover_drain_s", 10.0)
            ),
            metrics=metrics,
            clock=clock,
        )

    # -- wiring ------------------------------------------------------------

    def attach(self, *, batcher=None, mesh_factory=None) -> None:
        """Wire the device batch controller (outcome source + failover
        target) and the mesh factory re-promotion rebuilds from
        (service/app.py). Both optional for unit tests."""
        self._batcher = batcher
        self._mesh_factory = mesh_factory

    def register_metrics(self, registry) -> None:
        """The health gauge operators alert on — registered only when
        enabled, so the default-off app's /metrics is byte-identical."""
        registry.gauge(
            "flyimg_device_health",
            "Device backend health: 1 serving on the device backend, "
            "0 failed over to forced-CPU rendering",
            fn=lambda: 1.0 if self._state == DEVICE else 0.0,
        )

    # -- read surface ------------------------------------------------------

    def cpu_forced(self) -> bool:
        """True while misses render on the CPU fallback — the handler's
        degraded-tag gate and the brownout ``device_health`` source."""
        return self.enabled and self._state == CPU_FALLBACK

    def state(self) -> str:
        return self._state

    def snapshot(self) -> Dict[str, object]:
        """The /debug/device document (service/app.py)."""
        with self._lock:
            return {
                "enabled": self.enabled,
                "state": self._state,
                "state_age_s": round(
                    self._clock() - self._state_since, 3
                ),
                "storm": {
                    "threshold": self.storm_threshold,
                    "window_s": self.storm_window_s,
                    "consecutive_transient_failures": self._consecutive,
                    "window_failures": len(self._window),
                },
                "probe": {
                    "interval_s": self.probe_interval_s,
                    "timeout_s": self.probe_timeout_s,
                    "hysteresis": self.probe_hysteresis,
                    "hysteresis_mult": self._hysteresis_mult,
                    "clean_probes": self._clean_probes,
                    "last_outcome": self._last_probe_outcome,
                    "total": self._probes_total,
                },
                "failovers": self._failovers,
                "repromotions": self._repromotions,
            }

    # -- batcher outcome feed ----------------------------------------------

    def record_batch_success(self) -> None:
        """One successful device launch (primary or recovery): the
        backend answered, so any storm-in-progress resets."""
        if not self.enabled:
            return
        with self._lock:
            self._consecutive = 0
            self._window.clear()

    def record_batch_failure(self, kind: str) -> None:
        """One failed device launch, already classified by the batcher
        (runtime/resilience.classify_batch_error). Only TRANSIENT
        failures count toward a storm: poison is a property of an input
        (PR 3 isolates it), transient is a property of the backend
        moment — and a sustained run of those IS the backend dying."""
        if not self.enabled or kind != TRANSIENT:
            return
        trip = False
        with self._lock:
            now = self._clock()
            self._consecutive += 1
            self._window.append(now)
            floor = now - self.storm_window_s
            while self._window and self._window[0] < floor:
                self._window.popleft()
            if (
                self._state == DEVICE
                and not self._failing_over
                and self._consecutive >= self.storm_threshold
                and len(self._window) >= self.storm_threshold
            ):
                self._failing_over = True
                trip = True
        if trip:
            self._trip()

    # -- failover ----------------------------------------------------------

    def _trip(self) -> None:
        """The backend breaker trips: flip state NOW (new misses tag and
        the brownout component engages immediately), then run the heavy
        drain/rebuild on a worker thread — never on the batcher's drain
        thread that delivered the final storm failure."""
        with self._lock:
            now = self._clock()
            self._state = CPU_FALLBACK
            self._state_since = now
            self._failovers += 1
            if (
                self._last_repromote_at is not None
                and now - self._last_repromote_at < self.flap_window_s
            ):
                # the re-promotion did not stick: demand more evidence
                # before the next one (flap damping)
                self._hysteresis_mult = min(self._hysteresis_mult * 2, 8)
            else:
                self._hysteresis_mult = 1
            self._pending_events.append({
                "name": "device.failover",
                "to": "cpu",
                "consecutive_failures": self._consecutive,
            })
        self._record_failover("cpu")
        logging.getLogger(SUPERVISOR_LOGGER).error(
            "device backend failure storm: failing over to CPU rendering",
            extra={
                "event": "device.failover",
                "to": "cpu",
                "consecutive_failures": self._consecutive,
                "storm_threshold": self.storm_threshold,
            },
        )
        self._spawn(self._failover_worker, name="flyimg-device-failover")

    def _spawn(self, target, name: str = "flyimg-device-supervisor") -> None:
        """Run ``target`` on a daemon thread (tests monkeypatch this to
        run inline for determinism). Never called under the lock."""
        threading.Thread(target=target, name=name, daemon=True).start()

    def _failover_worker(self) -> None:
        batcher = self._batcher
        try:
            if batcher is not None:
                # hold NEW launches for the whole switch (submissions
                # keep queueing), then drain in-flight groups (bounded;
                # they are failing against the dead backend and resolve
                # through the containment paths) — the backend switch
                # below must never clear live arrays under a launch,
                # and the still-running old executor must not dispatch
                # a queued group into the half-switched window
                batcher.pause_launches()
                batcher.drain_inflight(self.failover_drain_s)
            self._switch_backend_to_cpu()
            if batcher is not None:
                # swap the mesh to None (single-stream CPU), replace
                # the executor, invalidate the program caches — the
                # batcher owns all of that (failover_backend; its own
                # drain pass is instant on the already-drained registry)
                batcher.failover_backend(
                    None,
                    drain_timeout_s=self.failover_drain_s,
                    reason="device_failover",
                )
        except Exception:
            logging.getLogger(SUPERVISOR_LOGGER).exception(
                "device failover rebuild failed; CPU fallback state stands"
            )
        finally:
            if batcher is not None:
                batcher.resume_launches()
            with self._lock:
                self._failing_over = False
                self._clean_probes = 0
                self._last_probe_at = None
            self._ensure_prober()

    # -- probing / re-promotion --------------------------------------------

    def _ensure_prober(self) -> None:
        """Start the background prober if none is running. The thread
        parks (and exits) once the state returns to DEVICE; a later
        failover starts a fresh one."""
        with self._lock:
            if self._closed or (
                self._prober is not None and self._prober.is_alive()
            ):
                return
            thread = threading.Thread(
                target=self._probe_loop,
                name="flyimg-device-prober",
                daemon=True,
            )
            self._prober = thread
        thread.start()

    def _probe_loop(self) -> None:
        while True:
            self._wake.wait(timeout=self.probe_interval_s)
            self._wake.clear()
            if self._closed:
                return
            with self._lock:
                if (
                    self._state != CPU_FALLBACK
                    or self._repromoting
                    or self._failing_over
                ):
                    # _failing_over: a NEW storm's worker is mid-switch —
                    # probing (and worse, re-promoting) would race two
                    # backend switches; wait for it to settle
                    if self._state == DEVICE:
                        return  # re-promoted: park until the next failover
                    continue
            self.probe_and_handle()

    def probe_and_handle(self) -> bool:
        """One probe attempt + hysteresis bookkeeping (the prober loop's
        body, callable directly by tests and the failover smoke). A
        probe exception is a recorded ``error`` outcome inside the
        shared helper — this method cannot crash the prober."""
        from flyimg_tpu.parallel.mesh import probe_device_backend

        # probe the SAVED selection when a real failover forced the
        # process env to cpu — trusting the current env would declare
        # the dead backend healthy immediately and flap the replica
        ok, detail = probe_device_backend(
            self.probe_timeout_s, selection=self._saved_selection
        )
        outcome = "ok" if ok else (
            "error" if detail.startswith("error:") else "dead"
        )
        self._record_probe(outcome)
        repromote = False
        with self._lock:
            self._probes_total += 1
            self._last_probe_at = self._clock()
            self._last_probe_outcome = f"{outcome}:{detail}"
            if (
                self._state != CPU_FALLBACK
                or self._repromoting
                or self._failing_over
            ):
                # never re-promote while a failover worker is mid-switch
                # (two concurrent backend switches would race; the
                # prober re-evaluates once the worker settles)
                return ok
            if ok:
                self._clean_probes += 1
                required = self.probe_hysteresis * self._hysteresis_mult
                if self._clean_probes >= required:
                    self._repromoting = True
                    repromote = True
            else:
                self._clean_probes = 0
        if repromote:
            self._repromote()
        return ok

    def _repromote(self) -> None:
        """N clean probes: restore the device backend atomically — swap
        the selection back, rebuild the mesh, replace the executor, and
        invalidate the program caches so every program recompiles
        against the revived backend (an expected, named compile family;
        the retrace sentinel counts repeated key values as clean)."""
        log = logging.getLogger(SUPERVISOR_LOGGER)
        batcher = self._batcher
        try:
            if batcher is not None:
                # hold new launches, then drain the HEALTHY in-flight
                # CPU batches before the backend switch: clearing
                # backends under live arrays — or letting the old
                # executor dispatch a queued group mid-switch — would
                # 5xx renders that were about to succeed
                batcher.pause_launches()
                batcher.drain_inflight(self.failover_drain_s)
            self._switch_backend_to_device()
            mesh = None
            if self._mesh_factory is not None:
                try:
                    mesh = self._mesh_factory()
                except Exception:
                    log.warning(
                        "mesh rebuild failed at re-promotion; serving "
                        "unsharded", exc_info=True,
                    )
            if batcher is not None:
                batcher.failover_backend(
                    mesh,
                    drain_timeout_s=self.failover_drain_s,
                    reason="device_repromote",
                )
            with self._lock:
                self._state = DEVICE
                self._state_since = self._clock()
                self._consecutive = 0
                self._window.clear()
                self._clean_probes = 0
                self._repromotions += 1
                self._last_repromote_at = self._clock()
                self._pending_events.append({
                    "name": "device.repromote",
                    "to": "device",
                })
            self._record_failover("device")
            log.warning(
                "device backend revived: re-promoted from CPU fallback",
                extra={"event": "device.repromote", "to": "device"},
            )
        except Exception:
            log.exception(
                "re-promotion failed; staying on CPU fallback"
            )
        finally:
            if batcher is not None:
                batcher.resume_launches()
            with self._lock:
                self._repromoting = False

    # -- process backend switch (real hardware only) -----------------------

    def _switch_backend_to_cpu(self) -> None:
        """Force the process onto the CPU platform when an accelerator
        was actually selected. On hosts already serving CPU (every test
        topology, and a boot that already fell back) this is a no-op —
        clearing live backends under in-flight arrays is exactly the
        damage the guard avoids."""
        import os

        import jax

        try:
            if jax.default_backend() == "cpu":
                return
        except Exception:
            # the backend is so dead even default_backend() raises:
            # switching is the treatment, proceed
            pass
        from flyimg_tpu.ops.compose import invalidate_program_caches
        from flyimg_tpu.parallel.mesh import force_cpu_platform

        self._saved_selection = {
            "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS"),
            "XLA_FLAGS": os.environ.get("XLA_FLAGS"),
        }
        force_cpu_platform()
        # close the window between dropping the backend and the
        # batcher-side invalidation: a request thread on the
        # single-image path (run_plan — wedged fallback, library
        # callers) must not fetch a cached handle compiled against the
        # backend that just went away. A render already EXECUTING a
        # cleared program can still fail on real hardware — bounded,
        # accepted residual: the batched path (the serving hot path) is
        # fully quiesced by pause+drain, and on the failover direction
        # those renders were dying with the device anyway.
        invalidate_program_caches()

    def _switch_backend_to_device(self) -> None:
        """Undo ``_switch_backend_to_cpu`` (no-op when it was one):
        restore the saved platform selection and drop the CPU-forced
        backends so the next program compiles on the revived device."""
        saved = self._saved_selection
        if saved is None:
            return
        import os

        import jax

        for key, value in saved.items():
            if value is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = value
        self._saved_selection = None
        from jax.extend.backend import clear_backends

        clear_backends()
        req = os.environ.get("JAX_PLATFORMS", "").strip()
        # an empty selection must RESET the config to the default plugin
        # choice, not leave it where force_cpu_platform pinned it ("cpu"
        # — config beats env, so skipping the update would re-promote
        # onto a backend that is still the CPU: health 1, untagged
        # cached CPU renders, the exact masking this module forbids)
        jax.config.update("jax_platforms", req if req else None)
        # same window-closing invalidation as the cpu direction: no
        # single-image caller may fetch a handle compiled against the
        # just-dropped CPU-forced backends
        from flyimg_tpu.ops.compose import invalidate_program_caches

        invalidate_program_caches()

    # -- observability -----------------------------------------------------

    def evaluate(self) -> None:
        """Rides the request middleware next to brownout
        evaluation: drains span events queued by the worker/prober
        threads onto THIS request's trace. One list check when idle;
        nothing at all when disabled."""
        if not self.enabled or not self._pending_events:
            return
        with self._lock:
            pending, self._pending_events = self._pending_events, []
        for event in pending:
            name = str(event.pop("name"))
            tracing.add_event(name, **event)

    def _record_failover(self, to: str) -> None:
        if self._metrics is None:
            return
        self._metrics.counter(
            f'flyimg_backend_failovers_total{{to="{to}"}}',
            "Backend failovers by destination (cpu = storm tripped the "
            "breaker, device = re-promotion)",
        ).inc()

    def _record_probe(self, outcome: str) -> None:
        if self._metrics is None:
            return
        self._metrics.counter(
            f'flyimg_backend_probe_total{{outcome="{outcome}"}}',
            "Device-backend re-probe attempts by outcome",
        ).inc()

    def close(self) -> None:
        """Stop the prober (app shutdown)."""
        self._closed = True
        self._wake.set()
