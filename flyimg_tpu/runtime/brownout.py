"""Brownout engine: graceful degradation under overload.

PR 1-3 gave the pipeline *binary* overload responses — admission-gate
503s, queue-depth sheds, breaker rejections — and PR 4's SLO engine
*measures* burn rates without acting on them. "Beyond Inference"
(arXiv 2403.12981, PAPERS.md) shows host-side queueing dominates exactly
when load spikes, and PATCHEDSERVE (arXiv 2501.09253) argues an SLO-aware
tier should *adapt work per request* under pressure instead of merely
rejecting. This module closes that loop: a hysteresis state machine

    NORMAL -> DEGRADED -> BROWNOUT -> SHED

driven by the live pressure signals the runtime already exports (batcher
queue depth, batch queue-wait share, SLO multi-window burn rates, inflight
gauge, breaker-open count), with per-level degradation policies threaded
through the serving layers (docs/degradation.md):

- **DEGRADED**: stale-while-revalidate — a cache hit past its freshness
  TTL (``brownout_stale_ttl_s``) serves immediately with ``Warning: 110``
  / ``X-Flyimg-Degraded: stale`` markers while ONE coalesced background
  refresh re-renders through the handler's single-flight table, bounded
  by this module's ``RefreshQueue``.
- **BROWNOUT**: DEGRADED plus plan rewriting — ``spec.plan.degrade_plan``
  drops the finishing conv ops, the smart-crop device scoring pass is
  replaced with the deterministic host entropy crop, and encode quality
  is clamped to ``brownout_quality``. Degraded renders are served direct
  (never cached) and tagged ``X-Flyimg-Degraded``.
- **SHED**: BROWNOUT plus cache-miss rejection — hits (fresh or stale)
  still serve; misses shed as 503 + Retry-After before any decode or
  device work.

Escalation is immediate (overload punishes hesitation); de-escalation is
deliberate: one level at a time, only after ``brownout_min_dwell_s`` at
the current level AND pressure below ``threshold * brownout_hysteresis``
(the gap that prevents flapping at a boundary). Every transition emits a
span event + a structured ``flyimg.brownout`` log line + moves the
``flyimg_brownout_level`` gauge and the
``flyimg_brownout_transitions_total{to=}`` counter; every degradation
action counts in ``flyimg_degraded_total{mode=}``.

Also here, because they share the same "serve something cheaper instead
of failing" posture:

- ``NegativeCache``: a TTL'd table of recently-failing origins
  (host+path), fed by fetch outcomes (transient-exhausted retries and
  open circuit breakers); a hit short-circuits the fetch to an immediate
  502 instead of burning deadline budget re-proving a dead origin.
- ``RefreshQueue``: the bounded, key-coalesced background worker that
  runs stale-while-revalidate re-renders.

Everything defaults OFF (``brownout_enable: false``,
``negative_cache_ttl_s: 0``, ``storage_hedge_delay_ms: 0``): with the
knobs at their defaults the serving path is byte-for-byte today's
behavior (pinned by tests/test_brownout.py). Clocks are injectable for
deterministic hysteresis tests; the ``brownout.signal`` fault point
(flyimg_tpu/testing/faults.py) lets tests and smoke drive the pressure
scalar directly.
"""

from __future__ import annotations

import logging
import queue as queue_mod
import threading
import time
from typing import Callable, Dict, Optional, Tuple
from urllib.parse import urlsplit

from flyimg_tpu.runtime import tracing
from flyimg_tpu.testing import faults

__all__ = [
    "NORMAL",
    "DEGRADED",
    "BROWNOUT",
    "SHED",
    "LEVEL_NAMES",
    "BrownoutEngine",
    "NegativeCache",
    "RefreshQueue",
]

BROWNOUT_LOGGER = "flyimg.brownout"

#: degradation levels, ordered by severity
NORMAL, DEGRADED, BROWNOUT, SHED = 0, 1, 2, 3
LEVEL_NAMES = {NORMAL: "normal", DEGRADED: "degraded",
               BROWNOUT: "brownout", SHED: "shed"}


class BrownoutEngine:
    """The hysteresis state machine NORMAL -> DEGRADED -> BROWNOUT -> SHED.

    ``evaluate()`` (called once per pipeline request by the HTTP
    middleware, rate-limited to ``eval_interval_s``) folds the attached
    pressure signals into one scalar — the max of each signal normalized
    by its reference — and maps it to a target level through the
    ``*_at`` thresholds. Rising pressure escalates immediately; falling
    pressure de-escalates one level per evaluation, and only after
    ``min_dwell_s`` at the current level with pressure under
    ``threshold * hysteresis``.
    """

    def __init__(
        self,
        *,
        enabled: bool = False,
        degraded_at: float = 0.6,
        brownout_at: float = 0.85,
        shed_at: float = 1.1,
        hysteresis: float = 0.75,
        min_dwell_s: float = 5.0,
        eval_interval_s: float = 0.25,
        queue_ref: float = 64.0,
        inflight_ref: float = 0.0,
        breaker_ref: float = 0.0,
        lease_ref: float = 8.0,
        quality: int = 40,
        stale_ttl_s: float = 300.0,
        refresh_max_pending: int = 8,
        shed_retry_after_s: float = 1.0,
        metrics=None,
        clock: Callable[[], float] = time.monotonic,
    ) -> None:
        self.enabled = bool(enabled)
        # thresholds must be ordered or the target mapping is nonsense
        self.degraded_at = float(degraded_at)
        self.brownout_at = max(float(brownout_at), self.degraded_at)
        self.shed_at = max(float(shed_at), self.brownout_at)
        self.hysteresis = min(max(float(hysteresis), 0.0), 1.0)
        self.min_dwell_s = max(float(min_dwell_s), 0.0)
        self.eval_interval_s = max(float(eval_interval_s), 0.0)
        self.queue_ref = max(float(queue_ref), 1.0)
        self.inflight_ref = float(inflight_ref)
        self.breaker_ref = float(breaker_ref)
        self.lease_ref = float(lease_ref)
        self.quality = int(quality)
        self.stale_ttl_s = float(stale_ttl_s)
        self.shed_retry_after_s = float(shed_retry_after_s)
        self._metrics = metrics
        self._clock = clock
        self._lock = threading.Lock()
        self._level = NORMAL
        self._level_since = clock()
        self._last_eval = float("-inf")
        self._last_pressure = 0.0
        self._last_components: Dict[str, float] = {}
        self._transitions_total = 0
        # escalation listeners (service/app.py wires the flight
        # recorder's dump here): queued inside _transition_locked,
        # FIRED after the engine lock is released in evaluate() — a
        # listener doing file IO under this lock would convoy every
        # request that rides an evaluation
        self._transition_listeners = []
        self._pending_notifications = []
        # signal sources (attach() below); all optional
        self._batchers: Tuple = ()
        self._slo = None
        self._inflight_fn: Optional[Callable[[], float]] = None
        self._breaker_open_fn: Optional[Callable[[], float]] = None
        self._host_pipeline = None
        self._lease_waiters_fn: Optional[Callable[[], float]] = None
        self._device_supervisor = None
        self._rss_fn: Optional[Callable[[], float]] = None
        self.refresh = RefreshQueue(
            max_pending=refresh_max_pending, metrics=metrics
        )

    @classmethod
    def from_params(cls, params, *, metrics=None) -> "BrownoutEngine":
        # clock is injectable through the (non-YAML) `brownout_clock`
        # param, the same object-passing hook style as `fault_injector`,
        # so hysteresis/dwell tests never sleep
        clock = params.by_key("brownout_clock") or time.monotonic
        return cls(
            enabled=bool(params.by_key("brownout_enable", False)),
            degraded_at=float(params.by_key("brownout_degraded_at", 0.6)),
            brownout_at=float(params.by_key("brownout_brownout_at", 0.85)),
            shed_at=float(params.by_key("brownout_shed_at", 1.1)),
            hysteresis=float(params.by_key("brownout_hysteresis", 0.75)),
            min_dwell_s=float(params.by_key("brownout_min_dwell_s", 5.0)),
            eval_interval_s=float(
                params.by_key("brownout_eval_interval_s", 0.25)
            ),
            queue_ref=float(params.by_key("brownout_queue_ref", 0.0))
            or float(params.by_key("batch_max_queue_depth", 0) or 0)
            or 64.0,
            inflight_ref=float(params.by_key("brownout_inflight_ref", 0.0)),
            breaker_ref=float(params.by_key("brownout_breaker_ref", 0.0)),
            lease_ref=float(params.by_key("brownout_lease_ref", 8.0)),
            quality=int(params.by_key("brownout_quality", 40)),
            stale_ttl_s=float(params.by_key("brownout_stale_ttl_s", 300.0)),
            refresh_max_pending=int(
                params.by_key("brownout_refresh_max_pending", 8)
            ),
            shed_retry_after_s=float(params.by_key("shed_retry_after_s", 1.0)),
            metrics=metrics,
            clock=clock,
        )

    # -- signal wiring -----------------------------------------------------

    def add_transition_listener(self, listener) -> None:
        """Register a callback fired on every ESCALATION (level up),
        outside the engine lock, with ``{from, to, pressure}``. The
        serving wiring dumps the batch flight recorder here — the ring
        still holds the launches that built the pressure."""
        self._transition_listeners.append(listener)

    def attach(self, *, batchers=(), slo=None, inflight_fn=None,
               breaker_open_fn=None, host_pipeline=None,
               lease_waiters_fn=None, device_supervisor=None,
               rss_fn=None) -> None:
        """Wire the live pressure sources (service/app.py): batch
        controllers (queue depth + efficiency window), the SLO engine
        (burn rates), the inflight-request gauge, the breaker registry's
        open count, the host stage-DAG (runtime/hostpipeline.py — its
        worst stage-pool saturation, 1.0 = a stage at its admission
        bound), the L2 lease follower count (storage/tiered.py
        ``L2Lease.waiters`` — threads parked behind a remote leader are
        load, not idleness), and the RSS watchdog's normalized process
        memory pressure (runtime/memgovernor.py ``RssWatchdog.pressure``
        — sampled on this engine's evaluation cadence, so approaching
        the host memory limit degrades gracefully instead of ending in
        the OOM killer). All optional — a missing source simply
        contributes no pressure."""
        self._batchers = tuple(batchers)
        self._slo = slo
        self._inflight_fn = inflight_fn
        self._breaker_open_fn = breaker_open_fn
        self._host_pipeline = host_pipeline
        self._lease_waiters_fn = lease_waiters_fn
        self._rss_fn = rss_fn
        # the backend supervisor (runtime/devicesupervisor.py): a
        # replica failed over to CPU rendering carries a fixed pressure
        # so degradation reacts to the much slower render path
        self._device_supervisor = device_supervisor

    def register_metrics(self, registry) -> None:
        """Render-time gauges on the shared registry: the level an
        operator alerts on, and the pressure scalar that drives it. The
        level gauge RE-EVALUATES at scrape time (same lesson as the PR-4
        SLO gauges): after traffic stops, a scrape must watch the level
        walk back down as the windows drain, not read a latched value
        forever. Rendering samples gauge callbacks outside the registry
        lock, so the evaluation (which may create transition counters)
        cannot deadlock the scrape."""
        registry.gauge(
            "flyimg_brownout_level",
            "Degradation level: 0 normal, 1 degraded, 2 brownout, 3 shed",
            fn=lambda: float(self.evaluate()),
        )
        registry.gauge(
            "flyimg_brownout_pressure",
            "Normalized overload pressure (max across attached signals)",
            fn=lambda: self._last_pressure,
        )

    # -- pressure ----------------------------------------------------------

    def _components(self) -> Dict[str, float]:
        """Each attached signal normalized so 1.0 ~ 'at capacity'."""
        out: Dict[str, float] = {}
        pending = 0.0
        for batcher in self._batchers:
            try:
                pending += float(batcher.admission.pending)
            except Exception:
                continue
        if self._batchers:
            out["queue_depth"] = pending / self.queue_ref
        metrics = self._metrics
        if metrics is not None and self._batchers:
            try:
                eff = metrics.batch_efficiency(
                    self._batchers[0].name
                ).stats()
                out["queue_wait_share"] = float(eff["queue_wait_share"])
            except Exception:
                pass
        if self._slo is not None and getattr(self._slo, "enabled", False):
            fast = self._slo.burn_rate("fast")
            slow = self._slo.burn_rate("slow")
            out["burn_fast"] = fast / max(
                self._slo.burn_threshold_fast, 1e-9
            )
            out["burn_slow"] = slow / max(
                self._slo.burn_threshold_slow, 1e-9
            )
        if (
            self._host_pipeline is not None
            and getattr(self._host_pipeline, "enabled", False)
        ):
            try:
                # worst stage-pool saturation (pending / admission
                # bound): a saturated decode pool is host overload the
                # batcher queues can't see (runtime/hostpipeline.py)
                out["host_stage"] = float(self._host_pipeline.pressure())
            except Exception:
                pass
        if self._device_supervisor is not None:
            try:
                # device backend failed over to CPU rendering
                # (runtime/devicesupervisor.py): a fixed pressure at
                # exactly the BROWNOUT entry threshold — misses on the
                # slow CPU path degrade (cheaper plans, stale serving)
                # but never shed (docs/degradation.md "Device-loss
                # pressure")
                out["device_health"] = (
                    self.brownout_at
                    if self._device_supervisor.cpu_forced() else 0.0
                )
            except Exception:
                pass
        if self._lease_waiters_fn is not None and self.lease_ref > 0:
            try:
                # followers blocked in an L2Lease wait (a fleet-wide
                # hot-key stampede): each parked request thread is load
                # this replica is carrying even though its own queues
                # look empty (docs/degradation.md "Lease-aware pressure")
                out["l2_lease"] = (
                    float(self._lease_waiters_fn()) / self.lease_ref
                )
            except Exception:
                pass
        if self._rss_fn is not None:
            try:
                # process RSS vs the configured host memory limit
                # (runtime/memgovernor.py): sampled here so memory
                # pressure rides the same evaluation cadence — and the
                # same stale-serve → degrade → shed ladder — as every
                # other overload signal
                out["rss"] = float(self._rss_fn())
            except Exception:
                pass
        # a failing pressure source degrades to no-signal: the engine
        # must never turn a broken gauge callback into per-request 500s
        if self._inflight_fn is not None and self.inflight_ref > 0:
            try:
                out["inflight"] = (
                    float(self._inflight_fn()) / self.inflight_ref
                )
            except Exception:
                pass
        if self._breaker_open_fn is not None and self.breaker_ref > 0:
            try:
                out["breakers_open"] = (
                    float(self._breaker_open_fn()) / self.breaker_ref
                )
            except Exception:
                pass
        return out

    def pressure(self) -> float:
        """Current pressure scalar (also recomputed by evaluate())."""
        components = self._components()
        return max(components.values(), default=0.0)

    def _target_level(self, pressure: float) -> int:
        if pressure >= self.shed_at:
            return SHED
        if pressure >= self.brownout_at:
            return BROWNOUT
        if pressure >= self.degraded_at:
            return DEGRADED
        return NORMAL

    def _threshold_for(self, level: int) -> float:
        return {DEGRADED: self.degraded_at, BROWNOUT: self.brownout_at,
                SHED: self.shed_at}.get(level, self.degraded_at)

    # -- evaluation --------------------------------------------------------

    def evaluate(self) -> int:
        """Recompute the level from current pressure; returns it.

        Called once per pipeline request (service/app.py middleware) —
        cheap: disabled is one bool check; enabled re-evaluates at most
        every ``eval_interval_s`` unless the ``brownout.signal`` fault
        point injects a pressure override (then every call evaluates, so
        scripted tests are deterministic)."""
        if not self.enabled:
            return NORMAL
        injected = faults.fire("brownout.signal")
        now = self._clock()
        level = self._evaluate_locked_region(injected, now)
        self._flush_notifications()
        return level

    def _evaluate_locked_region(self, injected, now: float) -> int:
        with self._lock:
            if (
                injected is faults.PASS
                and now - self._last_eval < self.eval_interval_s
            ):
                return self._level
            self._last_eval = now
            if injected is not faults.PASS and injected is not None:
                pressure = float(injected)
                components = {"injected": pressure}
            else:
                components = self._components()
                pressure = max(components.values(), default=0.0)
            self._last_pressure = pressure
            self._last_components = components
            target = self._target_level(pressure)
            if target > self._level:
                # escalate immediately — overload punishes hesitation
                self._transition_locked(target, pressure, now)
            else:
                # de-escalate deliberately: one level per elapsed dwell
                # window, and only while pressure sits clearly under the
                # current level's entry threshold (the hysteresis gap).
                # Each step consumes ONE dwell of the elapsed credit, so
                # a long idle gap walks all the way down in one
                # evaluation instead of latching — the first request (or
                # scrape) after a quiet night must not be served at the
                # spike's level.
                while (
                    self._level > target
                    and now - self._level_since >= self.min_dwell_s
                    and pressure < (
                        self._threshold_for(self._level) * self.hysteresis
                    )
                ):
                    self._transition_locked(
                        self._level - 1, pressure,
                        self._level_since + self.min_dwell_s,
                    )
            return self._level

    def _flush_notifications(self) -> None:
        """Fire queued escalation notifications OUTSIDE the engine lock
        (listeners do file IO — the flight-recorder dump)."""
        with self._lock:
            pending, self._pending_notifications = (
                self._pending_notifications, []
            )
        for doc in pending:
            for listener in self._transition_listeners:
                try:
                    listener(doc)
                except Exception:
                    logging.getLogger(BROWNOUT_LOGGER).warning(
                        "brownout transition listener failed", exc_info=True
                    )

    def _transition_locked(self, to: int, pressure: float,
                           since: float) -> None:
        """Move to ``to``; ``since`` is the new level's start time —
        ``now`` on escalation, the consumed dwell boundary on
        de-escalation (so multi-dwell idle credit carries across
        steps)."""
        frm = self._level
        self._level = to
        self._level_since = since
        self._transitions_total += 1
        name = LEVEL_NAMES[to]
        if self._metrics is not None:
            from flyimg_tpu.runtime.metrics import escape_label_value

            self._metrics.counter(
                "flyimg_brownout_transitions_total"
                f'{{to="{escape_label_value(name)}"}}',
                "Brownout level transitions by destination level",
            ).inc()
        tracing.add_event(
            "brownout.transition",
            frm=LEVEL_NAMES[frm],
            to=name,
            pressure=round(pressure, 4),
        )
        if to > frm and self._transition_listeners:
            # escalations notify listeners (queued; evaluate() fires
            # them after this lock is released)
            self._pending_notifications.append({
                "event": "brownout.escalation",
                "from": LEVEL_NAMES[frm],
                "to": name,
                "pressure": round(pressure, 4),
            })
        log = logging.getLogger(BROWNOUT_LOGGER)
        log_fn = log.warning if to > frm else log.info
        log_fn(
            "brownout level %s -> %s (pressure %.3f)",
            LEVEL_NAMES[frm], name, pressure,
            extra={
                "event": "brownout.transition",
                "from_level": LEVEL_NAMES[frm],
                "to_level": name,
                "pressure": round(pressure, 4),
                "components": {
                    k: round(v, 4) for k, v in self._last_components.items()
                },
            },
        )

    # -- per-request policy (handler reads these) --------------------------

    def level(self) -> int:
        return self._level

    def swr_active(self) -> bool:
        """DEGRADED+: serve stale cache hits + background refresh."""
        return self.enabled and self._level >= DEGRADED

    def plan_degrade_active(self) -> bool:
        """BROWNOUT+: rewrite plans to cheaper work."""
        return self.enabled and self._level >= BROWNOUT

    def shed_active(self) -> bool:
        """SHED: reject cache misses outright."""
        return self.enabled and self._level >= SHED

    def record_degraded(self, mode: str) -> None:
        """One degradation action (stale serve, plan rewrite component,
        quality clamp, shed) — the counter operators graph next to the
        level gauge."""
        if self._metrics is None:
            return
        from flyimg_tpu.runtime.metrics import escape_label_value

        self._metrics.counter(
            "flyimg_degraded_total"
            f'{{mode="{escape_label_value(mode)}"}}',
            "Requests degraded under brownout, by degradation mode",
        ).inc()

    def snapshot(self) -> Dict[str, object]:
        """The /debug/brownout JSON document (service/app.py)."""
        with self._lock:
            return {
                "enabled": self.enabled,
                "level": self._level,
                "level_name": LEVEL_NAMES[self._level],
                "pressure": round(self._last_pressure, 4),
                "components": {
                    k: round(v, 4) for k, v in self._last_components.items()
                },
                "thresholds": {
                    "degraded_at": self.degraded_at,
                    "brownout_at": self.brownout_at,
                    "shed_at": self.shed_at,
                    "hysteresis": self.hysteresis,
                    "min_dwell_s": self.min_dwell_s,
                },
                "transitions_total": self._transitions_total,
                "refresh_queue": self.refresh.stats(),
            }


# ---------------------------------------------------------------------------
# negative origin cache


class NegativeCache:
    """TTL'd cache of recently-failing origins.

    Fed by fetch outcomes (service/input_source.py): an origin whose
    transient failures exhausted the retry budget, or whose circuit
    breaker is open, enters for ``ttl_s``. A later fetch of the same
    key short-circuits to an immediate 502
    (``OriginUnavailableException``) instead of burning connect/read
    timeouts and deadline budget re-proving a dead origin — the request
    either serves a stale copy (the L1 original cache is checked BEFORE
    this table) or fails in microseconds.

    Keying is scoped to the failure class: a CONNECT-level failure
    (nothing ever reached the origin — dead host, open breaker) enters
    under ``(host, path)`` with the query excluded, so cache-busting
    query strings cannot bypass the table; a RESOURCE-level failure
    (the origin answered — 5xx, read stall on one object) additionally
    keys a digest of the query, so one broken ``/render?id=N`` cannot
    negative-cache every healthy sibling id on the same endpoint.
    ``hit`` checks the origin-scope key first, then the resource key.

    Size-bounded (oldest-expiry eviction) because the key is
    client-controlled; ``ttl_s <= 0`` disables the table entirely.
    Thread-safe; clock injectable for deterministic TTL tests.
    """

    def __init__(
        self,
        ttl_s: float,
        *,
        max_entries: int = 1024,
        metrics=None,
        clock: Callable[[], float] = time.monotonic,
    ) -> None:
        self.ttl_s = float(ttl_s)
        self.max_entries = max(1, int(max_entries))
        self._metrics = metrics
        self._clock = clock
        self._lock = threading.Lock()
        # (host, path, query-digest-or-"") -> (expires_at, error label)
        self._entries: Dict[
            Tuple[str, str, str], Tuple[float, str]
        ] = {}

    @property
    def enabled(self) -> bool:
        return self.ttl_s > 0

    @staticmethod
    def key_for(url: str, *, resource: bool = False) -> Tuple[str, str, str]:
        """host+path (+ a short query digest for resource-scope
        failures; userinfo excluded like resilience.host_of)."""
        try:
            parts = urlsplit(url)
            host = (parts.hostname or "local").lower()
            if parts.port:
                host = f"{host}:{parts.port}"
            digest = ""
            if resource and parts.query:
                import hashlib

                digest = hashlib.blake2b(
                    parts.query.encode("utf-8", "surrogatepass"),
                    digest_size=6,
                ).hexdigest()
            return host, parts.path or "/", digest
        except ValueError:
            return "local", "/", ""

    def add(self, url: str, error: str, *, resource: bool = False) -> None:
        """Remember one failing origin. ``resource=True`` scopes the
        entry to the exact host+path+query (the origin answered, so
        only that object is proven bad); False scopes host+path-wide
        (nothing connected — every query of that path would fail)."""
        if not self.enabled:
            return
        key = self.key_for(url, resource=resource)
        with self._lock:
            now = self._clock()
            if key not in self._entries and (
                len(self._entries) >= self.max_entries
            ):
                self._purge_locked(now)
                while len(self._entries) >= self.max_entries:
                    oldest = min(
                        self._entries, key=lambda k: self._entries[k][0]
                    )
                    del self._entries[oldest]
            self._entries[key] = (now + self.ttl_s, str(error))
        if self._metrics is not None:
            self._metrics.counter(
                "flyimg_negative_cache_entries_total",
                "Origins entered into the negative cache",
            ).inc()
        tracing.add_event(
            "fetch.negative_cache_store", host=key[0], error=str(error)
        )

    def hit(self, url: str) -> Optional[str]:
        """The cached failure label when ``url``'s origin is
        negative-cached and unexpired, else None. Checks the
        origin-scope key (matches ANY query of the path), then the
        resource-scope key (this exact query)."""
        if not self.enabled:
            return None
        origin_key = self.key_for(url)
        resource_key = self.key_for(url, resource=True)
        error = None
        with self._lock:
            now = self._clock()
            for key in (origin_key, resource_key):
                entry = self._entries.get(key)
                if entry is None:
                    continue
                expires_at, label = entry
                if now >= expires_at:
                    del self._entries[key]
                    continue
                error = label
                break
            if error is None:
                return None
        if self._metrics is not None:
            self._metrics.counter(
                "flyimg_negative_cache_hits_total",
                "Fetches short-circuited by the negative origin cache",
            ).inc()
        return error

    def _purge_locked(self, now: float) -> None:
        for key in [
            k for k, (exp, _e) in self._entries.items() if now >= exp
        ]:
            del self._entries[key]

    def __len__(self) -> int:
        with self._lock:
            self._purge_locked(self._clock())
            return len(self._entries)


# ---------------------------------------------------------------------------
# bounded, coalesced background refresh


class RefreshQueue:
    """The stale-while-revalidate worker: a bounded queue of re-render
    callables, coalesced per derived key (a key already queued or
    refreshing is not enqueued again — N stale hits for one key cost ONE
    background render), drained by a single lazily-started daemon thread.
    Over the bound, new refreshes are dropped (and counted): under
    sustained pressure the refresh queue must not become its own
    overload amplifier. The ``brownout.refresh`` fault point fires once
    per refresh actually run, which is how tests count renders."""

    def __init__(self, *, max_pending: int = 8, metrics=None) -> None:
        self.max_pending = max(1, int(max_pending))
        self._metrics = metrics
        self._lock = threading.Lock()
        self._keys: set = set()
        self._queue: "queue_mod.Queue" = queue_mod.Queue()
        self._thread: Optional[threading.Thread] = None
        # True between deciding to spawn the worker (under the lock) and
        # the spawn completing outside it, so a concurrent submit in that
        # window cannot double-spawn
        self._spawning = False

    def submit(self, key: str, fn: Callable[[], None]) -> bool:
        """Enqueue one refresh; False when coalesced away or dropped by
        the bound."""
        spawn = False
        with self._lock:
            if key in self._keys:
                return False  # already queued or refreshing: coalesced
            if len(self._keys) >= self.max_pending:
                if self._metrics is not None:
                    self._metrics.counter(
                        "flyimg_refresh_dropped_total",
                        "Stale-refresh renders dropped by the queue bound",
                    ).inc()
                return False
            self._keys.add(key)
            if not self._spawning and (
                self._thread is None or not self._thread.is_alive()
            ):
                self._spawning = spawn = True
        self._queue.put((key, fn))
        if spawn:
            # the worker starts OUTSIDE the lock: Thread.start blocks on
            # OS scheduling, and holding the lock across it would convoy
            # every stale-serving request thread submitting a refresh
            # (flylint: lock-held-blocking-call)
            thread = threading.Thread(
                target=self._run, name="flyimg-swr-refresh", daemon=True
            )
            try:
                thread.start()
            finally:
                with self._lock:
                    self._thread = thread
                    self._spawning = False
        return True

    def _run(self) -> None:
        while True:
            key, fn = self._queue.get()
            try:
                faults.fire("brownout.refresh", key=key)
                fn()
                if self._metrics is not None:
                    self._metrics.counter(
                        "flyimg_refresh_renders_total",
                        "Background stale-while-revalidate re-renders",
                    ).inc()
            except Exception as exc:
                # a failed refresh leaves the stale entry in place — the
                # next stale hit retries; never let it kill the worker
                logging.getLogger(BROWNOUT_LOGGER).warning(
                    "stale refresh for %s failed: %s", key, exc
                )
            finally:
                with self._lock:
                    self._keys.discard(key)

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"pending": len(self._keys),
                    "max_pending": self.max_pending}
