"""Deterministic fault injection for the serving pipeline.

Resilience behavior (retries, breakers, deadlines, load shedding) cannot be
proven with real network or device flakiness — tests need faults that fire
exactly N times, at exactly one pipeline point, and then stop. This module
provides that as named *injection points* the pipeline fires on its way
through:

    ``fetch.http``      one HTTP fetch attempt (service/input_source.py);
                        an injected plan may raise (simulated transport
                        failure) or return body bytes (simulated success)
    ``storage.read``    one storage fetch/read attempt
    ``storage.write``   one storage write attempt
    ``batcher.execute`` the batch executor about to run a group — a
                        blocking plan wedges the device executor; a
                        raising plan routes through the batcher's
                        classify/retry/bisect recovery
    ``batcher.member``  one member being assembled into a device launch
                        (primary AND recovery sub-launches), with
                        per-member ctx ``key``/``index``/``image`` — a
                        plan raising for one member models a poison
                        input failing the whole fused launch, which the
                        batcher then isolates by bisection
                        (docs/resilience.md)
    ``batcher.drain``   one device->host readback (primary drain thread
                        and recovery launches), ctx ``key``/``n``/
                        ``batch`` — raising models a transient readback
                        failure, retried at the batch level
    ``brownout.signal`` one brownout pressure evaluation
                        (runtime/brownout.py BrownoutEngine.evaluate):
                        a plan returning a float OVERRIDES the computed
                        pressure scalar (and bypasses the evaluation
                        rate limit), so tests script the exact
                        escalation/de-escalation sequence
    ``brownout.refresh`` one stale-while-revalidate background re-render
                        about to run (ctx ``key``); the fired count is
                        how tests assert refresh coalescing
    ``storage.read_delay`` one hedged-read attempt starting
                        (storage/base.py fetch_hedged), ctx ``name``/
                        ``attempt`` (0 = primary, 1 = backup); a plan
                        that sleeps only for attempt 0 models the
                        slow-primary tail. Return value ignored
                        (latency-only point — use ``storage.read`` for
                        value injection)
    ``reuse.ancestor``  one ancestor-rendition read by the derivative-
                        reuse rewriter (service/handler.py _fetch_ancestor),
                        ctx ``name``; a plan may return bytes (simulated
                        ancestor) or raise (simulated pruned/corrupt
                        ancestor — the handler must fall back to the
                        full from-source pipeline, docs/caching.md)
    ``device.backend``  one device-backend probe/init attempt
                        (parallel/mesh.py probe_device_backend — the ONE
                        helper shared by boot and the supervisor's
                        re-probe, runtime/devicesupervisor.py): a plan
                        returning a bool OVERRIDES the probe verdict
                        (True = backend up, False = dead); a raising
                        plan models backend init crashing — recorded as
                        a probe outcome, never a crash
    ``fleet.proxy``     one proxied owner GET (runtime/fleet.py
                        FleetRouter.proxy), ctx ``owner``/``attempt``; a
                        raising plan models a transport failure (the
                        attempt is retried then falls back to a local
                        render); a plan returning ``(status, headers,
                        body)`` stands in for the owner's response
    ``l2.lease``        one lease-marker operation (storage/tiered.py
                        L2Lease), ctx ``op`` (``read``/``write``/
                        ``confirm``) and ``name``; a raising plan models
                        lease IO failing — acquire degrades to an
                        uncoalesced render, never a request failure
    ``l2.storage``      one shared-L2 tier operation (storage/tiered.py
                        TieredStorage + runtime/tiersupervisor.py), ctx
                        ``op`` (``read``/``write``/``has``/``stat``/
                        ``delete``/``probe``/``replay``) and ``name``; a
                        raising plan models the shared tier going away —
                        reads degrade to an L1 miss, writes to
                        single-replica behavior for that key, existence
                        checks to the L1 answer; ``probe`` governs the
                        tier supervisor's re-promotion probe and
                        ``replay`` its journal replay, so one plan
                        scripts a full outage-and-recovery arc
    ``fleet.member``    one membership-marker operation
                        (runtime/membership.py FleetMembership), ctx
                        ``op`` (``read``/``write``/``confirm``/``list``/
                        ``delete``), ``name``, ``replica``; a raising
                        plan models marker IO failing — heartbeats count
                        a failure and retry next beat, the watcher keeps
                        the previous live set, requests never fail
    ``warmstart.cache`` one warm-start manifest operation
                        (runtime/warmstart.py WarmStartCache), ctx
                        ``op`` (``read``/``write``) and ``name``; a
                        raising plan models the shared tier refusing the
                        manifest — seeding degrades to a cold boot,
                        publishing retries on a later beat
    ``batcher.oom``     one device launch about to dispatch (primary
                        executor AND recovery sub-launches), ctx
                        ``key``/``n``/``batch``; a plan raising an
                        XLA-style RESOURCE_EXHAUSTED error forces the
                        OOM-class (OVERSIZE) recovery path — the batcher
                        must cap the family's capacity ceiling and
                        re-launch in smaller pieces, never quarantine
                        (runtime/memgovernor.py, docs/resilience.md
                        "Memory governor")
    ``mem.rss``         one RSS watchdog sample (runtime/memgovernor.py
                        RssWatchdog.rss_bytes): a plan returning a float
                        OVERRIDES the /proc-sampled byte count, so chaos
                        drills force memory pressure through the
                        brownout ladder without allocating it

Production cost is one module-level ``None`` check per point (no injector
installed -> ``fire`` returns ``PASS`` immediately). Tests install a
``FaultInjector`` either directly (``install``/``clear``) or through the
app-config hook: ``make_app`` installs whatever object sits under the
``fault_injector`` parameter, so an HTTP-level test can inject faults into
a fully assembled app without monkeypatching internals.

All plans are deterministic scripts — ``fail_n_then_succeed``, fixed
latency spikes, an Event-gated wedge — never random.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, Optional

__all__ = [
    "PASS",
    "KNOWN_POINTS",
    "FaultInjector",
    "install",
    "clear",
    "fire",
    "fail_n_then_succeed",
    "latency_spike",
    "wedge_until",
    "poison_member",
]

#: THE machine-checked registry of injection points (one entry per point
#: documented above). flylint's fault-point rules keep this and the
#: pipeline's ``fire`` call sites in lockstep, both directions: firing an
#: undeclared point and declaring a never-fired point are both findings
#: (docs/static-analysis.md).
KNOWN_POINTS = frozenset({
    "fetch.http",
    "storage.read",
    "storage.write",
    "storage.read_delay",
    "batcher.execute",
    "batcher.member",
    "batcher.drain",
    "brownout.signal",
    "brownout.refresh",
    "reuse.ancestor",
    "device.backend",
    "fleet.proxy",
    "l2.lease",
    "l2.storage",
    "fleet.member",
    "warmstart.cache",
    "batcher.oom",
    "mem.rss",
})

#: sentinel: "no plan fired — run the real code path"
PASS = object()


class FaultInjector:
    """A set of scripted fault plans keyed by injection point.

    A plan is ``callable(**ctx) -> value | PASS`` and may raise. ``value``
    short-circuits the real code path (simulated success); ``PASS`` falls
    through to it; an exception is the injected fault. Plans fire on every
    hit of their point until removed — determinism lives inside the plan
    (e.g. a fail-counter), not in the harness.
    """

    def __init__(self) -> None:
        self._plans: Dict[str, Callable] = {}
        self._lock = threading.Lock()
        self.fired: Dict[str, int] = {}

    def plan(self, point: str, fn: Callable) -> "FaultInjector":
        with self._lock:
            self._plans[point] = fn
        return self

    def remove(self, point: str) -> None:
        with self._lock:
            self._plans.pop(point, None)

    def fire(self, point: str, **ctx):
        with self._lock:
            fn = self._plans.get(point)
            if fn is None:
                return PASS
            self.fired[point] = self.fired.get(point, 0) + 1
        return fn(**ctx)


_active: Optional[FaultInjector] = None


def install(injector: FaultInjector) -> FaultInjector:
    """Install ``injector`` process-wide (tests: pair with ``clear`` in a
    finally block, or use the ``fault_injector`` app param)."""
    global _active
    _active = injector
    return injector


def clear() -> None:
    global _active
    _active = None


def fire(point: str, **ctx):
    """Called by the pipeline at each injection point. Returns ``PASS``
    (run the real code) or an injected value; raises injected faults."""
    if _active is None:
        return PASS
    return _active.fire(point, **ctx)


# ---------------------------------------------------------------------------
# canned deterministic plans


def fail_n_then_succeed(n: int, exc_factory: Callable[[], BaseException],
                        result=PASS) -> Callable:
    """Raise ``exc_factory()`` for the first ``n`` hits, then return
    ``result`` (default ``PASS`` — fall through to the real path)."""
    remaining = [n]
    lock = threading.Lock()

    def plan(**_ctx):
        with lock:
            if remaining[0] > 0:
                remaining[0] -= 1
                raise exc_factory()
        return result

    return plan


def latency_spike(seconds: float, then=PASS) -> Callable:
    """Sleep ``seconds`` on every hit, then return ``then`` (default:
    fall through; an exception instance/class is raised instead). Models
    a slow upstream/stage — slow-then-alive or slow-then-dead."""

    def plan(**_ctx):
        time.sleep(seconds)
        if isinstance(then, BaseException) or (
            isinstance(then, type) and issubclass(then, BaseException)
        ):
            raise then
        return then

    return plan


def poison_member(match: Callable[..., bool],
                  exc_factory: Callable[[], BaseException]) -> Callable:
    """A ``batcher.member`` plan: raise ``exc_factory()`` whenever
    ``match(**ctx)`` is truthy (ctx carries ``key``/``index``/``image``),
    else fall through — THE deterministic poison pill. The raise happens
    at launch-assembly time, so the whole fused batch fails exactly like
    a real member-caused device error and the batcher must bisect to
    find the offender."""

    def plan(**ctx):
        if match(**ctx):
            raise exc_factory()
        return PASS

    return plan


def wedge_until(event: threading.Event, timeout_s: float = 30.0) -> Callable:
    """Block until the test sets ``event`` (bounded by ``timeout_s`` so an
    aborted test cannot wedge the suite). Installed at ``batcher.execute``
    this freezes the device executor thread — the wedged-executor scenario."""

    def plan(**_ctx):
        event.wait(timeout=timeout_s)
        return PASS

    return plan
