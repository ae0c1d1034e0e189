"""HTTP application: routes, error mapping, CLI.

The reference's L1/L2 (Silex bootstrap + routes, reference app.php,
config/routes.yml, src/Core/Controller/DefaultController.php) as an aiohttp
app. Routes preserved exactly:

    GET /                                   -> demo homepage
    GET /upload/{options}/{imageSrc:.+}     -> transformed image bytes
    GET /path/{options}/{imageSrc:.+}       -> public URL of the stored file

plus the observability surface (docs/observability.md): /metrics,
/healthz (liveness), /readyz (readiness — 503 while draining for
shutdown), and — debug-gated — /debug/trace (jax.profiler capture),
/debug/traces (tail-sampled trace ring), /debug/traces/{id} (span tree),
/debug/slo (burn rates / error budget), /debug/perf (batch efficiency),
/debug/plans (per-plan XLA cost ledger), /debug/flightrecorder (the
per-launch ring + dump inventory), /debug/profile (arm/list/download
batch-scoped device-profile captures), /debug/brownout (degradation
level + pressure components), /debug/device (backend supervisor state:
breaker, probes, failovers), /debug/tier (shared-tier outage supervisor:
island state, journal, scrubber), /debug/memory (memory governor:
capacity ceilings, host byte budget, RSS watchdog), POST
/debug/fleet/replicas (dynamic replica-set reload).

plus the ``encrypt`` CLI subcommand (reference app.php:93-96):

    python -m flyimg_tpu.service.app encrypt '<options>/<url>'
    python -m flyimg_tpu.service.app serve --port 8080 [--params file.yml]

The per-request transform runs in a worker executor so the event loop keeps
accepting requests while decode/device/encode are busy; batched device
execution is handled underneath by the runtime (flyimg_tpu/runtime).
"""

from __future__ import annotations

import argparse
import asyncio
import sys
import time
from typing import Optional

from aiohttp import web

from flyimg_tpu.appconfig import AppParameters
from flyimg_tpu.exceptions import (
    AppException,
    DeadlineExceededException,
    ExecFailedException,
    InvalidArgumentException,
    MissingParamsException,
    OriginUnavailableException,
    PayloadTooLargeException,
    ReadFileException,
    SecurityException,
    ServiceUnavailableException,
    UnsupportedMediaException,
)
from flyimg_tpu.runtime.resilience import Deadline
from flyimg_tpu.service.handler import ImageHandler
from flyimg_tpu.service.response import (
    NOT_MODIFIED_HEADERS,
    image_headers,
    is_not_modified,
)
from flyimg_tpu.storage import make_storage

# config-overridable route patterns (reference config/routes.yml); 'home'
# is fixed at '/'
DEFAULT_ROUTES = {
    "upload": "/upload/{options}/{imageSrc:.+}",
    "path": "/path/{options}/{imageSrc:.+}",
}

# typed application-state keys (aiohttp's recommended pattern)
PARAMS_KEY: web.AppKey[AppParameters] = web.AppKey("params", AppParameters)
HANDLER_KEY: web.AppKey[ImageHandler] = web.AppKey("handler", ImageHandler)
METRICS_KEY: web.AppKey = web.AppKey("metrics", object)
TRACER_KEY: web.AppKey = web.AppKey("tracer", object)
# the fleet router (dynamic replica-set reload: POST /debug/fleet/replicas
# and the serve-mode SIGHUP re-read both reach it through this key)
FLEET_KEY: web.AppKey = web.AppKey("fleet", object)
# the backend supervisor (runtime/devicesupervisor.py): tests and the
# failover smoke reach the live state machine through this key
SUPERVISOR_KEY: web.AppKey = web.AppKey("device_supervisor", object)
# elastic fleet membership (runtime/membership.py): the SIGHUP handler
# and the split-brain guard on /debug/fleet/replicas reach it here
MEMBERSHIP_KEY: web.AppKey = web.AppKey("membership", object)
# fleet observatory (runtime/observatory.py): tests and the observatory
# smoke reach the digest/rollup/recommender agent through this key
OBSERVATORY_KEY: web.AppKey = web.AppKey("observatory", object)
# shared-tier outage supervisor (runtime/tiersupervisor.py): tests and
# the L2-outage smoke reach the island/journal state machine here
TIER_SUPERVISOR_KEY: web.AppKey = web.AppKey("tier_supervisor", object)
# telemetry warehouse + traffic-mix classifier (runtime/telemetry.py):
# tests and the telemetry smoke reach the archive/classifier here
TELEMETRY_KEY: web.AppKey = web.AppKey("telemetry", object)

# routes that run the image pipeline get a trace; infrastructure routes
# (/metrics scrapes, health probes) would only fill the ring with noise
_TRACED_ROUTES = frozenset(("upload", "path"))

_ERROR_STATUS = {
    SecurityException: 403,
    ReadFileException: 404,
    InvalidArgumentException: 400,
    UnsupportedMediaException: 415,
    DeadlineExceededException: 504,
    # negative-cached origin (runtime/brownout.py NegativeCache): the
    # upstream, not this request, is the problem — a fast 502
    OriginUnavailableException: 502,
    ServiceUnavailableException: 503,
    # source over the configured byte/pixel bound (runtime/memgovernor.py
    # satellites): the request can never succeed — 413, not 503
    PayloadTooLargeException: 413,
    ExecFailedException: 500,
    # server-side misconfiguration surfacing per-request (e.g. a signed
    # URL arriving with no security_key configured): our fault, 500 —
    # mapped EXPLICITLY so flylint's exception-unmapped rule can prove
    # every exceptions.py class has a deliberate status
    MissingParamsException: 500,
}

HOMEPAGE = """<!doctype html>
<html><head><title>flyimg-tpu</title>
<style>
 body { font-family: system-ui, sans-serif; max-width: 46em; margin: 3em auto;
        line-height: 1.5; padding: 0 1em; }
 code { background: #f3f3f3; padding: .1em .3em; border-radius: 3px; }
 input { font: inherit; padding: .3em; width: 100%; box-sizing: border-box; }
 label { font-size: .85em; color: #555; }
 .row { display: flex; gap: .6em; margin: .4em 0; }
 .row > div { flex: 1; }
 img.demo { max-width: 100%; border: 1px solid #ddd; margin-top: 1em; }
 footer { margin-top: 2em; font-size: .85em; color: #777; }
</style></head>
<body>
<h1>flyimg-tpu</h1>
<p>TPU-native on-the-fly image resizing, cropping and compression —
batched JAX/XLA pixel pipeline behind a flyimg-compatible URL API.</p>
<p>Usage: <code>GET /upload/{options}/{image-url}</code> — e.g.
<code>/upload/w_300,h_250,c_1/https://example.com/image.jpg</code>.
Common options: <code>w h c g r q o rz ett bg smc fc fb blr sh unsh clsp
mnchr e gf pg tm rf</code> (see <code>docs/url-options.md</code>).</p>
<h2>Try it</h2>
<div class="row">
 <div><label>options</label><input id="opts" value="w_300,h_250,c_1"></div>
</div>
<div class="row">
 <div><label>image URL</label><input id="src"
  value="https://raw.githubusercontent.com/flyimg/flyimg/main/web/Rovinj-Croatia.jpg"></div>
</div>
<div class="row"><div>
 <button onclick="go()">transform</button>
 <code id="url"></code>
</div></div>
<img id="out" class="demo" alt="" style="display:none">
<script>
function go() {
  var u = '/upload/' + document.getElementById('opts').value + '/' +
          document.getElementById('src').value;
  document.getElementById('url').textContent = u;
  var img = document.getElementById('out');
  img.style.display = 'block';
  img.src = u;
}
</script>
<footer><a href="/metrics">metrics</a> · <a href="/healthz">health</a></footer>
</body></html>"""


def make_app(params: Optional[AppParameters] = None) -> web.Application:
    params = params or AppParameters()
    from flyimg_tpu.runtime import BatchController, tracing
    from flyimg_tpu.runtime.logging import access_log
    from flyimg_tpu.runtime.metrics import MetricsRegistry

    from flyimg_tpu.runtime.slo import SloEngine

    metrics = MetricsRegistry(
        exemplars=bool(params.by_key("metrics_exemplars", True))
    )
    tracer = tracing.Tracer.from_params(params, metrics=metrics)
    # declarative SLOs evaluated over sliding windows (runtime/slo.py):
    # flyimg_slo_* gauges, /debug/slo, breach log+span events
    slo = SloEngine.from_params(params, metrics=metrics)
    slo.register_metrics(metrics)
    metrics.attach_slo(slo)
    # performance observatory (docs/observability.md): the per-plan XLA
    # cost ledger (process-wide, like the program caches it mirrors),
    # the batch flight recorder, and the on-demand device profiler
    from flyimg_tpu.runtime.costledger import get_ledger
    from flyimg_tpu.runtime.flightrecorder import FlightRecorder
    from flyimg_tpu.runtime.profiling import DeviceProfiler

    cost_ledger = get_ledger()
    cost_ledger.configure(
        max_entries=int(params.by_key("costledger_max_entries", 256))
    )
    cost_ledger.register_metrics(metrics)
    flight_recorder = FlightRecorder.from_params(params, metrics=metrics)
    profiler = DeviceProfiler.from_params(params, metrics=metrics)
    # the automatic dump triggers: the PR-4 SLO breach event and the
    # PR-5 brownout escalation hook — both fire while the evidence (the
    # launches that built the burn/pressure) is still in the ring
    slo.add_breach_listener(
        lambda info: flight_recorder.dump("slo_breach", context=info)
    )
    debug_enabled = bool(params.by_key("debug"))
    log_access = bool(params.by_key("log_access", True))
    # serving resample kernel (dense | banded | auto): a process global
    # because the program caches the choice keys into are process-wide
    # too (ops/resample.py; ROADMAP.md D11), so two apps in one process
    # share it and the last one built wins. Applied BEFORE any program is
    # built so the first compile already runs the configured variant.
    from flyimg_tpu.ops.resample import set_kernel_mode

    set_kernel_mode(str(params.by_key("resample_kernel", "dense")))
    storage = make_storage(params, metrics=metrics)
    import jax

    from flyimg_tpu.compilecache import DEFAULT_DIR, enable_compile_cache
    from flyimg_tpu.parallel.mesh import require_accelerator

    # Chip or fail: the backend initialises in process, and a CPU backend
    # nobody pinned (JAX_PLATFORMS=cpu) is a boot error, not a degraded
    # mode — JAX falls back to the CPU on its own when accelerator init
    # fails, and every health surface would then read "ok".
    device_info = require_accelerator()
    import logging

    from flyimg_tpu.codecs import native_codec

    # what the native host codec loader found, once: PIL-only serving is
    # a different host-stage cost under the same stage names
    host_codec = "native" if native_codec.available() else "pil"
    logging.getLogger("flyimg.boot").info(
        "backend %s (%s) x%d, host codec %s",
        device_info["platform"], device_info["device_kind"],
        device_info["count"], host_codec,
        extra={"event": "boot.backend", "host_codec": host_codec,
               **device_info},
    )
    # persistent XLA compilation cache: programs compiled once survive
    # process restarts (flyimg_tpu/compilecache.py says where it lives)
    enable_compile_cache(params.by_key("compilation_cache_dir", DEFAULT_DIR))

    # with more than one chip, shard every batch over a data-parallel mesh
    # (SPMD fan-out — the v4-8 serving story; parallel/mesh.py). Serving
    # meshes span LOCAL devices only: each pod host runs its own batcher
    # over its own chips (share-nothing across hosts, like the reference's
    # scale-out story) — a global mesh would need every host to launch the
    # same SPMD program in lockstep and would reject device_put of
    # host-local request pixels as non-addressable. Global meshes remain
    # the training/offline story (parallel/dist.py, __graft_entry__).
    mesh = None
    sp_mesh = None
    local_devices = jax.local_devices()
    if len(local_devices) > 1:
        from flyimg_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(devices=local_devices)
        sp_mesh = make_mesh(axis_names=("sp",), devices=local_devices)
    # admission bound: pending (queued or executing) submissions per
    # controller; over it, requests shed as 503 + Retry-After instead of
    # queueing into collapse (runtime/resilience.py). 0 = unbounded.
    shed_retry_after = float(params.by_key("shed_retry_after_s", 1.0))
    # blast-radius containment knobs shared by both controllers — the
    # same mapping bulk sweeps read (runtime/batcher.py
    # containment_params; docs/resilience.md)
    from flyimg_tpu.runtime.batcher import containment_params

    containment = containment_params(params)
    # memory governor (runtime/memgovernor.py; docs/resilience.md
    # "Memory governor"): HBM-aware launch admission + AIMD capacity
    # ceilings (device side), a decode byte budget and an RSS→brownout
    # watchdog (host side). Every piece is default off and inert — the
    # batcher holds no governor, the handler no accountant, brownout no
    # RSS source — so disabled serving is byte-identical (pinned by
    # tests/test_memgovernor.py).
    from flyimg_tpu.runtime.memgovernor import (
        HostByteAccountant,
        MemoryGovernor,
        RssWatchdog,
    )

    from flyimg_tpu.codecs.pil_codec import set_max_pixels

    set_max_pixels(int(params.by_key("mem_max_source_pixels", 0) or 0))
    governor = MemoryGovernor.from_params(params, metrics=metrics)
    mem_accountant = HostByteAccountant.from_params(params, metrics=metrics)
    rss_watchdog = RssWatchdog.from_params(params, metrics=metrics)
    if governor.enabled:
        governor.register_metrics(metrics)
    if mem_accountant.enabled:
        mem_accountant.register_metrics(metrics)
    if rss_watchdog.enabled:
        rss_watchdog.register_metrics(metrics)
    # backend supervisor (runtime/devicesupervisor.py; docs/resilience.md
    # "Backend failover"): watches device-batch outcomes for a
    # classified-transient failure STORM, trips the backend breaker,
    # fails the replica over to forced-CPU rendering, and re-promotes
    # after clean probes. Default off: the batcher carries no supervisor
    # reference, no metrics register, no threads exist — byte-identical
    # serving (pinned by tests/test_device_supervisor.py).
    from flyimg_tpu.runtime.devicesupervisor import DeviceSupervisor

    supervisor = DeviceSupervisor.from_params(params, metrics=metrics)
    batcher = BatchController(
        max_batch=int(params.by_key("batch_max_size", 64)),
        deadline_ms=float(params.by_key("batch_deadline_ms", 4.0)),
        metrics=metrics,
        mesh=mesh,
        pipeline_depth=int(params.by_key("batch_pipeline_depth", 2)),
        max_queue_depth=int(params.by_key("batch_max_queue_depth", 0)),
        shed_retry_after_s=shed_retry_after,
        name="device",
        flight_recorder=flight_recorder,
        profiler=profiler,
        supervisor=supervisor if supervisor.enabled else None,
        governor=governor if governor.enabled else None,
        **containment,
    )
    if supervisor.enabled:

        def _device_mesh_factory():
            # re-queried at every re-promotion: the revived backend's
            # device list, not boot's
            local = jax.local_devices()
            if len(local) > 1:
                from flyimg_tpu.parallel.mesh import make_mesh

                return make_mesh(devices=local)
            return None

        supervisor.attach(
            batcher=batcher, mesh_factory=_device_mesh_factory
        )
        supervisor.register_metrics(metrics)
    # host codec work gets its OWN controller/thread: JPEG-miss decode
    # batches (native DecodePool) must not serialize with device launches
    codec_batcher = BatchController(
        max_batch=int(params.by_key("decode_batch_max", 32)),
        deadline_ms=float(params.by_key("decode_deadline_ms", 1.0)),
        metrics=metrics,
        max_queue_depth=int(params.by_key("decode_max_queue_depth", 0)),
        shed_retry_after_s=shed_retry_after,
        name="codec",
        flight_recorder=flight_recorder,
        **containment,
    )
    # fault-injection hook (flyimg_tpu/testing/faults.py): tests assemble
    # a full app with scripted faults at named pipeline points; absent in
    # production configs
    injector = params.by_key("fault_injector")
    if injector is not None:
        from flyimg_tpu.testing import faults

        faults.install(injector)
    # face engine: 'auto' (haar where cascade XMLs exist, else the skin
    # proposer), 'haar', 'blazeface' (+ face_checkpoint), or 'facefind'
    from flyimg_tpu.models.faces import make_face_backend

    face_backend = make_face_backend(
        str(params.by_key("face_backend", "auto")),
        params.by_key("face_checkpoint"),
    )
    # brownout/degradation engine (runtime/brownout.py): consumes the
    # pressure signals wired below and drives the per-level degradation
    # policies inside the handler. Disabled by default — with
    # brownout_enable false the handler paths it guards are never taken
    # and responses are byte-for-byte the pre-brownout behavior.
    from flyimg_tpu.runtime.brownout import BrownoutEngine

    brownout = BrownoutEngine.from_params(params, metrics=metrics)
    brownout.register_metrics(metrics)
    # flight-recorder wiring: records carry the live brownout level, and
    # every escalation dumps the ring (the launches that built the
    # pressure are the evidence an operator wants afterwards)
    flight_recorder.attach(level_fn=brownout.level)
    brownout.add_transition_listener(
        lambda info: flight_recorder.dump(
            "brownout_escalation", context=info
        )
    )
    # fleet routing tier (runtime/fleet.py; docs/fleet.md): rendezvous
    # owner placement of derived cache keys over the static
    # fleet_replicas set, with owner proxying in fleet_route=proxy.
    # Inert (enabled False, never consulted) with fleet_replicas empty.
    from flyimg_tpu.runtime.fleet import HOP_HEADER, FleetRouter, route_key

    fleet = FleetRouter.from_params(params, metrics=metrics)
    replica_id = str(params.by_key("fleet_replica_id", "") or "")
    # pipelined host stage DAG (runtime/hostpipeline.py;
    # docs/host-pipeline.md): bounded fetch/decode/encode worker pools
    # with admission-gate backpressure. Inert (no pools, no gauges, no
    # new behavior) with host_pipeline_enable off.
    from flyimg_tpu.runtime.hostpipeline import HostPipeline

    host_pipeline = HostPipeline.from_params(
        params, metrics=metrics, flight_recorder=flight_recorder
    )
    for pool_name, stage_pool in host_pipeline.pools():
        metrics.gauge(
            f'flyimg_host_pool_queue_depth{{pool="{pool_name}"}}',
            "Pending (queued or executing) tasks per host stage pool",
            fn=lambda p=stage_pool: float(p.pending),
        )
    # telemetry warehouse + traffic-mix classifier (runtime/telemetry.py;
    # docs/observability.md "Telemetry warehouse & traffic-mix
    # classifier"): durable JSONL archive of the signal vocabulary plus
    # the nearest-centroid traffic-shape label. Constructed before the
    # handler (which records per-request mix features into it); the
    # signal surfaces attach after the observatory below. Inert (no
    # directory, no metrics, handler holds None) with telemetry_enable
    # off — byte-identical serving pinned by tests/test_telemetry.py.
    from flyimg_tpu.runtime.telemetry import TelemetryPipeline

    telemetry = TelemetryPipeline.from_params(
        params, metrics=metrics, replica_id=replica_id
    )
    handler = ImageHandler(
        storage, params, batcher=batcher, codec_batcher=codec_batcher,
        face_backend=face_backend, metrics=metrics, sp_mesh=sp_mesh,
        brownout=brownout, host_pipeline=host_pipeline,
        device_supervisor=supervisor if supervisor.enabled else None,
        telemetry=telemetry if telemetry.enabled else None,
        mem_accountant=mem_accountant if mem_accountant.enabled else None,
    )
    # shared-tier outage supervisor (runtime/tiersupervisor.py;
    # docs/resilience.md "Island mode"): watches L2 storage / lease /
    # membership-marker outcomes for a consecutive-failure STORM, trips
    # the tier into island mode (every L2 op short-circuits locally,
    # writes queue in the write-behind journal), re-promotes after clean
    # probes and replays the journal, and runs the anti-entropy
    # scrubber. Default off: no feed, no threads, no metrics —
    # byte-identical serving (pinned by tests/test_tier_supervisor.py).
    from flyimg_tpu.runtime.tiersupervisor import TierSupervisor

    tier_supervisor = TierSupervisor.from_params(params, metrics=metrics)
    if tier_supervisor.enabled:
        tier_supervisor.attach(
            storage=storage, variant_index=handler.variants
        )
        if hasattr(storage, "attach_supervisor"):
            storage.attach_supervisor(tier_supervisor)
        if handler.l2lease is not None:
            handler.l2lease.supervisor = tier_supervisor
        handler.variants.attach_supervisor(tier_supervisor)
        tier_supervisor.register_metrics(metrics)
    # state gauges (runtime/metrics.py Gauge): sampled at /metrics render
    inflight = metrics.gauge(
        "flyimg_inflight_requests", "HTTP requests currently in flight"
    )
    metrics.gauge(
        "flyimg_breaker_open",
        "Upstream circuit breakers currently open or half-open",
        fn=handler.fetch_policy.breakers.open_count,
    )
    metrics.gauge(
        "flyimg_traces_buffered",
        "Traces held in the tail-sampling ring buffer",
        fn=lambda: len(tracer),
    )
    # derivative-reuse variant index occupancy (runtime/variantindex.py;
    # docs/caching.md): reuse-safe renditions currently tracked — 0 and
    # static whenever reuse_enable is off
    metrics.gauge(
        "flyimg_variant_index_entries",
        "Reuse-safe renditions tracked by the per-source variant index",
        fn=lambda: float(len(handler.variants)),
    )
    # program-cache truth (ops/compose.py program_cache_entries): the
    # gauge behind the exact compile-hit accounting, replacing the old
    # miss-count inference (docs/observability.md)
    from flyimg_tpu.ops.compose import program_cache_entries

    metrics.gauge(
        "flyimg_program_cache_entries",
        "Live entries across the single-image and batched program caches",
        fn=program_cache_entries,
    )
    # host codec utilization (runtime/metrics.py PoolUtilization; the
    # codec layer wraps its pool calls): busy-ratio over the trailing
    # window, >1.0 = oversubscribed stage
    from flyimg_tpu.runtime.metrics import host_pool

    metrics.gauge(
        'flyimg_host_pool_busy_ratio{pool="decode"}',
        "Host codec pool busy-time share over the trailing window",
        fn=lambda: host_pool("decode").busy_ratio(),
    )
    metrics.gauge(
        'flyimg_host_pool_busy_ratio{pool="encode"}',
        "Host codec pool busy-time share over the trailing window",
        fn=lambda: host_pool("encode").busy_ratio(),
    )
    # the engine's pressure sources: batcher queue depth + efficiency
    # window, SLO burn rates, the inflight gauge, breaker-open count
    brownout.attach(
        batchers=(batcher, codec_batcher),
        slo=slo,
        # Gauge.value is a property: wrap it so the engine samples the
        # LIVE value each evaluation, not the attach-time float
        inflight_fn=lambda: inflight.value,
        breaker_open_fn=handler.fetch_policy.breakers.open_count,
        # stage-DAG saturation (worst pool pending/bound): host overload
        # the batcher queues cannot see feeds the same brownout ladder
        host_pipeline=host_pipeline,
        # followers parked behind remote lease leaders (docs/fleet.md):
        # a fleet-wide hot-key stampede is load this replica carries
        # even though its own queues look empty
        lease_waiters_fn=(
            (lambda: float(handler.l2lease.waiters))
            if handler.l2lease is not None else None
        ),
        # a replica failed over to CPU rendering carries a fixed
        # device_health pressure (docs/degradation.md "Device-loss
        # pressure") so degradation reacts
        device_supervisor=supervisor if supervisor.enabled else None,
        # process RSS vs the host memory limit (runtime/memgovernor.py
        # RssWatchdog): approaching the limit walks the same
        # stale-serve → degrade → shed ladder as every other signal
        rss_fn=rss_watchdog.pressure if rss_watchdog.enabled else None,
    )
    # fleet-wide warm start (runtime/warmstart.py; docs/fleet.md
    # "Membership and elasticity"): seed this replica's program cache
    # from the peer-published manifest on the SHARED tier BEFORE the
    # first request, then record/publish what this replica compiles.
    # Seeding is synchronous here by design — a replica that announces
    # itself ready has already absorbed its compile storm.
    # Inert (no recorder, no manifest IO, no metrics) with
    # warmstart_enable off.
    from flyimg_tpu.runtime import warmstart as warmstart_mod

    warmstart = warmstart_mod.WarmStartCache.from_params(
        params, storage=storage.shared, metrics=metrics
    )
    if warmstart.enabled:
        warmstart.install()
        warmstart.seed_programs(mesh=mesh)
    # elastic fleet membership (runtime/membership.py; docs/fleet.md):
    # announce/heartbeat/watch over TTL'd markers on the shared tier,
    # feeding FleetRouter.update_replicas so joins/leaves/crashes
    # re-home only the moved keys within one TTL. A device-down replica
    # heartbeats as degraded (the router's health gate routes around
    # it); the warm-start manifests publish on the membership beat.
    # Inert (no markers, no thread, no metrics) with
    # fleet_membership_enable off.
    from flyimg_tpu.runtime.membership import FleetMembership

    membership = FleetMembership.from_params(
        params,
        storage=storage.shared,
        router=fleet,
        supervisor=supervisor if supervisor.enabled else None,
        warmstart=warmstart if warmstart.enabled else None,
        metrics=metrics,
    )
    if tier_supervisor.enabled:
        # islanded heartbeats/listings short-circuit (no marker IO
        # timeouts) and marker outcomes feed the tier storm counter
        membership.tier_supervisor = tier_supervisor
    # fleet observatory + autoscale recommender (runtime/observatory.py;
    # docs/fleet.md "Fleet observatory & autoscaling signal"): publish
    # this replica's signal digest on the membership beat, assemble
    # every peer's into the fleet rollup (flyimg_fleet_* gauges,
    # /debug/fleet/status), and run the deterministic scale-out/in
    # recommender over it — scale-in honored inward through the
    # graceful-drain path when fleet_autoscale_drain is on. Inert (no
    # markers, no metrics, no digest IO) with fleet_observatory_enable
    # off or membership off.
    from flyimg_tpu.runtime.observatory import (
        FleetObservatory,
        reuse_signal_fn,
    )

    observatory = FleetObservatory.from_params(
        params,
        storage=storage.shared,
        membership=membership,
        slo=slo,
        brownout=brownout,
        supervisor=supervisor if supervisor.enabled else None,
        metrics=metrics,
    )
    if tier_supervisor.enabled:
        # islanded beats skip digest IO entirely and mark the cached
        # rollup stale — degrading loudly instead of timing out quietly
        observatory.tier_supervisor = tier_supervisor
    if observatory.enabled:
        observatory.window.attach(
            metrics=metrics,
            slo=slo,
            brownout=brownout,
            host_pipeline=host_pipeline,
            flight_recorder=flight_recorder,
            reuse_fn=(
                reuse_signal_fn(metrics)
                if handler.reuse_enable else None
            ),
        )
        # the digest/rollup/recommendation beat rides the membership
        # heartbeat, the same piggyback slot as the warm-start publish
        membership.observatory = observatory
    if telemetry.enabled:
        # the warehouse owns its OWN SignalWindow (launches_delta diffs
        # per instance — sharing the observatory's would corrupt both)
        telemetry.attach(
            metrics=metrics,
            slo=slo,
            brownout=brownout,
            host_pipeline=host_pipeline,
            flight_recorder=flight_recorder,
            reuse_fn=(
                reuse_signal_fn(metrics)
                if handler.reuse_enable else None
            ),
            ledger_fn=cost_ledger.aggregates,
        )
        # satellite retention unification: dump files join the archive's
        # retention family (telemetry_retention_max_dumps > 0 overrides
        # the legacy flightrecorder_max_dumps bound, kept as the alias)
        telemetry.adopt_dump_retention(
            flight_recorder,
            int(params.by_key("telemetry_retention_max_dumps", 0)),
        )

    @web.middleware
    async def observability(request: web.Request, handler):
        """The one per-request observability choke point: request/status
        metrics (including unexpected 500s), the in-flight gauge, trace
        lifecycle for pipeline routes (mint-or-adopt at ingress, tail
        sample at completion, `traceparent` echoed on the response), and
        the structured JSON access log carrying trace/span ids.
        (The `handler` param name is required by aiohttp and shadows the
        ImageHandler binding only inside this function.)"""
        # logical route name when registered (upload/path keep their names
        # under `routes` pattern overrides — a renamed pattern must not
        # silently disable tracing); canonical path segment otherwise
        route = request.match_info.route.name or (
            request.match_info.route.resource.canonical.strip("/").split("/")[0]
            if request.match_info.route.resource is not None
            else "unmatched"
        ) or "index"
        trace = None
        if route in _TRACED_ROUTES:
            trace = tracer.start(request.headers.get("traceparent"))
            # brownout pressure re-evaluation rides the request path
            # (rate-limited inside the engine; disabled = one bool
            # check) so the level tracks load without a timer thread.
            # It runs INSIDE this request's trace activation so a level
            # transition's brownout.transition span event lands on the
            # request that triggered it (add_event is a no-op with no
            # ambient trace).
            with tracing.activate(trace):
                brownout.evaluate()
                # the supervisor's failover/re-promotion span events
                # (queued by its worker threads, which have no ambient
                # trace) land on this request — one list check when idle
                supervisor.evaluate()
                # tier island/repromote events drain the same way
                tier_supervisor.evaluate()
                # the telemetry snapshot beat rides the same hook
                # (rate-limited inside it; one bool check when off) so
                # window records and mix flips cost no timer thread
                telemetry.evaluate()
            if trace is not None:
                trace.root.set_attribute("route", route)
                trace.root.set_attribute("http.method", request.method)
                trace.root.set_attribute("http.path", request.path)
                if request.remote:
                    trace.root.set_attribute("net.peer", request.remote)
                if replica_id:
                    # fleet attribution (docs/fleet.md): which replica's
                    # ring this trace lives in — the join key between
                    # multi-replica bench rows, log lines, and traces
                    trace.root.set_attribute("fleet.replica_id", replica_id)
                request["flyimg.trace"] = trace
        inflight.inc()
        t0 = time.perf_counter()
        status = 500
        response = None
        try:
            response = await handler(request)
            status = response.status
            return response
        except web.HTTPException as exc:
            status = exc.status
            raise
        finally:
            inflight.dec()
            duration = time.perf_counter() - t0
            metrics.record_request(route, status)
            if route in _TRACED_ROUTES:
                # the SLI is the image pipeline, not probes or scrapes;
                # record BEFORE tracer.finish so a breach's span event
                # rides the triggering trace into the ring
                slo.record(duration, ok=status < 500, trace=trace)
            if (
                debug_enabled
                and replica_id
                and route in _TRACED_ROUTES
                and response is not None
                and "X-Flyimg-Replica" not in response.headers
            ):
                # debug-only replica attribution on every response this
                # replica actually produced; a PROXIED response keeps the
                # rendering owner's header (docs/fleet.md), so bench rows
                # attribute latency to the replica that did the work
                response.headers["X-Flyimg-Replica"] = replica_id
            if trace is not None:
                trace.root.set_attribute("http.status", status)
                tracer.finish(
                    trace, "error" if status >= 500 else "ok"
                )
                if response is not None:
                    # echo OUR position in the trace so the caller (and
                    # any test) can join response -> trace -> span tree
                    response.headers["traceparent"] = (
                        tracing.format_traceparent(
                            trace.trace_id, trace.root.span_id
                        )
                    )
                    if debug_enabled:
                        # per-request stage split from the span tree —
                        # curl-visible without opening the trace ring
                        st_header = tracing.server_timing(trace)
                        if st_header:
                            response.headers["Server-Timing"] = st_header
            if log_access:
                access_log(
                    method=request.method,
                    path=request.path_qs,
                    route=route,
                    status=status,
                    duration_s=duration,
                    bytes_sent=(
                        response.content_length or 0
                        if response is not None else 0
                    ),
                    remote=request.remote,
                    trace_id=trace.trace_id if trace is not None else None,
                    span_id=(
                        trace.root.span_id if trace is not None else None
                    ),
                    user_agent=request.headers.get("User-Agent"),
                    replica=replica_id or None,
                )

    app = web.Application(
        client_max_size=64 * 1024 * 1024, middlewares=[observability]
    )
    app[PARAMS_KEY] = params
    app[HANDLER_KEY] = handler
    app[METRICS_KEY] = metrics
    app[TRACER_KEY] = tracer
    app[FLEET_KEY] = fleet
    app[SUPERVISOR_KEY] = supervisor
    app[MEMBERSHIP_KEY] = membership
    app[OBSERVATORY_KEY] = observatory
    app[TIER_SUPERVISOR_KEY] = tier_supervisor
    app[TELEMETRY_KEY] = telemetry

    # readiness vs liveness: /healthz answers "is the process + device
    # runtime up", /readyz answers "should a load balancer route here".
    # Graceful shutdown flips readiness FIRST (aiohttp runs on_shutdown
    # before on_cleanup), so LBs stop routing while the batcher drains
    # in-flight device work instead of feeding a dying instance.
    draining = {"flag": False}

    async def _begin_drain(_app):
        draining["flag"] = True
        # graceful scale-in, phase 1: flip the membership marker to
        # draining so peers stop routing owned keys here on their next
        # watch beat, while the bounded drains below finish in-flight
        # work. No-op with membership off.
        membership.begin_drain()

    app.on_shutdown.append(_begin_drain)

    drain_timeout_s = float(params.by_key("shutdown_drain_timeout_s", 30.0))

    async def _close_batcher(_app):
        draining["flag"] = True  # direct-cleanup callers flip it too
        membership.begin_drain()  # direct-cleanup callers drain too
        await fleet.aclose()
        supervisor.close()
        batcher.close(drain_timeout_s)
        codec_batcher.close(drain_timeout_s)
        handler.close()
        host_pipeline.close(drain_timeout_s)
        # phase 2: the drains finished — publish what this replica
        # compiled for the next scale-out, release the membership
        # marker, and disarm the process-wide recorder (like
        # faults.clear below: process-global state must not leak
        # across apps/tests)
        if warmstart.enabled:
            warmstart.maybe_publish()
            warmstart_mod.uninstall()
        observatory.close()  # digest released before the member marker
        membership.close()
        # after the marker release attempt: an islanded close skips the
        # marker IO above, and the prober/scrubber threads stop here
        tier_supervisor.close()
        # final telemetry beat (the shutdown window) + segment release
        telemetry.close()
        if injector is not None:
            from flyimg_tpu.testing import faults

            faults.clear()

    app.on_cleanup.append(_close_batcher)

    if membership.enabled:

        async def _start_membership(_app):
            membership.start()

        app.on_startup.append(_start_membership)

    if tier_supervisor.enabled:

        async def _start_tier_supervisor(_app):
            # the prober only exists while islanded; this starts the
            # (optional) anti-entropy scrub loop
            tier_supervisor.start()

        app.on_startup.append(_start_tier_supervisor)

    # automatic cache budget: prune least-recently-modified outputs in the
    # background when `cache_max_bytes` is set (local storage only — S3 /
    # GCS deployments use bucket lifecycle policies)
    cache_max = int(params.by_key("cache_max_bytes", 0) or 0)
    # a non-positive interval disables the loop (and can never busy-spin)
    prune_interval = float(params.by_key("cache_prune_interval_s", 300.0))
    # orphaned .part reclaim rides the same pass (storage/local.py
    # prune): a writer killed mid-write leaks a temp file invisible to
    # listing and the size budget — the TTL bounds how long it survives
    part_ttl = float(params.by_key("cache_part_ttl_s", 3600.0) or 0.0)
    if cache_max > 0 and prune_interval > 0 and hasattr(storage, "prune"):

        async def _prune_loop(app_):
            import contextlib
            import logging

            loop = asyncio.get_running_loop()
            log = logging.getLogger(__name__)

            async def run():
                while True:
                    await asyncio.sleep(prune_interval)
                    try:
                        summary = await loop.run_in_executor(
                            None, storage.prune, cache_max, part_ttl
                        )
                    except Exception as exc:
                        # a transient scan error must not silently END
                        # budget enforcement for the process lifetime
                        log.warning("cache prune pass failed: %s", exc)
                        continue
                    if summary["deleted"]:
                        metrics.counter(
                            "flyimg_cache_pruned_total",
                            "Cached outputs evicted by the size budget",
                        ).inc(summary["deleted"])
                    if summary.get("parts"):
                        metrics.counter(
                            "flyimg_cache_part_orphans_total",
                            "Orphaned .part temporaries reclaimed by "
                            "the prune pass",
                        ).inc(summary["parts"])

            task = asyncio.create_task(run())
            yield
            task.cancel()
            with contextlib.suppress(asyncio.CancelledError):
                await task

        app.cleanup_ctx.append(_prune_loop)

    def _accepts_webp(request: web.Request) -> bool:
        return "image/webp" in request.headers.get("Accept", "")

    async def _process(request: web.Request):
        options = request.match_info["options"]
        image_src = request.match_info["imageSrc"]
        # the request's latency budget starts HERE, at ingress — queue
        # time in the executor counts against it, so an overloaded
        # worker pool surfaces as fast 504s rather than invisible queueing
        deadline = Deadline.from_params(params, metrics=metrics)
        trace = request.get("flyimg.trace")
        accepts_webp = _accepts_webp(request)
        loop = asyncio.get_running_loop()

        def run():
            # the trace binds ambient INSIDE the worker thread: executor
            # threads don't inherit asyncio context, and every pipeline
            # stage below reads it through tracing.current_trace()
            with tracing.activate(trace):
                return handler.process_image(
                    options, image_src, accepts_webp=accepts_webp,
                    deadline=deadline,
                )

        return await loop.run_in_executor(None, run)

    async def index(_request: web.Request) -> web.Response:
        return web.Response(text=HOMEPAGE, content_type="text/html")

    async def _route_fleet(request: web.Request) -> Optional[web.Response]:
        """Owner routing for one /upload request (runtime/fleet.py;
        docs/fleet.md). Returns the proxied owner response, or None when
        THIS replica should render: it owns the key, the request already
        hopped once, the mode is ``local``, or the owner is down (breaker
        open / transport failure — the local render is the fallback, and
        the shared-L2 lease still dedups the work fleet-wide)."""
        if not fleet.enabled:
            return None
        key = route_key(
            request.match_info["options"], request.match_info["imageSrc"],
            separator=str(params.by_key("options_separator", ",")),
        )
        owner = fleet.owner(key)
        trace = request.get("flyimg.trace")
        # direct start_span/end rather than the ambient tracing.span
        # context manager: this coroutine awaits mid-span, and ambient
        # state is thread-local — another request's coroutine on this
        # loop thread would inherit our span across the await
        route_span = (
            trace.start_span("fleet.route") if trace is not None else None
        )
        outcome = "self"
        try:
            if HOP_HEADER in request.headers:
                # already forwarded once: render here regardless of what
                # our (possibly skewed) replica set says — no proxy loops
                outcome = "hop"
                return None
            if owner == fleet.self_id:
                return None
            if not fleet.proxies:
                # fleet_route=local: render here; the L2 write-through
                # makes the result every replica's cache hit anyway
                outcome = "local"
                return None
            deadline_cap = (
                float(params.by_key("request_deadline_s", 0.0) or 0.0)
                or None
            )
            relayed = await fleet.proxy(
                owner, request.path_qs, request.headers,
                timeout_s=deadline_cap,
                traceparent=(
                    tracing.format_traceparent(
                        trace.trace_id, route_span.span_id
                    )
                    if trace is not None and route_span is not None
                    else None
                ),
            )
            if relayed is None:
                outcome = "fallback"
                return None
            outcome = "proxied"
            status, headers, body = relayed
            return web.Response(status=status, body=body, headers=headers)
        finally:
            fleet.record(outcome)
            if route_span is not None:
                route_span.attributes.update({
                    "fleet.owner": owner,
                    "fleet.self": fleet.self_id,
                    "fleet.outcome": outcome,
                })
                route_span.end()

    async def upload(request: web.Request) -> web.Response:
        routed = await _route_fleet(request)
        if routed is not None:
            return routed
        try:
            result = await _process(request)
        except AppException as exc:
            return _error_response(exc)
        headers = image_headers(
            result, params.by_key("header_cache_days", 365)
        )
        if debug_enabled and result.reused_from:
            # debug-only reuse attribution (docs/caching.md): which
            # cached ancestor this render was re-derived from — the
            # per-request signal tools/bench_http.py --mix multisize
            # splits its latency rows on. Never emitted with debug off
            # or reuse off, so production headers are unchanged.
            headers["X-Flyimg-Reuse"] = result.reused_from
        if is_not_modified(request.headers, headers):
            return web.Response(
                status=304,
                headers={
                    k: headers[k] for k in NOT_MODIFIED_HEADERS if k in headers
                },
            )
        return web.Response(body=result.content, headers=headers)

    async def path(request: web.Request) -> web.Response:
        try:
            result = await _process(request)
        except AppException as exc:
            return _error_response(exc)
        base = f"{request.scheme}://{request.host}"
        url = storage.public_url(result.spec.name, base)
        return web.Response(text=url)

    async def metrics_route(request: web.Request) -> web.Response:
        """Prometheus scrape with content negotiation: clients that
        Accept OpenMetrics get exemplars + the `# EOF` terminator; the
        default text/plain response stays pure 0.0.4 (the classic text
        parser has no exemplar syntax and would abort the whole scrape
        on one)."""
        openmetrics = (
            "application/openmetrics-text"
            in request.headers.get("Accept", "")
        )
        if openmetrics:
            return web.Response(
                text=metrics.render_prometheus(openmetrics=True),
                headers={
                    "Content-Type": (
                        "application/openmetrics-text; version=1.0.0; "
                        "charset=utf-8"
                    )
                },
            )
        return web.Response(
            text=metrics.render_prometheus(),
            content_type="text/plain",
            charset="utf-8",
        )

    async def healthz(_request: web.Request) -> web.Response:
        """Liveness + device visibility (the reference's analog is 'is
        nginx/php-fpm up'; here the chip is part of the health surface).
        Carries `application_name` so fleet probes can tell which
        deployment answered."""
        import json as _json

        app_name = str(params.by_key("application_name", "flyimg-tpu"))
        try:
            import jax

            devices = [f"{d.platform}:{d.id}" for d in jax.devices()]
            body = {
                "status": "ok", "app": app_name, "devices": devices,
                "device_kind": device_info["device_kind"],
                "host_codec": host_codec,
            }
            status = 200
        except Exception as exc:  # device runtime down
            body = {"status": "error", "app": app_name, "error": str(exc)}
            status = 503
        return web.Response(
            text=_json.dumps(body), status=status,
            content_type="application/json",
        )

    async def readyz(_request: web.Request) -> web.Response:
        """Readiness (distinct from /healthz liveness): 503 while the app
        is draining for shutdown so load balancers pull this instance out
        of rotation before the batcher drain runs."""
        import json as _json

        # two drain initiators share this answer: process shutdown
        # (on_shutdown flips the flag) and an autoscale scale-in
        # nomination (the observatory calls membership.begin_drain()
        # directly — the marker flips for peers, and readiness must
        # agree so the external scaler pulls the nominated replica)
        if draining["flag"] or (
            membership.enabled and membership.current_status() == "draining"
        ):
            return web.Response(
                text=_json.dumps({"status": "draining"}), status=503,
                content_type="application/json",
            )
        doc = {"status": "ok"}
        if supervisor.enabled:
            # the device field the fleet health gate reads
            # (runtime/fleet.py _owner_device_ok): a device-down replica
            # stays ready (cache hits and CPU-degraded misses still
            # serve) but peers route owned keys around it. Absent
            # entirely with the supervisor off — byte-identical body.
            doc["device"] = "down" if supervisor.cpu_forced() else "ok"
        if membership.enabled:
            # the elastic drain walk (docs/fleet.md): ready ->
            # draining (503 above, via on_shutdown) -> gone. Absent
            # entirely with membership off — byte-identical body.
            doc["members"] = int(membership.member_count())
        if tier_supervisor.enabled:
            # an islanded replica stays READY (L1 hits and journaled
            # writes still serve) — the field is for operators and the
            # L2-outage smoke, not a routing gate. Absent entirely with
            # the supervisor off — byte-identical body.
            doc["tier"] = "island" if tier_supervisor.islanded() else "attached"
        return web.Response(
            text=_json.dumps(doc),
            content_type="application/json",
        )

    trace_lock = asyncio.Lock()

    async def debug_trace(request: web.Request) -> web.Response:
        """Capture a jax.profiler device trace for ?ms= milliseconds (default
        500, max 30s) into tmp_dir/traces; returns the trace directory. The
        TPU replacement for the reference's rf_1 'im-command' debugging
        (SURVEY.md section 5 tracing). Only served when the `debug` server
        parameter is on — profiling is an operator tool, not a public route."""
        import json as _json
        import os as _os

        if not params.by_key("debug"):
            return web.Response(
                status=403, text="debug disabled (set debug: true in params)"
            )
        try:
            ms = min(float(request.query.get("ms", 500)), 30_000.0)
            if not ms > 0:
                raise ValueError
        except ValueError:
            return web.Response(status=400, text="ms must be a positive number")
        if trace_lock.locked():
            return web.Response(status=409, text="a trace is already running")
        if profiler.busy:
            # the batch-scoped profiler (/debug/profile) and this
            # wall-clock capture share the ONE global jax profiler
            return web.Response(
                status=409, text="a /debug/profile capture is in flight"
            )
        trace_dir = _os.path.join(
            str(params.by_key("tmp_dir", "var/tmp")), "traces",
            time.strftime("%Y%m%d-%H%M%S"),
        )
        import jax

        async with trace_lock:
            jax.profiler.start_trace(trace_dir)
            try:
                await asyncio.sleep(ms / 1000.0)
            finally:
                jax.profiler.stop_trace()
        return web.Response(
            text=_json.dumps({"trace_dir": trace_dir, "captured_ms": ms}),
            content_type="application/json",
        )

    def _debug_gate() -> Optional[web.Response]:
        if not params.by_key("debug"):
            return web.Response(
                status=403, text="debug disabled (set debug: true in params)"
            )
        return None

    async def debug_traces_list(request: web.Request) -> web.Response:
        """Kept traces, newest first (summaries). Operator tool — gated
        on the `debug` server parameter like /debug/trace."""
        import json as _json

        denied = _debug_gate()
        if denied is not None:
            return denied
        try:
            limit = min(int(request.query.get("limit", 100)), 1000)
        except ValueError:
            return web.Response(status=400, text="limit must be an integer")
        return web.Response(
            text=_json.dumps({"traces": tracer.list(limit=limit)}),
            content_type="application/json",
        )

    def _debug_gate_404() -> Optional[web.Response]:
        """The perf-observability endpoints 404 (rather than 403) when
        debug is off: they are pure operator surface and their existence
        need not be advertised to the public internet."""
        if not params.by_key("debug"):
            return web.Response(status=404, text="not found")
        return None

    async def debug_slo(_request: web.Request) -> web.Response:
        """Objective, windowed p99s, error-budget remaining, and
        fast/slow burn rates as JSON (runtime/slo.py snapshot;
        docs/observability.md "SLOs and burn rates")."""
        import json as _json

        denied = _debug_gate_404()
        if denied is not None:
            return denied
        return web.Response(
            text=_json.dumps(slo.snapshot()),
            content_type="application/json",
        )

    async def debug_perf(_request: web.Request) -> web.Response:
        """Batch-efficiency analytics: per-controller rolling occupancy /
        padding waste / queue-wait share / compile amortization plus
        per-stage and device-time quantiles (runtime/metrics.py
        perf_snapshot; docs/observability.md "Batch efficiency")."""
        import json as _json

        denied = _debug_gate_404()
        if denied is not None:
            return denied
        doc = metrics.perf_snapshot()
        # stage-DAG occupancy/queue depth (runtime/hostpipeline.py):
        # null when the pipeline is off, per-pool workers/busy/pending
        # when on — the same document the bench harness scrapes
        doc["host_pipeline"] = (
            host_pipeline.snapshot() if host_pipeline.enabled else None
        )
        # fleet identity (docs/fleet.md): which replica produced these
        # batch-efficiency windows — bench_http --replicas joins the
        # per-replica occupancy/compile-miss deltas on this. Null when
        # the fleet tier is off.
        doc["fleet"] = (
            {
                "replica_id": replica_id,
                "replicas": fleet.replicas,
                "mode": fleet.mode,
            }
            if fleet.enabled else None
        )
        return web.Response(
            text=_json.dumps(doc),
            content_type="application/json",
        )

    async def debug_plans(_request: web.Request) -> web.Response:
        """Per-plan cost ledger: FLOPs / bytes accessed / peak device
        memory / compile wall time / cumulative device seconds keyed by
        program, plus program-cache introspection (runtime/costledger.py
        snapshot; docs/observability.md "Per-plan cost ledger")."""
        import json as _json

        denied = _debug_gate_404()
        if denied is not None:
            return denied
        from flyimg_tpu.ops.compose import program_cache_info

        doc = cost_ledger.snapshot()
        doc["program_cache"] = program_cache_info()
        return web.Response(
            text=_json.dumps(doc),
            content_type="application/json",
        )

    async def debug_flightrecorder(_request: web.Request) -> web.Response:
        """Batch flight recorder: the live per-launch ring + the dump
        inventory (runtime/flightrecorder.py snapshot;
        docs/observability.md "Batch flight recorder")."""
        import json as _json

        denied = _debug_gate_404()
        if denied is not None:
            return denied
        return web.Response(
            text=_json.dumps(flight_recorder.snapshot()),
            content_type="application/json",
        )

    async def debug_telemetry(_request: web.Request) -> web.Response:
        """Telemetry warehouse: classifier state (adopted/raw label,
        features, transitions) + the archive inventory + the unified
        artifact index (runtime/telemetry.py snapshot;
        docs/observability.md "Telemetry warehouse & traffic-mix
        classifier")."""
        import json as _json

        denied = _debug_gate_404()
        if denied is not None:
            return denied
        return web.Response(
            text=_json.dumps(telemetry.snapshot()),
            content_type="application/json",
        )

    async def debug_profile_get(_request: web.Request) -> web.Response:
        """On-demand profiler state + completed captures
        (runtime/profiling.py; docs/observability.md "On-demand device
        profiling")."""
        import json as _json

        denied = _debug_gate_404()
        if denied is not None:
            return denied
        return web.Response(
            text=_json.dumps(profiler.snapshot()),
            content_type="application/json",
        )

    async def debug_profile_arm(request: web.Request) -> web.Response:
        """Arm a device-profile capture of the next N batches
        (?batches=N, ?max_s=S; bounded by the profiling_* knobs). One
        concurrent capture; 409 while one is armed or running."""
        import json as _json

        denied = _debug_gate_404()
        if denied is not None:
            return denied
        if trace_lock.locked():
            # the wall-clock /debug/trace capture owns the one global
            # jax profiler right now (it already 409s in the other
            # direction while this profiler is busy)
            return web.Response(
                status=409, text="a /debug/trace capture is running"
            )
        try:
            batches = int(request.query.get("batches", 4))
            max_s = (
                float(request.query["max_s"])
                if "max_s" in request.query else None
            )
            if batches <= 0 or (max_s is not None and not max_s > 0):
                raise ValueError
        except ValueError:
            return web.Response(
                status=400,
                text="batches (int > 0) and max_s (seconds > 0) expected",
            )
        try:
            state = profiler.arm(batches, max_s)
        except RuntimeError as exc:
            return web.Response(status=409, text=str(exc))
        return web.Response(
            text=_json.dumps(state), content_type="application/json"
        )

    async def debug_profile_download(request: web.Request) -> web.Response:
        """Download one completed capture as a tar.gz (names come from
        the capture listing — an unlisted name is a 404, so a crafted
        path segment cannot escape the capture dir)."""
        import io as _io
        import tarfile as _tarfile

        denied = _debug_gate_404()
        if denied is not None:
            return denied
        name = request.match_info["name"]
        path = profiler.capture_path(name)
        if path is None:
            return web.Response(status=404, text="no such capture")
        loop = asyncio.get_running_loop()

        def _pack() -> bytes:
            buf = _io.BytesIO()
            with _tarfile.open(fileobj=buf, mode="w:gz") as tar:
                tar.add(path, arcname=name)
            return buf.getvalue()

        blob = await loop.run_in_executor(None, _pack)
        return web.Response(
            body=blob,
            headers={
                "Content-Type": "application/gzip",
                "Content-Disposition": (
                    f'attachment; filename="{name}.tar.gz"'
                ),
            },
        )

    async def debug_brownout(_request: web.Request) -> web.Response:
        """Brownout engine state: level, pressure components, thresholds,
        refresh-queue occupancy (runtime/brownout.py snapshot;
        docs/degradation.md)."""
        import json as _json

        denied = _debug_gate_404()
        if denied is not None:
            return denied
        return web.Response(
            text=_json.dumps(brownout.snapshot()),
            content_type="application/json",
        )

    async def debug_device(_request: web.Request) -> web.Response:
        """Backend supervisor state: breaker/storm bookkeeping, probe
        history, failover counts (runtime/devicesupervisor.py snapshot;
        docs/resilience.md "Backend failover")."""
        import json as _json

        denied = _debug_gate_404()
        if denied is not None:
            return denied
        return web.Response(
            text=_json.dumps(supervisor.snapshot()),
            content_type="application/json",
        )

    async def debug_fleet(_request: web.Request) -> web.Response:
        """Elastic membership state (runtime/membership.py snapshot +
        warm-start stats; docs/fleet.md "Membership and elasticity"):
        self status, the applied live set, every readable marker with
        its expiry verdict, heartbeat failures, and the warm-start
        seed/publish accounting."""
        import json as _json

        denied = _debug_gate_404()
        if denied is not None:
            return denied
        doc = membership.snapshot()
        doc["warmstart"] = warmstart.snapshot()
        return web.Response(
            text=_json.dumps(doc), content_type="application/json"
        )

    async def debug_tier(_request: web.Request) -> web.Response:
        """Shared-tier outage supervisor state (runtime/tiersupervisor.py
        snapshot; docs/resilience.md "Island mode"): attached/island
        state, storm counters, probe/flap bookkeeping, journal depth and
        drop/replay accounting, and the scrubber's purge counts."""
        import json as _json

        denied = _debug_gate_404()
        if denied is not None:
            return denied
        return web.Response(
            text=_json.dumps(tier_supervisor.snapshot()),
            content_type="application/json",
        )

    async def debug_memory(_request: web.Request) -> web.Response:
        """Memory governor state (runtime/memgovernor.py snapshots;
        docs/resilience.md "Memory governor"): device-side prediction
        model + active capacity ceilings, the host byte accountant's
        inflight charge, and the RSS watchdog sample — the document an
        operator checks when launches pre-split or decodes shed."""
        import json as _json

        denied = _debug_gate_404()
        if denied is not None:
            return denied
        doc = {
            "governor": governor.snapshot(),
            "host": mem_accountant.snapshot(),
            "rss": rss_watchdog.snapshot(),
        }
        return web.Response(
            text=_json.dumps(doc), content_type="application/json"
        )

    async def debug_fleet_status(_request: web.Request) -> web.Response:
        """One JSON snapshot of the whole fleet (docs/fleet.md "Fleet
        observatory & autoscaling signal"): every live signal digest,
        the assembled rollup, the current autoscale recommendation,
        joined with membership (markers + live set) and routing health
        (device-down peers) — the document an external scaler polls."""
        import json as _json

        denied = _debug_gate_404()
        if denied is not None:
            return denied
        doc = {
            "observatory": observatory.snapshot(),
            "membership": membership.snapshot(),
            "routing": fleet.peer_health(),
        }
        return web.Response(
            text=_json.dumps(doc), content_type="application/json"
        )

    async def debug_fleet_replicas(request: web.Request) -> web.Response:
        """Dynamic replica-set reload (docs/fleet.md "Dynamic replica
        sets"): swap the rendezvous routing set online. Body:
        ``{"replicas": [...], "replica_id": "..."}`` (replica_id
        optional). Routing stays consistent mid-flight: owner resolution
        reads the set as one reference, so in-flight proxied requests
        complete against the owner they already resolved. REJECTED
        while elastic membership is active — a manual swap would fight
        the watcher's next beat (split-brain; docs/fleet.md)."""
        import json as _json

        denied = _debug_gate_404()
        if denied is not None:
            return denied
        if membership.active:
            import logging as _logging

            _logging.getLogger("flyimg.fleet").warning(
                "manual replica-set reload rejected: elastic "
                "membership owns the replica set",
                extra={"event": "fleet.manual_reload_rejected",
                       "source": "debug_endpoint"},
            )
            return web.Response(
                status=400,
                text="replica set is managed by fleet membership "
                     "(fleet_membership_enable is on); a manual swap "
                     "would be overwritten by the watcher's next beat "
                     "— stop the replica or disable membership instead",
            )
        try:
            body = await request.json()
        except Exception:
            return web.Response(
                status=400, text="body must be JSON"
            )
        replicas = body.get("replicas") if isinstance(body, dict) else None
        if not isinstance(replicas, list) or not all(
            isinstance(r, str) for r in replicas
        ):
            return web.Response(
                status=400,
                text='body must be {"replicas": ["http://...", ...], '
                     '"replica_id": "..."} (replica_id optional)',
            )
        self_id = body.get("replica_id")
        if self_id is not None and not isinstance(self_id, str):
            return web.Response(status=400, text="replica_id must be a string")
        applied = fleet.update_replicas(replicas, self_id=self_id)
        import logging as _logging

        _logging.getLogger("flyimg.fleet").info(
            "replica set reloaded via /debug/fleet/replicas",
            extra={"event": "fleet.replicas_reloaded", **applied},
        )
        return web.Response(
            text=_json.dumps(applied), content_type="application/json"
        )

    async def debug_traces_get(request: web.Request) -> web.Response:
        """Full span tree of one kept trace as JSON."""
        import json as _json

        denied = _debug_gate()
        if denied is not None:
            return denied
        trace = tracer.get(request.match_info["trace_id"])
        if trace is None:
            return web.Response(
                status=404,
                text="no such trace (dropped by the tail sampler, evicted "
                     "from the ring, or never seen)",
            )
        return web.Response(
            text=_json.dumps(trace.as_dict()),
            content_type="application/json",
        )

    app.router.add_get("/", index)
    app.router.add_get("/metrics", metrics_route)
    app.router.add_get("/healthz", healthz)
    app.router.add_get("/readyz", readyz)
    app.router.add_get("/debug/trace", debug_trace)
    app.router.add_get("/debug/traces", debug_traces_list)
    app.router.add_get("/debug/traces/{trace_id}", debug_traces_get)
    app.router.add_get("/debug/slo", debug_slo)
    app.router.add_get("/debug/perf", debug_perf)
    app.router.add_get("/debug/plans", debug_plans)
    app.router.add_get("/debug/flightrecorder", debug_flightrecorder)
    app.router.add_get("/debug/telemetry", debug_telemetry)
    app.router.add_get("/debug/profile", debug_profile_get)
    app.router.add_post("/debug/profile", debug_profile_arm)
    app.router.add_get(
        "/debug/profile/captures/{name}", debug_profile_download
    )
    app.router.add_get("/debug/brownout", debug_brownout)
    app.router.add_get("/debug/device", debug_device)
    app.router.add_get("/debug/tier", debug_tier)
    app.router.add_get("/debug/memory", debug_memory)
    app.router.add_get("/debug/fleet", debug_fleet)
    app.router.add_get("/debug/fleet/status", debug_fleet_status)
    app.router.add_post("/debug/fleet/replicas", debug_fleet_replicas)
    # Route table is config-overridable like the reference's
    # config/routes.yml (RoutesResolver.php); imageSrc uses a catch-all
    # pattern so full URLs (with slashes) work as path parameters — the
    # reference's `imageSrc: .+` route requirement (config/routes.yml:9,14).
    # Misconfiguration fails HERE, at startup, not per-request.
    handlers = {"upload": upload, "path": path}
    routes = dict(DEFAULT_ROUTES)
    overrides = params.by_key("routes", {}) or {}
    unknown = set(overrides) - set(handlers)
    if unknown:
        raise InvalidArgumentException(
            f"unknown route names in `routes` config: {sorted(unknown)} "
            f"(known: {sorted(handlers)})"
        )
    routes.update(overrides)
    for name, pattern in routes.items():
        if "{options}" not in pattern or "{imageSrc" not in pattern:
            raise InvalidArgumentException(
                f"route pattern for {name!r} must contain {{options}} and "
                f"{{imageSrc:.+}} placeholders, got {pattern!r}"
            )
        # named: the observability middleware keys tracing and the route
        # metric label on the LOGICAL name, so pattern overrides keep
        # stable labels and stay traced
        app.router.add_get(pattern, handlers[name], name=name)
    return app


def _error_response(exc: AppException) -> web.Response:
    status = 500
    for cls, code in _ERROR_STATUS.items():
        if isinstance(exc, cls):
            status = code
            break
    headers = {}
    if status == 503:
        # shed responses advise the client when to come back (admission
        # control / open breaker set retry_after_s; 1s is the floor)
        headers["Retry-After"] = str(
            max(1, int(getattr(exc, "retry_after_s", 1) or 1))
        )
    return web.Response(
        status=status, text=f"{type(exc).__name__}: {exc}", headers=headers
    )


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="flyimg-tpu")
    sub = parser.add_subparsers(dest="cmd")
    enc = sub.add_parser("encrypt", help="mint a signed URL token")
    enc.add_argument("payload", help="'{options}/{imageSrc}' to encrypt")
    enc.add_argument("--params", default=None)
    srv = sub.add_parser("serve", help="run the HTTP service")
    srv.add_argument("--host", default="0.0.0.0")
    srv.add_argument("--port", type=int, default=8080)
    srv.add_argument("--params", default=None)
    prn = sub.add_parser(
        "prune",
        help="evict least-recently-modified cached outputs to a size budget",
    )
    prn.add_argument("--max-bytes", type=int, required=True)
    prn.add_argument("--params", default=None)
    args = parser.parse_args(argv)

    params = (
        AppParameters.from_yaml(args.params)
        if getattr(args, "params", None)
        else AppParameters()
    )
    if args.cmd == "encrypt":
        from flyimg_tpu.service.security import SecurityHandler

        print(SecurityHandler(params).encrypt(args.payload))
        return 0
    if args.cmd == "prune":
        import json as _json

        storage = make_storage(params)
        if not hasattr(storage, "prune"):
            print(
                f"{type(storage).__name__} does not support prune "
                "(use a bucket lifecycle policy for S3)",
                file=sys.stderr,
            )
            return 1
        print(_json.dumps(storage.prune(args.max_bytes)))
        return 0
    if args.cmd == "serve":
        from flyimg_tpu.parallel.dist import initialize_multihost
        from flyimg_tpu.runtime.logging import configure_logging

        # structured JSON logs (log_format/log_level knobs) before any
        # subsystem logs a line; access lines join them per request
        configure_logging(params)
        # multi-host pods: wire the DCN coordination plane before any mesh
        # is built so jax.devices() is the global view (no-op single host)
        initialize_multihost()
        app = make_app(params)
        if getattr(args, "params", None):
            # dynamic replica-set reload on SIGHUP (docs/fleet.md): where
            # the supervisor can deliver it, re-read the params file and
            # swap fleet_replicas/fleet_replica_id without a restart —
            # the same code path as POST /debug/fleet/replicas. Guarded:
            # platforms without SIGHUP (or embedded loops that own
            # signal handling) just keep the static boot set.
            import logging as _logging
            import signal as _signal

            def _reload_replicas(_signum=None, _frame=None):
                log = _logging.getLogger("flyimg.fleet")
                if app[MEMBERSHIP_KEY].active:
                    # split-brain guard (docs/fleet.md "Membership and
                    # elasticity"): while the watcher owns the replica
                    # set a SIGHUP swap would fight its next beat
                    log.warning(
                        "SIGHUP replica reload rejected: elastic "
                        "membership owns the replica set",
                        extra={"event": "fleet.manual_reload_rejected",
                               "source": "sighup"},
                    )
                    return
                try:
                    fresh = AppParameters.from_yaml(args.params)
                    applied = app[FLEET_KEY].update_replicas(
                        list(fresh.by_key("fleet_replicas", []) or []),
                        self_id=(
                            str(fresh.by_key("fleet_replica_id", "") or "")
                            or None
                        ),
                    )
                    log.info(
                        "replica set reloaded on SIGHUP",
                        extra={
                            "event": "fleet.replicas_reloaded", **applied
                        },
                    )
                except Exception as exc:
                    log.warning("SIGHUP replica reload failed: %s", exc)

            try:
                _signal.signal(_signal.SIGHUP, _reload_replicas)
            except (AttributeError, ValueError, OSError):
                pass
        web.run_app(app, host=args.host, port=args.port)
        return 0
    parser.print_help()
    return 1


if __name__ == "__main__":
    sys.exit(main())
