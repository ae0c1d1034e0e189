"""ImageHandler: the orchestration choke point.

Port of the reference's pipeline driver (src/Core/Handler/ImageHandler.php):
security checks -> options parse -> source fetch/ingest -> output naming +
cache check -> transform -> post-passes (smart-crop, face blur, face crop,
same order and GIF exclusions as ImageHandler.php:160-181,125-152) ->
store -> serve bytes.

The transform itself is the TPU pipeline: decode (with DCT prescale hint)
-> device program (ops/compose.py) -> host encode. Animated GIF outputs
run the device program per frame and re-assemble, replacing the reference's
`-coalesce` whole-animation convert (ImageProcessor.php:74-76).
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import Future
from concurrent.futures import TimeoutError as FutureTimeout
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from flyimg_tpu.appconfig import AppParameters
from flyimg_tpu.codecs import decode, encode, media_info, narrow_to_u8
from flyimg_tpu.codecs.native_codec import PngCall
from flyimg_tpu.codecs.sniff import sniff
from flyimg_tpu.exceptions import (
    DeadlineExceededException,
    PayloadTooLargeException,
    ServiceUnavailableException,
)
from flyimg_tpu.ops.compose import run_plan
from flyimg_tpu.runtime import tracing
from flyimg_tpu.runtime.metrics import GcWatch
from flyimg_tpu.runtime.resilience import Deadline
from flyimg_tpu.runtime.variantindex import VariantFacts, VariantIndex
from flyimg_tpu.service.input_source import FetchPolicy, load_source
from flyimg_tpu.service.output_image import (
    EXT_TO_MIME,
    OutputSpec,
    resolve_output,
)
from flyimg_tpu.service.security import SecurityHandler
from flyimg_tpu.spec.options import OptionsBag
from flyimg_tpu.spec.plan import (
    TransformPlan,
    build_plan,
    decode_roi_window,
    decode_target_hint,
    degrade_plan,
    lossy_output,
    parse_colorspace,
    reuse_frame_key,
    rewrite_for_reuse,
)
from flyimg_tpu.storage.base import Storage
from flyimg_tpu.testing import faults


class _SingleFlight:
    """Coalesce concurrent cache-misses for the same output name.

    The reference has a documented race here: N concurrent misses for one
    key each run the full pipeline and last-write-wins into storage
    (ImageHandler.php:103-111, see SURVEY.md section 5). Instead, the first
    thread in becomes the leader and computes; followers block on its
    future and reuse the bytes — one device pipeline per key, ever.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._inflight: Dict[str, Future] = {}

    def begin(self, key: str) -> Tuple[bool, Future]:
        """-> (is_leader, future). Leaders MUST call done() exactly once."""
        with self._lock:
            fut = self._inflight.get(key)
            if fut is not None:
                return False, fut
            fut = Future()
            self._inflight[key] = fut
            return True, fut

    def done(self, key: str, result=None, exc: Optional[BaseException] = None):
        """Settle and clear the leader's future. Idempotent: a second
        call for an already-settled key is a no-op — a leader error path
        that double-calls done() must surface ITS exception, not a bare
        KeyError from the pop (pinned by tests/test_reuse.py)."""
        with self._lock:
            fut = self._inflight.pop(key, None)
        if fut is None:
            return
        if exc is not None:
            fut.set_exception(exc)
        else:
            fut.set_result(result)


@dataclass
class ProcessedImage:
    """What a request resolves to (the reference's OutputImage after
    attachOutputContent)."""

    content: bytes
    spec: OutputSpec
    options: OptionsBag
    from_cache: bool = False
    timings: Dict[str, float] = field(default_factory=dict)
    # stored artifact's mtime (reference Last-Modified source,
    # Response.php:72-78); None -> response falls back to now()
    modified_at: Optional[float] = None
    # brownout markers (runtime/brownout.py; docs/degradation.md): the
    # degradation modes applied to this render ("refine"/"smartcrop"/
    # "quality"), and whether the bytes are a stale-while-revalidate
    # serve of an expired cache entry. Both drive response headers
    # (X-Flyimg-Degraded, Warning: 110) and stay empty/False — no new
    # headers — whenever the brownout engine is off or NORMAL.
    degraded: Tuple[str, ...] = ()
    stale: bool = False
    # derivative reuse (docs/caching.md): the cached ancestor rendition
    # this render was re-derived from, or None for a from-source render.
    # Drives the debug-gated X-Flyimg-Reuse header; always None with
    # reuse_enable off.
    reused_from: Optional[str] = None


class ImageHandler:
    # inputs at least this tall consider the spatially-tiled resample
    TILE_MIN_ROWS = 2048
    # default ceiling on any single wait for a batched device result
    # (config-overridable via device_result_timeout_s); a wedged executor
    # then degrades to the single-image CPU path instead of sticking the
    # worker thread
    DEVICE_RESULT_TIMEOUT_S = 120.0

    def __init__(
        self,
        storage: Storage,
        params: AppParameters,
        *,
        batcher=None,
        codec_batcher=None,
        face_backend=None,
        smartcrop_backend=None,
        metrics=None,
        sp_mesh=None,
        brownout=None,
        host_pipeline=None,
        device_supervisor=None,
        telemetry=None,
        mem_accountant=None,
    ) -> None:
        self.storage = storage
        self.params = params
        # telemetry warehouse (runtime/telemetry.py): per-request mix
        # feature recording at the outcome points below. None when
        # telemetry_enable is off — every call site is one `is None`
        # check, keeping the off path byte-identical.
        self.telemetry = telemetry
        self.security = SecurityHandler(params)
        self.batcher = batcher  # BatchController; None = direct device calls
        # separate controller (own executor thread) for HOST codec work:
        # concurrent JPEG misses decode as one native-pool batch without
        # serializing against device launches
        self.codec_batcher = codec_batcher
        self.metrics = metrics  # runtime.metrics.MetricsRegistry or None
        # the process's garbage collections into that registry, until
        # close()
        self._gc_watch = GcWatch(metrics) if metrics is not None else None
        # multi-device mesh with an 'sp' axis: very large inputs shard
        # H-wise with ppermute halo exchange (parallel/tiling.py — the
        # image-domain analog of context parallelism, SURVEY.md section 5)
        self.sp_mesh = sp_mesh
        self._face_backend = face_backend
        self._smartcrop_backend = smartcrop_backend
        # a deployment that NAMES the convnet detector loads its
        # checkpoint now, not inside its first face request
        if face_backend is None and str(
            params.by_key("face_backend", "auto")
        ).lower() == "blazeface":
            self._faces()
        self._singleflight = _SingleFlight()
        # resilience wiring (runtime/resilience.py): fetch retry/breaker
        # policy, per-request deadline default, wedged-executor behavior
        self.fetch_policy = FetchPolicy.from_params(params, metrics=metrics)
        self.default_deadline_s = float(
            params.by_key("request_deadline_s", 0.0) or 0.0
        )
        self.device_result_timeout_s = float(
            params.by_key(
                "device_result_timeout_s", self.DEVICE_RESULT_TIMEOUT_S
            )
        )
        # a wedged device executor degrades to the single-image direct
        # path (CPU-visible jit) instead of failing the request outright
        self.wedged_fallback = bool(
            params.by_key("wedged_executor_fallback", True)
        )
        # brownout engine (runtime/brownout.py): per-level degradation —
        # stale-while-revalidate, plan rewriting, miss shedding. None or
        # disabled = today's behavior exactly (docs/degradation.md).
        self.brownout = brownout
        # backend supervisor (runtime/devicesupervisor.py): while it
        # reports CPU failover, miss renders tag X-Flyimg-Degraded:
        # cpu-fallback and are served direct — never cached at the
        # device-quality keys, which would mask re-promotion. None or
        # disabled = zero checks, byte-identical serving.
        self.device_supervisor = device_supervisor
        # derivative-reuse rendering (docs/caching.md; ROADMAP item 2):
        # the per-source variant index + the cache-aware rewriter knobs.
        # Everything is inert with reuse_enable off — no lookups, no
        # recording, no manifests, byte-identical serving (pinned by
        # tests/test_reuse.py).
        self.reuse_enable = bool(params.by_key("reuse_enable", False))
        self.reuse_min_scale = float(params.by_key("reuse_min_scale", 2.0))
        self.reuse_max_generations = int(
            params.by_key("reuse_max_generations", 1)
        )
        # DEGRADED+ widening (the brownout compounding docs/degradation.md
        # describes): under pressure a nearer ancestor and one extra lossy
        # generation beat a full origin-fetch + decode + render
        self.reuse_degraded_min_scale = float(
            params.by_key("reuse_degraded_min_scale", 1.3)
        )
        # the index lives on the SHARED storage tier (docs/fleet.md):
        # with the L2 on, manifests written by any replica are read by
        # every replica's cold lookup — cross-replica derivative reuse.
        # Single-tier storage is its own shared tier (same behavior as
        # before the fleet tier existed). Storage-less callers (the bulk
        # runner) get a memory-only index, as before.
        self.variants = VariantIndex.from_params(
            params, storage=storage.shared if storage is not None else None
        )
        # ROI JPEG decode (docs/host-pipeline.md): crop/extract-dominant
        # plans decode only the source window they consume (decode_roi
        # knob; explicit off = byte-identical full decodes, pinned by
        # tests/test_roi_decode.py)
        self.decode_roi = bool(params.by_key("decode_roi", False))
        # pipelined stage DAG (runtime/hostpipeline.py): bounded
        # per-stage pools for fetch/decode/encode host work. None or
        # disabled = today's inline stages exactly.
        self.host_pipeline = host_pipeline
        # cross-replica single-flight (storage/tiered.py L2Lease;
        # docs/fleet.md): on a both-tier miss the first replica leases
        # the key in the shared L2 and renders; the rest poll for its
        # artifact instead of duplicating the pipeline. None (the
        # default — l2_enable off) keeps the miss path exactly today's.
        self.fleet_replica_id = str(
            params.by_key("fleet_replica_id", "") or ""
        )
        self.l2lease = None
        if storage is not None and bool(
            params.by_key("l2_enable", False)
        ) and bool(params.by_key("l2_lease_enable", True)):
            from flyimg_tpu.storage.tiered import L2Lease

            self.l2lease = L2Lease.from_params(
                params, storage=storage.shared
            )
        # host byte accountant (runtime/memgovernor.py): decode work
        # charges its header-sniffed footprint (w*h*3) before the full
        # decode and releases after. None (mem_host_budget_bytes 0, the
        # default) = no charge calls, byte-identical miss path.
        self.mem_accountant = mem_accountant
        # header-sniff pixel bound: over it, the miss rejects as 413
        # BEFORE decode allocates anything (0 = unbounded; PIL's
        # decompression-bomb guard still applies either way)
        self.max_source_pixels = int(
            params.by_key("mem_max_source_pixels", 0) or 0
        )

    def close(self) -> None:
        """Stop recording the process's garbage collections into this
        handler's registry. The controllers it was given are their
        owner's to close."""
        if self._gc_watch is not None:
            self._gc_watch.close()
            self._gc_watch = None

    def _stage(self, name: str, fn, deadline: Optional[Deadline],
               *, inline_fallback: bool = True):
        """Run one host stage through its pipeline pool when the stage
        DAG is on; inline otherwise. A stage-pool TIMEOUT (wedged or
        saturated workers) degrades to running the work inline in this
        request thread (``inline_fallback``, counted as a wedge like the
        batcher fallbacks) or sheds as a typed 503; a stage-pool SHED
        (admission bound) propagates as the 503 the pool raised. Any
        exception from ``fn`` itself surfaces unchanged either way."""
        pipeline = self.host_pipeline
        if pipeline is None or not getattr(pipeline, "enabled", False):
            return fn()
        try:
            return pipeline.run(
                name, fn, timeout=self._device_wait_s(deadline),
            )
        except (FutureTimeout, TimeoutError):
            # FutureTimeout: our bounded wait expired on a busy stage.
            # Builtin TimeoutError: the pool itself failed the task — a
            # wedged worker abandoned by self-healing, or a shutdown
            # drain stranding it (distinct classes before Python 3.11).
            # Both degrade the same way.
            if deadline is not None:
                deadline.check(name)
            self._record_wedge()
            if inline_fallback:
                return fn()
            raise ServiceUnavailableException(
                f"host {name} stage did not produce a result in time"
            ) from None

    # lazily import model backends so the service can run without them
    def _smartcrop(self):
        if self._smartcrop_backend is None:
            from flyimg_tpu.models import smartcrop

            self._smartcrop_backend = smartcrop
        return self._smartcrop_backend

    def _faces(self):
        if self._face_backend is None:
            from flyimg_tpu.models.faces import make_face_backend

            # honor the handler's OWN config first (a caller that set
            # face_backend in params but not the kwarg must get what it
            # configured); the default is the registry's auto chain
            # (haar -> blazeface -> no-op), NOT the skin proposer —
            # reference fallback semantics are "face options no-op when no
            # real detector exists" (FaceDetectProcessor.php:24)
            self._face_backend = make_face_backend(
                str(self.params.by_key("face_backend", "auto")),
                self.params.by_key("face_checkpoint"),
            )
        return self._face_backend

    def _record_mix(self, options, image_src: str,
                    source_key, outcome: str) -> None:
        """One traffic-mix observation into the telemetry classifier
        (runtime/telemetry.py). Rides every outcome point INCLUDING
        cache hits, so the body is one None check + one deque append;
        with telemetry off the call site is a single `is None` check.
        Computes its own source hash when reuse is off (source_key is
        only populated on the reuse path)."""
        if self.telemetry is None:
            return
        key = source_key or OptionsBag.hash_original_image_url(image_src)
        self.telemetry.record_request(
            options=options, source_key=key, outcome=outcome
        )

    def process_image(
        self,
        options_str: str,
        image_src: str,
        *,
        accepts_webp: bool = False,
        deadline: Optional[Deadline] = None,
    ) -> ProcessedImage:
        """The single choke point every image request goes through
        (reference ImageHandler::processImage, ImageHandler.php:92-118).

        ``deadline`` is the request's latency budget, minted at HTTP
        ingress; library callers that pass none get the configured default
        (``request_deadline_s``; 0 = unbounded)."""
        timings: Dict[str, float] = {}
        t0 = time.perf_counter()
        if deadline is None:
            deadline = Deadline(self.default_deadline_s, metrics=self.metrics)

        options_str, image_src = self.security.check_security_hash(
            options_str, image_src
        )
        self.security.check_restricted_domains(image_src)

        options = OptionsBag(
            options_str,
            options_keys=self.params.by_key("options_keys"),
            default_options=self.params.by_key("default_options"),
            separator=self.params.by_key("options_separator", ","),
        )

        # derivative reuse (docs/caching.md): when the rewriter is on and
        # the variant index already knows this source (mime + cached
        # renditions), output naming, the cache check, and a reuse-safe
        # render all proceed WITHOUT touching the origin — the fetch
        # happens lazily, inside the leader, only when no safe ancestor
        # exists. With reuse off this block is two cheap bool checks and
        # the path below is exactly today's.
        refresh = options.wants_refresh()
        source_key = (
            OptionsBag.hash_original_image_url(image_src)
            if self.reuse_enable else None
        )
        reuse_on = self.reuse_enable and not refresh
        reuse_entry = None
        source = None
        spec = None
        if reuse_on and source_key is not None:
            reuse_entry = self.variants.lookup(source_key)
            if reuse_entry is not None and reuse_entry.source_mime:
                spec = resolve_output(
                    options, image_src, reuse_entry.source_mime,
                    accepts_webp=accepts_webp,
                )
        if spec is None:
            source = self._load_source(image_src, options, timings, deadline)
            spec = resolve_output(
                options, image_src, source.info.mime,
                accepts_webp=accepts_webp,
            )

        if refresh:
            self.storage.delete(spec.name)  # idempotent when absent
            if source_key is not None:
                # the rebuilt output invalidates its index entry; the
                # re-render below records fresh facts
                self.variants.discard(source_key, spec.name)

        # ONE round trip answers cached? + bytes + stored-when? (separate
        # has/read/head calls would tax S3 serving's hot path 2-3x).
        # fetch_hedged == fetch when storage_hedge_delay_ms is 0; with it
        # set, a stalled primary read races one backup read so the
        # cache-hit tail is bounded by the hedge delay, not the stall.
        with tracing.span("storage", op="fetch"):
            cached = None if refresh else self.storage.fetch_hedged(spec.name)
        if cached is not None and not _cache_entry_valid(cached[0], spec):
            # corrupt/truncated entry (torn write, disk damage, bucket
            # tampering): treat it as a miss — delete and re-render —
            # instead of serving garbage bytes under image headers
            tracing.add_event(
                "cache.corrupt", key=spec.name, bytes=len(cached[0])
            )
            if self.metrics is not None:
                self.metrics.record_cache_corrupt()
            try:
                self.storage.delete(spec.name)
            except Exception:
                pass  # best effort; the re-render overwrites it anyway
            if source_key is not None:
                self.variants.discard(source_key, spec.name)
            cached = None
        if cached is not None:
            content, stat = cached
            tracing.add_event("cache.hit", key=spec.name)
            # stale-while-revalidate (runtime/brownout.py; DEGRADED+):
            # an entry past its freshness TTL serves IMMEDIATELY with
            # stale markers while ONE coalesced background refresh
            # re-renders it — under pressure a slightly-old image beats
            # a device-pipeline wait or a 503
            stale = False
            engine = self.brownout
            if (
                engine is not None
                and engine.swr_active()
                and stat.mtime is not None
                and engine.stale_ttl_s > 0
                and time.time() - stat.mtime > engine.stale_ttl_s
            ):
                stale = True
                engine.record_degraded("stale")
                tracing.add_event(
                    "brownout.stale_hit", key=spec.name,
                    age_s=round(time.time() - stat.mtime, 1),
                )
                if not engine.shed_active():
                    # at SHED even refreshes stop: the queue bound
                    # protects the device, but a shedding tier should
                    # spend zero miss-pipeline work it can avoid (on the
                    # reuse fast path the source was never fetched — the
                    # background refresh fetches it itself)
                    self._schedule_refresh(
                        spec, options,
                        source.data if source is not None else None,
                        image_src,
                        source_mime=(
                            source.info.mime if source is not None
                            else reuse_entry.source_mime
                            if reuse_entry is not None else ""
                        ),
                    )
            if self.metrics is not None:
                self.metrics.record_cache(hit=True)
                self.metrics.record_stage("cache_hit", time.perf_counter() - t0)
            self._record_mix(
                options, image_src, source_key,
                "stale" if stale else "hit",
            )
            return ProcessedImage(
                content=content,
                spec=spec,
                options=options,
                from_cache=True,
                timings=timings,
                modified_at=stat.mtime,
                stale=stale,
            )

        # SHED level (runtime/brownout.py): cache misses reject before
        # any decode/device work — hits and stale hits above still serve
        engine = self.brownout
        if engine is not None and engine.shed_active():
            engine.record_degraded("shed")
            tracing.add_event("brownout.shed", key=spec.name)
            self._record_mix(options, image_src, source_key, "shed")
            exc = ServiceUnavailableException(
                "shedding cache-miss work under overload (brownout level "
                "shed); cached outputs still serve"
            )
            exc.retry_after_s = max(1, int(engine.shed_retry_after_s))
            raise exc

        leader, flight = self._singleflight.begin(spec.name)
        if not leader:
            # another request is already computing these exact bytes;
            # wait for it instead of running a duplicate device pipeline —
            # but never forever: a wedged leader must shed followers as
            # 503s, not strand every coalesced request
            try:
                # generous multiple of the per-device-call budget: a slow
                # but healthy leader (multi-frame GIF, several post-pass
                # waits) must NOT shed its followers — only a wedged one.
                # The follower's own deadline caps the wait regardless.
                with tracing.span("coalesced_wait", key=spec.name):
                    content, modified_at, degraded = flight.result(
                        timeout=deadline.timeout(
                            5 * self.device_result_timeout_s
                        )
                    )
            except FutureTimeout:
                deadline.check("coalesced")  # budget gone -> 504, not 503
                raise ServiceUnavailableException(
                    "timed out waiting for the in-flight pipeline computing "
                    "this output"
                ) from None
            timings["coalesced"] = time.perf_counter() - t0
            timings["total"] = timings["coalesced"]
            if self.metrics is not None:
                # served without running a pipeline: a hit for traffic
                # accounting, plus the dedicated coalesce counter
                self.metrics.record_cache(hit=True)
                self.metrics.record_stage("coalesced", timings["coalesced"])
                self.metrics.counter(
                    "flyimg_requests_coalesced_total",
                    "Cache-miss requests served by an in-flight duplicate",
                ).inc()
            self._record_mix(options, image_src, source_key, "coalesced")
            return ProcessedImage(
                content=content, spec=spec, options=options, timings=timings,
                modified_at=modified_at, degraded=degraded,
            )

        lease_token: Optional[str] = None
        try:
            # cross-replica single-flight (docs/fleet.md): on a both-tier
            # miss, lease the key in the shared L2 — the fleet leader
            # renders below; a follower serves the leader's artifact here
            # (no fetch, no decode, no device work) and settles its own
            # local coalesced waiters with the same bytes. rf_1 refreshes
            # skip the wait (they must re-render) but still write through,
            # so the fleet converges on the refreshed bytes.
            if self.l2lease is not None and not refresh:
                verdict = self._l2_coalesce(spec, deadline)
                if verdict[0] == "serve":
                    _, remote_content, remote_mtime = verdict
                    self._singleflight.done(
                        spec.name, result=(remote_content, remote_mtime, ())
                    )
                    timings["l2_coalesced"] = time.perf_counter() - t0
                    timings["total"] = timings["l2_coalesced"]
                    if self.metrics is not None:
                        # served without running a pipeline, like the
                        # process-local coalesced path above
                        self.metrics.record_cache(hit=True)
                        self.metrics.record_stage(
                            "l2_coalesced", timings["l2_coalesced"]
                        )
                    self._record_mix(
                        options, image_src, source_key, "coalesced"
                    )
                    return ProcessedImage(
                        content=remote_content, spec=spec, options=options,
                        from_cache=True, timings=timings,
                        modified_at=remote_mtime,
                    )
                lease_token = verdict[1]
            # BROWNOUT+ plan degradation: finishing ops dropped, device
            # smart-crop swapped for the host entropy crop, encode
            # quality clamped (docs/degradation.md). modes stays empty
            # whenever the engine is off or below BROWNOUT.
            modes: List[str] = []
            degrade = (
                engine
                if engine is not None and engine.plan_degrade_active()
                else None
            )
            # cache-aware reuse rewriting (docs/caching.md): re-derive
            # from a cached ancestor rendition when one is reuse-safe —
            # skipping the origin fetch and the full-size decode. Every
            # unsafe combination falls through to the normal pipeline.
            content = None
            reused = None
            reuse_generation = 0
            render_info: Dict[str, object] = {}
            if reuse_on and not spec.is_gif:
                if reuse_entry is None:
                    self._record_reuse("miss")
                else:
                    hit = self._try_reuse(
                        reuse_entry, options, spec, timings,
                        deadline=deadline, degrade=degrade,
                        degraded_out=modes, render_info=render_info,
                    )
                    if hit is not None:
                        content, reused, reuse_generation = hit
            if content is None:
                if source is None:
                    # reuse fast path found no safe ancestor: pay the
                    # origin fetch now (followers coalesced above never
                    # fetch at all)
                    source = self._load_source(
                        image_src, options, timings, deadline
                    )
                render_info = {}
                content = self._process_new(
                    source.data, options, spec, timings, deadline=deadline,
                    degrade=degrade, degraded_out=modes,
                    render_info=render_info,
                )
            # cache-write-time recheck (not just the render-start one in
            # _process_new): a breaker that trips MID-render re-homes
            # this request's queued batch onto the rebuilt CPU executor,
            # and caching those bytes at the device-quality key is
            # exactly the re-promotion masking the supervisor forbids.
            # The false positive (a device render finishing just as the
            # breaker trips) costs one uncached render — the safe side.
            if self._device_down() and "cpu-fallback" not in modes:
                modes.append("cpu-fallback")
            if modes:
                # degraded renders are served direct, never cached: the
                # cache must only ever hold full-quality bytes, or a
                # brownout would poison it for a year of CDN max-age
                modified_at = None
                for mode in modes:
                    # engine is None for brownout-less handlers whose
                    # only degradation source is the CPU failover tag
                    if engine is not None:
                        engine.record_degraded(mode)
                tracing.add_event(
                    "brownout.degraded_render", key=spec.name,
                    modes=",".join(modes),
                )
            else:
                # write() returns the stored mtime so neither the leader
                # nor its followers re-query metadata for bytes written
                # just now
                with tracing.span("storage", op="write", bytes=len(content)):
                    modified_at = self.storage.write(spec.name, content)
                if source_key is not None:
                    self._record_variant(
                        source_key,
                        (
                            source.info.mime if source is not None
                            else reuse_entry.source_mime
                        ),
                        spec, options, render_info,
                        generations=reuse_generation,
                        ancestor=reused,
                    )
        except BaseException as exc:
            if lease_token is not None:
                # release BEFORE settling local waiters: polling replicas
                # steal a freed lease immediately instead of waiting out
                # the TTL behind a leader that just failed
                self.l2lease.release(spec.name, lease_token)
            self._singleflight.done(spec.name, exc=exc)
            raise
        if lease_token is not None:
            # the artifact write (when one happened) preceded this, so a
            # follower that sees the freed lease finds the bytes; after a
            # degraded (never-cached) render it finds nothing and renders
            # itself — correct, just not coalesced
            self.l2lease.release(spec.name, lease_token)
        self._singleflight.done(
            spec.name, result=(content, modified_at, tuple(modes))
        )
        timings["total"] = time.perf_counter() - t0
        if reused is not None:
            # the reuse-hit serve gets its own stage series (and a
            # perf-gate column, tools/perf_gate.py schema 4) so later
            # PRs can't silently regress the reuse path
            timings["reuse_hit"] = timings["total"]
        if self.metrics is not None:
            self.metrics.record_cache(hit=False)
            # the pipeline's stages (fetch, decode, device, encode, ...)
            # recorded themselves where they ran (tracing.stage), on this
            # path and under transform_bytes alike; only the request's
            # own totals are recorded here
            self.metrics.record_stage("total", timings["total"])
            if reused is not None:
                self.metrics.record_stage("reuse_hit", timings["reuse_hit"])
        self._record_mix(
            options, image_src, source_key,
            "degraded" if modes
            else "reuse" if reused is not None else "miss",
        )
        return ProcessedImage(
            content=content, spec=spec, options=options, timings=timings,
            modified_at=modified_at, degraded=tuple(modes),
            reused_from=reused.name if reused is not None else None,
        )

    # ------------------------------------------------------------------

    def transform_bytes(
        self,
        data: bytes,
        options: OptionsBag,
        spec: OutputSpec,
        timings: Optional[Dict[str, float]] = None,
        *,
        deadline: Optional[Deadline] = None,
    ) -> bytes:
        """Public entry for offline callers (the bulk runner): the exact
        cache-miss transform pipeline — decode, device program, smart-crop/
        face post-passes, alpha flatten over bg_, st_0 metadata graft,
        encode — with no storage or HTTP involved. Keeping bulk on this
        single code path is what makes its outputs byte-identical to
        serving for the same options."""
        return self._process_new(
            data, options, spec, {} if timings is None else timings,
            deadline=deadline,
        )

    def _load_source(
        self,
        image_src: str,
        options: OptionsBag,
        timings: Dict[str, float],
        deadline: Optional[Deadline],
    ):
        """The origin fetch + ingest step (service/input_source.py) with
        its span + stage timing — ONE copy shared by the eager path, the
        reuse fallback (lazy, inside the leader), and the background
        stale refresh. With the stage DAG on it runs on the bounded
        fetch I/O pool (a saturated/wedged pool sheds 503 instead of
        silently stacking origin connections on request threads)."""
        def _fetch():
            # timed where it runs (a fetch-pool worker when the stage DAG
            # is on: the pool's own queue wait is
            # flyimg_host_pool_queue_wait_seconds), so that retry and
            # breaker events land on this span
            with tracing.stage("fetch", timings, self.metrics) as fetch_span:
                source = load_source(
                    image_src,
                    options,
                    self.params.by_key("tmp_dir", "var/tmp"),
                    header_extra_options=self.params.by_key(
                        "header_extra_options", ""
                    ),
                    policy=self.fetch_policy,
                    deadline=deadline,
                )
                if fetch_span is not None:
                    fetch_span.set_attribute("source.bytes", len(source.data))
                    fetch_span.set_attribute("source.mime", source.info.mime)
            return source

        return self._stage("fetch", _fetch, deadline, inline_fallback=False)

    def _schedule_refresh(self, spec: OutputSpec, options: OptionsBag,
                          data: Optional[bytes], image_src: str,
                          source_mime: str = "") -> None:
        """Queue ONE background re-render of a stale cache entry
        (stale-while-revalidate, runtime/brownout.py). Coalescing is
        two-layer: the RefreshQueue dedups per derived key (N stale hits
        -> one queued refresh), and the refresh itself runs through the
        single-flight table, so it also coalesces with any concurrent
        foreground miss for the same key. The refresh renders FULL
        quality whatever the current level — the cache must converge to
        fresh, undegraded bytes — under the configured default deadline.
        ``data`` is None when the stale hit was served off the reuse
        fast path (no source in hand); the refresh fetches it here, on
        the background thread, not on the serving path."""
        engine = self.brownout

        def refresh() -> None:
            if self._device_down():
                # CPU failover (runtime/devicesupervisor.py): a
                # background refresh would both burn scarce CPU render
                # capacity and cache a CPU render at the device-quality
                # key — skip; the stale entry keeps serving and the
                # refresh happens after re-promotion
                return
            leader, _flight = self._singleflight.begin(spec.name)
            if not leader:
                return  # a foreground render is already computing it
            try:
                deadline = Deadline(
                    self.default_deadline_s, metrics=self.metrics
                )
                payload = data
                mime = source_mime
                if payload is None:
                    fetched = self._load_source(
                        image_src, options, {}, deadline
                    )
                    payload = fetched.data
                    mime = fetched.info.mime
                render_info: Dict[str, object] = {}
                content = self._process_new(
                    payload, options, spec, {}, deadline=deadline,
                    render_info=render_info,
                )
                if self._device_down():
                    # tripped mid-refresh: settle the coalesced waiters
                    # with the bytes but never cache the CPU render at
                    # the device-quality key (same write-time recheck as
                    # the foreground miss path)
                    self._singleflight.done(
                        spec.name, result=(content, None, ("cpu-fallback",))
                    )
                    return
                modified_at = self.storage.write(spec.name, content)
                if self.reuse_enable:
                    self._record_variant(
                        OptionsBag.hash_original_image_url(image_src),
                        mime, spec, options, render_info,
                    )
            except BaseException as exc:
                self._singleflight.done(spec.name, exc=exc)
                raise
            self._singleflight.done(
                spec.name, result=(content, modified_at, ())
            )

        engine.refresh.submit(spec.name, refresh)

    # ------------------------------------------------------------------
    # derivative reuse (docs/caching.md; runtime/variantindex.py)

    def _try_reuse(
        self,
        entry,
        options: OptionsBag,
        spec: OutputSpec,
        timings: Dict[str, float],
        *,
        deadline: Optional[Deadline],
        degrade,
        degraded_out: Optional[List[str]],
        render_info: Dict[str, object],
    ):
        """Attempt to render this miss from a cached ancestor rendition.
        Candidates are tried largest-first; the first one that passes
        the safety rules (spec.plan.rewrite_for_reuse) AND whose bytes
        are still readable wins. Returns ``(content, ancestor_facts,
        generations)`` or None after counting the outcome under
        ``flyimg_reuse_hits_total{outcome=}``."""
        min_scale = self.reuse_min_scale
        max_generations = self.reuse_max_generations
        widened = False
        engine = self.brownout
        if engine is not None and engine.swr_active():
            # DEGRADED+ widening (docs/degradation.md "Reuse widening"):
            # under pressure a nearer ancestor and one extra lossy
            # generation beat a full origin-fetch + decode + render
            widened = True
            min_scale = self.reuse_degraded_min_scale
            max_generations += 1
        reason = None
        for anc in entry.candidates():
            if anc.name == spec.name:
                continue
            plan, target_out, why = rewrite_for_reuse(
                options, spec.extension, anc,
                min_scale=min_scale, max_generations=max_generations,
            )
            if plan is None:
                reason = why
                continue
            blob = self._fetch_ancestor(entry.source_key, anc)
            if blob is None:
                reason = "ancestor_gone"
                continue
            try:
                content = self._process_new(
                    blob, options, spec, timings, deadline=deadline,
                    degrade=degrade, degraded_out=degraded_out,
                    render_info=render_info,
                )
            except DeadlineExceededException:
                raise  # an exhausted budget is a 504 either way
            except Exception:
                # a torn write can leave a blob with valid leading magic
                # but an undecodable body — the sniff in _fetch_ancestor
                # cannot see that. Drop the rendition and fall back to
                # the from-source pipeline instead of failing the
                # request (and its coalesced followers).
                self.variants.discard(entry.source_key, anc.name)
                tracing.add_event(
                    "reuse.ancestor_invalid", ancestor=anc.name
                )
                reason = "ancestor_gone"
                continue
            # hit accounting only AFTER the render succeeded — a failed
            # attempt above must not read as a hit in metrics or spans
            scale = min(
                anc.out_w / max(target_out[0], 1),
                anc.out_h / max(target_out[1], 1),
            )
            tracing.add_event(
                "reuse.ancestor_hit", ancestor=anc.name,
                scale=round(scale, 3), generations=anc.generations,
                widened=widened,
            )
            self._record_reuse("hit")
            generations = anc.generations + (1 if anc.lossy else 0)
            if self.metrics is not None:
                self.metrics.histogram(
                    "flyimg_reuse_generations",
                    "Lossy re-encode depth of reuse-rendered outputs",
                    bounds=(0.5, 1.5, 2.5, 3.5),
                ).observe(float(generations))
            return content, anc, generations
        self._record_reuse("unsafe" if reason is not None else "miss")
        return None

    def _fetch_ancestor(self, source_key: str, anc) -> Optional[bytes]:
        """Read + validate one candidate ancestor's bytes. A missing or
        corrupt rendition is dropped from the index (the index is a
        cache of storage state, never the truth) and the caller tries
        the next candidate. The ``reuse.ancestor`` fault point may
        inject bytes (simulated ancestor) or raise (simulated pruned
        object -> fall back to the full pipeline)."""
        try:
            injected = faults.fire("reuse.ancestor", name=anc.name)
            if injected is not faults.PASS:
                blob = injected
            else:
                fetched = self.storage.fetch(anc.name)
                blob = fetched[0] if fetched is not None else None
        except Exception:
            blob = None
        expected = EXT_TO_MIME.get(anc.extension)
        if not blob or (
            expected is not None and sniff(blob).mime != expected
        ):
            self.variants.discard(source_key, anc.name)
            return None
        return blob

    def _record_reuse(self, outcome: str) -> None:
        """One reuse-rewriter decision on a cache miss; ``outcome`` is
        the fixed vocabulary hit | unsafe | miss (docs/observability.md)."""
        if self.metrics is None:
            return
        self.metrics.counter(
            f'flyimg_reuse_hits_total{{outcome="{outcome}"}}',
            "Cache-miss reuse-rewriter decisions by outcome",
        ).inc()

    def _record_variant(
        self,
        source_key: str,
        source_mime: str,
        spec: OutputSpec,
        options: OptionsBag,
        render_info: Dict[str, object],
        *,
        generations: int = 0,
        ancestor=None,
    ) -> None:
        """Index a just-stored rendition when it is a reuse-safe
        ancestor (a pure full-frame resample). For reuse renders the
        recorded source dims propagate from the chosen ancestor, so the
        chain keeps describing the TRUE source scale."""
        plan = render_info.get("plan")
        src_size = (
            (ancestor.src_w, ancestor.src_h)
            if ancestor is not None
            else render_info.get("src_size")
        )
        if plan is None or src_size is None or spec.is_gif:
            return
        if spec.extension not in ("png", "jpg", "webp"):
            return
        pure = (
            plan.resize_to is not None
            and plan.extent is None
            and plan.extract is None
            and plan.rotate is None
            and plan.colorspace is None
            and not plan.monochrome
            and plan.unsharp is None
            and plan.sharpen is None
            and plan.blur is None
            and not plan.smart_crop
            and not plan.face_blur
            and not plan.face_crop
        )
        if not pure:
            return  # only reuse-safe ancestors are worth indexing
        out_w, out_h = plan.resize_to
        self.variants.record(
            source_key,
            source_mime,
            VariantFacts(
                name=spec.name,
                out_w=out_w,
                out_h=out_h,
                extension=spec.extension,
                quality=options.int_option("quality", 90) or 90,
                lossy=lossy_output(spec.extension, options),
                pure=True,
                colorspace=None,
                monochrome=False,
                background=plan.background,
                generations=generations,
                src_w=int(src_size[0]),
                src_h=int(src_size[1]),
                frame_key=reuse_frame_key(options),
                stored_at=time.time(),
            ),
        )

    # ------------------------------------------------------------------
    # cross-replica single-flight (storage/tiered.py L2Lease;
    # docs/fleet.md "The lease protocol")

    def _l2_coalesce(self, spec: OutputSpec, deadline: Optional[Deadline]):
        """Decide this replica's role for a both-tier miss. Returns
        ``("lead", token)`` when this replica must render (``token``
        releases the lease afterwards; None when lease IO itself failed
        and we render uncoalesced), or ``("serve", content, mtime)``
        with a remote leader's artifact.

        Followers poll with the configured cadence, bounded by the
        request Deadline (exhaustion -> 504, never a hang) and by the
        lease wait cap (-> 503, like a wedged local leader). A lease
        that expires or is released without an artifact — crashed
        leader, degraded never-cached render — is stolen and this
        replica renders. A torn artifact under an active lease is
        sniffed, discarded from BOTH tiers, and re-rendered once the
        lease frees (the read-time integrity posture of
        ``_cache_entry_valid``, fleet-wide)."""
        lease = self.l2lease
        with tracing.span("l2.lease", key=spec.name) as lease_span:
            token = lease.acquire(spec.name)
            if token is not None:
                # won the lease — but close the write-then-release race
                # first: a previous leader may have published the
                # artifact after our tiered fetch missed and before its
                # release let our acquire through
                cached = self.storage.fetch_hedged(spec.name)
                if cached is not None and _cache_entry_valid(
                    cached[0], spec
                ):
                    lease.release(spec.name, token)
                    self._record_lease("coalesced")
                    if lease_span is not None:
                        lease_span.set_attribute("lease.role", "coalesced")
                    return ("serve", cached[0], cached[1].mtime)
                self._record_lease("lead")
                tracing.add_event("l2.lease_acquired", key=spec.name)
                if lease_span is not None:
                    lease_span.set_attribute("lease.role", "leader")
                return ("lead", token)
            tracing.add_event(
                "l2.lease_wait", key=spec.name,
                holder=lease.holder(spec.name) or "",
            )
            # follower-wait accounting: while this thread polls behind a
            # remote leader it counts in lease.waiters, which the
            # brownout engine reads as the `l2_lease` pressure component
            # — a fleet-wide hot-key stampede parks every follower here,
            # and without this the blocked replica would look IDLE to
            # its own overload ladder (docs/degradation.md)
            lease.begin_wait()
            try:
                waited = 0.0
                while True:
                    if deadline is not None:
                        deadline.check("l2_lease")
                    if waited >= lease.wait_cap_s:
                        self._record_lease("timeout")
                        if lease_span is not None:
                            lease_span.set_attribute("lease.role", "timeout")
                        raise ServiceUnavailableException(
                            "timed out waiting for the fleet leader "
                            "rendering this output"
                        )
                    step = lease.poll_s
                    if deadline is not None:
                        step = deadline.timeout(step) or step
                    lease._sleep(max(step, 0.001))
                    waited += max(step, 0.001)
                    cached = self.storage.fetch_hedged(spec.name)
                    if cached is not None:
                        if _cache_entry_valid(cached[0], spec):
                            self._record_lease("coalesced")
                            if lease_span is not None:
                                lease_span.set_attribute(
                                    "lease.role", "coalesced"
                                )
                            return ("serve", cached[0], cached[1].mtime)
                        # torn under an active lease: a valid-magic,
                        # garbage-body blob must not serve anywhere in the
                        # fleet — discard both copies and re-render here
                        # once the lease frees
                        tracing.add_event(
                            "cache.corrupt", key=spec.name,
                            bytes=len(cached[0]),
                        )
                        if self.metrics is not None:
                            self.metrics.record_cache_corrupt()
                        try:
                            self.storage.delete(spec.name)
                        except Exception:
                            pass
                    token = lease.acquire(spec.name)
                    if token is not None:
                        self._record_lease("steal")
                        tracing.add_event("l2.lease_steal", key=spec.name)
                        if lease_span is not None:
                            lease_span.set_attribute("lease.role", "steal")
                        return ("lead", token)
            finally:
                lease.end_wait()

    def _record_lease(self, outcome: str) -> None:
        """One cross-replica lease decision; ``outcome`` is the fixed
        vocabulary lead | coalesced | steal | timeout
        (docs/observability.md)."""
        if self.metrics is None:
            return
        self.metrics.counter(
            f'flyimg_l2_lease_total{{outcome="{outcome}"}}',
            "Cross-replica lease decisions on both-tier cache misses",
        ).inc()

    # ------------------------------------------------------------------
    # deadline-aware device waits

    def _device_wait_s(self, deadline: Optional[Deadline]) -> float:
        """One batched-result wait, bounded by the stage cap AND the
        remaining request budget."""
        if deadline is None:
            return self.device_result_timeout_s
        return deadline.timeout(self.device_result_timeout_s)

    def _device_down(self) -> bool:
        """Is the backend supervisor serving on CPU failover right now?
        (runtime/devicesupervisor.py; False without one — zero cost.)"""
        sup = self.device_supervisor
        return sup is not None and sup.cpu_forced()

    def _record_wedge(self) -> None:
        """EVERY wedged-batcher degradation increments the one counter
        operators watch — transform, decode, encode, and post-pass
        fallbacks alike (docs/architecture.md "Resilience")."""
        if self.metrics is not None:
            self.metrics.counter(
                "flyimg_wedged_fallbacks_total",
                "Batched waits that timed out and ran the direct "
                "single-image path instead",
            ).inc()

    def _decode_launch(self, items: list) -> list:
        """The codec controller's runner for a decode group:
        ``codecs.batch_jpeg_decode``, then what the launch says of itself
        (``native_codec.LaunchSplit``) into the registry THIS handler was
        built with: the codec controller keeps one of its own, which no
        exporter reads. A bound method of one handler is one stable
        runner (the runner is part of the group's key)."""
        from flyimg_tpu.codecs import batch_jpeg_decode, native_codec

        split = native_codec.LaunchSplit()
        results = batch_jpeg_decode(items, split)
        if self.metrics is not None:
            self.metrics.record_codec_decode_launch(split)
            self.metrics.record_codec_workers("decode", split)
        return results

    def _encode_launch(self, items: list) -> list:
        """The encode-side twin of :meth:`_decode_launch`: the launch's
        buffers (each copied once into ``bytes``) and its workers."""
        from flyimg_tpu.codecs import batch_jpeg_encode, native_codec

        split = native_codec.LaunchSplit()
        results = batch_jpeg_encode(items, split)
        if self.metrics is not None:
            self.metrics.record_codec_buffers(
                "bytes", split.buffers, split.buffer_bytes
            )
            self.metrics.record_codec_workers("encode", split)
        return results

    def _face_detect_launch(self, items: list) -> list:
        """The device controller's runner for a face-detection group: the
        backend's batched detector, then what the launch says of itself
        into this handler's registry (blazeface: the network inputs it
        ran, real and padded, its forward launches and the seconds of each
        part of the launch; any detector: the boxes it kept)."""
        stats: Dict[str, float] = {}
        results = self._faces().detect_faces_batched(items, stats)
        if self.metrics is not None:
            self.metrics.record_face_detect_launch(
                stats, sum(len(boxes) for boxes in results)
            )
        return results

    def _face_pixelate_launch(self, items: list) -> list:
        """The runner of ``fb_1``'s second trip: the batched ``uint8``
        pixelation program (ops/pixelate.py), counted here."""
        from flyimg_tpu.ops import pixelate

        stats: Dict[str, int] = {}
        results = pixelate.pixelate_images(items, stats)
        if self.metrics is not None:
            self.metrics.record_face_pixelate_launch(stats)
        return results

    def _blur_faces(self, ff, out: np.ndarray, faces: list,
                    timings: Dict[str, float],
                    deadline: Optional[Deadline]) -> np.ndarray:
        """``fb_1`` on one rendition with at least one box: a second trip
        through the device controller, under a key of its own, so that
        the request's thread dispatches nothing to the device itself."""
        if self.batcher is None:
            return ff.blur_faces(out, faces)
        from flyimg_tpu.ops import pixelate

        item = pixelate.prepare_work(out, faces)
        with tracing.stage("faces_pixelate", timings, self.metrics,
                           span_name="faces.pixelate"):
            try:
                return self._aux_result(
                    self.batcher.submit_aux(
                        ("face_pixelate", item.bucket), item,
                        self._face_pixelate_launch,
                    ),
                    "faces_pixelate", timings, deadline,
                )
            except FutureTimeout:
                if deadline is not None:
                    deadline.check("faces")
                self._record_wedge()
                return ff.blur_faces(out, faces)

    def _aux_result(self, future: Future, stage: str,
                    timings: Dict[str, float],
                    deadline: Optional[Deadline]):
        """Wait for one aux member (a codec launch's, or the smart-crop
        scorer's on the device controller) and split the wait by the
        member's own instants (runtime/batcher.py ``launch_times``):
        ``<stage>_queue`` is enqueue -> its launch popped, ``<stage>_run``
        the runner call that carried it. What is left of the enclosing
        stage is the handler's own work around the launch (probe, ROI
        window, the prescale and the cut, the wake-up)."""
        result = future.result(timeout=self._device_wait_s(deadline))
        times = getattr(future, "launch_times", None)
        if times is not None:
            queued, popped, ready, _ = times
            tracing.stage_interval(
                f"{stage}_queue", queued, popped, timings, self.metrics,
                span_name=f"{stage}.queue",
            )
            tracing.stage_interval(
                f"{stage}_run", popped, ready, timings, self.metrics,
                span_name=f"{stage}.run",
            )
        return result

    def _await_transform(
        self,
        future: Future,
        frame: np.ndarray,
        frame_plan: TransformPlan,
        deadline: Optional[Deadline],
        src_window: Optional[Tuple[int, int]] = None,
    ) -> np.ndarray:
        """Resolve one batched transform, degrading sanely when it can't:
        an exhausted budget is a 504 (fail fast, no further waiting); a
        wedged executor falls back to the direct single-image program in
        THIS thread (degraded but correct) or, with the fallback disabled,
        sheds as a 503. A member answered by its launch records how long
        this thread took to wake after its ``set_result``
        (``launch_times``' last instant; ``flyimg_batch_wake_seconds``),
        and keeps the instant it woke on the future (``woke``)."""
        try:
            result = future.result(timeout=self._device_wait_s(deadline))
        except FutureTimeout:
            if deadline is not None:
                deadline.check("device")
            if self.wedged_fallback:
                self._record_wedge()
                return run_plan(frame, frame_plan, src_window=src_window)
            exc = ServiceUnavailableException(
                "device executor did not produce a result in time"
            )
            raise exc from None
        times = getattr(future, "launch_times", None)
        if times is not None:
            future.woke = time.perf_counter()
            if self.metrics is not None:
                self.metrics.record_member_wake(future.woke - times[3])
        return result

    def _tiled_or_none(self, frame: np.ndarray, plan: TransformPlan):
        """Run an H-sharded tiled program when one applies to a tall input:
        halo-exchange resample for full-frame resample-only plans (the
        4k-thumbnail-firehose path, BASELINE.md config 4), ppermute-ring
        rotate for rotate-only plans, halo-exchange conv for single-filter
        plans. Anything else -> None (batcher / direct path); every branch
        is an allowlist so any new pixel op fails safe to the batcher."""
        if self.sp_mesh is None or frame.dtype != np.uint8:
            return None
        single = self._tiled_single_op_or_none(frame, plan)
        if single is not None:
            return single
        if plan.resize_to is None:
            return None
        # allowlist, not denylist: the device plan must be EXACTLY a bare
        # resample (any pixel op — present or added later — fails safe to
        # the batcher, which runs the full compiled program)
        bare = TransformPlan(
            src_size=(0, 0), resize_to=None, extent=None,
            filter_method=plan.filter_method,
        )
        if plan.device_plan() != bare:
            return None
        h, w = frame.shape[:2]
        if h < self.TILE_MIN_ROWS:
            return None
        from flyimg_tpu.ops.compose import plan_layout

        # layout geometry checks cover crop windows / extent pads / extract
        # offsets in one generalizing form (span must be the full frame);
        # heights need NOT divide the sp axis — tiled_transform pads
        layout = plan_layout(plan)
        out_h, out_w = layout.resample_out
        if (
            layout.out_true != (out_h, out_w)
            or layout.pad_canvas is not None
            or layout.span_y != (0.0, float(h))
            or layout.span_x != (0.0, float(w))
        ):
            return None

        import jax.numpy as jnp

        from flyimg_tpu.parallel.tiling import tiled_transform

        try:
            out = tiled_transform(
                jnp.asarray(frame), (out_h, out_w), self.sp_mesh,
                method=plan.filter_method,
            )
        except ValueError:
            # infeasible geometry (halo would exceed a tile) -> batcher
            return None
        if self.metrics is not None:
            self.metrics.counter(
                "flyimg_tiled_resamples_total",
                "Large inputs resampled via sp-axis spatial tiling",
            ).inc()
        return np.asarray(
            jnp.clip(jnp.round(out), 0.0, 255.0).astype(jnp.uint8)
        )

    def _tiled_single_op_or_none(self, frame: np.ndarray, plan: TransformPlan):
        """Tiled execution for tall single-op plans: EXACTLY one of
        rotate / blur / sharpen / unsharp and nothing else (no geometry
        change, no color ops, no extract)."""
        h = frame.shape[0]
        if h < self.TILE_MIN_ROWS:
            return None
        # extract must fail-safe here explicitly: device_plan() zeroes the
        # extract field (it is applied as a resample-window pre-pass), so
        # the dp == allowed check below cannot see it — without this guard
        # an e_1 + single-op request would run the op on the UNcropped frame
        if (
            plan.resize_to is not None
            or plan.extent is not None
            or plan.extract is not None
        ):
            return None
        ops_set = [
            name for name in ("rotate", "blur", "sharpen", "unsharp")
            if getattr(plan, name) is not None
        ]
        if len(ops_set) != 1:
            return None
        # allowlist via device_plan, like the resample branch: the compiled
        # plan must be EXACTLY bare + this one op (+ background, which only
        # rotate reads when extent is None) — any other pixel-op field,
        # present or added later, fails safe to the batcher
        from dataclasses import replace

        dp = plan.device_plan()
        bare = TransformPlan(
            src_size=(0, 0), resize_to=None, extent=None,
            filter_method=plan.filter_method,
        )
        allowed = replace(
            bare, background=dp.background,
            **{ops_set[0]: getattr(dp, ops_set[0])},
        )
        if dp != allowed:
            return None
        import jax.numpy as jnp

        from flyimg_tpu.parallel.tiling import tiled_filter, tiled_rotate

        try:
            op = ops_set[0]
            if op == "rotate":
                out = tiled_rotate(
                    jnp.asarray(frame), float(plan.rotate), self.sp_mesh,
                    background=plan.background,
                )
            elif op == "blur":
                r, s = plan.blur
                out = tiled_filter(
                    jnp.asarray(frame, jnp.float32), self.sp_mesh, "blur", r, s
                )
            elif op == "sharpen":
                r, s, _, _ = plan.sharpen
                out = tiled_filter(
                    jnp.asarray(frame, jnp.float32), self.sp_mesh,
                    "sharpen", r, s,
                )
            else:
                r, s, gain, thr = plan.unsharp
                out = tiled_filter(
                    jnp.asarray(frame, jnp.float32), self.sp_mesh,
                    "unsharp", r, s, gain=gain, threshold=thr,
                )
        except ValueError:
            # infeasible geometry (halo/kernel exceeds a tile) -> batcher
            return None
        if self.metrics is not None:
            self.metrics.counter(
                "flyimg_tiled_single_ops_total",
                "Tall single-op plans run via sp-axis tiling (ring rotate / "
                "halo conv)",
            ).inc()
        return np.asarray(
            jnp.clip(jnp.round(out), 0.0, 255.0).astype(jnp.uint8)
        )

    def _encode_one(
        self,
        frame: np.ndarray,
        spec: OutputSpec,
        options: OptionsBag,
        *,
        alpha,
        deadline: Optional[Deadline] = None,
        quality_cap: Optional[int] = None,
        degraded_out: Optional[List[str]] = None,
        timings: Dict[str, float],
    ) -> bytes:
        """Encode a finished frame. JPEG outputs ride the native encode
        pool through the host-codec controller when available, so
        concurrent misses pay the trellis DP in parallel on C worker
        threads (the encode-side twin of _decode_batched); everything else
        (and every fallback) uses the single-image encode().
        ``quality_cap`` is the brownout clamp (docs/degradation.md): it
        applies — and tags "quality" into ``degraded_out`` — only when it
        actually lowers the effective quality of a LOSSY output, so the
        tag, the never-cache decision keyed on it, and the bytes can
        never drift apart (PNG/GIF ignore quality; lossless WebP bytes
        must stay byte-identical to the normal render)."""
        from flyimg_tpu.codecs import native_codec, parse_sampling_factor

        quality = options.int_option("quality", 90) or 90
        lossy = spec.extension == "jpg" or (
            spec.extension == "webp"
            and not options.truthy("webp-lossless")
        )
        if quality_cap is not None and lossy and int(quality_cap) < quality:
            quality = int(quality_cap)
            if degraded_out is not None:
                degraded_out.append("quality")
        mozjpeg = str(options.get_option("mozjpeg")) == "1"
        sampling_factor = str(options.get_option("sampling-factor") or "1x1")
        if parse_colorspace(options) == "cmyk":
            # CMYK is an ENCODE-side space: device pixels stay RGB and the
            # container stores CMYK samples (reference: IM converts and
            # writes CMYK JPEGs transparently, ImageProcessor.php:88).
            # Container validity was checked before any decode/device work
            # (_process_new). sf_ still validates — an invalid value is a
            # 400 on every jpg path, even though CMYK's 4-channel encode
            # does not subsample
            parse_sampling_factor(sampling_factor)
            return self._stage(
                "encode",
                lambda: _encode_cmyk_jpeg(frame, spec, quality, mozjpeg),
                deadline,
            )
        if (
            self.codec_batcher is not None
            and spec.extension == "jpg"
            and alpha is None
            and native_codec.get_pool() is not None
        ):
            # validate the grammar HERE so a bad sf_ raises in the request
            # thread (typed 400), not inside the shared pool runner
            sampling = parse_sampling_factor(sampling_factor)
            try:
                blob = self._aux_result(
                    self.codec_batcher.submit_aux(
                        ("jpegenc", quality, sampling, mozjpeg),
                        (np.ascontiguousarray(frame), quality, sampling,
                         mozjpeg),
                        self._encode_launch,
                    ),
                    "encode", timings, deadline,
                )
            except FutureTimeout:
                if deadline is not None:
                    deadline.check("encode")
                self._record_wedge()
                blob = None  # wedged codec pool: single-image encode below
            if blob is not None:
                return blob
        # CPU-bound single-image encode: with the stage DAG on it runs
        # on the bounded encode pool instead of oversubscribing request
        # threads (the codec-batcher path above already bounds its own
        # native parallelism)
        png_call = PngCall()
        blob = self._stage(
            "encode",
            lambda: encode(
                frame,
                spec.extension,
                quality=quality,
                webp_lossless=bool(options.truthy("webp-lossless")),
                mozjpeg=mozjpeg,
                sampling_factor=sampling_factor,
                strip=options.truthy("strip"),
                alpha=alpha,
                call=png_call,
            ),
            deadline,
        )
        self._record_png("encode", png_call)
        return blob

    def _record_png(self, op: str, call: PngCall) -> None:
        """One native PNG call (``op`` decode or encode): its seconds
        inside the library and its image, by the bits of a sample."""
        if self.metrics is None or not call.bits:
            return
        self.metrics.counter(
            f'flyimg_codec_png_seconds_total{{op="{op}",bits="{call.bits}"}}',
            "Seconds inside the native PNG codec, by operation and the "
            "bits of a sample (16: the stored samples, no gamma "
            "transform)",
        ).inc(call.seconds)
        self.metrics.counter(
            f'flyimg_codec_png_images_total{{op="{op}",bits="{call.bits}"}}',
            "Images through the native PNG codec, by operation and the "
            "bits of a sample",
        ).inc()

    def _record_narrowed(self, at: str) -> None:
        """A 16-bit source carried on at 8 bits: where the codec could
        not keep 16 (``decode``: alpha, a colour key, the Pillow
        fallback) or the answer holds 8 (``output``: a JPEG, WebP or GIF,
        or a post-pass that scores 8-bit renditions)."""
        if self.metrics is None:
            return
        self.metrics.counter(
            f'flyimg_depth_narrowed_total{{at="{at}"}}',
            "16-bit sources carried on at 8 bits, by where: decode (the "
            "codec read 8) or output (the answer holds 8)",
        ).inc()

    def _roi_window(self, options: OptionsBag, info, hint,
                    is_animated_gif_out: bool):
        """The post-prescale source window this request's plan lets the
        decoder restrict itself to (spec/plan.py decode_roi_window), or
        None for full decode. The probe plan is built against the dims
        the prescaled decode WILL produce (libjpeg's ceil rule), with
        metrics=None so the real build below does the filter-alias
        counting exactly once (same discipline as rewrite_for_reuse)."""
        if (
            info.mime != "image/jpeg"
            or not info.width
            or not info.height
            or is_animated_gif_out
        ):
            return None
        from flyimg_tpu.codecs import jpeg_batch_scale_num

        scale = jpeg_batch_scale_num(info, hint)
        sw = (info.width * scale + 7) // 8
        sh = (info.height * scale + 7) // 8
        try:
            probe_plan = build_plan(options, sw, sh)
        except Exception:
            # an invalid option raises identically in the real
            # build_plan below — the probe must not pre-empt (or alter)
            # that typed error path
            return None
        return decode_roi_window(probe_plan)

    @staticmethod
    def _decode_mode(decoded, info, hint) -> str:
        """The decode-mode vocabulary (full | prescale | roi) stamped on
        spans, the flyimg_decode_mode_total counter, and the per-mode
        stage series the perf gate's schema-5 legs read."""
        if decoded.roi_offset is not None:
            return "roi"
        if info.mime == "image/jpeg":
            w0, h0 = decoded.orig_size
            # EXIF orientation may have transposed the frame — only a
            # dims change beyond the swap means the DCT prescale ran
            if decoded.size not in ((w0, h0), (h0, w0)):
                return "prescale"
        return "full"

    def _decode_batched(self, data: bytes, hint, info,
                        timings: Dict[str, float],
                        deadline: Optional[Deadline] = None,
                        roi=None):
        """JPEG fast path through the native DecodePool: concurrent misses
        sharing a DCT prescale decode as ONE pool batch on the host-codec
        controller's thread. ``roi`` (a post-prescale ``(x0, y0, x1, y1)``
        window, docs/host-pipeline.md) rides the same coalesced pool call
        — mixed full/window members share one launch. Returns None for
        everything the pool doesn't cover (non-JPEG, pool unavailable, a
        per-image decode failure, or a wedged pool) — the caller falls
        back to the single-image decode()."""
        if self.codec_batcher is None:
            return None
        from flyimg_tpu.codecs import (
            DecodedImage,
            jpeg_batch_scale_num,
            native_codec,
        )
        from flyimg_tpu.codecs.exif import jpeg_orientation

        if info.mime != "image/jpeg" or native_codec.get_pool() is None:
            return None
        if roi is not None and jpeg_orientation(data) != 1:
            # the window coordinates would not survive the EXIF
            # transpose the full path applies — decode the full frame
            roi = None
        scale = jpeg_batch_scale_num(info, hint)
        try:
            result = self._aux_result(
                self.codec_batcher.submit_aux(
                    ("jpegdec", scale), (data, scale, roi),
                    self._decode_launch,
                ),
                "decode", timings, deadline,
            )
        except FutureTimeout:
            if deadline is not None:
                deadline.check("decode")
            self._record_wedge()
            return None
        if result is None:
            return None
        if isinstance(result, tuple):
            window, offset, frame_size = result
            return DecodedImage(
                rgb=window,
                alpha=None,
                mime="image/jpeg",
                orig_size=(
                    info.width or frame_size[0],
                    info.height or frame_size[1],
                ),
                roi_offset=offset,
                frame_size=frame_size,
            )
        rgb = result
        return DecodedImage(
            rgb=rgb,
            alpha=None,
            mime="image/jpeg",
            orig_size=(info.width or rgb.shape[1], info.height or rgb.shape[0]),
        )

    def _process_new(
        self,
        data: bytes,
        options: OptionsBag,
        spec: OutputSpec,
        timings: Dict[str, float],
        deadline: Optional[Deadline] = None,
        degrade=None,
        degraded_out: Optional[List[str]] = None,
        render_info: Optional[Dict[str, object]] = None,
    ) -> bytes:
        """Memory-governed admission around the miss pipeline
        (runtime/memgovernor.py; docs/resilience.md "Memory governor"):
        header-sniff the decoded footprint BEFORE anything allocates —
        a source over ``mem_max_source_pixels`` rejects as 413, and the
        host byte accountant charges ``w*h*3`` until the render ends
        (releases in a finally: an exception must not leak budget).
        With both knobs off (the default) this adds nothing and the
        pipeline below runs exactly as before."""
        charge = None
        if self.mem_accountant is not None or self.max_source_pixels > 0:
            info = media_info(data)
            if info.width and info.height:
                pixels = int(info.width) * int(info.height)
                if 0 < self.max_source_pixels < pixels:
                    raise PayloadTooLargeException(
                        f"source is {info.width}x{info.height} "
                        f"({pixels} px), over the mem_max_source_pixels "
                        f"bound of {self.max_source_pixels}"
                    )
                if self.mem_accountant is not None:
                    charge = self.mem_accountant.admit(pixels * 3)
        try:
            return self._process_new_inner(
                data, options, spec, timings, deadline=deadline,
                degrade=degrade, degraded_out=degraded_out,
                render_info=render_info,
            )
        finally:
            if charge is not None:
                self.mem_accountant.release(charge)

    def _process_new_inner(
        self,
        data: bytes,
        options: OptionsBag,
        spec: OutputSpec,
        timings: Dict[str, float],
        deadline: Optional[Deadline] = None,
        degrade=None,
        degraded_out: Optional[List[str]] = None,
        render_info: Optional[Dict[str, object]] = None,
    ) -> bytes:
        """Transform pipeline on a cache miss (reference
        ImageHandler::processNewImage, ImageHandler.php:160-181).

        ``render_info`` (when given) receives the resolved ``plan`` and
        the decoded ``src_size`` — the facts the variant index records
        about a stored rendition (docs/caching.md).

        ``degrade`` (the brownout engine, at BROWNOUT+) rewrites the plan
        to cheaper work — finishing ops dropped, host entropy crop in
        place of the device smart-crop scoring pass, encode quality
        clamped to ``brownout_quality`` — appending the applied mode
        names to ``degraded_out`` (docs/degradation.md). None = the
        byte-for-byte normal pipeline."""
        if deadline is not None:
            deadline.check("decode")

        # backend CPU failover (runtime/devicesupervisor.py): tag this
        # render degraded so it serves direct with X-Flyimg-Degraded:
        # cpu-fallback and is NEVER cached — a cached CPU render at the
        # device-quality key would keep serving after re-promotion and
        # mask it. Snapshot once: the state must not flip mid-render.
        if (
            degraded_out is not None
            and self._device_down()
            and "cpu-fallback" not in degraded_out
        ):
            degraded_out.append("cpu-fallback")

        is_animated_gif_out = spec.is_gif
        # clsp_CMYK can only be stored in a JPEG container: refuse HERE,
        # before decode and device work — and before the animation branch,
        # whose encoder would otherwise silently serve RGB GIF bytes under
        # a URL claiming CMYK
        if parse_colorspace(options) == "cmyk":
            _require_cmyk_container(spec)
        # decode target hint for JPEG DCT prescale (scale-aware)
        hint = decode_target_hint(options)

        gif_frame = options.int_option("gif-frame", 0) or 0
        with tracing.stage("decode", timings, self.metrics) as decode_span:
            data_info = media_info(data)  # one probe, shared by both paths
            # ROI decode (docs/host-pipeline.md): for crop/extract-
            # dominant plans, decode only the source window the plan's
            # resample actually samples (+ tap-support margin). The
            # window is computed against the post-prescale frame the
            # decode will produce, so ROI and the DCT prescale compose.
            roi = (
                self._roi_window(options, data_info, hint, is_animated_gif_out)
                if self.decode_roi else None
            )
            decoded = self._decode_batched(
                data, hint, data_info, timings, deadline, roi=roi
            )
            batched_decode = decoded is not None
            if decoded is None:
                png_call = PngCall()
                decoded = self._stage(
                    "decode",
                    lambda: decode(
                        data, target_hint=hint, frame=gif_frame,
                        info=data_info, roi=roi, call=png_call,
                    ),
                    deadline,
                )
                self._record_png("decode", png_call)
            decode_mode = self._decode_mode(decoded, data_info, hint)
            if decoded.narrowed_from:
                self._record_narrowed("decode")
            if decode_span is not None:
                decode_span.set_attribute("decode.batched", batched_decode)
                decode_span.set_attribute("decode.mode", decode_mode)
                decode_span.set_attribute(
                    "decode.bits", 8 * decoded.rgb.dtype.itemsize
                )
        # the per-mode stage series feeds the perf-gate's decode-mode
        # legs (tools/perf_gate.py schema 5) and bench_http's
        # decode-split reporting without disturbing the aggregate
        # `decode` stage every dashboard already reads: the same seconds
        # under the mode's name
        timings[f"decode_{decode_mode}"] = timings["decode"]
        if self.metrics is not None:
            self.metrics.record_stage(
                f"decode_{decode_mode}", timings["decode"]
            )
            # host-codec throughput accounting (the codec-overhaul
            # baseline, ROADMAP item 4): compressed bytes in, next to
            # the decode-pool busy-ratio gauge
            self.metrics.counter(
                "flyimg_decode_bytes_total",
                "Compressed source bytes through the host decode stage",
            ).inc(len(data))
            self.metrics.counter(
                f'flyimg_decode_mode_total{{mode="{decode_mode}"}}',
                "Host decodes by mode (full | prescale | roi)",
            ).inc()

        w, h = decoded.size
        src_window = None
        if decoded.roi_offset is not None and decoded.frame_size is not None:
            # the decoded pixels are a window; geometry must still
            # resolve against the FULL (post-prescale) frame dims, with
            # the window offset threaded to the device as a span shift
            w, h = decoded.frame_size
            src_window = decoded.roi_offset
        plan = build_plan(options, w, h, metrics=self.metrics)
        if render_info is not None:
            render_info["plan"] = plan
            render_info["src_size"] = (w, h)
        quality_cap = None
        if degrade is not None:
            plan, dropped = degrade_plan(plan)
            if degraded_out is not None:
                degraded_out.extend(dropped)
            # the "quality" mode is tagged by _encode_one itself, where
            # the clamp actually applies — the tag and the bytes cannot
            # drift apart
            quality_cap = int(degrade.quality)
        spec.command_repr = repr(plan)

        frames = [decoded.rgb]
        anim: Optional[_Animation] = None
        if is_animated_gif_out and decoded.n_frames > 1:
            anim = _decode_all_frames(data)
            frames = anim.frames
            if anim.alphas is not None:
                # transparent animation: the device transform runs on rgb
                # flattened over bg_ (what opaque viewers composite), and
                # the alpha planes ride through extra frames under a
                # GEOMETRY-ONLY variant of the plan: resample/extent/crop
                # must track the pixels, but value ops (dither, grayscale,
                # sharpen) would corrupt alpha, and fills (rotate corners,
                # extent pads) become opaque background in the output — so
                # the alpha plan strips value ops and fills with 255
                a_list = anim.alphas
                bg = np.asarray(
                    plan.background or (255, 255, 255), np.float32
                )
                flat = []
                for frame, alpha_plane in zip(frames, a_list):
                    a = alpha_plane[..., None].astype(np.float32) / 255.0
                    flat.append(
                        np.round(
                            frame.astype(np.float32) * a + bg * (1.0 - a)
                        ).astype(np.uint8)
                    )
                frames = flat + [
                    np.repeat(alpha_plane[..., None], 3, axis=2)
                    for alpha_plane in a_list
                ]

        # Alpha survives to the output only when no op changes geometry and
        # the format carries it; everywhere else flatten the RAW rgb over
        # the bg_ color now (IM flattens over -background,
        # ImageProcessor.php:95-101 — not hardcoded white).
        keeps_alpha = (
            decoded.alpha is not None
            and plan.resize_to is None and plan.extent is None
            and plan.extract is None and plan.rotate is None
            and not plan.smart_crop
            and not plan.face_blur and not plan.face_crop
            and anim is None
            and spec.extension in ("png", "webp")
        )
        if decoded.alpha is not None and not keeps_alpha and len(frames) == 1:
            a = decoded.alpha[..., None].astype(np.float32) / 255.0
            bg = np.asarray(plan.background or (255, 255, 255), np.float32)
            frames = [
                np.round(
                    frames[0].astype(np.float32) * a + bg * (1.0 - a)
                ).astype(np.uint8)
            ]

        # sample depth: a 16-bit frame stays 16-bit through the device and
        # the encoder only where the answer is a PNG and no post-pass
        # scores it (smart crop and faces read 8-bit renditions); any
        # other answer narrows here, on the host, as ImageMagick writes a
        # JPEG, WebP or GIF at 8 bits, and takes the 8-bit programs
        keeps_16 = spec.extension == "png" and not (
            plan.smart_crop or plan.face_blur or plan.face_crop
        )
        if frames[0].dtype == np.uint16 and not keeps_16:
            frames = [narrow_to_u8(frame) for frame in frames]
            self._record_narrowed("output")

        # submit every frame before waiting on any: coalesced GIF frames
        # share one program identity, so the batcher runs them as a single
        # vmapped launch instead of n_frames serial device round-trips
        alpha_start = (
            len(anim.frames)
            if anim is not None and anim.alphas is not None
            else None
        )
        with tracing.stage("device", timings, self.metrics,
                           span_name="batch_wait", frames=len(frames)):
            # submissions happen INSIDE this span so the batcher records
            # it as the parent of the shared device_execute span it fans
            # back into this trace (runtime/batcher.py)
            staged = []
            for idx, frame in enumerate(frames):
                fh, fw = frame.shape[:2]
                window = None
                if src_window is not None and anim is None:
                    # ROI decode: the frame IS a window of plan.src_size;
                    # the plan stays as built against the full frame and
                    # the offset shifts the traced spans downstream
                    frame_plan = plan
                    window = src_window
                elif (fw, fh) == plan.src_size:
                    frame_plan = plan
                else:
                    frame_plan = build_plan(
                        options, fw, fh, metrics=self.metrics
                    )
                    if degrade is not None:
                        # rebuilt per-frame plans (animation frames whose
                        # dims differ) must degrade identically to the
                        # primary plan or frames would mix work levels
                        frame_plan, _ = degrade_plan(frame_plan)
                if alpha_start is not None and idx >= alpha_start:
                    from dataclasses import replace as _replace

                    frame_plan = _replace(
                        frame_plan,
                        colorspace=None, monochrome=False,
                        unsharp=None, sharpen=None, blur=None,
                        background=(255, 255, 255),
                    )
                tiled = (
                    None if window is not None
                    else self._tiled_or_none(frame, frame_plan)
                )
                if tiled is not None:
                    staged.append((tiled, frame, frame_plan, None))
                elif self.batcher is not None:
                    # concurrent requests sharing a program batch into one
                    # device launch; the deadline-aware wait below parks
                    # this worker thread while the group fills
                    # (flyimg_tpu/runtime/batcher.py)
                    staged.append(
                        (
                            self.batcher.submit(
                                frame, frame_plan, src_window=window
                            ),
                            frame, frame_plan, window,
                        )
                    )
                else:
                    staged.append(
                        (
                            run_plan(frame, frame_plan, src_window=window),
                            frame, frame_plan, None,
                        )
                    )
            out_frames = [
                self._await_transform(s, frame, frame_plan, deadline, window)
                if isinstance(s, Future) else s
                for s, frame, frame_plan, window in staged
            ]
            # the copy of the frame into its launch's block, made by
            # submit on this thread (runtime/batcher.py _copy_in), and the
            # fill wait as this request saw it: its own enqueue -> its
            # launch popped (of an animation's frames, the longest of each)
            copies = [
                s.copy_times for s, _, _, _ in staged
                if isinstance(s, Future) and hasattr(s, "copy_times")
            ]
            if copies:
                start, end = max(copies, key=lambda c: c[1] - c[0])
                tracing.stage_interval(
                    "device_copy_in", start, end, timings, self.metrics,
                    span_name="batch.copy_in",
                )
            waits = [
                s.launch_times for s, _, _, _ in staged
                if isinstance(s, Future) and hasattr(s, "launch_times")
            ]
            if waits:
                queued, popped, _, _ = max(waits, key=lambda w: w[1] - w[0])
                tracing.stage_interval(
                    "device_queue", queued, popped, timings, self.metrics,
                    span_name="device.queue",
                )
            # where the request sat in its launch's resolve loop (its
            # result ready -> its set_result) and how long this thread
            # took to wake after it (of an animation's frames, the
            # latest to wake)
            woken = [
                (s.launch_times, s.woke) for s, _, _, _ in staged
                if isinstance(s, Future) and hasattr(s, "woke")
            ]
            if woken:
                (_, _, ready, answered), woke = max(
                    woken, key=lambda w: w[1] - w[0][3]
                )
                timings["device_answer"] = answered - ready
                timings["device_wake"] = woke - answered

        # post-passes on the transformed output, in reference order:
        # smart-crop, then face blur, then face crop — all skipped for GIF
        # outputs (ImageHandler.php:125-152)
        if not spec.is_gif:
            out = out_frames[0]
            if plan.smart_crop and degrade is not None:
                # BROWNOUT: the deterministic host entropy crop stands in
                # for the batched device scoring pass — same square
                # output contract, zero device work (docs/degradation.md)
                with tracing.stage("smartcrop", timings, self.metrics,
                                   degraded=True):
                    from flyimg_tpu.models import smartcrop as sc_mod

                    out = sc_mod.entropy_crop_image(out)
                if degraded_out is not None:
                    degraded_out.append("smartcrop")
            elif plan.smart_crop:
                with tracing.stage("smartcrop", timings, self.metrics):
                    sc = self._smartcrop()
                    if self.batcher is not None and hasattr(
                        sc, "prepare_work"
                    ):
                        # concurrent smc_1 requests score in ONE batched
                        # device launch per work-shape bucket — the same
                        # program shape bench.py measures; the per-image
                        # path would recompile analyse_features for every
                        # distinct post-resize size
                        # the host's share before the wait: the prescale
                        # to the scorer's work size and its bookkeeping
                        with tracing.stage("smartcrop_prepare", timings,
                                           self.metrics,
                                           span_name="smartcrop.prepare"):
                            item = sc.prepare_work(out)
                        try:
                            crop = self._aux_result(
                                self.batcher.submit_aux(
                                    ("smc", item.bucket, item.step),
                                    item,
                                    sc.find_best_crops_batched,
                                ),
                                "smartcrop", timings, deadline,
                            )
                        except FutureTimeout:
                            if deadline is not None:
                                deadline.check("smartcrop")
                            # wedged executor: score single-image in this
                            # thread
                            self._record_wedge()
                            out = sc.smart_crop_image(out)
                        else:
                            out = sc.apply_crop(out, crop)
                    else:
                        out = sc.smart_crop_image(out)
            if plan.face_blur or plan.face_crop:
                with tracing.stage("faces", timings, self.metrics):
                    ff = self._faces()
                    if self.batcher is not None and hasattr(
                        ff, "prepare_face_work"
                    ):
                        # batched detection on the device controller, as
                        # smc_1's scoring: the host's share first, here
                        # (blazeface: the six views as network inputs)
                        with tracing.stage("faces_prepare", timings,
                                           self.metrics,
                                           span_name="faces.prepare"):
                            item = ff.prepare_face_work(out)
                        try:
                            faces = self._aux_result(
                                self.batcher.submit_aux(
                                    ("face", item.bucket), item,
                                    self._face_detect_launch,
                                ),
                                "faces", timings, deadline,
                            )
                        except FutureTimeout:
                            if deadline is not None:
                                deadline.check("faces")
                            self._record_wedge()
                            faces = ff.detect_faces(out)
                    else:
                        faces = ff.detect_faces(out)
                    if plan.face_blur and faces:
                        out = self._blur_faces(
                            ff, out, faces, timings, deadline
                        )
                    if plan.face_crop:
                        out = ff.crop_face(out, faces, plan.face_crop_position)
            out_frames = [out]

        if deadline is not None:
            deadline.check("encode")
        with tracing.stage("encode", timings, self.metrics,
                           format=spec.extension,
                           bits=8 * out_frames[0].dtype.itemsize):
            # attach-time decision mirrors keeps_alpha (the flatten
            # decision): attaching alpha to rgb that was already flattened
            # over bg would double-composite semi-transparent pixels
            alpha = None
            if keeps_alpha and len(out_frames) == 1 and \
                    out_frames[0].shape[:2] == decoded.alpha.shape:
                alpha = decoded.alpha

            if anim is not None and len(out_frames) > 1:
                n = len(anim.frames)
                out_alphas = None
                if anim.alphas is not None:
                    # the second half of the staged frames are the
                    # transformed alpha planes; GIF transparency is binary,
                    # so threshold at 128 (IM's behavior quantizing
                    # resampled RGBA to GIF)
                    out_alphas = [
                        np.where(af[..., 0] >= 128, 255, 0).astype(np.uint8)
                        for af in out_frames[n:]
                    ]
                    out_frames = out_frames[:n]
                content = _encode_gif_animation(
                    out_frames, out_alphas, anim.durations, anim.loop
                )
            else:
                content = self._encode_one(
                    out_frames[0], spec, options, alpha=alpha,
                    deadline=deadline, quality_cap=quality_cap,
                    degraded_out=degraded_out, timings=timings,
                )
            # st_0: the reference preserves ALL source metadata when -strip
            # is off (ImageProcessor.php:97-99) — EXIF, ICC profile, XMP. A
            # raw-pixel decode loses them, so collect from the source
            # container (JPEG APPn / PNG iCCP+eXIf / WebP ICCP+EXIF+XMP)
            # and graft into the output (JPEG APPn train / PNG chunks /
            # WebP VP8X container). EXIF orientation is reset to 1 — the
            # rotation is baked into the pixels. GIF outputs drop metadata
            # (the format carries none).
            if (
                not options.truthy("strip")
                and spec.extension in ("jpg", "png", "webp")
                and len(out_frames) == 1
            ):
                from flyimg_tpu.codecs import metadata as meta_mod

                meta = meta_mod.collect(data, decoded.mime)
                if meta and parse_colorspace(options) == "cmyk":
                    # the source's RGB ICC profile must not be grafted onto
                    # CMYK samples — color-managed decoders would apply an
                    # RGB profile to 4-component data (EXIF/XMP still carry)
                    meta.icc = None
                if meta:
                    content = meta_mod.inject(content, spec.extension, meta)
        if self.metrics is not None:
            self.metrics.counter(
                "flyimg_encode_bytes_total",
                "Encoded output bytes through the host encode stage",
            ).inc(len(content))

        # rf_1 debug header payload (reference `identify` line via the
        # im-identify header, Response.php:62 + Processor.php:71-77),
        # rebuilt from our own no-decode probe of the encoded bytes —
        # only on debug requests; only they emit the header
        if options.wants_refresh():
            out_info = media_info(content)
            fmt = spec.extension.upper().replace("JPG", "JPEG")
            spec.identify_repr = (
                f"{spec.name} {fmt} {out_info.width}x{out_info.height} "
                f"{out_info.width}x{out_info.height}+0+0 "
                f"{8 * out_frames[0].dtype.itemsize}-bit sRGB "
                f"{len(content)}B"
            )
        return content


def _cache_entry_valid(content: bytes, spec: OutputSpec) -> bool:
    """Read-time integrity check for a cached output: non-empty and the
    leading magic bytes sniff to the container the name promises. Every
    servable output extension (png/jpg/gif/webp) is sniffable
    (codecs/sniff.py), so a mismatch can only mean corruption — an
    unknown extension (future formats) fails open rather than turning
    every hit into a re-render."""
    if not content:
        return False
    expected = EXT_TO_MIME.get(spec.extension)
    if expected is None:
        return True
    return sniff(content).mime == expected


@dataclass
class _Animation:
    """Coalesced animated-GIF state (reference -coalesce,
    ImageProcessor.php:74-76)."""

    frames: list            # [h, w, 3] uint8 per frame, composited
    alphas: Optional[list]  # [h, w] uint8 per frame; None = fully opaque
    durations: list         # ms per frame
    loop: Optional[int]     # NETSCAPE loop count; None = no ext (play once)


def _decode_all_frames(data: bytes) -> _Animation:
    """All frames of an animated GIF, coalesced with per-frame disposal
    and transparency respected (PIL's GIF plugin composites partial frames
    and handles disposal 2 'restore background' / 3 'restore previous';
    the RGBA convert keeps transparent regions transparent instead of
    baking in a palette color). Loop count is carried through — the old
    hardcoded loop=0 turned play-once GIFs into infinite loops."""
    import io

    from PIL import Image, ImageSequence

    img = Image.open(io.BytesIO(data))
    loop = img.info.get("loop")  # 0 = infinite; absent = play once
    frames, alphas, durations = [], [], []
    any_alpha = False
    for frame in ImageSequence.Iterator(img):
        durations.append(frame.info.get("duration", 100))
        rgba = np.asarray(frame.convert("RGBA"))
        frames.append(np.ascontiguousarray(rgba[..., :3]))
        alpha = rgba[..., 3]
        if alpha.min() < 255:
            any_alpha = True
        alphas.append(np.ascontiguousarray(alpha))
    return _Animation(
        frames=frames,
        alphas=alphas if any_alpha else None,
        durations=durations,
        loop=loop,
    )


def _require_cmyk_container(spec) -> None:
    """THE clsp_CMYK container rule (one copy): only JPEG stores CMYK
    samples. Called before any decode/device work in _process_new and
    again by the encoder for direct callers."""
    if spec.extension not in ("jpg", "jpeg"):
        from flyimg_tpu.exceptions import InvalidArgumentException

        raise InvalidArgumentException(
            "clsp_CMYK requires a JPEG output container (o_jpg); "
            f"{spec.extension!r} cannot store CMYK samples"
        )


def _encode_cmyk_jpeg(frame: np.ndarray, spec, quality: int,
                      optimize: bool) -> bytes:
    """clsp_CMYK output: IM's sRGB->CMYK black-extraction conversion
    (MagickCore colorspace.c sRGBToCMYK: K = min(C,M,Y), channels rescaled
    by 1-K) stored in a CMYK JPEG with the Adobe APP14 convention — the
    multiplicative inverse recovers the sRGB values exactly up to
    quantization (pinned in tests). JPEG is the only supported container
    for CMYK samples (PNG/WebP/GIF define none), matching what IM can
    actually store."""
    import io

    from PIL import Image

    from flyimg_tpu.exceptions import InvalidArgumentException

    _require_cmyk_container(spec)  # _process_new already refused; guard
    # stays for direct/library callers of the encode path
    f = frame.astype(np.float32) / 255.0
    cmy = 1.0 - f
    k = cmy.min(axis=2, keepdims=True)
    denom = np.where(k < 1.0, 1.0 - k, 1.0)
    cmyk = np.concatenate([(cmy - k) / denom, k], axis=2)
    arr = np.clip(cmyk * 255.0 + 0.5, 0, 255).astype(np.uint8)
    im = Image.frombytes(
        "CMYK", (frame.shape[1], frame.shape[0]), arr.tobytes()
    )
    buf = io.BytesIO()
    im.save(buf, "JPEG", quality=int(quality), optimize=bool(optimize))
    return buf.getvalue()


def _encode_gif_animation(frames, alphas, durations, loop) -> bytes:
    """Re-assemble a GIF. Transparency needs explicit palette surgery
    (PIL's RGBA->GIF save silently drops it): quantize to 255 colors and
    reserve index 255 as the transparent index, alpha thresholded at 128
    (GIF transparency is binary — the same quantization IM applies to
    resampled RGBA). Loop is emitted only when the source had a NETSCAPE
    extension; writing loop=0 unconditionally would turn play-once GIFs
    into infinite loops."""
    import io

    from PIL import Image

    pil_frames = []
    for i, frame in enumerate(frames):
        pil = Image.fromarray(frame)
        if alphas is not None:
            p = pil.convert("P", palette=Image.Palette.ADAPTIVE, colors=255)
            mask = Image.fromarray(
                np.where(alphas[i] < 128, 255, 0).astype(np.uint8)
            )
            p.paste(255, mask)
            p.info["transparency"] = 255
            pil = p
        pil_frames.append(pil)
    buf = io.BytesIO()
    kwargs = {}
    if loop is not None:
        kwargs["loop"] = loop
    if alphas is not None:
        # frames with holes must not stack on each other
        kwargs.update(disposal=2, transparency=255, optimize=False)
    pil_frames[0].save(
        buf,
        "GIF",
        save_all=True,
        append_images=pil_frames[1:],
        duration=durations or 100,
        **kwargs,
    )
    return buf.getvalue()
