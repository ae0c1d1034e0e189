"""Server configuration (the reference's parameters.yml tier).

Mirrors AppParameters (reference src/Core/Entity/AppParameters.php): a YAML
file of server-level settings merged over built-in defaults that match
reference config/parameters.yml:1-41. Per-request options live in
flyimg_tpu.spec.options; this is only the server tier.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional

try:
    import yaml
except ImportError:  # pragma: no cover - pyyaml is present in this image
    yaml = None

from flyimg_tpu.spec.options import DEFAULT_OPTIONS, OPTIONS_KEYS

# reference config/parameters.yml defaults
SERVER_DEFAULTS: Dict[str, Any] = {
    "application_name": "flyimg-tpu",
    "debug": False,
    "header_cache_days": 365,
    "options_separator": ",",
    "security_key": "",
    "security_iv": "",
    "restricted_domains": False,
    "whitelist_domains": [],
    "storage_system": "local",
    "aws_s3": {"access_id": "", "secret_key": "", "region": "", "bucket_name": ""},
    # GCS storage backend config (storage/gcs.py): bucket_name +
    # optional project; credentials come from ADC
    "gcs": {"bucket_name": "", "project": ""},
    # route-pattern overrides (service/app.py; reference config/routes.yml)
    "routes": {},
    "header_extra_options": (
        "User-Agent: Mozilla/5.0 (Windows; U; Windows NT 6.1; rv:2.2) "
        "Gecko/20110201"
    ),
    "options_keys": dict(OPTIONS_KEYS),
    "default_options": dict(DEFAULT_OPTIONS),
    # --- TPU-framework additions (no reference analog) ---
    "upload_dir": "web/uploads",
    "tmp_dir": "var/tmp",
    "batch_max_size": 64,
    "batch_deadline_ms": 4.0,
    # dispatched-but-unread batches in flight (2 = double buffering;
    # 1 = strict serial launch->read). See runtime/batcher.py.
    "batch_pipeline_depth": 2,
    # host-codec batch controller (native DecodePool JPEG-miss decode)
    "decode_batch_max": 32,
    "decode_deadline_ms": 1.0,
    # --- host codec overhaul (docs/host-pipeline.md). Both knobs default
    # ON since the recorded CPU soak A/B (benchmarks/HOSTPIPE_r02_soak.json:
    # cropzoom 4.2x rps / p50 3030->696 ms, thumbnail p99 2008->928 ms,
    # zero failures); explicit false restores the pre-overhaul inline
    # path byte-for-byte (pinned by tests/test_roi_decode.py +
    # tests/test_host_pipeline.py) ---
    # ROI JPEG decode: crop/extract-dominant plans decode only the source
    # window they consume (libjpeg-turbo crop/skip scanlines, composable
    # with the DCT prescale; PIL decode+crop fallback)
    "decode_roi": True,
    # pipelined stage DAG (runtime/hostpipeline.py): bounded per-stage
    # worker pools for the miss path's host work, with admission-gate
    # backpressure instead of silent queueing
    "host_pipeline_enable": True,
    "host_pipeline_fetch_workers": 4,
    "host_pipeline_decode_workers": 2,
    "host_pipeline_encode_workers": 2,
    # per-stage queue bound beyond the workers (pending > workers +
    # queue_depth sheds 503 + Retry-After through the admission gate)
    "host_pipeline_queue_depth": 16,
    # a stage worker stuck inside one task longer than this is abandoned
    # and replaced (same self-healing posture as the batch executor);
    # 0 disables the wedge check
    "host_pipeline_wedge_timeout_s": 60.0,
    # serving resample kernel (ops/resample.py; docs/kernels.md):
    # 'dense' = the shipped [out, in] weight-matrix einsums; 'banded' =
    # static K-tap gather-contract (~30x fewer resample MACs at serving
    # scales); 'auto' = banded whenever the band is narrower than the
    # dense matrix. The FLYIMG_RESAMPLE_KERNEL env var seeds the default
    # so offline A/B tools (bench.py, tools/bench_http.py) flip the
    # variant without config plumbing. Default dense until a chip
    # measurement decides (ROADMAP S4/D2).
    "resample_kernel": os.environ.get("FLYIMG_RESAMPLE_KERNEL", "dense"),
    # face engine selection + optional blazeface checkpoint dir
    # (models/faces.py make_face_backend)
    "face_backend": "auto",
    "face_checkpoint": None,
    # persistent XLA compilation cache dir, relative to the checkout
    # ('' disables; JAX_COMPILATION_CACHE_DIR wins over either;
    # flyimg_tpu/compilecache.py)
    "compilation_cache_dir": "var/cache/xla",
    # local-storage output-cache size budget + background prune cadence
    # (0 disables the budget; non-positive interval disables the loop)
    "cache_max_bytes": 0,
    "cache_prune_interval_s": 300.0,
    # orphaned atomic-write temp files (`.part`, left by a crash between
    # the temp write and its rename) older than this are reclaimed by
    # the same prune pass; 0 disables the sweep
    "cache_part_ttl_s": 3600.0,
    # --- resilience knobs (runtime/resilience.py; docs/architecture.md
    # "Resilience") ---
    # per-request latency budget, minted at HTTP ingress and consumed by
    # fetch/decode/batch-wait/encode; exhaustion -> 504. 0 = unbounded.
    "request_deadline_s": 0.0,
    # source-fetch component timeouts (httpx.Timeout): a blackholed origin
    # fails at the connect cap, not a flat 30s
    "fetch_connect_timeout_s": 3.0,
    "fetch_read_timeout_s": 10.0,
    "fetch_write_timeout_s": 10.0,
    # object-store client component timeouts (storage/s3.py botocore
    # Config connect/read; storage/gcs.py per-call deadlines): the same
    # split-timeout contract the source fetch honors, so a blackholed
    # bucket endpoint fails at the connect cap instead of the client
    # library's default (often 60s+). 0 keeps the library default.
    "storage_connect_timeout_s": 0.0,
    "storage_read_timeout_s": 0.0,
    # transient-failure retry: capped exponential backoff, FULL jitter
    "retry_max_attempts": 3,
    "retry_base_backoff_s": 0.05,
    "retry_max_backoff_s": 2.0,
    # per-upstream-host circuit breaker: consecutive transient failures to
    # trip open, and how long an open breaker sheds before one probe
    "breaker_failure_threshold": 5,
    "breaker_recovery_s": 10.0,
    # admission control: max pending (queued or executing) submissions per
    # batch controller before new work sheds as 503 + Retry-After
    # (0 = unbounded), and the Retry-After value shed responses carry
    "batch_max_queue_depth": 0,
    "decode_max_queue_depth": 0,
    "shed_retry_after_s": 1.0,
    # ceiling on ONE batched-result wait; on expiry the request degrades
    # to the direct single-image program (wedged_executor_fallback) or
    # sheds as 503
    "device_result_timeout_s": 120.0,
    "wedged_executor_fallback": True,
    # --- device-batch failure containment (runtime/batcher.py;
    # docs/resilience.md) ---
    # transient batch failures (device runtime hiccups) re-execute the
    # whole batch up to this many times with full-jitter backoff
    "resilience_batch_retries": 2,
    # poison batch failures (member-caused) re-execute by recursive
    # bisection so innocent members succeed and only the poison member's
    # request fails; off = whole-batch failure (pre-containment behavior)
    "resilience_bisect_enable": True,
    # isolated poison work is fingerprinted (plan key + image digest) and
    # short-circuited to singleton execution for this long; 0 disables
    "resilience_quarantine_ttl": 300.0,
    # an executor thread stuck inside one batch longer than this is
    # replaced (queued groups re-home to the new thread); 0 disables the
    # wedge check (a DEAD executor thread is always replaced)
    "resilience_executor_wedge_timeout_s": 60.0,
    # bounded batcher drain on graceful shutdown (readiness flips to 503
    # first so load balancers stop routing during the drain)
    "shutdown_drain_timeout_s": 30.0,
    # --- memory governor (runtime/memgovernor.py; docs/resilience.md
    # "Memory governor"). Default OFF: disabled the batcher holds no
    # governor, the handler holds no byte accountant, brownout carries
    # no RSS signal — byte-identical serving ---
    # master switch for device-side launch admission: footprint
    # prediction (cost-ledger memory_analysis estimate, else the
    # bytes-per-padded-pixel heuristic), pre-split caps, AIMD capacity
    # ceilings discovered from OOM-class launch failures
    "mem_governor_enable": False,
    # predicted-peak-HBM budget one launch must fit (pre-split over it);
    # 0 = no static budget (ceilings discovered from OOMs still apply)
    "mem_device_budget_bytes": 0,
    # fallback prediction for never-compiled plan families:
    # padded_batch * H * W * this many bytes per padded input pixel
    "mem_heuristic_bytes_per_pixel": 64.0,
    # a family's OOM-discovered capacity ceiling expires after this long
    # without reinforcement; the AIMD probe can raise it back sooner
    "mem_ceiling_ttl_s": 300.0,
    # consecutive clean launches at a ceiling before the additive raise,
    # and how many members each raise adds back
    "mem_probe_successes": 4,
    "mem_probe_step": 1,
    # host-side byte accountant: max predicted decoded bytes (header
    # sniffed w*h*3) inflight across fetch/decode/encode before decode
    # admissions shed 503 + Retry-After; 0 disables the bound
    "mem_host_budget_bytes": 0,
    # RSS watchdog: process RSS normalized against this limit feeds the
    # brownout engine as a pressure signal (1.0 = at the limit); 0
    # disables the watchdog
    "mem_rss_limit_bytes": 0,
    # source bomb guards (413 before allocation): max encoded source
    # bytes accepted from any origin, and max source pixel count
    # (header-sniffed width*height) accepted into any decode path
    "mem_max_source_bytes": 256 * 1024 * 1024,
    "mem_max_source_pixels": 512 * 1024 * 1024,
    # --- backend supervisor (runtime/devicesupervisor.py;
    # docs/resilience.md "Backend failover"). Default OFF: disabled the
    # batcher carries no supervisor reference, no metrics register, no
    # threads exist — byte-identical serving ---
    # master switch: storm detection over classified-transient batch
    # failures, backend breaker, CPU failover, probe re-promotion
    "device_supervisor_enable": False,
    # consecutive transient device-batch failures that trip the breaker
    # (they must ALSO all land within device_storm_window_s)
    "device_storm_threshold": 5,
    # the rate half of storm detection: the threshold failures must fall
    # inside this window — a slow trickle over hours is per-batch
    # retry's job, not a storm
    "device_storm_window_s": 30.0,
    # background re-probe cadence while failed over
    "device_probe_interval_s": 5.0,
    # consecutive clean probes required before re-promotion (hysteresis:
    # one lucky probe against a flapping backend must not re-promote)
    "device_probe_hysteresis": 2,
    # bound on the in-flight batch drain at failover/re-promotion;
    # leftovers are timeout-stamped like a shutdown drain
    "device_failover_drain_s": 10.0,
    # fleet health gate (runtime/fleet.py): how long a peer's
    # device-down verdict re-homes its keys to the next rendezvous
    # choice (active /readyz probe at most once per TTL per peer, plus
    # passive detection off relayed cpu-fallback responses); 0 disables
    "fleet_health_ttl_s": 5.0,
    # --- observability knobs (runtime/tracing.py, runtime/logging.py;
    # docs/observability.md) ---
    # per-request tracing: spans for fetch/decode/batch-wait/device/encode/
    # storage, W3C traceparent in/out, /debug/traces retrieval (debug-gated)
    "tracing_enabled": True,
    # bounded in-process ring of KEPT traces (tail-based sampling)
    "tracing_buffer_size": 256,
    # keep probability for ordinary traces; errors, deadline hits, and
    # slow requests are ALWAYS kept (tail-based sampling)
    "tracing_sample_rate": 1.0,
    # "slow" threshold for the always-keep rule
    "tracing_slow_threshold_s": 0.5,
    # structured logging: format json|text, stdlib level name, and the
    # per-request access line (carries trace_id/span_id for correlation)
    "log_format": "json",
    "log_level": "info",
    "log_access": True,
    # --- SLOs + perf observability (runtime/slo.py, runtime/metrics.py;
    # docs/observability.md "SLOs and burn rates") ---
    # declarative objectives evaluated over sliding windows; breaches
    # (fast AND slow burn over threshold) log + span-event + counter
    "slo_enabled": True,
    # latency objective: requests slower than this are "slow" against the
    # (1 - slo_latency_quantile) latency budget — the BASELINE target
    "slo_latency_p99_ms": 150.0,
    # availability objective in percent; 99.9 -> 0.1% error budget
    "slo_availability": 99.9,
    "slo_latency_quantile": 0.99,
    # multi-window burn-rate evaluation: fast window catches pages-now
    # incidents, slow window suppresses blips (SRE-workbook thresholds)
    "slo_window_fast_s": 300.0,
    "slo_window_slow_s": 3600.0,
    "slo_burn_threshold_fast": 14.4,
    "slo_burn_threshold_slow": 6.0,
    # OpenMetrics exemplars on latency-histogram buckets: each bucket
    # remembers the last traced observation's trace id, linking /metrics
    # tails straight to /debug/traces/{id}
    "metrics_exemplars": True,
    # --- performance observatory (runtime/costledger.py,
    # runtime/profiling.py, runtime/flightrecorder.py;
    # docs/observability.md "Performance observatory") ---
    # per-plan cost-ledger table bound (least-recently-launched evicted;
    # since-boot aggregates survive eviction)
    "costledger_max_entries": 256,
    # on-demand profiler (/debug/profile, debug-gated): ceiling on the
    # per-capture batch budget, hard capture-duration bound (the
    # watchdog stops an armed-but-idle capture), and the capture dir
    # ('' -> <tmp_dir>/profiles)
    "profiling_max_batches": 16,
    "profiling_max_seconds": 30.0,
    "profiling_dir": "",
    # batch flight recorder: ring capacity (launch records), dump dir
    # ('' -> <tmp_dir>/flightrecorder), minimum seconds between dumps
    # (an incident storm must not spam the disk), retained dump files
    "flightrecorder_size": 256,
    "flightrecorder_dump_dir": "",
    "flightrecorder_min_dump_interval_s": 30.0,
    "flightrecorder_max_dumps": 16,
    # --- telemetry warehouse + traffic-mix classifier
    # (runtime/telemetry.py; docs/observability.md "Telemetry warehouse
    # & traffic-mix classifier"). Default-off: with telemetry_enable
    # unset there is no directory, no metrics family, and the serving
    # path is byte-identical (pinned by tests/test_telemetry.py).
    "telemetry_enable": False,
    # archive directory ('' -> <tmp_dir>/telemetry)
    "telemetry_dir": "",
    # seconds between snapshot beats (the beat rides the request
    # middleware like brownout.evaluate(); never a timer thread)
    "telemetry_snapshot_interval_s": 10.0,
    # segment rotation: a segment closes when it reaches this many
    # bytes OR this many seconds old, whichever comes first
    "telemetry_segment_max_bytes": 1048576,
    "telemetry_segment_max_age_s": 300.0,
    # total retention: closed segments evict oldest-first past either
    # bound (the writable segment never evicts)
    "telemetry_retention_max_bytes": 33554432,
    "telemetry_retention_max_segments": 64,
    # flight-recorder dump files join the same retention family: >0
    # overrides the legacy flightrecorder_max_dumps bound (which stays
    # as the documented alias when this is 0)
    "telemetry_retention_max_dumps": 0,
    # traffic-mix classifier: fingerprint window (requests), minimum
    # samples before a label is proposed, and consecutive agreeing
    # beats required before the adopted label flips
    "telemetry_mix_window": 256,
    "telemetry_mix_min_samples": 8,
    "telemetry_mix_hysteresis": 2,
    # --- perf-regression gate defaults (tools/perf_gate.py; CLI flags
    # override; benchmarks/README.md "baseline refresh policy") ---
    # a stage regresses when its calibrated median exceeds
    # baseline * tolerance (CI passes a wider, noise-tolerant band)
    "perf_gate_tolerance": 1.6,
    "perf_gate_repeats": 30,
    "perf_gate_warmup": 3,
    # per-plan FLOP/byte regression band: XLA cost analysis is
    # deterministic for one jax version, so the band only absorbs
    # compiler-version drift (much tighter than the latency bands)
    "perf_gate_cost_tolerance": 1.2,
    # --- graceful degradation under overload (runtime/brownout.py;
    # docs/degradation.md). EVERYTHING here defaults off/fail-safe:
    # with the defaults the serving path is byte-for-byte the
    # non-brownout behavior (pinned by tests/test_brownout.py) ---
    # master switch for the NORMAL->DEGRADED->BROWNOUT->SHED engine
    "brownout_enable": False,
    # pressure thresholds (normalized: 1.0 ~ at capacity) that enter
    # each level; escalation is immediate
    "brownout_degraded_at": 0.6,
    "brownout_brownout_at": 0.85,
    "brownout_shed_at": 1.1,
    # de-escalation gap: drop a level only when pressure < threshold *
    # hysteresis (and after the dwell) — prevents flapping at a boundary
    "brownout_hysteresis": 0.75,
    # minimum seconds at a level before de-escalating (one level at a time)
    "brownout_min_dwell_s": 5.0,
    # pressure re-evaluation cadence (per-request calls cheaper than this
    # reuse the last answer)
    "brownout_eval_interval_s": 0.25,
    # queue-depth normalization reference: pending submissions at which
    # queue pressure reads 1.0 (0 = batch_max_queue_depth, else 64)
    "brownout_queue_ref": 0.0,
    # optional extra signals: inflight requests / open breakers at which
    # those pressures read 1.0 (0 = signal ignored)
    "brownout_inflight_ref": 0.0,
    "brownout_breaker_ref": 0.0,
    # BROWNOUT plan rewriting: encode quality clamp for degraded renders
    "brownout_quality": 40,
    # DEGRADED+ stale-while-revalidate: a cache hit older than this
    # serves immediately with stale markers while one coalesced
    # background refresh re-renders it
    "brownout_stale_ttl_s": 300.0,
    # bound on queued background refreshes (over it, refreshes drop —
    # the refresh queue must not amplify the overload it exists to ride)
    "brownout_refresh_max_pending": 8,
    # --- derivative-reuse rendering (runtime/variantindex.py +
    # service/handler.py; docs/caching.md). Default OFF: with
    # reuse_enable false the serving path is byte-for-byte today's
    # behavior — no index lookups, no manifests, no new headers
    # (pinned by tests/test_reuse.py) ---
    # master switch for the per-source variant index + cache-aware plan
    # rewriter (serve small renditions from cached larger ones)
    "reuse_enable": False,
    # a cached ancestor must be >= this multiple of the target's
    # resample box on BOTH axes (the ">=2x so the ancestor's resample is
    # never quality-determining" rule, same as the JPEG DCT prescale)
    "reuse_min_scale": 2.0,
    # bound on lossy re-encode depth along a reuse chain: an ancestor at
    # or past this many lossy generations is never reused
    "reuse_max_generations": 1,
    # DEGRADED+ widening (brownout compounding, docs/degradation.md):
    # the scale floor the rewriter accepts under pressure (plus one
    # extra lossy generation)
    "reuse_degraded_min_scale": 1.3,
    # variant-index bounds: tracked sources (LRU evicted), reuse-safe
    # renditions kept per source (smallest evicted), and the in-memory
    # TTL after which an entry re-reads its storage manifest
    "reuse_index_max_sources": 512,
    "reuse_index_max_variants": 16,
    "reuse_index_ttl_s": 3600.0,
    # --- fleet serving tier (runtime/fleet.py + storage/tiered.py;
    # docs/fleet.md). EVERYTHING here defaults off: with fleet_replicas
    # empty and l2_enable false the serving path is byte-for-byte the
    # single-replica behavior — no routing, no shared tier, no lease
    # markers, no new headers (pinned by tests/test_fleet.py) ---
    # static replica set (base URLs, e.g. ["http://10.0.0.1:8080", ...]);
    # non-empty arms rendezvous (HRW) owner routing of derived cache keys
    "fleet_replicas": [],
    # THIS replica's own entry in fleet_replicas (its identity in
    # routing, lease markers, log lines, span attributes, and the
    # debug-gated X-Flyimg-Replica header)
    "fleet_replica_id": "",
    # what a non-owner does with an owned key: 'proxy' forwards the
    # request to the owner replica (batches stay dense per plan);
    # 'local' renders here and write-through to the shared L2 makes the
    # result fleet-visible anyway
    "fleet_route": "proxy",
    # ceiling on one proxied request's wait (also bounded by the request
    # deadline); transport failure or expiry falls back to a local render
    "fleet_proxy_timeout_s": 30.0,
    # --- shared L2 cache tier (storage/tiered.py; docs/fleet.md) ---
    # promote the output store to L1 (per-replica, storage_system) + L2
    # (fleet-shared) with read-through promotion and write-through
    "l2_enable": False,
    # the shared tier's backend: 'local' (a shared mount at
    # l2_upload_dir) or 's3'/'gcs' (same aws_s3/gcs config dicts)
    "l2_storage_system": "local",
    "l2_upload_dir": "web/l2",
    # cross-replica single-flight over TTL'd lease markers in the L2:
    # one replica renders a both-tier miss, the others poll for its
    # artifact (bounded by the request deadline) instead of duplicating
    "l2_lease_enable": True,
    # lease expiry: a crashed leader's key becomes stealable after this
    # long (set WELL above any sane render time — an expired-but-alive
    # leader costs one duplicate render)
    "l2_lease_ttl_s": 30.0,
    # follower poll cadence while waiting on a leader's artifact
    "l2_lease_poll_ms": 50.0,
    # ceiling on one follower wait when no request deadline bounds it
    "l2_lease_wait_cap_s": 120.0,
    # L2-lease follower pressure normalization (runtime/brownout.py):
    # concurrent threads parked behind remote lease leaders at which the
    # `l2_lease` brownout component reads 1.0 — a fleet-wide hot-key
    # stampede registers as load instead of looking idle
    "brownout_lease_ref": 8.0,
    # write a blake2b checksum sidecar ("<name>.b2") next to every
    # artifact written through to the shared tier — the anti-entropy
    # scrubber's torn-write detector (runtime/tiersupervisor.py). Off =
    # no sidecars, magic-sniff only
    "l2_checksum_enable": False,
    # --- shared-tier (L2) outage supervisor (runtime/tiersupervisor.py;
    # docs/resilience.md "Island mode"). Default OFF: no storm counting,
    # no prober/scrubber threads, no flyimg_tier_* metrics, serving is
    # byte-identical (pinned by tests/test_tier_supervisor.py) ---
    # consecutive L2 failures within the storm window trip the tier into
    # ISLAND mode: every L2 op short-circuits locally (no per-op
    # timeouts), writes/manifest merges queue in a bounded write-behind
    # journal, and a background prober re-promotes + replays the journal
    # once the tier answers again
    "tier_supervisor_enable": False,
    # storm gate: this many CONSECUTIVE L2 failures, all inside the
    # window, trip island mode (any success resets the count)
    "tier_storm_threshold": 5,
    "tier_storm_window_s": 30.0,
    # re-promotion prober: probe cadence while islanded, and how many
    # consecutive clean probes re-attach (flap damping doubles the
    # requirement after each rapid re-trip, capped at 8x)
    "tier_probe_interval_s": 5.0,
    "tier_probe_hysteresis": 2,
    # write-behind journal bounds: at most this many distinct intents
    # (dedup by key — hot keys cost one entry; overflow drops oldest,
    # counted) and drop entries older than the TTL at replay time
    "tier_journal_max_entries": 512,
    "tier_journal_ttl_s": 900.0,
    # anti-entropy scrubber: walk a bounded random sample of L2
    # artifacts per period, verify magic-sniff + checksum sidecar, and
    # delete-and-count corrupt/torn entries from BOTH tiers. Requires
    # tier_supervisor_enable
    "tier_scrub_enable": False,
    "tier_scrub_interval_s": 60.0,
    "tier_scrub_sample": 8,
    # --- elastic fleet membership (runtime/membership.py;
    # docs/fleet.md "Membership and elasticity"). Default OFF: serving
    # is byte-identical — no markers, no heartbeat thread, no metrics,
    # and fleet_replicas/SIGHUP stay authoritative (pinned by
    # tests/test_fleet_membership.py) ---
    # replicas announce/heartbeat via TTL'd markers on the shared L2
    # tier and the watcher drives FleetRouter.update_replicas — the
    # static fleet_replicas list becomes the boot-time hint only, and
    # the manual escape hatches (POST /debug/fleet/replicas, SIGHUP)
    # are rejected to prevent split-brain. Requires l2_enable with a
    # listable shared backend (l2_storage_system: local)
    "fleet_membership_enable": False,
    # marker expiry: a crashed replica drops from every peer's
    # rendezvous set within this long of its last heartbeat (only ITS
    # keys re-home); must comfortably exceed the heartbeat cadence
    "fleet_membership_ttl_s": 15.0,
    # heartbeat/watch cadence: each beat renews this replica's marker,
    # re-lists the live set, and piggybacks warm-start publication
    "fleet_membership_heartbeat_s": 5.0,
    # --- fleet observatory + autoscale recommendation
    # (runtime/observatory.py; docs/fleet.md "Fleet observatory &
    # autoscaling signal"). Default OFF: no digest markers, no
    # flyimg_fleet_* rollup metrics, no recommendation — byte-identical
    # serving (pinned by tests/test_fleet_observatory.py) ---
    # publish a TTL'd signal digest (SLO burn, brownout level, batch
    # occupancy, shed/deadline rates, backend health, queue depth) on
    # each membership beat, assemble every peer's digest into the
    # fleet rollup, and run the scale-out/in recommender over it.
    # Requires fleet_membership_enable (the digest rides its beat and
    # expires on its TTL)
    "fleet_observatory_enable": False,
    # recommender bounds: never recommend below/above this many
    # routable replicas
    "fleet_autoscale_min_replicas": 1,
    "fleet_autoscale_max_replicas": 8,
    # scale-out triggers (any one): worst normalized burn across the
    # fleet (1.0 = a replica's own brownout threshold), fleet batch
    # occupancy, or any replica's brownout level reaching this rung
    "fleet_autoscale_burn_out": 1.0,
    "fleet_autoscale_occupancy_out": 0.85,
    "fleet_autoscale_brownout_out": 2,
    # scale-in requires ALL quiet below these lower bars (hysteresis:
    # the hold band between the in/out bars absorbs signal wobble)
    "fleet_autoscale_burn_in": 0.5,
    "fleet_autoscale_occupancy_in": 0.5,
    # dwell after any adopted scale_out/scale_in flip before the NEXT
    # non-hold flip may be adopted (dropping to hold is immediate)
    "fleet_autoscale_cooldown_s": 60.0,
    # honor a scale_in recommendation INWARD: the deterministic drain
    # candidate (last sorted ready member — every replica computes the
    # same one) walks itself through the graceful-drain path. Off =
    # recommend-only; an external scaler owns capacity
    "fleet_autoscale_drain": False,
    # --- fleet-wide warm start (runtime/warmstart.py; docs/fleet.md).
    # Default OFF: no recorder installed, no manifests read/written,
    # byte-identical serving ---
    # record the program identities this replica compiles, publish them
    # as a digest-stamped manifest on the shared tier, and AOT-precompile
    # a peer manifest at boot so a scale-out replica serves at speed
    "warmstart_enable": False,
    # ceiling on manifest size (entries recorded per replica AND seeded
    # per boot) — oldest entries trim first on publish
    "warmstart_max_entries": 64,
    # --- negative origin cache (runtime/brownout.py NegativeCache) ---
    # seconds a failing origin (retry-exhausted transient errors, open
    # breaker) short-circuits repeat fetches of the same host+path to an
    # immediate 502; 0 disables the table
    "negative_cache_ttl_s": 0.0,
    "negative_cache_max_entries": 1024,
    # --- hedged storage reads (storage/base.py fetch_hedged) ---
    # ms without a primary cache-read result before ONE backup read is
    # fired and the winner served (bounds cache-hit tail latency when
    # the backing store stalls); 0 disables hedging
    "storage_hedge_delay_ms": 0.0,
    # --- object-passing test hooks (never set in YAML) ---
    # a testing.faults.FaultInjector installed at app construction
    "fault_injector": None,
    # injectable monotonic clock for the brownout hysteresis engine
    # (runtime/brownout.py from_params) so dwell tests never sleep
    "brownout_clock": None,
    # injectable monotonic clock for the device supervisor's storm
    # window / probe bookkeeping (runtime/devicesupervisor.py
    # from_params) — same hook style
    "device_supervisor_clock": None,
    # injectable WALL clock for membership marker timestamps
    # (runtime/membership.py from_params) so TTL/skew tests never sleep
    # — wall, not monotonic: marker ages are compared across processes
    "fleet_membership_clock": None,
    # injectable WALL clock for signal-digest timestamps and the
    # autoscale cooldown (runtime/observatory.py from_params) — same
    # hook style as fleet_membership_clock, and wall for the same
    # reason: digest ages are compared across processes
    "fleet_observatory_clock": None,
    # injectable monotonic clock for the tier supervisor's storm window
    # / probe / journal-TTL bookkeeping (runtime/tiersupervisor.py
    # from_params) — same hook style as device_supervisor_clock
    "tier_supervisor_clock": None,
    # injectable WALL clock for telemetry archive timestamps and the
    # snapshot beat (runtime/telemetry.py from_params) — wall, not
    # monotonic: archive records are compared across restarts, the
    # same reasoning as fleet_membership_clock
    "telemetry_clock": None,
    # injectable monotonic clock for the memory governor's ceiling TTL
    # / probe bookkeeping (runtime/memgovernor.py from_params) — same
    # hook style as brownout_clock
    "mem_clock": None,
}


class AppParameters:
    """Loaded server parameters with reference-compatible accessors."""

    def __init__(self, params: Optional[Dict[str, Any]] = None) -> None:
        merged = dict(SERVER_DEFAULTS)
        if params:
            for key, value in params.items():
                merged[key] = value
        self._params = merged

    @classmethod
    def from_yaml(cls, path: str) -> "AppParameters":
        if not os.path.exists(path):
            raise FileNotFoundError(path)
        if yaml is None:
            raise RuntimeError("pyyaml unavailable; cannot load parameters file")
        with open(path, "r", encoding="utf-8") as fh:
            loaded = yaml.safe_load(fh) or {}
        return cls(loaded)

    def by_key(self, key: str, default: Any = None) -> Any:
        """parameterByKey (reference AppParameters.php:35-44)."""
        return self._params.get(key, default)

    def add(self, key: str, value: Any) -> None:
        self._params[key] = value

    def as_dict(self) -> Dict[str, Any]:
        return dict(self._params)
