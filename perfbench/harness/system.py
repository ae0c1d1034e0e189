"""The system under test: ``ImageHandler.transform_bytes`` behind the two
controllers, built the way ``flyimg_tpu/bulk.py`` builds them.

This is the only module of the benchmark that imports the program. From it
the benchmark takes the entry point, the program's counters (as Prometheus
text), its per-image ``timings`` and the names of its launches; every
yardstick (traffic, reference, comparison, trace reduction, peaks) is the
benchmark's own.
"""

from __future__ import annotations

import re
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np

_SAMPLE = re.compile(r"^([A-Za-z_:][A-Za-z0-9_:]*)(\{[^}]*\})?\s+(\S+)$")


def parse_prometheus(text: str) -> Dict[str, float]:
    """Prometheus text exposition -> ``{"name{labels}": value}``."""
    out: Dict[str, float] = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE.match(line.strip())
        if m:
            try:
                out[m.group(1) + (m.group(2) or "")] = float(m.group(3))
            except ValueError:
                continue
    return out


def device_info(chips: int, require_chip: bool = True) -> Dict[str, Any]:
    """The devices as JAX reports them. Raises where there is no accelerator
    or fewer chips than the cell asks for: a measurement never falls back."""
    import jax

    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    if require_chip:
        if info["platform"] == "cpu":
            raise RuntimeError("JAX found no accelerator; the benchmark does not run on the CPU")
        if info["count"] < chips:
            raise RuntimeError(f"the cell asks for {chips} chip(s), JAX has {info['count']}")
    return info


def memory_peak_bytes() -> Optional[int]:
    """Peak bytes in use on the fullest device, where the backend says."""
    import jax

    peaks = []
    for dev in jax.devices():
        stats = dev.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts the programs JAX builds: every pass through the backend compile
    (a read from the persistent cache counts too) and, apart, the reads that
    hit the cache. A run shows with it that nothing was built inside its
    window, and that a second run found every program in the cache."""

    BUILD = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        import jax.monitoring

        self.count = 0
        self.hits = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_: Any) -> None:
        if event == self.BUILD:
            with self._lock:
                self.count += 1

    def _on_event(self, event: str, **_: Any) -> None:
        if event == self.HIT:
            with self._lock:
                self.hits += 1


class System:
    """Handler + device controller + host-codec controller for one
    configuration. ``transform`` is the timed entry."""

    def __init__(self, config: Dict[str, Any]) -> None:
        from flyimg_tpu import compilecache
        from flyimg_tpu.appconfig import AppParameters
        from flyimg_tpu.ops.resample import set_kernel_mode
        from flyimg_tpu.runtime.batcher import BatchController, containment_params
        from flyimg_tpu.runtime.metrics import MetricsRegistry
        from flyimg_tpu.service.handler import ImageHandler

        self.config = config
        self.cache_dir = compilecache.enable_compile_cache()
        self.compiles = CompileCounter()
        params = AppParameters(dict(config.get("parameters") or {}))
        self.params = params
        set_kernel_mode(str(params.by_key("resample_kernel", "dense")))
        containment = containment_params(params)
        self.metrics = MetricsRegistry()
        self.batcher = BatchController(
            max_batch=int(params.by_key("batch_max_size", 64)),
            deadline_ms=float(params.by_key("batch_deadline_ms", 4.0)),
            pipeline_depth=int(params.by_key("batch_pipeline_depth", 2)),
            metrics=self.metrics,
            **containment,
        )
        self.codec_batcher = BatchController(
            max_batch=int(params.by_key("decode_batch_max", 32)),
            deadline_ms=float(params.by_key("decode_deadline_ms", 1.0)),
            name="codec",
            **containment,
        )
        self.handler = ImageHandler(
            storage=None, params=params, batcher=self.batcher,
            codec_batcher=self.codec_batcher, metrics=self.metrics,
        )
        self._options_keys = params.by_key("options_keys")
        self._default_options = params.by_key("default_options")
        self._separator = params.by_key("options_separator", ",")
        self.options_str = str(config["options"]["url"])
        self.extension = str(config["output"]["extension"])

    # -- the timed entry ------------------------------------------------

    def transform(self, data: bytes) -> Tuple[bytes, Dict[str, float]]:
        from flyimg_tpu.service.output_image import EXT_TO_MIME, OutputSpec
        from flyimg_tpu.spec.options import OptionsBag

        options = OptionsBag(
            self.options_str, options_keys=self._options_keys,
            default_options=self._default_options, separator=self._separator,
        )
        spec = OutputSpec(name=f"bench.{self.extension}", extension=self.extension,
                          mime=EXT_TO_MIME[self.extension])
        timings: Dict[str, float] = {}
        return self.handler.transform_bytes(data, options, spec, timings), timings

    # -- set-up ---------------------------------------------------------

    def _group_args(self, width: int, height: int):
        """The arguments ``BatchController.submit`` derives for a full
        ``width x height`` frame under this configuration's options: what
        keys the batched program. Mirrors ``submit`` with the program's own
        helpers; if it ever drifts, the pre-roll compiles and the run says
        so (``compiles_in_preroll``)."""
        from flyimg_tpu.ops.compose import _bucket_dim, plan_layout
        from flyimg_tpu.ops.resample import kernel_mode, select_band_taps
        from flyimg_tpu.spec.options import OptionsBag
        from flyimg_tpu.spec.plan import build_plan

        options = OptionsBag(
            self.options_str, options_keys=self._options_keys,
            default_options=self._default_options, separator=self._separator,
        )
        plan = build_plan(options, width, height)
        layout = plan_layout(plan)
        in_shape = (_bucket_dim(height), _bucket_dim(width))
        if plan.extent is not None:
            resample_out = layout.resample_out
        else:
            resample_out = (_bucket_dim(layout.resample_out[0], 64),
                            _bucket_dim(layout.resample_out[1], 64))
        band = select_band_taps(kernel_mode(), plan.filter_method, in_shape,
                                layout.span_y, layout.span_x, layout.out_true)
        return plan, layout, in_shape, resample_out, band

    def warm_programs(self, width: int, height: int, sizes: Sequence[int]) -> Dict[str, Any]:
        """Compile (or read from the cache) the batched program of every
        launch size in ``sizes`` without running it, several at once: a
        first request must never wait on a compile longer than the program's
        own time limits."""
        import jax
        from flyimg_tpu.runtime.batcher import build_batched_program

        plan, layout, in_shape, resample_out, band = self._group_args(width, height)

        def one(batch: int) -> float:
            t = time.perf_counter()
            handle = build_batched_program(
                batch, in_shape, resample_out, layout.pad_canvas,
                layout.pad_offset, plan.device_plan(), None, False, band,
            )
            f32 = np.float32
            handle.precompile((
                jax.ShapeDtypeStruct((batch,) + in_shape + (3,), np.uint8),
                jax.ShapeDtypeStruct((batch, 2), f32),
                jax.ShapeDtypeStruct((batch, 2), f32),
                jax.ShapeDtypeStruct((batch, 2), f32),
                jax.ShapeDtypeStruct((batch, 2), f32),
            ))
            return time.perf_counter() - t

        with ThreadPoolExecutor(max_workers=max(len(sizes), 1)) as pool:
            seconds = list(pool.map(one, sizes))
        return {"in_shape": list(in_shape), "resample_out": list(resample_out),
                "seconds": dict(zip(map(str, sizes), seconds))}

    # -- what the benchmark reads from the program ------------------------

    def counters(self) -> Dict[str, float]:
        return parse_prometheus(self.metrics.render_prometheus())

    def close(self) -> None:
        self.codec_batcher.close()
        self.batcher.close()
