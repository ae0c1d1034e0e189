"""The system under test: ``ImageHandler.transform_bytes`` behind the two
controllers, built the way ``flyimg_tpu/bulk.py`` builds them.

This module and the warmers (``warmers/<name>.py``, which build the programs
a configuration's traffic will use before the first request) are the two
places of the benchmark that import the program. From it the benchmark takes
the entry point, the program's counters (as Prometheus text), its per-image
``timings`` and the names of its launches; every yardstick (traffic,
reference, comparison, trace reduction, peaks) is the benchmark's own.
"""

from __future__ import annotations

import re
import threading
import time
from typing import Any, Dict, Optional, Tuple

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


def options_bag(config: Dict[str, Any], params: Any):
    """The program's own reading of the configuration's options string, made
    anew for each call as a request's is."""
    from flyimg_tpu.spec.options import OptionsBag

    return OptionsBag(
        str(config["options"]["url"]), options_keys=params.by_key("options_keys"),
        default_options=params.by_key("default_options"),
        separator=params.by_key("options_separator", ","),
    )


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
        self.extension = str(config["output"]["extension"])

    # -- the timed entry ------------------------------------------------

    def transform(self, data: bytes) -> Tuple[bytes, Dict[str, float]]:
        from flyimg_tpu.service.output_image import EXT_TO_MIME, OutputSpec

        options = options_bag(self.config, self.params)
        spec = OutputSpec(name=f"bench.{self.extension}", extension=self.extension,
                          mime=EXT_TO_MIME[self.extension])
        timings: Dict[str, float] = {}
        return self.handler.transform_bytes(data, options, spec, timings), timings

    # -- what the benchmark reads from the program ------------------------

    def counters(self) -> Dict[str, float]:
        return parse_prometheus(self.metrics.render_prometheus())

    def close(self) -> None:
        self.codec_batcher.close()
        self.batcher.close()
