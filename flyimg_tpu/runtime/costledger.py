"""Per-plan XLA cost ledger: compile-time cost analysis + cumulative
device accounting, keyed by program identity.

PRs 2/4 left device accounting at one lump-sum ``flyimg_device_seconds``
histogram — enough to see "the device is busy", useless for *attributing*
that time to a plan. The ROADMAP's next frontier (promote the banded
K-tap resample, overhaul the host codec path) needs exactly that
attribution: a 30x MAC-cut kernel swap must be provable in the serving
path as "this program's FLOPs dropped 30x and its cumulative device
seconds followed", not only in an offline experiment ("Beyond
Inference", arXiv 2403.12981: measure per stage or the wins hide).

This module is the accounting spine:

- ``ops/compose.py`` compiles every device program through the AOT API
  (``jit(...).lower(...).compile()`` — ``ProgramHandle``) and records the
  compiled program's ``cost_analysis()`` (FLOPs, bytes accessed) and
  ``memory_analysis()`` (peak device memory estimate) here, along with
  the measured compile wall time. Backends whose analysis calls return
  nothing or raise produce an entry with **nulled cost fields** — the ledger never turns a cost-analysis quirk into a
  serving failure (pinned by tests/test_costledger.py).
- The batch runtime (``runtime/batcher.py``) and the single-image path
  (``ops/compose.py run_plan``) record every launch's device seconds and
  image count against the same key.

The ledger is a process-wide singleton (like the program caches it
mirrors — programs are compiled per process, not per app);
``MetricsRegistry.summary()``, the ``flyimg_plan_*`` gauges, and the
debug-gated ``/debug/plans`` endpoint (service/app.py) read it. Bounded:
``max_entries`` entries, least-recently-launched evicted. See
docs/observability.md "Per-plan cost ledger".
"""

from __future__ import annotations

import hashlib
import threading
import time
from typing import Dict, List, Optional, Tuple

__all__ = [
    "PlanCostLedger",
    "get_ledger",
    "normalize_cost_analysis",
]

# cost_analysis() keys we carry (XLA's HloCostAnalysis vocabulary);
# anything else the backend reports rides through in `extra`
_FLOPS_KEY = "flops"
_BYTES_KEY = "bytes accessed"
_TRANSCENDENTALS_KEY = "transcendentals"


def normalize_cost_analysis(raw) -> Optional[Dict[str, float]]:
    """Normalize the backend's ``cost_analysis()`` return into one flat
    ``{flops, bytes_accessed, transcendentals}`` dict, or None when the
    backend reported nothing usable.

    The raw shape varies by jax version and backend: a list of one dict
    per computation (0.4.x), a bare dict (newer), or None (backends
    without an analysis). Sub-metric keys like ``bytes accessed0{}`` are
    ignored — the unsuffixed totals are the attribution figures."""
    if raw is None:
        return None
    if isinstance(raw, (list, tuple)):
        if not raw:
            return None
        merged: Dict[str, float] = {}
        for part in raw:
            if not isinstance(part, dict):
                continue
            for key in (_FLOPS_KEY, _BYTES_KEY, _TRANSCENDENTALS_KEY):
                if key in part:
                    merged[key] = merged.get(key, 0.0) + float(part[key])
        raw = merged
    if not isinstance(raw, dict) or not raw:
        return None
    out: Dict[str, float] = {}
    if _FLOPS_KEY in raw:
        out["flops"] = float(raw[_FLOPS_KEY])
    if _BYTES_KEY in raw:
        out["bytes_accessed"] = float(raw[_BYTES_KEY])
    if _TRANSCENDENTALS_KEY in raw:
        out["transcendentals"] = float(raw[_TRANSCENDENTALS_KEY])
    return out or None


def key_digest(key) -> str:
    """Stable short digest of a program cache key (the tuple the lru
    caches in ops/compose.py / runtime/batcher.py key on). repr is
    deterministic for the tuple-of-hashables keys those caches use, so
    the digest is stable across processes for one jax/config version —
    what lets perf_gate baselines compare per-plan cost across runs."""
    return hashlib.blake2b(
        repr(key).encode("utf-8"), digest_size=8
    ).hexdigest()


class _Entry:
    __slots__ = (
        "key", "descriptor", "flops", "bytes_accessed", "transcendentals",
        "peak_memory_bytes", "compile_s", "compiled_at", "costed",
        "devices", "launches", "images", "device_s", "last_launch_at",
    )

    def __init__(self, key: str, descriptor: Optional[Dict]) -> None:
        self.key = key
        self.descriptor = descriptor or {}
        self.flops: Optional[float] = None
        self.bytes_accessed: Optional[float] = None
        self.transcendentals: Optional[float] = None
        self.peak_memory_bytes: Optional[float] = None
        self.compile_s: Optional[float] = None
        self.compiled_at: Optional[float] = None
        self.costed = False
        self.devices: Optional[List[int]] = None
        self.launches = 0
        self.images = 0
        self.device_s = 0.0
        self.last_launch_at: Optional[float] = None

    def as_dict(self) -> Dict[str, object]:
        return {
            "key": self.key,
            "descriptor": dict(self.descriptor),
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "transcendentals": self.transcendentals,
            "peak_memory_bytes": self.peak_memory_bytes,
            "compile_s": (
                round(self.compile_s, 6)
                if self.compile_s is not None else None
            ),
            "costed": self.costed,
            # ids of the devices the compiled program's inputs are laid
            # out over (one id unless the batch is mesh-sharded)
            "devices": self.devices,
            "launches": self.launches,
            "images": self.images,
            "device_s": round(self.device_s, 6),
            # per-launch attribution: what one launch of this program
            # costs, estimated — flops are per compiled call
            "flops_executed": (
                self.flops * self.launches if self.flops is not None else None
            ),
            "bytes_executed": (
                self.bytes_accessed * self.launches
                if self.bytes_accessed is not None else None
            ),
        }


class PlanCostLedger:
    """Bounded, thread-safe per-program cost/usage table."""

    def __init__(self, max_entries: int = 256) -> None:
        self._lock = threading.Lock()
        self._max_entries = max(8, int(max_entries))
        self._entries: Dict[str, _Entry] = {}
        # since-boot aggregates survive entry eviction: the totals the
        # flyimg_plan_* gauges export must not dip when the table prunes
        self._total_compile_s = 0.0
        self._total_compiles = 0
        self._total_uncosted = 0
        self._total_flops_executed = 0.0
        self._total_bytes_executed = 0.0
        self._total_device_s = 0.0

    def configure(self, *, max_entries: Optional[int] = None) -> None:
        """Re-bound the table (service/app.py applies the
        ``costledger_max_entries`` knob; the singleton predates config)."""
        if max_entries is not None:
            with self._lock:
                self._max_entries = max(8, int(max_entries))
                self._evict_locked()

    # -- recording ---------------------------------------------------------

    def record_compile(
        self,
        key,
        *,
        descriptor: Optional[Dict] = None,
        compile_s: Optional[float] = None,
        cost: Optional[Dict[str, float]] = None,
        peak_memory_bytes: Optional[float] = None,
        devices: Optional[List[int]] = None,
    ) -> str:
        """One program compiled (``cost`` already normalized; None =
        the backend reported nothing — the entry still exists, with
        nulled cost fields). Returns the entry's key digest."""
        digest = key if isinstance(key, str) else key_digest(key)
        with self._lock:
            entry = self._entries.get(digest)
            if entry is None:
                entry = _Entry(digest, descriptor)
                self._entries[digest] = entry
            elif descriptor:
                entry.descriptor = dict(descriptor)
            if cost:
                entry.flops = cost.get("flops")
                entry.bytes_accessed = cost.get("bytes_accessed")
                entry.transcendentals = cost.get("transcendentals")
                entry.costed = entry.flops is not None
            if not entry.costed:
                self._total_uncosted += 1
            entry.peak_memory_bytes = peak_memory_bytes
            entry.devices = devices
            entry.compile_s = compile_s
            entry.compiled_at = time.time()
            self._total_compiles += 1
            if compile_s is not None:
                self._total_compile_s += float(compile_s)
            self._evict_locked()
        return digest

    def record_launch(self, key, *, device_s: Optional[float],
                      images: int = 0) -> None:
        """One launch of a program: cumulative device seconds + image
        count. Creates a (cost-less) entry when the compile record was
        evicted — usage accounting must not depend on table residency."""
        digest = key if isinstance(key, str) else key_digest(key)
        with self._lock:
            entry = self._entries.get(digest)
            if entry is None:
                entry = _Entry(digest, None)
                self._entries[digest] = entry
            entry.launches += 1
            entry.images += int(images)
            if device_s is not None:
                entry.device_s += float(device_s)
                self._total_device_s += float(device_s)
            entry.last_launch_at = time.time()
            if entry.flops is not None:
                self._total_flops_executed += entry.flops
            if entry.bytes_accessed is not None:
                self._total_bytes_executed += entry.bytes_accessed
            # evict AFTER stamping last_launch_at: a just-created entry
            # (fresh launch for an evicted compile record) must not sort
            # as least-recently-launched and evict itself on the spot
            self._evict_locked()

    def _evict_locked(self) -> None:
        while len(self._entries) > self._max_entries:
            # least-recently-launched goes first; never-launched entries
            # sort by compile time (oldest compile first)
            victim = min(
                self._entries.values(),
                key=lambda e: (
                    e.last_launch_at or e.compiled_at or 0.0
                ),
            )
            del self._entries[victim.key]

    # -- read surface ------------------------------------------------------

    def peak_memory(self, key) -> Optional[float]:
        """The backend's ``memory_analysis()`` peak estimate for one
        program, or None when the program never compiled (or its entry
        was evicted, or the backend reported nothing). The memory
        governor (runtime/memgovernor.py) consults this before launch to
        predict whether a batch fits the device budget."""
        digest = key if isinstance(key, str) else key_digest(key)
        with self._lock:
            entry = self._entries.get(digest)
            if entry is None:
                return None
            return entry.peak_memory_bytes

    def entries(self) -> List[Dict[str, object]]:
        with self._lock:
            rows = [e.as_dict() for e in self._entries.values()]
        rows.sort(key=lambda r: r["device_s"], reverse=True)
        return rows

    def aggregates(self) -> Dict[str, float]:
        """Since-boot totals — the flyimg_plan_* gauge callbacks and the
        ``summary()`` fold. Peak memory is the max across live entries
        (an estimate of the largest single program's working set)."""
        with self._lock:
            peak = max(
                (
                    e.peak_memory_bytes for e in self._entries.values()
                    if e.peak_memory_bytes is not None
                ),
                default=0.0,
            )
            return {
                "entries": float(len(self._entries)),
                "compiles": float(self._total_compiles),
                "compile_seconds": self._total_compile_s,
                "uncosted": float(self._total_uncosted),
                "flops_executed": self._total_flops_executed,
                "bytes_executed": self._total_bytes_executed,
                "device_seconds": self._total_device_s,
                "peak_memory_bytes": peak,
            }

    def snapshot(self, limit: int = 64) -> Dict[str, object]:
        """The /debug/plans JSON document: per-plan rows (by cumulative
        device seconds, descending) + the since-boot aggregates."""
        rows = self.entries()
        truncated = max(len(rows) - int(limit), 0)
        return {
            "plans": rows[: int(limit)],
            "truncated": truncated,
            "aggregates": self.aggregates(),
        }

    def register_metrics(self, registry) -> None:
        """Export the flyimg_plan_* family as render-time gauge
        callbacks on an app's registry (the ledger is process-wide, the
        registry per-app — callbacks keep them decoupled)."""
        registry.gauge(
            "flyimg_plan_entries",
            "Device programs tracked by the per-plan cost ledger",
            fn=lambda: self.aggregates()["entries"],
        )
        registry.gauge(
            "flyimg_plan_compile_seconds",
            "Cumulative wall time spent compiling device programs",
            fn=lambda: self.aggregates()["compile_seconds"],
        )
        registry.gauge(
            "flyimg_plan_flops_executed",
            "Estimated FLOPs executed through costed device programs",
            fn=lambda: self.aggregates()["flops_executed"],
        )
        registry.gauge(
            "flyimg_plan_bytes_executed",
            "Estimated bytes accessed by costed device programs",
            fn=lambda: self.aggregates()["bytes_executed"],
        )
        registry.gauge(
            "flyimg_plan_peak_memory_bytes",
            "Largest per-program peak device memory estimate in the ledger",
            fn=lambda: self.aggregates()["peak_memory_bytes"],
        )
        registry.gauge(
            "flyimg_plan_uncosted",
            "Compiles whose backend returned no usable cost analysis",
            fn=lambda: self.aggregates()["uncosted"],
        )


# process-wide singleton: programs (and their costs) are per-process
# state like the lru program caches; apps attach gauges to it
_LEDGER = PlanCostLedger()


def get_ledger() -> PlanCostLedger:
    return _LEDGER
