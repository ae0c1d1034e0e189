"""On-demand device profiling: arm a jax.profiler trace for the next N
device batches, over HTTP, without redeploying.

The existing ``/debug/trace`` endpoint captures *wall time* — whatever
happens to run during its sleep — which under sparse traffic is mostly
idle. This module captures *work*: arming sets a batch budget, the trace
starts at the next device-batch dispatch and stops after N batches (or a
deadline, whichever first), so one curl during a hardware window yields
a device timeline of exactly the launches that matter, each already
labeled ``flyimg:batch:<id>`` by the batcher's TraceAnnotation.

Contract:

- one concurrent capture, process-wide (``jax.profiler`` is global
  state); arming while armed/active answers busy.
- bounded: batch budget capped by ``profiling_max_batches``, duration by
  ``profiling_max_seconds`` (a watchdog stops an armed-but-idle or
  wedged capture).
- captures land under ``profiling_dir`` (default
  ``<tmp_dir>/profiles``), listed and downloadable (tar.gz) from the
  debug-gated ``/debug/profile`` routes (service/app.py; 404 when
  ``debug`` is off).

The batcher calls ``on_batch_start``/``on_batch_end`` around every
device launch; both are a single attribute check when no capture is
armed — the hot path stays free. See docs/observability.md "On-demand
device profiling".
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Dict, List, Optional

__all__ = ["DeviceProfiler"]

PROFILER_LOGGER = "flyimg.profiler"


class DeviceProfiler:
    """Batch-scoped jax.profiler capture with a single-flight arm."""

    def __init__(
        self,
        *,
        base_dir: str,
        max_batches: int = 16,
        max_seconds: float = 30.0,
        metrics=None,
    ) -> None:
        self.base_dir = base_dir
        self.max_batches = max(1, int(max_batches))
        self.max_seconds = max(1.0, float(max_seconds))
        self._metrics = metrics
        self._lock = threading.Lock()
        # `_armed` doubles as the hot-path gate: on_batch_start/end read
        # it unlocked (a stale read costs one lock round at worst)
        self._armed = False
        self._active = False          # start_trace has run
        self._remaining = 0
        self._capture_id = 0
        self._capture_dir: Optional[str] = None
        self._deadline = 0.0
        self._captures_total = 0
        self._last_error: Optional[str] = None

    @classmethod
    def from_params(cls, params, *, metrics=None) -> "DeviceProfiler":
        base_dir = str(params.by_key("profiling_dir", "") or "")
        if not base_dir:
            base_dir = os.path.join(
                str(params.by_key("tmp_dir", "var/tmp")), "profiles"
            )
        return cls(
            base_dir=base_dir,
            max_batches=int(params.by_key("profiling_max_batches", 16)),
            max_seconds=float(params.by_key("profiling_max_seconds", 30.0)),
            metrics=metrics,
        )

    # -- arming ------------------------------------------------------------

    @property
    def busy(self) -> bool:
        """True while a capture is armed or running — the /debug/trace
        wall-clock endpoint refuses (409) while this holds, since both
        drive the one global jax profiler."""
        with self._lock:
            return self._armed or self._active

    def arm(self, batches: int,
            max_s: Optional[float] = None) -> Dict[str, object]:
        """Arm a capture of the next ``batches`` device batches. Returns
        the armed-state doc; raises RuntimeError when a capture is
        already armed or running (single concurrent capture)."""
        batches = max(1, min(int(batches), self.max_batches))
        duration = min(
            float(max_s) if max_s else self.max_seconds, self.max_seconds
        )
        with self._lock:
            if self._armed or self._active:
                raise RuntimeError("a profiler capture is already in flight")
            self._capture_id += 1
            capture_id = self._capture_id
            self._armed = True
            self._active = False
            self._remaining = batches
            self._deadline = time.monotonic() + duration
            self._capture_dir = os.path.join(
                self.base_dir, time.strftime("capture-%Y%m%d-%H%M%S")
            )
            self._last_error = None
        # the watchdog bounds an armed-but-idle (no batches arrive) or
        # wedged capture; started OUTSIDE the lock (thread start blocks)
        threading.Thread(
            target=self._watchdog,
            args=(capture_id, duration),
            name="flyimg-profiler-watchdog",
            daemon=True,
        ).start()
        logging.getLogger(PROFILER_LOGGER).info(
            "profiler armed for %d batches (max %.1fs) -> %s",
            batches, duration, self._capture_dir,
        )
        return self.snapshot()

    def _watchdog(self, capture_id: int, duration: float) -> None:
        time.sleep(duration)
        self._finish(capture_id, "deadline")

    # -- batcher hooks (hot path) -----------------------------------------

    def on_batch_start(self) -> None:
        """Called by the batcher before every device dispatch. Starts
        the armed capture on the first batch. Never raises — a profiler
        failure must not take a batch down with it."""
        if not self._armed:
            return
        with self._lock:
            if not self._armed or self._active:
                return
            capture_dir = self._capture_dir
            try:
                import jax

                os.makedirs(capture_dir, exist_ok=True)
                jax.profiler.start_trace(capture_dir)
            except Exception as exc:
                # e.g. another profiler session (the /debug/trace
                # endpoint) owns the global profiler state
                self._armed = False
                self._remaining = 0
                self._last_error = f"{type(exc).__name__}: {exc}"
                logging.getLogger(PROFILER_LOGGER).warning(
                    "profiler start_trace failed: %s", exc
                )
                return
            self._active = True

    def on_batch_end(self) -> None:
        """Called by the batcher after every completed device readback;
        stops the capture when the batch budget is spent."""
        if not self._active:
            return
        capture_id = None
        with self._lock:
            if not self._active:
                return
            self._remaining -= 1
            if self._remaining <= 0:
                capture_id = self._capture_id
        if capture_id is not None:
            self._finish(capture_id, "batch_budget")

    def _finish(self, capture_id: int, reason: str) -> None:
        with self._lock:
            if self._capture_id != capture_id or not (
                self._armed or self._active
            ):
                return  # a newer capture owns the profiler, or already done
            was_active = self._active
            self._armed = False
            self._active = False
            self._remaining = 0
            capture_dir = self._capture_dir
        if not was_active:
            logging.getLogger(PROFILER_LOGGER).info(
                "profiler disarmed before any batch arrived (%s)", reason
            )
            return
        try:
            import jax

            jax.profiler.stop_trace()
        except Exception as exc:
            with self._lock:
                self._last_error = f"{type(exc).__name__}: {exc}"
            logging.getLogger(PROFILER_LOGGER).warning(
                "profiler stop_trace failed: %s", exc
            )
            return
        with self._lock:
            self._captures_total += 1
        if self._metrics is not None:
            self._metrics.counter(
                "flyimg_profiler_captures_total",
                "Completed on-demand device-profile captures",
            ).inc()
        logging.getLogger(PROFILER_LOGGER).info(
            "profiler capture complete (%s) -> %s", reason, capture_dir,
            extra={
                "event": "profiler.capture",
                "reason": reason,
                "capture_dir": capture_dir,
            },
        )

    # -- read surface ------------------------------------------------------

    def captures(self) -> List[Dict[str, object]]:
        """Completed capture directories under base_dir, newest first."""
        try:
            names = sorted(
                (
                    n for n in os.listdir(self.base_dir)
                    if n.startswith("capture-")
                ),
                reverse=True,
            )
        except OSError:
            return []
        out = []
        for name in names:
            path = os.path.join(self.base_dir, name)
            size = 0
            for root, _dirs, files in os.walk(path):
                for fname in files:
                    try:
                        size += os.path.getsize(os.path.join(root, fname))
                    except OSError:
                        pass
            out.append({"name": name, "bytes": size})
        return out

    def capture_path(self, name: str) -> Optional[str]:
        """Resolve one listed capture name to its directory — names are
        validated against the actual listing, so a crafted path segment
        cannot escape base_dir."""
        if any(c["name"] == name for c in self.captures()):
            return os.path.join(self.base_dir, name)
        return None

    def snapshot(self) -> Dict[str, object]:
        with self._lock:
            state = {
                "armed": self._armed,
                "active": self._active,
                "remaining_batches": self._remaining,
                "capture_dir": (
                    self._capture_dir
                    if (self._armed or self._active) else None
                ),
                "captures_total": self._captures_total,
                "last_error": self._last_error,
                "max_batches": self.max_batches,
                "max_seconds": self.max_seconds,
            }
        state["captures"] = self.captures()
        return state
