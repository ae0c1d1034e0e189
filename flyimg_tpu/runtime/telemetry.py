"""Telemetry warehouse: a crash-safe, append-only signal archive plus
the deterministic traffic-mix classifier (docs/observability.md
"Telemetry warehouse & traffic-mix classifier").

Every observability plane built so far — SLO windows, the flight
recorder, the cost ledger, the observatory's signal windows — lives in
bounded in-memory rings that vanish on restart, while ROADMAP item 4's
global pipeline planner needs durable traces per traffic mix to search
over. This module closes that gap:

- ``TelemetryArchive``: JSONL segment files under ``telemetry_dir``
  (default ``<tmp_dir>/telemetry``), rotated by size and age, bounded
  by a total-retention policy that evicts oldest-first, with
  corrupt-tail recovery on open — a torn last line (mid-write crash)
  is truncated and counted, never a boot failure. Flight-recorder dump
  files share the same retention family (one ``telemetry_retention_*``
  knob set instead of the separate ``flightrecorder_max_dumps`` path).
- ``TrafficMixClassifier``: a windowed fingerprint over plan-family
  shares, the size-bucket ladder, per-source size fan-out, and
  hit/miss/reuse/degraded ratios, classified by nearest centroid among
  ``thumbnail | cropzoom | multisize | panzoom | mixed`` with
  hysteresis so the adopted label cannot flap on one odd window.
- ``TelemetryPipeline``: the beat that rides the request middleware
  (rate-limited by ``telemetry_snapshot_interval_s``, exactly like
  ``brownout.evaluate()``) and snapshots the existing signal
  vocabulary — SignalWindow digests, per-launch flight-recorder
  records, cost-ledger deltas, SLO burn, brownout level — into one
  archive timeline, stamping the current mix label into every window
  record.

Everything here is default-off: with ``telemetry_enable`` unset there
is no directory, no metrics family, no per-request work beyond one
``is None`` check in the handler — pinned byte-identical by
``tests/test_telemetry.py``. The archive's record vocabulary is
declared in ``RECORD_SCHEMAS`` and enforced both at emit time (unknown
fields are dropped + counted, never written) and statically by
flylint's telemetry-schema-parity rule against the documented record
table (docs/observability.md).

Consumers: the debug-gated ``/debug/telemetry`` endpoint and
``tools/telemetry_query.py`` (windows / mix-report / burn-timeline /
export).
"""

from __future__ import annotations

import json
import logging
import math
import os
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

TELEMETRY_LOGGER = "flyimg.telemetry"

#: bumped when a record kind gains/loses fields in a way readers must
#: know about; every record carries it so an archive written by an old
#: process replays correctly under a new reader
SCHEMA_VERSION = 1

#: the archive's full record vocabulary: kind -> allowed TOP-LEVEL
#: fields. Emit-time validation drops (and counts) anything not listed
#: here, and flylint's telemetry-schema-parity rule keeps this dict and
#: the documented record table (docs/observability.md "Archive record
#: schema") in lockstep, both directions — a field added in code but
#: not documented (or vice versa) fails the scan.
RECORD_SCHEMAS: Dict[str, Tuple[str, ...]] = {
    # one per archive open: the recovery/continuity marker
    "boot": (
        "schema", "kind", "at_s", "replica", "segment",
        "torn_recovered", "segments", "archive_bytes",
    ),
    # one per beat: the SignalWindow digest + SLO/brownout/ledger deltas
    # + the traffic-mix stamp (controllers/host are embedded verbatim)
    "window": (
        "schema", "kind", "at_s", "replica", "window_s",
        "controllers", "host", "kernel_mode",
        "burn_fast_norm", "burn_slow_norm", "brownout_level",
        "slo", "reuse", "ledger_delta",
        "requests_delta", "hits_delta", "misses_delta", "degraded_delta",
        "mix", "mix_raw", "mix_distance", "mix_features", "mix_samples",
        "segments", "archive_bytes",
    ),
    # one per device/codec/host-stage launch, drained from the flight
    # recorder ring by seq (``kind``/``seq`` are renamed ``launch_kind``/
    # ``launch_seq`` so they cannot collide with the archive envelope)
    "launch": (
        "schema", "kind", "at_s", "replica",
        "controller", "batch_id", "plan_key", "occupancy", "capacity",
        "queue_wait_s", "h2d_s", "dispatch_s", "sync_s", "device_s",
        "assemble_s", "slot_wait_s", "run_s", "resolve_s",
        "assemble_cpu_s", "h2d_cpu_s",
        "compile_hit", "brownout_level", "launch_kind", "stage",
        "trace_id", "error", "launch_seq",
        "predicted_bytes", "budget_bytes", "mem_event",
    ),
}

_SEGMENT_PREFIX = "telemetry-"
_SEGMENT_SUFFIX = ".jsonl"

#: the classifier's label vocabulary (gauge labels, docs, centroids)
MIX_LABELS = ("thumbnail", "cropzoom", "multisize", "panzoom", "mixed")

#: feature order of the fingerprint vector (docs/observability.md
#: "Mix feature vector"): every component normalized into [0, 1]
MIX_FEATURES = ("crop_share", "small_share", "bucket_spread",
                "source_fanout", "hit_ratio")

#: per-feature distance weights: geometry features (what the plans DO)
#: dominate; the hit ratio is a weak tie-breaker because cache state is
#: a property of history, not of the traffic shape itself
MIX_WEIGHTS = (1.0, 0.8, 0.9, 0.9, 0.4)

#: nearest-centroid table. Deterministic and documented — the planner
#: (ROADMAP item 4) keys policy tables by these labels, so they must
#: mean the same thing in every replica and every offline replay.
MIX_CENTROIDS: Dict[str, Tuple[float, ...]] = {
    # small resizes, few sizes per source, no cropping
    "thumbnail": (0.05, 0.95, 0.15, 0.10, 0.50),
    # crop/extract-dominant plans at medium sizes, low per-source fan-out
    "cropzoom": (0.90, 0.30, 0.30, 0.15, 0.40),
    # the same sources rendered at MANY sizes (srcset ladders)
    "multisize": (0.10, 0.50, 0.80, 0.80, 0.35),
    # repeated extracts panning across the same sources (tile viewers)
    "panzoom": (0.90, 0.35, 0.40, 0.80, 0.55),
}

#: a window farther than this (weighted distance) from EVERY centroid
#: is "mixed" — the honest label for traffic no single table fits
MIX_RADIUS = 0.55


def request_features(options, source_key: Optional[str]) -> Dict[str, object]:
    """The per-request mix feature tuple, extracted from the resolved
    ``OptionsBag``. Pure and cheap (dict reads + one bit_length) — it
    runs on the serving path for every outcome, including cache hits,
    so it must cost nanoseconds, not microseconds.

    ``sig`` identifies the *plan shape* (family + size bucket + the
    quantized crop window) so the classifier can count distinct shapes
    per source: a pan/zoom viewer re-rendering one source at twenty
    crop windows produces twenty sigs, a thumbnail burst one.
    """
    try:
        # OptionsBag stores raw URL strings ("w_520" -> "520"); its typed
        # accessors do the tolerant parse. Plain dicts (tests, exotic
        # callers) fall back to duck-typed reads.
        if hasattr(options, "int_option"):
            width = options.int_option("width")
            height = options.int_option("height")
        else:
            width = options.get("width")
            height = options.get("height")
        if hasattr(options, "truthy"):
            crop = options.truthy("crop")
            extract = options.truthy("extract")
        else:
            crop = bool(options.get("crop"))
            extract = options.get("extract") is not None
    except Exception:  # an exotic options bag must never fail serving
        width = height = None
        crop = extract = False
    dims = []
    for v in (width, height):
        if isinstance(v, bool) or v is None:
            continue
        try:
            dims.append(int(float(v)))
        except (TypeError, ValueError):
            continue
    max_dim = max((d for d in dims if d > 0), default=0)
    # power-of-two ladder bucket; 0 = original-size (no w/h constraint)
    bucket = min(max_dim.bit_length(), 14) if max_dim > 0 else 0
    window = ""
    if extract:
        try:
            window = ",".join(
                str(options.get(key) or "")
                for key in ("extract-top-x", "extract-top-y",
                            "extract-bottom-x", "extract-bottom-y")
            )
        except Exception:
            window = ""
    family = "crop" if (crop or extract) else "resize"
    return {
        "family": family,
        "bucket": bucket,
        "sig": f"{family}:{bucket}:{window}",
        "source": source_key or "",
    }


class TrafficMixClassifier:
    """Windowed nearest-centroid traffic-shape classification with
    hysteresis. ``record()`` is the per-request write path (one lock +
    one deque append); ``classify()`` runs on the telemetry beat only.

    The adopted label changes only after ``hysteresis`` CONSECUTIVE
    beats agree on the same new label — a single odd window (one burst
    of crops inside thumbnail traffic) proposes but does not flip.
    """

    def __init__(self, *, window: int = 256, min_samples: int = 8,
                 hysteresis: int = 2) -> None:
        self.window = max(8, int(window))
        self.min_samples = max(1, int(min_samples))
        self.hysteresis = max(1, int(hysteresis))
        self._lock = threading.Lock()
        self._requests: deque = deque(maxlen=self.window)
        self.label = "mixed"        # adopted label
        self._candidate = "mixed"   # label proposed by recent beats
        self._streak = 0
        self.transitions = 0
        self.last_raw: Optional[str] = None
        self.last_distance: Optional[float] = None
        self.last_features: Optional[Dict[str, float]] = None
        self.last_samples = 0

    def record(self, features: Dict[str, object], outcome: str) -> None:
        """One request outcome. ``outcome`` is one of ``hit`` / ``stale``
        / ``coalesced`` / ``miss`` / ``reuse`` / ``degraded`` / ``shed``.
        """
        with self._lock:
            self._requests.append((
                features.get("family"), features.get("bucket"),
                features.get("sig"), features.get("source"), outcome,
            ))

    # -- fingerprint --------------------------------------------------------

    def fingerprint(self) -> Optional[Dict[str, float]]:
        """The current window's feature vector, or None below the
        sample floor (too little evidence to call a shape)."""
        with self._lock:
            rows = list(self._requests)
        if len(rows) < self.min_samples:
            return None
        n = float(len(rows))
        crop = sum(1 for r in rows if r[0] == "crop")
        small = sum(1 for r in rows if 0 < int(r[1] or 0) <= 9)  # <=512px
        buckets = {r[1] for r in rows}
        sources = {r[3] for r in rows if r[3]}
        sigs_per_source: Dict[str, set] = {}
        for r in rows:
            if r[3]:
                sigs_per_source.setdefault(r[3], set()).add(r[2])
        if sigs_per_source:
            fanout_mean = sum(
                len(s) for s in sigs_per_source.values()
            ) / float(len(sigs_per_source))
        else:
            fanout_mean = 1.0
        hits = sum(1 for r in rows if r[4] in ("hit", "stale", "coalesced"))
        return {
            "crop_share": crop / n,
            "small_share": small / n,
            # distinct size buckets, saturating at 6 (a real srcset
            # ladder); sources without explicit dims share bucket 0
            "bucket_spread": min((len(buckets) - 1) / 5.0, 1.0),
            # mean distinct plan shapes per source, saturating at 5
            "source_fanout": min((fanout_mean - 1.0) / 4.0, 1.0)
            if sources else 0.0,
            "hit_ratio": hits / n,
        }

    @staticmethod
    def nearest(features: Dict[str, float]) -> Tuple[str, float]:
        """Weighted-Euclidean nearest centroid; ``mixed`` past
        MIX_RADIUS. Pure — tools/telemetry_query.py replays archives
        through this exact function to reproduce live labels offline."""
        vec = [float(features.get(name, 0.0)) for name in MIX_FEATURES]
        best_label, best_dist = "mixed", float("inf")
        for label, centroid in MIX_CENTROIDS.items():
            dist = math.sqrt(sum(
                (MIX_WEIGHTS[i] * (vec[i] - centroid[i])) ** 2
                for i in range(len(MIX_FEATURES))
            ))
            if dist < best_dist:
                best_label, best_dist = label, dist
        if best_dist > MIX_RADIUS:
            return "mixed", best_dist
        return best_label, best_dist

    def classify(self) -> Dict[str, object]:
        """One beat: fingerprint -> raw label -> hysteresis. Returns the
        mix block stamped into the window record; ``changed`` is True
        on the beat the ADOPTED label flipped."""
        features = self.fingerprint()
        changed = False
        previous = self.label
        if features is None:
            raw, dist = None, None
        else:
            raw, dist = self.nearest(features)
            if raw == self.label:
                self._candidate, self._streak = raw, 0
            elif raw == self._candidate:
                self._streak += 1
                if self._streak >= self.hysteresis:
                    self.label = raw
                    self._streak = 0
                    self.transitions += 1
                    changed = True
            else:
                self._candidate, self._streak = raw, 1
                if self.hysteresis <= 1:
                    self.label = raw
                    self.transitions += 1
                    changed = True
        self.last_raw = raw
        self.last_distance = dist
        self.last_features = features
        self.last_samples = len(self._requests)
        return {
            "label": self.label,
            "raw": raw,
            "distance": round(dist, 4) if dist is not None else None,
            "features": (
                {k: round(v, 4) for k, v in features.items()}
                if features else None
            ),
            "samples": self.last_samples,
            "changed": changed,
            "previous": previous,
        }

    def snapshot(self) -> Dict[str, object]:
        return {
            "label": self.label,
            "raw": self.last_raw,
            "distance": self.last_distance,
            "features": self.last_features,
            "samples": self.last_samples,
            "transitions": self.transitions,
            "hysteresis": self.hysteresis,
            "window": self.window,
        }


class TelemetryArchive:
    """Append-only JSONL segment store with rotation, bounded retention,
    and corrupt-tail recovery.

    Layout: ``<dir>/telemetry-<seq>.jsonl``, strictly increasing
    ``seq``; the newest segment is the only writable one. Writers
    append one ``\\n``-terminated JSON object per record and flush — a
    crash can tear at most the final line, and ``_recover_tail`` on the
    next open truncates exactly that line (counted in the boot record,
    never a boot failure).

    Thread-safe; the wall clock is injectable (``clock``) because
    record timestamps are compared across processes and restarts, the
    same reasoning as the membership marker clocks.
    """

    def __init__(self, directory: str, *,
                 segment_max_bytes: int = 1 << 20,
                 segment_max_age_s: float = 300.0,
                 retention_max_bytes: int = 32 << 20,
                 retention_max_segments: int = 64,
                 clock: Optional[Callable[[], float]] = None,
                 replica_id: str = "") -> None:
        self.directory = directory
        self.segment_max_bytes = max(4096, int(segment_max_bytes))
        self.segment_max_age_s = max(1.0, float(segment_max_age_s))
        self.retention_max_bytes = max(
            self.segment_max_bytes, int(retention_max_bytes)
        )
        self.retention_max_segments = max(2, int(retention_max_segments))
        self.clock = clock or time.time
        self.replica_id = replica_id
        self._lock = threading.Lock()
        self._fh = None
        self._segment_name = ""
        self._segment_bytes = 0
        self._segment_opened_at = 0.0
        self.torn_recovered = 0
        self.rotations = 0
        self.evicted_segments = 0
        self.records_written: Dict[str, int] = {}
        self.dropped_fields = 0
        os.makedirs(self.directory, exist_ok=True)
        self._open_newest()

    # -- segment lifecycle --------------------------------------------------

    def _segment_files(self) -> List[str]:
        try:
            names = [
                n for n in os.listdir(self.directory)
                if n.startswith(_SEGMENT_PREFIX)
                and n.endswith(_SEGMENT_SUFFIX)
            ]
        except OSError:
            return []
        return sorted(names)  # zero-padded seq => lexicographic == numeric

    @staticmethod
    def _segment_seq(name: str) -> int:
        try:
            return int(name[len(_SEGMENT_PREFIX):-len(_SEGMENT_SUFFIX)])
        except ValueError:
            return 0

    def _segment_path(self, name: str) -> str:
        return os.path.join(self.directory, name)

    def _recover_tail(self, path: str) -> None:
        """Truncate a torn (unterminated or unparseable) final line.
        Only the last line can be damaged by an append crash; anything
        earlier that fails to parse is left for readers to skip."""
        try:
            size = os.path.getsize(path)
        except OSError:
            return
        if size == 0:
            return
        with open(path, "rb+") as fh:
            fh.seek(max(0, size - 1))
            if fh.read(1) == b"\n":
                # terminated — but the final LINE may still be garbage
                # from a torn overwrite; verify it parses
                fh.seek(0)
                data = fh.read()
                end = len(data) - 1
                start = data.rfind(b"\n", 0, end) + 1
                try:
                    json.loads(data[start:end + 1].decode("utf-8"))
                    return
                except (ValueError, UnicodeDecodeError):
                    fh.truncate(start)
                    self.torn_recovered += 1
                    return
            fh.seek(0)
            data = fh.read()
            cut = data.rfind(b"\n") + 1
            fh.truncate(cut)
            self.torn_recovered += 1

    def _open_newest(self) -> None:
        segments = self._segment_files()
        if segments:
            newest = segments[-1]
            self._recover_tail(self._segment_path(newest))
            size = 0
            try:
                size = os.path.getsize(self._segment_path(newest))
            except OSError:
                pass
            if size < self.segment_max_bytes:
                self._segment_name = newest
                self._segment_bytes = size
                # a pre-existing segment's age runs from its mtime; if
                # that is unreadable, start the age clock now
                try:
                    self._segment_opened_at = os.path.getmtime(
                        self._segment_path(newest)
                    )
                except OSError:
                    self._segment_opened_at = self.clock()
                self._fh = open(
                    self._segment_path(newest), "a", encoding="utf-8"
                )
                return
        self._start_segment(
            (self._segment_seq(segments[-1]) + 1) if segments else 1
        )

    def _start_segment(self, seq: int) -> None:
        if self._fh is not None:
            try:
                self._fh.close()
            except OSError:
                pass
        name = f"{_SEGMENT_PREFIX}{seq:08d}{_SEGMENT_SUFFIX}"
        self._segment_name = name
        self._segment_bytes = 0
        self._segment_opened_at = self.clock()
        self._fh = open(self._segment_path(name), "a", encoding="utf-8")

    def _rotate_locked(self) -> None:
        self.rotations += 1
        self._start_segment(self._segment_seq(self._segment_name) + 1)
        self._enforce_retention_locked()

    def _enforce_retention_locked(self) -> None:
        """Oldest-first eviction of CLOSED segments until both the byte
        and count bounds hold (the writable segment never evicts)."""
        segments = self._segment_files()
        closed = [n for n in segments if n != self._segment_name]
        sizes = {}
        for name in segments:
            try:
                sizes[name] = os.path.getsize(self._segment_path(name))
            except OSError:
                sizes[name] = 0
        total = sum(sizes.values())
        while closed and (
            total > self.retention_max_bytes
            or len(closed) + 1 > self.retention_max_segments
        ):
            victim = closed.pop(0)
            try:
                os.unlink(self._segment_path(victim))
            except OSError:
                pass
            total -= sizes.get(victim, 0)
            self.evicted_segments += 1

    # -- the write path -----------------------------------------------------

    def append(self, kind: str, fields: Dict[str, object]) -> bool:
        """Append one schema-validated record. Unknown kinds are
        refused; unknown top-level fields are dropped and counted —
        the archive's vocabulary is RECORD_SCHEMAS, nothing else ever
        reaches disk. Returns True when a line was written (IO errors
        are absorbed: telemetry must never fail a request)."""
        allowed = RECORD_SCHEMAS.get(kind)
        if allowed is None:
            return False
        record: Dict[str, object] = {
            "schema": SCHEMA_VERSION,
            "kind": kind,
            "at_s": round(self.clock(), 3),
            "replica": self.replica_id or None,
        }
        for key, value in fields.items():
            if key in allowed:
                record[key] = value
            else:
                self.dropped_fields += 1
        line = json.dumps(record, separators=(",", ":")) + "\n"
        with self._lock:
            if self._fh is None:
                return False
            now = self.clock()
            if now - self._segment_opened_at >= self.segment_max_age_s:
                self._rotate_locked()
            try:
                self._fh.write(line)
                self._fh.flush()
            except (OSError, ValueError):
                return False
            self._segment_bytes += len(line.encode("utf-8"))
            self.records_written[kind] = (
                self.records_written.get(kind, 0) + 1
            )
            if self._segment_bytes >= self.segment_max_bytes:
                self._rotate_locked()
        return True

    # -- read/inspect -------------------------------------------------------

    def total_bytes(self) -> int:
        total = 0
        for name in self._segment_files():
            try:
                total += os.path.getsize(self._segment_path(name))
            except OSError:
                pass
        return total

    def inventory(self) -> Dict[str, object]:
        segments = self._segment_files()
        return {
            "dir": self.directory,
            "segments": segments,
            "active_segment": self._segment_name,
            "bytes": self.total_bytes(),
            "rotations": self.rotations,
            "evicted_segments": self.evicted_segments,
            "torn_recovered": self.torn_recovered,
            "records_written": dict(self.records_written),
            "dropped_fields": self.dropped_fields,
        }

    def close(self) -> None:
        with self._lock:
            if self._fh is not None:
                try:
                    self._fh.close()
                except OSError:
                    pass
                self._fh = None


def read_archive(directory: str,
                 kinds: Optional[Tuple[str, ...]] = None) -> Dict[str, object]:
    """Tolerant archive reader shared by tools/telemetry_query.py and
    the tests: records in SEGMENT + LINE order
    (never timestamp order — a writer whose wall clock jumped must not
    reorder the timeline for readers; reader-clock skew is pinned by
    tests/test_telemetry.py), torn/corrupt lines skipped and counted.
    """
    records: List[Dict] = []
    torn = 0
    segments: List[str] = []
    try:
        names = sorted(
            n for n in os.listdir(directory)
            if n.startswith(_SEGMENT_PREFIX) and n.endswith(_SEGMENT_SUFFIX)
        )
    except OSError:
        names = []
    for name in names:
        segments.append(name)
        try:
            with open(os.path.join(directory, name), "r",
                      encoding="utf-8", errors="replace") as fh:
                for line in fh:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        torn += 1
                        continue
                    if not isinstance(rec, dict):
                        torn += 1
                        continue
                    if kinds is not None and rec.get("kind") not in kinds:
                        continue
                    records.append(rec)
        except OSError:
            continue
    return {"records": records, "torn": torn, "segments": segments}


class TelemetryPipeline:
    """The assembled warehouse: archive + classifier + the beat that
    snapshots the signal vocabulary. Construction follows the module
    template every PR since brownout uses: ``from_params`` gates on the
    enable knob; disabled means no directory, no metrics, no SignalWindow
    — ``evaluate()`` is one bool check and ``record_request`` is never
    wired (the handler holds None).
    """

    def __init__(self, *, enabled: bool, directory: str = "",
                 interval_s: float = 10.0,
                 archive: Optional[TelemetryArchive] = None,
                 classifier: Optional[TrafficMixClassifier] = None,
                 clock: Optional[Callable[[], float]] = None,
                 metrics=None, replica_id: str = "") -> None:
        self.enabled = enabled
        self.directory = directory
        self.interval_s = max(0.05, float(interval_s))
        self.archive = archive
        self.classifier = classifier
        self.clock = clock or time.time
        self.metrics = metrics
        self.replica_id = replica_id
        self._lock = threading.Lock()
        self._last_beat = 0.0
        self._last_launch_seq = 0
        self._prev_ledger: Optional[Dict[str, float]] = None
        self._prev_counters: Dict[str, float] = {}
        self._beat_outcomes: Dict[str, int] = {}
        # read surfaces (attach())
        self.window = None
        self._slo = None
        self._flight_recorder = None
        self._ledger_fn: Optional[Callable[[], Dict]] = None

    @classmethod
    def from_params(cls, params, *, metrics=None,
                    replica_id: str = "") -> "TelemetryPipeline":
        enabled = bool(params.by_key("telemetry_enable", False))
        if not enabled:
            return cls(enabled=False)
        directory = str(params.by_key("telemetry_dir", "") or "")
        if not directory:
            directory = os.path.join(
                str(params.by_key("tmp_dir", "var/tmp")), "telemetry"
            )
        clock = params.by_key("telemetry_clock") or time.time
        archive = TelemetryArchive(
            directory,
            segment_max_bytes=int(
                params.by_key("telemetry_segment_max_bytes", 1 << 20)
            ),
            segment_max_age_s=float(
                params.by_key("telemetry_segment_max_age_s", 300.0)
            ),
            retention_max_bytes=int(
                params.by_key("telemetry_retention_max_bytes", 32 << 20)
            ),
            retention_max_segments=int(
                params.by_key("telemetry_retention_max_segments", 64)
            ),
            clock=clock,
            replica_id=replica_id,
        )
        classifier = TrafficMixClassifier(
            window=int(params.by_key("telemetry_mix_window", 256)),
            min_samples=int(params.by_key("telemetry_mix_min_samples", 8)),
            hysteresis=int(params.by_key("telemetry_mix_hysteresis", 2)),
        )
        pipeline = cls(
            enabled=True,
            directory=directory,
            interval_s=float(
                params.by_key("telemetry_snapshot_interval_s", 10.0)
            ),
            archive=archive,
            classifier=classifier,
            clock=clock,
            metrics=metrics,
            replica_id=replica_id,
        )
        if metrics is not None:
            pipeline._register_metrics(metrics)
        return pipeline

    # -- wiring -------------------------------------------------------------

    def attach(self, *, metrics=None, slo=None, brownout=None,
               host_pipeline=None, flight_recorder=None,
               reuse_fn=None, ledger_fn: Optional[Callable[[], Dict]] = None,
               ) -> None:
        """Wire the read surfaces. The pipeline owns its OWN SignalWindow
        instance — launches_delta diffs recorded_total per window, so
        sharing the observatory's would corrupt both consumers' deltas
        (the observatory docstring pins this)."""
        if not self.enabled:
            return
        from flyimg_tpu.runtime.observatory import SignalWindow

        self.window = SignalWindow()
        self.window.attach(
            metrics=metrics, slo=slo, brownout=brownout,
            host_pipeline=host_pipeline, flight_recorder=flight_recorder,
            reuse_fn=reuse_fn,
        )
        self._slo = slo
        self._flight_recorder = flight_recorder
        self._ledger_fn = ledger_fn
        # the boot record: continuity marker + the recovery verdict
        inv = self.archive.inventory()
        self.archive.append("boot", {
            "segment": inv["active_segment"],
            "torn_recovered": inv["torn_recovered"],
            "segments": len(inv["segments"]),
            "archive_bytes": inv["bytes"],
        })

    def _register_metrics(self, registry) -> None:
        from flyimg_tpu.runtime.metrics import escape_label_value

        for label in MIX_LABELS:
            safe = escape_label_value(label)
            registry.gauge(
                f'flyimg_traffic_mix{{mix="{safe}"}}',
                "Adopted traffic-mix label (1 = current, 0 = not)",
                fn=lambda lbl=label: (
                    1.0 if self.classifier.label == lbl else 0.0
                ),
            )
        registry.gauge(
            "flyimg_telemetry_segments",
            "Archive segment files currently retained on disk",
            fn=lambda: float(len(self.archive.inventory()["segments"])),
        )
        registry.gauge(
            "flyimg_telemetry_archive_bytes",
            "Total bytes across retained archive segments",
            fn=lambda: float(self.archive.total_bytes()),
        )

    # -- the per-request write path (handler) -------------------------------

    def record_request(self, *, options, source_key: Optional[str],
                       outcome: str) -> None:
        """One request outcome into the classifier window. Rides every
        outcome point including cache hits, so the body is one feature
        extraction + one deque append — no IO, no archive touch."""
        if not self.enabled:
            return
        try:
            features = request_features(options, source_key)
            self.classifier.record(features, outcome)
            with self._lock:
                self._beat_outcomes[outcome] = (
                    self._beat_outcomes.get(outcome, 0) + 1
                )
        except Exception:
            # telemetry must never fail (or slow) a request visibly
            logging.getLogger(TELEMETRY_LOGGER).debug(
                "mix feature recording failed", exc_info=True
            )

    # -- the beat -----------------------------------------------------------

    def evaluate(self) -> bool:
        """The snapshot beat, riding the request middleware exactly like
        ``brownout.evaluate()``: rate-limited by the interval, one float
        compare when idle, one bool check when disabled. Returns True
        when a window record was written (tests drive this directly)."""
        if not self.enabled:
            return False
        now = self.clock()
        with self._lock:
            if now - self._last_beat < self.interval_s:
                return False
            since = now - (self._last_beat or now)
            self._last_beat = now
            outcomes = dict(self._beat_outcomes)
            self._beat_outcomes.clear()
        try:
            self._drain_launches()
            self._write_window(since, outcomes)
            return True
        except Exception:
            logging.getLogger(TELEMETRY_LOGGER).warning(
                "telemetry beat failed", exc_info=True
            )
            return False

    def _drain_launches(self) -> None:
        """Every flight-recorder record newer than the last beat's high
        -water seq becomes one durable launch record. The ring already
        bounds the worst case to its own capacity per beat."""
        recorder = self._flight_recorder
        if recorder is None:
            return
        doc = recorder.snapshot(limit=len(recorder) or 1)
        fresh = [
            r for r in doc.get("records", [])
            if int(r.get("seq") or 0) > self._last_launch_seq
        ]
        fresh.sort(key=lambda r: int(r.get("seq") or 0))
        for rec in fresh:
            fields = dict(rec)
            fields["launch_kind"] = fields.pop("kind", None)
            fields["launch_seq"] = fields.pop("seq", None)
            fields.pop("at_s", None)  # the envelope stamps archive time
            self.archive.append("launch", fields)
            self._count_record("launch")
        if fresh:
            self._last_launch_seq = int(fresh[-1].get("launch_seq")
                                        or fresh[-1].get("seq") or 0)

    def _ledger_delta(self) -> Optional[Dict[str, float]]:
        if self._ledger_fn is None:
            return None
        try:
            aggregates = {
                k: float(v) for k, v in self._ledger_fn().items()
                if isinstance(v, (int, float))
            }
        except Exception:
            return None
        prev = self._prev_ledger or {}
        self._prev_ledger = aggregates
        return {
            k: round(v - prev.get(k, 0.0), 6) for k, v in aggregates.items()
        }

    def _counter_delta(self, family: str) -> float:
        if self.metrics is None:
            return 0.0
        try:
            total = float(self.metrics.family_total(family))
        except Exception:
            return 0.0
        prev = self._prev_counters.get(family, total)
        self._prev_counters[family] = total
        return max(0.0, total - prev)

    def _write_window(self, since_s: float, outcomes: Dict[str, int]) -> None:
        from flyimg_tpu.runtime import tracing

        mix = self.classifier.classify()
        if mix["changed"]:
            self._on_mix_change(mix)
        signals = self.window.assemble() if self.window is not None else {}
        slo_fields = {}
        slo = self._slo
        if slo is not None and getattr(slo, "enabled", False):
            try:
                slo_fields = dict(slo.digest_fields())
            except Exception:
                slo_fields = {}
        inv = self.archive.inventory()
        hits = sum(outcomes.get(k, 0)
                   for k in ("hit", "stale", "coalesced"))
        misses = sum(outcomes.get(k, 0) for k in ("miss", "reuse"))
        degraded = outcomes.get("degraded", 0) + outcomes.get("shed", 0)
        record = {
            "window_s": round(since_s, 3),
            "controllers": signals.get("controllers") or {},
            "host": signals.get("host") or {},
            "kernel_mode": signals.get("kernel_mode"),
            "burn_fast_norm": signals.get("burn_fast_norm"),
            "burn_slow_norm": signals.get("burn_slow_norm"),
            "brownout_level": signals.get("brownout_level"),
            "slo": slo_fields or None,
            "reuse": signals.get("reuse"),
            "ledger_delta": self._ledger_delta(),
            "requests_delta": self._counter_delta("flyimg_requests_total"),
            "hits_delta": hits,
            "misses_delta": misses,
            "degraded_delta": degraded,
            "mix": mix["label"],
            "mix_raw": mix["raw"],
            "mix_distance": mix["distance"],
            "mix_features": mix["features"],
            "mix_samples": mix["samples"],
            "segments": len(inv["segments"]),
            "archive_bytes": inv["bytes"],
        }
        if self.archive.append("window", record):
            self._count_record("window")
        tracing.add_event(
            "telemetry.window", mix=mix["label"], samples=mix["samples"]
        )

    def _on_mix_change(self, mix: Dict[str, object]) -> None:
        """Edge-triggered mix flip: one counter, one structured log
        line, one span event on whichever request's beat saw it."""
        from flyimg_tpu.runtime import tracing

        if self.metrics is not None:
            from flyimg_tpu.runtime.metrics import escape_label_value

            self.metrics.counter(
                "flyimg_traffic_mix_transitions_total"
                f'{{to="{escape_label_value(str(mix["label"]))}"}}',
                "Adopted traffic-mix label flips by destination "
                "(edge-triggered, after hysteresis)",
            ).inc()
        tracing.add_event(
            "telemetry.mix_changed",
            to=mix["label"], previous=mix["previous"],
            distance=mix["distance"],
        )
        logging.getLogger(TELEMETRY_LOGGER).info(
            "traffic mix changed: %s -> %s", mix["previous"], mix["label"],
            extra={
                "event": "telemetry.mix_changed",
                "to": mix["label"],
                "previous": mix["previous"],
                "distance": mix["distance"],
                "features": mix["features"],
                "samples": mix["samples"],
                "replica": self.replica_id or None,
            },
        )

    def _count_record(self, kind: str) -> None:
        if self.metrics is None:
            return
        from flyimg_tpu.runtime.metrics import escape_label_value

        self.metrics.counter(
            "flyimg_telemetry_records_total"
            f'{{kind="{escape_label_value(kind)}"}}',
            "Records appended to the telemetry archive, by kind",
        ).inc()

    # -- artifact retention (flight-recorder dumps) -------------------------

    def adopt_dump_retention(self, recorder, max_dumps: int) -> None:
        """Satellite-1 unification: the flight recorder's dump files
        join the archive's retention family. A positive
        ``telemetry_retention_max_dumps`` overrides the legacy
        ``flightrecorder_max_dumps`` bound (kept as the documented
        alias when 0); the recorder keeps pruning on its own dump path
        so the bound holds even between beats."""
        if not self.enabled or recorder is None:
            return
        if max_dumps > 0:
            recorder.max_dumps = int(max_dumps)
            recorder.prune_dumps()
        self._flight_recorder = recorder

    # -- surfaces -----------------------------------------------------------

    def snapshot(self) -> Dict[str, object]:
        """The /debug/telemetry JSON document."""
        if not self.enabled:
            return {"enabled": False}
        doc: Dict[str, object] = {
            "enabled": True,
            "schema": SCHEMA_VERSION,
            "interval_s": self.interval_s,
            "mix": self.classifier.snapshot(),
            "archive": self.archive.inventory(),
        }
        recorder = self._flight_recorder
        if recorder is not None:
            try:
                doc["artifacts"] = {
                    "dumps": recorder.dump_files(),
                    "dump_dir": recorder.dump_dir,
                    "max_dumps": recorder.max_dumps,
                }
            except Exception:
                doc["artifacts"] = None
        return doc

    def close(self) -> None:
        if not self.enabled:
            return
        # final beat so the shutdown window is on disk, then release
        with self._lock:
            self._last_beat = 0.0
        self.evaluate()
        self.archive.close()
