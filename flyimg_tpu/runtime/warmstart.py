"""Fleet-wide warm start: program-cache seeding over the shared L2
tier (docs/fleet.md "Membership and elasticity"; ROADMAP item 3;
arXiv 2403.12981 on why cold-start compile/warm-up — not steady-state
compute — dominates perceived capacity during scale events).

A scale-out replica boots into a compile storm: every plan family in
the live mix is a fresh XLA compile before it serves at speed. The
fix is the TensorFlow-playbook split (arXiv 1605.08695) — durable
state in the storage tier, elastic stateless workers:

- **recording**: while serving, each replica notes the IDENTITY of
  every program it builds (the exact ``build_program`` /
  ``build_batched_program`` cache-key fields, minus the environmental
  mesh — ``record_single``/``record_batched`` fire inside the lru
  bodies, so once per key, zero on hits) and periodically publishes a
  digest-stamped JSON **program manifest** to the shared tier
  (piggybacked on the membership heartbeat; also at shutdown).
- **seeding**: a freshly booted replica reads the manifest and AOT-
  compiles each entry through ``ProgramHandle.precompile`` with
  ``jax.ShapeDtypeStruct`` abstract values — compile without
  executing — so its first real render of a known plan family is a
  program-cache hit.

**Validation rules** (the "foreign blob is never executed"
guarantee): the manifest carries program *identities*, never
compiled artifacts — XLA executables are backend/topology-specific
and deserializing one from shared storage would mean executing bytes
another process produced. Seeding always compiles LOCALLY from this
replica's own code against its own backend/mesh. Each entry is
digest-stamped (blake2b over its canonical JSON); a corrupted or
tampered entry fails the digest check and is SKIPPED — the program
it named simply compiles on demand at first request (recompile, not
execute). Unknown fields/kinds are skipped the same way (forward
compatibility), and a per-entry compile failure never fails the
boot.

Inert by default: with ``warmstart_enable`` off (the default) the
recorder is never installed — the hooks in compose/batcher are one
module-level ``None`` check (the ``faults.fire`` pattern), no
manifests are read or written, and no metrics register (byte
identity pinned by tests/test_fleet_membership.py).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import logging
import threading
from typing import Any, Dict, List, Optional

from flyimg_tpu.testing import faults

__all__ = [
    "WarmStartCache",
    "PROGRAMS_MANIFEST",
    "record_single",
    "record_batched",
    "install",
    "uninstall",
]

LOGGER = "flyimg.fleet"

#: shared-tier object name (flat — LocalStorage basenames every name)
PROGRAMS_MANIFEST = "warmstart-programs.manifest"

#: TransformPlan fields whose JSON lists must round back to tuples so
#: the reconstructed plan is hash/eq-identical to the recorded one
#: (the lru cache key demands exact equality)
_PLAN_TUPLE_FIELDS = frozenset({
    "src_size", "resize_to", "extent", "background", "unsharp",
    "sharpen", "blur", "extract",
})


def _entry_digest(entry: Dict[str, Any]) -> str:
    """Digest over the entry's canonical JSON (sans the digest field
    itself) — what load-time validation recomputes."""
    doc = {k: v for k, v in entry.items() if k != "digest"}
    return hashlib.blake2b(
        json.dumps(doc, sort_keys=True).encode("utf-8"), digest_size=16
    ).hexdigest()


def _tupled(value):
    return tuple(value) if isinstance(value, (list, tuple)) else value


def _plan_to_doc(plan) -> Dict[str, Any]:
    return dataclasses.asdict(plan)


def _plan_from_doc(doc: Dict[str, Any]):
    from flyimg_tpu.spec.plan import TransformPlan

    names = {f.name for f in dataclasses.fields(TransformPlan)}
    if not isinstance(doc, dict) or set(doc) - names:
        raise ValueError("unknown TransformPlan fields in manifest entry")
    kwargs = {
        k: (_tupled(v) if k in _PLAN_TUPLE_FIELDS else v)
        for k, v in doc.items()
    }
    return TransformPlan(**kwargs)


class _Recorder:
    """Bounded, deduplicated set of program identities this replica
    built. ``note`` runs on render worker threads (inside the lru
    bodies, so once per distinct program) — one lock, one dict op."""

    def __init__(self, max_entries: int) -> None:
        self.max_entries = max(int(max_entries), 1)
        self._lock = threading.Lock()
        self._entries: Dict[str, Dict[str, Any]] = {}
        self.dirty = False
        self.dropped = 0

    def note(self, entry: Dict[str, Any]) -> None:
        entry = dict(entry)
        entry["digest"] = _entry_digest(entry)
        with self._lock:
            if entry["digest"] in self._entries:
                return
            if len(self._entries) >= self.max_entries:
                # bounded, not silent: the drop count surfaces in the
                # /debug/fleet snapshot
                self.dropped += 1
                return
            self._entries[entry["digest"]] = entry
            self.dirty = True

    def drain(self) -> List[Dict[str, Any]]:
        with self._lock:
            self.dirty = False
            return list(self._entries.values())

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


class WarmStartCache:
    """One replica's warm-start agent: the recorder, the manifest
    publisher, and the boot-time seeder. All IO runs against the
    **shared** tier and is advisory — any failure degrades to a cold
    boot / an unpublished manifest, never a request or boot failure."""

    def __init__(
        self,
        storage,
        *,
        enabled: bool = False,
        max_entries: int = 64,
        metrics=None,
    ) -> None:
        self.storage = storage
        self.enabled = bool(enabled)
        self.max_entries = max(int(max_entries), 1)
        self.metrics = metrics
        self.recorder = _Recorder(self.max_entries)
        self._lock = threading.Lock()
        # seed-time accounting for /debug/fleet and the elastic smoke
        self.stats: Dict[str, int] = {
            "seeded": 0, "mismatch": 0, "skipped": 0, "failed": 0,
        }

    def _count(self, outcome: str, n: int = 1) -> None:
        self.stats[outcome] = self.stats.get(outcome, 0) + n
        if self.metrics is not None:
            self.metrics.counter(
                "flyimg_warmstart_programs_total"
                f'{{outcome="{outcome}"}}',
                "Warm-start manifest entries by seeding outcome "
                "(mismatch = digest validation failed; the program "
                "recompiles on demand instead)",
            ).inc(n)

    # -- recording ---------------------------------------------------------

    def install(self) -> "WarmStartCache":
        """Arm the process-wide recorder hooks in compose/batcher
        (service/app.py pairs this with ``uninstall`` at cleanup, the
        ``faults.install``/``clear`` discipline)."""
        if self.enabled:
            install(self)
        return self

    def note_single(self, in_shape, resample_out, pad_canvas, pad_offset,
                    plan, band_taps, depth=8) -> None:
        self.recorder.note(_with_depth({
            "kind": "single",
            "in_shape": list(in_shape),
            "resample_out": list(resample_out) if resample_out else None,
            "pad_canvas": list(pad_canvas) if pad_canvas else None,
            "pad_offset": list(pad_offset),
            "plan": _plan_to_doc(plan),
            "band_taps": list(band_taps) if band_taps else None,
        }, depth))

    def note_batched(self, batch_size, in_shape, resample_out, pad_canvas,
                     pad_offset, plan, rotate_dynamic, sharded,
                     band_taps, depth=8) -> None:
        # the mesh is ENVIRONMENTAL and stays out of the manifest: a
        # seeding replica compiles against its OWN topology (sharded
        # entries take its local mesh), which is the program it will
        # actually launch
        self.recorder.note(_with_depth({
            "kind": "batched",
            "batch_size": int(batch_size),
            "in_shape": list(in_shape),
            "resample_out": list(resample_out) if resample_out else None,
            "pad_canvas": list(pad_canvas) if pad_canvas else None,
            "pad_offset": list(pad_offset),
            "plan": _plan_to_doc(plan),
            "rotate_dynamic": bool(rotate_dynamic),
            "sharded": bool(sharded),
            "band_taps": list(band_taps) if band_taps else None,
        }, depth))

    # -- publishing --------------------------------------------------------

    def _read_manifest(self, name: str) -> Optional[dict]:
        try:
            # fault hook (flyimg_tpu/testing/faults.py warmstart.cache):
            # a raising plan models the shared tier refusing the
            # manifest read — seeding degrades to a cold boot, publish
            # merges degrade to replace, never a failure
            faults.fire("warmstart.cache", op="read", name=name)
            doc = json.loads(self.storage.read(name).decode("utf-8"))
        except Exception:
            return None
        return doc if isinstance(doc, dict) else None

    def _write_manifest(self, name: str, doc: dict) -> bool:
        try:
            faults.fire("warmstart.cache", op="write", name=name)
            self.storage.write(
                name, json.dumps(doc, sort_keys=True).encode("utf-8")
            )
            return True
        except Exception as exc:
            logging.getLogger(LOGGER).warning(
                "warm-start manifest write of %s failed (next publish "
                "retries): %s", name, exc,
            )
            return False

    def publish(self) -> None:
        """Merge this replica's recorded program identities into the
        shared manifest (union by digest, newest appended, oldest
        trimmed to ``warmstart_max_entries``). Last-write-wins
        storage makes concurrent publishers benign: each merges the
        other's last published set, so entries converge within a few
        beats."""
        if not self.enabled:
            return
        recorded = self.recorder.drain()
        if recorded:
            merged: Dict[str, Dict[str, Any]] = {}
            existing = self._read_manifest(PROGRAMS_MANIFEST) or {}
            for entry in existing.get("entries", []) or []:
                if (
                    isinstance(entry, dict)
                    and entry.get("digest")
                    and entry["digest"] == _entry_digest(entry)
                ):
                    merged[entry["digest"]] = entry
            for entry in recorded:
                merged[entry["digest"]] = entry
            entries = list(merged.values())[-self.max_entries:]
            self._write_manifest(
                PROGRAMS_MANIFEST, {"version": 1, "entries": entries}
            )

    def maybe_publish(self) -> None:
        """The membership-beat hook: publish only when new programs
        were recorded."""
        if self.enabled and self.recorder.dirty:
            self.publish()

    # -- seeding -----------------------------------------------------------

    def _seed_one(self, entry: Dict[str, Any], mesh) -> None:
        import jax
        import numpy as np

        plan = _plan_from_doc(entry["plan"])
        in_shape = _tupled(entry["in_shape"])
        resample_out = _tupled(entry.get("resample_out"))
        pad_canvas = _tupled(entry.get("pad_canvas"))
        pad_offset = _tupled(entry["pad_offset"])
        band_taps = _tupled(entry.get("band_taps"))
        depth = int(entry.get("depth", 8))
        f32 = np.dtype("float32")
        sample = np.dtype("uint16" if depth == 16 else "uint8")
        # both builders are called FULLY POSITIONALLY, matching their
        # production call sites (compose._render/BatchWorker): a seeded
        # entry only warms the cache if the real render path lands on
        # the exact same key
        if entry["kind"] == "single":
            from flyimg_tpu.ops.compose import build_program

            handle = build_program(
                in_shape, resample_out, pad_canvas, pad_offset, plan,
                band_taps, depth,
            )
            args = (
                jax.ShapeDtypeStruct((*in_shape, 3), sample),
                jax.ShapeDtypeStruct((2,), f32),
                jax.ShapeDtypeStruct((2,), f32),
                jax.ShapeDtypeStruct((2,), f32),
                jax.ShapeDtypeStruct((2,), f32),
            )
        else:
            from flyimg_tpu.runtime.batcher import build_batched_program

            batch = int(entry["batch_size"])
            rotate_dynamic = bool(entry.get("rotate_dynamic", False))
            handle = build_batched_program(
                batch, in_shape, resample_out, pad_canvas, pad_offset,
                plan, mesh if entry.get("sharded") else None,
                rotate_dynamic, band_taps, depth,
            )
            true_w = 4 if rotate_dynamic else 2
            args = (
                jax.ShapeDtypeStruct((batch, *in_shape, 3), sample),
                jax.ShapeDtypeStruct((batch, true_w), f32),
                jax.ShapeDtypeStruct((batch, 2), f32),
                jax.ShapeDtypeStruct((batch, 2), f32),
                jax.ShapeDtypeStruct((batch, 2), f32),
            )
        handle.precompile(args)

    def seed_programs(self, mesh=None) -> Dict[str, int]:
        """Boot-time program-cache seeding (service/app.py, before the
        first request): compile every digest-valid manifest entry
        locally. Returns the outcome counts (also kept in ``stats``
        for /debug/fleet and the elastic smoke's warm-vs-cold
        assertion)."""
        if not self.enabled:
            return {}
        manifest = self._read_manifest(PROGRAMS_MANIFEST)
        if manifest is None:
            return dict(self.stats)
        for entry in (manifest.get("entries") or [])[:self.max_entries]:
            if not isinstance(entry, dict) or entry.get("kind") not in (
                "single", "batched"
            ):
                self._count("skipped")
                continue
            if entry.get("digest") != _entry_digest(entry):
                # corrupted/tampered entry: recompile-on-demand, never
                # compile (let alone execute) a mangled identity
                self._count("mismatch")
                logging.getLogger(LOGGER).warning(
                    "warm-start manifest entry failed digest "
                    "validation; skipping (the program recompiles on "
                    "demand)",
                )
                continue
            try:
                self._seed_one(entry, mesh)
            except Exception as exc:
                self._count("failed")
                logging.getLogger(LOGGER).warning(
                    "warm-start compile of one manifest entry failed "
                    "(recompiles on demand): %s", exc,
                )
                continue
            self._count("seeded")
        return dict(self.stats)

    def snapshot(self) -> Dict[str, object]:
        return {
            "enabled": self.enabled,
            "max_entries": self.max_entries,
            "recorded": len(self.recorder),
            "recorder_dropped": self.recorder.dropped,
            "stats": dict(self.stats),
        }

    @classmethod
    def from_params(cls, params, *, storage, metrics=None) -> "WarmStartCache":
        return cls(
            storage,
            enabled=bool(params.by_key("warmstart_enable", False)),
            max_entries=int(params.by_key("warmstart_max_entries", 64)),
            metrics=metrics,
        )


def _with_depth(entry: Dict[str, Any], depth: int) -> Dict[str, Any]:
    """A manifest entry with its program's sample depth, where it is not
    8: an 8-bit entry reads (and digests) as it did before depths were
    recorded, and an entry without one seeds an 8-bit program."""
    if int(depth) != 8:
        entry["depth"] = int(depth)
    return entry


# ---------------------------------------------------------------------------
# process-wide recorder hooks (the faults.install/clear pattern):
# compose.build_program / batcher.build_batched_program call these inside
# their lru-cached bodies — once per distinct program, a single None
# check when warm start is off

_active: Optional[WarmStartCache] = None


def install(cache: WarmStartCache) -> WarmStartCache:
    global _active
    _active = cache
    return cache


def uninstall() -> None:
    global _active
    _active = None


def record_single(in_shape, resample_out, pad_canvas, pad_offset, plan,
                  band_taps, depth=8) -> None:
    """Called by ops/compose.build_program on each lru miss."""
    cache = _active
    if cache is None:
        return
    try:
        cache.note_single(
            in_shape, resample_out, pad_canvas, pad_offset, plan, band_taps,
            depth,
        )
    except Exception:  # recording must never fail a compile
        logging.getLogger(LOGGER).debug(
            "warm-start recording failed for one single program",
            exc_info=True,
        )


def record_batched(batch_size, in_shape, resample_out, pad_canvas,
                   pad_offset, plan, rotate_dynamic, sharded,
                   band_taps, depth=8) -> None:
    """Called by runtime/batcher.build_batched_program on each lru miss."""
    cache = _active
    if cache is None:
        return
    try:
        cache.note_batched(
            batch_size, in_shape, resample_out, pad_canvas, pad_offset,
            plan, rotate_dynamic, sharded, band_taps, depth,
        )
    except Exception:
        logging.getLogger(LOGGER).debug(
            "warm-start recording failed for one batched program",
            exc_info=True,
        )
