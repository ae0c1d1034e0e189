"""BatchController: dynamic batching of concurrent transform requests.

Requests are grouped by their device-program identity — the same key the
compile cache uses: (input bucket shape, static resample output, pad config,
``plan.device_plan()``). Every member of a group differs only in pixels and
traced geometry scalars, so a group executes as ONE jitted vmapped program:

    uint8 [B, Hb, Wb, 3] + per-image spans/true-sizes -> uint8 [B, Ho, Wo, 3]

(uint16 for 16-bit samples: the sample's dtype is part of the identity, so
8-bit and 16-bit members never share a launch or a host block).

Flush policy (reference-free; this subsystem has no analog in the
per-request reference): a group flushes when it reaches ``max_batch`` or
when its oldest member has waited ``deadline_ms`` — the standard
throughput/latency dial for dynamic batching; a lone request (unless the
last transform launch was full), and an aux group whatever else is pending,
flushes at once when the executor is idle (``_group_ready``). Batch sizes
are bucketed to
powers of two (padding repeats the last image) so XLA compiles a handful of
batch shapes per program, not one per occupancy. A transform group owns its
launch's padded host block from the moment it is made, and ``submit`` copies
each member's frame into its slot on the caller's thread while the launch is
still filling (``_Group``, ``_copy_in``): a launch that is full is ready to
stage. A block whose launch has run becomes the controller's spare, and the
next group of that shape takes it in place of a fresh ``np.zeros``
(``_take_block``, ``_keep_block``): its pages are there already.

A single executor thread owns device DISPATCH: groups launch serially (the
chip executes serially anyway), submissions return futures usable from
threads or asyncio. Result READBACK runs on per-batch daemon drain threads
behind a bounded in-flight window (``pipeline_depth``, default 2 = classic
double buffering): jax dispatch is asynchronous, so the executor can assemble and
launch batch N+1 while batch N's device->host read is still in flight,
overlapping the D2H copy with compute (not yet measured on the chip:
ROADMAP S3/D5). Every launch keeps ONE account of its phases (``_Launch``:
fill, assemble, slot wait, h2d, dispatch, run, d2h, resolve) and hands it
whole to the span, the histograms, the efficiency window and the flight
recorder; every member's future carries its own queued / popped / ready /
answered instants (``launch_times``). The transform controller also puts
every second in which none of its launches ran down to what it was doing
then (``devicegaps.GapAccount``).

Failure containment (docs/resilience.md): sharing a batch must not mean
sharing its failures. A failed launch is classified
(runtime/resilience.py classify_batch_error): TRANSIENT device/runtime
errors get a bounded whole-batch retry with full-jitter backoff
(``batch_retries``); member-caused POISON errors re-execute by recursive
bisection down to singletons (``bisect_enable``), so innocent members
still succeed and only the poison member's future fails. Fingerprints of
isolated poison work (plan key + image digest) enter a TTL'd quarantine
(``quarantine_ttl_s``); repeat offenders short-circuit to isolated
singleton execution at submit time. The executor thread self-heals: a
dead or wedged (``executor_wedge_timeout_s``) executor is detected at
submit time and replaced, the new thread re-homing all queued groups —
instead of permanently stranding submissions behind the handler's
per-request CPU fallback.
"""

from __future__ import annotations

import hashlib
import itertools
import sys
import threading
import time
from concurrent.futures import Future
from contextlib import contextmanager
from dataclasses import dataclass, field
from functools import lru_cache
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from flyimg_tpu.ops.compose import (
    WORD_BYTES,
    ProgramHandle,
    ReadbackByteOrderError,
    _bucket_dim,
    bound_lru_cache,
    bucket_batch,
    final_extent,
    flat_output_format,
    flatten_images,
    make_program_fn,
    plan_descriptor,
    plan_layout,
    readback_word_size,
    stage_pieces,
    unflatten_images,
)
from flyimg_tpu.ops.resample import kernel_mode, select_band_taps
from flyimg_tpu.runtime import costledger, tracing
from flyimg_tpu.runtime.devicegaps import GapAccount
from flyimg_tpu.runtime.resilience import (
    OVERSIZE,
    POISON,
    TRANSIENT,
    QuarantineTable,
    RetryPolicy,
    classify_batch_error,
)
from flyimg_tpu.spec.plan import TransformPlan
from flyimg_tpu.testing import faults

MAX_BATCH_BUCKET = 64
# the host blocks' dtype by the bits of a sample (``_Group.depth``)
_SAMPLE_DTYPES = {8: np.uint8, 16: np.uint16}
# the name (and `controller` label) of the controller that runs transform
# launches: ``BatchController``'s default
TRANSFORM_CONTROLLER = "device"


def containment_params(params) -> dict:
    """The blast-radius containment kwargs ``BatchController`` reads from
    appconfig — ONE mapping shared by serving (service/app.py) and
    offline bulk sweeps (bulk.py), so the ``resilience_*`` knobs mean
    the same thing everywhere (docs/resilience.md)."""
    return dict(
        batch_retries=int(params.by_key("resilience_batch_retries", 2)),
        bisect_enable=bool(
            params.by_key("resilience_bisect_enable", True)
        ),
        quarantine_ttl_s=float(
            params.by_key("resilience_quarantine_ttl", 300.0)
        ),
        executor_wedge_timeout_s=float(
            params.by_key("resilience_executor_wedge_timeout_s", 60.0)
        ),
    )


def _image_digest(image) -> str:
    """Quarantine fingerprint component for one member's pixels. Only
    computed on the poison paths (isolation bookkeeping, and submit-time
    checks while the quarantine table is non-empty) — never on the
    fault-free hot path."""
    return hashlib.blake2b(
        np.ascontiguousarray(image).tobytes(), digest_size=12
    ).hexdigest()


def _round_batch(n: int) -> int:
    """The shared power-of-two occupancy ladder, capped: groups never
    exceed max_batch (<= 64 by default) members anyway."""
    return min(bucket_batch(n), MAX_BATCH_BUCKET)


@bound_lru_cache(maxsize=256)
def build_batched_program(
    batch_size: int,
    in_shape: Tuple[int, int],
    resample_out: Optional[Tuple[int, int]],
    pad_canvas: Optional[Tuple[int, int]],
    pad_offset: Tuple[int, int],
    plan: TransformPlan,
    mesh=None,
    rotate_dynamic: bool = False,
    band_taps: Optional[Tuple[int, int]] = None,
    depth: int = 8,
) -> ProgramHandle:
    """vmap of the single-image program over a static batch axis; with a
    mesh, the batch axis is sharded over its 'data' axis (SPMD fan-out, no
    collectives — each device transforms its slice of the batch). Returned
    as a ``ProgramHandle``: the first call AOT-compiles and records XLA
    cost analysis in the per-plan ledger; ``handle.is_compiled`` is the
    batcher's exact compile-hit signal. One cache entry = one (batch,
    shape) program = one compiled executable. The program takes the
    images flat and in pieces (``stage_pieces`` of them, each ``u8[batch /
    pieces, h, w * 3]``; one piece for every launch of small frames),
    un-flattens and transforms them piece by piece, in a loop over one
    traced body, and returns the output flat too, ``u8[batch, H, W *
    3]``, laid out row-major (``flat_output_format``), and packed four
    samples (two of 16 bits) to a ``uint32`` word where a row holds a
    whole number of words (``readback_word_size`` of the output's static
    width, found by one abstract trace a family of programs, and part of
    the key as ``word_size``: a read-back of words is some four times
    faster than of the samples: ``tools/readback_probe.py``); callers keep
    assembling and describing ``[batch, h, w, 3]`` and go through the
    handle's ``stage`` / ``precompile`` / ``unstage``, which own the
    mapping (ops/compose.py ``flat_pieces``, ``flatten_images``).
    ``band_taps`` (the banded resample's static per-axis K;
    docs/kernels.md) is part of the cache key AND the ledger key — dense
    and banded variants of one plan must never collide in either. So is
    ``depth``, the bits of a sample in and out (8: ``u8``, 16: ``u16``
    throughout, staged in pieces of the same bytes: fewer frames a piece);
    a 16-bit program's XLA module is ``jit_program16``, so that a profile
    tells it from the 8-bit ``jit_program``."""
    batched = jax.vmap(make_program_fn(
        resample_out, pad_canvas, pad_offset, plan,
        rotate_dynamic=rotate_dynamic, band_taps=band_taps, depth=depth,
    ))

    # a sharded program takes the images in one piece: the runtime moves
    # one transfer a device, and a piece must not straddle the mesh
    pieces = (
        stage_pieces(batch_size, in_shape, depth // 8) if mesh is None else 1
    )
    frames = batch_size // pieces
    samples = jnp.uint16 if depth == 16 else jnp.uint8
    word_size = _output_word_size(
        in_shape, resample_out, pad_canvas, pad_offset, plan,
        rotate_dynamic, band_taps, depth,
    )
    if word_size == WORD_BYTES and sys.byteorder != "little":
        raise ReadbackByteOrderError(
            f"a {sys.byteorder}-endian host cannot view the program's "
            "uint32 words as the samples they pack"
        )

    def program(flat_pieces_u8, in_true, span_y, span_x, out_true):
        # the images arrive as ProgramHandle.stage leaves them (flat, in
        # pieces: the form the host copies straight through) and every
        # piece is un-flattened, transformed and flattened again here,
        # inside the one jit_program module, so the device time of both
        # re-layouts counts with the program's, and the read-back lands
        # in the host's row order
        def one(piece, *scalars):
            return flatten_images(
                batched(unflatten_images(piece, in_shape), *scalars),
                word_size,
            )

        scalars = (in_true, span_y, span_x, out_true)
        if pieces == 1:
            return one(flat_pieces_u8[0], *scalars)

        # several pieces: ONE traced body in a loop over them, not the
        # body unrolled `pieces` times (unrolled, the 64-piece program of
        # a 24 MP launch compiled for 104-112 s, took 23 s to read back
        # from the compile cache and outgrew it; PERF.md section 6, PR
        # 28). The step picks its piece among the program's parameters (a
        # copy of one piece, at HBM speed), so the temporaries are one
        # piece's whatever the launch's size.
        picks = [lambda ps, k=k: ps[k] for k in range(pieces)]

        def step(xs):
            k, rows = xs
            return one(jax.lax.switch(k, picks, flat_pieces_u8), *rows)

        out = jax.lax.map(step, (
            jnp.arange(pieces),
            tuple(a.reshape(pieces, frames, *a.shape[1:]) for a in scalars),
        ))
        return out.reshape(batch_size, *out.shape[2:])

    if depth != 8:
        program.__name__ = f"program{depth}"

    # the output is laid out row-major wherever it lives (the device's
    # default for a flat uint8 array depends on its shape)
    sharding = None
    if mesh is None:
        from jax.sharding import SingleDeviceSharding

        jitted = jax.jit(program, out_shardings=flat_output_format(
            SingleDeviceSharding(jax.local_devices()[0])
        ))
    else:
        from jax.sharding import NamedSharding, PartitionSpec as P

        sharding = NamedSharding(mesh, P("data"))
        jitted = jax.jit(
            program,
            in_shardings=(sharding,) * 5,
            out_shardings=flat_output_format(sharding),
        )
    key = (
        "batched", batch_size, in_shape, resample_out, pad_canvas,
        pad_offset, plan, rotate_dynamic,
        tuple(mesh.shape.items()) if mesh is not None else None,
        band_taps,
        depth,
        word_size,
    )
    # fleet warm start (runtime/warmstart.py): note this program's
    # identity for the shared manifest — the mesh stays out (a seeding
    # replica compiles against its OWN topology); a no-op unless a
    # recorder is installed
    from flyimg_tpu.runtime import warmstart

    warmstart.record_batched(
        batch_size, in_shape, resample_out, pad_canvas, pad_offset,
        plan, rotate_dynamic, mesh is not None, band_taps, depth,
    )
    return ProgramHandle(
        jitted,
        key,
        plan_descriptor(
            plan, in_shape=in_shape, batch=batch_size,
            resample_out=resample_out, pad_canvas=pad_canvas,
            pad_offset=pad_offset, rotate_dynamic=rotate_dynamic,
            band_taps=band_taps, depth=depth, word_size=word_size,
        ),
        in_sharding=sharding,
        pieces=pieces,
        samples=samples,
    )


def _output_word_size(*family) -> int:
    """``readback_word_size`` of a batched program's static output width,
    one abstract trace (``jax.eval_shape``: nothing is compiled or run) of
    the single-image program a family of ``build_batched_program``'s
    arguments less the batch size: every launch size of a family has the
    same width, and a warm-up builds them all at once, so the first
    caller traces and the others wait for its answer."""
    with _WORD_SIZE_LOCK:
        return _family_word_size(*family)


_WORD_SIZE_LOCK = threading.Lock()


@lru_cache(maxsize=256)
def _family_word_size(in_shape, resample_out, pad_canvas, pad_offset, plan,
                      rotate_dynamic, band_taps, depth) -> int:
    f32 = jnp.float32
    out = jax.eval_shape(
        make_program_fn(
            resample_out, pad_canvas, pad_offset, plan,
            rotate_dynamic=rotate_dynamic, band_taps=band_taps, depth=depth,
        ),
        jax.ShapeDtypeStruct(
            (*in_shape, 3), jnp.uint16 if depth == 16 else jnp.uint8
        ),
        # in_true is four wide for a dynamic rotate (``_assemble``)
        jax.ShapeDtypeStruct((4 if rotate_dynamic else 2,), f32),
        *(jax.ShapeDtypeStruct((2,), f32),) * 3,
    )
    return readback_word_size(out.shape[1], depth)


@dataclass(eq=False)  # identity equality: generated __eq__ would compare
class _Pending:       # ndarray fields ("truth value is ambiguous" in any
    # list membership test over in-flight batches)
    # [h, w, 3] uint8 or uint16 (or aux payload); None once answered
    image: Optional[np.ndarray]
    plan: Optional[TransformPlan]
    future: Future
    enqueued_at: float              # time.monotonic(): the flush policy's clock
    final_true: Tuple[int, int]     # final valid (h, w) of the output
    needs_slice: bool = False       # output is bucket-padded; slice final_true
    # trace fan-in: the submitting request's trace + the span that was
    # active at submit time, so the SHARED batch span can be attached to
    # every member request's trace (runtime/tracing.py)
    trace: Optional[object] = None
    parent_span_id: Optional[str] = None
    # lazily computed quarantine digest (poison paths only)
    fp_digest: Optional[str] = None
    # ROI decode (docs/host-pipeline.md): `image` is only the window of
    # the plan's source starting at this (x, y) offset; _assemble shifts
    # the member's TRACED spans by it — program identity is untouched
    src_window: Optional[Tuple[int, int]] = None
    # the same instant as enqueued_at on the clock spans and launch phases
    # use (time.perf_counter()): the start of this member's queue wait
    enqueued_pc: float = field(default_factory=time.perf_counter)
    # the slot of its group's block that ``submit`` reserved for this
    # member's pixels and copied them into (``_Group.block``); None where
    # the group had no block or no slot left: ``_assemble`` copies then
    slot: Optional[int] = None


class _Launch:
    """The account of ONE launch, made when the launch is popped and handed
    whole to every sink: the shared batch span, the registry's histograms
    and batch-efficiency window (``MetricsRegistry.record_launch``), and
    the flight recorder. Phases, in the order a transform launch runs them
    (docs/observability.md "Launch phases"):

    ``fill``       oldest member's enqueue -> pop (``queue_wait_s``)
    ``assemble``   ``_assemble``: the padded host batch, executor thread
    ``slot_wait``  the wait for a pipeline slot (``pipeline_depth``)
    ``h2d``        start of ``fn.stage`` -> the staged inputs are ON the
                   device (``jax.block_until_ready``, waited on the drain
                   thread: the executor never waits on the transfer)
    ``dispatch``   the ``fn(*dev_args)`` call (enqueue; compile on a miss)
    ``run``        inputs on the device -> output ready
    ``d2h``        output ready -> ``np.asarray`` returned (the read-back)
    ``resolve``    ``_resolve_members``: slice, copy, set each future

    ``device_s`` keeps the meaning of ``flyimg_device_seconds``: start of
    the dispatch call -> completed read-back, which is the part of h2d
    after the dispatch, plus run, plus d2h, exactly (the three laps share
    their end points). An aux launch has ``fill``, ``run`` (the runner
    call) and ``resolve``. ``marks`` holds ``time.perf_counter()`` pairs;
    ``cpu_s`` the CPU seconds of the one thread that ran ``assemble`` and
    ``resolve`` (``time.thread_time()``), and the executor's over the
    staging call that opens ``h2d``: over the phase's seconds, the share
    its thread computed rather than waited; ``transfer_bytes`` the bytes
    staged (``h2d``) and read back (``d2h``); ``readback`` the form the
    read-back arrived in (``row_major`` or ``strided``), ``d2h_word`` the
    dtype it crossed the link in (``u32`` words or the samples, ``u8`` /
    ``u16``). Every phase is
    also opened as a ``jax.profiler.TraceAnnotation`` named
    ``flyimg:batch:<seq>:<phase>``
    (an aux launch's: ``flyimg:aux:<controller>:<seq>:<phase>``) on the
    thread that runs it (``h2d`` is two: ``h2d`` around the staging call,
    ``h2d_wait`` around the wait; each member's answer is ``answer`` inside
    ``resolve``), so a profiler trace carries the same intervals on the
    device trace's clock. A transform launch of the transform controller
    also tells the controller's ``GapAccount`` each phase it enters
    (``gaps``): popped, ``staging`` at the staging call, ``run`` when its
    inputs are on the device, ``d2h``, ``resolve`` from the read-back's end,
    done when it is answered or has failed (``settle``)."""

    __slots__ = (
        "seq", "kind", "aux", "images", "capacity", "popped",
        "queue_wait_s", "marks", "cpu_s", "compile_hit", "dev_args",
        "block", "transfer_bytes", "readback", "d2h_word", "_cursor",
        "_opened", "prefix", "_gaps", "_at", "depth",
    )

    def __init__(self, seq: int, members: List[_Pending], *,
                 kind: str = "primary", aux: bool = False,
                 controller: str = TRANSFORM_CONTROLLER,
                 gaps: Optional[GapAccount] = None, depth: int = 8) -> None:
        self.seq = seq
        self.kind = kind
        self.aux = aux
        # bits of a sample of a transform launch (``_Group.depth``)
        self.depth = depth
        self.images = self.capacity = len(members)
        self.popped = time.perf_counter()
        self.queue_wait_s = time.monotonic() - min(
            m.enqueued_at for m in members
        )
        self.marks: Dict[str, Tuple[float, float]] = {}
        self.cpu_s: Dict[str, float] = {}
        # bytes over the link by direction ("h2d": every staged array,
        # "d2h": the output read back): with the two transfer phases'
        # seconds, the rate an operator reads
        self.transfer_bytes: Dict[str, int] = {}
        # the form the output was read back in: "row_major" where the host
        # array is C-contiguous (the host's order: a member is a view of
        # it), "strided" where it is a view in another order (None: not
        # read back)
        self.readback: Optional[str] = None
        # the dtype the output crossed the link in: "u32" where the program
        # packs its samples into words, "u8" / "u16" where it returns them
        # as they are (None: not read back)
        self.d2h_word: Optional[str] = None
        self.compile_hit: Optional[bool] = None
        # the staged inputs, held only until the h2d wait returns: the
        # drain thread must not keep a launch's inputs alive through the
        # read-back (a Thread keeps its args until run() returns)
        self.dev_args = None
        # the host block the inputs were staged from, held until the
        # program has run and then handed to the controller as its spare
        # (``BatchController._keep_block``); a launch that fails lets it go
        self.block: Optional[np.ndarray] = None
        self._cursor = self.popped
        self._opened = None
        # aux launches number their own controller's sequence: their names
        # carry the controller, apart from the transform launches'
        self.prefix = (
            f"flyimg:aux:{controller}:{seq}" if aux else f"flyimg:batch:{seq}"
        )
        self._gaps = None if aux else gaps
        self._at: Optional[str] = None
        self._enter("launch", self.popped)

    def annotate(self, label: str):
        return jax.profiler.TraceAnnotation(f"{self.prefix}:{label}")

    def _enter(self, category: Optional[str],
               now: Optional[float] = None) -> None:
        """Tell the controller's gap account the launch is in
        ``category`` now (None: done)."""
        if self._gaps is not None and category != self._at:
            self._gaps.move(self._at, category, now)
            self._at = category

    def settle(self) -> None:
        """The launch is answered or has failed: it holds nothing of the
        controller's any more."""
        self._enter(None)

    @contextmanager
    def phase(self, name: str, *, cpu: bool = False):
        """Time (and annotate) a phase that starts and ends on this
        thread; ``cpu``: with the thread's own CPU seconds over it."""
        with self.annotate(name):
            cpu0 = time.thread_time() if cpu else 0.0
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.marks[name] = (t0, time.perf_counter())
                if cpu:
                    self.cpu_s[name] = time.thread_time() - cpu0
                if name == "resolve":
                    self.settle()

    def open(self, name: str) -> None:
        """Start a phase that another thread will ``close`` (h2d: staged
        on the executor, waited for on the drain thread)."""
        self._opened = (name, time.perf_counter())
        self._enter("staging", self._opened[1])

    def close(self) -> None:
        """End the ``open`` phase now; ``lap`` continues from here, and
        the launch runs."""
        name, t0 = self._opened
        self._cursor = time.perf_counter()
        self.marks[name] = (t0, self._cursor)
        self._enter("run", self._cursor)

    def lap(self, name: str) -> None:
        """End phase ``name`` now; it began where the last lap (or
        ``close``) ended, so consecutive laps leave no gap between them."""
        now = time.perf_counter()
        self.marks[name] = (self._cursor, now)
        self._cursor = now
        self._enter(_GAP_AFTER_LAP.get(name, self._at), now)

    def seconds(self, name: str) -> Optional[float]:
        mark = self.marks.get(name)
        return mark[1] - mark[0] if mark is not None else None

    @property
    def device_s(self) -> Optional[float]:
        """Dispatch -> completed read-back (``flyimg_device_seconds``);
        for an aux launch, the runner call."""
        if self.aux:
            return self.seconds("run")
        start, end = self.marks.get("dispatch"), self.marks.get("d2h")
        if start is None or end is None:
            return None
        return end[1] - start[0]

    def fields(self) -> Dict[str, Optional[float]]:
        """The flight recorder's view (``FlightRecorder.record``
        ``phases``): seconds by the recorder's field names."""
        out: Dict[str, Optional[float]] = {
            "queue_wait_s": self.queue_wait_s,
            "device_s": self.device_s,
        }
        for name, (_, field_name) in _PHASE_NAMES.items():
            out[field_name] = self.seconds(name)
        for name, cpu in self.cpu_s.items():
            out[f"{name}_cpu_s"] = cpu
        return out

    def annotate_span(self, span_obj) -> None:
        """The shared batch span's view: ``batch.*`` for the phases the
        batcher spends on the host, ``device.*`` for the launch's hold on
        the device."""
        for name, (attr, _) in _PHASE_NAMES.items():
            seconds = self.seconds(name)
            if seconds is not None:
                if self.aux and name == "run":
                    attr = "batch.run_s"  # a host runner call, not the device's
                span_obj.set_attribute(attr, round(seconds, 6))
        for name, cpu in self.cpu_s.items():
            span_obj.set_attribute(
                _PHASE_NAMES[name][0][:-2] + "_cpu_s", round(cpu, 6)
            )
        for direction, nbytes in self.transfer_bytes.items():
            span_obj.set_attribute(f"device.{direction}_bytes", nbytes)
        if self.d2h_word is not None:
            span_obj.set_attribute("device.d2h_word", self.d2h_word)
        device_s = self.device_s
        if device_s is not None and not self.aux:
            span_obj.set_attribute("device.seconds", round(device_s, 6))


# what the gap account (``GapAccount``) reads a transform launch as doing
# from the end of each lap (from the pop it is ``launch``, from the staging
# call ``staging``, from the end of ``h2d`` ``run``)
_GAP_AFTER_LAP = {"run": "d2h", "d2h": "resolve"}


# phase -> (shared span attribute, flight-recorder field): ``batch.*`` is
# the batcher's own work on the host, ``device.*`` the launch's hold on the
# device; the read-back kept its first name, ``sync``
_PHASE_NAMES = {
    "assemble": ("batch.assemble_s", "assemble_s"),
    "slot_wait": ("batch.slot_wait_s", "slot_wait_s"),
    "h2d": ("device.h2d_s", "h2d_s"),
    "dispatch": ("device.dispatch_s", "dispatch_s"),
    "run": ("device.run_s", "run_s"),
    "d2h": ("device.sync_s", "sync_s"),
    "resolve": ("batch.resolve_s", "resolve_s"),
}


def _fill_slot(frames: np.ndarray, k: int, image: np.ndarray,
               edge: bool, stale: bool = False) -> None:
    """One member's pixels into slot ``k`` of a launch's padded host block
    ``u8[batch, bh, bw, 3]``: THE copy of a frame on the host,
    made by ``submit`` on the caller's thread where the group owns its
    block, else by ``_assemble``. ``edge``: a pixel-op-only bucket, whose
    padding replicates the frame's edge so that convolutions stay correct
    at the valid region's boundary; every other bucket keeps zeros there
    (the resample never samples them). ``stale``: the block has carried a
    launch before (``_Group.block_kept``), so the slot holds that launch's
    pixels and not zeros: the margin outside the frame is cleared after
    the frame is written (under 128 rows and 128 columns, the bucket's
    step), and the slot holds byte for byte what a fresh block's would.
    An edge bucket's ``np.pad`` writes the whole slot as it is."""
    h, w = image.shape[:2]
    bh, bw = frames.shape[1:3]
    if edge and (h, w) != (bh, bw):
        frames[k] = np.pad(
            image, ((0, bh - h), (0, bw - w), (0, 0)), mode="edge"
        )
    else:
        frames[k, :h, :w] = image
        if stale:
            frames[k, h:] = 0
            frames[k, :h, w:] = 0


@dataclass
class _Group:
    """The members queued for one program identity, and (transform groups)
    the padded host block of the launch they will make: ``block`` is
    ``u8[_padded_batch(max_batch), bh, bw, 3]`` from the moment the group
    is made (``BatchController._take_block``), member ``k`` of the group
    owns slot ``k`` of it, and ``submit`` copies the member's pixels there
    on the caller's thread while the group is still filling (``copying``
    counts the copies in flight: a group with one is never popped). A pop
    hands the block to the popped launch and leaves what stays queued
    without one, so does a copy that raised: members without a block (or
    beyond its capacity) are copied by ``_assemble`` at their own launch.

    Where the block comes from decides what a copy costs. A fresh
    ``np.zeros`` costs nothing until it is written, and then every page is
    touched for the first time: 0.4-0.6 thread-seconds a 72 MB frame with
    64 callers at it, and the faults are taken in the address space the
    decode workers fault their own buffers in. The controller's spare (a
    block whose launch has run; ``block_kept``) has its pages already:
    some 30 ms a frame. Its slots hold the last launch's pixels, so
    ``_fill_slot`` clears the margin of each slot it writes; slots no
    member of this launch owns keep what is there, and no launch reads
    them (``_assemble`` fills the pad slots up to the padded batch and
    stages no slot beyond it). Either way a lone launch pays for one
    frame."""

    key: Tuple
    in_shape: Tuple[int, int]
    resample_out: Optional[Tuple[int, int]]
    pad_canvas: Optional[Tuple[int, int]]
    pad_offset: Tuple[int, int]
    device_plan: Optional[TransformPlan]
    members: List[_Pending] = field(default_factory=list)
    # arbitrary-angle rotate on a shape bucket: per-member geometry rides
    # in as traced scalars (in_true widens to [h, w, rot_h, rot_w])
    rotate_dynamic: bool = False
    # banded-resample static per-axis K (None = dense); part of the group
    # key, so members group by K bucket like they group by input shape
    band_taps: Optional[Tuple[int, int]] = None
    # bits of a sample (8: uint8, 16: uint16); part of the group key, so
    # 8-bit and 16-bit members never share a launch or a block
    depth: int = 8
    # aux groups (e.g. batched smart-crop scoring) run this instead of the
    # vmapped transform program: runner(payloads) -> results, one per member
    runner: Optional[callable] = None
    # quarantine fingerprints use the PROGRAM identity: quarantined
    # submissions ride a nonce-suffixed key (forced singleton group), so
    # the un-suffixed key is carried separately or a re-offender would be
    # fingerprinted under a key no later submission can ever match
    base_key: Optional[Tuple] = None
    # memory-governor pre-split (runtime/memgovernor.py): the member cap
    # this launch was held to by the HBM budget / family ceiling, None
    # when admission didn't constrain the pop
    mem_cap: Optional[int] = None
    # the launch's padded host block and the submit-time copies into it
    # still in flight (class docstring); aux groups own none
    block: Optional[np.ndarray] = None
    copying: int = 0
    # the block has carried a launch before (the controller's spare, not a
    # fresh np.zeros): its slots hold that launch's pixels
    block_kept: bool = False


class BatchController:
    """Thread-safe dynamic batcher in front of the device."""

    def __init__(
        self,
        *,
        max_batch: int = 64,
        deadline_ms: float = 4.0,
        metrics=None,
        mesh=None,
        lone_flush: bool = True,
        pipeline_depth: int = 2,
        max_queue_depth: int = 0,
        shed_retry_after_s: float = 1.0,
        name: str = TRANSFORM_CONTROLLER,
        batch_retries: int = 2,
        bisect_enable: bool = True,
        quarantine_ttl_s: float = 0.0,
        executor_wedge_timeout_s: float = 0.0,
        flight_recorder=None,
        profiler=None,
        supervisor=None,
        governor=None,
    ) -> None:
        from flyimg_tpu.runtime.metrics import (
            MetricsRegistry,
            escape_label_value,
        )
        from flyimg_tpu.runtime.resilience import AdmissionGate

        self.name = name
        # the `controller` label this controller's AUX launches are
        # observed under (flyimg_batch_* histograms, the efficiency
        # window): the series of the transform controller hold transform
        # launches alone, so that occupancy, launch sizes and the fill wait
        # read what they say; a controller that runs aux work only (the
        # codec controller) has one kind of launch and keeps its name
        self.aux_name = (
            f"{name}_aux" if name == TRANSFORM_CONTROLLER else name
        )
        # the flush policy: fixed here, never written again
        self.max_batch = int(max_batch)
        self.deadline_s = deadline_ms / 1000.0
        # flush a lone request immediately when the device is idle (cuts
        # sparse-traffic p99 by deadline_ms; disable for deterministic
        # batch-forming in tests)
        self.lone_flush = lone_flush
        # the last transform launch popped held max_batch members: the
        # callers are a saturated stream, and a lone request waits for its
        # launch to fill (``_group_ready``)
        self._last_launch_full = False
        # optional data-parallel mesh: batches shard over its 'data' axis
        self.mesh = mesh
        self._n_devices = 1
        if mesh is not None:
            if "data" not in mesh.axis_names:
                raise ValueError("batcher mesh needs a 'data' axis")
            self._n_devices = int(mesh.shape["data"])
        # single source of truth for batch accounting; the app passes its
        # shared registry, standalone use gets a private one
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        # performance observatory wiring (all optional; None = zero-cost):
        # the batch flight recorder (runtime/flightrecorder.py) gets one
        # record per launch resolution; the on-demand profiler
        # (runtime/profiling.py) is poked around every device dispatch;
        # the per-plan cost ledger (process-wide singleton) accrues
        # device seconds per program key
        self.flight_recorder = flight_recorder
        self.profiler = profiler
        # backend supervisor (runtime/devicesupervisor.py): fed one
        # outcome per launch resolution so it can tell a poison input
        # (PR-3's job) from a backend-failure STORM (its job). None —
        # the default, and always the codec controller — is zero-cost.
        self.supervisor = supervisor
        # memory governor (runtime/memgovernor.py): consulted before
        # launch for a pre-split member cap, fed launch outcomes for its
        # AIMD capacity ceilings. None — the default, and always the
        # codec controller — is zero-cost: no prediction, no caps, the
        # disabled path is byte-identical.
        self.governor = governor
        self._ledger = costledger.get_ledger()
        # what the transform controller was doing while none of its
        # launches ran (runtime/devicegaps.py); a controller of aux work
        # alone (the codec controller) keeps none
        self._gaps = (
            GapAccount(self.metrics) if name == TRANSFORM_CONTROLLER else None
        )
        # admission control: "pending" = submitted and not yet resolved
        # (queued OR executing). When the bound is hit, submit sheds with
        # a 503 + Retry-After instead of queueing into collapse; 0 keeps
        # the legacy unbounded behavior (runtime/resilience.py).
        self.admission = AdmissionGate(
            max_pending=int(max_queue_depth),
            retry_after_s=shed_retry_after_s,
            name="batch queue",
            metrics=self.metrics,
        )
        # live queue-depth gauge: pending = submitted and unresolved
        # (queued OR executing), sampled at /metrics render time
        self.metrics.gauge(
            "flyimg_batcher_queue_depth"
            f'{{controller="{escape_label_value(name)}"}}',
            "Pending (queued or executing) submissions per controller",
            fn=lambda: self.admission.pending,
        )
        # failure containment (docs/resilience.md): bounded whole-batch
        # retry for transient errors, bisection isolation for poison
        # members, TTL'd quarantine of repeat offenders (0 = disabled)
        self.batch_retries = max(0, int(batch_retries))
        self.bisect_enable = bool(bisect_enable)
        self.quarantine = (
            QuarantineTable(quarantine_ttl_s)
            if quarantine_ttl_s and quarantine_ttl_s > 0
            else None
        )
        # backoff source for batch-level retries (full jitter, same policy
        # the edge retries use); tests stub .sleep for determinism
        self._retry_policy = RetryPolicy(
            max_attempts=self.batch_retries + 1
        )
        # executor self-healing: a dead executor thread is always
        # replaced at the next submission; a wedged one (inside _execute
        # longer than this bound) is replaced too when the bound is > 0
        self.executor_wedge_timeout_s = float(executor_wedge_timeout_s)
        self._busy_since: Optional[float] = None
        self._busy_owner: Optional[threading.Thread] = None
        self._quarantine_seq = itertools.count()
        self._batch_seq = 0  # batch-id counter (executor thread only)
        self._groups: Dict[Tuple, _Group] = {}
        # host blocks of launches that have run, newest first, for the next
        # groups of their shapes (``_take_block`` / ``_keep_block``). Two
        # at most of each dtype, whatever their shapes: one filling while
        # one is in flight is how launches overlap, and a lone launch
        # beside a full one makes a second block too; a third is let go
        self._spare_blocks: List[np.ndarray] = []
        self._lock = threading.Condition()
        self._stop = False
        # double buffering (see module docstring): dispatch up to
        # pipeline_depth batches before blocking on the oldest readback.
        # depth 1 restores strict launch->read->launch serialization.
        # Readbacks run on per-batch DAEMON threads, not a pool: a
        # hung device->host read can be unkillable, and pool
        # workers would block both close() and interpreter exit on it
        # (ThreadPoolExecutor threads are joined at shutdown).
        self._pipeline_depth = max(1, int(pipeline_depth))
        self._inflight = threading.Semaphore(self._pipeline_depth)
        self._inflight_batches: List[List[_Pending]] = []
        # True between installing a replacement executor (under the lock)
        # and its first scheduling in _run: an installed-but-unstarted
        # thread is not alive, and without this flag a concurrent
        # submitter would mis-read it as dead and heal AGAIN
        self._executor_pending = False
        # True while a backend switch is in progress (the device
        # supervisor's failover/re-promotion): launches hold — a batch
        # dispatched against a backend being cleared would crash —
        # while submissions keep queueing normally
        self._paused = False
        self._spawn_executor().start()

    def _spawn_executor(self) -> threading.Thread:
        """Install (or, from self-healing, replace) THE executor thread
        and return it UNSTARTED — callers start it outside the lock
        (``Thread.start`` blocks on the new OS thread coming up;
        flylint: lock-held-blocking-call). ``self._thread`` identity
        doubles as the supersession marker: a replaced thread notices
        ``self._thread is not me`` and exits; the not-yet-started
        replacement is safe to install under the lock because its first
        action in ``_run`` is to take the lock itself."""
        self._thread = threading.Thread(
            target=self._run, name="flyimg-batcher", daemon=True
        )
        self._executor_pending = True
        return self._thread

    # ------------------------------------------------------------------

    def submit(
        self,
        image: np.ndarray,
        plan: TransformPlan,
        src_window: Optional[Tuple[int, int]] = None,
    ) -> Future:
        """Queue one image+plan; resolves to the output array, of the
        image's dtype (``uint8``, or ``uint16`` for 16-bit samples).

        The member's pixels are copied into its launch's padded host block
        HERE, on the caller's thread, before the future is returned: under
        the lock the member joins its group and is given the next slot of
        the group's block, outside it the frame is copied there
        (``_copy_in``), and the landing is recorded under the lock again.
        The callers of a filling launch are parked until it has run, so the
        copies of a launch run beside one another and beside the decodes
        that fill it, and ``_assemble`` finds the block whole. ``image``
        stays the caller's array and the member keeps it until it is
        answered: every recovery path assembles from it.

        ``src_window`` (docs/host-pipeline.md "ROI window math"): the
        image is only the window of the plan's source at this (x, y)
        offset — the ROI-decode contract. Spans are per-member traced
        inputs, so ``_assemble`` shifting them by the offset reproduces
        the full-frame sampling exactly; the window's (smaller) bucketed
        in_shape keys its own program like any other input shape."""
        h, w = int(image.shape[0]), int(image.shape[1])
        needs_resample = (
            plan.resize_to is not None
            or plan.extent is not None
            or plan.extract is not None
        )
        if src_window is not None:
            wx, wy = int(src_window[0]), int(src_window[1])
            if (
                wx < 0 or wy < 0
                or wx + w > plan.src_size[0] or wy + h > plan.src_size[1]
            ):
                raise ValueError(
                    f"src_window {(wx, wy)} + image {(w, h)} exceeds "
                    f"plan src {plan.src_size}"
                )
            if not needs_resample:
                # only the windowed resample consumes spans; a pixel-op
                # or bare-rotate plan reads the whole frame
                raise ValueError(
                    "src_window requires a resample/extract plan"
                )
        elif plan.src_size != (w, h):
            raise ValueError("plan src_size does not match image dims")
        layout = plan_layout(plan)
        # arbitrary-angle rotate runs shape-bucketed with traced geometry
        # (rotate_image_dynamic) UNLESS (a) an extent pad fixed the frame
        # to a static canvas first — the static rotate is already shared —
        # or (b) a conv op follows the rotate: on a bucketed frame those
        # would blur the background fill across the valid-region edge,
        # where the exact-shape path edge-replicates (visible halo)
        rotate_dynamic = (
            plan.rotate is not None
            and layout.pad_canvas is None
            and plan.blur is None
            and plan.sharpen is None
            and plan.unsharp is None
        )
        final_true = final_extent(plan, layout)
        needs_slice = False
        if needs_resample:
            in_shape = (_bucket_dim(h), _bucket_dim(w))
            if plan.extent is not None or (
                plan.rotate is not None and not rotate_dynamic
            ):
                # crop/extent path: every member lands on the identical
                # static extent. Static rotate (conv post-ops) keeps the
                # exact per-aspect output so nothing pads the frame.
                resample_out = layout.resample_out
            else:
                # fit path: output height varies with source aspect; bucket
                # the static output so mixed-aspect members share one
                # program (the valid region is sliced per member below).
                # Padding rows replicate the edge row (clamped sampling), so
                # convolutional post-ops see 'edge' padding — benign; a
                # dynamic rotate samples only the valid region regardless.
                resample_out = (
                    _bucket_dim(layout.resample_out[0], 64),
                    _bucket_dim(layout.resample_out[1], 64),
                )
                needs_slice = (
                    rotate_dynamic or resample_out != layout.resample_out
                )
        elif plan.rotate is None or rotate_dynamic:
            # pixel-op-only and rotate plans ride input buckets too
            # (edge-replicate fill in _fill_slot keeps convolutional ops
            # correct; dynamic rotate never samples padding). The valid
            # region is sliced per member. Same policy as ops/compose.py.
            in_shape = (_bucket_dim(h), _bucket_dim(w))
            resample_out = None
            needs_slice = rotate_dynamic or in_shape != (h, w)
        else:
            # static rotate (conv post-ops) without resample: exact
            # frame, DELIBERATELY unbucketed — bucket padding would
            # blur the background fill across the valid-region edge
            # (visible halo) and the rotate bbox derives from the full
            # frame; same accepted jax-retrace-hazard as run_plan's
            # exact-frame branch (ops/compose.py).
            # flylint: disable=jax-retrace-hazard
            in_shape = (h, w)
            resample_out = None
        # kernel-variant policy from the member's TRUE geometry (the
        # serving-wide resample_kernel knob): members whose geometry
        # needs a different K bucket land in different groups, exactly
        # like members in different input-shape buckets (docs/kernels.md)
        band_taps = None
        if needs_resample:
            band_taps = select_band_taps(
                kernel_mode(), plan.filter_method, in_shape,
                layout.span_y, layout.span_x, layout.out_true,
            )
        device_plan = plan.device_plan()
        # 16-bit samples keep 16 bits; anything else is copied into a
        # uint8 block, as it always was
        depth = 16 if image.dtype == np.uint16 else 8
        key = (
            in_shape, resample_out, layout.pad_canvas, layout.pad_offset,
            device_plan, rotate_dynamic, band_taps, depth,
        )
        future: Future = Future()
        submit_span = tracing.current_span()
        pending = _Pending(
            image=image,
            plan=plan,
            future=future,
            enqueued_at=time.monotonic(),
            final_true=final_true,
            needs_slice=needs_slice,
            trace=tracing.current_trace(),
            parent_span_id=(
                submit_span.span_id if submit_span is not None else None
            ),
            src_window=src_window,
        )
        base_key = key
        # quarantine short-circuit: recently-poison work executes as a
        # forced singleton (nonce-suffixed key -> its own group) so a hot
        # bad input cannot re-poison a fresh shared batch every tick. The
        # full-image digest is only computed when THIS plan key has a
        # live quarantine entry — unrelated submissions (and the
        # fault-free hot path) pay one dict lookup.
        if self.quarantine is not None and self.quarantine.has_prefix(
            base_key
        ):
            pending.fp_digest = _image_digest(image)
            if self.quarantine.hit((base_key, pending.fp_digest)):
                self.metrics.record_quarantine_hit()
                tracing.add_event(
                    "quarantine.hit",
                    controller=self.name,
                    digest=pending.fp_digest,
                )
                key = base_key + (
                    ("__quarantine__", next(self._quarantine_seq)),
                )
        group_key = key

        def make_group() -> _Group:
            block, kept = self._take_block(in_shape, _SAMPLE_DTYPES[depth])
            return _Group(
                key=group_key,
                in_shape=in_shape,
                resample_out=resample_out,
                pad_canvas=layout.pad_canvas,
                pad_offset=layout.pad_offset,
                device_plan=device_plan,
                rotate_dynamic=rotate_dynamic,
                band_taps=band_taps,
                depth=depth,
                base_key=base_key,
                block=block,
                block_kept=kept,
            )

        reserved = self._admit_and_enqueue(group_key, pending, make_group)
        if reserved is not None:
            self._copy_in(*reserved, pending)
        return future

    def _take_block(self, in_shape: Tuple[int, int], dtype=np.uint8):
        """The host block of a new transform group (caller holds the lock)
        and whether it has carried a launch before: the spare of exactly
        this shape and dtype where there is one, else a fresh ``np.zeros``
        (whose untouched pages cost nothing until a member is copied into
        them). A spare of another shape or dtype is left where it is.
        Nothing to set: what happens depends only on what launches of this
        shape have left behind. ``flyimg_batch_blocks_total{from=}`` counts
        either kind."""
        shape = (self._padded_batch(self.max_batch), *in_shape, 3)
        for i, spare in enumerate(self._spare_blocks):
            if spare.shape == shape and spare.dtype == dtype:
                del self._spare_blocks[i]
                self.metrics.record_block("kept")
                return spare, True
        self.metrics.record_block("fresh")
        return np.zeros(shape, dtype=dtype), False

    def _keep_block(self, launch: _Launch) -> None:
        """A launch's host block becomes the controller's spare: called
        when the program has RUN (``_await_launch``), the one point at
        which nothing reads the block on any backend (the CPU backend's
        ``device_put`` may alias the host array, so there the staged inputs
        ARE the block until the output is ready). The two newest of its
        dtype are kept; spares of the other dtype stay as they are."""
        block, launch.block = launch.block, None
        if block is None:
            return
        with self._lock:
            if not self._stop:
                same = [b for b in self._spare_blocks if b.dtype == block.dtype]
                other = [b for b in self._spare_blocks if b.dtype != block.dtype]
                self._spare_blocks = [block, *same[:1], *other]

    def _copy_in(self, group: _Group, block: np.ndarray,
                 pending: _Pending) -> None:
        """The submit-time copy of one member into the slot it was given,
        outside the lock, on the submitting thread; the landing is recorded
        under the lock, and that of the group's last copy in flight with a
        ``notify``, which is what wakes the executor for a group that was
        held back for them (``_group_ready``). A copy
        that raises fails this member alone: it leaves the group, which
        lets go of its block (a slot is empty now), so that the members
        left are copied by ``_assemble``. Timed as
        ``flyimg_batch_member_copy_seconds`` and, on the future, as
        ``copy_times`` (``time.perf_counter()`` start and end), from which
        the handler makes the request's ``device_copy_in`` stage."""
        error = None
        t0 = time.perf_counter()
        try:
            _fill_slot(
                block, pending.slot, pending.image,
                group.resample_out is None, group.block_kept,
            )
        except BaseException as exc:
            # settled below whatever was raised (and re-raised there unless
            # an Exception): a copy left in flight would hold the group
            error = exc
        t1 = time.perf_counter()
        with self._lock:
            group.copying -= 1
            if error is not None:
                group.block = None
                group.members.remove(pending)
                if not group.members and self._groups.get(group.key) is group:
                    del self._groups[group.key]
                self._note_queue_locked()
            if not group.copying:
                # the group's last copy in flight: only now can it be ready
                self._lock.notify()
        if error is not None:
            pending.future.set_exception(error)
            if not isinstance(error, Exception):
                raise error
            return
        pending.future.copy_times = (t0, t1)
        self.metrics.record_member_copy(t1 - t0)

    def submit_aux(self, key: Tuple, payload, runner) -> Future:
        """Queue one item for a batched AUXILIARY program (smart-crop
        scoring, face detection, ...): concurrent submissions sharing
        ``(runner, key)`` execute as ONE ``runner(payloads)`` call on the
        executor thread; an aux group flushes when it is full, when its
        oldest member has waited the deadline, or at once when the executor
        is idle (``_group_ready``).
        ``runner`` must be a stable module-level callable (it is part of
        the group key) returning one result per payload, in order."""
        future: Future = Future()
        submit_span = tracing.current_span()
        pending = _Pending(
            image=payload,
            plan=None,
            future=future,
            enqueued_at=time.monotonic(),
            final_true=(0, 0),
            trace=tracing.current_trace(),
            parent_span_id=(
                submit_span.span_id if submit_span is not None else None
            ),
        )
        full_key = ("aux", runner, key)
        # same admission bound as transform submissions: aux work holds
        # executor time too, so overload must shed it the same way
        self._admit_and_enqueue(
            full_key,
            pending,
            lambda: _Group(
                key=full_key,
                in_shape=(0, 0),
                resample_out=None,
                pad_canvas=None,
                pad_offset=(0, 0),
                device_plan=None,
                runner=runner,
                base_key=full_key,
            ),
        )
        return future

    def _admit_and_enqueue(self, key: Tuple, pending: _Pending, make_group):
        """THE submission path (submit + submit_aux): admission BEFORE
        enqueue — over the bound this raises a typed 503 (load shed) in
        the submitter's thread; the slot frees when the future resolves,
        however it resolves — then group get-or-create + append under the
        lock, releasing the admission slot if enqueue itself fails. Where
        the group owns a block with a slot left, the member is given the
        next one and ``(group, block)`` is returned: the caller copies the
        member's pixels there (``_copy_in``), and that landing, not this
        enqueue, wakes the executor."""
        self.admission.acquire()
        pending.future.add_done_callback(
            lambda _f: self.admission.release()
        )
        replacement = reserved = None
        try:
            with self._lock:
                if self._stop:
                    raise RuntimeError("batcher is closed")
                replacement = self._maybe_heal_executor_locked()
                group = self._groups.get(key)
                if group is None:
                    group = make_group()
                    self._groups[key] = group
                block = group.block
                if block is not None and len(group.members) < len(block):
                    # no member of a group that holds its block has been
                    # popped yet, so the next slot is the member's index
                    pending.slot = len(group.members)
                    group.copying += 1
                    reserved = (group, block)
                else:
                    self._lock.notify()
                group.members.append(pending)
                self._note_queue_locked()
        except BaseException:
            if not pending.future.done():
                self.admission.release()
            raise
        finally:
            # start the healed executor OUTSIDE the lock (thread start
            # blocks on OS scheduling; under the lock it would convoy
            # every concurrent submitter) — and in a finally so an
            # enqueue failure can never strand an installed-but-unstarted
            # executor: queued groups would wait forever
            if replacement is not None:
                try:
                    replacement.start()
                except BaseException:
                    # spawn failure: clear the pending marker so the next
                    # submission can attempt healing again
                    with self._lock:
                        self._executor_pending = False
                    raise
        return reserved

    def _note_queue_locked(self) -> None:
        """Tell the gap account whether transform members are queued
        (caller holds the lock)."""
        if self._gaps is not None:
            self._gaps.queued(any(
                g.members and g.runner is None for g in self._groups.values()
            ))

    def _maybe_heal_executor_locked(self) -> Optional[threading.Thread]:
        """Executor self-healing, checked at every submission (caller
        holds the lock): a DEAD executor thread (killed by a
        BaseException escaping a batch) is always replaced; a WEDGED one
        (inside _execute longer than ``executor_wedge_timeout_s``, e.g.
        a device launch hung in the transport) is replaced when that
        bound is set. The replacement re-homes every queued group —
        ``self._groups`` is shared state, not thread state — so later
        submissions stop stranding behind the per-request CPU fallback.
        The superseded thread, if it ever unwedges, sees
        ``self._thread is not me`` and exits; its in-flight futures
        resolve normally (every resolution is done()-guarded).

        Returns the replacement thread UNSTARTED (None when no healing
        happened): the caller must ``start()`` it after releasing the
        lock — starting a thread blocks, and blocking under this lock
        convoys every submitter (flylint lock-held-blocking-call)."""
        if self._stop or self._executor_pending:
            return None
        reason = None
        if not self._thread.is_alive():
            reason = "dead"
        elif (
            self.executor_wedge_timeout_s > 0
            and self._busy_since is not None
            and time.monotonic() - self._busy_since
            > self.executor_wedge_timeout_s
        ):
            reason = "wedged"
        if reason is None:
            return None
        self.metrics.record_executor_restart(reason)
        tracing.add_event(
            "executor_restart", reason=reason, controller=self.name
        )
        if reason == "wedged":
            # a thread wedged AFTER acquiring a pipeline slot (e.g. hung
            # inside the device dispatch) never releases it; abandon the
            # old semaphore with the wedged thread so the replacement
            # gets full pipeline depth. Release paths release the
            # semaphore instance they acquired, so late releases from
            # superseded threads land on the abandoned object harmlessly.
            # (A DEAD thread always released its slot on the way out —
            # its semaphore stays live for the in-flight drain threads.)
            self._inflight = threading.Semaphore(self._pipeline_depth)
        self._busy_since = None
        self._busy_owner = None
        return self._spawn_executor()

    def _touch_busy(self) -> None:
        """Refresh the wedge-detection progress clock. The wedge timeout
        bounds time-without-progress, not total _execute time: a long
        but healthy recovery (backoff sleeps + up to 2·log2 n bisection
        launches, some compiling) must not read as wedged. Owner-guarded:
        recovery launches running on DRAIN threads must not mask a
        genuinely wedged executor."""
        me = threading.current_thread()
        with self._lock:
            if self._busy_owner is me:
                self._busy_since = time.monotonic()

    def _suspend_busy(self) -> None:
        """Pause the wedge clock across a compile-bearing dispatch: the
        first call of a new program shape compiles synchronously and can
        legitimately take tens of seconds to minutes — it must not read
        as a wedge (a restart would spawn a second live executor and
        swap the pipeline semaphore under a healthy one). Detection
        re-arms at the next progress touch; a transport hang during a
        compile-miss launch is still caught on any later launch."""
        me = threading.current_thread()
        with self._lock:
            if self._busy_owner is me:
                self._busy_since = None

    def stats(self) -> Dict[str, float]:
        summary = self.metrics.summary()
        images = summary.get("flyimg_images_processed_total", 0.0)
        slots = summary.get("flyimg_batch_slots_total", 0.0)
        # rolling per-controller efficiency (runtime/metrics.py
        # BatchEfficiency): the same vocabulary /debug/perf serves, so
        # bulk sweeps and the HTTP path report identical fields. The
        # occupancy/waste pair comes from the SAME window (occupancy from
        # the since-boot counters next to a rolling waste would read
        # mutually inconsistent on long sweeps); the counter-derived
        # ratio stays available as `cumulative_occupancy`.
        eff = self.metrics.batch_efficiency(self.name).stats()
        return {
            "batches": summary.get("flyimg_batches_total", 0.0),
            "images": images,
            "mean_occupancy": eff["mean_occupancy"],
            "cumulative_occupancy": images / slots if slots else 0.0,
            "padding_waste": eff["padding_waste"],
            "queue_wait_share": eff["queue_wait_share"],
            "batches_per_compile_miss": eff["batches_per_compile_miss"],
        }

    @staticmethod
    def _member_trace_id(members: List[_Pending]) -> Optional[str]:
        """First traced member's trace id — the exemplar the latency
        histograms attach so a bucket links to a retrievable trace."""
        for member in members:
            if member.trace is not None:
                return member.trace.trace_id
        return None

    def close(self, drain_timeout_s: float = 30.0) -> None:
        with self._lock:
            self._stop = True
            self._spare_blocks = []
            self._lock.notify_all()
        # a wedged executor cannot be joined; don't let the join spend
        # more than the caller's whole drain budget waiting for it
        try:
            self._thread.join(timeout=min(5.0, max(drain_timeout_s, 0.1)))
        except RuntimeError:
            # installed-but-not-yet-started replacement (heal race with
            # close): nothing to join, _run exits on the stop flag
            pass
        # BOUNDED drain: resolve every in-flight readback before the
        # controller dies — callers (serving shutdown, bulk sweeps) still
        # hold those futures — but a hung read must not wedge
        # shutdown forever; leftovers get a TimeoutError and the hung
        # daemon reader is abandoned. ONE drain implementation shared
        # with the backend-failover path (drain_inflight).
        self.drain_inflight(
            drain_timeout_s,
            message="batcher closed while a device readback hung",
        )

    def failover_backend(
        self,
        mesh,
        *,
        drain_timeout_s: float = 10.0,
        reason: str = "failover",
    ) -> None:
        """Rebuild the execution backend ONLINE — the device
        supervisor's failover/re-promotion write path
        (runtime/devicesupervisor.py; docs/resilience.md "Backend
        failover"):

        1. bounded drain of in-flight device batches (they resolve via
           the normal containment paths; past the budget leftovers are
           timeout-stamped exactly like a shutdown drain, so no caller
           strands behind a dead backend),
        2. the mesh swaps under the lock together with a fresh pipeline
           semaphore and a replacement executor (queued groups re-home
           to it; the superseded thread notices and exits — the
           self-healing machinery, reused),
        3. BOTH program caches invalidate, so no executable compiled
           against the old backend is ever called again; every program
           recompiles lazily against the new one.

        The controller keeps accepting submissions throughout: new
        groups queue behind the swap and launch on the rebuilt backend.
        """
        # validate BEFORE any state mutates: a bad mesh must raise with
        # the in-flight registry, semaphore, and executor untouched —
        # not after leftovers were cleared but never timeout-stamped
        if mesh is not None and "data" not in mesh.axis_names:
            raise ValueError("batcher mesh needs a 'data' axis")
        # launches hold for the WHOLE rebuild — owned here, not by the
        # caller, so the docstring's "submissions keep queueing and
        # launch on the rebuilt backend" is true for every caller: the
        # still-live old executor must not dispatch a queued group (and
        # re-cache an old-backend executable under unchanged keys)
        # between the invalidation and the swap. Idempotent under the
        # supervisor's own outer pause: the inner resume below fires
        # only after the swap is complete, which is exactly when
        # launches are safe again.
        self.pause_launches()
        try:
            self.drain_inflight(drain_timeout_s)
            # invalidate BEFORE the replacement executor can run: with
            # an unchanged mesh the cache keys are identical across the
            # switch, and a post-start invalidation would let the new
            # executor hit a stale executable compiled against the old
            # backend first
            from flyimg_tpu.ops.compose import invalidate_program_caches

            invalidate_program_caches()
            replacement = None
            with self._lock:
                self.mesh = mesh
                self._n_devices = (
                    int(mesh.shape["data"]) if mesh is not None else 1
                )
                # a batch wedged against the dead backend never releases
                # its pipeline slot: abandon the old semaphore with it
                # (releases land on the captured instance harmlessly,
                # same as the wedge-heal path)
                self._inflight = threading.Semaphore(self._pipeline_depth)
                self._busy_since = None
                self._busy_owner = None
                if not self._stop and not self._executor_pending:
                    replacement = self._spawn_executor()
        finally:
            self.resume_launches()
        self.metrics.record_executor_restart(reason)
        tracing.add_event(
            "executor_restart", reason=reason, controller=self.name
        )
        if replacement is not None:
            try:
                replacement.start()
            except BaseException:
                with self._lock:
                    self._executor_pending = False
                raise

    def pause_launches(self) -> None:
        """Hold new device launches (submissions keep queueing) while a
        backend switch is in progress — the window between clearing the
        old backend and installing the rebuilt executor must not see a
        launch against either backend (runtime/devicesupervisor.py).
        Pair with ``resume_launches`` in a finally."""
        with self._lock:
            self._paused = True

    def resume_launches(self) -> None:
        with self._lock:
            self._paused = False
            self._lock.notify_all()

    def drain_inflight(
        self,
        drain_timeout_s: float,
        message: str = "device batch abandoned during backend failover",
    ) -> None:
        """THE bounded in-flight drain (one copy: backend failover /
        re-promotion AND shutdown ``close()`` share it): wait for every
        in-flight device batch to resolve; past the budget, leftovers
        are timeout-stamped with ``message`` and deregistered. Exposed
        separately from ``failover_backend`` because RE-promotion must
        drain the healthy CPU batches BEFORE the process backend
        switches — clearing backends under live in-flight arrays is the
        damage the drain exists to prevent
        (runtime/devicesupervisor.py)."""
        deadline = time.monotonic() + max(float(drain_timeout_s), 0.0)
        while time.monotonic() < deadline:
            with self._lock:
                if not self._inflight_batches:
                    return
            time.sleep(0.05)
        with self._lock:
            leftovers = [
                m for batch in self._inflight_batches for m in batch
            ]
            # abandoned batches leave the registry NOW: their (possibly
            # transport-hung) drain threads' removals are membership-
            # guarded, and close() must not wait a second budget on them
            self._inflight_batches = []
        for member in leftovers:
            try:
                member.future.set_exception(TimeoutError(message))
            except Exception:
                pass  # a drain thread won the race and resolved it

    # ------------------------------------------------------------------

    def _run(self) -> None:
        me = threading.current_thread()
        with self._lock:
            if self._thread is me:
                self._executor_pending = False
        while True:
            group = None
            with self._lock:
                if self._thread is not me:
                    # superseded (self-healing or a backend-failover
                    # rebuild). Forward the wakeup first: submit()'s
                    # notify() wakes ONE waiter, and if that waiter is
                    # this stale thread, exiting without re-notifying
                    # would leave the LIVE executor parked forever with
                    # work queued (lost-wakeup; pinned by
                    # tests/test_device_supervisor.py)
                    self._lock.notify()
                    return
                while not self._stop and (
                    self._paused or not self._ready_group()
                ):
                    # wake at the earliest deadline among queued members.
                    # While PAUSED, deadlines are irrelevant (launches
                    # hold regardless) and an already-expired member
                    # would make _next_deadline() return 0 — a hot spin
                    # for the whole switch window; resume_launches'
                    # notify_all is the wake signal instead.
                    timeout = (
                        None if self._paused else self._next_deadline()
                    )
                    self._lock.wait(timeout=timeout)
                    if self._thread is not me:
                        self._lock.notify()  # pass the baton (see above)
                        return
                if self._stop and not any(
                    g.members for g in self._groups.values()
                ):
                    return
                group = self._pop_ready_group()
                if group is not None:
                    # wedge detection base: how long THIS thread has been
                    # inside _execute (cleared below, owner-guarded so a
                    # replacement's accounting is never clobbered)
                    self._busy_since = time.monotonic()
                    self._busy_owner = me
                    # register the batch as in flight BEFORE any dispatch
                    # work: close()'s drain snapshot must see a batch
                    # whose dispatch is still executing (or wedged at a
                    # fault gate) and timeout-stamp its futures, instead
                    # of returning while callers block forever
                    self._inflight_batches.append(group.members)
            if group is None:
                continue
            handed_off = False
            try:
                handed_off = self._execute(group)
            except Exception as exc:  # pragma: no cover - _execute
                # contains its own failure handling; this is the last
                # line keeping the singleton executor alive
                self._fail_members(group.members, exc)
            except BaseException as exc:
                # SystemExit/KeyboardInterrupt-class: the thread dies,
                # but its batch must not die silently — and the next
                # submission's heal check replaces the executor
                self._fail_members(
                    group.members,
                    RuntimeError(f"batch executor died: {exc!r}"),
                )
                self._clear_busy(me)
                raise
            finally:
                if not handed_off:
                    # every non-pipelined outcome (aux batch, recovery,
                    # dispatch failure, executor death) resolved the
                    # members on this thread; a handed-off batch stays
                    # registered until its drain thread finishes
                    with self._lock:
                        if group.members in self._inflight_batches:
                            self._inflight_batches.remove(group.members)
            self._clear_busy(me)

    def _clear_busy(self, me: threading.Thread) -> None:
        with self._lock:
            if self._busy_owner is me:
                self._busy_since = None
                self._busy_owner = None

    @staticmethod
    def _fail_members(members: List[_Pending], exc: BaseException) -> None:
        for member in members:
            if not member.future.done():
                member.future.set_exception(exc)

    def _group_ready(self, group: _Group, now: float,
                     total_pending: int) -> bool:
        """The ONE flush-readiness predicate (used by both the wait loop and
        the pop — drift between two copies would make _run busy-spin):
        batch full, deadline expired, or the idle-executor fast path. The
        fast path: the executor thread IS the device owner, so evaluating
        this means the chip is idle — holding a single request for the
        deadline buys no batching (any later arrival lands in the next
        batch, which forms while this one executes). Cuts sparse-traffic
        p99 by deadline_ms (SURVEY.md section 7 hard part 2). An AUX group
        takes the fast path whatever else is pending: its runner holds this
        thread to the end, so what arrives meanwhile forms the next aux
        launch, up the power-of-two ladder, and the deadline (which a bulk
        deployment sets to seconds so that a transform launch fills) would
        only hold a post-pass back while the executor has nothing to run.
        A lone TRANSFORM request takes the fast path only where the last
        transform launch was not full: after a full one the callers are a
        saturated stream (a bulk job's workers, in step), the rest of the
        launch is on its way, and a lone launch would make the next full
        launch wait for its caller's whole turnaround (on a TPU v5e, a
        16-bit PNG cycle of 32 callers ran 10.5 s with one and 8.2 s
        without: PERF.md section 6). The deadline still bounds the wait,
        and that is its cost: a job's last lone request after a full launch
        waits out ``deadline_ms``.
        A group with a submit-time copy in flight is not ready whatever
        else holds: no launch reads a slot whose copy has not landed (a copy
        is short, and the landing of the group's last one notifies)."""
        if group.copying:
            return False
        if len(group.members) >= self.max_batch:
            return True
        if now - group.members[0].enqueued_at >= self.deadline_s:
            return True
        return self.lone_flush and (
            group.runner is not None
            or (total_pending == 1 and not self._last_launch_full)
        )

    def _ready_group(self) -> bool:
        now = time.monotonic()
        total_pending = sum(len(g.members) for g in self._groups.values())
        return any(
            self._group_ready(group, now, total_pending)
            for group in self._groups.values()
            if group.members
        )

    def _next_deadline(self) -> Optional[float]:
        """Seconds until the earliest deadline among the queued groups
        that a deadline could flush. A group with a copy in flight is not
        one: the landing's ``notify`` wakes the executor, and an expired
        deadline of such a group would otherwise read 0, a busy wait for
        as long as the copy lasts."""
        now = time.monotonic()
        deadlines = [
            group.members[0].enqueued_at + self.deadline_s - now
            for group in self._groups.values()
            if group.members and not group.copying
        ]
        if not deadlines:
            return None
        return max(min(deadlines), 0.0)

    def _pop_ready_group(self) -> Optional[_Group]:
        now = time.monotonic()
        total_pending = sum(len(g.members) for g in self._groups.values())
        best = None
        best_score = None
        starving = None
        starving_age = 0.0
        for key, group in list(self._groups.items()):
            if not group.members:
                self._groups.pop(key, None)
                continue
            if not self._group_ready(group, now, total_pending):
                continue
            age = now - group.members[0].enqueued_at
            # starvation guard: full groups normally win (throughput), but
            # under sustained full-batch traffic that would strand a small
            # group forever. The floor keeps this a LAST resort: batch
            # service time routinely exceeds a few deadlines, so a bare
            # 4x-deadline trigger would fire on nearly every pop under
            # load and collapse the fullest-group policy into oldest-first
            if age >= max(4.0 * self.deadline_s, 0.25) and age > starving_age:
                starving, starving_age = key, age
            full = len(group.members) >= self.max_batch
            score = (1 if full else 0, len(group.members))
            if best_score is None or score > best_score:
                best, best_score = key, score
        if starving is not None:
            best = starving
        if best is None:
            return None
        group = self._groups[best]
        take_n = min(self.max_batch, len(group.members))
        mem_cap = None
        if group.runner is None and self.governor is not None:
            # memory-governor admission (runtime/memgovernor.py): cap
            # the take so the PADDED launch's predicted peak HBM fits
            # the device budget and the family's capacity ceiling — the
            # remainder stays queued and pops as its own smaller launch
            cap = self.governor.member_cap(
                group.base_key or group.key, group.in_shape, take_n,
                self._padded_batch,
            )
            if cap is not None and cap < take_n:
                mem_cap = take_n = cap
                self.governor.record_presplit()
        take = group.members[:take_n]
        group.members = group.members[take_n:]
        if not group.members:
            self._groups.pop(best, None)
        if group.runner is None:
            self._last_launch_full = take_n >= self.max_batch
        # the block goes with the launch; what stays queued (a pre-split's
        # remainder, members beyond the block) is copied by _assemble at
        # its own pop
        block, group.block = group.block, None
        ready = _Group(
            key=group.key,
            in_shape=group.in_shape,
            resample_out=group.resample_out,
            pad_canvas=group.pad_canvas,
            pad_offset=group.pad_offset,
            device_plan=group.device_plan,
            members=take,
            rotate_dynamic=group.rotate_dynamic,
            band_taps=group.band_taps,
            depth=group.depth,
            runner=group.runner,
            base_key=group.base_key,
            mem_cap=mem_cap,
            block=block,
        )
        self._note_queue_locked()
        return ready

    # ------------------------------------------------------------------

    @staticmethod
    def _attach_batch_span(members: List[_Pending], span_obj) -> List:
        """Fan the SHARED batch span back into every member request's
        trace (same span id everywhere), re-parented under the span each
        member had active at submit time. Returns the attached copies:
        the attach has to precede the members' resolution (a resolved
        request may finish its trace at once), so the one phase that
        follows it, ``resolve``, is written onto the copies afterwards
        (``_publish_resolve``)."""
        return [
            member.trace.attach_shared(span_obj, member.parent_span_id)
            for member in members
            if member.trace is not None
        ]

    def _start_batch_span(self, name: str, n: int, batch: int,
                          members: List[_Pending],
                          launch: Optional[_Launch] = None):
        """Mint the shared span for one batch launch — only when at least
        one member is traced (the untraced path must stay free).
        ``launch`` carries the launch's captured batch id and its fill
        wait; concurrent recovery launches share the counter, so reading
        it live could name the wrong launch."""
        if not any(m.trace is not None for m in members):
            return None
        span_obj = tracing.Span(name)
        span_obj.set_attribute(
            "batch.id", launch.seq if launch is not None else self._batch_seq
        )
        span_obj.set_attribute("batch.occupancy", n)
        span_obj.set_attribute("batch.size", batch)
        span_obj.set_attribute("batch.padded_slots", batch - n)
        if launch is not None:
            queue_wait_s = launch.queue_wait_s
        else:
            queue_wait_s = time.monotonic() - min(
                m.enqueued_at for m in members
            )
        span_obj.set_attribute("batch.queue_wait_s", round(queue_wait_s, 6))
        return span_obj

    def _end_batch_span(self, span_obj, members: List[_Pending],
                        launch: Optional[_Launch] = None,
                        exc: Optional[BaseException] = None) -> List:
        """End the shared span (as an error when ``exc`` is given), put
        the launch's phases on it and attach it to every member trace."""
        if span_obj is None:
            return []
        if exc is not None:
            span_obj.add_event(
                "exception", type=type(exc).__name__, message=str(exc)
            )
        span_obj.end("error" if exc is not None else None)
        if launch is not None:
            launch.annotate_span(span_obj)
        return self._attach_batch_span(members, span_obj)

    @staticmethod
    def _flight_plan_key(group: _Group, fn=None) -> Optional[str]:
        """The flight-recorder's plan identity for one launch: the
        program handle's ledger key (joins /debug/plans) for transform
        launches, an ``aux:<runner>`` tag for auxiliary batches."""
        if group.runner is not None:
            return f"aux:{getattr(group.runner, '__name__', 'aux')}"
        return fn.ledger_key if fn is not None else None

    def _record_flight(self, group: _Group, members: List[_Pending],
                       launch: _Launch, *, fn=None,
                       error: Optional[str] = None,
                       mem_event: Optional[str] = None) -> Optional[Dict]:
        """One flight-recorder entry per launch resolution (primary,
        recovery, aux, and failures alike), from the launch's one record.
        No recorder wired -> one None check; the record itself is a dict
        append. With a memory governor attached, every device-launch
        record also carries the predicted peak HBM vs the configured
        budget, and ``mem_event`` tags governor interventions
        (``presplit``/``ceiling`` launches, ``oversize`` failures) so
        post-incident triage can replay the admission decisions from the
        flight alone. Returns the recorder's row (``_publish_resolve``
        fills its ``resolve_s`` once the members are resolved)."""
        if self.flight_recorder is None:
            return None
        predicted_bytes = budget_bytes = None
        if (
            self.governor is not None
            and self.governor.enabled
            and group.runner is None
        ):
            predicted_bytes = self.governor.predict_bytes(
                group.base_key or group.key, launch.capacity, group.in_shape
            )
            budget_bytes = self.governor.device_budget_bytes or None
        if mem_event is None and group.mem_cap is not None:
            mem_event = "presplit"
        return self.flight_recorder.record(
            controller=self.name,
            batch_id=launch.seq,
            plan_key=self._flight_plan_key(group, fn),
            occupancy=launch.images,
            capacity=launch.capacity,
            phases=launch.fields(),
            compile_hit=launch.compile_hit,
            kind=launch.kind,
            trace_id=self._member_trace_id(members),
            error=error,
            predicted_bytes=predicted_bytes,
            budget_bytes=budget_bytes,
            mem_event=mem_event,
        )

    def _publish_resolve(self, launch: _Launch, row: Optional[Dict],
                         span_copies: List) -> None:
        """The ``resolve`` phase is over only after every sink had to be
        fed (a resolved request may read the counters, the flight ring or
        its own trace at once), so it is written late: its histogram,
        the flight row's ``resolve_s`` (the key is there from the start:
        only the value changes) and the attached span copies."""
        seconds = launch.seconds("resolve")
        if seconds is None:
            return
        if not launch.aux:
            self.metrics.record_launch_resolve(
                seconds, launch.cpu_s.get("resolve")
            )
        if row is not None:
            row["resolve_s"] = round(seconds, 6)
        for copy in span_copies:
            copy.set_attribute("batch.resolve_s", round(seconds, 6))

    def _execute(self, group: _Group):
        """Run one popped group. Returns True when the batch was handed
        off to a drain thread (it stays registered in
        ``_inflight_batches`` until the drain finishes); every other
        outcome resolves the members synchronously and returns falsy so
        ``_run`` deregisters the batch."""
        members = group.members
        n = len(members)
        # the block is this launch's alone: taken off the group here, so
        # that every recovery sub-launch (which gets the group) assembles
        # from the members' own arrays. The launch's record holds it until
        # the program has run, then it is the controller's spare
        # (``_keep_block``); a launch that fails lets it go
        block, group.block = group.block, None
        # capture the id under the lock: drain-thread recovery launches
        # share the counter, and the span attribute + profiler
        # annotation below must name THIS launch, not whichever
        # increment happened last
        with self._lock:
            self._batch_seq += 1
            seq = self._batch_seq
        # fault hook: a blocking plan here wedges the executor thread —
        # the scenario the wedge-restart self-healing and the handler's
        # CPU fallback defend against (flyimg_tpu/testing/faults.py). A
        # RAISING plan routes through the same classify/retry/bisect
        # recovery as a real launch failure.
        try:
            faults.fire("batcher.execute", key=group.key, n=n)
        except Exception as exc:
            self._recover(group, members, exc)
            return
        # the launch's one record (_Launch): the fill wait ends here, at
        # the pop, BEFORE the batch is assembled — queue_wait_s is the
        # batch-efficiency record's "how long did batching cost" half
        # (the other half is device_s, measured at readback)
        if group.runner is not None:
            self._execute_aux(
                group, members,
                _Launch(seq, members, kind="aux", aux=True,
                        controller=self.name),
            )
            return
        launch = _Launch(seq, members, gaps=self._gaps, depth=group.depth)
        launch.block = block
        span_obj = None
        fn = None
        profiler_poked = False
        try:
            with launch.phase("assemble", cpu=True):
                launch.capacity, arrays = self._assemble(
                    group, members, block
                )
            batch = launch.capacity
            fn, launch.compile_hit = self._program(group, batch)
            # fault hook: a plan raising an XLA-style RESOURCE_EXHAUSTED
            # here models device OOM at dispatch — the failure routes
            # through _recover's OVERSIZE branch (cap the family
            # ceiling, re-launch smaller), never through quarantine
            faults.fire("batcher.oom", key=group.key, n=n, batch=batch)
            span_obj = self._start_batch_span(
                "device_execute", n, batch, members, launch
            )
            if span_obj is not None:
                span_obj.set_attribute(
                    "program.compile_cache",
                    "hit" if launch.compile_hit else "miss",
                )
                if group.mem_cap is not None:
                    # the pre-split happened on the executor thread with
                    # no ambient trace — the decision rides the shared
                    # batch span into every member trace instead
                    span_obj.add_event("mem.presplit", cap=group.mem_cap)
            # bound the pipeline: at most pipeline_depth batches between
            # dispatch and completed readback (memory + fairness).
            # Capture the semaphore INSTANCE: wedge self-healing may swap
            # self._inflight, and every release must land on the object
            # this launch acquired from.
            inflight = self._inflight
            # waiting for a slot is backpressure, not a wedge: pause the
            # clock so slow-but-alive drains (long recoveries, compiles)
            # holding both slots cannot trigger a spurious restart
            self._suspend_busy()
            with launch.phase("slot_wait"):
                inflight.acquire()
            self._touch_busy()
            try:
                # the device side of the launch's record: the staging
                # call here returns before the copy has happened, so the
                # h2d phase is closed on the drain thread, which waits
                # for the staged inputs first (the executor never does),
                # then for the output, then reads it back. The
                # TraceAnnotations label the launch in jax.profiler
                # device traces (/debug/trace, /debug/profile) so
                # profiler timelines and request traces share batch ids.
                if self.profiler is not None:
                    self.profiler.on_batch_start()
                    profiler_poked = True
                self._stage(launch, fn, arrays)
                if not launch.compile_hit:
                    self._suspend_busy()  # synchronous XLA compile ahead
                with jax.profiler.TraceAnnotation(f"flyimg:batch:{seq}"):
                    with launch.phase("dispatch"):
                        dev_out = fn(*launch.dev_args)
                self._touch_busy()  # dispatch returned: progress
                # the batch was registered in _inflight_batches by _run
                # BEFORE dispatch (close()-drain visibility); ownership
                # now passes to the drain thread, whose finally removes it
                threading.Thread(
                    target=self._drain,
                    args=(group, members, dev_out, launch, span_obj,
                          inflight, fn),
                    name="flyimg-batcher-drain",
                    daemon=True,
                ).start()
                return True
            except BaseException:
                launch.dev_args = None
                inflight.release()
                raise
        except Exception as exc:
            launch.block = None
            launch.settle()
            if profiler_poked:
                # a failed dispatch never reaches _drain's finally — the
                # armed capture's batch budget must still decrement or
                # the trace runs to the watchdog deadline
                self.profiler.on_batch_end()
            if span_obj is not None and span_obj.duration_s is None:
                # dispatch failed after the span was minted: the errored
                # span must still reach the member traces (tail sampling
                # keeps exactly these), mirroring the aux/drain paths
                self._end_batch_span(span_obj, members, launch, exc)
            self._record_flight(
                group, members, launch, fn=fn, error=type(exc).__name__,
                mem_event=(
                    "oversize"
                    if classify_batch_error(exc) == OVERSIZE else None
                ),
            )
            self._recover(group, members, exc)

    def _execute_aux(self, group: _Group, members: List[_Pending],
                     launch: _Launch) -> None:
        """One aux launch (host codec batches, smart-crop scoring, face
        detection): ``runner(payloads)`` on this thread. The wedge clock
        keeps running across the runner call (deliberate: aux batches are
        sub-second host codec work, so a long silence there IS the
        hung-native-pool wedge worth re-homing the queue over)."""
        n = len(members)
        span_obj = self._start_batch_span("aux_execute", n, n, members, launch)
        try:
            outputs = self._run_aux(group, members, launch)
            # aux items are requests already counted by their transform
            # batch — separate counters so images_processed/occupancy
            # keep meaning "images through the transform pipeline"
            self.metrics.counter(
                "flyimg_aux_batches_total",
                "Batched auxiliary (scoring/detection) launches",
            ).inc()
            self.metrics.counter(
                "flyimg_aux_items_total",
                "Items through batched auxiliary programs",
            ).inc(n)
            # efficiency window and launch histograms under the aux
            # label (``aux_name``; an aux record skips the transform
            # counters): aux launches have no padding or compile step
            self.metrics.record_launch(
                self.aux_name, launch,
                trace_id=self._member_trace_id(members),
            )
            row = self._record_flight(group, members, launch)
            copies = self._end_batch_span(span_obj, members, launch)
            with launch.phase("resolve", cpu=True):
                self._resolve_members(group, members, outputs, launch)
            self._publish_resolve(launch, row, copies)
        except Exception as exc:
            if span_obj is not None and span_obj.duration_s is None:
                self._end_batch_span(span_obj, members, launch, exc)
            self._record_flight(
                group, members, launch, error=type(exc).__name__
            )
            self._recover(group, members, exc)

    def _run_aux(self, group: _Group, members: List[_Pending],
                 launch: _Launch) -> list:
        """The ``run`` of an aux launch: ``runner(payloads)`` on this
        thread, named as the launch for what the runner annotates, and
        counted by the gap account as an aux runner while it runs."""
        if self._gaps is not None:
            self._gaps.aux(+1)
        try:
            with launch.phase("run"), tracing.launch_scope(launch.prefix):
                outputs = group.runner([m.image for m in members])
        finally:
            if self._gaps is not None:
                self._gaps.aux(-1)
        if len(outputs) != len(members):
            raise RuntimeError(
                f"aux runner returned {len(outputs)} results for "
                f"{len(members)} payloads"
            )
        return outputs

    def _padded_batch(self, n: int) -> int:
        """The padded device batch one launch of ``n`` members actually
        dispatches: the power-of-two occupancy ladder, rounded up to a
        multiple of the data axis (sharded execution needs the batch
        divisible by it, and device counts are not necessarily powers of
        two). Shared by ``_assemble`` and the memory governor's launch
        admission, which must predict against the same padded size."""
        batch = _round_batch(n)
        nd = self._n_devices
        return -(-batch // nd) * nd

    def _assemble(self, group: _Group, members: List[_Pending],
                  block: Optional[np.ndarray] = None):
        """Padded host arrays for ONE launch of ``members`` (shared by
        the pipelined primary path and the synchronous recovery path).
        ``block`` is the popped group's block: where ``members`` are
        exactly its slots ``0..n-1`` in order (a full launch, a deadline
        pop, a lone flush), their pixels are there already, copied by
        ``submit``, and only the four small per-member arrays and the pad
        slots are built here; the images handed on are ``block[:batch]``.
        Anything else (no block: every recovery sub-launch; a remainder
        left by a pre-split; members beyond the block) is zero-filled and
        copied from the members' own arrays, here, and gives the same
        bytes.
        ``flyimg_batch_member_copies_total{at=}`` counts the members of
        either kind. Fires the ``batcher.member`` fault point per member —
        an injected raising plan models a poison member taking down the
        whole launch (the real failure mode: the device cannot say WHICH
        input killed a fused batch program)."""
        n = len(members)
        batch = self._padded_batch(n)
        bh, bw = group.in_shape
        # dynamic-rotate groups widen in_true with the host-computed
        # rotated output extent (ops/compose.py make_program_fn)
        true_w = 4 if group.rotate_dynamic else 2
        early = (
            block is not None
            and batch <= len(block)
            and all(m.slot == i for i, m in enumerate(members))
        )
        if early:
            images = block[:batch]
        else:
            images = np.zeros(
                (batch, bh, bw, 3), dtype=_SAMPLE_DTYPES[group.depth]
            )
        in_true = np.zeros((batch, true_w), dtype=np.float32)
        span_y = np.zeros((batch, 2), dtype=np.float32)
        span_x = np.zeros((batch, 2), dtype=np.float32)
        out_true = np.zeros((batch, 2), dtype=np.float32)
        for i, member in enumerate(members):
            faults.fire(
                "batcher.member",
                key=group.key,
                index=i,
                image=member.image,
            )
            h, w = member.image.shape[:2]
            if not early:
                _fill_slot(
                    images, i, member.image, group.resample_out is None
                )
            layout = plan_layout(member.plan)
            in_true[i, :2] = (h, w)
            if group.rotate_dynamic:
                in_true[i, 2:] = member.final_true
            span_y[i] = layout.span_y
            span_x[i] = layout.span_x
            if member.src_window is not None:
                # ROI decode: the member's pixels are a window of the
                # plan's source — shift the traced span origins so the
                # resample samples the same absolute positions
                span_x[i, 0] -= member.src_window[0]
                span_y[i, 0] -= member.src_window[1]
            out_true[i] = layout.out_true
        for i in range(n, batch):  # pad slots repeat the last member
            images[i] = images[n - 1]
            in_true[i] = in_true[n - 1]
            span_y[i] = span_y[n - 1]
            span_x[i] = span_x[n - 1]
            out_true[i] = out_true[n - 1]
        self.metrics.record_member_copies("submit" if early else "assemble", n)
        return batch, (images, in_true, span_y, span_x, out_true)

    def _program(self, group: _Group, batch: int):
        """Resolve the batched program handle for one launch. The
        compile hit/miss comes from the HANDLE itself
        (``ProgramHandle.is_compiled`` — has this program's executable
        been built yet), not from lru miss-count deltas: the old
        inference mis-labeled launches when concurrent recovery launches
        raced the counter read, and said nothing about a cache-evicted
        handle that will recompile on its next call."""
        fn = build_batched_program(
            batch,
            group.in_shape,
            group.resample_out,
            group.pad_canvas,
            group.pad_offset,
            group.device_plan,
            self.mesh,
            group.rotate_dynamic,
            group.band_taps,
            group.depth,
        )
        compile_hit = fn.is_compiled
        self.metrics.record_compile_event(compile_hit)
        return fn, compile_hit

    @staticmethod
    def _stage(launch: _Launch, fn, arrays) -> None:
        """Start the launch's ``h2d`` phase: hand the assembled arrays to
        the program's handle, which stages them in the form the program
        takes (``ProgramHandle.stage``: the images flat and in pieces,
        views of ``_assemble``'s array, which is the group's block where
        ``submit`` copied the members into it). The call returns before
        the transfers have happened; ``_await_launch`` closes the phase."""
        launch.open("h2d")
        with launch.annotate("h2d"):
            cpu0 = time.thread_time()
            launch.dev_args = fn.stage(arrays)
            launch.cpu_s["h2d"] = time.thread_time() - cpu0
        launch.transfer_bytes["h2d"] = sum(a.nbytes for a in arrays)

    def _resolve_members(self, group: _Group, members: List[_Pending],
                         outputs, launch: _Launch) -> None:
        """Resolve every member future from one launch's outputs.
        done()-guarded THROUGHOUT: one already-settled/cancelled future
        (client gone, shutdown race, a superseded executor finishing
        late) must skip, not raise InvalidStateError mid-loop — which
        previously diverted to the except path and wrongly failed every
        remaining member of the batch. Each future first gets the
        member's own four instants (``launch_times``: queued, its launch
        popped, its result ready, and its ``set_result``, which is
        ``answered``; ``time.perf_counter()``), from which the handler
        fills the request's ``*_queue`` / ``*_run`` stages and its place in
        this loop. Each member's slice, copy and ``set_result`` are the
        annotation ``answer`` of the launch. An answered member lets its
        ``image`` go at once: nothing recovers it after, and the launch's
        other members may take a second to be answered, while its caller
        goes on to decode its next frame."""
        ready = time.perf_counter()
        for i, member in enumerate(members):
            with launch.annotate("answer"):
                result = outputs[i]
                if group.runner is None:
                    if member.needs_slice:
                        th, tw = member.final_true
                        result = result[: int(th), : int(tw)]
                    result = np.ascontiguousarray(result)
                if not member.future.done():
                    member.future.launch_times = (
                        member.enqueued_pc, launch.popped, ready,
                        time.perf_counter(),
                    )
                    member.future.set_result(result)
                    member.image = None

    def _await_launch(self, launch: _Launch, dev_out, fn: ProgramHandle):
        """The device side of one launch after its dispatch, in three
        laps that share their end points: the staged inputs are on the
        device (``h2d`` ends; the inputs are let go at once, so that a
        launch's 4 GiB of them are not held through its read-back), the
        output is ready (``run``; the launch's host block goes to the
        controller here, ``_keep_block``), the output is on the host
        (``d2h``). The inputs are not donated, so waiting on them after the
        dispatch is legal. Returns the output as a host array ``[n, h, w,
        3]`` of samples (``fn.unstage``: a view of the read-back where it
        arrived row-major, which ``launch.readback`` records, whether it
        crossed the link as words or as the samples, which
        ``launch.d2h_word`` records)."""
        with launch.annotate("h2d_wait"):
            jax.block_until_ready(launch.dev_args)
        launch.dev_args = None
        launch.close()
        with launch.annotate("run"):
            jax.block_until_ready(dev_out)
        launch.lap("run")
        self._keep_block(launch)
        with launch.annotate("d2h"):
            out = np.asarray(dev_out)
        launch.lap("d2h")
        launch.transfer_bytes["d2h"] = out.nbytes
        launch.readback = (
            "row_major" if out.flags.c_contiguous else "strided"
        )
        launch.d2h_word = f"u{8 * out.dtype.itemsize}"
        return fn.unstage(out)

    def _launch_done(self, group: _Group, members: List[_Pending],
                     launch: _Launch, fn, span_obj=None,
                     mem_event: Optional[str] = None):
        """Feed every sink from one completed launch's record — cost
        ledger, memory governor, shared span, histograms and efficiency
        window, flight recorder, backend supervisor — all BEFORE the
        members resolve. Returns what ``_publish_resolve`` needs."""
        n, batch = launch.images, launch.capacity
        trace_id = self._member_trace_id(members)
        # per-plan attribution: cumulative device seconds against the
        # program key the cost ledger costed at compile time
        self._ledger.record_launch(
            fn.ledger_key, device_s=launch.device_s, images=n
        )
        if self.governor is not None:
            # governor feedback: a completed readback is the "this batch
            # size fits" signal — and the ledger's compile-time peak
            # estimate (if the family ever compiled) refines the
            # per-member prediction
            family = group.base_key or group.key
            self.governor.observe(
                family, batch, self._ledger.peak_memory(fn.ledger_key)
            )
            self.governor.record_success(family, n)
            if launch.kind == "recovery" and self.governor.has_ceiling(
                family
            ):
                # a clean launch at a live ceiling counts toward the
                # additive-raise probe
                mem_event = "ceiling"
        # the phases ride the SHARED span into every member trace (and
        # the Server-Timing header derives from it)
        copies = self._end_batch_span(span_obj, members, launch)
        self.metrics.record_launch(self.name, launch, trace_id=trace_id)
        row = self._record_flight(
            group, members, launch, fn=fn, mem_event=mem_event
        )
        if self.supervisor is not None:
            # backend evidence for the device supervisor: a completed
            # readback means the backend answered, so any failure storm
            # in progress resets
            self.supervisor.record_batch_success()
        return row, copies

    def _drain(self, group: _Group, members, dev_out, launch: _Launch,
               span_obj=None,
               inflight: Optional[threading.Semaphore] = None,
               fn=None) -> None:
        """Blocking device->host read + future resolution for one
        dispatched batch (runs on a daemon drain thread). ``inflight`` is
        the pipeline semaphore instance this batch acquired from (the
        live one unless wedge self-healing swapped it since). ``launch``
        carries the executor's half of the record (fill, assemble, slot
        wait, the start of h2d, dispatch); the waits are timed here."""
        n, batch = launch.images, launch.capacity
        try:
            faults.fire("batcher.drain", key=group.key, n=n, batch=batch)
            out = self._await_launch(launch, dev_out, fn)
            row, copies = self._launch_done(
                group, members, launch, fn, span_obj
            )
            with launch.phase("resolve", cpu=True):
                self._resolve_members(group, members, out, launch)
            self._publish_resolve(launch, row, copies)
        except Exception as exc:
            launch.dev_args = launch.block = None
            launch.settle()
            if span_obj is not None and span_obj.duration_s is None:
                # not yet ended -> the failure happened before the attach
                # above; record and attach the errored span instead
                self._end_batch_span(span_obj, members, launch, exc)
            self._record_flight(
                group, members, launch, fn=fn, error=type(exc).__name__,
                mem_event=(
                    "oversize"
                    if classify_batch_error(exc) == OVERSIZE else None
                ),
            )
            self._recover(group, members, exc)
        finally:
            launch.settle()
            if self.profiler is not None:
                self.profiler.on_batch_end()
            (inflight if inflight is not None else self._inflight).release()
            with self._lock:
                if members in self._inflight_batches:
                    self._inflight_batches.remove(members)

    # ------------------------------------------------------------------
    # failure containment: classify -> retry (transient) / bisect (poison)

    def _recover(self, group: _Group, members: List[_Pending],
                 exc: Exception) -> None:
        """Blast-radius containment for one failed launch, dispatch OR
        readback side (docs/resilience.md). Runs synchronously on the
        calling thread (executor or drain): the device is the serial
        resource either way, and recovery launches are bounded —
        ``batch_retries`` for transient errors, O(2·log2 n) sub-batches
        for bisection. With both knobs off this degrades to exactly the
        pre-containment behavior: every member fails with ``exc``."""
        live = [m for m in members if not m.future.done()]
        if not live:
            return
        kind = classify_batch_error(exc)
        if self.supervisor is not None:
            # one outcome per failed launch, already classified: only
            # TRANSIENT counts toward a backend-failure storm
            # (runtime/devicesupervisor.py) — poison stays PR-3's
            # bisection problem
            self.supervisor.record_batch_failure(kind)
        span_obj = self._start_batch_span(
            "batch_recovery", len(live), len(live), live
        )
        if span_obj is not None:
            span_obj.set_attribute("recovery.error", type(exc).__name__)
            span_obj.set_attribute("recovery.class", kind)
        status = "ok"
        try:
            if kind == OVERSIZE:
                self._recover_oversize(group, live, exc, span_obj)
                return
            if kind == TRANSIENT and self.batch_retries > 0:
                exc = self._retry_batch(group, live, exc, span_obj)
                if exc is None:
                    return  # a retry resolved every live member
                # retries exhausted — or a retry surfaced a poison error
                kind = classify_batch_error(exc)
            if kind == POISON and self.bisect_enable:
                if len(live) == 1:
                    self._fail_poison(group, live[0], exc, span_obj)
                else:
                    self._bisect(group, live, span_obj)
                return
            status = "error"
            self._fail_members(live, exc)
        finally:
            if span_obj is not None:
                span_obj.end(status)
                self._attach_batch_span(live, span_obj)

    def _retry_batch(self, group: _Group, members: List[_Pending],
                     first_exc: Exception, span_obj) -> Optional[Exception]:
        """Bounded whole-batch retry with full-jitter backoff for
        transient launch failures. Returns None when a retry resolved the
        members, else the error to keep handling (the last transient one,
        or the first non-transient one — handed straight to bisection)."""
        last = first_exc
        for attempt in range(1, self.batch_retries + 1):
            delay = self._retry_policy.backoff(attempt)
            self.metrics.record_batch_retry()
            if span_obj is not None:
                span_obj.add_event(
                    "batch_retry",
                    attempt=attempt,
                    backoff_s=round(delay, 4),
                    error=type(last).__name__,
                )
            if delay > 0:
                self._retry_policy.sleep(delay)
            try:
                self._run_members(group, members)
            except Exception as exc:
                last = exc
                retry_kind = classify_batch_error(exc)
                if self.supervisor is not None:
                    # every failed retry attempt is storm evidence too —
                    # a dead backend fails batch_retries times per batch,
                    # and counting each attempt trips the breaker sooner
                    self.supervisor.record_batch_failure(retry_kind)
                if retry_kind != TRANSIENT:
                    return exc
                continue
            return None
        return last

    def _recover_oversize(self, group: _Group, live: List[_Pending],
                          exc: Exception, span_obj) -> None:
        """OOM-class (RESOURCE_EXHAUSTED) launch failure: the error
        indicts the LAUNCH footprint, not any member — so cap the plan
        family's capacity ceiling (the governor halves it and later
        re-probes upward) and re-launch the same members in smaller
        pieces. A singleton that still OOMs cannot shrink further: it
        fails with a deterministic 503 + Retry-After and is NEVER
        quarantined — the same input may well fit once the ceiling
        expires or HBM pressure clears (docs/resilience.md "Memory
        governor")."""
        cap = None
        if self.governor is not None:
            cap = self.governor.record_oom(
                group.base_key or group.key, len(live)
            )
        if span_obj is not None:
            span_obj.add_event(
                "mem.ceiling", cap=cap, size=len(live),
                error=type(exc).__name__,
            )
        if len(live) == 1:
            self._fail_oversize(live[0], exc)
            return
        self._split_oversize(group, live, span_obj)

    def _fail_oversize(self, member: _Pending, exc: Exception) -> None:
        """Terminal OOM failure of ONE member: a capacity condition, not
        an input property — the member maps to 503 + Retry-After (retry
        is the correct client move once the ceiling re-probes) and never
        enters quarantine."""
        if member.future.done():
            return
        from flyimg_tpu.exceptions import ServiceUnavailableException

        failure = ServiceUnavailableException(
            "device memory exhausted at the smallest possible launch; "
            "the plan family's capacity ceiling was capped — retry "
            "shortly"
        )
        failure.__cause__ = exc
        member.future.set_exception(failure)

    def _split_oversize(self, group: _Group, members: List[_Pending],
                        span_obj, depth: int = 0) -> None:
        """Halving re-launch for an OOM'd batch. Unlike bisection this
        is not a search — EVERY member is presumed innocent; a half that
        still OOMs halves again (tightening the governor's ceiling each
        time), down to singletons. Non-OOM errors surfaced by a smaller
        launch hand off to the existing transient-retry / poison-bisect
        machinery."""
        if span_obj is not None:
            span_obj.add_event("mem.split", size=len(members), depth=depth)
        mid = len(members) // 2
        for part in (members[:mid], members[mid:]):
            live = [m for m in part if not m.future.done()]
            if not live:
                continue
            try:
                self._run_members(group, live)
            except Exception as sub_exc:
                kind = classify_batch_error(sub_exc)
                if kind == OVERSIZE:
                    if self.governor is not None:
                        self.governor.record_oom(
                            group.base_key or group.key, len(live)
                        )
                    if len(live) > 1:
                        self._split_oversize(
                            group, live, span_obj, depth + 1
                        )
                    else:
                        self._fail_oversize(live[0], sub_exc)
                    continue
                if kind == TRANSIENT and self.batch_retries > 0:
                    retried = self._retry_batch(
                        group, live, sub_exc, span_obj
                    )
                    if retried is None:
                        continue
                    sub_exc = retried
                    kind = classify_batch_error(sub_exc)
                if kind == POISON and self.bisect_enable:
                    if len(live) > 1:
                        self._bisect(group, live, span_obj)
                    else:
                        self._fail_poison(group, live[0], sub_exc, span_obj)
                    continue
                self._fail_members(live, sub_exc)

    def _bisect(self, group: _Group, members: List[_Pending],
                span_obj, depth: int = 0) -> None:
        """Recursive bisection isolation: re-execute a failed batch as
        two halves, recursing into whichever halves still fail, down to
        singletons — innocent members resolve on the first passing
        sub-batch, and only the poison member(s) fail. Worst case for one
        poison in n members: 2·ceil(log2 n) extra launches."""
        if span_obj is not None:
            span_obj.add_event("batch_bisect", size=len(members), depth=depth)
        mid = len(members) // 2
        for part in (members[:mid], members[mid:]):
            live = [m for m in part if not m.future.done()]
            if not live:
                continue
            try:
                self._run_members(group, live)
            except Exception as exc:
                if len(live) > 1:
                    self._bisect(group, live, span_obj, depth + 1)
                    continue
                if (
                    classify_batch_error(exc) == TRANSIENT
                    and self.batch_retries > 0
                ):
                    # a device hiccup DURING recovery must not turn an
                    # innocent singleton into a 5xx: give it the same
                    # bounded retry a batch-level transient gets
                    exc = self._retry_batch(group, live, exc, span_obj)
                    if exc is None:
                        continue
                self._fail_poison(group, live[0], exc, span_obj)

    def _fail_poison(self, group: _Group, member: _Pending,
                     exc: Exception, span_obj) -> None:
        """Terminal isolation of ONE member: the failure is request-
        scoped (only this future errors, with the original exception so
        the HTTP layer maps it as any other pipeline failure), and
        poison-classified work is fingerprinted into quarantine so the
        same input cannot re-poison a fresh shared batch within the TTL."""
        digest = None
        if classify_batch_error(exc) == POISON:
            digest = self._quarantine_add(group, member)
            self.metrics.record_poison_isolated()
            if span_obj is not None:
                span_obj.add_event(
                    "poison_isolated",
                    error=type(exc).__name__,
                    digest=digest,
                )
        if not member.future.done():
            member.future.set_exception(exc)

    def _quarantine_add(self, group: _Group, member: _Pending):
        """Fingerprint (base plan key + image digest) one isolated poison
        member. Aux members (no plan/pixels contract) are not
        fingerprintable; quarantine may be disabled entirely."""
        if self.quarantine is None or member.plan is None:
            return None
        if member.fp_digest is None:
            member.fp_digest = _image_digest(member.image)
        self.quarantine.add(
            (group.base_key or group.key, member.fp_digest)
        )
        return member.fp_digest

    def _run_members(self, group: _Group, members: List[_Pending]) -> None:
        """ONE synchronous launch (assemble -> dispatch -> blocking
        readback -> resolve) for the recovery paths; raises on failure.
        Successful recovery launches count in the batch/occupancy metrics
        like primary launches do, with the same phases (no slot wait: a
        recovery launch runs inside its failed launch's slot)."""
        with self._lock:  # drain-thread recoveries race the executor
            self._batch_seq += 1
            seq = self._batch_seq
        self._touch_busy()  # each recovery launch is wedge-clock progress
        launch = _Launch(
            seq, members, kind="recovery", aux=group.runner is not None,
            controller=self.name, gaps=self._gaps, depth=group.depth,
        )
        try:
            self._run_recovery(group, members, launch)
        finally:
            launch.settle()

    def _run_recovery(self, group: _Group, members: List[_Pending],
                      launch: _Launch) -> None:
        """``_run_members``' one launch, whatever its kind."""
        n = len(members)
        if group.runner is not None:
            for i, member in enumerate(members):
                faults.fire(
                    "batcher.member",
                    key=group.key,
                    index=i,
                    image=member.image,
                )
            outputs = self._run_aux(group, members, launch)
            faults.fire("batcher.drain", key=group.key, n=n, batch=n)
            self.metrics.record_launch(
                self.aux_name, launch,
                trace_id=self._member_trace_id(members),
            )
            row, copies = self._record_flight(group, members, launch), []
        else:
            with launch.phase("assemble", cpu=True):
                launch.capacity, arrays = self._assemble(group, members)
            batch = launch.capacity
            fn, launch.compile_hit = self._program(group, batch)
            # same OOM fault hook as the primary path: recovery
            # sub-launches can hit device memory exhaustion too, and must
            # route through the same OVERSIZE handling in their caller
            faults.fire("batcher.oom", key=group.key, n=n, batch=batch)
            if not launch.compile_hit:
                self._suspend_busy()  # synchronous XLA compile ahead
            if self.profiler is not None:
                self.profiler.on_batch_start()
            try:
                self._stage(launch, fn, arrays)
                with jax.profiler.TraceAnnotation(f"flyimg:batch:{launch.seq}"):
                    with launch.phase("dispatch"):
                        dev_out = fn(*launch.dev_args)
                self._touch_busy()  # dispatch returned: progress
                faults.fire("batcher.drain", key=group.key, n=n, batch=batch)
                outputs = self._await_launch(launch, dev_out, fn)
            finally:
                launch.dev_args = None
                if self.profiler is not None:
                    self.profiler.on_batch_end()
            row, copies = self._launch_done(group, members, launch, fn)
        with launch.phase("resolve", cpu=True):
            self._resolve_members(group, members, outputs, launch)
        self._publish_resolve(launch, row, copies)
