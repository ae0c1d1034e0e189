"""Plan -> compiled device program.

The analog of the reference's ImageProcessor::generateCommand + exec
(reference src/Core/Processor/ImageProcessor.php:66-110, Processor.php:44-62),
except the "command" is a fused XLA program:

    uint8 in -> f32 -> windowed resample (MXU einsums) -> [extent pad]
    -> [grayscale] -> [monochrome dither] -> [rotate] -> [unsharp]
    -> [sharpen] -> [blur] -> round/clip -> uint8 out

(uint16 in and out for 16-bit samples: ``make_program_fn``'s ``depth``).

Programs are cached by (plan signature, padded input bucket, output shape):
the per-image geometry (true sizes + source window spans) enters as traced
scalars, so one executable serves every source size that lands in the same
bucket. Stage order matches ImageMagick's left-to-right command-line
application order used by the reference.
"""

from __future__ import annotations

import functools
import inspect
import threading
import time
from dataclasses import dataclass
from functools import lru_cache
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import layout as jax_layout

from flyimg_tpu.ops.color import monochrome_dither, to_grayscale
from flyimg_tpu.ops.filters import gaussian_blur, sharpen as sharpen_op, unsharp_mask
from flyimg_tpu.ops.pad import extent_pad
from flyimg_tpu.ops.resample import (
    SAMPLE_PRECISION,
    kernel_mode,
    resample_image,
    resample_image_banded,
    select_band_taps,
)
from flyimg_tpu.ops.rotate import rotate_image, rotate_image_dynamic
from flyimg_tpu.spec.geometry import gravity_offset
from flyimg_tpu.spec.plan import TransformPlan


@dataclass(frozen=True)
class Layout:
    """Host-resolved geometry for one image under one plan: the source
    window (span per axis) and the valid output extent the device program
    needs as dynamic inputs."""

    span_y: Tuple[float, float]          # (start, size) in source rows
    span_x: Tuple[float, float]          # (start, size) in source cols
    out_true: Tuple[int, int]            # valid (h, w) of resample output
    resample_out: Tuple[int, int]        # static (h, w) of resample stage
    pad_canvas: Optional[Tuple[int, int]] = None   # (w, h) ett pad canvas
    pad_offset: Tuple[int, int] = (0, 0)


def plan_layout(plan: TransformPlan) -> Layout:
    """Collapse extract + resize/crop-fill + extent-crop into one windowed
    resample (see ops/resample.py). Pure host math, no device work."""
    src_w, src_h = plan.src_size
    if plan.extract is not None:
        x0, y0, x1, y1 = plan.extract
        base_x, base_y = float(x0), float(y0)
        eff_w, eff_h = float(x1 - x0), float(y1 - y0)
    else:
        base_x = base_y = 0.0
        eff_w, eff_h = float(src_w), float(src_h)

    if plan.resize_to is not None:
        rw, rh = plan.resize_to
    else:
        rw, rh = int(eff_w), int(eff_h)

    pad_canvas = None
    pad_offset = (0, 0)
    if plan.extent is not None:
        tw, th = plan.extent
        off_x, off_y = gravity_offset(rw, rh, tw, th, plan.gravity)
        if off_x >= 0 and off_y >= 0 and tw <= rw and th <= rh:
            # pure crop: fuse into the resample window
            sx = eff_w / rw
            sy = eff_h / rh
            span_x = (base_x + off_x * sx, tw * sx)
            span_y = (base_y + off_y * sy, th * sy)
            return Layout(span_y, span_x, (th, tw), (th, tw))
        # pad direction (or mixed): resample to (rw, rh) then extent-pad.
        # gravity_offset gives the crop-region offset within the image; the
        # image's position on the larger canvas is its negation.
        pad_canvas = (tw, th)
        pad_offset = (-off_x, -off_y)

    span_x = (base_x, eff_w)
    span_y = (base_y, eff_h)
    return Layout(span_y, span_x, (rh, rw), (rh, rw), pad_canvas, pad_offset)


def _needs_resample(plan: TransformPlan, layout: Layout) -> bool:
    return (
        plan.resize_to is not None
        or plan.extent is not None
        or plan.extract is not None
    )


def make_program_fn(
    resample_out: Optional[Tuple[int, int]],
    pad_canvas: Optional[Tuple[int, int]],
    pad_offset: Tuple[int, int],
    plan: TransformPlan,
    rotate_dynamic: bool = False,
    band_taps: Optional[Tuple[int, int]] = None,
    depth: int = 8,
):
    """The raw (unjitted) device program closure for one op config. Shared
    by the single-image path (build_program jits it) and the batch runtime
    (which vmaps it over a batch axis before jitting).

    With ``rotate_dynamic`` the rotate stage runs on a shape-bucketed frame
    with traced valid dims, so mixed-size rotate traffic shares one
    executable; ``in_true`` is then [h, w, rot_h, rot_w] — valid input dims
    plus the host-computed rotated output extent (see final_extent).

    ``band_taps`` selects the resample formulation: None runs the dense
    [out, in] matrix einsums; ``(Ky, Kx)`` runs the banded K-tap
    gather-contract (ops/resample.py resample_image_banded) with those
    STATIC per-axis band widths — callers derive them from the plan's
    true geometry via ``select_band_taps`` and carry them in the program
    cache key (docs/kernels.md).

    ``depth`` is the bits of a sample in and out: 8 (``uint8``) or 16
    (``uint16``). Every stage works in levels of 255 whatever the depth,
    so that the constants of the pixel ops (dither thresholds, fills,
    unsharp thresholds) hold: 16-bit samples are divided by 257 on the way
    in and multiplied back on the way out, and the resample's contractions
    keep 16 significant bits (``SAMPLE_PRECISION``)."""

    def program(img_u8, in_true, span_y, span_x, out_true):
        # every stage runs under a jax.named_scope: the names reach the
        # HLO's op metadata, so a profile's device ops can be told apart
        # by stage whatever the compiler numbers its fusions
        # (flyimg.resample_rows / flyimg.resample_cols / flyimg.weights
        # are opened inside ops/resample.py)
        x = img_u8.astype(jnp.float32)
        if depth == 16:
            x = x / 257.0
        precision = SAMPLE_PRECISION[depth]
        cur_true = in_true[:2]
        if resample_out is not None:
            if band_taps is not None:
                x = resample_image_banded(
                    x, resample_out, span_y, span_x, out_true,
                    in_true[:2], band_taps, method=plan.filter_method,
                    precision=precision,
                )
            else:
                x = resample_image(
                    x, resample_out, span_y, span_x, out_true, in_true[:2],
                    method=plan.filter_method, precision=precision,
                )
            cur_true = out_true
        if pad_canvas is not None:
            with jax.named_scope("flyimg.pad"):
                x = extent_pad(x, pad_canvas, pad_offset, plan.background)
            cur_true = jnp.array(
                (pad_canvas[1], pad_canvas[0]), jnp.float32
            )
        with jax.named_scope("flyimg.pixel_ops"):
            if plan.colorspace == "gray":
                x = to_grayscale(x)
            elif plan.colorspace == "gray601":
                from flyimg_tpu.ops.color import LUMA_WEIGHTS_601

                x = to_grayscale(x, LUMA_WEIGHTS_601)
            if plan.monochrome:
                x = monochrome_dither(x)
        if plan.rotate is not None:
            with jax.named_scope("flyimg.rotate"):
                if rotate_dynamic:
                    x = rotate_image_dynamic(
                        x, plan.rotate, plan.background, cur_true,
                        in_true[2:4],
                    )
                else:
                    x = rotate_image(x, plan.rotate, plan.background)
        with jax.named_scope("flyimg.pixel_ops"):
            if plan.unsharp is not None:
                r, s, gain, thr = plan.unsharp
                x = unsharp_mask(x, r, s, gain, thr)
            if plan.sharpen is not None:
                r, s, _, _ = plan.sharpen
                x = sharpen_op(x, r, s)
            if plan.blur is not None:
                r, s = plan.blur
                x = gaussian_blur(x, r, s)
            if depth == 16:
                return jnp.clip(
                    jnp.round(x * 257.0), 0.0, 65535.0
                ).astype(jnp.uint16)
            return jnp.clip(jnp.round(x), 0.0, 255.0).astype(jnp.uint8)

    return program


# cached module ref for the per-plan cost ledger (lazy: importing
# flyimg_tpu.runtime at module scope would cycle through the batcher,
# which imports this module)
_costledger_mod: Any = None


def _ledger():
    global _costledger_mod
    if _costledger_mod is None:
        from flyimg_tpu.runtime import costledger as _c

        _costledger_mod = _c
    return _costledger_mod.get_ledger()


def plan_descriptor(plan: TransformPlan, *, in_shape=None, batch=None,
                    resample_out=None, pad_canvas=None,
                    pad_offset=(0, 0), rotate_dynamic=False,
                    band_taps=None, depth=8) -> Dict[str, object]:
    """Compact human-readable program identity for the cost ledger /
    ``/debug/plans`` — which ops the program fuses and at what static
    shapes, without dumping the whole TransformPlan repr. ``kernel``
    names the resample formulation (dense | banded) so dense and banded
    ledger entries are tellable apart at a glance; banded entries also
    carry their static per-axis band widths. Every cache-keyed,
    trace-read component must be representable here — two programs with
    different keys must never produce identical descriptors (the
    flylint ``program-key-drift`` rule holds this to the cache keys
    mechanically), which is why extent entries carry ``pad_offset`` and
    the fill ``background`` alongside the canvas, and a program of 16-bit
    samples its ``depth``."""
    ops = []
    if resample_out is not None:
        ops.append("resample")
    if pad_canvas is not None:
        ops.append("extent_pad")
    if plan.colorspace:
        ops.append(f"colorspace:{plan.colorspace}")
    if plan.monochrome:
        ops.append("monochrome")
    if plan.rotate is not None:
        ops.append("rotate_dynamic" if rotate_dynamic else "rotate")
    if plan.unsharp is not None:
        ops.append("unsharp")
    if plan.sharpen is not None:
        ops.append("sharpen")
    if plan.blur is not None:
        ops.append("blur")
    desc: Dict[str, object] = {"ops": ops or ["copy"]}
    if in_shape is not None:
        desc["in_shape"] = list(in_shape)
    if batch is not None:
        desc["batch"] = int(batch)
    if resample_out is not None:
        desc["resample_out"] = list(resample_out)
        desc["kernel"] = "banded" if band_taps is not None else "dense"
        if band_taps is not None:
            desc["band_taps"] = list(band_taps)
    if pad_canvas is not None:
        desc["pad_canvas"] = list(pad_canvas)
        desc["pad_offset"] = list(pad_offset)
    if pad_canvas is not None or plan.rotate is not None:
        # the fill color is part of the compiled program wherever a
        # canvas (extent pad) or rotate background is painted
        desc["background"] = (
            list(plan.background) if plan.background is not None else None
        )
    desc["filter"] = plan.filter_method
    if depth != 8:
        desc["depth"] = int(depth)
    return desc


#: the most bytes one host-to-device transfer of a launch's images carries.
#: The runtime copies every transfer through a host buffer it maps for the
#: device once, at start (4 GiB on a v5e host), and the bytes its transfer
#: threads have in flight must fit it: one transfer of a 4.73 GB launch (64
#: frames of 24 MP) takes 4-26 s, the same bytes as 64 transfers of 73.9 MB
#: 0.35 s, as 32 of 148 MB 0.37 s, as 16 of 296 MB 0.4-3.2 s, as 8 of 591
#: MB 2.9-3.0 s (PERF.md section 6, PR 28). Half the largest piece that
#: was steady, for a second launch staging beside the first and for hosts
#: with more transfer threads. Small launches (every launch of served
#: thumbnails) stay one transfer.
STAGE_PIECE_BYTES = 128 << 20


def stage_pieces(batch: int, in_shape: Tuple[int, int],
                 sample_bytes: int = 1) -> int:
    """How many pieces a batched program takes its images in: equal runs
    of whole frames along the batch axis, a power of two of them to a
    piece, each piece at most ``STAGE_PIECE_BYTES`` (one frame where a
    single frame is larger) at ``sample_bytes`` a sample. A function of
    the program's static shape alone, so it is settled when the program
    is built."""
    frames = max(
        STAGE_PIECE_BYTES // (in_shape[0] * in_shape[1] * 3 * sample_bytes),
        1,
    )
    frames = 1 << (frames.bit_length() - 1)
    while batch % frames:
        frames //= 2
    return batch // frames


def flat_pieces(images, pieces: int):
    """The staged form of a batched program's image argument: ``u8[n, h,
    w, 3]`` as the batcher assembles it -> ``pieces`` arrays ``u8[n /
    pieces, h, w * 3]``, the same bytes in the same order. Views of a
    C-contiguous host array (nothing is copied), the matching
    ``ShapeDtypeStruct``s of an abstract value.

    Flat, because the device keeps ``[n, h, w, 3]`` planar and tiled (w
    minor, then h, then c), so the runtime's transfer threads de-interleave
    every 3-byte pixel on the host; the flat shape's device layout is
    row-major, which the host side copies in long runs (1.4-1.8 times
    faster at any size, a third of the host CPU). In pieces, because the
    bytes in flight have to fit the runtime's transfer buffer
    (``STAGE_PIECE_BYTES``). The program un-flattens each piece on the
    device (``unflatten_images``). docs/architecture.md "The transform
    program's input contract"."""
    n, h, w, c = images.shape
    frames = n // pieces
    if isinstance(images, jax.ShapeDtypeStruct):
        piece = jax.ShapeDtypeStruct(
            (frames, h, w * c), images.dtype, sharding=images.sharding
        )
        return (piece,) * pieces
    flat = images.reshape(n, h, w * c)
    return tuple(
        flat[k * frames:(k + 1) * frames] for k in range(pieces)
    )


def unflatten_images(flat, in_shape: Tuple[int, int]):
    """One piece of ``flat_pieces`` back to ``u8[frames, h, w, 3]`` inside
    the device program: the first operation on every piece. A reshape of
    the same bytes; the device's own re-layout behind it is the
    compiler's."""
    with jax.named_scope("flyimg.unflatten"):
        return flat.reshape(flat.shape[0], *in_shape, 3)


def flatten_images(images):
    """``u8[frames, h, w, 3]`` -> ``u8[frames, h, w * 3]`` inside the device
    program: the last operation on every piece's output, the mirror of
    ``unflatten_images``. The device keeps the NHWC output planar, and the
    host's read-back of a planar array is a strided view whose every
    member the resolve re-interleaved pixel by pixel; flat, and laid out
    row-major (``flat_output_format``), the read-back lands in the host's
    order and ``ProgramHandle.unstage`` re-shapes it without a copy."""
    with jax.named_scope("flyimg.flatten"):
        return images.reshape(*images.shape[:2], -1)


def flat_output_format(sharding) -> jax_layout.Format:
    """The format a batched program returns its flat output in: on
    ``sharding``, laid out row-major (batch, then rows, then the row's
    bytes), which is the host's order. Pinned, because the device picks a
    ``uint8`` array's layout by its shape to spare its tiles' padding: on a
    TPU v5e ``u8[64, 1066, 4800]`` defaults to the batch axis between the
    rows and the row's bytes (``major_to_minor=(1, 0, 2)``), whose
    read-back is strided again, and ``u8[64, 1216, 2496]`` to row-major. A
    function of nothing but the program's static shape."""
    return jax_layout.Format(
        jax_layout.Layout(major_to_minor=(0, 1, 2)), sharding
    )


class ProgramHandle:
    """One device program: callable like the jitted function it wraps,
    but compiled through the AOT API so its XLA cost analysis feeds the
    per-plan cost ledger.

    The first call lowers and compiles (``jit(...).lower(*args)
    .compile()``) — the AOT and call-time compile caches are disjoint in
    this jax, so the handle *owns* the compile and every later call runs
    the compiled executable directly (same one-compile-per-shape
    semantics as calling the jit; the lru caches in build_program /
    build_batched_program key the shapes). The compiled object exposes
    ``cost_analysis()``/``memory_analysis()``, which the call-time path
    discards — FLOPs, bytes accessed, peak memory, and the measured
    compile wall time are recorded in the ledger keyed by this handle's
    program key. A program the compiler refuses raises to the caller —
    its requests fail, nothing retries it down another path; only the
    two analysis calls are guarded, because cost accounting must never
    fail a render that compiled.

    A batched program (``pieces`` >= 1) takes its image argument in the
    staged form (``flat_pieces``) and returns its output flat (``u8[n,
    h, w * 3]``, ``flatten_images``), and the handle owns both forms:
    callers assemble and describe images as ``[n, h, w, 3]``; ``stage``
    and ``precompile`` map them, so the executable a handle is warmed with
    is the one its staged arrays run, and ``unstage`` turns the read-back
    into ``[n, h, w, 3]`` again.
    """

    __slots__ = (
        "_jitted", "_compiled", "_lock", "ledger_key", "descriptor",
        "in_sharding", "pieces",
    )

    def __init__(self, jitted, key, descriptor: Dict[str, object],
                 in_sharding=None, pieces: int = 0) -> None:
        self._jitted = jitted
        self._compiled = None
        self._lock = threading.Lock()
        # the sharding every argument of a mesh-sharded batched program
        # takes (None: single device)
        self.in_sharding = in_sharding
        # the program takes its first argument as flat_pieces leaves it,
        # in this many pieces (0: a single-image program, images as given)
        self.pieces = pieces
        if isinstance(key, str):
            self.ledger_key = key
        else:
            _ledger()  # populate the lazy module ref
            self.ledger_key = _costledger_mod.key_digest(key)
        self.descriptor = descriptor

    @property
    def is_compiled(self) -> bool:
        """True once this handle holds a compiled program — the batcher's
        EXACT compile-hit signal, replacing the old lru-miss-count
        inference."""
        return self._compiled is not None

    def _staged(self, args):
        """``args`` as the program takes them: the image argument of a
        batched program as its flat pieces, everything else as it is."""
        if not self.pieces:
            return list(args)
        return [flat_pieces(args[0], self.pieces), *args[1:]]

    def stage(self, arrays):
        """Host arrays as the batcher assembles them (images ``[n, h, w,
        3]``, C-contiguous) -> device arrays as this program takes them:
        the images as their flat pieces, views, so the one copy made is
        the transfer's own, a piece a transfer. With an input sharding each
        device receives its slice of the batch straight from the host —
        staged unsharded, the whole batch would land on device 0 and be
        resharded at every launch (a sharded program takes one piece: the
        runtime already moves one transfer a device, and the flat form
        keeps the batch axis the sharding splits). Returns before the
        copies have happened (the caller waits with
        ``jax.block_until_ready``); ``__call__`` takes what this returns."""
        return jax.device_put(self._staged(arrays), self.in_sharding)

    def unstage(self, out):
        """The host read-back of this program's output as the batcher uses
        it: a batched program's ``u8[n, h, w * 3]`` as ``[n, h, w, 3]``, a
        view where the read-back is C-contiguous (a copy in the host's
        order where it is not); a single-image program's as it is."""
        if not self.pieces:
            return out
        n, h, row = out.shape
        return out.reshape(n, h, row // 3, 3)

    def precompile(self, args) -> None:
        """Compile (and ledger-record) for ``args``'s shapes WITHOUT
        executing — ``args`` may be ``jax.ShapeDtypeStruct`` abstract
        values, and describe what ``stage`` is given, images as ``[n, h,
        w, 3]``: the same mapping is applied to them, so the executable
        held afterwards is exactly the one staged arrays run (a warmed
        handle never compiles at its first launch). Lets warm-up, cost A/B
        tooling and tests obtain the executable and its ledger entry for a
        geometry (e.g. the canonical 4k plan) that would be
        seconds-per-image to actually execute on a CPU host."""
        with self._lock:
            if self._compiled is None:
                self._compile(self._staged(args))

    def __call__(self, *args):
        compiled = self._compiled
        if compiled is None:
            with self._lock:
                # double-checked: a concurrent first call compiled while
                # we waited — run it below, outside the lock
                if self._compiled is None:
                    self._compile(args)
                compiled = self._compiled
        return compiled(*args)

    def _compile(self, args) -> None:
        """AOT-compile for ``args``'s shapes and record the cost ledger
        entry (caller holds the handle lock; contention is only ever
        concurrent *first* calls of one program, which would all block
        on the same XLA compile anyway)."""
        ledger = _ledger()  # also populates the lazy module ref the
        # cost-normalization below reads
        t0 = time.perf_counter()
        compiled = self._jitted.lower(*args).compile()
        compile_s = time.perf_counter() - t0
        cost = None
        try:
            cost = _costledger_mod.normalize_cost_analysis(
                compiled.cost_analysis()
            )
        except Exception:
            cost = None  # backend raised: entry keeps nulled cost fields
        peak = None
        try:
            mem = compiled.memory_analysis()
            if mem is not None:
                peak = float(
                    getattr(mem, "argument_size_in_bytes", 0)
                    + getattr(mem, "output_size_in_bytes", 0)
                    + getattr(mem, "temp_size_in_bytes", 0)
                )
        except Exception:
            peak = None
        self._compiled = compiled
        ledger.record_compile(
            self.ledger_key,
            descriptor=self.descriptor,
            compile_s=compile_s,
            cost=cost,
            peak_memory_bytes=peak,
            devices=_input_devices(compiled),
        )


def _input_devices(compiled):
    """Sorted ids of the devices a compiled program's first argument is
    laid out over, or None where the executable does not say."""
    try:
        sharding = jax.tree_util.tree_leaves(compiled.input_shardings)[0]
        return sorted(d.id for d in sharding.device_set)
    except (AttributeError, IndexError, TypeError):
        return None


def bound_lru_cache(maxsize: int):
    """``functools.lru_cache`` keyed on the arguments as the signature
    binds them, defaults filled in: ``f(a, b)``, ``f(a, b, 8)`` and ``f(a,
    b, depth=8)`` are one entry, where a plain ``lru_cache`` keys each
    spelling apart and a caller that omits a default would build (and
    compile) a second handle of the same program. ``cache_info`` and
    ``cache_clear`` are the cache's own."""
    def wrap(fn):
        signature = inspect.signature(fn)
        cached = lru_cache(maxsize=maxsize)(fn)

        @functools.wraps(fn)
        def call(*args, **kwargs):
            bound = signature.bind(*args, **kwargs)
            bound.apply_defaults()
            return cached(*bound.args)

        call.cache_info = cached.cache_info
        call.cache_clear = cached.cache_clear
        return call

    return wrap


@bound_lru_cache(maxsize=256)
def build_program(
    in_shape: Tuple[int, int],
    resample_out: Optional[Tuple[int, int]],
    pad_canvas: Optional[Tuple[int, int]],
    pad_offset: Tuple[int, int],
    plan: TransformPlan,
    band_taps: Optional[Tuple[int, int]] = None,
    depth: int = 8,
) -> ProgramHandle:
    """Compile (lazily, on first call) the device program for one op
    config at one padded input shape, as a ``ProgramHandle`` feeding the
    per-plan cost ledger. Callers must pass ``plan.device_plan()`` so the
    cache key ignores per-image geometry (it arrives as traced spans).
    ``in_shape`` keys the cache — one handle per input shape keeps each
    handle single-shape, which is what lets it hold ONE compiled
    executable. ``band_taps`` is part of the cache AND ledger key:
    dense and banded variants of one plan are distinct programs that
    must never collide in either table; so is ``depth``, the bits of a
    sample (``make_program_fn``)."""
    key = (
        "single", in_shape, resample_out, pad_canvas, pad_offset, plan,
        band_taps,
        depth,
    )
    # fleet warm start (runtime/warmstart.py): note this program's
    # identity for the shared manifest — inside the lru body, so once
    # per distinct program; a no-op unless a recorder is installed
    from flyimg_tpu.runtime import warmstart

    warmstart.record_single(
        in_shape, resample_out, pad_canvas, pad_offset, plan, band_taps,
        depth,
    )
    return ProgramHandle(
        jax.jit(make_program_fn(
            resample_out, pad_canvas, pad_offset, plan,
            band_taps=band_taps, depth=depth,
        )),
        key,
        plan_descriptor(
            plan, in_shape=in_shape, resample_out=resample_out,
            pad_canvas=pad_canvas, pad_offset=pad_offset,
            band_taps=band_taps, depth=depth,
        ),
    )


def program_cache_info() -> Dict[str, Any]:
    """Introspection over BOTH program caches (this module's single-image
    cache and the batcher's batched cache) — the source of truth the
    compile-hit accounting and the ``flyimg_program_cache_entries`` gauge
    read, instead of inferring state from miss-count deltas."""
    single = build_program.cache_info()
    doc: Dict[str, Any] = {
        "single": {
            "entries": single.currsize,
            "hits": single.hits,
            "misses": single.misses,
            "maxsize": single.maxsize,
        },
    }
    try:
        from flyimg_tpu.runtime.batcher import build_batched_program

        batched = build_batched_program.cache_info()
        doc["batched"] = {
            "entries": batched.currsize,
            "hits": batched.hits,
            "misses": batched.misses,
            "maxsize": batched.maxsize,
        }
    except Exception:
        doc["batched"] = None
    return doc


def program_cache_entries() -> float:
    """Total live entries across both program caches (the gauge fn)."""
    info = program_cache_info()
    total = info["single"]["entries"]
    if info.get("batched"):
        total += info["batched"]["entries"]
    return float(total)


def invalidate_program_caches() -> None:
    """Drop every cached ``ProgramHandle`` — single-image AND batched.

    The backend-failover path (runtime/devicesupervisor.py): an
    executable compiled against a dead (or just-replaced) backend must
    never be called again, so both lru tables clear and the next launch
    of each program recompiles against whatever backend is live. Handles
    already held by in-flight launches keep working (they are standalone
    objects; only the cache mapping clears), and recompiling the SAME
    key values is clean under the retrace sentinel — re-promotion
    compiles repeat known values, they do not grow any family's
    distinct-value count (tools/flylint/retrace_sentinel.py)."""
    build_program.cache_clear()
    try:
        from flyimg_tpu.runtime.batcher import build_batched_program

        build_batched_program.cache_clear()
    except Exception:  # batcher not imported yet: nothing cached there
        pass


def final_extent(plan: TransformPlan, layout: Layout) -> Tuple[int, int]:
    """Final valid (h, w) of the program output for one image — what a
    padded/bucketed output must be sliced to. Follows the stage order:
    resample valid extent -> extent canvas -> rotated bounds."""
    from flyimg_tpu.spec.plan import rotated_bounds

    h, w = layout.out_true
    if layout.pad_canvas is not None:
        w, h = layout.pad_canvas
    if plan.rotate is not None:
        rw, rh = rotated_bounds(w, h, plan.rotate)
        h, w = rh, rw
    return (int(h), int(w))


def _bucket_dim(size: int, step: int = 128) -> int:
    return max(((size + step - 1) // step) * step, step)


def bucket_batch(n: int) -> int:
    """Round a batch occupancy up the power-of-two ladder so XLA compiles a
    handful of batch shapes per program, not one per occupancy. Shared by
    the transform batcher and the aux (scoring/detection) programs."""
    return 1 << max(n - 1, 0).bit_length()


def run_plan(
    image: np.ndarray,
    plan: TransformPlan,
    src_window: Optional[Tuple[int, int]] = None,
) -> np.ndarray:
    """Execute a plan on one host image [h, w, 3] uint8 -> uint8 output
    (uint16 -> uint16 for 16-bit samples).

    Pads the input up to a shape bucket so repeated calls with same-signature
    plans and similar sizes reuse one compiled program; the pad region is
    masked out of the resample by construction.

    ``src_window`` (docs/host-pipeline.md "ROI window math"): the image is
    only the window of the plan's source starting at this (x, y) offset —
    the ROI-decode contract. The source spans are per-image TRACED inputs,
    so shifting them by the offset reproduces the full-frame sampling
    bit-for-bit on the window array (the decode window includes the tap
    support margin by construction); program identity is untouched.
    """
    h, w = int(image.shape[0]), int(image.shape[1])
    if src_window is not None:
        wx, wy = int(src_window[0]), int(src_window[1])
        if (
            wx < 0 or wy < 0
            or wx + w > plan.src_size[0] or wy + h > plan.src_size[1]
        ):
            raise ValueError(
                f"src_window {(wx, wy)} + image {(w, h)} exceeds plan "
                f"src {plan.src_size}"
            )
        if not _needs_resample(plan, None):
            # only the windowed-resample path consumes spans; a pixel-op
            # or bare-rotate plan reads the whole frame and a window
            # would silently produce window-sized output
            raise ValueError("src_window requires a resample/extract plan")
    elif plan.src_size != (w, h):
        # geometry (pns clamping, fill dims, extract clamps) was resolved
        # against plan.src_size; silently patching it here would run a stale
        # plan. Callers must rebuild the plan for the actual decoded dims.
        raise ValueError(
            f"plan was built for src {plan.src_size}, got image {(w, h)}; "
            "rebuild the plan with build_plan(options, w, h)"
        )
    layout = plan_layout(plan)
    if src_window is not None:
        layout = Layout(
            (layout.span_y[0] - wy, layout.span_y[1]),
            (layout.span_x[0] - wx, layout.span_x[1]),
            layout.out_true,
            layout.resample_out,
            layout.pad_canvas,
            layout.pad_offset,
        )

    slice_out = None
    band = None
    depth = 16 if image.dtype == np.uint16 else 8
    if _needs_resample(plan, layout):
        bh, bw = _bucket_dim(h), _bucket_dim(w)
        padded = np.zeros((bh, bw, image.shape[2]), dtype=image.dtype)
        padded[:h, :w] = image
        resample_out = layout.resample_out
        in_shape = (bh, bw)
        # kernel-variant policy from the member's TRUE geometry (the
        # serving-wide resample_kernel knob; docs/kernels.md) — K is
        # static per compile, so it joins the cache key below
        band = select_band_taps(
            kernel_mode(), plan.filter_method, in_shape,
            layout.span_y, layout.span_x, layout.out_true,
        )
    elif plan.rotate is None:
        # pixel-op-only plans also ride shape buckets (otherwise every
        # distinct source resolution would force a fresh XLA compile).
        # Edge-replicate padding keeps convolutional ops correct at the
        # valid-region boundary (== IM's edge virtual-pixel policy); the
        # valid region is sliced back out below. Rotate is excluded: its
        # output bbox is derived from the full (padded) frame.
        bh, bw = _bucket_dim(h), _bucket_dim(w)
        padded = np.pad(image, ((0, bh - h), (0, bw - w), (0, 0)), mode="edge")
        resample_out = None
        in_shape = (bh, bw)
        slice_out = (h, w)
    else:
        padded = image
        resample_out = None
        # DELIBERATE exact-frame path (one compile per source size):
        # static rotate with conv post-ops must see the true frame —
        # bucket padding would blur the background fill across the
        # valid-region edge (visible halo), and the rotate bbox derives
        # from the full frame. jax-retrace-hazard accepted for exactly
        # this branch; all other shapes ride _bucket_dim above.
        # flylint: disable=jax-retrace-hazard
        in_shape = (h, w)

    fn = build_program(
        in_shape,
        resample_out,
        layout.pad_canvas,
        layout.pad_offset,
        plan.device_plan(),
        band,
        depth,
    )
    t0 = time.perf_counter()
    out = fn(
        jnp.asarray(padded),
        jnp.array([h, w], jnp.float32),
        jnp.array(layout.span_y, jnp.float32),
        jnp.array(layout.span_x, jnp.float32),
        jnp.array(layout.out_true, jnp.float32),
    )
    result = np.asarray(out)
    # single-image launches count in the per-plan ledger too (the CPU
    # fallback / library path must not be invisible to attribution)
    _ledger().record_launch(
        fn.ledger_key, device_s=time.perf_counter() - t0, images=1
    )
    if slice_out is not None:
        result = np.ascontiguousarray(result[: slice_out[0], : slice_out[1]])
    return result
