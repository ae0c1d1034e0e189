"""Windowed separable resampling as MXU einsums — dense or banded.

This is the framework's core kernel and its central TPU-first design move:
the reference's whole geometry chain — extract crop, fill-resize, gravity
crop/extent (reference src/Core/Processor/ImageProcessor.php:115-162 emitting
``-thumbnail WxH^ -gravity G -extent WxH``) — collapses into ONE windowed
resample per axis: output pixel i samples source coordinate

    x(i) = span_start + (i + 0.5) * span_size / out_true - 0.5

so a crop is just a span smaller than the image and a resize is just
out != span. The per-output-row filter weights form a dense [out, in]
matrix computed from *traced* scalars (span, true sizes) — meaning one
compiled program serves every source size in a padded bucket, and the
two per-axis weight applications are einsums that XLA tiles onto the MXU.

The dense matrices are ~95% zeros at serving scales (lanczos3 support is
10-13 taps of a 512-bucket axis), so the **banded** formulation
(``resample_image_banded``; docs/kernels.md) gathers a static K-tap band
per output sample instead and contracts over K — ~30x fewer resample MACs
at the flagship geometry, validated against the dense path to 9e-5 by
``benchmarks/resample_experiment.py``. K is derived from the filter
support and the plan's scale on the host (``band_taps``/``select_band_taps``)
and is STATIC per compiled program: plans whose geometry needs a different
K bucket compile (and batch) separately, exactly like input-shape buckets.
The serving-wide choice between the forms is the ``resample_kernel``
appconfig knob (dense | banded | auto), applied via ``set_kernel_mode``.

Filter kernels mirror ImageMagick's resize filters (magick/resize.c):
lanczos3 (IM default 'Lanczos'), triangle, mitchell ('Cubic'/'Catrom'
approximation), box, nearest ('Point'). Downscale antialiasing stretches the
kernel by the scale factor and renormalizes, like IM's support scaling.

Edge policy: sample coordinates are clamped to [0, true-1] and taps beyond
the image's true extent are masked then rows renormalized — equivalent to
IM's edge virtual-pixel handling, and it makes bucket padding invisible
(padding pixels get zero weight, so zero-padded H2D buffers are safe).
The banded form computes weights from the UNCLIPPED tap positions and
zeroes out-of-range taps before renormalizing — clipping the positions
first would pile duplicate taps on the edge samples and over-weight them
(docs/kernels.md "the unclipped-tap invariant").
"""

from __future__ import annotations

import math
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

# Filter support radii: the half-width of _kernel_fn's nonzero region.
# The K-from-support computation below is THE shared source of truth for
# band widths — the serving kernel (ops/compose.py, runtime/batcher.py)
# and benchmarks/resample_experiment.py both import it, so the benchmark
# and the serving path can never disagree about what K a geometry needs.
FILTER_SUPPORT = {
    "lanczos3": 3.0,
    "triangle": 1.0,
    "gaussian": 1.5,
    "cubic": 2.0,
    "box": 0.5,
    "nearest": 0.5,
}

#: serving-wide resample formulation: 'dense' (the shipped [out, in]
#: matrix einsums), 'banded' (static K-tap gather-contract), or 'auto'
#: (banded whenever the band is narrower than the dense matrix). The env
#: var seeds the default so offline tools (bench.py, bench_http A/B legs)
#: can flip the variant without config plumbing; the ``resample_kernel``
#: appconfig knob overrides it at app construction (service/app.py).
KERNEL_MODES = ("dense", "banded", "auto")
_kernel_mode = os.environ.get("FLYIMG_RESAMPLE_KERNEL", "dense")
if _kernel_mode not in KERNEL_MODES:
    # a typo'd env seed must not become a request-time ValueError deep
    # in submit; the knob path (set_kernel_mode) still raises loudly
    _kernel_mode = "dense"


def kernel_mode() -> str:
    """The current process-wide resample-kernel mode."""
    return _kernel_mode


def set_kernel_mode(mode: str) -> str:
    """Set the process-wide resample-kernel mode (dense|banded|auto).
    Process-wide like the program caches the choice keys into: two apps
    in one process share it, last writer wins."""
    global _kernel_mode
    if mode not in KERNEL_MODES:
        raise ValueError(
            f"resample_kernel must be one of {KERNEL_MODES}, got {mode!r}"
        )
    _kernel_mode = mode
    return _kernel_mode


def band_taps(method: str, scale: float) -> int:
    """Exact taps one output sample needs at ``scale`` (= span/out; > 1
    is a downscale). Downscale antialiasing stretches the kernel by the
    scale factor, so the tap count grows with it: taps sit at integer
    positions within ``support * max(scale, 1)`` of the sample point, and
    a band of ``2*ceil(R) + 2`` centered at ``floor(x)`` covers every
    such position for any fractional x (the +2 absorbs the worst-case
    fractional offset on both sides)."""
    support = FILTER_SUPPORT.get(method, 3.0)
    radius = support * max(float(scale), 1.0)
    return int(2 * math.ceil(radius)) + 2


def bucket_taps(taps: int) -> int:
    """Round a tap count up the power-of-two ladder (floor 8) so XLA
    compiles a handful of band widths per program shape, not one per
    geometry — the same bucketing philosophy as the batch-size ladder
    (ops/compose.py bucket_batch)."""
    return max(8, 1 << max(int(taps) - 1, 0).bit_length())


def select_band_taps(
    mode: str,
    method: str,
    in_hw: Tuple[int, int],
    span_y: Tuple[float, float],
    span_x: Tuple[float, float],
    out_true_hw: Tuple[float, float],
) -> Optional[Tuple[int, int]]:
    """Host-side kernel-variant policy for one plan geometry: the static
    per-axis band widths ``(Ky, Kx)`` for the banded path, or ``None``
    for dense. Called at submit time (runtime/batcher.py) and by the
    single-image path (ops/compose.py run_plan) with the member's true
    geometry, so K is dynamic per *program* and static per *compile* —
    the result is part of the program-cache key and the batch group key.

    ``mode='banded'`` always bands (K clamped to the bucket axis — a
    band as wide as the axis is just a permuted dense contract);
    ``mode='auto'`` bands only when BOTH axes' bands are strictly
    narrower than the dense matrices they replace."""
    if mode == "dense":
        return None
    if mode not in KERNEL_MODES:
        raise ValueError(
            f"resample_kernel must be one of {KERNEL_MODES}, got {mode!r}"
        )
    in_h, in_w = int(in_hw[0]), int(in_hw[1])
    out_h = max(float(out_true_hw[0]), 1.0)
    out_w = max(float(out_true_hw[1]), 1.0)
    ky = bucket_taps(band_taps(method, float(span_y[1]) / out_h))
    kx = bucket_taps(band_taps(method, float(span_x[1]) / out_w))
    if mode == "auto" and not (ky < in_h and kx < in_w):
        return None
    return (min(ky, max(in_h, 1)), min(kx, max(in_w, 1)))


#: the precision of the resample's two contractions by the bits of a
#: sample. ``DEFAULT`` is bfloat16 operands on the TPU: 8 significant bits,
#: under half a level of an 8-bit sample, and out by up to about 128 of
#: 65,535. A 16-bit sample takes ``HIGHEST``, float32 as the configuration
#: states it (six bfloat16 MXU passes on the TPU): ``HIGH``'s three passes
#: were biased by about a third of a level a contraction on a TPU v5e
#: (PERF.md section 2). The CPU computes float32 either way.
SAMPLE_PRECISION = {
    8: jax.lax.Precision.DEFAULT,
    16: jax.lax.Precision.HIGHEST,
}


def _kernel_fn(method: str, x: jnp.ndarray) -> jnp.ndarray:
    if method == "lanczos3":
        return jnp.where(jnp.abs(x) < 3.0, jnp.sinc(x) * jnp.sinc(x / 3.0), 0.0)
    if method == "triangle":
        return jnp.maximum(0.0, 1.0 - jnp.abs(x))
    if method == "gaussian":
        # IM 'Gaussian' (magick/resize.c Gaussian): sigma 1/2, support 1.5
        # => exp(-2 x^2); the amplitude constant cancels in the row
        # renormalization below
        return jnp.where(jnp.abs(x) < 1.5, jnp.exp(-2.0 * x * x), 0.0)
    if method == "cubic":
        # Mitchell-Netravali B=C=1/3 (IM's general-purpose cubic)
        b, c = 1.0 / 3.0, 1.0 / 3.0
        ax = jnp.abs(x)
        ax2, ax3 = ax * ax, ax * ax * ax
        p1 = ((12 - 9 * b - 6 * c) * ax3 + (-18 + 12 * b + 6 * c) * ax2 + (6 - 2 * b)) / 6.0
        p2 = ((-b - 6 * c) * ax3 + (6 * b + 30 * c) * ax2 + (-12 * b - 48 * c) * ax + (8 * b + 24 * c)) / 6.0
        return jnp.where(ax < 1.0, p1, jnp.where(ax < 2.0, p2, 0.0))
    if method in ("box", "nearest"):
        return jnp.where((x >= -0.5) & (x < 0.5), 1.0, 0.0)
    raise ValueError(f"unknown resample method: {method}")


def resample_matrix(
    in_size: int,
    out_size: int,
    span_start: jnp.ndarray,
    span_size: jnp.ndarray,
    out_true: jnp.ndarray,
    in_true: jnp.ndarray,
    method: str = "lanczos3",
) -> jnp.ndarray:
    """Dense [out_size, in_size] weight matrix for one axis.

    ``in_size``/``out_size`` are the STATIC (bucket) sizes; ``span_start``,
    ``span_size`` (source window), ``out_true`` (valid output extent) and
    ``in_true`` (valid input extent) are traced scalars, so the same
    executable serves any image in the bucket. Rows at i >= out_true are
    edge-replicated don't-cares (the host slices the valid region).
    """
    span_start = jnp.asarray(span_start, jnp.float32)
    span_size = jnp.asarray(span_size, jnp.float32)
    out_true = jnp.asarray(out_true, jnp.float32)
    in_true = jnp.asarray(in_true, jnp.float32)

    i = jnp.arange(out_size, dtype=jnp.float32)
    j = jnp.arange(in_size, dtype=jnp.float32)
    x = span_start + (i + 0.5) * (span_size / jnp.maximum(out_true, 1.0)) - 0.5
    x = jnp.clip(x, 0.0, jnp.maximum(in_true - 1.0, 0.0))

    if method == "nearest":
        # IM 'Point': one-hot at the floor-rounded sample position
        idx = jnp.clip(jnp.floor(x + 0.5), 0.0, jnp.maximum(in_true - 1.0, 0.0))
        return (j[None, :] == idx[:, None]).astype(jnp.float32)

    # antialias: stretch kernel by the downscale factor (never below 1)
    s = jnp.maximum(span_size / jnp.maximum(out_true, 1.0), 1.0)
    d = (j[None, :] - x[:, None]) / s
    w = _kernel_fn(method, d)
    w = jnp.where(j[None, :] < in_true, w, 0.0)
    denom = jnp.sum(w, axis=-1, keepdims=True)
    return w / jnp.where(denom == 0.0, 1.0, denom)


def resample_image(
    image: jnp.ndarray,
    out_hw: Tuple[int, int],
    span_y: jnp.ndarray,
    span_x: jnp.ndarray,
    out_true_hw: jnp.ndarray,
    in_true_hw: jnp.ndarray,
    method: str = "lanczos3",
    precision=jax.lax.Precision.DEFAULT,
) -> jnp.ndarray:
    """Resample one [H, W, C] float image to static [out_h, out_w, C].

    ``span_y``/``span_x`` are (start, size) source windows per axis;
    ``out_true_hw``/``in_true_hw`` are (h, w) valid extents. All four may be
    traced. Two einsums -> both land on the MXU. ``precision`` is that of
    both contractions (``SAMPLE_PRECISION``): 8-bit samples take
    ``DEFAULT``, 16-bit ones ``HIGHEST``.
    """
    in_h, in_w = image.shape[0], image.shape[1]
    out_h, out_w = out_hw
    with jax.named_scope("flyimg.weights"):
        wy = resample_matrix(
            in_h, out_h, span_y[0], span_y[1], out_true_hw[0], in_true_hw[0],
            method,
        )
        wx = resample_matrix(
            in_w, out_w, span_x[0], span_x[1], out_true_hw[1], in_true_hw[1],
            method,
        )
    if RESAMPLE_FORM == "fold2d_bf16" and precision == jax.lax.Precision.DEFAULT:
        return _apply_fold2d_bf16(image, wy, wx, out_h, out_w)
    # DEFAULT precision (8-bit samples) = bf16 multiplies with f32
    # accumulation on TPU: 2.3x the throughput of the f32 path, worst-case
    # error well under one uint8 level (bf16 has 8 mantissa bits); HIGHEST
    # (16-bit samples) is float32. On CPU both are plain f32, so
    # conformance tests are unaffected.
    with jax.named_scope("flyimg.resample_rows"):
        tmp = jnp.einsum("oh,hwc->owc", wy, image, precision=precision)
    with jax.named_scope("flyimg.resample_cols"):
        return jnp.einsum("ow,hwc->hoc", wx, tmp, precision=precision)


def _band_axis(
    in_size: int,
    out_size: int,
    taps: int,
    span_start: jnp.ndarray,
    span_size: jnp.ndarray,
    out_true: jnp.ndarray,
    in_true: jnp.ndarray,
    method: str,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Banded weights for one axis: ``(idx [out, K] int32, w [out, K])``
    from traced geometry scalars, with ``taps`` (K) static.

    Same sampling model as ``resample_matrix`` — the K tap positions are
    the integer window centered at ``floor(x)``; weights come from the
    UNCLIPPED tap positions and out-of-range taps ([0, in_true) in the
    true input frame) are zeroed before row renormalization, so the
    nonzero weights are exactly the dense matrix's row restricted to the
    band (parity pinned by tests/test_resample_banded.py). Gather
    indices are clipped to the static axis as don't-cares."""
    span_start = jnp.asarray(span_start, jnp.float32)
    span_size = jnp.asarray(span_size, jnp.float32)
    out_true = jnp.asarray(out_true, jnp.float32)
    in_true = jnp.asarray(in_true, jnp.float32)

    i = jnp.arange(out_size, dtype=jnp.float32)
    x = span_start + (i + 0.5) * (span_size / jnp.maximum(out_true, 1.0)) - 0.5
    x = jnp.clip(x, 0.0, jnp.maximum(in_true - 1.0, 0.0))

    if taps >= in_size:
        # the band would cover the whole axis: a centered window of K <
        # needed taps could MISS contributing positions at the edges, so
        # degrade to the full axis — identical weights to the dense
        # matrix, gathered in index order (select_band_taps clamps K to
        # the axis size, so this branch is the K == in_size case)
        j = jnp.broadcast_to(
            jnp.arange(in_size, dtype=jnp.int32)[None, :],
            (out_size, in_size),
        )
    else:
        j0 = jnp.floor(x).astype(jnp.int32) - taps // 2 + 1
        j = j0[:, None] + jnp.arange(taps, dtype=jnp.int32)[None, :]

    if method == "nearest":
        # IM 'Point': one-hot at the floor-rounded sample position (the
        # dense path's early-return special case, band-local here)
        near = jnp.clip(
            jnp.floor(x + 0.5), 0.0, jnp.maximum(in_true - 1.0, 0.0)
        )
        w = (j.astype(jnp.float32) == near[:, None]).astype(jnp.float32)
        return jnp.clip(j, 0, in_size - 1), w

    s = jnp.maximum(span_size / jnp.maximum(out_true, 1.0), 1.0)
    d = (j.astype(jnp.float32) - x[:, None]) / s
    w = _kernel_fn(method, d)
    w = jnp.where((j >= 0) & (j.astype(jnp.float32) < in_true), w, 0.0)
    denom = jnp.sum(w, axis=-1, keepdims=True)
    return (
        jnp.clip(j, 0, in_size - 1),
        w / jnp.where(denom == 0.0, 1.0, denom),
    )


def resample_image_banded(
    image: jnp.ndarray,
    out_hw: Tuple[int, int],
    span_y: jnp.ndarray,
    span_x: jnp.ndarray,
    out_true_hw: jnp.ndarray,
    in_true_hw: jnp.ndarray,
    taps_hw: Tuple[int, int],
    method: str = "lanczos3",
    precision=jax.lax.Precision.DEFAULT,
) -> jnp.ndarray:
    """Banded K-tap resample of one [H, W, C] float image to static
    [out_h, out_w, C] — the ``resample_image`` contract with a static
    per-axis band width ``taps_hw`` (Ky, Kx) instead of dense matrices,
    and the same ``precision``.

    Two gather + contract passes: rows are gathered into [out_h, Ky, W, C]
    and contracted over Ky, then columns into [out_h, out_w, Kx, C] and
    contracted over Kx — ~(in/K)x fewer MACs than the dense einsums,
    traded against gather cost and a VPU (not MXU) reduction. Callers
    size ``taps_hw`` via ``select_band_taps`` (too-small bands drop
    contributing taps; docs/kernels.md)."""
    in_h, in_w = image.shape[0], image.shape[1]
    out_h, out_w = out_hw
    with jax.named_scope("flyimg.weights"):
        iy, wy = _band_axis(
            in_h, out_h, int(taps_hw[0]), span_y[0], span_y[1],
            out_true_hw[0], in_true_hw[0], method,
        )
        ix, wx = _band_axis(
            in_w, out_w, int(taps_hw[1]), span_x[0], span_x[1],
            out_true_hw[1], in_true_hw[1], method,
        )
    with jax.named_scope("flyimg.resample_rows"):
        rows = jnp.take(image, iy, axis=0)            # [oh, Ky, w, c]
        tmp = jnp.einsum("ok,okwc->owc", wy, rows, precision=precision)
    with jax.named_scope("flyimg.resample_cols"):
        cols = jnp.take(tmp, ix, axis=1)              # [oh, ow, Kx, c]
        return jnp.einsum("ok,hokc->hoc", wx, cols, precision=precision)


#: Weight-application formulation. 'einsum' is the shipped two-einsum
#: form over [h, w, c]; 'fold2d_bf16' folds channels into plain 2D
#: matmuls with explicit bf16 operands + f32 accumulation — the
#: benchmarks/resample_experiment.py candidate that avoids XLA
#: padding/permuting C=3 on the (8,128) tile minor dim. Flip the default
#: only on a measured >=10%-within-one-uint8-level on-chip win; the env
#: var exists so the A/B can run the SERVING code path.
RESAMPLE_FORM = os.environ.get("FLYIMG_RESAMPLE_FORM", "einsum")


def _apply_fold2d_bf16(
    image: jnp.ndarray, wy: jnp.ndarray, wx: jnp.ndarray,
    out_h: int, out_w: int,
) -> jnp.ndarray:
    """H-pass as [oh,h]@[h,w*c], W-pass as [oh*c,w]@[w,ow]: both clean 2D
    MXU matmuls. bf16 operands halve the HBM traffic of image+intermediate;
    accumulation stays f32 (preferred_element_type), so the result differs
    from the einsum form by well under one uint8 level on 8-bit imagery."""
    h, w = image.shape[0], image.shape[1]
    c = image.shape[2]
    imgb = image.astype(jnp.bfloat16)
    tmp = jax.lax.dot_general(
        wy.astype(jnp.bfloat16), imgb.reshape(h, w * c),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    ).reshape(out_h, w, c)
    t2 = jnp.transpose(tmp.astype(jnp.bfloat16), (0, 2, 1)).reshape(
        out_h * c, w
    )
    out = jax.lax.dot_general(
        t2, wx.astype(jnp.bfloat16).T,
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    ).reshape(out_h, c, out_w)
    return jnp.transpose(out, (0, 2, 1))
