"""Smart-crop: the reference's scoring algorithm, vectorized for TPU.

Faithful reimplementation of the reference's smartcrop scorer
(reference python/smartcrop.py, itself a port of smartcrop.js) with the
per-pixel Python double loop (smartcrop.py:315-332 — O(crops * W * H), the
reference's slowest path) replaced by closed-form convolutions:

The observation that makes this TPU-native: the importance field
(smartcrop.py:276-298) depends only on a pixel's position RELATIVE to the
crop window, so for a fixed crop size it is a fixed [ch, cw] kernel; scoring
every candidate position (stride-8 grid, smartcrop.py:193-229) is therefore
ONE strided cross-correlation of the feature maps with that kernel, plus an
outside-the-crop term expressible with box sums:

    score(x, y) = conv(weighted_features, importance)[x, y]
                  + outside_importance * (total_sum - boxsum(x, y))

Feature maps (luma-Laplacian edge, skin-color distance, saturation —
smartcrop.py:231-274) are computed in one fused JAX program, quantized to
uint8 exactly like the reference's PIL round-trip so scores match.

Behavioral contract preserved from the reference driver (smartcrop.py:353-377
+ SmartCropProcessor.php:21-36): 100x100 target -> square-ish crop, prescale
to ~111px, scales {1.0, 0.9}, stride 8, and the quirky output geometry
"(x+w)x(y+h)+x+y" that IM's -crop then clamps to the image bounds.
"""

from __future__ import annotations

import math
from collections import defaultdict
from dataclasses import dataclass
from functools import lru_cache, partial
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# reference smartcrop.py:41-77 constructor defaults
DETAIL_WEIGHT = 0.2
EDGE_RADIUS = 0.4
EDGE_WEIGHT = -10.0
OUTSIDE_IMPORTANCE = -0.5
RULE_OF_THIRDS = True
SATURATION_BIAS = 0.2
SATURATION_BRIGHTNESS_MAX = 0.9
SATURATION_BRIGHTNESS_MIN = 0.05
SATURATION_THRESHOLD = 0.4
SATURATION_WEIGHT = 0.3
SKIN_BIAS = 0.01
SKIN_BRIGHTNESS_MAX = 1.0
SKIN_BRIGHTNESS_MIN = 0.2
SKIN_COLOR = (0.78, 0.57, 0.44)
SKIN_THRESHOLD = 0.8
SKIN_WEIGHT = 1.8


def _thirds(x: np.ndarray) -> np.ndarray:
    """reference smartcrop.py:30-34."""
    x = ((x + 2.0 / 3.0) % 2.0 * 0.5 - 0.5) * 16.0
    return np.maximum(1.0 - x * x, 0.0)


@lru_cache(maxsize=64)
def importance_kernel(crop_w: float, crop_h: float) -> np.ndarray:
    """The importance field for in-crop pixels (reference
    smartcrop.py:276-298, evaluated at integer pixel offsets). ``crop_w/h``
    are the reference's FLOAT crop dims (crop_size * scale): a pixel is
    in-crop while offset < crop_w, so the kernel spans ceil(crop_w) columns,
    and relative positions divide by the float dims."""
    kw = int(math.ceil(crop_w))
    kh = int(math.ceil(crop_h))
    xs = (np.arange(kw, dtype=np.float64)) / crop_w
    ys = (np.arange(kh, dtype=np.float64)) / crop_h
    px = np.abs(0.5 - xs)[None, :] * 2.0
    py = np.abs(0.5 - ys)[:, None] * 2.0
    dx = np.maximum(px - 1.0 + EDGE_RADIUS, 0.0)
    dy = np.maximum(py - 1.0 + EDGE_RADIUS, 0.0)
    d = (dx * dx + dy * dy) * EDGE_WEIGHT
    s = 1.41 - np.sqrt(px * px + py * py)
    if RULE_OF_THIRDS:
        s = s + (np.maximum(0.0, s + d + 0.5) * 1.2) * (_thirds(px) + _thirds(py))
    return (s + d).astype(np.float32)


# ---------------------------------------------------------------------------
# feature maps (one fused device program)
# ---------------------------------------------------------------------------


@jax.jit
def analyse_features(rgb: jnp.ndarray) -> jnp.ndarray:
    """[h, w, 3] uint8 -> [h, w, 3] float32 feature maps in [0, 255]:
    channel 0 = skin, 1 = edge (detail), 2 = saturation — the reference's
    R/G/B analyse image (smartcrop.py:97-101), quantized like its uint8
    round-trip. One implementation serves both the exact-shape and the
    bucket-padded (batched serving) paths: here the valid region IS the
    array."""
    h, w = rgb.shape[:2]
    return _analyse_features_valid(rgb, jnp.array([h, w], jnp.float32))


# ---------------------------------------------------------------------------
# candidate scoring: every position of a crop size in one contraction
# ---------------------------------------------------------------------------


def _window_scores(
    field: jnp.ndarray, kernels: jnp.ndarray, stride: int
) -> jnp.ndarray:
    """Valid cross-correlation of a [h, w] field with [kh, kw, C] kernels at
    the stride-``stride`` candidate grid -> [ny, nx, C]: every crop position
    scored at once, as the candidate windows stacked (static slices — the
    grid is a few dozen positions) and ONE float32-exact contraction.

    Not a ``conv_general_dilated``: at HIGHEST precision a conv with a
    ~112x112 window takes the TPU v5e compiler 101-134 s per batch shape
    (3.5 s at DEFAULT, whose bf16 products are too coarse to rank
    near-tied candidates), which outlasts ``device_result_timeout_s`` on a
    cold server; this form compiles in 2-7 s, runs as fast, and is as close
    to float64 (3e-6 relative; measured on the chip, PR 21, CHANGES.md)."""
    fh, fw = field.shape
    kh, kw = kernels.shape[:2]
    ny = (fh - kh) // stride + 1
    nx = (fw - kw) // stride + 1
    rows = jnp.stack(
        [field[y * stride: y * stride + kh] for y in range(ny)]
    )
    windows = jnp.stack(
        [rows[:, :, x * stride: x * stride + kw] for x in range(nx)], axis=1
    )
    return jnp.einsum(
        "yxij,ijc->yxc", windows, kernels,
        precision=jax.lax.Precision.HIGHEST,
    )


def weighted_field(features: jnp.ndarray) -> jnp.ndarray:
    """Merge the three feature maps with the reference's scoring channel
    weights into the scalar field candidate scoring convolves over."""
    skin = features[..., 0] / 255.0
    detail = features[..., 1] / 255.0
    sat = features[..., 2] / 255.0
    return (
        detail * DETAIL_WEIGHT
        + skin * (detail + SKIN_BIAS) * SKIN_WEIGHT
        + sat * (detail + SATURATION_BIAS) * SATURATION_WEIGHT
    )


def score_grid(
    features: jnp.ndarray, crop_w: float, crop_h: float, stride: int = 8
) -> jnp.ndarray:
    """Scores for every candidate position of a (crop_w, crop_h) float-dim
    window, normalized by the float area like the reference (the score is
    compared ACROSS scales, smartcrop.py:333-337).

    Decomposition of the reference's score() (smartcrop.py:300-338): each
    feature's per-pixel weight is feature-dependent but position-independent,
    the importance factor is crop-relative (= fixed kernel), and outside
    pixels contribute OUTSIDE_IMPORTANCE * weight.
    """
    return score_grid_from_weighted(weighted_field(features), crop_w, crop_h, stride)


def score_grid_from_weighted(
    weighted: jnp.ndarray, crop_w: float, crop_h: float, stride: int = 8
) -> jnp.ndarray:
    """Candidate scores given a precomputed weighted field
    (``weighted_field(analyse_features(...))``)."""
    kernel = importance_kernel(crop_w, crop_h)
    grids = _window_scores(
        weighted, jnp.asarray(np.stack([kernel, np.ones_like(kernel)], -1)),
        stride,
    )
    inside, boxsum = grids[..., 0], grids[..., 1]
    total = jnp.sum(weighted)
    scores = inside + OUTSIDE_IMPORTANCE * (total - boxsum)
    return scores / (crop_w * crop_h)


# ---------------------------------------------------------------------------
# driver (reference smartcrop.py:137-191 crop() + :353-377 main())
# ---------------------------------------------------------------------------


def find_best_crop(
    rgb: np.ndarray,
    target_w: int = 100,
    target_h: int = 100,
    *,
    min_scale: float = 0.9,
    max_scale: float = 1.0,
    scale_step: float = 0.1,
    step: int = 8,
) -> Dict[str, int]:
    """Best crop of [h, w, 3] uint8 -> dict(x, y, width, height), in source
    pixel coords. Mirrors SmartCrop.crop() including prescale bookkeeping
    (one implementation, shared with the batched path: prepare_work)."""
    item = prepare_work(
        rgb, target_w, target_h, min_scale=min_scale, max_scale=max_scale,
        scale_step=scale_step, step=step,
    )

    # the weighted scoring field, computed ONCE and reused across scales.
    # XLA fuses this elementwise + small-stencil chain itself: a
    # hand-written fused-VMEM Pallas kernel for it was measured on-chip in
    # round 3 at the SAME speed as this path while diverging numerically
    # by up to ~7e-3 (enough to flip an argmax near-tie), so it was
    # removed — don't hand-schedule what the compiler already fuses.
    weighted = weighted_field(analyse_features(jnp.asarray(item.work)))

    best = None
    for s in item.scales:
        geom = _member_scale_geometry(item, s)
        if geom is None:
            continue
        cw, ch, max_x, max_y = geom
        scores = np.asarray(
            score_grid_from_weighted(weighted, cw, ch, stride=item.step)
        )
        ny = max_y // item.step + 1
        nx = max_x // item.step + 1
        sub = scores[:ny, :nx]
        if sub.size == 0:
            continue
        idx = np.unravel_index(np.argmax(sub), sub.shape)
        top = float(sub[idx])
        if best is None or top > best[0]:
            best = (top, idx[1] * item.step, idx[0] * item.step, cw, ch)

    return _crop_from_best(best, item)


def _host_thumbnail(rgb: np.ndarray, w: int, h: int) -> np.ndarray:
    from PIL import Image

    return np.asarray(Image.fromarray(rgb).resize((max(w, 1), max(h, 1)), Image.LANCZOS))


def apply_crop(rgb: np.ndarray, crop: Dict[str, int]) -> np.ndarray:
    """Apply a found crop the way the reference pipeline does
    (SmartCropProcessor.php:21-36): the reference prints "WxH+X+Y" with
    W = x + width, H = y + height (smartcrop.py:372-377 — the bottom-right
    corner, not the size) and IM's -crop clamps the oversized region to the
    image bounds; reproduce both quirks exactly."""
    img_h, img_w = rgb.shape[:2]
    geom_w = crop["width"] + crop["x"]
    geom_h = crop["height"] + crop["y"]
    x0 = min(crop["x"], img_w)
    y0 = min(crop["y"], img_h)
    x1 = min(x0 + geom_w, img_w)
    y1 = min(y0 + geom_h, img_h)
    return rgb[y0:y1, x0:x1]


def smart_crop_image(rgb: np.ndarray) -> np.ndarray:
    """The single-image post-pass: crop `rgb` like the reference's
    `smartcrop.py | convert -crop` pipeline. The batched serving path is
    ``prepare_work`` + ``find_best_crops_batched`` + ``apply_crop``."""
    # reference main(): width=100, height=int(h_opt / w_opt * 100) = 100
    return apply_crop(rgb, find_best_crop(rgb, 100, 100))


def entropy_crop_image(rgb: np.ndarray) -> np.ndarray:
    """Brownout-mode substitute for ``smart_crop_image`` (runtime/
    brownout.py; docs/degradation.md): the same square output contract —
    a side-``min(h, w)`` window — chosen by a pure host heuristic
    instead of the batched device scoring pass. The window slides along
    the long axis on the scorer's stride-8 grid and lands where summed
    gradient energy (|∇luma|, the cheap stand-in for entropy) is
    highest, ties going to the more central position — deterministic,
    O(W·H) numpy, no device work, no BlazeFace/feature program."""
    h, w = rgb.shape[:2]
    side = min(h, w)
    if h == w:
        return rgb
    luma = rgb.astype(np.float32).mean(axis=2)
    axis = 0 if h > w else 1
    # per-line energy along the long axis: gradient magnitude summed over
    # the short axis, then a sliding-window sum via one cumsum
    grad = np.abs(np.diff(luma, axis=axis)).sum(axis=1 - axis)
    grad = np.concatenate([grad, [0.0]])
    csum = np.concatenate([[0.0], np.cumsum(grad)])
    span = (h if axis == 0 else w) - side
    offsets = np.arange(0, span + 1, 8)
    if offsets[-1] != span:
        offsets = np.concatenate([offsets, [span]])
    window = csum[offsets + side] - csum[offsets]
    # strict argmax-first-win would bias toward the top/left edge on flat
    # images; prefer the candidate nearest center among near-ties
    best = window.max()
    near = offsets[window >= best * 0.999999]
    center = span / 2.0
    off = int(near[np.argmin(np.abs(near - center))])
    if axis == 0:
        return np.ascontiguousarray(rgb[off:off + side])
    return np.ascontiguousarray(rgb[:, off:off + side])


# ---------------------------------------------------------------------------
# batched serving path: many images -> crops in ONE device launch per
# shape bucket (the program bench.py measures is batched; serving must be
# too, or every distinct post-resize shape recompiles analyse_features)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class WorkItem:
    """Everything the batched scorer needs about one image: the prescaled
    work pixels plus the crop-geometry bookkeeping of find_best_crop()."""

    work: np.ndarray                 # [wh, ww, 3] uint8 prescaled image
    prescale_size: float
    crop_w: float                    # base crop dims in work coords
    crop_h: float
    scales: Tuple[float, ...]        # candidate scale multipliers
    step: int
    img_w: int
    img_h: int
    bucket: Tuple[int, int]          # padded (h, w) compile bucket


def prepare_work(
    rgb: np.ndarray,
    target_w: int = 100,
    target_h: int = 100,
    *,
    min_scale: float = 0.9,
    max_scale: float = 1.0,
    scale_step: float = 0.1,
    step: int = 8,
) -> WorkItem:
    """The host-side prescale bookkeeping of find_best_crop(), split out so
    the device part can batch across requests."""
    from flyimg_tpu.ops.compose import _bucket_dim

    img_h, img_w = rgb.shape[:2]
    scale = min(img_w / target_w, img_h / target_h)
    crop_w = int(math.floor(target_w * scale))
    crop_h = int(math.floor(target_h * scale))
    mscale = min(max_scale, max(1.0 / scale, min_scale))

    prescale_size = 1.0 / scale / mscale
    work = rgb
    if prescale_size < 1.0:
        work = _host_thumbnail(
            rgb, int(img_w * prescale_size), int(img_h * prescale_size)
        )
        crop_w = int(math.floor(crop_w * prescale_size))
        crop_h = int(math.floor(crop_h * prescale_size))
    else:
        prescale_size = 1.0

    scales = tuple(
        pct / 100.0
        for pct in range(
            int(max_scale * 100),
            int((mscale - scale_step) * 100),
            -int(scale_step * 100),
        )
    )
    wh, ww = work.shape[:2]
    bucket = (_bucket_dim(wh, 32), _bucket_dim(ww, 32))
    return WorkItem(
        work=np.ascontiguousarray(work),
        prescale_size=prescale_size,
        crop_w=float(crop_w),
        crop_h=float(crop_h),
        scales=scales,
        step=step,
        img_w=img_w,
        img_h=img_h,
        bucket=bucket,
    )


def _analyse_features_valid(rgb: jnp.ndarray, true_hw: jnp.ndarray) -> jnp.ndarray:
    """The one feature-map implementation, on a possibly bucket-padded
    image with a dynamic valid region: pixels at (y, x) < true_hw get
    exactly the reference maps — the PIL unfiltered border lands on the
    VALID edge, not the padded array edge — and the padded remainder is
    garbage the caller masks off."""
    rgbf = rgb.astype(jnp.float32)
    r, g, b = rgbf[..., 0], rgbf[..., 1], rgbf[..., 2]
    # PIL convert('L', (0.2126, 0.7152, 0.0722, 0)) truncates to uint8
    cie = jnp.floor(0.2126 * r + 0.7152 * g + 0.0722 * b)

    # edge: 3x3 Laplacian, offset 1, clamped (PIL Kernel scale=1 offset=1,
    # smartcrop.py:231-232); PIL convolves the L (uint8) image and leaves
    # the 1px (valid-region) border unfiltered
    lap = (
        4.0 * cie
        - jnp.roll(cie, 1, 0) - jnp.roll(cie, -1, 0)
        - jnp.roll(cie, 1, 1) - jnp.roll(cie, -1, 1)
    )
    h, w = cie.shape
    th, tw = true_hw[0], true_hw[1]
    yy = jnp.arange(h)[:, None]
    xx = jnp.arange(w)[None, :]
    border = (yy == 0) | (yy == th - 1) | (xx == 0) | (xx == tw - 1)
    edge = jnp.where(border, cie, jnp.clip(lap + 1.0, 0.0, 255.0))
    edge = jnp.floor(edge)

    # skin (smartcrop.py:250-274)
    mag = jnp.sqrt(r * r + g * g + b * b)
    safe_mag = jnp.where(mag < 1e-6, 1.0, mag)
    rd = jnp.where(mag < 1e-6, -SKIN_COLOR[0], r / safe_mag - SKIN_COLOR[0])
    gd = jnp.where(mag < 1e-6, -SKIN_COLOR[1], g / safe_mag - SKIN_COLOR[1])
    bd = jnp.where(mag < 1e-6, -SKIN_COLOR[2], b / safe_mag - SKIN_COLOR[2])
    skin = 1.0 - jnp.sqrt(rd * rd + gd * gd + bd * bd)
    skin_mask = (
        (skin > SKIN_THRESHOLD)
        & (cie >= SKIN_BRIGHTNESS_MIN * 255.0)
        & (cie <= SKIN_BRIGHTNESS_MAX * 255.0)
    )
    skin_data = (skin - SKIN_THRESHOLD) * (255.0 / (1.0 - SKIN_THRESHOLD))
    skin_out = jnp.floor(jnp.clip(jnp.where(skin_mask, skin_data, 0.0), 0.0, 255.0))

    # saturation (smartcrop.py:16-27, 234-248)
    maximum = jnp.maximum(jnp.maximum(r, g), b)
    minimum = jnp.minimum(jnp.minimum(r, g), b)
    eq = maximum == minimum
    ssum = (maximum + minimum) / 255.0
    d_ = (maximum - minimum) / 255.0
    d_ = jnp.where(eq, 0.0, d_)
    ssum = jnp.where(eq, 1.0, ssum)
    ssum = jnp.where(ssum > 1.0, 2.0 - d_, ssum)
    sat = d_ / ssum
    sat_mask = (
        (sat > SATURATION_THRESHOLD)
        & (cie >= SATURATION_BRIGHTNESS_MIN * 255.0)
        & (cie <= SATURATION_BRIGHTNESS_MAX * 255.0)
    )
    sat_data = (sat - SATURATION_THRESHOLD) * (255.0 / (1.0 - SATURATION_THRESHOLD))
    sat_out = jnp.floor(jnp.clip(jnp.where(sat_mask, sat_data, 0.0), 0.0, 255.0))

    return jnp.stack([skin_out, edge, sat_out], axis=-1)


@jax.jit
def _batched_weighted(images: jnp.ndarray, in_true: jnp.ndarray) -> jnp.ndarray:
    """[B, bh, bw, 3] uint8 + [B, 2] valid dims -> [B, bh, bw] float32
    weighted scoring fields, zero outside each member's valid region (so
    box sums / totals over the padded array are exact)."""

    def one(img, true_hw):
        wf = weighted_field(_analyse_features_valid(img, true_hw))
        h, w = img.shape[:2]
        valid = (jnp.arange(h)[:, None] < true_hw[0]) & (
            jnp.arange(w)[None, :] < true_hw[1]
        )
        return jnp.where(valid, wf, 0.0)

    with jax.named_scope("flyimg.smartcrop_features"):
        return jax.vmap(one)(images, in_true)


@partial(jax.jit, static_argnames=("stride",))
def _batched_scores(weighted: jnp.ndarray, kernels: jnp.ndarray, stride: int):
    """[B, fh, fw] fields x [B, khm, kwm, C] per-member kernel stacks ->
    ([B, ny, nx, C] candidate grids, [B] field totals). Channel c < S is the
    scale-c importance kernel, channel S+c its box-sum ones mask; both are
    zero-padded to the (khm, kwm) bucket, which contributes exactly nothing
    to a VALID correlation over a field that is itself zero-padded."""
    with jax.named_scope("flyimg.smartcrop_scores"):
        grids = jax.vmap(partial(_window_scores, stride=stride))(
            weighted, kernels
        )
        totals = jnp.sum(weighted, axis=(1, 2))
        return grids, totals


def _crop_from_best(best, item: WorkItem) -> Dict[str, int]:
    """(score, x, y, cw, ch) in work coords -> source-coords crop dict;
    None (degenerate image smaller than any candidate) -> whole image."""
    if best is None:
        return {"x": 0, "y": 0, "width": item.img_w, "height": item.img_h}
    _, x, y, cw, ch = best
    ps = item.prescale_size
    return {
        "x": int(math.floor(x / ps)),
        "y": int(math.floor(y / ps)),
        "width": int(math.floor(cw / ps)),
        "height": int(math.floor(ch / ps)),
    }


def _member_scale_geometry(item: WorkItem, s: float):
    """(cw, ch, max_x, max_y) for one candidate scale, or None when the
    scale is skipped (find_best_crop's `continue` guards)."""
    cw = item.crop_w * s
    ch = item.crop_h * s
    if cw < 1.0 or ch < 1.0:
        return None
    wh, ww = item.work.shape[:2]
    max_x = int((ww - cw) // item.step) * item.step
    max_y = int((wh - ch) // item.step) * item.step
    if max_x < 0 or max_y < 0:
        return None
    return cw, ch, max_x, max_y


def find_best_crops_batched(items: Sequence[WorkItem]) -> List[Dict[str, int]]:
    """Crops for many images in one batched device launch per shape bucket.
    Exactly equivalent to per-image find_best_crop (pinned by
    tests/test_smartcrop.py): padding is zeros that cancel out of every conv
    and sum, and the per-member float crop dims ride in the kernels."""
    results: List[Dict[str, int]] = [None] * len(items)  # type: ignore
    by_bucket = defaultdict(list)
    for i, item in enumerate(items):
        by_bucket[(item.bucket, item.step)].append(i)
    for (bucket, step), idxs in by_bucket.items():
        crops = _run_bucket([items[i] for i in idxs], bucket, step)
        for i, crop in zip(idxs, crops):
            results[i] = crop
    return results


def _run_bucket(
    items: Sequence[WorkItem], bucket: Tuple[int, int], step: int
) -> List[Dict[str, int]]:
    from flyimg_tpu.ops.compose import _bucket_dim, bucket_batch

    n = len(items)
    # batch axis rides the power-of-two ladder (pad slots repeat the last
    # member) so occupancy 3 vs 5 vs 7 doesn't each compile a fresh program
    nb = bucket_batch(n)
    bh, bw = bucket
    images = np.zeros((nb, bh, bw, 3), np.uint8)
    in_true = np.zeros((nb, 2), np.float32)
    for i, item in enumerate(items):
        wh, ww = item.work.shape[:2]
        images[i, :wh, :ww] = item.work
        in_true[i] = (wh, ww)
    for i in range(n, nb):
        images[i] = images[n - 1]
        in_true[i] = in_true[n - 1]
    weighted = _batched_weighted(jnp.asarray(images), jnp.asarray(in_true))

    n_scales = max(len(item.scales) for item in items)
    kh_max = kw_max = 1
    y_max = x_max = 0
    geoms = []
    for item in items:
        per_scale = []
        for s in item.scales:
            geom = _member_scale_geometry(item, s)
            per_scale.append(geom)
            if geom is None:
                continue
            cw, ch, mx, my = geom
            kh_max = max(kh_max, int(math.ceil(ch)))
            kw_max = max(kw_max, int(math.ceil(cw)))
            y_max = max(y_max, my)
            x_max = max(x_max, mx)
        geoms.append(per_scale)
    khm = _bucket_dim(kh_max, 16)
    kwm = _bucket_dim(kw_max, 16)
    # the conv's VALID grid must reach every candidate position: grow the
    # (zero-padded, score-neutral) field so (fh - khm)//step covers y_max
    fh = max(bh, _bucket_dim(y_max + khm, 32))
    fw = max(bw, _bucket_dim(x_max + kwm, 32))
    if (fh, fw) != (bh, bw):
        weighted = jnp.pad(weighted, ((0, 0), (0, fh - bh), (0, fw - bw)))

    kernels = np.zeros((nb, khm, kwm, 2 * n_scales), np.float32)
    for i, item in enumerate(items):
        for si, geom in enumerate(geoms[i]):
            if geom is None:
                continue
            cw, ch, _, _ = geom
            ker = importance_kernel(cw, ch)
            kh, kw = ker.shape
            kernels[i, :kh, :kw, si] = ker
            kernels[i, :kh, :kw, n_scales + si] = 1.0
    for i in range(n, nb):
        kernels[i] = kernels[n - 1]

    grids, totals = _batched_scores(weighted, jnp.asarray(kernels), stride=step)
    grids = np.asarray(grids)
    totals = np.asarray(totals)

    out: List[Dict[str, int]] = []
    for i, item in enumerate(items):
        best = None
        for si, geom in enumerate(geoms[i]):
            if geom is None:
                continue
            cw, ch, mx, my = geom
            ny = my // step + 1
            nx = mx // step + 1
            inside = grids[i, :ny, :nx, si]
            boxsum = grids[i, :ny, :nx, n_scales + si]
            scores = (
                inside + OUTSIDE_IMPORTANCE * (totals[i] - boxsum)
            ) / (cw * ch)
            if scores.size == 0:
                continue
            idx = np.unravel_index(np.argmax(scores), scores.shape)
            top = float(scores[idx])
            if best is None or top > best[0]:
                best = (top, idx[1] * step, idx[0] * step, cw, ch)
        out.append(_crop_from_best(best, item))
    return out
