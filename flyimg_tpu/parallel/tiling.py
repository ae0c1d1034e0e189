"""Spatial tiling: H-sharded image transforms with halo exchange / ring.

The image-domain analog of ring/context parallelism (SURVEY.md section 5
"long-context"): a very large image (4k+) is sharded across devices along
its height. Two communication patterns, both pure ``jax.lax.ppermute``
over the mesh axis so the traffic rides ICI exactly like a ring-attention
block transfer:

- **halo exchange** (``tiled_transform``, ``tiled_filter``): ops whose
  output rows need a BOUNDED neighborhood of input rows (resample kernel
  support, convolution radius) fetch that many boundary rows from each
  neighbor in one ppermute pair.
- **ring accumulation** (``tiled_rotate``): rotation needs input rows
  from arbitrarily far away (a 45-degree rotation of a tall image mixes
  top and bottom), so tiles circulate the whole ring — n steps, O(H/n)
  memory per device, never an all_gather — and every device accumulates
  the bilinear taps that each visiting tile owns. This is structurally
  the ring-attention schedule with "taps owned by the visiting block" in
  place of attention scores.

Used for the "4k -> 256 thumbnail firehose" config (BASELINE.json
configs[4]) where a single image's transform is worth splitting across the
pod; the serving batch path (runtime/batcher.py) stays pure data-parallel.
"""

from __future__ import annotations

from functools import lru_cache
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from flyimg_tpu.ops.resample import resample_matrix


def _halo_exchange(
    tile: jnp.ndarray, halo: int, axis_name: str, fill: str = "zero"
) -> jnp.ndarray:
    """Concatenate ``halo`` rows from the previous/next device around the
    local tile. At the image's outer edges (device 0's top, device n-1's
    bottom) the ring wraps, so those halos are replaced per ``fill``:
    ``"zero"`` (masked out of resample weights) or ``"edge"`` (replicate
    the boundary row — ImageMagick's edge virtual-pixel policy, matching
    ops.filters._separable_conv's mode='edge' padding)."""
    n = jax.lax.axis_size(axis_name)
    fwd = [(i, (i + 1) % n) for i in range(n)]
    bwd = [(i, (i - 1) % n) for i in range(n)]
    # my bottom rows -> next device's top halo; my top rows -> prev's bottom
    from_prev = jax.lax.ppermute(tile[-halo:], axis_name, fwd)
    from_next = jax.lax.ppermute(tile[:halo], axis_name, bwd)
    idx = jax.lax.axis_index(axis_name)
    if fill == "edge":
        top_fill = jnp.broadcast_to(tile[:1], (halo,) + tile.shape[1:])
        bot_fill = jnp.broadcast_to(tile[-1:], (halo,) + tile.shape[1:])
    else:
        top_fill = jnp.zeros_like(from_prev)
        bot_fill = jnp.zeros_like(from_next)
    from_prev = jnp.where(idx == 0, top_fill, from_prev)
    from_next = jnp.where(idx == n - 1, bot_fill, from_next)
    return jnp.concatenate([from_prev, tile, from_next], axis=0)


def tiled_transform(
    image: jnp.ndarray,
    out_hw: Tuple[int, int],
    mesh: Mesh,
    *,
    axis: str = "sp",
    method: str = "lanczos3",
) -> jnp.ndarray:
    """Resize [H, W, 3] -> [out_h, out_w, 3] with H sharded over
    ``mesh[axis]``. Heights that don't divide the axis size are padded to
    it (edge-replicated input rows, garbage output rows sliced off), so
    ANY tall image rides the firehose path, not just divisible ones.

    Programs are cached by (geometry, mesh, method) — serving hot paths
    (handler._tiled_or_none) re-trace nothing for a repeated geometry.
    """
    n = int(mesh.shape[axis])
    in_h, in_w = int(image.shape[0]), int(image.shape[1])
    out_h, out_w = int(out_hw[0]), int(out_hw[1])
    pad_in = (-in_h) % n
    pad_out = (-out_h) % n
    if required_halo(in_h + pad_in, out_h + pad_out, in_h, out_h, n) > (
        (in_h + pad_in) // n
    ):
        # extreme downscales of short-ish tiles would need more neighbor
        # rows than a tile holds; clamping would silently corrupt pixels
        raise ValueError(
            f"tiled resample infeasible: halo exceeds tile height for "
            f"{in_h}->{out_h} over {n} devices"
        )
    # pad rows only so the shard splits evenly — the kernel's bottom_valid
    # mask zeroes their weights, so the replicated values never matter
    x = image.astype(jnp.float32)
    if pad_in:
        x = jnp.pad(x, ((0, pad_in), (0, 0), (0, 0)), mode="edge")
    fn = _build_tiled_program(
        in_h + pad_in, in_w, (out_h + pad_out, out_w), mesh, axis, method,
        true_in_h=in_h, true_out_h=out_h,
    )
    out = fn(x)
    return out[:out_h] if pad_out else out


def required_halo(
    in_h_pad: int, out_h_pad: int, src_h: int, dst_h: int, n: int
) -> int:
    """Neighbor rows each tile needs: kernel support at the true scale plus
    the cumulative drift between the padded tile grid and the true span
    (device idx's outputs start at idx*out_tile_h*row_scale but its tile
    starts at idx*tile_h)."""
    scale_y = max(src_h / dst_h, 1.0)
    drift = (out_h_pad // n) * (src_h / dst_h) - in_h_pad // n
    return int(3.0 * scale_y + 2.0 + abs(drift) * (n - 1)) + 1


@lru_cache(maxsize=128)
def _build_tiled_program(
    in_h: int,
    in_w: int,
    out_hw: Tuple[int, int],
    mesh: Mesh,
    axis: str,
    method: str,
    *,
    true_in_h: int = None,
    true_out_h: int = None,
):
    """Jitted shard_map program for one tiled-resample geometry.

    Per-device work: resample the full width axis locally (replicated W),
    and the height axis from (local tile + halos) with a weight matrix whose
    sample coordinates are offset by the device's global tile position —
    ppermute is the only cross-device communication.

    ``true_in_h``/``true_out_h`` carry the unpadded geometry when the
    sharded dims were rounded up to the axis size: sampling coordinates
    derive from the TRUE scale, rows at/past true_in_h are masked out of
    the weights (clamp-to-edge semantics, matching ops/resample.py), and
    output rows past true_out_h are garbage the caller slices off.
    """
    n = mesh.shape[axis]
    out_h, out_w = out_hw
    if in_h % n or out_h % n:
        raise ValueError(f"H={in_h} and out_h={out_h} must divide mesh axis {n}")
    src_h = true_in_h if true_in_h is not None else in_h
    dst_h = true_out_h if true_out_h is not None else out_h
    tile_h = in_h // n
    out_tile_h = out_h // n
    # neighbor rows each tile needs (callers pre-check feasibility; the
    # assert is the safety net against silent pixel corruption). Programs
    # compile per (in_h_pad, out) geometry — tall-image traffic clusters
    # on a handful of camera/pipeline geometries (the firehose config is
    # ONE), matching the pre-padding behavior for divisible heights.
    halo = required_halo(in_h, out_h, src_h, dst_h, n)
    assert halo <= tile_h, (halo, tile_h)

    def kernel(tile):  # [tile_h, W, 3] on each device
        idx = jax.lax.axis_index(axis)
        padded = _halo_exchange(tile, halo, axis)  # [tile_h + 2*halo, W, 3]
        local_rows = tile_h + 2 * halo
        # global source span of MY output rows, expressed in local coords:
        # out row r (global r0 = idx*out_tile_h) samples global source
        # y = (r + .5) * src_h/dst_h - .5; local y = y - (idx*tile_h - halo)
        row_scale = src_h / dst_h
        global_start = idx * out_tile_h * row_scale
        local_offset = idx * tile_h - halo
        span_start = global_start - local_offset
        span_size = out_tile_h * row_scale
        # valid local rows: [halo, halo+tile_h) plus real halo rows where the
        # neighbor exists; weight masking uses in_true rows from the top.
        # Rows at/past the TRUE source height (bucket padding) are invalid
        # everywhere — the min() folds both limits into one clamp.
        top_valid = jnp.where(idx == 0, halo, 0)
        bottom_valid = jnp.where(
            idx == jax.lax.axis_size(axis) - 1, local_rows - halo, local_rows
        )
        bottom_valid = jnp.minimum(
            bottom_valid, jnp.float32(src_h) - local_offset
        )
        wy = resample_matrix(
            local_rows, out_tile_h,
            span_start, span_size,
            jnp.float32(out_tile_h), jnp.float32(bottom_valid),
            method,
        )
        # also zero taps above top_valid (edge devices' wrapped halo)
        j = jnp.arange(local_rows, dtype=jnp.float32)
        wy = jnp.where(j[None, :] >= top_valid, wy, 0.0)
        denom = jnp.sum(wy, axis=-1, keepdims=True)
        wy = wy / jnp.where(denom == 0.0, 1.0, denom)
        wx = resample_matrix(
            in_w, out_w,
            jnp.float32(0.0), jnp.float32(in_w),
            jnp.float32(out_w), jnp.float32(in_w),
            method,
        )
        tmp = jnp.einsum(
            "oh,hwc->owc", wy, padded.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        return jnp.einsum(
            "ow,hwc->hoc", wx, tmp, precision=jax.lax.Precision.HIGHEST,
        )

    sharded = jax.shard_map(
        kernel,
        mesh=mesh,
        in_specs=P(axis, None, None),
        out_specs=P(axis, None, None),
    )
    return jax.jit(sharded)


# ---------------------------------------------------------------------------
# tiled convolution filters: halo exchange with IM's edge virtual pixels
# ---------------------------------------------------------------------------


def tiled_filter(
    image: jnp.ndarray,
    mesh: Mesh,
    op: str,
    radius: float,
    sigma: float,
    *,
    gain: float = 1.0,
    threshold: float = 0.05,
    axis: str = "sp",
) -> jnp.ndarray:
    """Gaussian ``blur`` / ``sharpen`` / ``unsharp`` of [H, W, 3] with H
    sharded over ``mesh[axis]`` — same semantics as ops.filters, with the
    kernel's half-width exchanged as halo rows (one ppermute pair; the
    bounded-neighborhood pattern, vs the ring rotate's unbounded one).

    Bottom-padding for indivisible heights uses mode='edge', which IS the
    filter's virtual-pixel policy, so sliced-off pad rows never perturb
    true outputs.
    """
    from flyimg_tpu.ops.filters import _gaussian_kernel

    if op not in ("blur", "sharpen", "unsharp"):
        raise ValueError(f"unknown tiled filter op {op!r}")
    n = int(mesh.shape[axis])
    in_h = int(image.shape[0])
    kernel = _gaussian_kernel(radius, sigma)
    half = int(kernel.shape[0]) // 2
    pad_in = (-in_h) % n
    if half > (in_h + pad_in) // n:
        raise ValueError(
            f"tiled filter infeasible: kernel half-width {half} exceeds "
            f"tile height {(in_h + pad_in) // n} over {n} devices"
        )
    x = image.astype(jnp.float32)
    if pad_in:
        x = jnp.pad(x, ((0, pad_in), (0, 0), (0, 0)), mode="edge")
    fn = _build_tiled_filter(
        in_h + pad_in, int(image.shape[1]), mesh, axis, op,
        float(radius), float(sigma), float(gain), float(threshold),
    )
    out = fn(x)
    return out[:in_h] if pad_in else out


@lru_cache(maxsize=128)
def _build_tiled_filter(
    in_h: int, in_w: int, mesh: Mesh, axis: str, op: str,
    radius: float, sigma: float, gain: float, threshold: float,
):
    from flyimg_tpu.ops.filters import _gaussian_kernel

    n = int(mesh.shape[axis])
    tile_h = in_h // n

    def kernel_fn(tile):  # [tile_h, in_w, 3]
        kern = _gaussian_kernel(radius, sigma)
        half = kern.shape[0] // 2
        ext = _halo_exchange(tile, half, axis, fill="edge")  # [tile_h+2*half, W, 3]
        # exactly ops.filters' conv body, with the H pad rows supplied by
        # neighbors instead of local edge replication
        from flyimg_tpu.ops.filters import _separable_conv_core, unsharp_from_blurred

        blurred = _separable_conv_core(ext[None], kern)[0]
        if op == "blur":
            return blurred
        # sharpen == unsharp with gain 1, no threshold (ops.filters.sharpen)
        eff_gain = gain if op == "unsharp" else 1.0
        eff_threshold = threshold if op == "unsharp" else 0.0
        return unsharp_from_blurred(tile, blurred, eff_gain, eff_threshold)

    sharded = jax.shard_map(
        kernel_fn,
        mesh=mesh,
        in_specs=P(axis, None, None),
        out_specs=P(axis, None, None),
    )
    return jax.jit(sharded)


# ---------------------------------------------------------------------------
# ring rotate: all-to-all-distance gather via tile circulation
# ---------------------------------------------------------------------------


def tiled_rotate(
    image: jnp.ndarray,
    degrees: float,
    mesh: Mesh,
    *,
    axis: str = "sp",
    background=None,
) -> jnp.ndarray:
    """Rotate [H, W, 3] by ``degrees`` (IM convention, clockwise) with H
    sharded over ``mesh[axis]`` — same sampling semantics as
    ops.rotate.rotate_image (inverse-affine bilinear, clamped taps,
    background fill), executed as an n-step ppermute ring.

    Every output pixel's two y-taps are CLAMPED to the true image rows, so
    each tap row is owned by exactly one input tile; accumulating "the taps
    the visiting tile owns" over a full ring cycle therefore reconstructs
    the exact single-device bilinear sum. No halo rows and no all_gather:
    peak per-device memory is one visiting tile + one output tile.
    """
    from flyimg_tpu.spec.plan import rotated_bounds

    quad = float(degrees) % 360.0
    if quad == 0.0:
        return image
    n = int(mesh.shape[axis])
    in_h, in_w = int(image.shape[0]), int(image.shape[1])
    out_w, out_h = rotated_bounds(in_w, in_h, quad)
    pad_in = (-in_h) % n
    pad_out = (-out_h) % n
    x = image.astype(jnp.float32)
    if pad_in:
        # padded rows are never sampled (taps clamp to true rows); edge
        # mode just keeps the values finite
        x = jnp.pad(x, ((0, pad_in), (0, 0), (0, 0)), mode="edge")
    fn = _build_ring_rotate(
        in_h + pad_in, in_w, quad, mesh, axis,
        true_in_h=in_h,
        out_hw=(out_h + pad_out, out_w),
        true_out_hw=(out_h, out_w),
        background=tuple(background) if background else None,
    )
    out = fn(x)
    return out[:out_h] if pad_out else out


@lru_cache(maxsize=128)
def _build_ring_rotate(
    in_h: int,
    in_w: int,
    degrees: float,
    mesh: Mesh,
    axis: str,
    *,
    true_in_h: int,
    out_hw: Tuple[int, int],
    true_out_hw: Tuple[int, int],
    background,
):
    import math

    n = int(mesh.shape[axis])
    out_h, out_w = out_hw
    rot_h, rot_w = true_out_hw
    tile_h = in_h // n
    out_tile_h = out_h // n
    th = float(true_in_h)
    tw = float(in_w)
    theta = math.radians(degrees)
    cos_t, sin_t = math.cos(theta), math.sin(theta)
    bg = jnp.array(background or (255, 255, 255), jnp.float32)

    def kernel(tile):  # [tile_h, in_w, 3] on each device
        idx = jax.lax.axis_index(axis)
        # my output rows, in global coordinates
        yo, xo = jnp.meshgrid(
            jnp.arange(out_tile_h, dtype=jnp.float32)
            + idx.astype(jnp.float32) * out_tile_h,
            jnp.arange(out_w, dtype=jnp.float32),
            indexing="ij",
        )
        cy_out = (rot_h - 1.0) / 2.0
        cx_out = (rot_w - 1.0) / 2.0
        cy_in = (th - 1.0) / 2.0
        cx_in = (tw - 1.0) / 2.0
        dx = xo - cx_out
        dy = yo - cy_out
        xs = cos_t * dx + sin_t * dy + cx_in
        ys = -sin_t * dx + cos_t * dy + cy_in

        x0 = jnp.floor(xs)
        y0 = jnp.floor(ys)
        fx = (xs - x0)[..., None]
        fy = (ys - y0)[..., None]
        xc0 = jnp.clip(x0, 0.0, tw - 1.0).astype(jnp.int32)
        xc1 = jnp.clip(x0 + 1.0, 0.0, tw - 1.0).astype(jnp.int32)
        # clamped GLOBAL tap rows: each is owned by exactly one tile
        yc0 = jnp.clip(y0, 0.0, th - 1.0).astype(jnp.int32)
        yc1 = jnp.clip(y0 + 1.0, 0.0, th - 1.0).astype(jnp.int32)

        def tap_rows(visit, src0, yc, wrow):
            """Accumulate one y-tap's x-interpolated row values where the
            visiting tile [src0, src0+tile_h) owns the tap row."""
            local = yc - src0
            owned = ((local >= 0) & (local < tile_h))[..., None]
            lc = jnp.clip(local, 0, tile_h - 1)
            row0 = visit[lc, xc0]
            row1 = visit[lc, xc1]
            val = row0 * (1.0 - fx) + row1 * fx
            return jnp.where(owned, val * wrow, 0.0)

        perm = [(i, (i - 1) % n) for i in range(n)]

        def accumulate(visit, k, acc):
            # at step k I hold the tile of device (idx + k) mod n
            src0 = ((idx + k) % n) * tile_h
            acc = acc + tap_rows(visit, src0, yc0, 1.0 - fy)
            return acc + tap_rows(visit, src0, yc1, fy)

        def step(k, carry):
            visit, acc = carry
            acc = accumulate(visit, k, acc)
            visit = jax.lax.ppermute(visit, axis, perm)
            return visit, acc

        acc = jnp.zeros((out_tile_h, out_w, tile.shape[-1]), jnp.float32)
        # the fresh zeros are unvaried over the mesh axis while the loop
        # output varies with it; align the carry's varying-axes type
        acc = jax.lax.pcast(acc, (axis,), to="varying")
        # n-1 permuted steps, then the last visiting tile outside the loop:
        # XLA can't DCE a collective in a uniform loop body, so a full-n
        # loop would pay one extra full-tile ICI hop per rotate
        visit, acc = jax.lax.fori_loop(0, n - 1, step, (tile, acc))
        acc = accumulate(visit, n - 1, acc)

        inside = (
            (xs >= -0.5) & (xs <= tw - 0.5) & (ys >= -0.5) & (ys <= th - 0.5)
        )[..., None]
        return jnp.where(inside, acc, bg)

    sharded = jax.shard_map(
        kernel,
        mesh=mesh,
        in_specs=P(axis, None, None),
        out_specs=P(axis, None, None),
    )
    return jax.jit(sharded)
