"""Region pixelation for face blur.

Replaces the reference's per-face ``mogrify -gravity NorthWest -region
WxH+X+Y -scale 10% -scale 1000%`` (reference
src/Core/Processor/FaceDetectProcessor.php:51-76) — pixelation by 10x
down/up scaling inside each face rectangle.

TPU-first shape: instead of one exec per face, the WHOLE image is block-
averaged once (the 10%/1000% round trip == average over aligned 10x10
blocks, nearest-upsampled), then a per-pixel mask selects the pixelated
value inside any of the (padded, dynamic) face boxes. One jitted program
per (shape bucket, padded batch), ``uint8`` in and out, any number of
faces, batched over the images that share a bucket; the handler submits
its items to the device controller's aux path (``submit_aux``) and
``pixelate_images`` is what the runner calls.

**Block alignment (a stated departure from upstream):** the blocks are
aligned to the IMAGE's top-left corner, not to each region's; upstream's
``-region`` scales every box by itself, so its blocks start at the box's
corner. A partial block at the image's right or bottom edge repeats the
last column or row up to the factor; a block's value is its mean rounded
half to even.

Everything stays two-dimensional with the channels folded into the row
(``[h, w*3]``): a trailing axis of 3 is the worst layout a TPU can be
handed. Block sums and the nearest-neighbour expansion along the row are
products with 0/1 (and edge-multiplicity) matrices built from iotas and
the image's true size, exact in float32 at ``HIGHEST``; the mean's
rounding is integer arithmetic.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# the reference's -scale 10% ... 1000% round trip = factor-10 blocks
PIXELATE_FACTOR = 10
#: boxes an image's program takes (zero-area rows are inert padding)
MAX_BOXES = 32
#: images one program call takes: a launch of more runs in chunks, so the
#: programs to build stop at this padded batch and a call's float32
#: intermediates (some 60 MB an image at 1600x1066) stay under a GB
MAX_BATCH = 16
_BUCKET_STEP = 64


@dataclass(frozen=True)
class PixelateWork:
    image: np.ndarray            # [h, w, 3] uint8
    boxes: np.ndarray            # [MAX_BOXES, 4] int32 (x, y, w, h)
    bucket: Tuple[int, int]      # padded (h, w) compile bucket


def prepare_work(rgb: np.ndarray, boxes: Sequence[Sequence[int]]) -> PixelateWork:
    """One image and its (x, y, w, h) boxes as an item of the batched
    program; boxes beyond ``MAX_BOXES`` are dropped."""
    from flyimg_tpu.ops.compose import _bucket_dim

    h, w = rgb.shape[:2]
    padded = np.zeros((MAX_BOXES, 4), np.int32)
    for i, box in enumerate(list(boxes)[:MAX_BOXES]):
        padded[i] = box
    return PixelateWork(
        image=np.ascontiguousarray(rgb, dtype=np.uint8),
        boxes=padded,
        bucket=(_bucket_dim(h, _BUCKET_STEP), _bucket_dim(w, _BUCKET_STEP)),
    )


def _block_sum_matrix(size: int, true: jnp.ndarray, lanes: int) -> jnp.ndarray:
    """``[blocks*lanes, size*lanes]`` float32: how often sample ``x`` (of
    ``lanes`` interleaved channels) counts in the sum of block ``x //
    factor`` of its own channel, when an axis of ``true`` real samples is
    edge-padded to whole blocks: once, and the last real sample once more
    for every padded place of its block; samples beyond ``true`` never."""
    f = PIXELATE_FACTOR
    blocks = -(-size // f)
    x = jnp.arange(size * lanes, dtype=jnp.int32)[None, :]
    j = jnp.arange(blocks * lanes, dtype=jnp.int32)[:, None]
    pos, lane = x // lanes, x % lanes
    blk, blk_lane = j // lanes, j % lanes
    whole = ((true + f - 1) // f) * f
    padded_places = jnp.clip(
        jnp.minimum((blk + 1) * f, whole) - jnp.maximum(blk * f, true), 0, f
    )
    counts = (pos // f == blk).astype(jnp.int32) + (pos == true - 1) * padded_places
    return jnp.where((pos < true) & (lane == blk_lane), counts, 0).astype(jnp.float32)


def _pixelate_one(image: jnp.ndarray, true_hw: jnp.ndarray, boxes: jnp.ndarray) -> jnp.ndarray:
    """``[bh, bw*3]`` uint8, its true (h, w) and ``[K, 4]`` int32 boxes ->
    the same shape, pixelated inside the boxes. Beyond the true size the
    output is unspecified (the caller slices)."""
    f = PIXELATE_FACTOR
    bh, bw3 = image.shape
    bw = bw3 // 3
    hi = jax.lax.Precision.HIGHEST
    x = image.astype(jnp.float32)
    rows = _block_sum_matrix(bh, true_hw[0], 1)                      # [hb, bh]
    cols = _block_sum_matrix(bw, true_hw[1], 3)                      # [wb*3, bw*3]
    sums = jnp.matmul(jnp.matmul(rows, x, precision=hi), cols.T, precision=hi)
    # a block's mean, rounded half to even, in whole numbers: the sums are
    # exact integers under 2**24
    total = sums.astype(jnp.int32)
    area = f * f
    q, r = total // area, total % area
    q = q + ((2 * r > area) | ((2 * r == area) & (q % 2 == 1))).astype(jnp.int32)
    # back to the image's grid: along the row by a 0/1 product (values up
    # to 255 and one term a sum: exact), down the rows by repetition
    spread = (cols > 0).astype(jnp.float32)                          # a sample's own block
    wide = jnp.matmul(q.astype(jnp.float32), spread, precision=hi)   # [hb, bw*3]
    pixelated = jnp.repeat(wide, f, axis=0)[:bh]

    ys = jnp.arange(bh, dtype=jnp.int32)[None, :]
    xs = (jnp.arange(bw3, dtype=jnp.int32) // 3)[None, :]
    bx, by, bwid, bhei = (boxes[:, k:k + 1] for k in range(4))
    in_rows = ((ys >= by) & (ys < by + bhei)).astype(jnp.bfloat16)    # [K, bh]
    in_cols = ((xs >= bx) & (xs < bx + bwid)).astype(jnp.bfloat16)    # [K, bw*3]
    inside = jnp.matmul(in_rows.T, in_cols, preferred_element_type=jnp.float32) > 0
    return jnp.where(inside, pixelated, x).astype(jnp.uint8)


@jax.jit
def _pixelate_batch(images: jnp.ndarray, true_hw: jnp.ndarray, boxes: jnp.ndarray) -> jnp.ndarray:
    """``[B, bh, bw*3]`` uint8, ``[B, 2]`` int32, ``[B, K, 4]`` int32."""
    with jax.named_scope("flyimg.face_pixelate"):
        return jax.vmap(_pixelate_one)(images, true_hw, boxes)


def pixelate_images(
    items: List[PixelateWork], stats: Optional[Dict[str, int]] = None
) -> List[np.ndarray]:
    """Pixelate many images: one program call per shape bucket and chunk
    of ``MAX_BATCH`` (batch axis on the power-of-two ladder). ``stats``,
    where given, gains ``images``, ``slots`` (the padded batches run) and
    ``launches``."""
    from collections import defaultdict

    from flyimg_tpu.ops.compose import bucket_batch

    results: List[np.ndarray] = [None] * len(items)  # type: ignore
    by_bucket = defaultdict(list)
    for i, item in enumerate(items):
        by_bucket[item.bucket].append(i)
    launches = slots = 0
    for (bh, bw), idxs in by_bucket.items():
        for start in range(0, len(idxs), MAX_BATCH):
            chunk = idxs[start:start + MAX_BATCH]
            nb = bucket_batch(len(chunk))
            images = np.zeros((nb, bh, bw * 3), np.uint8)
            true_hw = np.ones((nb, 2), np.int32)
            boxes = np.zeros((nb, MAX_BOXES, 4), np.int32)
            for j, i in enumerate(chunk):
                h, w = items[i].image.shape[:2]
                images[j, :h, :w * 3] = items[i].image.reshape(h, w * 3)
                true_hw[j] = (h, w)
                boxes[j] = items[i].boxes
            out = np.asarray(_pixelate_batch(
                jnp.asarray(images), jnp.asarray(true_hw), jnp.asarray(boxes)
            ))
            launches += 1
            slots += nb
            for j, i in enumerate(chunk):
                h, w = items[i].image.shape[:2]
                results[i] = np.ascontiguousarray(out[j, :h, :w * 3]).reshape(h, w, 3)
    if stats is not None:
        stats["images"] = stats.get("images", 0) + len(items)
        stats["slots"] = stats.get("slots", 0) + slots
        stats["launches"] = stats.get("launches", 0) + launches
    return results


def pixelate_image(rgb: np.ndarray, boxes: Sequence[Sequence[int]]) -> np.ndarray:
    """One image through the same program (the path without a device
    controller, and the wedged-executor fallback)."""
    if not len(boxes):
        return rgb
    return pixelate_images([prepare_work(rgb, boxes)])[0]
