"""BlazeFace-style face detector in flax, with a sharded training step.

The north-star face backend (BASELINE.json: "python/smartcrop.py's OpenCV
Haar face-detect is replaced with a vmapped MediaPipe/BlazeFace JAX model").
Architecture follows the BlazeFace recipe (single-shot anchor detector built
from depthwise-separable "BlazeBlocks", two anchor scales at 16x16 and 8x8
feature maps, 128x128 RGB input) — implemented from the paper's shape, not
ported from any codebase.

Serving: ``detect_faces(params, rgb)`` is vmap/jit-friendly and returns the
same (x, y, w, h) box contract as models/facefind.py; a trained checkpoint
can be dropped in via orbax. Training: ``make_train_step`` builds a
jit-compiled step shardable over a (data, model) mesh — data parallelism
shards the batch, tensor parallelism shards the widest conv channels —
which is what __graft_entry__.dryrun_multichip exercises.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

INPUT_SIZE = 128
ANCHORS_16 = 2   # anchors per cell on the 16x16 map
ANCHORS_8 = 6    # anchors per cell on the 8x8 map
NUM_ANCHORS = 16 * 16 * ANCHORS_16 + 8 * 8 * ANCHORS_8  # 896, as in the paper


class BlazeBlock(nn.Module):
    """Depthwise 5x5 + pointwise 1x1 with residual; optional stride-2."""

    features: int
    stride: int = 1

    @nn.compact
    def __call__(self, x):
        residual = x
        y = nn.Conv(
            x.shape[-1], (5, 5), strides=(self.stride, self.stride),
            padding="SAME", feature_group_count=x.shape[-1], use_bias=False,
        )(x)
        y = nn.Conv(self.features, (1, 1), use_bias=True)(y)
        if self.stride == 2:
            residual = nn.max_pool(residual, (2, 2), strides=(2, 2))
        if residual.shape[-1] != self.features:
            pad = self.features - residual.shape[-1]
            residual = jnp.pad(residual, ((0, 0), (0, 0), (0, 0), (0, pad)))
        return nn.relu(y + residual)


class BlazeFace(nn.Module):
    """Backbone + dual-scale anchor heads (classification + box offsets)."""

    @nn.compact
    def __call__(self, x):
        # x: [B, 128, 128, 3] float32 in [-1, 1]
        x = nn.Conv(24, (5, 5), strides=(2, 2), padding="SAME")(x)  # 64x64
        x = nn.relu(x)
        x = BlazeBlock(24)(x)
        x = BlazeBlock(28)(x)
        x = BlazeBlock(32, stride=2)(x)    # 32x32
        x = BlazeBlock(36)(x)
        x = BlazeBlock(42)(x)
        x = BlazeBlock(48, stride=2)(x)    # 16x16
        x = BlazeBlock(56)(x)
        x = BlazeBlock(64)(x)
        x = BlazeBlock(72)(x)
        x = BlazeBlock(80)(x)
        x = BlazeBlock(88)(x)
        x16 = x                             # [B, 16, 16, 88]
        x = BlazeBlock(96, stride=2)(x16)  # 8x8
        x = BlazeBlock(96)(x)
        x = BlazeBlock(96)(x)
        x = BlazeBlock(96)(x)
        x8 = BlazeBlock(96)(x)             # [B, 8, 8, 96]

        cls16 = nn.Conv(ANCHORS_16, (1, 1))(x16)       # [B,16,16,2]
        reg16 = nn.Conv(ANCHORS_16 * 4, (1, 1))(x16)   # [B,16,16,8]
        cls8 = nn.Conv(ANCHORS_8, (1, 1))(x8)          # [B,8,8,6]
        reg8 = nn.Conv(ANCHORS_8 * 4, (1, 1))(x8)      # [B,8,8,24]

        batch = x.shape[0]
        scores = jnp.concatenate(
            [cls16.reshape(batch, -1), cls8.reshape(batch, -1)], axis=1
        )
        boxes = jnp.concatenate(
            [reg16.reshape(batch, -1, 4), reg8.reshape(batch, -1, 4)], axis=1
        )
        return scores, boxes  # [B, 896], [B, 896, 4]


def anchor_centers() -> np.ndarray:
    """[896, 4] anchors as (cx, cy, w, h) in [0,1] (uniform grid, unit-ish
    scale per map, as in the BlazeFace anchor scheme)."""
    anchors = []
    for grid, count, scale in ((16, ANCHORS_16, 0.10), (8, ANCHORS_8, 0.30)):
        for gy in range(grid):
            for gx in range(grid):
                cx = (gx + 0.5) / grid
                cy = (gy + 0.5) / grid
                for k in range(count):
                    s = scale * (1.0 + 0.5 * k / max(count - 1, 1))
                    anchors.append((cx, cy, s, s))
    return np.asarray(anchors, dtype=np.float32)


_ANCHORS_NP: Optional[np.ndarray] = None


def get_anchors() -> jnp.ndarray:
    """Anchor table as a jnp value. The cache holds the NUMPY array and
    converts per call: caching the jnp conversion would capture a tracer
    when the first caller is inside a jit trace, and any later retrace
    (a new batch bucket) would then reuse that dead tracer
    (UnexpectedTracerError). As a trace constant the conversion is free."""
    global _ANCHORS_NP
    if _ANCHORS_NP is None:
        _ANCHORS_NP = anchor_centers()
    return jnp.asarray(_ANCHORS_NP)


def init_params(rng: jax.Array) -> Dict[str, Any]:
    model = BlazeFace()
    dummy = jnp.zeros((1, INPUT_SIZE, INPUT_SIZE, 3), jnp.float32)
    return model.init(rng, dummy)


def decode_boxes(raw: jnp.ndarray) -> jnp.ndarray:
    """Anchor-relative offsets -> (cx, cy, w, h) in [0, 1]."""
    anchors = get_anchors()
    cx = anchors[:, 0] + raw[..., 0] * 0.1 * anchors[:, 2]
    cy = anchors[:, 1] + raw[..., 1] * 0.1 * anchors[:, 3]
    w = anchors[:, 2] * jnp.exp(jnp.clip(raw[..., 2] * 0.2, -4.0, 4.0))
    h = anchors[:, 3] * jnp.exp(jnp.clip(raw[..., 3] * 0.2, -4.0, 4.0))
    return jnp.stack([cx, cy, w, h], axis=-1)


@jax.jit
def _forward(params, images):
    """[B, 128, 128, 3] float32 in [-1, 1] -> probabilities [B, 896] and
    decoded (cx, cy, w, h) boxes [B, 896, 4] of the view, before any
    threshold. On the TPU the float32 convolutions run at
    ``jax.lax.Precision.DEFAULT``: bfloat16 operands, float32 sums."""
    with jax.named_scope("flyimg.blazeface"):
        scores, raw = BlazeFace().apply(params, images)
        probs = jax.nn.sigmoid(scores)
        boxes = decode_boxes(raw)
        return probs, boxes


def _network_input(rgb: np.ndarray) -> np.ndarray:
    from PIL import Image

    resized = np.asarray(
        Image.fromarray(rgb).resize((INPUT_SIZE, INPUT_SIZE), Image.BILINEAR),
        dtype=np.float32,
    )
    return resized / 127.5 - 1.0


def _boxes_from_scores(
    probs: np.ndarray,
    boxes: np.ndarray,
    src_w: int,
    src_h: int,
    score_threshold: float,
    max_faces: int,
) -> List[Tuple[int, int, int, int]]:
    """Greedy NMS over decoded anchors -> pixel boxes (shared by the
    single-image and batched entry points). The candidate budget scales
    with the anchor count: multiscale concatenates several views, whose
    cross-view duplicates of a strong face would otherwise crowd weaker
    faces out of a fixed top-64 before NMS dedups them."""
    n_views = max(1, len(probs) // NUM_ANCHORS)
    keep = np.argsort(-probs)[: max_faces * 4 * n_views]
    out: List[Tuple[int, int, int, int]] = []
    taken: List[Tuple[float, float, float, float]] = []
    for idx in keep:
        if probs[idx] < score_threshold or len(out) >= max_faces:
            break
        cx, cy, w, h = boxes[idx]
        cand = (cx - w / 2, cy - h / 2, cx + w / 2, cy + h / 2)
        if any(_iou(cand, t) > 0.3 for t in taken):
            continue
        taken.append(cand)
        x0 = int(max(cand[0], 0.0) * src_w)
        y0 = int(max(cand[1], 0.0) * src_h)
        x1 = int(min(cand[2], 1.0) * src_w)
        y1 = int(min(cand[3], 1.0) * src_h)
        if x1 > x0 and y1 > y0:
            out.append((x0, y0, x1 - x0, y1 - y0))
    return out


#: tile views kick in above this size: a 128^2 network input means a face
#: spanning < ~15% of a large frame lands below the training scale range
#: (tools/train_blazeface.py pastes at 15-55%); 0.6-side corner tiles with
#: 20% overlap bring group-photo heads back into range
MULTISCALE_MIN_SIDE = 256
_TILE_FRAC = 0.6


def _views(rgb: np.ndarray) -> List[Tuple[int, int, int, int]]:
    """(x, y, w, h) regions to run the fixed-input network over: the full
    frame, a zoomed-OUT 2x canvas (a portrait crop whose face fills the
    frame lands back in the training scale range), plus four overlapping
    corner tiles for large frames. Regions may extend beyond the image;
    extraction pads with mid-gray."""
    h, w = rgb.shape[:2]
    views = [(0, 0, w, h), (-w // 2, -h // 2, 2 * w, 2 * h)]
    if min(h, w) >= MULTISCALE_MIN_SIDE:
        tw, th = int(w * _TILE_FRAC), int(h * _TILE_FRAC)
        for ox in (0, w - tw):
            for oy in (0, h - th):
                views.append((ox, oy, tw, th))
    return views


def _view_input(rgb: np.ndarray, x: int, y: int, vw: int, vh: int) -> np.ndarray:
    """Network input for view (x, y, vw, vh), which may extend beyond the
    image (mid-gray outside). The padded case resizes the visible part
    DIRECTLY to its slot in the 128x128 canvas — materializing the view
    at source resolution first (e.g. a 2w x 2h zoom-out canvas of a large
    upload) would allocate 4x the image per request just to throw it away
    in the downscale."""
    from PIL import Image

    h, w = rgb.shape[:2]
    if 0 <= x and 0 <= y and x + vw <= w and y + vh <= h:
        return _network_input(rgb[y : y + vh, x : x + vw])
    canvas = np.full((INPUT_SIZE, INPUT_SIZE, 3), 128, np.uint8)
    sx0, sy0 = max(x, 0), max(y, 0)
    sx1, sy1 = min(x + vw, w), min(y + vh, h)
    if sx1 > sx0 and sy1 > sy0:
        dx0 = round((sx0 - x) * INPUT_SIZE / vw)
        dx1 = round((sx1 - x) * INPUT_SIZE / vw)
        dy0 = round((sy0 - y) * INPUT_SIZE / vh)
        dy1 = round((sy1 - y) * INPUT_SIZE / vh)
        if dx1 > dx0 and dy1 > dy0:
            canvas[dy0:dy1, dx0:dx1] = np.asarray(
                Image.fromarray(rgb[sy0:sy1, sx0:sx1]).resize(
                    (dx1 - dx0, dy1 - dy0), Image.BILINEAR
                )
            )
    return canvas.astype(np.float32) / 127.5 - 1.0


#: boxes a detection keeps (the serving default; facefind keeps 32)
MAX_FACES = 16


@dataclass(frozen=True)
class FaceViews:
    """One image's network inputs, made where the request runs."""

    inputs: np.ndarray                           # [V, 128, 128, 3] float32
    views: Tuple[Tuple[int, int, int, int], ...]  # (x, y, w, h) of each, in the image
    width: int
    height: int
    # fixed network input -> every request shares one aux bucket/key
    bucket: Tuple[int, int] = (INPUT_SIZE, INPUT_SIZE)


def prepare_views(rgb: np.ndarray) -> FaceViews:
    """[h, w, 3] uint8 -> the image's views as network inputs: the host's
    share of a detection (six Pillow resizes for a large frame), on the
    caller's thread."""
    h, w = rgb.shape[:2]
    views = _views(rgb)
    inputs = np.stack([_view_input(rgb, *view) for view in views])
    return FaceViews(inputs=inputs, views=tuple(views), width=w, height=h)


def forward_views(params, works: List[FaceViews],
                  stats: Optional[Dict[str, float]] = None):
    """Every view of every work through ``_forward``, in chunks of the
    runtime's batch-bucket ceiling (runtime/batcher.py MAX_BATCH_BUCKET):
    a 64-image aux flush carries up to 6 views each, and one 512-wide
    forward would mean fresh XLA compiles for never-before-seen buckets
    at serve time, under burst load. Returns probabilities and boxes,
    one row a view, in order. ``stats``, where given, gains ``views``
    (real inputs), ``slots`` (padded inputs run) and ``forwards``, and
    the seconds of two parts, each chunk's summed: ``stack_s`` (the
    views into one padded array) and ``forward_s`` (the array to the
    device, the forward, both read-backs). Each part is also the
    annotation of its name inside the launch that runs this
    (``tracing.launch_annotation``)."""
    from flyimg_tpu.runtime import tracing
    from flyimg_tpu.runtime.batcher import MAX_BATCH_BUCKET, _round_batch

    flat = [view for work in works for view in work.inputs]
    probs_parts, boxes_parts = [], []
    slots = 0
    stack_s = forward_s = 0.0
    for start in range(0, len(flat), MAX_BATCH_BUCKET):
        chunk = flat[start : start + MAX_BATCH_BUCKET]
        nb = _round_batch(len(chunk))
        t0 = time.perf_counter()
        with tracing.launch_annotation("stack"):
            inputs = np.zeros((nb, INPUT_SIZE, INPUT_SIZE, 3), np.float32)
            np.stack(chunk, out=inputs[: len(chunk)])
        t1 = time.perf_counter()
        with tracing.launch_annotation("forward"):
            p, b = _forward(params, jnp.asarray(inputs))
            probs_parts.append(np.asarray(p)[: len(chunk)])
            boxes_parts.append(np.asarray(b)[: len(chunk)])
        t2 = time.perf_counter()
        stack_s += t1 - t0
        forward_s += t2 - t1
        slots += nb
    if stats is not None:
        stats["views"] = stats.get("views", 0) + len(flat)
        stats["slots"] = stats.get("slots", 0) + slots
        stats["forwards"] = stats.get("forwards", 0) + len(probs_parts)
        stats["stack_s"] = stats.get("stack_s", 0.0) + stack_s
        stats["forward_s"] = stats.get("forward_s", 0.0) + forward_s
    return np.concatenate(probs_parts), np.concatenate(boxes_parts)


def detect_prepared(
    params,
    works: List[FaceViews],
    *,
    score_threshold: float = 0.5,
    max_faces: int = MAX_FACES,
    stats: Optional[Dict[str, float]] = None,
) -> List[List[Tuple[int, int, int, int]]]:
    """Prepared images -> boxes: every view of every image shares the
    fixed 128x128 network input, so the whole multiscale pyramid across
    all images is one compiled program a chunk (batch axis on the
    power-of-two ladder). Per image, view detections merge in one global
    NMS (anchors from a corner tile compete with full-frame anchors on
    score). THE detection path: ``detect_faces`` and the batched runner
    (models/faces.py) both end here. ``stats`` as ``forward_views``',
    and ``boxes_s``: the view boxes mapped back and the NMS of every
    image (annotated ``boxes``)."""
    from flyimg_tpu.runtime import tracing

    if not works:
        return []
    probs, boxes = forward_views(params, works, stats)
    t0 = time.perf_counter()
    with tracing.launch_annotation("boxes"):
        out = _boxes_of(works, probs, boxes, score_threshold, max_faces)
    if stats is not None:
        stats["boxes_s"] = stats.get("boxes_s", 0.0) + (
            time.perf_counter() - t0
        )
    return out


def _boxes_of(works: List[FaceViews], probs: np.ndarray, boxes: np.ndarray,
              score_threshold: float, max_faces: int):
    """Each image's view boxes mapped back to the frame, then its NMS."""
    out: List[List[Tuple[int, int, int, int]]] = []
    vi = 0
    for work in works:
        w, h = work.width, work.height
        ps, bs = [], []
        for x, y, vw, vh in work.views:
            b = boxes[vi]
            ps.append(probs[vi])
            vi += 1
            # view-normalized (cx, cy, w, h) -> full-frame normalized
            bs.append(np.stack(
                [
                    (x + b[:, 0] * vw) / w,
                    (y + b[:, 1] * vh) / h,
                    b[:, 2] * vw / w,
                    b[:, 3] * vh / h,
                ],
                axis=-1,
            ))
        out.append(
            _boxes_from_scores(
                np.concatenate(ps), np.concatenate(bs), w, h,
                score_threshold, max_faces,
            )
        )
    return out


def detect_faces(
    params,
    rgb: np.ndarray,
    *,
    score_threshold: float = 0.5,
    max_faces: int = MAX_FACES,
) -> List[Tuple[int, int, int, int]]:
    """[h, w, 3] uint8 -> list of (x, y, w, h) pixel boxes. Same contract as
    facefind.detect_faces so the handler can swap backends."""
    return detect_prepared(
        params, [prepare_views(rgb)],
        score_threshold=score_threshold, max_faces=max_faces,
    )[0]


def _iou(a, b) -> float:
    ix = max(0.0, min(a[2], b[2]) - max(a[0], b[0]))
    iy = max(0.0, min(a[3], b[3]) - max(a[1], b[1]))
    inter = ix * iy
    area_a = (a[2] - a[0]) * (a[3] - a[1])
    area_b = (b[2] - b[0]) * (b[3] - b[1])
    union = area_a + area_b - inter
    return inter / union if union > 0 else 0.0


# ---------------------------------------------------------------------------
# training (exercised by __graft_entry__.dryrun_multichip on a fake mesh)
# ---------------------------------------------------------------------------


def loss_fn(params, images, target_probs, target_boxes, anchor_mask):
    """Focal-ish BCE on anchor scores + smooth-L1 on positive anchor boxes."""
    scores, raw = BlazeFace().apply(params, images)
    probs = jax.nn.sigmoid(scores)
    bce = -(
        target_probs * jnp.log(probs + 1e-7)
        + (1.0 - target_probs) * jnp.log(1.0 - probs + 1e-7)
    )
    focal = bce * (0.25 + 0.75 * target_probs)
    cls_loss = jnp.mean(focal)

    diff = raw - target_boxes
    l1 = jnp.where(jnp.abs(diff) < 1.0, 0.5 * diff * diff, jnp.abs(diff) - 0.5)
    reg_loss = jnp.sum(l1 * anchor_mask[..., None]) / (
        jnp.sum(anchor_mask) * 4.0 + 1e-6
    )
    return cls_loss + reg_loss


def make_train_step(optimizer: Optional[optax.GradientTransformation] = None):
    optimizer = optimizer or optax.adam(1e-3)

    def train_step(params, opt_state, images, target_probs, target_boxes, anchor_mask):
        loss, grads = jax.value_and_grad(loss_fn)(
            params, images, target_probs, target_boxes, anchor_mask
        )
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return optimizer, train_step


def synthetic_batch(rng: np.random.Generator, batch: int):
    """Synthetic training batch: colored ellipse "faces" on noise, with the
    matching anchor targets — enough to drive a real optimization step (and
    the multi-chip dryrun) without external data."""
    anchors = np.asarray(anchor_centers())
    images = rng.uniform(-1, 1, (batch, INPUT_SIZE, INPUT_SIZE, 3)).astype(np.float32)
    target_probs = np.zeros((batch, NUM_ANCHORS), np.float32)
    target_boxes = np.zeros((batch, NUM_ANCHORS, 4), np.float32)
    mask = np.zeros((batch, NUM_ANCHORS), np.float32)
    for i in range(batch):
        cx, cy = rng.uniform(0.3, 0.7, 2)
        size = rng.uniform(0.15, 0.4)
        yy, xx = np.mgrid[0:INPUT_SIZE, 0:INPUT_SIZE] / INPUT_SIZE
        ellipse = ((xx - cx) ** 2 + (yy - cy) ** 2) < (size / 2) ** 2
        images[i][ellipse] = (0.56, 0.14, -0.12)  # skin-ish in [-1,1]
        dist = np.abs(anchors[:, 0] - cx) + np.abs(anchors[:, 1] - cy)
        pos = np.argsort(dist)[:8]
        target_probs[i, pos] = 1.0
        mask[i, pos] = 1.0
        target_boxes[i, pos, 0] = (cx - anchors[pos, 0]) / (0.1 * anchors[pos, 2])
        target_boxes[i, pos, 1] = (cy - anchors[pos, 1]) / (0.1 * anchors[pos, 3])
        target_boxes[i, pos, 2] = np.log(size / anchors[pos, 2]) / 0.2
        target_boxes[i, pos, 3] = np.log(size / anchors[pos, 3]) / 0.2
    return images, target_probs, target_boxes, mask


# ---------------------------------------------------------------------------
# checkpointing (orbax) + synthetic pre-training
# ---------------------------------------------------------------------------


def save_checkpoint(params, path: str) -> None:
    """Persist params with orbax (async-capable on real pods; used
    synchronously here)."""
    import os

    import orbax.checkpoint as ocp

    with ocp.StandardCheckpointer() as ckptr:
        ckptr.save(os.path.abspath(path), params, force=True)


def load_checkpoint(path: str):
    """Restore params saved by save_checkpoint, against the tree of
    ``init_params`` (its shapes alone: nothing is initialised or
    compiled), so a checkpoint of another architecture fails here and not
    at the first forward."""
    import os

    import orbax.checkpoint as ocp

    device = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    target = jax.tree_util.tree_map(
        lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=device),
        jax.eval_shape(init_params, jax.random.PRNGKey(0)),
    )
    with ocp.StandardCheckpointer() as ckptr:
        return ckptr.restore(os.path.abspath(path), target)


def train_synthetic(
    steps: int = 200,
    batch: int = 16,
    seed: int = 0,
    log_every: int = 0,
):
    """Train from scratch on the synthetic ellipse-face task — enough for
    detect_faces to localize high-contrast blobs. Real deployments restore a
    checkpoint trained on face data instead; the training loop is identical
    (swap synthetic_batch for a real loader)."""
    rng = np.random.default_rng(seed)
    params = init_params(jax.random.PRNGKey(seed))
    optimizer, train_step = make_train_step()
    opt_state = optimizer.init(params)
    # accepted uncached jit (flylint baseline): ONE jitted step per
    # training run (offline tooling, not the serving path) — the compile
    # amortizes over every step of the loop below
    step_fn = jax.jit(train_step, donate_argnums=(0, 1))
    loss = float("nan")  # steps=0: params back unchanged, loss undefined
    for step in range(steps):
        images, probs, boxes, mask = synthetic_batch(rng, batch)
        params, opt_state, loss = step_fn(
            params, opt_state,
            jnp.asarray(images), jnp.asarray(probs),
            jnp.asarray(boxes), jnp.asarray(mask),
        )
        if log_every and step % log_every == 0:
            print(f"step {step}: loss {float(loss):.4f}")
    return params, float(loss)
