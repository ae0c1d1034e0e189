"""Face detection + face ops (blur / crop).

The reference shells out to wavexx/facedetect (OpenCV Haar cascades) which
prints one "x y w h" line per face (reference
src/Core/Processor/FaceDetectProcessor.php:22-76). This framework keeps the
same list-of-boxes contract with two interchangeable backends:

- ``facefind`` (this module, default): a classical skin-region proposer —
  skin-probability map (same normalized-rgb skin distance family as the
  smart-crop scorer) computed on device, morphological cleanup via max/min
  pooling, connected components + box extraction on host (scipy). No
  weights needed, fully deterministic.
- ``blazeface`` (models/blazeface.py): a BlazeFace-style convnet (the north
  star per BASELINE.json) usable once a trained checkpoint is supplied;
  same detect_faces() signature.

Face blur reproduces the reference's pixelation (down/up-scale 10% region
round trip, FaceDetectProcessor.php:51-76) via ops/pixelate.py in one
jitted, batched program (blocks aligned to the image, not to each
region); face crop slices the Nth detected box (``fcp``,
FaceDetectProcessor.php:22-42).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from flyimg_tpu.ops.pixelate import pixelate_image

Box = Tuple[int, int, int, int]  # x, y, w, h

MIN_FACE_FRACTION = 0.001  # reject blobs below 0.1% of image area
MAX_FACES = 32


@jax.jit
def _skin_probability(rgb: jnp.ndarray) -> jnp.ndarray:
    """[h, w, 3] uint8 -> [h, w] float32 skin likelihood in [0, 1].

    Normalized-rgb chromaticity ellipse + simple RGB rules — the standard
    classical skin segmentation recipe; no learned weights.
    """
    rgbf = rgb.astype(jnp.float32)
    r, g, b = rgbf[..., 0], rgbf[..., 1], rgbf[..., 2]
    total = r + g + b + 1e-6
    rn, gn = r / total, g / total

    # chromaticity gaussian centered on skin tones
    d2 = ((rn - 0.44) / 0.07) ** 2 + ((gn - 0.31) / 0.05) ** 2
    chroma = jnp.exp(-0.5 * d2)

    # brightness + rule-based gates (skin is not too dark, r > b, r > g)
    gates = (
        (r > 60.0) & (r > b) & (r > g * 0.9) & (jnp.abs(r - g) > 10.0)
    ).astype(jnp.float32)
    return chroma * gates


@jax.jit
def _morph_clean(mask: jnp.ndarray) -> jnp.ndarray:
    """Binary open+close via max/min pooling (device-friendly morphology)."""

    def pool(m, op, k=5):
        init = -jnp.inf if op is jax.lax.max else jnp.inf
        return jax.lax.reduce_window(
            m, init, op, (k, k), (1, 1), "SAME"
        )

    # erosion = -maxpool(-m); opening then closing with 5x5 windows
    m = mask.astype(jnp.float32)
    m = -pool(-m, jax.lax.max)          # erode
    m = pool(m, jax.lax.max)            # dilate (open complete)
    m = pool(m, jax.lax.max)            # dilate
    m = -pool(-m, jax.lax.max)          # erode (close complete)
    return m > 0.5


def _boxes_from_mask(mask: np.ndarray) -> List[Box]:
    """Connected components -> face boxes, sorted left-to-right then
    top-to-bottom (matching facedetect's reading-order output, so ``fcp``
    indices behave comparably)."""
    from scipy import ndimage

    labels, count = ndimage.label(mask)
    if count == 0:
        return []
    h, w = mask.shape
    min_area = max(int(h * w * MIN_FACE_FRACTION), 16)
    boxes: List[Box] = []
    for sl in ndimage.find_objects(labels):
        if sl is None:
            continue
        bh = sl[0].stop - sl[0].start
        bw = sl[1].stop - sl[1].start
        if bh * bw < min_area:
            continue
        # faces are roughly square-ish; reject extreme aspect blobs
        aspect = bw / max(bh, 1)
        if aspect < 0.25 or aspect > 4.0:
            continue
        boxes.append((sl[1].start, sl[0].start, bw, bh))
    boxes.sort(key=lambda b: (b[1], b[0]))
    return boxes[:MAX_FACES]


def detect_faces(rgb: np.ndarray, threshold: float = 0.35) -> List[Box]:
    """Detect face-like skin regions in one image. The batched serving
    path is ``prepare_face_work`` + ``detect_faces_batched``."""
    prob = np.asarray(_skin_probability(jnp.asarray(rgb)))
    mask = np.asarray(_morph_clean(jnp.asarray(prob > threshold)))
    return _boxes_from_mask(mask)


# ---------------------------------------------------------------------------
# batched serving path: detection for many images in one device launch per
# shape bucket (per-image jits would recompile for every post-resize size)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class FaceWork:
    image: np.ndarray                # [h, w, 3] uint8
    threshold: float
    bucket: Tuple[int, int]          # padded (h, w) compile bucket


def prepare_face_work(rgb: np.ndarray, threshold: float = 0.35) -> FaceWork:
    from flyimg_tpu.ops.compose import _bucket_dim

    h, w = rgb.shape[:2]
    return FaceWork(
        image=np.ascontiguousarray(rgb),
        threshold=threshold,
        bucket=(_bucket_dim(h, 32), _bucket_dim(w, 32)),
    )


@jax.jit
def _batched_face_masks(
    images: jnp.ndarray, in_true: jnp.ndarray, thresholds: jnp.ndarray
) -> jnp.ndarray:
    """[B, bh, bw, 3] uint8 + valid dims + thresholds -> [B, bh, bw] bool
    cleaned masks. Morphology windows are clipped to each member's valid
    region (padding forced to the pooling identity), which is exactly the
    'SAME' border behavior of the unbatched path on an unpadded image."""

    def pool_max(m, k=5):
        return jax.lax.reduce_window(
            m, -jnp.inf, jax.lax.max, (k, k), (1, 1), "SAME"
        )

    def one(img, true_hw, threshold):
        prob = _skin_probability(img)
        h, w = prob.shape
        valid = (jnp.arange(h)[:, None] < true_hw[0]) & (
            jnp.arange(w)[None, :] < true_hw[1]
        )
        m = jnp.where(valid, (prob > threshold).astype(jnp.float32), 0.0)

        def erode(x):
            return -pool_max(jnp.where(valid, -x, -jnp.inf))

        def dilate(x):
            return pool_max(jnp.where(valid, x, -jnp.inf))

        m = dilate(dilate(erode(m)))  # open (erode+dilate), then dilate
        m = erode(m)                  # close complete
        return (m > 0.5) & valid

    with jax.named_scope("flyimg.face_masks"):
        return jax.vmap(one)(images, in_true, thresholds)


def detect_faces_batched(items: List[FaceWork], stats=None) -> List[List[Box]]:
    """Face boxes for many images: one jitted mask program per shape
    bucket, host component extraction per member. Equivalent to per-image
    detect_faces (pinned by tests/test_handler.py). ``stats`` is the
    runner's contract (models/faces.py); this detector has nothing to say
    there."""
    del stats
    from collections import defaultdict

    from flyimg_tpu.ops.compose import bucket_batch

    results: List[List[Box]] = [None] * len(items)  # type: ignore
    by_bucket = defaultdict(list)
    for i, item in enumerate(items):
        by_bucket[item.bucket].append(i)
    for bucket, idxs in by_bucket.items():
        bh, bw = bucket
        n = len(idxs)
        nb = bucket_batch(n)  # power-of-two occupancy ladder
        images = np.zeros((nb, bh, bw, 3), np.uint8)
        in_true = np.zeros((nb, 2), np.float32)
        thresholds = np.zeros((nb,), np.float32)
        for j, i in enumerate(idxs):
            h, w = items[i].image.shape[:2]
            images[j, :h, :w] = items[i].image
            in_true[j] = (h, w)
            thresholds[j] = items[i].threshold
        for j in range(n, nb):
            images[j] = images[n - 1]
            in_true[j] = in_true[n - 1]
            thresholds[j] = thresholds[n - 1]
        masks = np.asarray(
            _batched_face_masks(
                jnp.asarray(images), jnp.asarray(in_true),
                jnp.asarray(thresholds),
            )
        )
        for j, i in enumerate(idxs):
            h, w = items[i].image.shape[:2]
            results[i] = _boxes_from_mask(masks[j, :h, :w])
    return results


def blur_faces(rgb: np.ndarray, boxes: List[Box]) -> np.ndarray:
    """Pixelate every face region (reference blurFaces,
    FaceDetectProcessor.php:51-76): one image through the batched
    ``uint8`` program of ops/pixelate.py. The handler sends its images
    there through the device controller instead (``submit_aux``); this is
    the path without one, and the wedged-executor fallback."""
    return pixelate_image(rgb, boxes)


def crop_face(rgb: np.ndarray, boxes: List[Box], position: int = 0) -> np.ndarray:
    """Crop the Nth face (reference cropFaces, FaceDetectProcessor.php:22-42;
    silently returns the image unchanged when no face matches, mirroring the
    reference's no-op on missing binary/face)."""
    if not boxes:
        return rgb
    position = min(max(position, 0), len(boxes) - 1)
    x, y, w, h = boxes[position]
    return rgb[y : y + h, x : x + w]
