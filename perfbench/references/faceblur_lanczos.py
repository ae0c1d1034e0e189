"""The plain reference of crop-fill followed by ``fb_1`` with the BlazeFace
detector: the same semantics as the program's, written down independently.
It imports nothing of the program; of the program's files it reads one, the
packaged checkpoint's arrays (``flyimg_tpu/models/weights/blazeface``, through
orbax, against a tree of shapes made here). The decode, the Lanczos resize
and the block comparison are the shared ones of ``harness/plain.py``; the
detector and the pixelation are below, in numpy float32.

The semantics are those of the flyimg URL options (docs/url-options.md):

``w_,h_,c_1``  ``-thumbnail WxH^ -gravity Center -extent WxH`` (crop-fill).
``fb_1``       operates on that rendition: every detected face region is
               pixelated. Upstream: facedetect's boxes, then ``-region WxH+X+Y
               -scale 10% -scale 1000%`` in each (FaceDetectProcessor.php
               blurFaces, lines 51-76).

**The detector, the plain way** (the north star's BlazeFace, as
``models/blazeface.py`` describes it: from the paper's shape). Six views of
the uint8 rendition (the full frame; a 2x zoom-out on mid-grey; four corner
tiles of 0.6 of each side where the short side is 256 px or more), each
resized to 128x128 with Pillow's BILINEAR and scaled to [-1, 1]. The network:
a 5x5 stride-2 convolution 3 -> 24 with bias and ReLU; sixteen BlazeBlocks
(depthwise 5x5 without bias, pointwise 1x1 with bias, the input added back,
max-pooled 2x2 where the block strides and zero-padded in its channels where
it widens, ReLU), eleven to the 16x16x88 map and five more to the 8x8x96
map; four 1x1 heads, 2 anchors a cell on the 16x16 map and 6 on the 8x8 map,
896 anchors; sigmoid; the box decode (offsets scaled 0.1 of the anchor's
size, log-sizes scaled 0.2 and clipped at +-4). Each convolution is written
out as shifted slices and one matrix product, SAME padding as XLA places it
(the extra row below and to the right). Every view's boxes are taken back to
the frame; one greedy NMS over all six views (best score first, IoU over 0.3
with a kept box drops a candidate, at most ``MAX_FACES`` boxes, score at
least ``THRESHOLD``); the kept boxes clipped to the frame and truncated to
whole pixels.

**The pixelation, the plain way.** The frame in 10x10 blocks aligned to the
IMAGE's corner (a partial block at the right or the bottom repeats the last
row or column to its ten); each block's mean, rounded half to even; a pixel
inside any box takes its block's value. **A departure from upstream, stated,
not changed:** upstream's ``-region`` scales each box by itself, so its blocks
align to the box's corner; the program's align to the image's
(``ops/pixelate.py``), and so do these.

The numbers an answer is judged by, each with a limit in the configuration:

``dims_gap``   |width| + |height| by which the answer's size misses.
``block_err``  largest |mean over a 32x32 block and channel| of answer minus
               the reference's render pixelated at the reference's own boxes,
               over the pixels that are settled (``regions``): ``must``,
               inside every box the reference is SURE of (score at least
               ``THRESHOLD + MARGIN``) less ``EDGE`` px, and ``free``, outside
               every box it scores at ``THRESHOLD - MARGIN`` or over plus
               ``EDGE`` px. A box's edge moves by a pixel or two with the
               detector's rounding; and where NMS dropped an anchor that
               scores within ``RIVAL`` of the one it kept, a rounding can keep
               the other (seen: 1 face of some 100, the two boxes 11 to 15 px
               apart), so such a rival's box narrows ``must`` and widens what
               is not ``free``. Blocks with under a quarter of their pixels
               settled are left out.
``face_gap``   the area of the sure boxes that were not pixelated (a box
               counts whole where more of its blocks in ``must`` read plain
               than pixelated), plus what was pixelated in ``free``, over
               the sure boxes' area. Whether a 10x10 block
               of the answer is pixelated is read where it can be: where the
               reference's pixelated and plain renders differ by
               ``EVIDENCE`` levels or more in the block's mean |difference|,
               the block is pixelated if the answer is ``CLEARLY`` nearer the
               pixelated render (in summed squares), plain if it is as much
               nearer the plain one, and says nothing between: the output
               JPEG takes a weak texture half the way to its block's mean. A box whose score is within ``MARGIN`` of the
               threshold may go either way: with trained weights too, a score
               beside the threshold turns on rounding.

``rms_err`` is returned beside them and not compared; so are ``sure_boxes``
and ``kept_boxes``, what the reference found in the original.

``render`` takes one control: ``operands`` lowers the precision of the
resample (``plain.resize``). The face pass's controls are ``detect``'s
(``operands`` of the detector's products: ``"float32"``, ``"bfloat16"``,
``"float8_e4m3fn"``; ``head8=False`` leaves the 8x8 map's anchors out) and
what ``control_faces.py`` does with the boxes.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np
from PIL import Image

from perfbench.harness import plain, work as work_mod

NUMBERS = ("dims_gap", "block_err", "face_gap")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CHECKPOINT = os.path.join(ROOT, "flyimg_tpu", "models", "weights", "blazeface")

# the detector, as the program serves it (models/faces.py BlazeFaceBackend)
INPUT = 128
THRESHOLD = 0.8
MAX_FACES = 16
NMS_IOU = 0.3
TILE_SHARE = 0.6
TILES_FROM_SIDE = 256
STEM = 24
# (features, stride) of the sixteen BlazeBlocks
BLOCKS = ((24, 1), (28, 1), (32, 2), (36, 1), (42, 1), (48, 2), (56, 1), (64, 1), (72, 1),
          (80, 1), (88, 1), (96, 2), (96, 1), (96, 1), (96, 1), (96, 1))
BLOCKS_TO_16 = 11
# (cells a side, anchors a cell, the first anchor's side as a share of the view)
MAPS = ((16, 2, 0.10), (8, 6, 0.30))
ANCHORS = sum(g * g * n for g, n, _ in MAPS)   # 896

# the pixelation
BLOCK = 10

# the judge
MARGIN = 0.05     # a score this near the threshold may fall either side of it (bfloat16 moves one by 0.01)
RIVAL = 0.02      # an anchor dropped by NMS for one scoring this little more could have won (twice what bfloat16 moves)
EDGE = 5          # px either side of a box's edge that are not judged: a band one block wide
EVIDENCE = 3.0    # levels a block's two renders must differ by to say which the answer is
CLEARLY = 2.0     # times nearer (in summed squares) the answer must be to one render than to the other


# -- the options ----------------------------------------------------------------

def parse(config: Dict[str, Any]) -> Dict[str, Any]:
    """The reference's own reading of the options string: ``w_<n>``, ``h_<n>``,
    ``c_1`` and ``fb_1``, each once; any other option is an error here, since
    the reference would not be rendering it."""
    url = config["options"]["url"]
    parts = url.split(",")
    out: Dict[str, Any] = {}
    for part in parts:
        key, _, value = part.partition("_")
        if parts.count(part) > 1:
            raise ValueError(f"the reference renders option {part!r} once, not twice in {url!r}")
        if key == "w":
            out["width"] = int(value)
        elif key == "h":
            out["height"] = int(value)
        elif part not in ("c_1", "fb_1"):
            raise ValueError(f"the reference does not render option {part!r}")
    if not {"c_1", "fb_1"} <= set(parts) or set(out) != {"width", "height"}:
        raise ValueError(f"the reference renders w_,h_,c_1,fb_1 together, not {url!r}")
    return out


def geometry(options: Dict[str, Any], src_w: int, src_h: int) -> Dict[str, Any]:
    """Crop-fill: the size the whole frame is resized to, and the window of
    that which is kept."""
    tw, th = int(options["width"]), int(options["height"])
    scale = max(tw / src_w, th / src_h)
    rw = max(plain.round_half_up(src_w * scale), 1)
    rh = max(plain.round_half_up(src_h * scale), 1)
    x0, y0 = max((rw - tw) // 2, 0), max((rh - th) // 2, 0)
    return {"resize": (rw, rh), "rows": (y0, min(y0 + th, rh)), "cols": (x0, min(x0 + tw, rw))}


def render_fill(data: bytes, options: Dict[str, Any], operands: str = "float32") -> np.ndarray:
    """Encoded original -> the resized, cut frame as float32, unrounded."""
    rgb = plain.decode(data)
    geo = geometry(options, rgb.shape[1], rgb.shape[0])
    return plain.resize(rgb, geo["resize"][0], geo["resize"][1], geo["rows"], geo["cols"], operands)


# -- the detector's weights -------------------------------------------------------

def weight_shapes() -> Dict[str, Any]:
    """The tree of the checkpoint, as the architecture above gives it: the
    names are those flax gives the modules in the order they are made."""
    def conv(kh, cin, cout, bias=True):
        leaf = {"kernel": (kh, kh, cin, cout)}
        if bias:
            leaf["bias"] = (cout,)
        return leaf

    tree: Dict[str, Any] = {"Conv_0": conv(5, 3, STEM)}
    width = STEM
    for i, (features, _) in enumerate(BLOCKS):
        tree[f"BlazeBlock_{i}"] = {"Conv_0": conv(5, 1, width, bias=False), "Conv_1": conv(1, width, features)}
        width = features
    w16, w8 = BLOCKS[BLOCKS_TO_16 - 1][0], BLOCKS[-1][0]
    tree.update({"Conv_1": conv(1, w16, MAPS[0][1]), "Conv_2": conv(1, w16, MAPS[0][1] * 4),
                 "Conv_3": conv(1, w8, MAPS[1][1]), "Conv_4": conv(1, w8, MAPS[1][1] * 4)})
    return {"params": tree}


_WEIGHTS: Dict[str, Any] = {}
_WEIGHTS_LOCK = threading.Lock()


def _tree_map(fn, tree):
    return {k: _tree_map(fn, v) if isinstance(v, dict) else fn(v) for k, v in tree.items()}


def packaged_weights(path: str = CHECKPOINT) -> Dict[str, Any]:
    """The packaged checkpoint's arrays as numpy float32, read once."""
    with _WEIGHTS_LOCK:
        if path not in _WEIGHTS:
            import jax
            import orbax.checkpoint as ocp

            # onto the host: the reference keeps off the accelerator
            device = jax.sharding.SingleDeviceSharding(jax.devices("cpu")[0])
            target = _tree_map(lambda shape: jax.ShapeDtypeStruct(shape, np.float32, sharding=device),
                               weight_shapes())
            with ocp.StandardCheckpointer() as ckptr:
                restored = ckptr.restore(os.path.abspath(path), target)
            _WEIGHTS[path] = _tree_map(lambda a: np.asarray(a, dtype=np.float32), restored)
        return _WEIGHTS[path]


# -- the network ------------------------------------------------------------------

def _same(x: np.ndarray, kernel: int, stride: int) -> Tuple[np.ndarray, int]:
    """``x`` [b, h, w, c] zero-padded as XLA's SAME does for a square kernel
    and stride, and the output's side."""
    side = x.shape[1]
    out = -(-side // stride)
    total = max((out - 1) * stride + kernel - side, 0)
    lo = total // 2
    return np.pad(x, ((0, 0), (lo, total - lo), (lo, total - lo), (0, 0))), out


def _dense(x: np.ndarray, kernel: np.ndarray, bias: Optional[np.ndarray], q) -> np.ndarray:
    """A 1x1 convolution, or any product over the last axis."""
    cin, cout = kernel.shape[-2], kernel.shape[-1]
    y = q(x, False).reshape(-1, cin) @ q(kernel.reshape(cin, cout), True)
    if bias is not None:
        y = y + bias
    return y.reshape(x.shape[:-1] + (cout,)).astype(np.float32)


def _conv5(x: np.ndarray, kernel: np.ndarray, bias: np.ndarray, stride: int, q) -> np.ndarray:
    """The stem: a full 5x5 convolution as one product over 75 taps."""
    padded, out = _same(x, 5, stride)
    span = stride * (out - 1) + 1
    taps = [padded[:, dy:dy + span:stride, dx:dx + span:stride, :] for dy in range(5) for dx in range(5)]
    patches = np.concatenate(taps, axis=-1)                       # (dy, dx, c), as the kernel reshapes
    return _dense(patches, kernel.reshape(1, 1, -1, kernel.shape[-1]), bias, q)


def _depthwise5(x: np.ndarray, kernel: np.ndarray, stride: int, q) -> np.ndarray:
    """Depthwise 5x5, no bias: 25 shifted slices, each times its tap."""
    padded, out = _same(q(x, False), 5, stride)
    span = stride * (out - 1) + 1
    taps = q(kernel, True)
    y = np.zeros((x.shape[0], out, out, x.shape[-1]), np.float32)
    for dy in range(5):
        for dx in range(5):
            y += padded[:, dy:dy + span:stride, dx:dx + span:stride, :] * taps[dy, dx, 0]
    return y


def _blaze_block(x: np.ndarray, weights: Dict[str, Any], features: int, stride: int, q) -> np.ndarray:
    y = _depthwise5(x, weights["Conv_0"]["kernel"], stride, q)
    y = _dense(y, weights["Conv_1"]["kernel"], weights["Conv_1"]["bias"], q)
    if stride == 2:
        b, h, w, c = x.shape
        x = x.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))
    if x.shape[-1] != features:
        x = np.pad(x, ((0, 0), (0, 0), (0, 0), (0, features - x.shape[-1])))
    return np.maximum(y + x, 0.0)


def anchors() -> np.ndarray:
    """[896, 4] as (cx, cy, w, h) of the view: a uniform grid a map, cells in
    rows then columns, the anchors of a cell growing to 1.5 times the first."""
    rows = []
    for cells, count, first in MAPS:
        for gy in range(cells):
            for gx in range(cells):
                for k in range(count):
                    side = first * (1.0 + 0.5 * k / max(count - 1, 1))
                    rows.append(((gx + 0.5) / cells, (gy + 0.5) / cells, side, side))
    return np.asarray(rows, dtype=np.float32)


def forward(weights: Dict[str, Any], inputs: np.ndarray, operands: str = "float32",
            head8: bool = True) -> Tuple[np.ndarray, np.ndarray]:
    """``[b, 128, 128, 3]`` float32 in [-1, 1] -> probabilities ``[b, 896]``
    and decoded boxes ``[b, 896, 4]`` as (cx, cy, w, h) of the view, before
    any threshold. ``operands`` rounds both sides of every product to that
    type first; sums stay float32."""
    q = plain._quantiser(operands)
    p = weights["params"]
    x = np.maximum(_conv5(np.asarray(inputs, np.float32), p["Conv_0"]["kernel"], p["Conv_0"]["bias"], 2, q), 0.0)
    x16 = None
    for i, (features, stride) in enumerate(BLOCKS):
        x = _blaze_block(x, p[f"BlazeBlock_{i}"], features, stride, q)
        if i == BLOCKS_TO_16 - 1:
            x16 = x
    b = x.shape[0]
    heads = [_dense(m, p[name]["kernel"], p[name]["bias"], q)
             for m, name in ((x16, "Conv_1"), (x16, "Conv_2"), (x, "Conv_3"), (x, "Conv_4"))]
    logits = np.concatenate([heads[0].reshape(b, -1), heads[2].reshape(b, -1)], axis=1)
    raw = np.concatenate([heads[1].reshape(b, -1, 4), heads[3].reshape(b, -1, 4)], axis=1)
    probs = (1.0 / (1.0 + np.exp(-logits.astype(np.float64)))).astype(np.float32)
    if not head8:
        probs[:, MAPS[0][0] ** 2 * MAPS[0][1]:] = 0.0
    a = anchors()
    boxes = np.stack([
        a[:, 0] + raw[..., 0] * 0.1 * a[:, 2],
        a[:, 1] + raw[..., 1] * 0.1 * a[:, 3],
        a[:, 2] * np.exp(np.clip(raw[..., 2] * 0.2, -4.0, 4.0)),
        a[:, 3] * np.exp(np.clip(raw[..., 3] * 0.2, -4.0, 4.0)),
    ], axis=-1).astype(np.float32)
    return probs, boxes


# -- the views ----------------------------------------------------------------------

def views(width: int, height: int) -> List[Tuple[int, int, int, int]]:
    """(x, y, w, h) of every region the network looks at; a region may reach
    beyond the frame (mid-grey there)."""
    out = [(0, 0, width, height), (-((width + 1) // 2), -((height + 1) // 2), 2 * width, 2 * height)]
    if min(width, height) >= TILES_FROM_SIDE:
        tw, th = int(width * TILE_SHARE), int(height * TILE_SHARE)
        out += [(ox, oy, tw, th) for ox in (0, width - tw) for oy in (0, height - th)]
    return out


def view_input(rgb: np.ndarray, view: Tuple[int, int, int, int]) -> np.ndarray:
    """One view as the network's input: the part of the frame under it
    resized straight to its place in a 128x128 mid-grey canvas."""
    x, y, vw, vh = view
    h, w = rgb.shape[:2]
    canvas = np.full((INPUT, INPUT, 3), 128, np.uint8)
    sx0, sy0, sx1, sy1 = max(x, 0), max(y, 0), min(x + vw, w), min(y + vh, h)
    if sx1 > sx0 and sy1 > sy0:
        dx0, dx1 = round((sx0 - x) * INPUT / vw), round((sx1 - x) * INPUT / vw)
        dy0, dy1 = round((sy0 - y) * INPUT / vh), round((sy1 - y) * INPUT / vh)
        if dx1 > dx0 and dy1 > dy0:
            part = Image.fromarray(np.ascontiguousarray(rgb[sy0:sy1, sx0:sx1]))
            canvas[dy0:dy1, dx0:dx1] = np.asarray(part.resize((dx1 - dx0, dy1 - dy0), Image.BILINEAR))
    return canvas.astype(np.float32) / 127.5 - 1.0


def network_inputs(rgb: np.ndarray) -> Tuple[np.ndarray, List[Tuple[int, int, int, int]]]:
    regions = views(rgb.shape[1], rgb.shape[0])
    return np.stack([view_input(rgb, v) for v in regions]), regions


# -- candidates, NMS ----------------------------------------------------------------

def _iou(a: Sequence[float], b: Sequence[float]) -> float:
    ix = max(0.0, min(a[2], b[2]) - max(a[0], b[0]))
    iy = max(0.0, min(a[3], b[3]) - max(a[1], b[1]))
    union = (a[2] - a[0]) * (a[3] - a[1]) + (b[2] - b[0]) * (b[3] - b[1]) - ix * iy
    return ix * iy / union if union > 0 else 0.0


def pixel_box(corners: Sequence[float], width: int, height: int) -> Optional[Tuple[int, int, int, int]]:
    """Frame-normalised corners -> (x0, y0, x1, y1) in whole pixels, clipped;
    nothing where that is empty."""
    x0, y0 = int(max(corners[0], 0.0) * width), int(max(corners[1], 0.0) * height)
    x1, y1 = int(min(corners[2], 1.0) * width), int(min(corners[3], 1.0) * height)
    return (x0, y0, x1, y1) if x1 > x0 and y1 > y0 else None


def detect(rgb: np.ndarray, weights: Optional[Dict[str, Any]] = None, operands: str = "float32",
           head8: bool = True, floor: float = THRESHOLD) -> List[Dict[str, Any]]:
    """uint8 rendition -> the boxes a greedy NMS over all views keeps among
    the anchors scoring ``floor`` or over, best first: ``{"score", "box":
    (x0, y0, x1, y1) in pixels or None, "rivals"}``, at most ``MAX_FACES``.
    With ``floor`` under the threshold the list says too what all but made
    it; ``rivals`` are the boxes of the anchors dropped for this one that
    score within ``RIVAL`` of it."""
    height, width = rgb.shape[:2]
    inputs, regions = network_inputs(rgb)
    probs, boxes = forward(weights or packaged_weights(), inputs, operands, head8)
    scores, corners = [], []
    for (x, y, vw, vh), p, b in zip(regions, probs, boxes):
        cx, cy = (x + b[:, 0] * vw) / width, (y + b[:, 1] * vh) / height
        bw, bh = b[:, 2] * vw / width, b[:, 3] * vh / height
        scores.append(p)
        corners.append(np.stack([cx - bw / 2, cy - bh / 2, cx + bw / 2, cy + bh / 2], axis=-1))
    scores, corners = np.concatenate(scores), np.concatenate(corners)
    kept: List[Dict[str, Any]] = []
    for idx in np.argsort(-scores, kind="stable")[:MAX_FACES * 4 * len(regions)]:
        if scores[idx] < floor or sum(k["box"] is not None for k in kept) >= MAX_FACES:
            break
        over = next((k for k in kept if _iou(corners[idx], k["corners"]) > NMS_IOU), None)
        if over is not None:
            # dropped for a kept box; one that scores all but the same could
            # have been kept in its place had a product rounded otherwise
            box = pixel_box(corners[idx], width, height)
            if box and scores[idx] >= over["score"] - RIVAL:
                over["rivals"].append(box)
            continue
        kept.append({"score": float(scores[idx]), "corners": corners[idx],
                     "box": pixel_box(corners[idx], width, height), "rivals": []})
    return kept


# -- the pixelation -----------------------------------------------------------------

def pixelated(frame_u8: np.ndarray) -> np.ndarray:
    """The whole uint8 frame in 10x10 image-aligned blocks, each its mean
    rounded half to even; a partial block repeats the frame's last row or
    column."""
    h, w = frame_u8.shape[:2]
    padded = np.pad(frame_u8, ((0, -h % BLOCK), (0, -w % BLOCK), (0, 0)), mode="edge").astype(np.float64)
    hb, wb = padded.shape[0] // BLOCK, padded.shape[1] // BLOCK
    means = np.rint(padded.reshape(hb, BLOCK, wb, BLOCK, 3).mean(axis=(1, 3)))
    return np.repeat(np.repeat(means, BLOCK, axis=0), BLOCK, axis=1)[:h, :w].astype(np.uint8)


def box_mask(shape_hw: Tuple[int, int], boxes: Sequence[Tuple[int, int, int, int]], grow: int = 0) -> np.ndarray:
    """True inside any of ``boxes`` (x0, y0, x1, y1), each grown by ``grow``
    px a side (shrunk where it is negative)."""
    mask = np.zeros(shape_hw, bool)
    for x0, y0, x1, y1 in boxes:
        x0, y0, x1, y1 = x0 - grow, y0 - grow, x1 + grow, y1 + grow
        if x1 > x0 and y1 > y0:
            mask[max(y0, 0):max(y1, 0), max(x0, 0):max(x1, 0)] = True
    return mask


def pixelate(frame_u8: np.ndarray, boxes: Sequence[Tuple[int, int, int, int]]) -> np.ndarray:
    """``fb_1`` on a uint8 frame at ``boxes`` (x0, y0, x1, y1)."""
    if not boxes:
        return frame_u8
    inside = box_mask(frame_u8.shape[:2], boxes)
    return np.where(inside[..., None], pixelated(frame_u8), frame_u8)


# -- the interface ------------------------------------------------------------------

def render(data: bytes, options: Dict[str, Any], operands: str = "float32") -> np.ndarray:
    """Encoded original -> the crop-filled, face-blurred frame as float32
    ``[h, w, 3]``. ``operands`` is the resample's control."""
    frame = render_fill(data, options, operands)
    u8 = plain.to_u8(frame)
    boxes = [k["box"] for k in detect(u8) if k["box"]]
    if not boxes:
        return frame
    return np.where(box_mask(u8.shape[:2], boxes)[..., None], pixelated(u8).astype(np.float32), frame)


def _blocks(values: np.ndarray, side: int) -> np.ndarray:
    """Sums over ``side x side`` blocks of a 2-D (or [h, w, c]) array, the
    ragged right and bottom left out."""
    h, w = (values.shape[0] // side) * side, (values.shape[1] // side) * side
    v = values[:h, :w]
    return v.reshape((h // side, side, w // side, side) + v.shape[2:]).sum(axis=(1, 3))


def core_of(k: Dict[str, Any]) -> Tuple[int, int, int, int]:
    """What a kept box and every rival of it share: pixelated whichever of
    them NMS keeps."""
    boxes = [k["box"]] + list(k["rivals"])
    return (max(b[0] for b in boxes), max(b[1] for b in boxes),
            min(b[2] for b in boxes), min(b[3] for b in boxes))


def regions(shape_hw: Tuple[int, int], found: List[Dict[str, Any]]) -> Tuple[np.ndarray, np.ndarray]:
    """What the reference's detections settle, as two masks. ``must``: inside
    every box the reference is sure of, and inside every rival of it, less
    ``EDGE``: pixelated whichever of them NMS keeps. ``free``: outside every
    box the reference keeps or all but keeps, and every rival of one, plus
    ``EDGE``: never pixelated. Between the two an answer may go either way."""
    must, maybe = np.zeros(shape_hw, bool), np.zeros(shape_hw, bool)
    for k in found:
        if not k["box"]:
            continue
        maybe |= box_mask(shape_hw, [k["box"]] + list(k["rivals"]), EDGE)
        if k["score"] >= THRESHOLD + MARGIN:
            must |= box_mask(shape_hw, [core_of(k)], -EDGE)
    return must, ~maybe


def judge_answer(answer: np.ndarray, frame: np.ndarray, found: List[Dict[str, Any]]) -> Dict[str, float]:
    """One decoded answer against the reference's rendition ``frame``
    (float32) and what the reference's detector ``found`` in it at
    ``THRESHOLD - MARGIN`` and over."""
    verdict = plain.against_frame(answer, frame)
    if verdict["dims_gap"]:
        return {"dims_gap": verdict["dims_gap"]}
    u8 = plain.to_u8(frame)
    must, free = regions(u8.shape[:2], found)
    blurred = pixelated(u8).astype(np.float32)
    a = answer.astype(np.float32)
    # against the reference's own answer where it is settled: pixelated in
    # ``must``, plain in ``free``
    settled = must | free
    diff = (a - np.where(must[..., None], blurred, frame)) * settled[..., None]
    count = _blocks(settled.astype(np.float32), plain.BLOCK)
    means = np.abs(_blocks(diff, plain.BLOCK)) / np.maximum(count, 1.0)[..., None]
    judged = count >= 0.25 * plain.BLOCK * plain.BLOCK
    block_err = float(means[judged].max()) if judged.any() else 0.0
    rms = float(np.sqrt(np.mean(np.square(diff[settled])))) if settled.any() else 0.0

    # which 10x10 blocks of the answer are pixelated, where the renders tell
    evidence = _blocks(np.abs(blurred - frame).mean(axis=-1), BLOCK) / BLOCK ** 2 >= EVIDENCE
    to_blurred = _blocks(np.square(a - blurred).sum(axis=-1), BLOCK)
    to_plain = _blocks(np.square(a - frame).sum(axis=-1), BLOCK)
    is_blurred, is_plain = CLEARLY * to_blurred < to_plain, CLEARLY * to_plain < to_blurred

    def whole_blocks(mask: np.ndarray) -> np.ndarray:
        return _blocks(mask.astype(np.float32), BLOCK) == BLOCK * BLOCK

    # a sure face was missed where more of its blocks that tell are plain
    # than pixelated: its whole box counts
    sure = [k for k in found if k["box"] and k["score"] >= THRESHOLD + MARGIN]
    kept = [k for k in found if k["box"] and k["score"] >= THRESHOLD]
    missed = 0.0
    for k in sure:
        inside = whole_blocks(box_mask(u8.shape[:2], [core_of(k)], -EDGE)) & evidence
        if (inside & is_plain).sum() > (inside & is_blurred).sum():
            missed += (k["box"][2] - k["box"][0]) * (k["box"][3] - k["box"][1])
    extra = float((whole_blocks(free) & evidence & is_blurred).sum()) * BLOCK * BLOCK
    area = float(sum((k["box"][2] - k["box"][0]) * (k["box"][3] - k["box"][1]) for k in sure))
    gap = (missed + extra) / area if area > 0 else (1.0 if extra else 0.0)
    return {"dims_gap": 0.0, "block_err": block_err, "rms_err": rms, "face_gap": gap,
            "sure_boxes": float(len(sure)), "kept_boxes": float(len(kept))}


def judge_original(data: bytes, answers: List[np.ndarray], options: Dict[str, Any]) -> List[Dict[str, float]]:
    """The numbers of every distinct answer (decoded, uint8) to one original,
    against one render of it and one pass of the detector over that."""
    frame = render_fill(data, options)
    found = detect(plain.to_u8(frame), floor=THRESHOLD - MARGIN)
    return [judge_answer(answer, frame, found) for answer in answers]


# -- needed work --------------------------------------------------------------------

def forward_work() -> Dict[str, float]:
    """FLOPs and bytes of one view's forward pass, whatever implements it:
    two a multiply-add; the input read once as float32, every weight once,
    the scores and boxes written."""
    side = INPUT // 2
    flops = 2.0 * side * side * 75 * STEM
    weights = 75 * STEM + STEM
    width = STEM
    for i, (features, stride) in enumerate(BLOCKS):
        side //= stride
        flops += 2.0 * side * side * width * (25 + features)
        weights += 25 * width + width * features + features
        width = features
        if i in (BLOCKS_TO_16 - 1, len(BLOCKS) - 1):
            per_cell = 5 * MAPS[0 if i < len(BLOCKS) - 1 else 1][1]
            flops += 2.0 * side * side * width * per_cell
            weights += width * per_cell + per_cell
    return {"flops": flops, "bytes": 4.0 * (INPUT * INPUT * 3 + weights + ANCHORS * 5)}


def work(config: Dict[str, Any]) -> Dict[str, Dict[str, float]]:
    """Needed work by kernel. ``resample``: per image, the window of the
    source that the kept box comes from. ``blazeface_forward``: per VIEW
    (an image has six). ``face_pixelate``: per image, the rendition read and
    written once as uint8, an add a sample for the block sums and a select."""
    frame = config["frame"]
    geo = geometry(parse(config), frame["width"], frame["height"])
    rw, rh = geo["resize"]
    out_w, out_h = geo["cols"][1] - geo["cols"][0], geo["rows"][1] - geo["rows"][0]
    samples = 3.0 * out_w * out_h
    return {"resample": work_mod.resize_work(frame["width"], frame["height"], frame["width"] * out_w / rw,
                                             frame["height"] * out_h / rh, out_w, out_h),
            "blazeface_forward": forward_work(),
            "face_pixelate": {"flops": 2.0 * samples, "bytes": 2.0 * samples}}
