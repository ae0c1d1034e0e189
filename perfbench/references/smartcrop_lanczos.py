"""The plain reference of a fit resize followed by ``smc_1``: the same
semantics as the program's, written down independently. It imports nothing
of the program and takes nothing the program has made; the decode, the
Lanczos resize and the block comparison are the shared ones of
``harness/plain.py``, the scorer is below.

The semantics are those of the flyimg URL options (docs/url-options.md):

``w_,h_``   without ``c_1``: ``-thumbnail WxH``, the frame scaled to fit inside
            the box, each axis rounded to the nearest pixel; nothing is cut.
``smc_1``   operates on that rendition: upstream ``python/smartcrop.py`` (a
            port of smartcrop.js) called for a 100x100 target, then
            ``SmartCropProcessor.php``'s ``convert -crop``.

**The scorer, the plain way.** The rendition is prescaled with Pillow's
LANCZOS so that the smallest candidate window is 100 px (here to about 111 px
on the short side); three feature maps are made with Pillow and numpy as
upstream makes them and quantised to uint8 as its ``Image.fromarray`` round
trip does (luma by ``convert('L', matrix)``, detail by the 3x3 Laplacian
``ImageFilter.Kernel`` with offset 1, skin by the distance to a skin colour,
saturation by upstream's formula); every candidate window (scales 1.0 and 0.9
of the largest 1:1 window, on a grid of 8 px) is scored by evaluating
upstream's importance field at EVERY pixel of the prescaled image for THAT
window (rule of thirds, edge falloff, ``outside_importance`` outside it) and
summing importance times the pixel's weight, in float64, one window after
another: no correlation, no box sums, no batching. The best window (the first
of equals, scales then rows then columns, as upstream's loop) is taken back
to the rendition's pixels and cut with the quirk of the PHP driver: the script
prints ``WxH+X+Y`` with ``W = x + width`` and ``H = y + height`` (the window's
far corner, not its size), and ImageMagick's ``-crop`` clamps that to the
frame.

**Departures from upstream, each on purpose.** (1) Upstream scores a window
with a Python loop over pixels; here the same sum is one numpy expression per
window. (2) Upstream prescales with ``Image.thumbnail``, which in today's
Pillow reduces by whole factors first and rounds the long side its own way;
the reference resizes to exactly ``(int(w p), int(h p))`` with LANCZOS, which
is what ``thumbnail`` did in the Pillow upstream was written for, and what
the program does. (3) ``convert('L', matrix)`` rounds to the nearest level in
the Pillow installed here and truncated in upstream's day; the reference
takes Pillow as it is (the program truncates: a luma level apart on half the
pixels, far below what separates two windows of this corpus).

The numbers it judges an answer by, each with a limit of its own in the
configuration's ``limits``:

``dims_gap``   |width| + |height| by which an answer's size misses the nearest
               size a candidate window cuts to. Exact: limit 0.
``block_err``  largest |mean over a 32x32 block and channel| of the answer
               minus the reference's own rendition cut at the window the
               answer is judged at: of the candidate windows that cut to the
               answer's size, the one whose cut the answer is nearest to.
``score_gap``  the reference's score of that window below its best window's,
               as a share of the spread between its best and its worst
               candidate: 0 for the best window or one that scores the same;
               a window one step of the grid off, or the choice of a scorer
               that leaves a term out or computes below the stated
               precision, reads far above the limit on a corpus whose
               windows score apart (``corpora/portrait.py``).

``rms_err`` is returned beside them and not compared.

``render`` takes two controls: ``operands`` lowers the precision of the
resample (``plain.resize``), and ``scorer="no_skin"`` leaves the skin term
out of the score (``"plain"`` is the scorer as described).
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import numpy as np
from PIL import Image, ImageFilter

from perfbench.harness import plain, work as work_mod

NUMBERS = ("dims_gap", "block_err", "score_gap")

# upstream smartcrop.py, the constructor's defaults
DETAIL_WEIGHT = 0.2
EDGE_RADIUS = 0.4
EDGE_WEIGHT = -10.0
OUTSIDE_IMPORTANCE = -0.5
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
# upstream's main(): the target it asks crop() for, and crop()'s defaults
TARGET = 100
MAX_SCALE, MIN_SCALE, SCALE_STEP, STEP = 1.0, 0.9, 0.1, 8

SCORERS = ("plain", "no_skin")


def parse(config: Dict[str, Any]) -> Dict[str, Any]:
    """The reference's own reading of the options string: ``w_<n>``, ``h_<n>``
    and ``smc_1``, each once; any other option, and any of these twice, is an
    error here, since the reference would not be rendering it."""
    url = config["options"]["url"]
    out: Dict[str, Any] = {}
    seen = set()
    for part in url.split(","):
        key, _, value = part.partition("_")
        if part in seen or key in seen:
            raise ValueError(f"the reference renders option {part!r} once, not twice in {url!r}")
        seen.update((part, key))
        if key == "w":
            out["width"] = int(value)
        elif key == "h":
            out["height"] = int(value)
        elif part != "smc_1":
            raise ValueError(f"the reference does not render option {part!r}")
    if "smc_1" not in seen or set(out) != {"width", "height"}:
        raise ValueError(f"the reference renders w_,h_,smc_1 together, not {url!r}")
    return out


def fit_size(options: Dict[str, Any], src_w: int, src_h: int) -> Tuple[int, int]:
    """``-thumbnail WxH``: the size of the frame scaled to fit inside the box."""
    scale = min(int(options["width"]) / src_w, int(options["height"]) / src_h)
    return (max(plain.round_half_up(src_w * scale), 1), max(plain.round_half_up(src_h * scale), 1))


# -- the scorer ---------------------------------------------------------------

def geometry(img_w: int, img_h: int) -> Dict[str, Any]:
    """Upstream ``crop()``'s bookkeeping for a rendition of ``img_w x img_h``:
    the prescale factor, the prescaled size, the side of the largest window
    in the prescaled image's pixels, and the candidate scales."""
    scale = min(img_w / TARGET, img_h / TARGET)
    side = int(math.floor(TARGET * scale))
    min_scale = min(MAX_SCALE, max(1.0 / scale, MIN_SCALE))
    factor = 1.0 / scale / min_scale
    size = (img_w, img_h)
    if factor < 1.0:
        size = (max(int(img_w * factor), 1), max(int(img_h * factor), 1))
        side = int(math.floor(side * factor))
    steps = range(int(MAX_SCALE * 100), int((min_scale - SCALE_STEP) * 100), -int(SCALE_STEP * 100))
    return {"factor": min(factor, 1.0), "size": size, "side": side,
            "scales": [pct / 100.0 for pct in steps]}


def grid(geo: Dict[str, Any]):
    """Every candidate window ``(x, y, side)`` of the prescaled image, in
    upstream's order: scales, then rows, then columns, on the 8 px grid."""
    w, h = geo["size"]
    for scale in geo["scales"]:
        cw = geo["side"] * scale
        if cw < 1.0:
            continue
        for y in range(0, h, STEP):
            if y + cw > h:
                break
            for x in range(0, w, STEP):
                if x + cw > w:
                    break
                yield x, y, cw


def prescale(rendition: np.ndarray, geo: Dict[str, Any]) -> np.ndarray:
    """The image the scorer works on: the rendition resized to the prescaled
    size with Pillow's LANCZOS, or itself where nothing is to be shrunk."""
    if geo["factor"] >= 1.0:
        return rendition
    return np.asarray(Image.fromarray(rendition).resize(geo["size"], Image.LANCZOS))


def feature_maps(rgb: np.ndarray) -> np.ndarray:
    """``[h, w, 3]`` uint8 -> uint8 maps ``[h, w, 3]``: skin, detail,
    saturation, as upstream's ``analyse`` merges them into one image."""
    image = Image.fromarray(rgb)
    luma_image = image.convert("L", (0.2126, 0.7152, 0.0722, 0))
    luma = np.asarray(luma_image).astype(np.float64)
    detail = np.asarray(luma_image.filter(ImageFilter.Kernel((3, 3), (0, -1, 0, -1, 4, -1, 0, -1, 0), 1, 1)))
    r, g, b = (rgb[..., c].astype(np.float64) for c in range(3))

    mag = np.sqrt(r * r + g * g + b * b)
    lit = mag >= 1e-6
    safe = np.where(lit, mag, 1.0)
    rd = np.where(lit, r / safe, 0.0) - SKIN_COLOR[0]
    gd = np.where(lit, g / safe, 0.0) - SKIN_COLOR[1]
    bd = np.where(lit, b / safe, 0.0) - SKIN_COLOR[2]
    skin = 1.0 - np.sqrt(rd * rd + gd * gd + bd * bd)
    skin_mask = (skin > SKIN_THRESHOLD) & (luma >= SKIN_BRIGHTNESS_MIN * 255) & (luma <= SKIN_BRIGHTNESS_MAX * 255)
    skin = np.where(skin_mask, (skin - SKIN_THRESHOLD) * (255.0 / (1.0 - SKIN_THRESHOLD)), 0.0)

    top, low = np.maximum(np.maximum(r, g), b), np.minimum(np.minimum(r, g), b)
    total, spread = (top + low) / 255.0, (top - low) / 255.0
    grey = top == low
    spread = np.where(grey, 0.0, spread)
    total = np.where(grey, 1.0, total)
    total = np.where(total > 1.0, 2.0 - spread, total)
    sat = spread / total
    sat_mask = (sat > SATURATION_THRESHOLD) & (luma >= SATURATION_BRIGHTNESS_MIN * 255) \
        & (luma <= SATURATION_BRIGHTNESS_MAX * 255)
    sat = np.where(sat_mask, (sat - SATURATION_THRESHOLD) * (255.0 / (1.0 - SATURATION_THRESHOLD)), 0.0)
    return np.stack([skin.astype(np.uint8), detail, sat.astype(np.uint8)], axis=-1)


def _thirds(x: np.ndarray) -> np.ndarray:
    x = ((x + 2.0 / 3.0) % 2.0 * 0.5 - 0.5) * 16.0
    return np.maximum(1.0 - x * x, 0.0)


def importance(hw: Tuple[int, int], x0: float, y0: float, cw: float, ch: float) -> np.ndarray:
    """Upstream's ``importance(crop, x, y)`` at every pixel of an ``hw``
    image for the window at ``(x0, y0)`` of ``cw x ch``."""
    ys, xs = np.mgrid[0:hw[0], 0:hw[1]].astype(np.float64)
    inside = (xs >= x0) & (xs < x0 + cw) & (ys >= y0) & (ys < y0 + ch)
    px = np.abs(0.5 - (xs - x0) / cw) * 2.0
    py = np.abs(0.5 - (ys - y0) / ch) * 2.0
    dx = np.maximum(px - 1.0 + EDGE_RADIUS, 0.0)
    dy = np.maximum(py - 1.0 + EDGE_RADIUS, 0.0)
    d = (dx * dx + dy * dy) * EDGE_WEIGHT
    s = 1.41 - np.sqrt(px * px + py * py)
    s = s + (np.maximum(0.0, s + d + 0.5) * 1.2) * (_thirds(px) + _thirds(py))
    return np.where(inside, s + d, OUTSIDE_IMPORTANCE)


def candidates(small: np.ndarray, geo: Dict[str, Any], scorer: str = "plain") -> List[Dict[str, float]]:
    """Every candidate window of the prescaled image with its score."""
    if scorer not in SCORERS:
        raise ValueError(f"unknown scorer control {scorer!r}")
    maps = feature_maps(small).astype(np.float64) / 255.0
    skin, detail, sat = maps[..., 0], maps[..., 1], maps[..., 2]
    weight = detail * DETAIL_WEIGHT + sat * (detail + SATURATION_BIAS) * SATURATION_WEIGHT
    if scorer != "no_skin":
        weight = weight + skin * (detail + SKIN_BIAS) * SKIN_WEIGHT
    return [{"x": x, "y": y, "width": cw, "height": cw,
             "score": float((weight * importance(small.shape[:2], x, y, cw, cw)).sum() / (cw * cw))}
            for x, y, cw in grid(geo)]


def cut_box(window: Dict[str, float], factor: float, rendition_hw: Tuple[int, int]) -> Tuple[int, int, int, int]:
    """A window of the prescaled image -> ``(x0, y0, x1, y1)`` of the
    rendition, as upstream hands it to ``convert -crop`` and ImageMagick
    clamps it: the geometry's size is the window's far corner."""
    img_h, img_w = rendition_hw
    x, y, w, h = (int(math.floor(window[k] / factor)) for k in ("x", "y", "width", "height"))
    x0, y0 = min(x, img_w), min(y, img_h)
    return x0, y0, min(x0 + x + w, img_w), min(y0 + y + h, img_h)


def score_rendition(rendition: np.ndarray, scorer: str = "plain"):
    """uint8 rendition -> (every candidate with its score and its cut box,
    the chosen one: the first of the best, as upstream's ``>``). No candidate
    (a rendition smaller than any window): the whole rendition, as the
    program answers then."""
    hw = rendition.shape[:2]
    geo = geometry(hw[1], hw[0])
    windows = candidates(prescale(rendition, geo), geo, scorer)
    for window in windows:
        window["box"] = cut_box(window, geo["factor"], hw)
    if not windows:
        windows = [{"x": 0, "y": 0, "width": hw[1], "height": hw[0], "score": 0.0,
                    "box": (0, 0, hw[1], hw[0])}]
    return windows, max(windows, key=lambda window: window["score"])


# -- the interface -------------------------------------------------------------

def render_fit(data: bytes, options: Dict[str, Any], operands: str = "float32") -> np.ndarray:
    rgb = plain.decode(data)
    out_w, out_h = fit_size(options, rgb.shape[1], rgb.shape[0])
    return plain.resize(rgb, out_w, out_h, operands=operands)


def render(data: bytes, options: Dict[str, Any], operands: str = "float32",
           scorer: str = "plain") -> np.ndarray:
    """Encoded original -> the fitted, smart-cropped frame as float32
    ``[h, w, 3]``. ``operands`` and ``scorer`` are the two controls."""
    frame = render_fit(data, options, operands)
    _, chosen = score_rendition(plain.to_u8(frame), scorer)
    x0, y0, x1, y1 = chosen["box"]
    return np.ascontiguousarray(frame[y0:y1, x0:x1])


def judge_answer(answer: np.ndarray, frame: np.ndarray, windows: List[Dict[str, float]]) -> Dict[str, float]:
    """One decoded answer against the rendition ``frame`` and its scored
    candidate windows."""
    sizes = [(w["box"][2] - w["box"][0], w["box"][3] - w["box"][1]) for w in windows]
    gaps = [abs(answer.shape[1] - sw) + abs(answer.shape[0] - sh) for sw, sh in sizes]
    if min(gaps):
        return {"dims_gap": float(min(gaps))}
    # of the windows that cut to this size, the one the answer is nearest to
    by_box: Dict[Tuple[int, int, int, int], float] = {}
    for window, gap in zip(windows, gaps):
        if not gap:
            by_box[window["box"]] = max(window["score"], by_box.get(window["box"], -math.inf))
    judged = []
    for (x0, y0, x1, y1), score in by_box.items():
        block, rms = plain.block_and_rms(answer, frame[y0:y1, x0:x1])
        judged.append((rms, block, score))
    rms, block, score = min(judged)
    top = max(w["score"] for w in windows)
    spread = top - min(w["score"] for w in windows)
    gap = (top - score) / spread if spread > 0 else 0.0
    return {"dims_gap": 0.0, "block_err": block, "rms_err": rms, "score_gap": float(gap)}


def judge_original(data: bytes, answers: List[np.ndarray],
                   options: Dict[str, Any]) -> List[Dict[str, float]]:
    """The numbers of every distinct answer (decoded, uint8) to one original,
    against one rendition of it and one scoring of that."""
    frame = render_fit(data, options)
    windows, _ = score_rendition(plain.to_u8(frame))
    return [judge_answer(answer, frame, windows) for answer in answers]


# one pixel of the three feature maps and of the weight they merge into:
# luma 5, Laplacian 6, skin 20, saturation 14, the weighted sum 9
FEATURE_FLOPS_PER_PIXEL = 54.0


def work(config: Dict[str, Any]) -> Dict[str, Dict[str, float]]:
    """Needed work per image, by kernel. ``resample``: the whole frame to the
    fitted rendition. ``smartcrop_score``: the feature maps once over the
    prescaled image, then for every candidate window a multiply-add per pixel
    of the window for the importance sum and an add for the sum of the
    weights under it, and one sum of all weights; bytes: the prescaled image
    read once as uint8, one importance field per scale as float32, a score
    written per window. What happens outside the window is one number, and
    a sum per window over the whole image, as the plain scorer makes, is not
    needed work."""
    frame = config["frame"]
    out_w, out_h = fit_size(parse(config), frame["width"], frame["height"])
    geo = geometry(out_w, out_h)
    pixels = float(geo["size"][0] * geo["size"][1])
    flops = FEATURE_FLOPS_PER_PIXEL * pixels + pixels
    nbytes = 3.0 * pixels
    sides = [cw for _, _, cw in grid(geo)]
    for cw in sorted(set(sides)):
        flops += sides.count(cw) * 3.0 * math.ceil(cw) ** 2
        nbytes += 4.0 * math.ceil(cw) ** 2 + 4.0 * sides.count(cw)
    return {"resample": work_mod.resize_work(frame["width"], frame["height"], frame["width"],
                                             frame["height"], out_w, out_h),
            "smartcrop_score": {"flops": flops, "bytes": nbytes}}
