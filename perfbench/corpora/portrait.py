"""Corpus kind ``portrait``: a subject on a quiet ground, made from the seed.

What a smart crop has to find, and nothing that helps it by accident:

- the **ground**: a smooth field of dull, bluish or greenish greys with a
  faint texture and sensor grain. Little detail, saturation under the
  scorer's threshold, no skin tone;
- the **subject**: a head and neck in skin tones with dark hair, eyes and a
  mouth, textured, so that the scorer's skin term (skin x detail) and its
  detail term both find it. Its height in the frame is drawn from the seed,
  anywhere from a fifth to four fifths of the way down, so that the best
  window differs from image to image and the windows around it score far
  apart;
- a **prop**: a smaller, strongly saturated and textured patch of no skin
  tone, almost half a frame above or below the subject. With the skin term
  the subject outweighs it; a scorer that leaves the skin term out goes to
  the prop, which is how the comparison sees that fault.

Built at half size and enlarged, like ``photo``, so that a 24 MP frame takes
a second or two; numpy and Pillow only, nothing of the program.
"""

from __future__ import annotations

import numpy as np
from PIL import Image, ImageDraw

SKIN = np.array([0.78, 0.57, 0.44])   # the direction upstream's scorer calls skin


def _field(rng: np.random.Generator, grid_hw, size_wh, lo: float, hi: float) -> np.ndarray:
    coarse = rng.uniform(lo, hi, size=grid_hw).astype(np.float32)
    return np.asarray(Image.fromarray(coarse, mode="F").resize(size_wh, Image.BICUBIC))


def _noise(rng: np.random.Generator, size_wh, cell: int) -> np.ndarray:
    """Band-limited noise in [-1, 1]: white noise at ``1/cell`` of the size,
    enlarged."""
    w, h = size_wh
    small = rng.standard_normal((max(h // cell, 2), max(w // cell, 2))).astype(np.float32)
    big = np.asarray(Image.fromarray(small, mode="F").resize(size_wh, Image.BILINEAR))
    return np.clip(big / 2.5, -1.0, 1.0)


def make_image(seed: int, index: int, width: int, height: int) -> np.ndarray:
    """One [height, width, 3] uint8 frame, the same for the same arguments."""
    rng = np.random.default_rng([int(seed), int(index), width, height, 7])
    w2, h2 = max(width // 2, 8), max(height // 2, 8)
    unit = min(w2, h2)

    # the ground: grey with a cool cast that drifts over the frame
    level = _field(rng, (5, 4), (w2, h2), 95.0, 150.0)
    cast = _field(rng, (3, 3), (w2, h2), -8.0, 8.0)
    ground = np.stack([level - 6.0 - cast, level + cast * 0.5, level + 8.0 + cast], axis=-1)
    ground += (5.0 * _noise(rng, (w2, h2), 6))[..., None]
    canvas = Image.fromarray(np.clip(ground, 20, 235).astype(np.uint8))
    draw = ImageDraw.Draw(canvas)

    # where the subject and the prop stand
    cy = rng.uniform(0.2, 0.8) * h2
    cx = rng.uniform(0.42, 0.58) * w2
    head_rx, head_ry = 0.15 * unit, 0.20 * unit
    tone = float(rng.uniform(0.75, 1.0))
    skin = tuple(int(v) for v in np.clip(SKIN * 270.0 * tone, 0, 255))
    shade = tuple(int(v * 0.82) for v in skin)
    hair = tuple(int(v) for v in rng.integers(18, 60, size=3))
    # hair behind, neck below, head on top, then the features
    draw.ellipse([cx - 1.12 * head_rx, cy - 1.18 * head_ry, cx + 1.12 * head_rx, cy + 0.55 * head_ry], fill=hair)
    draw.rectangle([cx - 0.45 * head_rx, cy + 0.7 * head_ry, cx + 0.45 * head_rx, cy + 1.45 * head_ry], fill=shade)
    draw.ellipse([cx - head_rx, cy - head_ry, cx + head_rx, cy + head_ry], fill=skin)
    for side in (-1.0, 1.0):
        ex, ey = cx + side * 0.42 * head_rx, cy - 0.12 * head_ry
        draw.ellipse([ex - 0.16 * head_rx, ey - 0.07 * head_ry, ex + 0.16 * head_rx, ey + 0.07 * head_ry],
                     fill=(245, 245, 240))
        draw.ellipse([ex - 0.07 * head_rx, ey - 0.07 * head_ry, ex + 0.07 * head_rx, ey + 0.07 * head_ry],
                     fill=(30, 22, 18))
        draw.arc([ex - 0.24 * head_rx, ey - 0.22 * head_ry, ex + 0.24 * head_rx, ey + 0.06 * head_ry],
                 200, 340, fill=hair, width=max(int(0.035 * head_ry), 1))
    draw.polygon([(cx, cy - 0.05 * head_ry), (cx - 0.12 * head_rx, cy + 0.3 * head_ry),
                  (cx + 0.12 * head_rx, cy + 0.3 * head_ry)], fill=shade)
    draw.ellipse([cx - 0.35 * head_rx, cy + 0.48 * head_ry, cx + 0.35 * head_rx, cy + 0.62 * head_ry],
                 fill=(150, 60, 60))
    # the prop: almost half a frame away, on the side that has the room
    py = cy + (0.46 * h2 if cy < 0.5 * h2 else -0.46 * h2)
    px = rng.uniform(0.3, 0.7) * w2
    prx, pry = 0.13 * unit, 0.09 * unit
    hue = [(25, 70, 215), (20, 150, 60), (120, 40, 200)][int(rng.integers(0, 3))]
    draw.rectangle([px - prx, py - pry, px + prx, py + pry], fill=hue)
    for k in range(6):   # stripes: detail inside the prop
        x0 = px - prx + (2 * k + 0.5) * prx / 6.0
        draw.rectangle([x0, py - pry, x0 + prx / 12.0, py + pry], fill=tuple(min(int(v * 1.6) + 25, 255) for v in hue))

    half = np.asarray(canvas).astype(np.int16)
    # texture on the subject and the prop alone: pores and weave, not ground
    yy, xx = np.mgrid[0:h2, 0:w2].astype(np.float32)
    on_subject = ((xx - cx) / (1.15 * head_rx)) ** 2 + ((yy - cy) / (1.5 * head_ry)) ** 2 <= 1.0
    on_prop = (np.abs(xx - px) <= prx) & (np.abs(yy - py) <= pry)
    texture = 16.0 * _noise(rng, (w2, h2), 3) * on_subject + 12.0 * _noise(rng, (w2, h2), 2) * on_prop
    half += texture.astype(np.int16)[..., None]
    half = np.clip(half, 10, 245, out=half).astype(np.uint8)
    # bilinear enlargement cannot overshoot, so [10, 245] holds at full size
    full = np.array(Image.fromarray(half).resize((width, height), Image.BILINEAR))
    # sensor grain, +-3 levels, in place: the range above leaves the room
    grain = rng.integers(0, 7, size=(height, width, 1), dtype=np.uint8)
    np.subtract(full, np.uint8(3), out=full)
    np.add(full, grain, out=full)
    return full
