"""Corpus kind ``photo``: photo-like frames made from the seed.

Each image has a smooth colour field, hard-edged structure, a band-limited
texture whose strength varies over the frame (so some regions stay smooth)
and sensor grain, so that file size, entropy-decode cost and the resample's
input statistics are those of a photograph and not of noise or flat colour.

Made with numpy and Pillow only; nothing of the program is used here.
"""

from __future__ import annotations

import numpy as np
from PIL import Image, ImageDraw


def _field(rng: np.random.Generator, grid_hw, size_wh, lo: float, hi: float) -> np.ndarray:
    """A smooth random scalar field: a coarse grid, bicubic-enlarged."""
    coarse = rng.uniform(lo, hi, size=grid_hw).astype(np.float32)
    return np.asarray(Image.fromarray(coarse, mode="F").resize(size_wh, Image.BICUBIC))


def make_image(seed: int, index: int, width: int, height: int) -> np.ndarray:
    """One [height, width, 3] uint8 frame, the same for the same arguments."""
    rng = np.random.default_rng([int(seed), int(index), width, height])
    w2, h2 = max(width // 2, 8), max(height // 2, 8)
    grid = (9, 6) if height > width else (6, 9)
    coarse = rng.integers(40, 216, size=grid + (3,)).astype(np.float32)
    canvas = Image.fromarray(coarse.astype(np.uint8)).resize((w2, h2), Image.BICUBIC)
    draw = ImageDraw.Draw(canvas)
    for _ in range(int(rng.integers(10, 18))):
        cx, cy = rng.uniform(0, w2), rng.uniform(0, h2)
        rx, ry = rng.uniform(0.02, 0.16) * w2, rng.uniform(0.02, 0.16) * h2
        colour = tuple(int(v) for v in rng.integers(25, 230, size=3))
        box = [cx - rx, cy - ry, cx + rx, cy + ry]
        if rng.random() < 0.5:
            draw.ellipse(box, fill=colour)
        else:
            draw.rectangle(box, fill=colour)
    for _ in range(int(rng.integers(6, 12))):
        pts = [(rng.uniform(0, w2), rng.uniform(0, h2)) for _ in range(2)]
        colour = tuple(int(v) for v in rng.integers(10, 245, size=3))
        draw.line(pts, fill=colour, width=int(rng.integers(2, 9)))
    # band-limited luma texture whose strength varies over the frame: zero
    # over a good part of it, so smooth regions stay smooth
    w4, h4 = max(w2 // 2, 4), max(h2 // 2, 4)
    noise = rng.standard_normal((h4, w4)).astype(np.float32)
    noise = np.asarray(Image.fromarray(noise, mode="F").resize((w2, h2), Image.BILINEAR))
    strength = np.clip(_field(rng, (5, 5), (w2, h2), -0.8, 1.0), 0.0, 1.0)
    texture = (22.0 * strength * noise).astype(np.int16)
    half = np.asarray(canvas).astype(np.int16)
    half += texture[..., None]
    half = np.clip(half, 10, 245, out=half).astype(np.uint8)
    # bilinear enlargement cannot overshoot, so [10, 245] holds at full size
    full = np.array(Image.fromarray(half).resize((width, height), Image.BILINEAR))
    # sensor grain, +-3 levels, in place: the range above leaves the room
    grain = rng.integers(0, 7, size=(height, width, 1), dtype=np.uint8)
    np.subtract(full, np.uint8(3), out=full)
    np.add(full, grain, out=full)
    return full
