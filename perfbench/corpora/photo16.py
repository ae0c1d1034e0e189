"""Corpus kind ``photo16``: photo-like 16-bit RGB frames made from the seed,
as a scanner or a raw converter writes a master.

The scene is ``photo``'s, drawn in float at half size: a smooth colour field,
hard-edged shapes and lines, a band-limited texture whose strength varies
over the frame (so some regions stay smooth). Each channel is enlarged to
full size bilinearly in float, so that edges and gradients fall between the
8-bit levels, and sensor grain of about 160 levels of 65,535 is added to
every sample. So the low byte of every block carries information: an 8-bit
image widened by 257 (every sample a multiple of 257) cannot come out of
this kind, and a path that keeps 8 bits of a sample reads far out.

Made with numpy and Pillow only; nothing of the program is used here.
"""

from __future__ import annotations

import numpy as np
from PIL import Image, ImageDraw

BIT_DEPTH = 16
# one 8-bit level in 16-bit levels
LEVEL = 257.0
# standard deviation of the sensor grain, in levels of 65,535
GRAIN = 160.0


def _channel(values: np.ndarray, size_wh) -> Image.Image:
    return Image.fromarray(values.astype(np.float32), mode="F").resize(size_wh, Image.BICUBIC)


def make_image(seed: int, index: int, width: int, height: int) -> np.ndarray:
    """One [height, width, 3] uint16 frame, the same for the same arguments."""
    rng = np.random.default_rng([int(seed), int(index), width, height, BIT_DEPTH])
    w2, h2 = max(width // 2, 8), max(height // 2, 8)
    grid = (9, 6) if height > width else (6, 9)
    coarse = rng.uniform(40 * LEVEL, 216 * LEVEL, size=grid + (3,))
    planes = [_channel(coarse[..., c], (w2, h2)) for c in range(3)]
    draws = [ImageDraw.Draw(p) for p in planes]
    for _ in range(int(rng.integers(10, 18))):
        cx, cy = rng.uniform(0, w2), rng.uniform(0, h2)
        rx, ry = rng.uniform(0.02, 0.16) * w2, rng.uniform(0.02, 0.16) * h2
        colour = rng.uniform(25 * LEVEL, 230 * LEVEL, size=3)
        box = [cx - rx, cy - ry, cx + rx, cy + ry]
        ellipse = rng.random() < 0.5
        for draw, value in zip(draws, colour):
            if ellipse:
                draw.ellipse(box, fill=float(value))
            else:
                draw.rectangle(box, fill=float(value))
    for _ in range(int(rng.integers(6, 12))):
        pts = [(rng.uniform(0, w2), rng.uniform(0, h2)) for _ in range(2)]
        colour = rng.uniform(10 * LEVEL, 245 * LEVEL, size=3)
        line_width = int(rng.integers(2, 9))
        for draw, value in zip(draws, colour):
            draw.line(pts, fill=float(value), width=line_width)
    # band-limited luma texture whose strength varies over the frame: zero
    # over a good part of it, so smooth regions stay smooth
    w4, h4 = max(w2 // 2, 4), max(h2 // 2, 4)
    noise = rng.standard_normal((h4, w4)).astype(np.float32)
    noise = np.asarray(Image.fromarray(noise, mode="F").resize((w2, h2), Image.BILINEAR))
    strength = np.clip(_channel(rng.uniform(-0.8, 1.0, size=(5, 5)), (w2, h2)), 0.0, 1.0)
    texture = (22.0 * LEVEL) * np.asarray(strength, dtype=np.float32) * noise
    out = np.empty((height, width, 3), dtype=np.uint16)
    for c, plane in enumerate(planes):
        half = np.asarray(plane, dtype=np.float32) + texture
        # bilinear enlargement cannot overshoot, and the grain stays over
        # ten deviations inside the margin: nothing is clipped
        np.clip(half, 10 * LEVEL, 245 * LEVEL, out=half)
        full = np.asarray(Image.fromarray(half, mode="F").resize((width, height), Image.BILINEAR))
        grain = rng.standard_normal((height, width), dtype=np.float32)
        grain *= GRAIN
        grain += full
        out[..., c] = np.rint(grain)
    return out
