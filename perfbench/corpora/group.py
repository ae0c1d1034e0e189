"""Corpus kind ``group``: a group photograph, made from the seed.

What a face blur has to find, and what it has to leave alone:

- the **ground**: a photo-like scene (a smooth colour field of walls, lawn
  and sky tones, hard-edged structure, band-limited texture, sensor grain),
  detailed enough that a shifted or swapped answer reads in the block means
  and that a pixelated block differs from the plain one;
- six to ten **subjects**: a torso in a clothing colour, a neck, a head with
  hair, brows, eyes, a nose's shadow and a mouth, textured. The face is 0.11
  to 0.19 of the frame's height (450 to 750 px of a 4000 px frame, 120 to
  200 px in a 1066 px rendition: inside the 15 to 55% of a view the detector
  was trained at, for the full view and the corner tiles both). Skin tones
  run from light to dark, and each subject is lit from one side by a
  different amount. They stand in two loose rows; some reach the frame's
  edge, and a front-row head may stand in front of a back-row torso or
  overlap a neighbour's head;
- at least one **skin-toned thing that is no face** a frame: a bare arm (a
  slanted bar) or a stretch of wall in a skin tone, with no features. A
  detector that answers to colour alone blurs it.

**The corpus is held to the mechanism.** A cell whose detector finds nothing
measures a resize alone, so a frame is redrawn (from the same seed, the next
attempt) until the configuration's reference finds at least ``NEEDED`` faces
it is sure of in a stand-in for the rendition: the frame reduced with
Pillow to ``RENDITION_SHARE`` of its side (4/15: 6000 -> 1600, and the toy's
1500 -> 400), which is what every configuration of this kind cuts it to.
The stand-in is made from the half-size scene, before the enlargement and
the grain, and is not the reference's own Lanczos render of the ENCODED
frame, which takes seconds a frame; the check asks for one face more than
the cell needs so that the difference cannot matter. The detector is reached through
the reference's file (``references/faceblur_lanczos.py``), loaded by name as
the harness loads it: nothing of the program is used here.

Built at half size and enlarged, like ``photo``; numpy and Pillow only.
"""

from __future__ import annotations

import functools
import os
from typing import Tuple

import numpy as np
from PIL import Image, ImageDraw

from perfbench.harness import manifest

RENDITION_SHARE = 4.0 / 15.0
NEEDED = 5          # sure faces in the stand-in: the cell needs 4
ATTEMPTS = 48

# light to dark, as RGB at full light
SKIN_TONES = ((236, 188, 160), (224, 172, 138), (198, 150, 116), (172, 122, 90), (141, 98, 70), (110, 74, 52))

TONE_SHARES = (0.2, 0.2, 0.2, 0.2, 0.12, 0.08)

@functools.lru_cache(maxsize=None)
def _reference():
    roots = (os.path.dirname(os.path.dirname(os.path.abspath(__file__))), manifest.BENCH_DIR)
    return manifest.load_plug("references", "faceblur_lanczos", ("detect", "THRESHOLD", "MARGIN"), roots)


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


def _shade(colour, factor: float) -> Tuple[int, int, int]:
    return tuple(int(min(max(v * factor, 0), 255)) for v in colour)


def _ground(rng: np.random.Generator, w2: int, h2: int) -> Image.Image:
    """Walls, lawn and sky: cool or green tones, none of them skin."""
    coarse = rng.integers(50, 200, size=(6, 9, 3)).astype(np.float32)
    coarse[..., 0] *= 0.8          # keep the red under the green and blue: no skin by accident
    canvas = Image.fromarray(coarse.astype(np.uint8)).resize((w2, h2), Image.BICUBIC)
    draw = ImageDraw.Draw(canvas)
    for _ in range(int(rng.integers(10, 18))):
        cx, cy = rng.uniform(0, w2), rng.uniform(0, h2)
        rx, ry = rng.uniform(0.02, 0.12) * w2, rng.uniform(0.02, 0.12) * h2
        g = int(rng.integers(40, 220))
        colour = (int(g * rng.uniform(0.5, 0.85)), g, int(min(g * rng.uniform(0.8, 1.2), 255)))
        if rng.random() < 0.5:
            draw.ellipse([cx - rx, cy - ry, cx + rx, cy + ry], fill=colour)
        else:
            draw.rectangle([cx - rx, cy - ry, cx + rx, cy + ry], fill=colour)
    for _ in range(int(rng.integers(6, 12))):
        pts = [(rng.uniform(0, w2), rng.uniform(0, h2)) for _ in range(2)]
        g = int(rng.integers(10, 245))
        draw.line(pts, fill=(int(g * 0.7), g, g), width=int(rng.integers(2, 9)))
    return canvas


def _subject(draw: ImageDraw.ImageDraw, rng: np.random.Generator, cx: float, cy: float, ry: float) -> None:
    """One person whose face is centred on (cx, cy) with half-height ``ry``."""
    rx = ry * float(rng.uniform(0.82, 0.95))      # a head with its ears and hair: wide for a face
    tone = SKIN_TONES[int(rng.choice(len(SKIN_TONES), p=TONE_SHARES))]
    light = float(rng.uniform(0.85, 1.05))
    skin, dim = _shade(tone, light), _shade(tone, light * float(rng.uniform(0.72, 0.9)))
    hair = tuple(int(v) for v in (rng.integers(15, 70), rng.integers(12, 55), rng.integers(8, 45)))
    g = int(rng.integers(40, 225))                # clothes: any colour whose red stays under its green or blue
    cloth = (int(g * rng.uniform(0.15, 0.8)), int(g * rng.uniform(0.5, 1.0)), int(g * rng.uniform(0.5, 1.0)))
    lit_from = 1.0 if rng.random() < 0.5 else -1.0
    # torso and shoulders, then the neck, the hair behind, the head, one cheek in shade
    draw.rounded_rectangle([cx - 2.3 * rx, cy + 1.25 * ry, cx + 2.3 * rx, cy + 6.0 * ry], radius=0.9 * rx, fill=cloth)
    draw.rectangle([cx - 0.45 * rx, cy + 0.75 * ry, cx + 0.45 * rx, cy + 1.5 * ry], fill=dim)
    draw.ellipse([cx - 1.14 * rx, cy - 1.22 * ry, cx + 1.14 * rx, cy + 0.5 * ry], fill=hair)
    draw.ellipse([cx - rx, cy - ry, cx + rx, cy + ry], fill=skin)
    draw.chord([cx - rx, cy - ry, cx + rx, cy + ry], 300 if lit_from > 0 else 120, 60 if lit_from > 0 else 240, fill=dim)
    draw.ellipse([cx - 0.8 * rx, cy - 0.95 * ry, cx + 0.8 * rx, cy + 0.95 * ry], fill=skin)
    draw.chord([cx - 1.02 * rx, cy - 1.04 * ry, cx + 1.02 * rx, cy + 0.2 * ry], 200, 340, fill=hair)   # the hairline
    for side in (-1.0, 1.0):
        ex, ey = cx + side * 0.42 * rx, cy - 0.12 * ry
        draw.ellipse([ex - 0.2 * rx, ey - 0.08 * ry, ex + 0.2 * rx, ey + 0.08 * ry], fill=(240, 238, 232))
        draw.ellipse([ex - 0.09 * rx, ey - 0.08 * ry, ex + 0.09 * rx, ey + 0.08 * ry], fill=(34, 24, 18))
        draw.arc([ex - 0.3 * rx, ey - 0.26 * ry, ex + 0.3 * rx, ey + 0.08 * ry], 200, 340, fill=hair,
                 width=max(int(0.05 * ry), 1))
    draw.polygon([(cx, cy - 0.05 * ry), (cx - 0.14 * rx, cy + 0.32 * ry), (cx + 0.14 * rx, cy + 0.32 * ry)], fill=dim)
    draw.ellipse([cx - 0.36 * rx, cy + 0.5 * ry, cx + 0.36 * rx, cy + 0.64 * ry], fill=_shade((170, 70, 70), light))


def _half(rng: np.random.Generator, width: int, height: int) -> np.ndarray:
    """The scene at half size, uint8 in [10, 245]."""
    w2, h2 = max(width // 2, 8), max(height // 2, 8)
    canvas = _ground(rng, w2, h2)
    draw = ImageDraw.Draw(canvas)

    # skin-toned things that are no face: a stretch of wall, a bare arm
    for _ in range(int(rng.integers(1, 3))):
        tone = _shade(SKIN_TONES[int(rng.integers(0, len(SKIN_TONES)))], float(rng.uniform(0.85, 1.0)))
        x, y = rng.uniform(0.05, 0.8) * w2, rng.uniform(0.05, 0.8) * h2
        if rng.random() < 0.5:
            draw.rectangle([x, y, x + rng.uniform(0.1, 0.2) * w2, y + rng.uniform(0.12, 0.3) * h2], fill=tone)
        else:
            length, thick = rng.uniform(0.15, 0.3) * w2, rng.uniform(0.03, 0.05) * h2
            slant = rng.uniform(-0.5, 0.5) * length
            draw.polygon([(x, y), (x + length, y + slant), (x + length, y + slant + thick), (x, y + thick)], fill=tone)

    # two loose rows, the back one first so that the front one stands before it
    count = int(rng.integers(6, 11))
    back = count // 2
    for row, n in ((0, back), (1, count - back)):
        slots = np.linspace(0.04, 0.96, n + 1)
        for k in range(n):
            # 0.1125 to 0.1875 of the height, the larger more often
            ry = 0.5 * (0.1125 + 0.075 * float(np.sqrt(rng.uniform()))) * h2
            cx = float(rng.uniform(slots[k] - 0.02, slots[k + 1] + 0.02)) * w2    # may reach the edge or a neighbour
            cy = (float(rng.uniform(0.20, 0.34)) if row == 0 else float(rng.uniform(0.50, 0.68))) * h2
            _subject(draw, rng, cx, cy, ry)

    half = np.asarray(canvas).astype(np.int16)
    # texture: weave and foliage (its strength varies over the frame), pores
    strength = np.clip(_field(rng, (5, 5), (w2, h2), 0.3, 1.2), 0.0, 1.0)
    texture = 20.0 * strength * _noise(rng, (w2, h2), 2) + 8.0 * _noise(rng, (w2, h2), 5)
    half += texture.astype(np.int16)[..., None]
    return np.clip(half, 10, 245, out=half).astype(np.uint8)


def sure_faces(half: np.ndarray, width: int, height: int) -> int:
    """Faces the reference is sure of in the stand-in for the rendition of
    the ``width x height`` frame that ``half`` enlarges to."""
    ref = _reference()
    size = (max(int(round(width * RENDITION_SHARE)), 1), max(int(round(height * RENDITION_SHARE)), 1))
    stand_in = np.asarray(Image.fromarray(half).resize(size, Image.LANCZOS))
    return sum(k["box"] is not None and k["score"] >= ref.THRESHOLD + ref.MARGIN for k in ref.detect(stand_in))


def make_image(seed: int, index: int, width: int, height: int) -> np.ndarray:
    """One [height, width, 3] uint8 frame, the same for the same arguments."""
    for attempt in range(ATTEMPTS):
        rng = np.random.default_rng([int(seed), int(index), width, height, 11, attempt])
        half = _half(rng, width, height)
        if sure_faces(half, width, height) >= NEEDED:
            break
    else:
        raise RuntimeError(f"corpus kind group: no frame of seed {seed}, index {index} shows {NEEDED} faces the "
                           f"reference is sure of in {ATTEMPTS} attempts")
    # bilinear enlargement cannot overshoot, so [10, 245] holds at full size
    full = np.array(Image.fromarray(half).resize((width, height), Image.BILINEAR))
    # sensor grain, +-3 levels, in place: the range above leaves the room
    grain = rng.integers(0, 7, size=(height, width, 1), dtype=np.uint8)
    np.subtract(full, np.uint8(3), out=full)
    np.add(full, grain, out=full)
    return full
