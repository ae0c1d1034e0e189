"""The plain reference's shared pieces: what every reference of
``references/`` renders and judges with, written down independently of the
program. It imports nothing of the program and takes nothing the program has
made. Pillow decodes and encodes; everything between is numpy in float32
(weights are worked out in float64).

Resizing is ImageMagick's Lanczos: output pixel ``i`` samples the source at
``(i + 0.5) * scale - 0.5``, the three-lobe kernel is stretched by the
downscale factor, taps outside the frame are dropped and the rest renormalised.

``operands`` lowers the precision for the control: the pixels, the weights and
the intermediate between the two passes are rounded to that type before each
multiplication, as a kernel with operands of that type would hold them, and
sums stay in float32.
"""

from __future__ import annotations

import io
import math
from typing import Callable, Optional, Tuple

import numpy as np
from PIL import Image

BLOCK = 32

def _quantiser(operands: str) -> Callable[[np.ndarray, bool], np.ndarray]:
    """``q(array, is_weight)``: the array as a kernel with operands of this
    type would hold it, returned as float32."""
    if operands == "float32":
        return lambda a, is_weight=False: a
    if operands == "int8":
        # symmetric per-tensor scaling, as an int8 matmul would be fed: the
        # weights scaled to +-127 at their largest, the pixels centred on 128
        def q_int8(a: np.ndarray, is_weight: bool = False) -> np.ndarray:
            if is_weight:
                scale = 127.0 / max(float(np.abs(a).max()), 1e-30)
                return (np.round(a * scale) / scale).astype(np.float32)
            return np.clip(np.round(a - 128.0), -128, 127).astype(np.float32) + 128.0
        return q_int8
    import ml_dtypes

    low = {"bfloat16": ml_dtypes.bfloat16,
           "float8_e4m3fn": ml_dtypes.float8_e4m3fn}[operands]
    return lambda a, is_weight=False: a.astype(low).astype(np.float32)


def decode(data: bytes) -> np.ndarray:
    with Image.open(io.BytesIO(data)) as im:
        return np.asarray(im.convert("RGB"))


def encode_jpeg(rgb: np.ndarray, quality: int = 90) -> bytes:
    """The reference's own encoder (libjpeg through Pillow, 4:4:4)."""
    buf = io.BytesIO()
    Image.fromarray(rgb).save(buf, format="JPEG", quality=quality, subsampling=0)
    return buf.getvalue()


def _lanczos3(x: np.ndarray) -> np.ndarray:
    return np.where(np.abs(x) < 3.0, np.sinc(x) * np.sinc(x / 3.0), 0.0)


def axis_taps(in_size: int, out_size: int) -> Tuple[np.ndarray, np.ndarray]:
    """Tap indices ``[out, K]`` and float64 weights ``[out, K]`` that take an
    axis of ``in_size`` samples to ``out_size`` (the whole axis to the whole
    axis; a crop takes rows of the result)."""
    scale = in_size / out_size
    stretch = max(scale, 1.0)
    x = (np.arange(out_size, dtype=np.float64) + 0.5) * scale - 0.5
    x = np.clip(x, 0.0, in_size - 1.0)
    taps = 2 * int(math.ceil(3.0 * stretch)) + 2
    first = np.floor(x).astype(np.int64) - taps // 2 + 1
    idx = first[:, None] + np.arange(taps)[None, :]
    w = _lanczos3((idx - x[:, None]) / stretch)
    w[(idx < 0) | (idx >= in_size)] = 0.0
    w /= w.sum(axis=1, keepdims=True)
    return np.clip(idx, 0, in_size - 1), w


def _apply(idx: np.ndarray, w: np.ndarray, a: np.ndarray, q, block: int = 64) -> np.ndarray:
    """``out[i] = sum_k w[i, k] * a[idx[i, k]]`` over the first axis of the
    2-D ``a``, a block of output rows at a time as one dense product."""
    out = np.empty((idx.shape[0], a.shape[1]), dtype=np.float32)
    for start in range(0, idx.shape[0], block):
        rows = slice(start, min(start + block, idx.shape[0]))
        lo, hi = int(idx[rows].min()), int(idx[rows].max()) + 1
        dense = np.zeros((rows.stop - rows.start, hi - lo), dtype=np.float64)
        np.add.at(dense, (np.arange(rows.stop - rows.start)[:, None], idx[rows] - lo), w[rows])
        out[rows] = q(dense.astype(np.float32), True) @ a[lo:hi]
    return out


def resize(rgb: np.ndarray, out_w: int, out_h: int,
           rows: Optional[Tuple[int, int]] = None,
           cols: Optional[Tuple[int, int]] = None,
           operands: str = "float32") -> np.ndarray:
    """Lanczos-resize ``[h, w, 3]`` uint8 to ``out_w x out_h`` and return the
    rows ``rows`` and columns ``cols`` of it as float32, unrounded."""
    q = _quantiser(operands)
    h, w = rgb.shape[:2]
    iy, wy = axis_taps(h, out_h)
    ix, wx = axis_taps(w, out_w)
    if rows is not None:
        iy, wy = iy[rows[0]:rows[1]], wy[rows[0]:rows[1]]
    if cols is not None:
        ix, wx = ix[cols[0]:cols[1]], wx[cols[0]:cols[1]]
    a = q(rgb.reshape(h, w * 3).astype(np.float32), False)
    tmp = _apply(iy, wy, a, q)                               # [oh, w*3]
    oh = tmp.shape[0]
    tmp = q(tmp, False).reshape(oh, w, 3).transpose(1, 0, 2).reshape(w, oh * 3)
    out = _apply(ix, wx, np.ascontiguousarray(tmp), q)       # [ow, oh*3]
    return np.ascontiguousarray(out.reshape(-1, oh, 3).transpose(1, 0, 2))


def round_half_up(x: float) -> int:
    return int(math.floor(x + 0.5))


def to_u8(frame: np.ndarray) -> np.ndarray:
    return np.clip(np.floor(frame + 0.5), 0.0, 255.0).astype(np.uint8)


def against_frame(answer: np.ndarray, frame: np.ndarray) -> dict:
    """What every reference judges a decoded answer by, against its render:
    ``dims_gap`` (|width| + |height| by which the sizes miss) and, where that
    is 0, ``block_err`` and ``rms_err`` (``block_and_rms``)."""
    gap = abs(answer.shape[1] - frame.shape[1]) + abs(answer.shape[0] - frame.shape[0])
    if gap:
        return {"dims_gap": float(gap)}
    block, rms = block_and_rms(answer, frame)
    return {"dims_gap": 0.0, "block_err": block, "rms_err": rms}


def block_and_rms(answer: np.ndarray, ref: np.ndarray) -> Tuple[float, float]:
    """Largest |mean over a 32x32 block and channel| of answer minus
    reference, and the root mean square of it, in uint8 levels. A JPEG's own
    quantisation noise averages out over a block; a shifted window, a swapped
    image, a damaged patch or operands of too few bits do not."""
    diff = answer.astype(np.float32) - ref
    rms = float(np.sqrt(np.mean(diff * diff)))
    h, w = (diff.shape[0] // BLOCK) * BLOCK, (diff.shape[1] // BLOCK) * BLOCK
    if h == 0 or w == 0:
        return float(np.abs(diff.mean(axis=(0, 1))).max()), rms
    blocks = diff[:h, :w].reshape(h // BLOCK, BLOCK, w // BLOCK, BLOCK, 3).mean(axis=(1, 3))
    return float(np.abs(blocks).max()), rms
