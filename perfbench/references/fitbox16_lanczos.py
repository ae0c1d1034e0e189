"""The plain reference of a fit inside a box for 16-bit originals, answered
losslessly at their own depth: the semantics of configuration
``archive-png16-lossless``, written down independently. It imports nothing
of the program and takes nothing the program has made; the decode, the
Lanczos resize and the block comparison are the shared ones of
``harness/plain.py``.

The semantics are those of the flyimg URL options (docs/url-options.md, after
ImageMagick):

``w_<W>,h_<H>``  ``-thumbnail WxH``: the frame scaled by the smaller of
                 ``W / width`` and ``H / height``, so that it fits inside the
                 box with its aspect kept; each side rounded half up to a
                 whole pixel (``plain.round_half_up``), nothing cut. The
                 answer is a PNG at the depth the original was read at
                 (upstream's ``o_auto``; ImageMagick writes the depth it
                 read).

Both numbers are in 16-bit levels (of 65,535), and an answer is read at its
own depth (``plain.decode``): an 8-bit answer to a 16-bit original is 257
times too dark and fails by thousands.

``dims_gap``   |width| + |height| by which an answer's size misses. Limit 0.
``block_err``  largest |mean over a 32x32 block and channel| of answer minus
               reference (``plain.block_and_rms``). An answer that kept 8
               bits of a sample, was resampled with bfloat16 operands, or is
               shifted by a pixel reads over its limit (PERF.md section 2).
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np

from perfbench.harness import plain, work as work_mod

NUMBERS = ("dims_gap", "block_err")
MAX_LEVEL = 65535.0


def parse(config: Dict[str, Any]) -> Dict[str, Any]:
    """The reference's own reading of the options string: ``w_`` and ``h_``
    together and nothing else (``c_1`` is a crop, another semantics)."""
    url = config["options"]["url"]
    out: Dict[str, Any] = {}
    for part in url.split(","):
        key, _, value = part.partition("_")
        if key == "w" and "width" not in out:
            out["width"] = int(value)
        elif key == "h" and "height" not in out:
            out["height"] = int(value)
        else:
            raise ValueError(f"the reference does not render option {part!r}")
    if set(out) != {"width", "height"} or min(out.values()) <= 0:
        raise ValueError(f"the reference renders w_<W>,h_<H> together, not {url!r}")
    return out


def size(options: Dict[str, Any], src_w: int, src_h: int) -> Tuple[int, int]:
    """The answer's width and height for a ``src_w x src_h`` frame."""
    scale = min(options["width"] / src_w, options["height"] / src_h)
    return (max(plain.round_half_up(src_w * scale), 1),
            max(plain.round_half_up(src_h * scale), 1))


def render(data: bytes, options: Dict[str, Any], operands: str = "float32") -> np.ndarray:
    """Encoded original -> the fitted frame as float32 ``[h, w, 3]`` in
    levels of the original's depth, unrounded."""
    rgb = plain.decode(data)
    out_w, out_h = size(options, rgb.shape[1], rgb.shape[0])
    return plain.resize(rgb, out_w, out_h, operands=operands)


def judge_original(data: bytes, answers: List[np.ndarray],
                   options: Dict[str, Any]) -> List[Dict[str, float]]:
    """The numbers of every distinct answer (decoded at its own depth) to
    one original, against one render of it, held to the samples' range as
    any answer is: Lanczos rings past a hard edge, and over 0 or 65,535
    an answer is clipped (at 16 bits one such pixel moves a block's mean
    by a few levels)."""
    frame = np.clip(render(data, options), 0.0, MAX_LEVEL)
    return [plain.against_frame(answer, frame) for answer in answers]


def work(config: Dict[str, Any]) -> Dict[str, Dict[str, float]]:
    """Needed work of one image for the rooflines: the resize of the whole
    frame, its samples read and the answer's written at 2 bytes each."""
    frame = config["frame"]
    out_w, out_h = size(parse(config), frame["width"], frame["height"])
    return {"resample": work_mod.resize_work(
        frame["width"], frame["height"], frame["width"], frame["height"], out_w, out_h,
        sample_bytes=2)}
