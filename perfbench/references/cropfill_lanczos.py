"""The plain reference of crop-fill: the same semantics as the program's,
written down independently. It imports nothing of the program and takes
nothing the program has made; the decode, the Lanczos resize and the block
comparison are the shared ones of ``harness/plain.py``.

The semantics are those of the flyimg URL options (docs/url-options.md, after
ImageMagick):

``w_,h_,c_1``  ``-thumbnail WxH^ -gravity Center -extent WxH``: scale both axes
               so the frame covers the box (each rounded to the nearest pixel),
               then cut the box out of the middle.

The numbers it judges an answer by, each with a limit of its own in the
configuration's ``limits``:

``dims_gap``   |width| + |height| by which an answer's size misses the
               reference's. Exact: limit 0.
``block_err``  largest |mean over a 32x32 block and channel| of answer minus
               reference, in uint8 levels (``plain.block_and_rms``).

``rms_err`` is returned beside them and not compared: it is mostly the output
JPEG's own quantisation, and the control reads under twice the program there
(PERF.md section 2).
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from perfbench.harness import plain, work as work_mod

NUMBERS = ("dims_gap", "block_err")


def parse(config: Dict[str, Any]) -> Dict[str, Any]:
    """The reference's own reading of the configuration's options string: the
    keys it renders (``w_``, ``h_``, ``c_1``); any other is an error here,
    since the reference would not be rendering it."""
    url = config["options"]["url"]
    parts = url.split(",")
    out: Dict[str, Any] = {}
    for part in parts:
        key, _, value = part.partition("_")
        if key == "w":
            out["width"] = int(value)
        elif key == "h":
            out["height"] = int(value)
        elif part != "c_1":
            raise ValueError(f"the reference does not render option {part!r}")
    if "c_1" not in parts or set(out) != {"width", "height"}:
        raise ValueError(f"the reference renders w_,h_,c_1 together, not {url!r}")
    return out


def geometry(options: Dict[str, Any], src_w: int, src_h: int) -> Dict[str, Any]:
    """What the options make of a ``src_w x src_h`` frame: the size the whole
    frame is resized to, and the window of that which is kept."""
    tw, th = int(options["width"]), int(options["height"])
    scale = max(tw / src_w, th / src_h)
    rw = max(plain.round_half_up(src_w * scale), 1)
    rh = max(plain.round_half_up(src_h * scale), 1)
    x0, y0 = max((rw - tw) // 2, 0), max((rh - th) // 2, 0)
    return {"resize": (rw, rh), "rows": (y0, min(y0 + th, rh)),
            "cols": (x0, min(x0 + tw, rw))}


def render(data: bytes, options: Dict[str, Any], operands: str = "float32") -> np.ndarray:
    """Encoded original -> the resized, cut frame as float32 ``[h, w, 3]``."""
    rgb = plain.decode(data)
    geo = geometry(options, rgb.shape[1], rgb.shape[0])
    return plain.resize(rgb, geo["resize"][0], geo["resize"][1], geo["rows"],
                        geo["cols"], operands)


def judge_original(data: bytes, answers: List[np.ndarray],
                   options: Dict[str, Any]) -> List[Dict[str, float]]:
    """The numbers of every distinct answer (decoded, uint8) to one original,
    against one render of it."""
    frame = render(data, options)
    return [plain.against_frame(answer, frame) for answer in answers]


def work(config: Dict[str, Any]) -> Dict[str, Dict[str, float]]:
    """Needed work per image, by kernel: one resample of the window of the
    source that the kept box comes from."""
    frame = config["frame"]
    geo = geometry(parse(config), frame["width"], frame["height"])
    rw, rh = geo["resize"]
    out_w, out_h = geo["cols"][1] - geo["cols"][0], geo["rows"][1] - geo["rows"][0]
    return {"resample": work_mod.resize_work(
        frame["width"], frame["height"], frame["width"] * out_w / rw,
        frame["height"] * out_h / rh, out_w, out_h)}
