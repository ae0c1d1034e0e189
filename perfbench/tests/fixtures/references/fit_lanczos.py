"""The plain reference of an aspect-preserving resize, for the fixture
deployment of ``perfbench/tests/fixtures/``: semantics other than crop-fill,
added as files alone. It imports nothing of the program.

``w_<n>`` alone  ``-thumbnail <n>x``: the width becomes ``n`` and the height
                 follows the frame's aspect, rounded to the nearest pixel;
                 nothing is cut.

The numbers it judges by are crop-fill's two and one more:

``dims_gap``   |width| + |height| by which an answer's size misses. Limit 0.
``block_err``  largest |mean over a 32x32 block and channel| of answer minus
               reference, in uint8 levels.
``mean_err``   largest |mean over the whole frame| of answer minus reference
               of any channel, in uint8 levels: a shift of tone too faint
               for any one block.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np

from perfbench.harness import plain, work as work_mod

NUMBERS = ("dims_gap", "block_err", "mean_err")


def parse(config: Dict[str, Any]) -> Dict[str, Any]:
    url = config["options"]["url"]
    key, _, value = url.partition("_")
    if "," in url or key != "w":
        raise ValueError(f"the reference renders w_<n> alone, not {url!r}")
    return {"width": int(value)}


def size(options: Dict[str, Any], src_w: int, src_h: int) -> Tuple[int, int]:
    out_w = int(options["width"])
    return out_w, max(plain.round_half_up(src_h * out_w / src_w), 1)


def render(data: bytes, options: Dict[str, Any], operands: str = "float32") -> np.ndarray:
    rgb = plain.decode(data)
    out_w, out_h = size(options, rgb.shape[1], rgb.shape[0])
    return plain.resize(rgb, out_w, out_h, operands=operands)


def judge_original(data: bytes, answers: List[np.ndarray],
                   options: Dict[str, Any]) -> List[Dict[str, float]]:
    frame = render(data, options)
    out = []
    for answer in answers:
        numbers = plain.against_frame(answer, frame)
        if not numbers["dims_gap"]:
            numbers["mean_err"] = float(np.abs((answer.astype(np.float32) - frame).mean(axis=(0, 1))).max())
        out.append(numbers)
    return out


def work(config: Dict[str, Any]) -> Dict[str, Dict[str, float]]:
    frame = config["frame"]
    out_w, out_h = size(parse(config), frame["width"], frame["height"])
    return {"resample": work_mod.resize_work(
        frame["width"], frame["height"], frame["width"], frame["height"], out_w, out_h)}
