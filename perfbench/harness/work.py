"""The yardstick for rooflines: the chip's peaks, and the work a resize
needs whatever implements it.

Needed work reads only an image's true source size, its output size and the
filter: the source is read once and the output written once, as uint8, and
the multiply-adds are those of a separable filter at its tap count (kernel
support stretched by the downscale factor), in the cheaper order of the two
passes. A dense ``[out, in]`` matrix, a banded gather or a Pallas kernel all
read against this same number.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict

FILTER_SUPPORT = {"lanczos3": 3.0, "triangle": 1.0, "cubic": 2.0, "box": 0.5}

_PEAKS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "peaks.json")


def peaks(device_kind: str) -> Dict[str, Any]:
    """Peak FLOP/s and bytes/s of one chip of ``device_kind``. A device that
    is not in the table is an error, not a default."""
    with open(_PEAKS, "r", encoding="utf-8") as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it to peaks.json with its source")
    return table[device_kind]


def taps(method: str, in_size: float, out_size: float) -> float:
    """Source samples under the kernel for one output sample of an axis."""
    return 2.0 * FILTER_SUPPORT[method] * max(in_size / out_size, 1.0)


def resize_work(src_w: int, src_h: int, span_w: float, span_h: float,
                out_w: int, out_h: int, method: str = "lanczos3",
                channels: int = 3) -> Dict[str, float]:
    """FLOPs and bytes needed to resample the ``span_w x span_h`` window of a
    ``src_w x src_h`` uint8 frame to ``out_w x out_h``."""
    tx, ty = taps(method, span_w, out_w), taps(method, span_h, out_h)
    rows_first = out_h * span_w * ty + out_h * out_w * tx
    cols_first = span_h * out_w * tx + out_h * out_w * ty
    macs = channels * min(rows_first, cols_first)
    return {"flops": 2.0 * macs,
            "bytes": float(channels * (src_w * src_h + out_w * out_h))}


def least_seconds(work: Dict[str, float], peak: Dict[str, Any]) -> Dict[str, Any]:
    """The least time the chip could take for ``work``, and which bound it is."""
    t_flops = work["flops"] / float(peak["flops_per_s"])
    t_bytes = work["bytes"] / float(peak["bytes_per_s"])
    return {"seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}
