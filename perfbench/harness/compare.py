"""The comparison that decides ``correct``.

Every distinct answer the window produced (one per source image and output
digest, so that identical bytes are judged once and no answer is skipped) is
decoded and held against the plain reference's render of the same original.
The numbers compared, each the worst over the answers, each with a limit of
its own from the configuration's file:

``dims_gap``   |width| + |height| by which an answer's size misses the
               reference's. Exact: limit 0.
``block_err``  largest |mean over a 32x32 block and channel| of answer minus
               reference, in uint8 levels. A JPEG's own quantisation noise
               averages out over a block; a shifted window, a swapped image,
               a damaged patch or operands of too few bits do not.
``unanswered`` calls that never answered, and answers that would not decode.

The root mean square of answer minus reference is printed beside them and not
compared: it is mostly the output JPEG's own quantisation, and the control
reads under twice the program there (PERF.md section 2).
"""

from __future__ import annotations

import io
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Tuple

import numpy as np
from PIL import Image

from . import reference

BLOCK = 32
MISSING = 1.0e9   # a number that could not be read fails its limit


def block_and_rms(answer: np.ndarray, ref: np.ndarray) -> Tuple[float, float]:
    diff = answer.astype(np.float32) - ref
    rms = float(np.sqrt(np.mean(diff * diff)))
    h, w = (diff.shape[0] // BLOCK) * BLOCK, (diff.shape[1] // BLOCK) * BLOCK
    if h == 0 or w == 0:
        return float(np.abs(diff.mean(axis=(0, 1))).max()), rms
    blocks = diff[:h, :w].reshape(h // BLOCK, BLOCK, w // BLOCK, BLOCK, 3).mean(axis=(1, 3))
    return float(np.abs(blocks).max()), rms


class Judge:
    """Holds the reference's render of each original, made once, and judges
    answers against it."""

    def __init__(self, config: Dict[str, Any], corpus: List[bytes]) -> None:
        self.options = reference.parse_options(config["options"]["url"])
        self.limits = dict(config["limits"])
        self._corpus = corpus
        self._refs: Dict[int, np.ndarray] = {}

    def _ref(self, item: int) -> np.ndarray:
        if item not in self._refs:
            self._refs[item] = reference.render(self._corpus[item], self.options)
        return self._refs[item]

    def judge_one(self, item: int, answer_bytes: bytes) -> Dict[str, float]:
        try:
            with Image.open(io.BytesIO(answer_bytes)) as im:
                answer = np.asarray(im.convert("RGB"))
        except Exception:
            return {"dims_gap": MISSING, "block_err": MISSING, "rms_err": MISSING,
                    "unanswered": 1.0}
        frame = self._ref(item)
        gap = abs(answer.shape[1] - frame.shape[1]) + abs(answer.shape[0] - frame.shape[0])
        if gap:
            return {"dims_gap": float(gap), "block_err": MISSING, "rms_err": MISSING}
        block, rms = block_and_rms(answer, frame)
        return {"dims_gap": 0.0, "block_err": block, "rms_err": rms}

    def judge(self, answers: Dict[Tuple[int, str], bytes], unanswered: int = 0,
              threads: int = 4) -> Dict[str, Any]:
        """All distinct answers -> ``{"correct", "numbers": {name: {value, limit}}}``."""
        keys = sorted(answers)
        with ThreadPoolExecutor(max_workers=threads) as pool:
            # one render per original, in parallel, then the answers
            list(pool.map(self._ref, sorted({k[0] for k in keys})))
            verdicts = list(pool.map(lambda k: self.judge_one(k[0], answers[k]), keys))
        numbers: Dict[str, Dict[str, float]] = {}
        for name in ("dims_gap", "block_err"):
            values = [v.get(name, 0.0) for v in verdicts] or [MISSING]
            numbers[name] = {"value": float(max(values)), "limit": float(self.limits[name])}
        lost = float(unanswered) + sum(v.get("unanswered", 0.0) for v in verdicts)
        if not keys:
            lost += 1.0  # a window with no answer at all proves nothing
        numbers["unanswered"] = {"value": lost, "limit": 0.0}
        correct = all(n["value"] <= n["limit"] for n in numbers.values())
        rms = max([v.get("rms_err", 0.0) for v in verdicts] or [MISSING])
        return {"correct": bool(correct), "numbers": numbers, "answers": len(keys),
                "rms_err_not_compared": float(rms)}


def format_numbers(numbers: Dict[str, Dict[str, float]]) -> str:
    return " ".join(f"{k}={v['value']:.6g}(limit {v['limit']:.6g})" for k, v in numbers.items())
