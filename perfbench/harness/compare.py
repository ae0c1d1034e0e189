"""The comparison that decides ``correct``: what is common to every
configuration. The render and the numbers are the configuration's reference's
(``references/<name>.py``, handed in as a module).

Every distinct answer the window produced (one per source image and output
digest, so that identical bytes are judged once and no answer is skipped) is
decoded and handed, with the other answers to the same original, to the
reference's ``judge_original``, which renders that original once and returns
each answer's numbers. Each number compared is the worst over the answers and
has a limit of its own from the configuration's file; a number that an answer
could not be read for fails its limit. Beside the reference's numbers:

``unanswered`` calls that never answered, and answers that would not decode.

``rms_err``, where the reference returns it, is printed beside them and not
compared.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from types import SimpleNamespace
from typing import Any, Dict, List, Tuple

import numpy as np

from . import plain

MISSING = 1.0e9   # a number that could not be read fails its limit


class Judge:
    """Judges a run's answers against the reference's render of each
    original, made once."""

    def __init__(self, bound: SimpleNamespace, corpus: List[bytes]) -> None:
        """``bound``: what the configuration names, as ``manifest.bind`` loads it."""
        self.reference, self.options, self.limits = bound.reference, bound.options, bound.limits
        self._corpus = corpus

    def _judge_original(self, item: int, answers: List[bytes]) -> Tuple[List[Dict[str, float]], int]:
        decoded: List[np.ndarray] = []
        for data in answers:
            try:
                decoded.append(plain.decode(data))
            except Exception:   # whatever Pillow makes of damaged bytes: no answer
                pass
        verdicts = self.reference.judge_original(self._corpus[item], decoded, self.options)
        return verdicts, len(answers) - len(decoded)

    def judge(self, answers: Dict[Tuple[int, str], bytes], unanswered: int = 0,
              threads: int = 4) -> Dict[str, Any]:
        """All distinct answers -> ``{"correct", "numbers": {name: {value, limit}}}``."""
        by_item: Dict[int, List[bytes]] = {}
        for key in sorted(answers):
            by_item.setdefault(key[0], []).append(answers[key])
        with ThreadPoolExecutor(max_workers=threads) as pool:
            judged = list(pool.map(lambda kv: self._judge_original(*kv), by_item.items()))
        verdicts = [v for vs, _ in judged for v in vs]
        numbers: Dict[str, Dict[str, float]] = {}
        for name in self.reference.NUMBERS:
            values = [v.get(name, MISSING) for v in verdicts] or [MISSING]
            numbers[name] = {"value": float(max(values)), "limit": float(self.limits[name])}
        lost = float(unanswered) + sum(n for _, n in judged)
        if not answers:
            lost += 1.0  # a window with no answer at all proves nothing
        numbers["unanswered"] = {"value": lost, "limit": 0.0}
        correct = all(n["value"] <= n["limit"] for n in numbers.values())
        rms = max([v.get("rms_err", MISSING) for v in verdicts] or [MISSING])
        return {"correct": bool(correct), "numbers": numbers, "answers": len(answers),
                "rms_err_not_compared": float(rms)}


def format_numbers(numbers: Dict[str, Dict[str, float]]) -> str:
    return " ".join(f"{k}={v['value']:.6g}(limit {v['limit']:.6g})" for k, v in numbers.items())
