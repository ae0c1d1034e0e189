"""The one general load generator. A traffic mix is a data file of its
parameters (``traffic/<name>.json``):

``loop``            ``closed``: a fixed number of callers, each waiting for its
                    reply before it sends the next, as a job's workers do
``in_flight``       how many callers
``order``           ``shuffled_cycle``: the corpus in a seeded order, cycled,
                    so every seed offers the same set of sizes in another order
``preroll_images``  completions that end the pre-roll (set-up); the time they
                    take is the harness's measure of a cycle
``window_opens_cycle_share``  the window opens this share of a cycle after the
                    pre-roll's last answer: between bursts, where the callers
                    move in step, so that no burst straddles the window's edge
``drain_seconds``   how long to wait, after the window, for answers still due
``warm_launch_sizes``  padded launch sizes the loop can produce, to be warmed
``trace_at_cycle_share``  a traced run calls the profiler this share of a
                    cycle after the pre-roll's last answer, while the window's
                    launch is being staged (see ``cell.trace_one_launch``)

The generator knows nothing of images: it calls ``call(item)`` and records
when each call was sent, when it answered, and what ``record`` makes of the
answer.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

import numpy as np


@dataclass
class Record:
    item: int
    sent: float
    done: float
    ok: bool
    info: Any = None
    error: str = ""


class ClosedLoop:
    def __init__(self, mix: Dict[str, Any], n_items: int, seed: int,
                 call: Callable[[int], Any]) -> None:
        if mix.get("loop") != "closed":
            raise ValueError(f"this generator drives closed loops, not {mix.get('loop')!r}")
        if mix.get("order", "shuffled_cycle") != "shuffled_cycle":
            raise ValueError(f"unknown order {mix.get('order')!r}")
        self.mix = mix
        self.in_flight = int(mix["in_flight"])
        self._n_items = n_items
        self._rng = np.random.default_rng([int(seed), 0x7AFF1C])
        self._queue: List[int] = []
        self._call = call
        self._lock = threading.Condition()
        self._records: List[Record] = []
        self._sent = 0
        self._stop = False
        self._threads: List[threading.Thread] = []

    def _next_item(self) -> int:
        if not self._queue:
            self._queue = [int(i) for i in self._rng.permutation(self._n_items)]
        return self._queue.pop()

    def _worker(self) -> None:
        while True:
            with self._lock:
                if self._stop:
                    return
                item = self._next_item()
                self._sent += 1
            sent = time.perf_counter()
            try:
                info = self._call(item)
                rec = Record(item, sent, time.perf_counter(), True, info)
            except Exception as exc:  # a failed request is a result, not a crash
                rec = Record(item, sent, time.perf_counter(), False,
                             error=f"{type(exc).__name__}: {exc}")
            with self._lock:
                self._records.append(rec)
                self._lock.notify_all()

    def start(self) -> None:
        for i in range(self.in_flight):
            t = threading.Thread(target=self._worker, name=f"bench-caller-{i}", daemon=True)
            self._threads.append(t)
            t.start()

    def wait_completed(self, count: int, timeout: float) -> float:
        """Block until ``count`` calls have answered; returns the clock at the
        answer that made it so (the window then opens on a completion)."""
        deadline = time.perf_counter() + timeout
        with self._lock:
            while len(self._records) < count:
                left = deadline - time.perf_counter()
                if left <= 0:
                    raise TimeoutError(
                        f"pre-roll: {len(self._records)} of {count} answers in {timeout:.0f} s")
                self._lock.wait(timeout=left)
            return self._records[count - 1].done

    def wait_answer_after(self, start: float, timeout: float) -> Optional[float]:
        """Block until a call answers later than ``start``; returns the clock
        at that answer, or None where none comes within ``timeout``."""
        deadline = time.perf_counter() + timeout
        with self._lock:
            while True:
                for rec in reversed(self._records):
                    if rec.done > start:
                        return rec.done
                left = deadline - time.perf_counter()
                if left <= 0:
                    return None
                self._lock.wait(timeout=left)

    def stop(self) -> None:
        with self._lock:
            self._stop = True

    def drain(self, seconds: float) -> int:
        """Wait for the calls still out; returns how many never answered."""
        deadline = time.perf_counter() + seconds
        for t in self._threads:
            t.join(timeout=max(deadline - time.perf_counter(), 0.0))
        alive = sum(t.is_alive() for t in self._threads)
        return alive

    def window(self, start: float, end: float) -> List[Record]:
        """The calls that answered inside ``[start, end]``."""
        with self._lock:
            return [r for r in self._records if start <= r.done <= end]

    def all_records(self) -> List[Record]:
        with self._lock:
            return list(self._records)
