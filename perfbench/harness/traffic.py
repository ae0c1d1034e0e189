"""The one general load generator. A traffic mix is a data file of its
parameters (``traffic/<name>.json``):

``loop``            ``closed``: a fixed number of callers, each waiting for its
                    reply before it sends the next, as a job's workers do
``in_flight``       how many callers
``order``           ``shuffled_cycle``: the corpus in a seeded order, cycled,
                    so every seed offers the same set of sizes in another order
``preroll_images``  the pre-roll (set-up) ends when the first so many calls
                    sent have all answered; the time they take is the
                    harness's measure of a cycle. The window opens on the
                    last of those answers: at the end of a burst, as it closes
``burst_gap_cycle_share``, ``burst_cap_cycle_share``  how the window closes
                    (``close_at``): when the time is up nothing more is sent,
                    all that was sent is waited for, and the clock is read
                    after that wait. The callers move in step with a launch,
                    so a burst of answers that straddles the time-up is let
                    through whole (its callers send again, and the last launch
                    is a full one, not a part of one that waits out the
                    program's deadline): a caller answered after the time-up
                    sends again only where its answer belongs to a burst that
                    began before it, and not later than the second share of a
                    cycle after it. A burst begins with an answer before which
                    nothing answered for the first share of a cycle; but where
                    the burst so far has answered more than one caller and
                    fewer than ``in_flight``, and began less than the second
                    share of a cycle ago, the pause was the machine standing
                    still in the middle of it and the burst goes on (PR 34: a
                    stall of 5.9 s inside the straddling burst left 15 calls
                    to wait out the deadline as a launch of their own)
``drain_seconds``   how long to wait, after the time-up, for answers still due
``warm_launch_sizes``  padded launch sizes the loop can produce, to be warmed

A traced run takes nothing from the mix but the gap that tells two bursts
apart: its slice of the profiler is placed by the window's opening and by the
answers alone (``cell.trace_one_launch``).

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
    seq: int
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
        self._close: Optional[tuple] = None  # (time-up, gap, cap), seconds
        self._last_done = float("-inf")
        # the burst of answers in progress, once ``close_at`` has said what a
        # pause is: when it began and how many it has answered
        self._burst_began, self._burst_count = float("-inf"), 0
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
                seq = self._sent
                self._sent += 1
            sent = time.perf_counter()
            try:
                info = self._call(item)
                rec = Record(seq, item, sent, time.perf_counter(), True, info)
            except Exception as exc:  # a failed request is a result, not a crash
                rec = Record(seq, item, sent, time.perf_counter(), False,
                             error=f"{type(exc).__name__}: {exc}")
            with self._lock:
                self._records.append(rec)
                if self._close is not None:
                    up, gap, cap = self._close
                    stalled_inside = (1 < self._burst_count < self.in_flight
                                      and rec.done - self._burst_began < cap)
                    if rec.done - self._last_done >= gap and not stalled_inside:
                        self._burst_began, self._burst_count = rec.done, 0
                    self._burst_count += 1
                    if rec.done >= up and (self._burst_began >= up or rec.done >= up + cap):
                        self._stop = True
                self._last_done = max(self._last_done, rec.done)
                self._lock.notify_all()

    def start(self) -> None:
        for i in range(self.in_flight):
            t = threading.Thread(target=self._worker, name=f"bench-caller-{i}", daemon=True)
            self._threads.append(t)
            t.start()

    def wait_first_sent(self, count: int, timeout: float) -> float:
        """Block until the first ``count`` calls sent have all answered;
        returns the clock at the last of their answers (the window then opens
        on a completion, and no answer of the pre-roll's falls into it)."""
        deadline = time.perf_counter() + timeout
        with self._lock:
            while True:
                first = [r.done for r in self._records if r.seq < count]
                if len(first) >= count:
                    return max(first)
                left = deadline - time.perf_counter()
                if left <= 0:
                    raise TimeoutError(
                        f"pre-roll: {len(first)} of the first {count} calls answered in {timeout:.0f} s")
                self._lock.wait(timeout=left)

    def burst_began(self, end: float, gap: float) -> float:
        """The clock at the first answer of the burst of answers that ``end``
        closes: going back from ``end``, the last answer before which nothing
        answered for ``gap`` seconds or longer."""
        with self._lock:
            times = sorted((r.done for r in self._records if r.done <= end), reverse=True)
        began = end
        for done in times:
            if began - done >= gap:
                break
            began = done
        return began

    def wait_answers(self, count: int, done_after: float, sent_after: float,
                     timeout: float) -> Optional[float]:
        """Block until ``count`` calls sent at ``sent_after`` or later have
        answered later than ``done_after``; returns the clock at the last of
        those answers, or None where they do not come within ``timeout``."""
        deadline = time.perf_counter() + timeout
        with self._lock:
            while True:
                hits = sorted(r.done for r in self._records
                              if r.done > done_after and r.sent >= sent_after)
                if len(hits) >= count:
                    return hits[count - 1]
                left = deadline - time.perf_counter()
                if left <= 0:
                    return None
                self._lock.wait(timeout=left)

    def close_at(self, up: float, gap: float, cap: float) -> None:
        """The time is up at ``up``: see ``burst_gap_cycle_share`` above."""
        with self._lock:
            self._close = (up, gap, cap)
            # called where a burst has just ended: the next pause begins a new one
            self._burst_began, self._burst_count = self._last_done, self.in_flight

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
