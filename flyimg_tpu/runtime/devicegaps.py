"""What the device controller was doing while no transform launch ran.

``BatchController`` (runtime/batcher.py) tells this account each time one
of its transform launches moves from phase to phase, each time its queue
of transform members turns empty or not, and each time an aux runner
starts or ends on its executor; the account keeps no clock of its own but
the instant of each of those moves. From the controller's first ``run`` on,
every interval in which no launch's ``run`` lap is open is a gap, and each
gap is split, in time order, by the first of these that holds:

``staging``   a popped launch is in ``h2d`` or ``dispatch``
``launch``    a popped launch is in ``assemble`` or ``slot_wait``
``d2h``       a launch is reading its output back
``resolve``   a launch is answering its members (and feeding its sinks)
``fill``      transform members are queued, none of them popped
``empty``     nothing is queued

into ``flyimg_device_gap_seconds_total{during=}``. The seconds of those
labels and the seconds in which some ``run`` was open (``run_s``, not
exported: ``flyimg_device_run_seconds`` sums the runs themselves) add up to
the time from the first run to the latest move (``first_run``,
``latest``). Of the gaps' seconds, those
in which an aux runner ran on the executor (smart-crop scoring, face
detection, pixelation) are counted once more, apart, as ``aux_overlap``.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Optional

__all__ = ["GapAccount", "LABELS"]

#: the gap labels in the order the first that holds is taken
LABELS = ("staging", "launch", "d2h", "resolve", "fill", "empty")
_PHASES = ("run", "staging", "launch", "d2h", "resolve")


class GapAccount:
    """The device controller's gap split (module docstring). Every method
    takes the instant of the move (``time.perf_counter()`` when not given),
    so a test can drive it with a timeline of its own."""

    def __init__(self, metrics=None) -> None:
        self._metrics = metrics
        self._lock = threading.Lock()
        self._open: Dict[str, int] = {phase: 0 for phase in _PHASES}
        self._queued = False
        self._aux = 0
        # the first run's start, and the latest move since (the end of
        # what is counted); None until the first run: nothing is counted
        self.first_run: Optional[float] = None
        self.latest: Optional[float] = None
        self.run_s = 0.0
        if metrics is not None:
            # every label is in the exposition from the start: a label no
            # second has gone to yet reads 0, not absent
            for label in LABELS + ("aux_overlap",):
                metrics.record_device_gap(label, 0.0)

    def _label(self) -> str:
        if self._open["run"]:
            return "run"
        for phase in ("staging", "launch", "d2h", "resolve"):
            if self._open[phase]:
                return phase
        return "fill" if self._queued else "empty"

    def _advance(self, now: float) -> None:
        """Put the seconds since the last move down to what held then. A
        move read on another thread a moment before the last one's took
        the lock adds nothing: the account never goes back in time."""
        if self.latest is None or now <= self.latest:
            return
        seconds = now - self.latest
        self.latest = now
        label = self._label()
        if label == "run":
            self.run_s += seconds
            return
        self._count(label, seconds)
        if self._aux:
            self._count("aux_overlap", seconds)

    def _count(self, label: str, seconds: float) -> None:
        if self._metrics is not None:
            self._metrics.record_device_gap(label, seconds)

    def move(self, old: Optional[str], new: Optional[str],
             now: Optional[float] = None) -> None:
        """A transform launch leaves phase ``old`` for ``new`` (either
        None: popped, done)."""
        now = time.perf_counter() if now is None else now
        with self._lock:
            self._advance(now)
            if old is not None:
                self._open[old] -= 1
            if new is not None:
                self._open[new] += 1
                if new == "run" and self.first_run is None:
                    self.first_run = self.latest = now

    def queued(self, queued: bool, now: Optional[float] = None) -> None:
        """Transform members are queued, none of them popped (or not)."""
        now = time.perf_counter() if now is None else now
        with self._lock:
            if queued != self._queued:
                self._advance(now)
                self._queued = queued

    def aux(self, delta: int, now: Optional[float] = None) -> None:
        """An aux runner starts (+1) or ends (-1) on the executor."""
        now = time.perf_counter() if now is None else now
        with self._lock:
            self._advance(now)
            self._aux += delta
