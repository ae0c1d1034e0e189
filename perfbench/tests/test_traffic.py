"""How the load generator closes a window: when the time is up nothing more is
sent, except that a burst of answers that straddles the time-up is let through
whole, so that callers who move in step with a launch never leave a part of
one behind. Against a stand-in for the program: a barrier that answers its
callers together, one after another. No chip, no program."""

import threading
import time

import pytest

from perfbench.harness.traffic import ClosedLoop

CALLERS, HOLD, STAGGER = 8, 0.4, 0.04   # a burst of answers lasts 0.28 s, 0.4 s after the last


def launch_of(n):
    """``call(item)`` of a program that runs one full launch at a time."""
    barrier = threading.Barrier(n)

    def call(item):
        place = barrier.wait(timeout=10)
        time.sleep(HOLD + STAGGER * place)
        return place
    return call


@pytest.mark.parametrize("into_the_cycle,sent_after_up", [
    (0.15, False),                 # the time is up between two bursts
    (HOLD + 0.14, True),           # the time is up inside a burst of answers
])
def test_the_window_closes_on_whole_bursts(into_the_cycle, sent_after_up):
    mix = {"loop": "closed", "in_flight": CALLERS, "order": "shuffled_cycle"}
    loop = ClosedLoop(mix, 4, 7, launch_of(CALLERS))
    loop.start()
    t_burst = loop.wait_first_sent(CALLERS, 10)
    up = t_burst + into_the_cycle
    loop.close_at(up, gap=0.2, cap=2.0)
    assert loop.drain(10) == 0
    records = loop.all_records()
    assert all(r.ok for r in records)
    # whole launches only: a part of one would have hung at the barrier
    assert len(records) % CALLERS == 0
    assert any(r.sent > up for r in records) == sent_after_up
    waves = len(records) // CALLERS
    assert waves == (3 if sent_after_up else 2)


def test_answers_that_never_pause_end_at_the_cap():
    mix = {"loop": "closed", "in_flight": 4, "order": "shuffled_cycle"}
    loop = ClosedLoop(mix, 4, 7, lambda item: time.sleep(0.01))
    loop.start()
    t_burst = loop.wait_first_sent(4, 10)
    loop.close_at(t_burst + 0.2, gap=0.5, cap=0.3)
    assert loop.drain(10) == 0
    last = max(r.sent for r in loop.all_records())
    assert t_burst + 0.2 < last < t_burst + 0.2 + 0.3 + 0.1
