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


def launch_of(n, stall_in_wave=None, stall=0.0):
    """``call(item)`` of a program that runs one full launch at a time. In
    wave ``stall_in_wave`` (the pre-roll's is 0) the machine stands still for
    ``stall`` seconds once three callers have been answered."""
    waves = []
    barrier = threading.Barrier(n, action=lambda: waves.append(len(waves)))

    def call(item):
        place = barrier.wait(timeout=10)
        held = stall if waves[-1] == stall_in_wave and place >= 3 else 0.0
        time.sleep(HOLD + STAGGER * place + held)
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


@pytest.mark.parametrize("into_the_cycle,waves", [
    (HOLD + 0.06, 3),              # the time is up inside the burst, before the machine stands still
    (HOLD + 0.25, 3),              # ... while it stands still: three answered, five to come
    (HOLD + 0.75, 3),              # ... in the quiet after that burst: the next is sent, and is the last
])
def test_a_stall_inside_a_burst_does_not_split_it(into_the_cycle, waves):
    """PR 34, on the chip: 15 of 64 answered, the machine stood still for
    5.9 s, the other 49 were taken for a new burst and sent nothing, and the
    15 waited out the program's deadline as a launch of their own."""
    mix = {"loop": "closed", "in_flight": CALLERS, "order": "shuffled_cycle"}
    loop = ClosedLoop(mix, 4, 7, launch_of(CALLERS, stall_in_wave=1, stall=0.3))
    loop.start()
    t_burst = loop.wait_first_sent(CALLERS, 10)
    loop.close_at(t_burst + into_the_cycle, gap=0.2, cap=2.0)
    assert loop.drain(15) == 0
    records = loop.all_records()
    assert all(r.ok for r in records), [r.error for r in records if not r.ok][:1]
    assert len(records) == waves * CALLERS


def test_answers_that_never_pause_end_at_the_cap():
    mix = {"loop": "closed", "in_flight": 4, "order": "shuffled_cycle"}
    loop = ClosedLoop(mix, 4, 7, lambda item: time.sleep(0.01))
    loop.start()
    t_burst = loop.wait_first_sent(4, 10)
    loop.close_at(t_burst + 0.2, gap=0.5, cap=0.3)
    assert loop.drain(10) == 0
    last = max(r.sent for r in loop.all_records())
    assert t_burst + 0.2 < last < t_burst + 0.2 + 0.3 + 0.1
