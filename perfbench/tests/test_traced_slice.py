"""Where a traced run puts the profiler (``cell.trace_one_launch``): the slice
opens with the window and ends on the full launch's read-back, however long
the fill is. Against a stand-in for the program (callers that decode, meet in
one full launch, hold the device and are answered one after another) and a
stubbed profiler. No chip, no program."""

import threading
import time

import jax
import pytest

from perfbench.harness import cell
from perfbench.harness.traffic import ClosedLoop, Record

CALLERS, CYCLE, STAGGER = 8, 1.0, 0.01


class StandIn:
    """One full launch at a time: each call decodes for ``fill`` seconds, the
    launch is staged when every caller's frame is in, holds the device for
    ``hold`` seconds and answers its callers ``STAGGER`` apart, so a cycle
    lasts ``fill + hold + 0.07`` s and the launch is staged ``fill`` seconds
    after the burst. ``lone`` seconds, where given, is how long the first call
    of the second cycle takes, alone: a lone launch. ``wedge`` holds every
    call of the second cycle until it is set."""

    def __init__(self, fill, lone=None, wedge=None):
        self.fill, self.hold = fill, CYCLE - fill - STAGGER * (CALLERS - 1)
        self.lone, self.wedge = lone, wedge
        self.barrier = threading.Barrier(CALLERS)
        self.lock = threading.Lock()
        self.calls = 0
        self.staged, self.read_back = [], []

    def call(self, item):
        with self.lock:
            number, self.calls = self.calls, self.calls + 1
        if number == CALLERS and self.lone is not None:
            time.sleep(self.lone)
            return "lone"
        if number >= CALLERS and self.wedge is not None:
            self.wedge.wait(20)
        time.sleep(self.fill)
        place = self.barrier.wait(timeout=20)
        if place == 0:
            self.staged.append(time.perf_counter())
        time.sleep(self.hold)
        if place == 0:
            self.read_back.append(time.perf_counter())
        time.sleep(STAGGER * place)
        return place


@pytest.fixture
def profiler(monkeypatch):
    """``start_trace`` and ``stop_trace`` that only note when they were
    called."""
    at = {}
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda log_dir, **kw: at.setdefault("on", time.perf_counter()))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: at.setdefault("off", time.perf_counter()))
    return at


def traced(program, close_after=30.0):
    """The pre-roll, then ``trace_one_launch`` as ``run_cell`` calls it;
    returns the burst's clock, the pre-roll's cycle and the slice's seconds."""
    mix = {"loop": "closed", "in_flight": CALLERS, "order": "shuffled_cycle"}
    loop = ClosedLoop(mix, 4, 7, program.call)
    t = time.perf_counter()
    loop.start()
    try:
        t_burst = loop.wait_first_sent(CALLERS, 10)
        cycle = t_burst - t
        trace_dir, slice_s, took = cell.trace_one_launch(
            loop, t_burst, cycle, t_burst + close_after, 0.25 * cycle)
        assert set(took) == {"start_trace_s", "stop_trace_s"} and trace_dir
    finally:
        loop.stop()
        if program.wedge is not None:
            program.wedge.set()
        program.barrier.abort()
        assert loop.drain(10) == 0
    return t_burst, cycle, slice_s


@pytest.mark.parametrize("share", [0.05, 0.5, 0.9])
def test_a_launch_staged_early_or_late_in_the_cycle_is_inside_the_slice(profiler, share):
    program = StandIn(fill=share * CYCLE)
    t_burst, cycle, slice_s = traced(program)
    assert cycle == pytest.approx(CYCLE, abs=0.3)
    # the slice opened with the window, before the second launch was staged,
    # and ended half a second after its read-back had answered two callers
    assert profiler["on"] - t_burst < 0.2
    assert program.staged[1] - t_burst == pytest.approx(share * CYCLE, abs=0.15)
    assert profiler["on"] < program.staged[1] and program.read_back[1] < profiler["off"]
    assert profiler["off"] - program.read_back[1] == pytest.approx(0.5 + STAGGER, abs=0.15)
    assert slice_s == pytest.approx(profiler["off"] - profiler["on"], abs=0.01)


def test_a_lone_answer_does_not_end_the_slice(profiler):
    # the first caller answered sends a frame that goes alone and is answered
    # 0.3 s after the opening; the full launch waits for that caller's next
    program = StandIn(fill=0.2, lone=0.3 + STAGGER * (CALLERS - 1))
    t_burst, cycle, slice_s = traced(program)
    assert program.staged[1] - t_burst == pytest.approx(0.5, abs=0.15)
    assert profiler["on"] < program.staged[1] and program.read_back[1] < profiler["off"]
    assert profiler["off"] - program.read_back[1] == pytest.approx(0.5, abs=0.15)
    assert slice_s > cycle            # a cycle with a lone launch is longer than the pre-roll's


def test_no_answer_ends_the_slice_at_the_cap(profiler):
    program = StandIn(fill=0.2, wedge=threading.Event())
    _, cycle, slice_s = traced(program)
    assert slice_s == pytest.approx(cell.SLICE_CAP_CYCLES * cycle, abs=0.15)


def test_a_window_about_to_close_ends_the_slice_a_second_before(profiler):
    program = StandIn(fill=0.5)
    t_burst, _, slice_s = traced(program, close_after=1.4)
    assert profiler["off"] - t_burst == pytest.approx(0.4, abs=0.15)
    assert len(program.read_back) == 1       # the second launch was not waited for


def test_an_answer_counts_for_the_new_cycle_by_when_its_call_was_sent():
    """After a lone launch in the pre-roll its caller's second call rides the
    pre-roll's full launch and can answer after the window has opened: that
    answer, with one lone answer of the new cycle, does not make two."""
    loop = ClosedLoop({"loop": "closed", "in_flight": 4, "order": "shuffled_cycle"}, 4, 7, lambda i: None)
    # the pre-roll: a lone answer at 3.0, the burst 10.0 .. 10.06; its caller's
    # next call, sent at 3.0, answers at 10.2, after the opening at 10.06
    loop._records = [Record(0, 0, 0.0, 3.0, True)] + \
        [Record(k, k, 0.0, 10.0 + 0.02 * k, True) for k in (1, 2, 3)] + \
        [Record(4, 0, 3.0, 10.2, True)]
    began = loop.burst_began(10.06, 2.5)
    assert began == pytest.approx(10.02)
    assert loop.burst_began(10.06, 20.0) == 3.0      # no pause that long: one burst
    loop._records.append(Record(5, 1, 10.02, 13.0, True))         # a lone launch of the new cycle
    assert loop.wait_answers(2, 10.06, began, 0.0) is None
    assert loop.wait_answers(2, 10.06, float("-inf"), 0.0) == 13.0  # the plain rule would have ended
    loop._records += [Record(6, 2, 10.04, 17.0, True), Record(7, 3, 10.06, 17.02, True)]
    assert loop.wait_answers(2, 10.06, began, 0.0) == 17.0
