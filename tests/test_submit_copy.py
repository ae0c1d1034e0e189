"""A transform group owns its launch's padded host block, and ``submit``
copies each member into its slot on the caller's thread while the launch
is still filling (PR 32; runtime/batcher.py ``_Group``, ``_copy_in``,
``_assemble``). Pinned here: the arrays handed to ``stage`` are the same
bytes on the fast path and on the copying path; a copy in flight holds its
group back without a busy wait; every case that is not "the block's slots
0..n-1 in order" falls back to the copying path and answers correctly; a
copy that raises fails its member alone; the counters and the histogram
read what happened. Small shapes, CPU, no upper bound on any time."""

import sys
import threading
import time

import numpy as np
import pytest

from flyimg_tpu.ops.compose import run_plan
from flyimg_tpu.runtime import batcher as batcher_mod
from flyimg_tpu.runtime.batcher import BatchController
from flyimg_tpu.runtime.memgovernor import MemoryGovernor
from flyimg_tpu.runtime.metrics import MetricsRegistry
from flyimg_tpu.spec.options import OptionsBag
from flyimg_tpu.spec.plan import build_plan
from flyimg_tpu.testing import faults

from test_ops import make_test_image


@pytest.fixture(autouse=True)
def _clean_faults():
    yield
    faults.clear()


class _Parked(BatchController):
    """The executor is parked: the test thread owns the queue, so a pop
    and ``_assemble`` are called on exactly what a launch would get."""

    def _run(self):
        return


def _close(ctl):
    with ctl._lock:
        members = [m for g in ctl._groups.values() for m in g.members]
        ctl._groups.clear()
    for member in members:
        member.future.cancel()
    ctl.close()


def _plan(options, w, h):
    return build_plan(OptionsBag(options), w, h)


def _members(options, sizes):
    return [(make_test_image(w, h, seed=seed), _plan(options, w, h), None)
            for seed, (w, h) in enumerate(sizes)]


def _roi_members():
    """Two windows of one 640x480 source, as the ROI decode hands them
    over: the plan is the full frame's, the pixels a window of it."""
    full = make_test_image(640, 480, seed=7)
    plan = _plan("w_200,h_300,c_1", 640, 480)
    return [(np.ascontiguousarray(full[y:y + 400, x:x + 300]), plan, (x, y))
            for x, y in ((160, 40), (176, 48))]


def _counts(metrics):
    """(histogram observations, members at submit, members at assemble)."""
    summary = metrics.summary()
    text = metrics.render_prometheus()
    observed = 0
    for line in text.splitlines():
        if line.startswith("flyimg_batch_member_copy_seconds_count"):
            observed = int(float(line.split()[-1]))
    return (
        observed,
        int(summary.get('flyimg_batch_member_copies_total{at="submit"}', 0)),
        int(summary.get('flyimg_batch_member_copies_total{at="assemble"}', 0)),
    )


# ---------------------------------------------------------------------------
# 1. the same bytes on both paths

# name -> (members, max_batch, padded batch of the launch)
_LAUNCHES = {
    "full_launch": (lambda: _members("w_120,h_90,c_1", [(320, 240)] * 4), 4, 4),
    "lone_launch": (lambda: _members("w_120,h_90,c_1", [(320, 240)]), 4, 1),
    # the pad slot repeats the last member
    "deadline_pop_of_3": (
        lambda: _members("w_120,h_90,c_1", [(320, 240), (300, 200), (310, 250)]),
        4, 4),
    # a pixel-op-only bucket: the padding replicates the frame's edge
    "edge_replicated_bucket": (
        lambda: _members("blr_2x1", [(250, 190), (240, 180)]), 2, 2),
    "roi_members_with_src_window": (_roi_members, 2, 2),
}


@pytest.mark.parametrize("name", sorted(_LAUNCHES))
def test_stage_gets_the_same_bytes_on_the_fast_and_the_copying_path(name):
    make, max_batch, padded = _LAUNCHES[name]
    members = make()
    ctl = _Parked(max_batch=max_batch, deadline_ms=0.0, lone_flush=False)
    try:
        futures = [ctl.submit(image, plan, src_window=window)
                   for image, plan, window in members]
        assert all(hasattr(f, "copy_times") for f in futures)
        with ctl._lock:
            (queued,) = ctl._groups.values()
            assert queued.copying == 0
            assert queued.block.shape[0] == max_batch
            ready = ctl._pop_ready_group()
        assert [m.slot for m in ready.members] == list(range(len(members)))
        assert queued.block is None and not ctl._groups
        block = ready.block
        batch, fast = ctl._assemble(ready, ready.members, block)
        batch_copy, copied = ctl._assemble(ready, ready.members)
        assert batch == batch_copy == padded
        assert np.shares_memory(fast[0], block)
        assert fast[0].flags.c_contiguous
        assert not np.shares_memory(copied[0], block)
        for early, late in zip(fast, copied):
            assert early.dtype == late.dtype and early.shape == late.shape
            assert early.tobytes() == late.tobytes()
        # every member's own pixels are where the copying path puts them
        for i, (image, _, _) in enumerate(members):
            h, w = image.shape[:2]
            assert np.array_equal(fast[0][i, :h, :w], image)
        if name == "deadline_pop_of_3":
            assert np.array_equal(fast[0][3], fast[0][2])
            assert np.array_equal(fast[1][3], fast[1][2])
        if name == "edge_replicated_bucket":
            h, w = members[0][0].shape[:2]
            assert np.array_equal(fast[0][0, h:, :w],
                                  np.broadcast_to(members[0][0][-1:], (fast[0].shape[1] - h, w, 3)))
        if name == "roi_members_with_src_window":
            # the spans are shifted by the window's offset on both paths
            layout = batcher_mod.plan_layout(members[0][1])
            assert fast[3][0, 0] == pytest.approx(layout.span_x[0] - 160)
            assert fast[2][0, 0] == pytest.approx(layout.span_y[0] - 40)
        assert _counts(ctl.metrics) == (len(members), len(members), len(members))
    finally:
        _close(ctl)


def test_members_beyond_the_block_are_copied_at_their_own_pop():
    ctl = _Parked(max_batch=2, deadline_ms=0.0, lone_flush=False)
    try:
        futures = [ctl.submit(image, plan)
                   for image, plan, _ in _members("w_120,h_90,c_1", [(320, 240)] * 3)]
        assert [hasattr(f, "copy_times") for f in futures] == [True, True, False]
        with ctl._lock:
            first = ctl._pop_ready_group()
            (left,) = ctl._groups.values()
            assert left.block is None and [m.slot for m in left.members] == [None]
            second = ctl._pop_ready_group()
        assert first.block is not None and second.block is None
        _, fast = ctl._assemble(first, first.members, first.block)
        assert np.shares_memory(fast[0], first.block)
        _, late = ctl._assemble(second, second.members, second.block)
        assert np.array_equal(late[0][0, :240, :320], second.members[0].image)
        assert _counts(ctl.metrics) == (2, 2, 1)
    finally:
        _close(ctl)


# ---------------------------------------------------------------------------
# 2. a copy in flight


def test_a_group_with_a_copy_in_flight_is_not_popped_and_run_does_not_spin(monkeypatch):
    gate, entered = threading.Event(), threading.Event()
    real = batcher_mod._fill_slot

    def blocked_at_submit(frames, k, image, edge):
        if threading.current_thread().name == "submitter":
            entered.set()
            assert gate.wait(timeout=30)
        real(frames, k, image, edge)

    monkeypatch.setattr(batcher_mod, "_fill_slot", blocked_at_submit)
    # a deadline that is over at once: a predicate that let the deadline
    # stand for a group in flight would pop it, a timeout of 0 would spin
    ctl = BatchController(max_batch=4, deadline_ms=1.0)
    wakes = []
    next_deadline = ctl._next_deadline

    def counted():
        timeout = next_deadline()
        wakes.append(timeout)
        return timeout

    ctl._next_deadline = counted
    image = make_test_image(320, 240, seed=1)
    plan = _plan("w_120,h_90,c_1", 320, 240)
    box = {}
    submitter = threading.Thread(
        target=lambda: box.update(future=ctl.submit(image, plan)),
        name="submitter")
    try:
        submitter.start()
        assert entered.wait(timeout=30)
        before = len(wakes)
        with ctl._lock:
            ctl._lock.notify_all()  # wakes the executor: it looks, and parks again
        time.sleep(0.3)  # many deadlines long
        # parked on the condition, not polling it
        assert len(wakes) - before <= 2, wakes
        with ctl._lock:
            (group,) = ctl._groups.values()
            assert group.copying == 1 and len(group.members) == 1
            assert not ctl._ready_group()
            assert next_deadline() is None
        assert "future" not in box  # submit returns once the copy has landed
        gate.set()
        submitter.join(timeout=30)
        assert not submitter.is_alive()
        out = box["future"].result(timeout=120)
        np.testing.assert_array_equal(out, run_plan(image, plan))
        assert _counts(ctl.metrics) == (1, 1, 0)
    finally:
        gate.set()
        ctl.close()


# ---------------------------------------------------------------------------
# 3. what is not the block's prefix takes the copying path


class _CapAtTwo(MemoryGovernor):
    def member_cap(self, family, in_shape, requested, pad_fn):
        return 2 if requested > 2 else None


def _waiting_ctl(**over):
    """Nothing launches before the test says so."""
    kw = dict(max_batch=4, deadline_ms=60_000.0, lone_flush=False,
              metrics=MetricsRegistry())
    kw.update(over)
    ctl = BatchController(**kw)
    ctl._retry_policy.sleep = lambda _s: None
    return ctl


def _expire_deadline(ctl):
    """The test's clock: what is queued has waited out the deadline."""
    with ctl._lock:
        for group in ctl._groups.values():
            for member in group.members:
                member.enqueued_at -= 2 * ctl.deadline_s
        ctl._lock.notify_all()


def _submit_all(ctl, n=4):
    members = _members("w_120,h_90,c_1", [(320, 240), (300, 200), (310, 250), (290, 230)][:n])
    return members, [ctl.submit(image, plan) for image, plan, _ in members]


def _check_answers(members, futures):
    for (image, plan, _), future in zip(members, futures):
        np.testing.assert_array_equal(future.result(timeout=120),
                                      run_plan(image, plan))


def test_governor_presplit_keeps_the_block_for_the_prefix_and_copies_the_rest():
    metrics = MetricsRegistry()
    ctl = _waiting_ctl(metrics=metrics,
                       governor=_CapAtTwo(enabled=True, metrics=metrics))
    try:
        members, futures = _submit_all(ctl)  # the fourth fills the launch
        _check_answers(members[:2], futures[:2])
        assert metrics.summary()["flyimg_mem_presplits_total"] == 1
        assert _counts(metrics) == (4, 2, 0)
        _expire_deadline(ctl)                # the remainder's own pop
        _check_answers(members[2:], futures[2:])
        assert _counts(metrics) == (4, 2, 2)
    finally:
        ctl.close()


def test_bisect_after_an_execute_fault_assembles_from_the_members_own_arrays():
    injector = faults.install(faults.FaultInjector())
    injector.plan("batcher.execute", faults.fail_n_then_succeed(
        1, lambda: ValueError("the launch was refused")))
    ctl = _waiting_ctl()
    try:
        members, futures = _submit_all(ctl)
        _check_answers(members, futures)
        # the primary launch never assembled; two halves did, by copying
        assert _counts(ctl.metrics) == (4, 0, 4)
    finally:
        ctl.close()


def test_a_copy_that_raises_fails_that_member_alone(monkeypatch):
    real = batcher_mod._fill_slot
    marked = []

    def refuses_the_marked_frame_once(frames, k, image, edge):
        if image[0, 0, 0] == 251 and not marked:
            marked.append(k)
            raise MemoryError("no page for the slot")
        real(frames, k, image, edge)

    monkeypatch.setattr(batcher_mod, "_fill_slot", refuses_the_marked_frame_once)
    ctl = _waiting_ctl(max_batch=3, max_queue_depth=8)
    try:
        members = _members("w_120,h_90,c_1", [(320, 240)] * 4)
        members[1][0][0, 0, 0] = 251
        futures = [ctl.submit(image, plan) for image, plan, _ in members]
        assert marked == [1]
        with pytest.raises(MemoryError, match="no page for the slot"):
            futures[1].result(timeout=30)
        # the three left are a full launch; their group let go of its block
        ok = [0, 2, 3]
        _check_answers([members[i] for i in ok], [futures[i] for i in ok])
        assert _counts(ctl.metrics) == (1, 0, 3)
        assert ctl.admission.pending == 0
    finally:
        ctl.close()


# ---------------------------------------------------------------------------
# 4. the fault point, the counters, and many callers at once


@pytest.mark.parametrize("path", ["fast", "copying"])
def test_member_fault_point_fires_once_a_member_in_order(path):
    fired = []
    injector = faults.install(faults.FaultInjector())

    def note(index=None, image=None, **_ctx):
        fired.append((index, int(image[0, 0, 0])))
        return faults.PASS

    injector.plan("batcher.member", note)
    if path == "copying":
        injector.plan("batcher.execute", faults.fail_n_then_succeed(
            1, lambda: ConnectionError("transient device hiccup")))
    ctl = _waiting_ctl(max_batch=3)
    try:
        members = _members("w_120,h_90,c_1", [(320, 240)] * 3)
        for i, (image, _, _) in enumerate(members):
            image[0, 0, 0] = 10 + i
        futures = [ctl.submit(image, plan) for image, plan, _ in members]
        _check_answers(members, futures)
        assert fired == [(0, 10), (1, 11), (2, 12)]
        assert _counts(ctl.metrics) == ((3, 3, 0) if path == "fast" else (3, 0, 3))
    finally:
        ctl.close()


def test_many_callers_at_once_every_answer_is_its_own():
    """More submitters than cores, three program identities, launches of
    every size the deadline makes: each answer is the single-image path's
    for its own frame, and every member is counted once."""
    sizes = {"w_120,h_90,c_1": (320, 240), "w_64": (300, 200), "blr_2x1": (250, 190)}
    jobs = []
    for n in range(36):
        options = sorted(sizes)[n % 3]
        w, h = sizes[options]
        jobs.append((make_test_image(w - 4 * (n % 2), h, seed=n), options))
    expected = [run_plan(image, _plan(options, image.shape[1], image.shape[0]))
                for image, options in jobs]
    ctl = BatchController(max_batch=8, deadline_ms=5.0, metrics=MetricsRegistry())
    results = [None] * len(jobs)
    start = threading.Barrier(len(jobs))

    def caller(i):
        image, options = jobs[i]
        start.wait(timeout=60)
        results[i] = ctl.submit(
            image, _plan(options, image.shape[1], image.shape[0])).result(timeout=300)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    threads = [threading.Thread(target=caller, args=(i,)) for i in range(len(jobs))]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
        ctl.close()
    for got, want in zip(results, expected):
        np.testing.assert_array_equal(got, want)
    observed, early, late = _counts(ctl.metrics)
    assert early + late == len(jobs)
    assert observed >= early
    assert ctl.metrics.summary()["flyimg_images_processed_total"] == len(jobs)
