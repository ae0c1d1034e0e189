"""A transform group owns its launch's padded host block, and ``submit``
copies each member into its slot on the caller's thread while the launch
is still filling (PR 32; runtime/batcher.py ``_Group``, ``_copy_in``,
``_assemble``). Pinned here: the arrays handed to ``stage`` are the same
bytes on the fast path and on the copying path; a copy in flight holds its
group back without a busy wait; every case that is not "the block's slots
0..n-1 in order" falls back to the copying path and answers correctly; a
copy that raises fails its member alone; the counters and the histogram
read what happened. Since PR 38 a block whose launch has run is the
controller's spare and the next group of its shape takes it (``_take_block``,
``_keep_block``): section 5 pins that a launch on a kept block stages the
bytes a fresh one would, that what it leaves in slots no member owns
reaches no answer, when the block changes hands, how many are held, and
what ``flyimg_batch_blocks_total`` counts. Small shapes, CPU, no upper
bound on any time."""

import sys
import threading
import time
from types import SimpleNamespace

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


def test_an_answered_member_lets_its_frame_go_before_the_next_is_answered():
    """Nothing recovers an answered member, so its frame is not held while
    the launch's other members are answered; a member nobody answers
    keeps it."""
    ctl = _Parked(max_batch=3, deadline_ms=0.0, lone_flush=False)
    try:
        members = _members("w_120,h_90,c_1", [(320, 240)] * 3)
        futures = [ctl.submit(image, plan) for image, plan, _ in members]
        with ctl._lock:
            group = ctl._pop_ready_group()
        seen = []
        futures[1].add_done_callback(
            lambda _f: seen.append([m.image is None for m in group.members]))
        futures[2].cancel()  # its caller gave up
        outputs = [run_plan(image, plan) for image, plan, _ in members]
        launch = batcher_mod._Launch(0, group.members)
        ctl._resolve_members(group, group.members, outputs, launch)
        assert seen == [[True, False, False]]
        assert [m.image is None for m in group.members] == [True, True, False]
        for i in (0, 1):
            np.testing.assert_array_equal(futures[i].result(timeout=0), outputs[i])
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

    def blocked_at_submit(frames, k, image, edge, *stale):
        if threading.current_thread().name == "submitter":
            entered.set()
            assert gate.wait(timeout=30)
        real(frames, k, image, edge, *stale)

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


def _refuse_the_marked_frame_once(monkeypatch):
    """``_fill_slot`` raises for the first frame whose first sample is 251;
    returns the list that gets the slot it refused."""
    real = batcher_mod._fill_slot
    marked = []

    def refuses_the_marked_frame_once(frames, k, image, edge, *stale):
        if image[0, 0, 0] == 251 and not marked:
            marked.append(k)
            raise MemoryError("no page for the slot")
        real(frames, k, image, edge, *stale)

    monkeypatch.setattr(batcher_mod, "_fill_slot", refuses_the_marked_frame_once)
    return marked


def test_a_copy_that_raises_fails_that_member_alone(monkeypatch):
    marked = _refuse_the_marked_frame_once(monkeypatch)
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


# ---------------------------------------------------------------------------
# 5. a block kept from launch to launch (PR 38)


def _blocks(metrics):
    """(groups made with a kept block, with a fresh one)."""
    summary = metrics.summary()
    return (int(summary.get('flyimg_batch_blocks_total{from="kept"}', 0)),
            int(summary.get('flyimg_batch_blocks_total{from="fresh"}', 0)))


def _parked_launch(ctl, members):
    """Submit, pop and assemble ``members`` on a parked controller as a
    launch would: (the popped group, the arrays handed to ``stage``)."""
    for image, plan, window in members:
        ctl.submit(image, plan, src_window=window)
    with ctl._lock:
        ready = ctl._pop_ready_group()
    assert [m.slot for m in ready.members] == list(range(len(members)))
    _, arrays = ctl._assemble(ready, ready.members, ready.block)
    assert np.shares_memory(arrays[0], ready.block)
    return ready, arrays


def _has_run(ctl, block):
    """What ``_await_launch`` does with a launch's block once the output is
    ready (``_keep_block`` touches nothing of the launch but ``block``)."""
    launch = SimpleNamespace(block=block)
    ctl._keep_block(launch)
    assert launch.block is None


# name -> (options, first launch's sizes, second launch's sizes, max_batch):
# every frame of the second launch is smaller than the one its slot held
_SECOND_LAUNCHES = {
    # bucket 256 x 384; a pad slot (3 members of 4) repeats the last member
    "resample_bucket": ("w_120,h_90,c_1", [(380, 250)] * 4,
                        [(300, 200), (290, 230), (310, 140)], 4),
    "resample_bucket_lone_launch": ("w_120,h_90,c_1", [(380, 250)] * 4,
                                    [(300, 200)], 4),
    # bucket 256 x 256, padding replicated from the frame's edge; the last
    # frame is the bucket's own size (no padding: the slot is the frame)
    "edge_replicated_bucket": ("blr_2x1", [(250, 190), (240, 250)],
                               [(200, 150), (256, 256)], 2),
    "edge_replicated_bucket_lone_launch": ("blr_2x1", [(250, 190), (240, 250)],
                                           [(130, 140)], 2),
}


@pytest.mark.parametrize("name", sorted(_SECOND_LAUNCHES))
def test_a_second_launch_on_a_kept_block_stages_the_same_bytes_as_on_a_fresh_one(name):
    options, first_sizes, second_sizes, max_batch = _SECOND_LAUNCHES[name]
    ctl = _Parked(max_batch=max_batch, deadline_ms=0.0, lone_flush=False)
    try:
        first, _ = _parked_launch(ctl, _members(options, first_sizes))
        assert _blocks(ctl.metrics) == (0, 1)
        block = first.block
        _has_run(ctl, block)
        assert ctl._spare_blocks == [block]
        members = [(255 - image, plan, window)  # no zeros to hide behind
                   for image, plan, window in _members(options, second_sizes)]
        second, kept = _parked_launch(ctl, members)
        assert second.block is block
        assert ctl._spare_blocks == [] and _blocks(ctl.metrics) == (1, 1)
        batch, fresh = ctl._assemble(second, second.members)
        assert not np.shares_memory(fresh[0], block)
        assert kept[0].shape == fresh[0].shape == (batch, *block.shape[1:])
        for early, late in zip(kept, fresh):
            assert early.tobytes() == late.tobytes()
        # the slots beyond the padded batch hold the first launch's pixels,
        # and no array of this launch reaches them
        if batch < len(block):
            h, w = first.members[batch].image.shape[:2]
            assert np.array_equal(block[batch, :h, :w], first.members[batch].image)
            assert kept[0].shape[0] == batch
    finally:
        _close(ctl)


def test_a_fresh_blocks_slots_are_not_cleared(monkeypatch):
    """A fresh ``np.zeros`` pays for the frame alone: ``_fill_slot`` is told
    that the block is stale only where it is."""
    seen = []
    real = batcher_mod._fill_slot

    def noting(frames, k, image, edge, stale=False):
        seen.append(stale)
        real(frames, k, image, edge, stale)

    monkeypatch.setattr(batcher_mod, "_fill_slot", noting)
    ctl = _Parked(max_batch=2, deadline_ms=0.0, lone_flush=False)
    try:
        first, _ = _parked_launch(ctl, _members("w_120,h_90,c_1", [(320, 240)] * 2))
        _has_run(ctl, first.block)
        _parked_launch(ctl, _members("w_120,h_90,c_1", [(300, 200)]))
        assert seen == [False, False, True]
    finally:
        _close(ctl)


def _launch_and_check(ctl, members, expire=False):
    futures = [ctl.submit(image, plan) for image, plan, _ in members]
    if expire:
        _expire_deadline(ctl)
    _check_answers(members, futures)


def _bright(members):
    """Other pixels in the same shapes: a later launch's own frames."""
    return [(255 - image, plan, window) for image, plan, window in members]


def test_stale_pad_slots_change_no_real_members_answer():
    """A full launch, then three members and then one on the block it left:
    the slots they do not own hold the full launch's pixels, and every
    answer is the single-image path's for its own frame."""
    ctl = _waiting_ctl()
    try:
        full, _ = _submit_all(ctl)
        _check_answers(full, _)
        (block,) = ctl._spare_blocks
        assert np.array_equal(block[3, :230, :290], full[3][0])
        three = _bright(_members("w_120,h_90,c_1", [(280, 210), (270, 140), (300, 220)]))
        _launch_and_check(ctl, three, expire=True)
        assert ctl._spare_blocks[0] is block
        lone = _members("w_120,h_90,c_1", [(260, 130)])
        _launch_and_check(ctl, lone, expire=True)
        assert ctl._spare_blocks[0] is block
        # slot 1 still holds the launch of three's frame
        assert np.array_equal(block[1, :140, :270], three[1][0])
        assert _blocks(ctl.metrics) == (2, 1)
        assert _counts(ctl.metrics) == (8, 8, 0)
    finally:
        ctl.close()


def test_a_group_of_another_shape_gets_a_fresh_block_and_at_most_two_are_held():
    ctl = _waiting_ctl(max_batch=2)
    try:
        shapes = {"a": (320, 240), "b": (640, 480), "c": (900, 200)}
        blocks = {}

        def launch(name, seed):
            w, h = shapes[name]
            members = [(make_test_image(w, h, seed=seed + k),
                        _plan("w_120,h_90,c_1", w, h), None) for k in range(2)]
            futures = [ctl.submit(image, plan) for image, plan, _ in members]
            with ctl._lock:
                held = list(ctl._spare_blocks)
            _check_answers(members, futures)
            return held

        launch("a", 0)
        (blocks["a"],) = ctl._spare_blocks
        # another shape: a fresh block, and the spare stays where it is
        assert [b is blocks["a"] for b in launch("b", 10)] == [True]
        assert _blocks(ctl.metrics) == (0, 2)
        assert ctl._spare_blocks[1] is blocks["a"]
        blocks["b"] = ctl._spare_blocks[0]
        assert blocks["b"].shape == (2, 512, 640, 3)
        # a third shape: fresh again; when it comes back the oldest is let go
        assert len(launch("c", 20)) == 2
        assert len(ctl._spare_blocks) == 2
        assert ctl._spare_blocks[1] is blocks["b"]
        assert ctl._spare_blocks[0].shape == (2, 256, 1024, 3)
        # the shape that was let go starts fresh, one that is held does not
        launch("a", 30)
        assert _blocks(ctl.metrics) == (0, 4)
        assert launch("c", 40) == [ctl._spare_blocks[1]]
        assert _blocks(ctl.metrics) == (1, 4)
    finally:
        ctl.close()
    assert ctl._spare_blocks == []
    # a launch that ends after the close hands nothing on
    _has_run(ctl, np.zeros((2, 8, 8, 3), np.uint8))
    assert ctl._spare_blocks == []


def test_the_block_is_not_handed_on_before_the_launchs_output_is_ready(monkeypatch):
    """On the CPU backend the staged inputs may BE the block until the
    program has run: while the output is held back the controller has no
    spare, and a group made meanwhile gets a fresh block."""
    import jax

    gate, entered = threading.Event(), threading.Event()
    real = jax.block_until_ready

    def held_output(x):
        # the staged inputs are a list; the output is one array
        if not isinstance(x, (list, tuple)) and not entered.is_set():
            entered.set()
            assert gate.wait(timeout=60)
        return real(x)

    monkeypatch.setattr(batcher_mod.jax, "block_until_ready", held_output)
    ctl = _waiting_ctl(max_batch=2)
    try:
        first = _members("w_120,h_90,c_1", [(320, 240)] * 2)
        futures = [ctl.submit(image, plan) for image, plan, _ in first]
        assert entered.wait(timeout=120)
        with ctl._lock:
            assert ctl._spare_blocks == []
        assert not any(f.done() for f in futures)
        second = _bright(first)
        later = [ctl.submit(image, plan) for image, plan, _ in second]
        assert _blocks(ctl.metrics) == (0, 2)
        gate.set()
        _check_answers(first, futures)
        _check_answers(second, later)
        assert len(ctl._spare_blocks) == 2
        assert ctl._spare_blocks[0] is not ctl._spare_blocks[1]
    finally:
        gate.set()
        ctl.close()


def _copy_raises(monkeypatch):
    _refuse_the_marked_frame_once(monkeypatch)
    return _waiting_ctl(), 1


def _execute_fault(monkeypatch):
    injector = faults.install(faults.FaultInjector())
    injector.plan("batcher.execute", faults.fail_n_then_succeed(
        1, lambda: ValueError("the launch was refused")))
    return _waiting_ctl(), None


def _drain_fault(monkeypatch):
    injector = faults.install(faults.FaultInjector())
    injector.plan("batcher.drain", faults.fail_n_then_succeed(
        1, lambda: ValueError("the read-back was refused")))
    return _waiting_ctl(), None


def _presplit(monkeypatch):
    metrics = MetricsRegistry()
    return _waiting_ctl(
        metrics=metrics, governor=_CapAtTwo(enabled=True, metrics=metrics)), None


# name -> (what happens to the first launch, blocks (kept, fresh) after the
# two launches that follow it)
_EVENTS = {
    # the group let go of its block; nothing came back
    "a_copy_that_raises": (_copy_raises, (1, 2)),
    # the failed launch let its block go; the halves assembled their own
    "a_bisect_after_an_execute_fault": (_execute_fault, (1, 2)),
    "a_bisect_after_a_drain_fault": (_drain_fault, (1, 2)),
    # the prefix launched from the block and handed it on
    "a_governor_presplit": (_presplit, (2, 1)),
}


@pytest.mark.parametrize("name", sorted(_EVENTS))
def test_after_a_launch_that_went_wrong_the_next_launchs_answers_are_its_own(name, monkeypatch):
    make, blocks = _EVENTS[name]
    ctl, poisoned = make(monkeypatch)
    try:
        members = _members("w_120,h_90,c_1", [(320, 240), (300, 200), (310, 250), (290, 230)])
        if poisoned is not None:
            members[poisoned][0][0, 0, 0] = 251
        futures = [ctl.submit(image, plan) for image, plan, _ in members]
        if poisoned is not None:
            # that member's copy raised; the three left launch without a block
            with pytest.raises(MemoryError):
                futures.pop(poisoned).result(timeout=30)
            members.pop(poisoned)
        _expire_deadline(ctl)
        _check_answers(members, futures)
        for later in (_bright(members), members[::-1]):
            futures = [ctl.submit(image, plan) for image, plan, _ in later]
            _expire_deadline(ctl)
            _check_answers(later, futures)
        assert _blocks(ctl.metrics) == blocks
        assert ctl.admission.pending == 0
    finally:
        ctl.close()


def test_kept_block_share_reads_the_counter_the_controller_keeps():
    """``perfbench/metrics/kept_block_share.json`` through the benchmark's
    own reader, on a controller's registry as the harness scrapes it."""
    from perfbench.harness import manifest
    from perfbench.harness.system import parse_prometheus

    doc = manifest.load_manifest()
    entry = next(m for m in doc["per_layer"] if m["name"] == "kept_block_share")
    assert entry["layer"] == "batcher" and entry["moves"] == "images_per_s"
    spec = manifest.load_metric("kept_block_share")
    # every cell reads it: those of this entry, and those that came after
    # its list was accepted through a twin of the same counter
    # (`kept_block_share.png16` for the 16-bit cell)
    twins = [m for m in doc["per_layer"] if m["name"].startswith("kept_block_share.")]
    assert twins
    covered = entry["workloads"] + [c for t in twins for c in t["workloads"]]
    assert sorted(covered) == sorted(c["name"] for c in doc["workloads"])
    for twin in twins:
        assert (twin["layer"], twin["moves"]) == (entry["layer"], entry["moves"])
        twin_spec = manifest.load_metric(twin["name"])
        assert (twin_spec["reader"], twin_spec["args"]) == (spec["reader"], spec["args"])
    read = manifest.load_reader(spec["reader"])
    ctl = _waiting_ctl()
    try:
        before = parse_prometheus(ctl.metrics.render_prometheus())
        members, futures = _submit_all(ctl)
        _check_answers(members, futures)
        first = parse_prometheus(ctl.metrics.render_prometheus())
        # one launch, on a fresh block: no block kept yet, nothing read
        assert first['flyimg_batch_blocks_total{from="fresh"}'] == 1.0
        assert read({"counters_before": before, "counters_after": first},
                    **spec["args"]) is None
        for _ in range(3):
            _launch_and_check(ctl, _bright(members))
        after = parse_prometheus(ctl.metrics.render_prometheus())
        assert read({"counters_before": before, "counters_after": after},
                    **spec["args"]) == pytest.approx(75.0)
        assert read({"counters_before": first, "counters_after": after},
                    **spec["args"]) == pytest.approx(100.0)
    finally:
        ctl.close()
    # the parent's program has no such counter: nothing read, nothing raised
    assert read({"counters_before": {}, "counters_after": {
        "flyimg_batches_total": 4.0}}, **spec["args"]) is None
