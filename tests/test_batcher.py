"""BatchController tests: grouping, padding, correctness vs the single-image
path, deadline flush, mixed-aspect fit batching."""

import numpy as np
import pytest

from flyimg_tpu.ops.compose import run_plan
from flyimg_tpu.runtime.batcher import BatchController
from flyimg_tpu.spec.options import OptionsBag
from flyimg_tpu.spec.plan import build_plan

from test_ops import make_test_image


@pytest.fixture()
def controller():
    # lone_flush off: fixture users pin batch-FORMING behavior
    ctl = BatchController(max_batch=8, deadline_ms=30.0, lone_flush=False)
    yield ctl
    ctl.close()


def _plan(opts, w, h):
    return build_plan(OptionsBag(opts), w, h)


# plan family -> options. Every family the transform path groups and pads
# differently (runtime/batcher.py ``submit``): a static extent (crop-fill,
# extract + box, extent pad), a fit whose output is on a 64-px bucket edge
# and one whose output is bucketed and sliced, the three rotates (a
# multiple of 90, shape-bucketed dynamic alone and behind a resample),
# pixel ops that ride the input bucket, and the conv post-ops, whose pad
# rows must replicate the edge.
_FAMILIES = {
    "crop_fill": "w_100,h_75,c_1",
    "fit": "w_128",
    "fit_bucketed": "w_100",
    "extract": "e_1,p1x_20,p1y_10,p2x_150,p2y_120,w_64",
    "extent_pad_bg": "w_100,h_100,ett_120x120,bg_red",
    "r_90": "r_90",
    "r_30_dynamic": "r_30",
    "resize_rotate": "r_-45,w_96,h_96",
    "gray": "clsp_Gray",
    "blur": "blr_2x1",
    "sharpen": "sh_2x1",
    "unsharp": "unsh_2x1+1+0.05",
}
# dynamic rotate against run_plan's static one: see _assert_rotate_parity
_WITHIN_ONE_LEVEL = {"r_30_dynamic", "resize_rotate"}
# all three in the 256 x 256 input bucket; the square fills it to the edge
_SOURCES = {
    "landscape": (250, 180),
    "portrait": (180, 250),
    "square_on_bucket_edge": (256, 256),
}
# launch -> members; the checked member is the last one
_LAUNCHES = {"one_member": 1, "three_padded_to_four": 3}


@pytest.mark.parametrize("launch", _LAUNCHES)
@pytest.mark.parametrize("source", _SOURCES)
@pytest.mark.parametrize("family", _FAMILIES)
def test_batch_matches_single_path(family, source, launch):
    """What ``submit`` answers is what ``run_plan`` answers for the same
    image and plan, whatever the slot and however many pad slots ride
    along: byte for byte, but for the dynamic rotate."""
    n = _LAUNCHES[launch]
    w, h = _SOURCES[source]
    plan = _plan(_FAMILIES[family], w, h)
    images = [make_test_image(w, h, seed=seed) for seed in range(n)]
    # full at n, and a deadline no test waits out: exactly one launch
    ctl = BatchController(max_batch=n, deadline_ms=60_000.0, lone_flush=False)
    try:
        futures = [ctl.submit(image, plan) for image in images]
        out = futures[-1].result(timeout=120)
        summary = ctl.metrics.summary()
    finally:
        ctl.close()
    assert summary["flyimg_batches_total"] == 1
    assert summary["flyimg_images_processed_total"] == n
    assert summary["flyimg_batch_slots_total"] == {1: 1, 3: 4}[n]
    single = run_plan(images[-1], plan)
    assert out.shape == single.shape
    if family in _WITHIN_ONE_LEVEL:
        _assert_rotate_parity(out, single)
    else:
        np.testing.assert_array_equal(out, single)


def test_mixed_aspect_fit_shares_batch():
    # max_batch == number of submits + a long deadline makes the flush
    # trigger deterministically on batch-full, immune to slow cold starts
    # lone_flush off: this test pins GROUP-SHARING semantics, so the first
    # submit must wait for the other two instead of flushing solo
    ctl = BatchController(max_batch=3, deadline_ms=10_000.0, lone_flush=False)
    futures = []
    expected_shapes = []
    # different aspects, same 128-px input bucket (640 x 512)
    for i, (w, h) in enumerate([(600, 400), (600, 430), (600, 450)]):
        img = make_test_image(w, h, seed=10 + i)
        plan = _plan("w_300", w, h)
        expected_shapes.append((plan.resize_to[1], plan.resize_to[0], 3))
        futures.append(ctl.submit(img, plan))
    try:
        outs = [f.result(timeout=120) for f in futures]
    finally:
        ctl.close()
    stats = ctl.stats()
    for out, shape in zip(outs, expected_shapes):
        assert out.shape == shape
    # all three different aspects must have run as ONE batch
    assert stats["batches"] == 1
    assert stats["images"] == 3


def test_deadline_flush_single_item(controller):
    img = make_test_image(300, 200)
    fut = controller.submit(img, _plan("w_100", 300, 200))
    out = fut.result(timeout=120)
    assert out.shape == (67, 100, 3)


def test_mismatched_plan_rejected(controller):
    img = make_test_image(300, 200)
    with pytest.raises(ValueError):
        controller.submit(img, _plan("w_100", 999, 999))


def test_different_ops_in_different_groups(controller):
    img_a = make_test_image(300, 200, seed=1)
    img_b = make_test_image(300, 200, seed=2)
    fa = controller.submit(img_a, _plan("w_100,clsp_gray", 300, 200))
    fb = controller.submit(img_b, _plan("w_100", 300, 200))
    out_a = fa.result(timeout=120)
    out_b = fb.result(timeout=120)
    np.testing.assert_array_equal(out_a[..., 0], out_a[..., 1])
    assert not np.array_equal(out_b[..., 0], out_b[..., 1])


def test_mesh_sharded_batch_matches_unsharded():
    """A data-parallel mesh batcher returns the same pixels as the
    single-device path, with batches padded to the device count."""
    import jax

    from flyimg_tpu.parallel.mesh import make_mesh
    from flyimg_tpu.spec.options import OptionsBag
    from flyimg_tpu.spec.plan import build_plan

    mesh = make_mesh()  # 8 virtual CPU devices, axis 'data'
    plain = BatchController(max_batch=8, deadline_ms=5.0, lone_flush=False)
    sharded = BatchController(
        max_batch=8, deadline_ms=5.0, mesh=mesh, lone_flush=False
    )
    try:
        rng = np.random.default_rng(5)
        imgs = [
            rng.integers(0, 256, size=(96, 128, 3), dtype=np.uint8)
            for _ in range(8)
        ]
        plans = [build_plan(OptionsBag("w_64,h_48,c_1"), 128, 96) for _ in imgs]
        want = [f.result(timeout=60) for f in
                [plain.submit(im, pl) for im, pl in zip(imgs, plans)]]
        got = [f.result(timeout=60) for f in
               [sharded.submit(im, pl) for im, pl in zip(imgs, plans)]]
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)
    finally:
        plain.close()
        sharded.close()


def test_mesh_single_item_pads_to_device_count():
    from flyimg_tpu.parallel.mesh import make_mesh
    from flyimg_tpu.spec.options import OptionsBag
    from flyimg_tpu.spec.plan import build_plan

    mesh = make_mesh()
    ctrl = BatchController(max_batch=8, deadline_ms=2.0, mesh=mesh)
    try:
        rng = np.random.default_rng(6)
        img = rng.integers(0, 256, size=(64, 64, 3), dtype=np.uint8)
        plan = build_plan(OptionsBag("w_32,h_32,rz_1"), 64, 64)
        out = ctrl.submit(img, plan).result(timeout=60)
        assert out.shape == (32, 32, 3)
        stats = ctrl.stats()
        # 1 real image in an 8-slot (device-count) batch
        assert stats["images"] == 1
        assert stats["mean_occupancy"] == pytest.approx(1 / 8)
    finally:
        ctrl.close()


def test_mesh_without_data_axis_rejected():
    from flyimg_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(axis_names=("sp",))
    with pytest.raises(ValueError):
        BatchController(mesh=mesh)


def test_mesh_nonpow2_device_count_rounds_batch():
    """A 6-device data axis must still get divisible batches (5 -> 12)."""
    import jax

    from flyimg_tpu.parallel.mesh import make_mesh
    from flyimg_tpu.spec.options import OptionsBag
    from flyimg_tpu.spec.plan import build_plan

    mesh = make_mesh((6,), ("data",), devices=jax.devices()[:6])
    # lone_flush off so all 5 submits form the one batch whose 5 -> 12
    # rounding this test exists to pin
    ctrl = BatchController(
        max_batch=8, deadline_ms=5.0, mesh=mesh, lone_flush=False
    )
    try:
        rng = np.random.default_rng(7)
        imgs = [
            rng.integers(0, 256, size=(64, 64, 3), dtype=np.uint8)
            for _ in range(5)
        ]
        plans = [build_plan(OptionsBag("w_32,h_32,rz_1"), 64, 64) for _ in imgs]
        outs = [f.result(timeout=60) for f in
                [ctrl.submit(im, pl) for im, pl in zip(imgs, plans)]]
        assert all(o.shape == (32, 32, 3) for o in outs)
    finally:
        ctrl.close()


def test_lone_request_flushes_before_deadline():
    """A single pending request on an idle device must not wait out the
    batching deadline."""
    import time as _t

    from flyimg_tpu.spec.options import OptionsBag
    from flyimg_tpu.spec.plan import build_plan

    ctrl = BatchController(max_batch=8, deadline_ms=2000.0)
    try:
        rng = np.random.default_rng(8)
        img = rng.integers(0, 256, size=(64, 64, 3), dtype=np.uint8)
        plan = build_plan(OptionsBag("w_32,h_32,rz_1"), 64, 64)
        ctrl.submit(img, plan).result(timeout=60)  # warm the compile
        t0 = _t.monotonic()
        out = ctrl.submit(img, plan).result(timeout=60)
        elapsed = _t.monotonic() - t0
        assert out.shape == (32, 32, 3)
        assert elapsed < 1.0, f"lone request waited {elapsed:.2f}s (deadline 2s)"
    finally:
        ctrl.close()


def test_after_a_full_launch_a_lone_request_waits_for_its_launch_to_fill():
    """Callers in step (a bulk job's workers) come back one by one after a
    full launch: the first of them does not launch alone, which would hold
    the next full launch for its whole turnaround; it waits for the launch
    to fill (here the second request) or for the deadline. After a launch
    that was not full, a lone request goes at once as before."""
    import threading
    import time as _t

    ctrl = BatchController(max_batch=2, deadline_ms=60_000.0)
    try:
        rng = np.random.default_rng(9)
        img = rng.integers(0, 256, size=(64, 64, 3), dtype=np.uint8)
        plan = _plan("w_32,h_32,rz_1", 64, 64)
        ctrl.submit(img, plan).result(timeout=60)  # partial: warms batch 1
        ctrl.pause_launches()
        pair = [ctrl.submit(img, plan) for _ in range(2)]
        ctrl.resume_launches()
        for f in pair:
            f.result(timeout=60)                   # a full launch of 2
        lone = ctrl.submit(img, plan)
        _t.sleep(0.5)
        assert not lone.done(), "a lone request after a full launch went alone"
        second = ctrl.submit(img, plan)
        assert lone.result(timeout=60).shape == second.result(timeout=60).shape
        # the launch of two was full again; a request after a partial one
        # goes at once: make one partial launch with the deadline
        ctrl.deadline_s = 0.2
        ctrl.submit(img, plan).result(timeout=60)
        ctrl.deadline_s = 60.0
        done = threading.Event()
        t0 = _t.monotonic()
        ctrl.submit(img, plan).add_done_callback(lambda f: done.set())
        assert done.wait(30.0) and _t.monotonic() - t0 < 5.0
    finally:
        ctrl.close()


def test_aux_group_batches_and_orders():
    calls = []

    def runner(payloads):
        calls.append(list(payloads))
        return [p * 2 for p in payloads]

    ctl = BatchController(max_batch=3, deadline_ms=10_000.0, lone_flush=False)
    try:
        futures = [ctl.submit_aux(("toy",), i, runner) for i in range(3)]
        assert [f.result(timeout=30) for f in futures] == [0, 2, 4]
        assert calls == [[0, 1, 2]]  # ONE grouped call, submission order
        summary = ctl.metrics.summary()
        # aux work is accounted separately from transform batches
        assert summary.get("flyimg_aux_batches_total") == 1.0
        assert summary.get("flyimg_aux_items_total") == 3.0
        assert ctl.stats()["batches"] == 0.0
    finally:
        ctl.close()


def test_aux_runner_failure_propagates():
    def runner(payloads):
        raise RuntimeError("boom")

    ctl = BatchController(max_batch=2, deadline_ms=10_000.0, lone_flush=False)
    try:
        futures = [ctl.submit_aux(("bad",), i, runner) for i in range(2)]
        for f in futures:
            with pytest.raises(RuntimeError, match="boom"):
                f.result(timeout=30)
    finally:
        ctl.close()


def test_aux_and_transform_groups_coexist(controller):
    def runner(payloads):
        return [p + 1 for p in payloads]

    img = make_test_image(600, 400, seed=3)
    plan = _plan("w_200,h_150,c_1", 600, 400)
    f_transform = controller.submit(img, plan)
    f_aux = controller.submit_aux(("inc",), 41, runner)
    assert f_aux.result(timeout=120) == 42
    assert f_transform.result(timeout=120).shape == (150, 200, 3)


def test_aux_item_never_waits_a_long_deadline_behind_a_pending_transform():
    """The aux flush rule (docs/architecture.md): on a controller whose
    deadline is seconds long, with a transform group pending and far from
    full, an aux item goes as soon as the executor is idle — the lone-flush
    fast path holds for an aux group whatever else is pending — while the
    transform group keeps waiting by its own rule."""
    import time as _t

    ctl = BatchController(max_batch=4, deadline_ms=2_000.0)
    try:
        img = make_test_image(600, 400, seed=3)
        plan = _plan("w_200,h_150,c_1", 600, 400)
        # two pending transforms: neither alone (total_pending != 1) nor full
        pending = [ctl.submit(img, plan) for _ in range(2)]
        t0 = _t.monotonic()
        assert ctl.submit_aux(("inc",), 41, lambda ps: [p + 1 for p in ps]) \
            .result(timeout=30) == 42
        waited = _t.monotonic() - t0
        assert waited < 1.0, f"aux item waited {waited:.2f}s (deadline 2s)"
        assert not any(f.done() for f in pending)  # their rule is unchanged
        assert all(f.result(timeout=120).shape == (150, 200, 3) for f in pending)
        assert _t.monotonic() - t0 >= 1.5
    finally:
        ctl.close()


def test_aux_items_arriving_during_an_aux_launch_form_the_next_one():
    import threading

    started, release = threading.Event(), threading.Event()
    calls = []

    def runner(payloads):
        calls.append(list(payloads))
        if len(calls) == 1:
            started.set()
            release.wait(timeout=30)
        return list(payloads)

    ctl = BatchController(max_batch=8, deadline_ms=10_000.0)
    try:
        first = ctl.submit_aux(("toy",), 0, runner)
        assert started.wait(timeout=30)
        rest = [ctl.submit_aux(("toy",), i, runner) for i in (1, 2, 3)]
        release.set()
        assert [f.result(timeout=30) for f in [first] + rest] == [0, 1, 2, 3]
        assert calls == [[0], [1, 2, 3]]
    finally:
        ctl.close()


@pytest.mark.parametrize("name,label", [("device", "device_aux"),
                                        ("codec", "codec")])
def test_aux_launches_are_observed_apart_from_transform_launches(name, label):
    """``controller="device"`` series hold transform launches alone; a
    controller that runs aux work only keeps its name."""
    ctl = BatchController(max_batch=2, deadline_ms=10_000.0, name=name)
    try:
        futures = [ctl.submit_aux(("toy",), i, list) for i in range(2)]
        assert [f.result(timeout=30) for f in futures] == [0, 1]
        text = ctl.metrics.render_prometheus()
        for family in ("flyimg_batch_bucket_size", "flyimg_batch_occupancy_ratio",
                       "flyimg_batch_queue_wait_seconds"):
            assert f'{family}_count{{controller="{label}"}} 1' in text
        if label != name:
            assert [line for line in text.splitlines()
                    if f'controller="{name}"' in line
                    and not line.startswith("flyimg_batcher_queue_depth")] == []
        assert ctl.metrics.batch_efficiency(label).stats()["window_batches"] == 1
        assert ctl.metrics.batch_efficiency(name).stats()["window_batches"] == \
            (1 if label == name else 0)
        assert ctl.metrics.summary().get("flyimg_aux_items_total") == 2.0
    finally:
        ctl.close()


def test_mixed_size_rotate_shares_one_batch():
    """Two DIFFERENT-sized r_45 requests must land in one group (one
    compiled executable) and match the single-image path pixel-exactly."""
    ctl = BatchController(max_batch=2, deadline_ms=10_000.0, lone_flush=False)
    try:
        sources = []
        futures = []
        for i, (w, h) in enumerate([(300, 200), (260, 180)]):
            img = make_test_image(w, h, seed=20 + i)
            plan = _plan("r_45", w, h)
            sources.append((img, plan))
            futures.append(ctl.submit(img, plan))
        outs = [f.result(timeout=120) for f in futures]
        assert ctl.stats()["batches"] == 1.0  # ONE executable, shared
        for out, (img, plan) in zip(outs, sources):
            single = run_plan(img, plan)
            assert out.shape == single.shape
            _assert_rotate_parity(out, single)
    finally:
        ctl.close()


def _assert_rotate_parity(out, single):
    """Dynamic vs static rotate may differ by 1 uint8 step on a handful of
    pixels (traced-scalar vs constant-folded centers change XLA's float
    contraction at round() knife-edges); anything more is a real bug."""
    diff = np.abs(out.astype(np.int16) - single.astype(np.int16))
    assert diff.max() <= 1, diff.max()
    assert (diff != 0).mean() < 1e-4


def test_rotate_90_multiples_batch_match_single(controller):
    for angle in (90, 180, 270):
        img = make_test_image(250, 170, seed=angle)
        plan = _plan(f"r_{angle}", 250, 170)
        out = controller.submit(img, plan).result(timeout=120)
        np.testing.assert_array_equal(out, run_plan(img, plan))


def test_resize_plus_rotate_mixed_sizes_share_batch():
    """The reference bench scenario shape (r_-45,w_400,h_400) across mixed
    source sizes: fit-resample buckets + dynamic rotate = one group."""
    ctl = BatchController(max_batch=2, deadline_ms=10_000.0, lone_flush=False)
    try:
        sources = []
        futures = []
        for i, (w, h) in enumerate([(640, 480), (600, 400)]):
            img = make_test_image(w, h, seed=30 + i)
            plan = _plan("r_-45,w_400,h_400", w, h)
            sources.append((img, plan))
            futures.append(ctl.submit(img, plan))
        outs = [f.result(timeout=120) for f in futures]
        assert ctl.stats()["batches"] == 1.0
        for out, (img, plan) in zip(outs, sources):
            single = run_plan(img, plan)
            assert out.shape == single.shape
            _assert_rotate_parity(out, single)
    finally:
        ctl.close()


def test_rotate_with_conv_postop_stays_exact(controller):
    """Conv ops after a rotate opt OUT of the shape-bucketed rotate: on a
    padded frame the blur would smear background fill across the valid
    edge. This combo must stay pixel-identical to the single path."""
    img = make_test_image(300, 200, seed=77)
    plan = _plan("r_45,blr_2", 300, 200)
    out = controller.submit(img, plan).result(timeout=120)
    np.testing.assert_array_equal(out, run_plan(img, plan))


def test_starving_group_preempts_full_groups():
    """A group 4x past its deadline preempts the fullest-group policy:
    under sustained full-batch traffic a lone odd-shaped request must not
    be starved indefinitely. Truly deterministic: the executor thread is
    PARKED (subclass no-ops _run), so the test thread owns pop + execute
    serially — no race with the real executor, no timing dependence."""
    import time as _time

    class _ParkedExecutor(BatchController):
        def _run(self):  # executor parked: pop policy driven by the test
            return

    ctl = _ParkedExecutor(max_batch=4, deadline_ms=20.0, lone_flush=False)
    try:
        img_a = make_test_image(200, 100, seed=1)
        plan_a = _plan("w_50,o_jpg", 200, 100)
        img_b = make_test_image(100, 200, seed=2)
        plan_b = _plan("w_40,o_jpg", 100, 200)
        futs = [ctl.submit(img_a, plan_a) for _ in range(4)]  # full group
        fut_b = ctl.submit(img_b, plan_b)                     # lone member
        with ctl._lock:
            # backdate the lone group past the starvation floor; the full
            # group stays fresh and would otherwise win the pop
            for group in ctl._groups.values():
                if len(group.members) == 1:
                    group.members[0].enqueued_at = _time.monotonic() - 2.0
            popped = ctl._pop_ready_group()
        assert popped is not None and len(popped.members) == 1
        ctl._execute(popped)
        assert fut_b.result(timeout=120).shape[1] == 40
        # next pop serves the full group as usual
        with ctl._lock:
            rest = ctl._pop_ready_group()
        assert rest is not None and len(rest.members) == 4
        ctl._execute(rest)
        for f in futs:
            assert f.result(timeout=120).shape[1] == 50
    finally:
        ctl.close()


def test_pipelined_batches_match_serial():
    # pipeline_depth 2 (double buffering: dispatch N+1 overlaps N's
    # readback) must be byte-identical to strict serial depth 1, across
    # several consecutive batches and mixed shapes
    serial = BatchController(
        max_batch=4, deadline_ms=5.0, lone_flush=False, pipeline_depth=1
    )
    piped = BatchController(
        max_batch=4, deadline_ms=5.0, lone_flush=False, pipeline_depth=2
    )
    try:
        jobs = []
        for i, (w, h) in enumerate(
            [(600, 400), (620, 410), (580, 390), (600, 400),
             (300, 200), (310, 210), (300, 200), (290, 190)]
        ):
            img = make_test_image(w, h, seed=40 + i)
            plan = _plan("w_200,h_150,c_1", w, h)
            jobs.append((img, plan))
        fs = [serial.submit(img, plan) for img, plan in jobs]
        fp = [piped.submit(img, plan) for img, plan in jobs]
        for a, b in zip(fs, fp):
            np.testing.assert_array_equal(
                a.result(timeout=180), b.result(timeout=180)
            )
    finally:
        serial.close()
        piped.close()


def test_close_drains_inflight_readbacks():
    # close() must resolve futures whose batches were dispatched but not
    # yet read back (the drain pool shuts down with wait=True)
    ctl = BatchController(max_batch=2, deadline_ms=1.0, pipeline_depth=2)
    futs = []
    for i in range(6):
        img = make_test_image(400, 300, seed=60 + i)
        futs.append(ctl.submit(img, _plan("w_100", 400, 300)))
    ctl.close()
    for f in futs:
        out = f.result(timeout=60)  # already resolved by close()
        assert out.shape[1] == 100


def test_equal_length_inflight_batches_drain_cleanly():
    # _Pending must use identity equality: with the generated dataclass
    # __eq__, comparing one in-flight batch against another EQUAL-LENGTH
    # batch evaluates ndarray == ndarray and raises "truth value is
    # ambiguous" inside _drain's bookkeeping, leaking the entry forever
    ctl = BatchController(max_batch=2, deadline_ms=1.0, pipeline_depth=2)
    try:
        futs = []
        for i in range(8):  # four consecutive equal-sized batches
            img = make_test_image(400, 300, seed=80 + i)
            futs.append(ctl.submit(img, _plan("w_100", 400, 300)))
        for f in futs:
            assert f.result(timeout=120).shape[1] == 100
        # every batch's bookkeeping entry must be gone
        deadline = __import__("time").monotonic() + 10
        while __import__("time").monotonic() < deadline:
            with ctl._lock:
                if not ctl._inflight_batches:
                    break
        with ctl._lock:
            assert not ctl._inflight_batches
    finally:
        ctl.close()


def test_flush_policy_is_fixed_at_construction():
    """``max_batch`` and ``deadline_s`` are what the constructor was given,
    for as long as the controller lives: nothing on it changes them, and a
    group made after 1,000 submissions owns a block of as many slots as
    one made before them."""
    ctl = BatchController(max_batch=4, deadline_ms=60_000.0, lone_flush=False)
    for name in ("apply_policy", "policy", "_policy"):
        assert not hasattr(ctl, name), name
    image = make_test_image(40, 30)
    plan = _plan("clsp_Gray", 40, 30)

    def slots_of_the_waiting_group():
        with ctl._lock:
            (group,) = ctl._groups.values()
            return group.block.shape[0]

    try:
        first = ctl.submit(image, plan)  # not full: its group waits
        before = slots_of_the_waiting_group()
        futures = [first] + [ctl.submit(image, plan) for _ in range(1_003)]
        for future in futures:  # 251 full launches
            future.result(timeout=120)
        last = ctl.submit(image, plan)
        after = slots_of_the_waiting_group()
        rest = [ctl.submit(image, plan) for _ in range(3)]
        for future in [last] + rest:
            future.result(timeout=120)
        assert before == after == 4
        assert (ctl.max_batch, ctl.deadline_s) == (4, 60.0)
        assert ctl.stats()["batches"] == 252
    finally:
        ctl.close()
