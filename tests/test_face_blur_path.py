"""The face-blur path (PR 36): ``fb_1`` with the BlazeFace detector does all
its device work through the device controller. Held here, at a small size on
the CPU: the plain reference's detector (``perfbench/references/
faceblur_lanczos.py``, which imports nothing of the program) against the
program's ``_forward`` on seeded random weights, before any threshold; the
batched path with the views made on the caller's thread against the
per-image ``detect_faces``; the batched ``uint8`` pixelation against the
reference's numpy pixelation, exactly; a ``fb_1`` request under a
``BatchController`` (its timings, spans, counters, and which thread
dispatched to the device); and the faults the reference's comparison has to
see."""

import io
import os
import sys
import threading
import time

import numpy as np
import pytest
from PIL import Image

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from flyimg_tpu.appconfig import AppParameters  # noqa: E402
from flyimg_tpu.runtime import tracing  # noqa: E402
from flyimg_tpu.runtime.batcher import BatchController  # noqa: E402
from flyimg_tpu.runtime.metrics import MetricsRegistry  # noqa: E402
from flyimg_tpu.service.handler import ImageHandler  # noqa: E402
from flyimg_tpu.service.output_image import EXT_TO_MIME, OutputSpec  # noqa: E402
from flyimg_tpu.spec.options import OptionsBag  # noqa: E402
from perfbench.harness import compare, corpus, manifest, plain  # noqa: E402

DOC = manifest.load_manifest()
CONFIG = "group-faceblur-24mp"


@pytest.fixture(scope="module")
def bound():
    config = manifest.load_json(manifest.config_file(DOC, CONFIG))
    manifest.apply_toy(config)
    return config, manifest.bind(DOC, CONFIG, config)


@pytest.fixture(scope="module")
def ref(bound):
    return bound[1].reference


@pytest.fixture(scope="module")
def backend():
    from flyimg_tpu.models.faces import make_face_backend

    return make_face_backend("blazeface")


def _group(bound, seed, w=400, h=266):
    """A group photograph of the benchmark's corpus kind, made as a toy
    frame and reduced to ``w x h``."""
    frame = bound[1].make_image(seed, 0, 1500, 1000)
    return np.asarray(Image.fromarray(frame).resize((w, h), Image.LANCZOS))


# ---------------------------------------------------------------------------
# 1. the reference's detector against the program's, before any threshold

# float32 against float32, two ways of summing: the random weights' logits
# run to the hundreds, where a last-place difference is 1e-5 of a
# probability and 1e-4 of a box side
FORWARD_TOLERANCE = {"probs": 1e-4, "boxes": 1e-3}


@pytest.mark.parametrize("seed", [0, 7])
def test_reference_forward_equals_the_programs_on_seeded_random_weights(ref, seed):
    import jax
    import jax.numpy as jnp

    from flyimg_tpu.models import blazeface

    params = blazeface.init_params(jax.random.PRNGKey(seed))
    weights = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float32), params)
    assert jax.tree_util.tree_map(lambda a: a.shape, weights) == ref.weight_shapes()
    x = np.random.default_rng(seed).uniform(-1, 1, (3, 128, 128, 3)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        probs, boxes = blazeface._forward(params, jnp.asarray(x))
    ref_probs, ref_boxes = ref.forward(weights, x)
    assert ref_probs.shape == (3, ref.ANCHORS) and ref_boxes.shape == (3, ref.ANCHORS, 4)
    assert 0.05 < float((ref_probs > 0.5).mean()) < 0.95, "random weights that say nothing either way"
    assert np.abs(np.asarray(probs) - ref_probs).max() <= FORWARD_TOLERANCE["probs"]
    assert np.abs(np.asarray(boxes) - ref_boxes).max() <= FORWARD_TOLERANCE["boxes"]


def test_reference_reads_the_packaged_checkpoint_and_finds_the_programs_boxes(bound, ref, backend):
    """Trained weights, a group photograph: the same views, and box for box
    the program's detections at the serving threshold."""
    from flyimg_tpu.models import blazeface

    rendition = _group(bound, 31)
    ref_inputs, ref_views = ref.network_inputs(rendition)
    work = blazeface.prepare_views(rendition)
    np.testing.assert_array_equal(work.inputs, ref_inputs)
    assert list(work.views) == ref_views and len(ref_views) == 6
    found = [k["box"] for k in ref.detect(rendition) if k["box"]]
    got = [(x, y, x + w, y + h) for x, y, w, h in backend.detect_faces(rendition)]
    assert len(found) >= 4
    assert got == found


def test_forward_has_no_static_threshold():
    """One compiled program a batch shape, whatever threshold is served."""
    import inspect

    from flyimg_tpu.models import blazeface

    assert list(inspect.signature(blazeface._forward).parameters) == ["params", "images"]


def test_reference_imports_nothing_of_the_program():
    path = os.path.join(ROOT, "perfbench", "references", "faceblur_lanczos.py")
    with open(path, encoding="utf-8") as fh:
        lines = [line.split() for line in fh]
    imported = [words[1] for words in lines if words[:1] in (["import"], ["from"])]
    assert imported and not [name for name in imported if name.split(".")[0] == "flyimg_tpu"]


# ---------------------------------------------------------------------------
# 2. one detection path: prepared on the caller's thread, run batched

def test_batched_detection_equals_per_image_box_for_box(bound, backend):
    images = [_group(bound, 41), _group(bound, 42, 300, 200), _group(bound, 43),
              np.zeros((120, 90, 3), np.uint8), _group(bound, 44, 520, 346)]
    items = [backend.prepare_face_work(img) for img in images]
    assert [len(item.inputs) for item in items] == [6, 2, 6, 2, 6]
    assert all(item.inputs.dtype == np.float32 and item.inputs.shape[1:] == (128, 128, 3)
               for item in items)
    stats = {}
    batched = backend.detect_faces_batched(items, stats)
    assert batched == [backend.detect_faces(img) for img in images]
    assert sum(map(len, batched)) >= 8
    # 22 views in one chunk, padded up the ladder to 32, and the seconds of
    # the launch's three parts
    assert stats.pop("stack_s") >= 0 and stats.pop("forward_s") > 0
    assert stats.pop("boxes_s") >= 0
    assert stats == {"views": 22, "slots": 32, "forwards": 1}


@pytest.mark.parametrize("copies", [1, 12])
def test_the_parts_of_a_detection_launch_add_up_to_no_more_than_its_call(
        bound, backend, copies):
    """``stack_s`` + ``forward_s`` + ``boxes_s`` are disjoint parts of the
    runner's call, on its one thread (in one chunk and in two)."""
    item = backend.prepare_face_work(_group(bound, 46))
    backend.detect_faces_batched([item] * copies)  # compiled before timing
    stats = {}
    t0 = time.perf_counter()
    backend.detect_faces_batched([item] * copies, stats)
    call_s = time.perf_counter() - t0
    parts = stats["stack_s"] + stats["forward_s"] + stats["boxes_s"]
    assert 0 < parts <= call_s
    assert min(stats["stack_s"], stats["forward_s"], stats["boxes_s"]) > 0


def test_a_launch_of_many_views_runs_in_chunks_of_the_bucket_ceiling(bound, backend):
    item = backend.prepare_face_work(_group(bound, 45))
    stats = {}
    out = backend.detect_faces_batched([item] * 12, stats)
    assert all(boxes == out[0] for boxes in out)
    assert {k: stats[k] for k in ("views", "slots", "forwards")} == {
        "views": 72, "slots": 64 + 8, "forwards": 2}


# ---------------------------------------------------------------------------
# 3. the batched uint8 pixelation against the reference's numpy, exactly

@pytest.mark.parametrize("size", [(97, 133), (266, 400), (60, 60)])
@pytest.mark.parametrize("boxes", [0, 1, "max"])
def test_batched_pixelation_equals_the_reference_exactly(ref, size, boxes):
    from flyimg_tpu.ops import pixelate

    h, w = size
    rng = np.random.default_rng([h, w, 5])
    image = rng.integers(0, 256, (h, w, 3), dtype=np.uint8)
    count = pixelate.MAX_BOXES if boxes == "max" else boxes
    xywh = []
    for _ in range(count):
        x, y = int(rng.integers(0, w - 1)), int(rng.integers(0, h - 1))
        xywh.append((x, y, int(rng.integers(1, w - x + 1)), int(rng.integers(1, h - y + 1))))
    stats = {}
    out = pixelate.pixelate_images([pixelate.prepare_work(image, xywh)], stats)[0]
    assert out.dtype == np.uint8 and stats == {"images": 1, "slots": 1, "launches": 1}
    np.testing.assert_array_equal(out, ref.pixelate(image, [(x, y, x + bw, y + bh) for x, y, bw, bh in xywh]))
    if not count:
        np.testing.assert_array_equal(out, image)


def test_pixelation_batch_of_several_true_sizes_in_one_bucket(ref):
    from flyimg_tpu.ops import pixelate

    rng = np.random.default_rng(9)
    images = [rng.integers(0, 256, (h, w, 3), dtype=np.uint8) for h, w in ((50, 60), (64, 64), (33, 61))]
    items = [pixelate.prepare_work(img, [(3, 4, 30, 20)]) for img in images]
    assert len({item.bucket for item in items}) == 1
    stats = {}
    outs = pixelate.pixelate_images(items, stats)
    assert stats == {"images": 3, "slots": 4, "launches": 1}
    for out, img in zip(outs, images):
        np.testing.assert_array_equal(out, ref.pixelate(img, [(3, 4, 33, 24)]))


# ---------------------------------------------------------------------------
# 4. a fb_1 request under a BatchController

class _System:
    def __init__(self):
        self.params = AppParameters({"face_backend": "blazeface"})
        self.metrics = MetricsRegistry()
        self.batcher = BatchController(deadline_ms=1.0, metrics=self.metrics)
        self.codec = BatchController(deadline_ms=1.0, name="codec")
        self.handler = ImageHandler(storage=None, params=self.params, batcher=self.batcher,
                                    codec_batcher=self.codec, metrics=self.metrics)

    def transform(self, data, timings, text):
        options = OptionsBag(text, options_keys=self.params.by_key("options_keys"),
                             default_options=self.params.by_key("default_options"),
                             separator=self.params.by_key("options_separator", ","))
        spec = OutputSpec(name="t.jpg", extension="jpg", mime=EXT_TO_MIME["jpg"])
        return self.handler.transform_bytes(data, options, spec, timings)

    def close(self):
        self.codec.close()
        self.batcher.close()


@pytest.fixture()
def system():
    sut = _System()
    yield sut
    sut.close()


def _jpeg(rgb):
    buf = io.BytesIO()
    Image.fromarray(rgb).save(buf, format="JPEG", quality=92, subsampling=0)
    return buf.getvalue()


def _counter(metrics, name):
    for line in metrics.render_prometheus().splitlines():
        if line.startswith(name + " "):
            return float(line.split()[-1])
    return 0.0


def _find(node, name):
    if node["name"] == name:
        return node
    for child in node.get("children", []):
        hit = _find(child, name)
        if hit is not None:
            return hit
    return None


def test_handler_that_names_the_detector_loads_it_when_it_is_built(system):
    assert system.handler._face_backend is not None
    assert type(system.handler._face_backend).__name__ == "BlazeFaceBackend"


def test_fb_request_goes_through_the_device_controller(bound, ref, system, monkeypatch):
    from flyimg_tpu.models import blazeface
    from flyimg_tpu.ops import pixelate

    dispatched = []
    for module, name in ((blazeface, "_forward"), (pixelate, "_pixelate_batch")):
        program = getattr(module, name)

        def spy(*args, _program=program, _name=name):
            dispatched.append((_name, threading.current_thread().name))
            return _program(*args)

        monkeypatch.setattr(module, name, spy)
    data = _jpeg(_group(bound, 51, 800, 532))
    trace = tracing.Trace(name="img0.jpg")
    timings = {}
    with tracing.activate(trace):
        out = system.transform(data, timings, "w_400,h_266,c_1,fb_1")
    trace.finish()
    answer = np.asarray(Image.open(io.BytesIO(out)).convert("RGB"))
    assert answer.shape == (266, 400, 3)
    # the four timings (and the second trip's own queue and run), each its span's
    root = trace.as_dict()["spans"][0]
    for stage, span_name in (("faces", "faces"), ("faces_prepare", "faces.prepare"),
                             ("faces_queue", "faces.queue"), ("faces_run", "faces.run"),
                             ("faces_pixelate", "faces.pixelate"),
                             ("faces_pixelate_queue", "faces_pixelate.queue"),
                             ("faces_pixelate_run", "faces_pixelate.run")):
        assert _find(root, span_name)["duration_s"] == pytest.approx(timings[stage], abs=2e-3), stage
    assert timings["faces_prepare"] + timings["faces_queue"] + timings["faces_run"] \
        + timings["faces_pixelate"] <= timings["faces"] + 1e-3
    assert timings["faces_pixelate_queue"] + timings["faces_pixelate_run"] <= timings["faces_pixelate"] + 1e-3
    # the counters: six views in eight slots, one forward, the boxes, one image pixelated
    m = system.metrics
    assert _counter(m, "flyimg_face_views_total") == 6
    assert _counter(m, "flyimg_face_view_slots_total") == 8
    assert _counter(m, "flyimg_face_forwards_total") == 1
    boxes = _counter(m, "flyimg_face_boxes_total")
    assert boxes >= 3
    assert _counter(m, "flyimg_face_pixelate_images_total") == 1
    assert _counter(m, "flyimg_face_pixelate_slots_total") == 1
    assert _counter(m, "flyimg_face_pixelate_launches_total") == 1
    # both launches were aux launches of the device controller, beside one transform launch
    text = m.render_prometheus()
    assert 'flyimg_batch_bucket_size_count{controller="device"} 1' in text
    assert 'flyimg_batch_bucket_size_count{controller="device_aux"} 2' in text
    assert _counter(m, "flyimg_aux_items_total") == 2
    assert _counter(m, "flyimg_wedged_fallbacks_total") == 0
    # and the request's thread dispatched neither program
    assert sorted(name for name, _ in dispatched) == ["_forward", "_pixelate_batch"]
    assert threading.current_thread().name not in {thread for _, thread in dispatched}
    assert len({thread for _, thread in dispatched}) == 1   # the controller's one executor
    # and the answer is the reference's: its sure faces pixelated, nothing else
    options = {"width": 400, "height": 266}
    frame = ref.render_fill(data, options)
    found = ref.detect(plain.to_u8(frame), floor=ref.THRESHOLD - ref.MARGIN)
    numbers = ref.judge_answer(answer, frame, found)
    assert numbers["sure_boxes"] >= 3 and abs(numbers["kept_boxes"] - boxes) <= 1
    assert numbers["dims_gap"] == 0 and numbers["face_gap"] <= bound[0]["limits"]["face_gap"], numbers
    assert numbers["block_err"] <= bound[0]["limits"]["block_err"], numbers
    sharp = ref.judge_answer(plain.to_u8(frame), frame, found)
    assert sharp["face_gap"] > bound[0]["limits"]["face_gap"], "the same judge refuses the rendition left sharp"


def test_an_image_with_no_face_skips_the_second_trip(system):
    timings = {}
    flat = np.full((300, 400, 3), (40, 90, 160), np.uint8)
    out = system.transform(_jpeg(flat), timings, "w_200,h_150,c_1,fb_1")
    assert Image.open(io.BytesIO(out)).size == (200, 150)
    assert {"faces", "faces_prepare", "faces_queue", "faces_run"} <= set(timings)
    assert "faces_pixelate" not in timings
    assert _counter(system.metrics, "flyimg_face_pixelate_images_total") == 0
    assert _counter(system.metrics, "flyimg_face_boxes_total") == 0
    assert _counter(system.metrics, "flyimg_aux_items_total") == 1


def test_face_crop_keeps_its_host_slice(bound, system):
    timings = {}
    out = system.transform(_jpeg(_group(bound, 52, 800, 532)), timings, "w_400,h_266,c_1,fc_1")
    w, h = Image.open(io.BytesIO(out)).size
    assert (w, h) != (400, 266) and w <= 400 and h <= 266
    assert "faces_pixelate" not in timings and "faces_run" in timings


# ---------------------------------------------------------------------------
# 5. the comparison sees the faults of this path

@pytest.fixture(scope="module")
def originals(bound):
    config, b = bound
    return corpus.make_corpus(b.make_image, 97, config["frame"], 3)


def test_corpus_shows_faces_the_reference_is_sure_of(bound, ref, originals):
    _, b = bound
    for data in originals:
        found = ref.detect(plain.to_u8(ref.render_fill(data, b.options)))
        assert sum(k["score"] >= ref.THRESHOLD + ref.MARGIN for k in found) >= 4


def _answers(bound, ref, originals, make):
    _, b = bound
    return {(i, "x"): plain.encode_jpeg(make(i, ref.render_fill(data, b.options)), 90)
            for i, data in enumerate(originals)}


def _boxes(ref, u8, **controls):
    return [k["box"] for k in ref.detect(u8, **controls) if k["box"]]


@pytest.mark.parametrize("fault,over", [
    ("sound", None),
    ("detector_in_bfloat16", None),
    ("no_face_pixelated", "face_gap"),
    ("boxes_one_block_off", "block_err"),
    ("one_face_missed", "face_gap"),
    ("a_wall_pixelated", "face_gap"),
    ("another_images_answer", "block_err"),
])
def test_planted_face_faults_read_not_correct(bound, ref, originals, fault, over):
    _, b = bound

    def make(i, frame):
        u8 = plain.to_u8(frame)
        if fault == "no_face_pixelated":
            return u8
        if fault == "another_images_answer" and i:
            return None
        boxes = _boxes(ref, u8, operands="bfloat16" if fault == "detector_in_bfloat16" else "float32")
        if fault == "boxes_one_block_off":
            boxes = [(x0 + 10, y0 + 10, x1 + 10, y1 + 10) for x0, y0, x1, y1 in boxes]
        if fault == "one_face_missed":
            boxes = boxes[1:]   # the best-scoring face stays sharp
        if fault == "a_wall_pixelated":
            boxes = boxes + [(0, u8.shape[0] - 60, 80, u8.shape[0])]
        return ref.pixelate(u8, boxes)

    answers = {}
    for i, data in enumerate(originals):
        out = make(i, ref.render_fill(data, b.options))
        answers[(i, "x")] = answers[(0, "x")] if out is None else plain.encode_jpeg(out, 90)
    verdict = compare.Judge(b, originals).judge(answers)
    numbers = verdict["numbers"]
    failing = [k for k, n in numbers.items() if n["value"] > n["limit"]]
    if over is None:
        assert verdict["correct"] and not failing, numbers
    else:
        assert not verdict["correct"] and over in failing, numbers


def test_reference_work_counts_the_detector_by_hand(ref):
    """The stem (5x5x3 taps to 24 channels at 64x64) and the first block
    (25 depthwise taps and 24 pointwise, 24 channels at 64x64) by hand; the
    whole near the 75 MFLOP a view the issue counted."""
    config = manifest.load_config(DOC, CONFIG)
    kernels = ref.work(config)
    assert set(kernels) == {"resample", "blazeface_forward", "face_pixelate"}
    flops = kernels["blazeface_forward"]["flops"]
    assert flops > 2.0 * 64 * 64 * (75 * 24 + 24 * (25 + 24))
    assert 70e6 < flops < 80e6
    assert kernels["face_pixelate"] == {"flops": 2.0 * 3 * 1600 * 1066, "bytes": 2.0 * 3 * 1600 * 1066}
    assert kernels["resample"]["bytes"] == 3.0 * (6000 * 4000 + 1600 * 1066)


# ---------------------------------------------------------------------------
# 6. the warmer and the reader this configuration brings

def test_faces_warmer_refuses_a_program_without_the_batched_path(bound):
    from types import SimpleNamespace

    config, b = bound
    warm = dict(b.warmers)["faces_aux"]
    assert next(iter(dict(b.warmers))) == "faces_aux", "refuses in the first seconds: listed first"
    old = SimpleNamespace(handler=SimpleNamespace(_faces=lambda: None))
    with pytest.raises(RuntimeError, match="no batched face path"):
        warm(old, config, {})


def test_faces_warmer_covers_every_padded_size_a_launch_can_have(bound):
    _, b = bound
    warmer = manifest.load_plug("warmers", "faces_aux", ("warm",))
    # six views an image, up to 64 images a launch, chunks of 64 views
    assert warmer.items_for_every_padded_size(6, 64, 64) == {8: 1, 16: 2, 32: 3, 64: 6, 2: 11, 4: 22}
    # one item an image, chunks of 16
    assert warmer.items_for_every_padded_size(1, 64, 16) == {1: 1, 2: 2, 4: 3, 8: 5, 16: 9}
    assert warmer.items_for_every_padded_size(1, 8, 16) == {1: 1, 2: 2, 4: 3, 8: 5}


def test_batched_share_reader_counts_slots_by_the_traced_shapes_and_the_real_share_by_counters():
    read = manifest.load_reader("trace_batched_share")
    ops = [["%copy.16 = bf16[8,128,128,3]{3,2,1,0} copy(f32[8,128,128,3]{3,2,1,0} %images.1)", 1000.0, 400.0],
           ["%fusion.2 = f32[8,16,16,88]{3,2,1,0} fusion(...)", 1500.0, 100.0],
           ["%fusion.102 = f32[64,64,64,24]{3,2,1,0} fusion(f32[64,128,128,3]{3,2,1,0} %images.1, ...)", 5000.0, 900.0]]
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit__forward(11)", 1000.0, 1000.0], ["jit__forward(12)", 5000.0, 1000.0],
                                           ["jit_program(3)", 9000.0, 5000.0]]},
        {"name": "XLA Ops", "events": ops}]}]
    ctx = {"trace_planes": planes, "device": {"kind": "TPU v5 lite"},
           "work_per_image": {"blazeface_forward": {"flops": 1e6, "bytes": 8.19e5}},
           "counters_before": {}, "counters_after": {"views": 54.0, "slots": 72.0}}
    args = dict(manifest.load_metric("detector_roofline")["args"], real="views", slots="slots")
    value = read(ctx, **args)
    from perfbench.harness import work as work_mod

    least = work_mod.least_seconds(ctx["work_per_image"]["blazeface_forward"], work_mod.peaks("TPU v5 lite"))
    assert value == pytest.approx(100.0 * least["seconds"] * 72 * 0.75 / 2e-6)
    assert ctx["notes"]["blazeface_forward_traced_slots"] == 72
    # a run whose parameter no operation names is read from its activations; one that says nothing is left out
    ops[0][0] = "%fusion.1 = f32[8,64,64,24]{3,2,1,0} fusion(bf16[8,128,128,3]{3,2,1,0} %bitcast.5)"
    assert read(ctx, **args) == pytest.approx(value)
    ops[0][0], ops[1][0] = "%fusion.1 = f32[512,24]{1,0} fusion(...)", "%fusion.2 = f32[512,88]{1,0} fusion(...)"
    assert read(ctx, **args) == pytest.approx(100.0 * least["seconds"] * 64 * 0.75 / 1e-6)
    assert ctx["notes"]["blazeface_forward_traced_runs_unread"] == 1
    # nothing to read: no such module, no counters, a CPU run
    assert read(dict(ctx, counters_after={}), **args) is None
    assert read(ctx, **dict(args, module="^jit__pixelate_batch")) is None
    assert read(dict(ctx, trace_planes=[]), **args) is None
