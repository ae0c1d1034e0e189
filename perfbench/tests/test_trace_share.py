"""What a traced run reads of the full launch (``readers/trace_share.py``) and
of its place in the slice (``trace.slice_margins``), on the recorded fixture
``fixtures/phase_trace.json``: one launch, seq 7, whose program ran 0.206 s
on the device (1.000-1.206 s) between the start of its dispatch (0.900 s) and
the end of its read-back (1.356 s). No chip, no program."""

import copy
import os

import pytest
from conftest import HERE

from perfbench.harness import manifest, trace

FIXTURES = os.path.join(os.path.dirname(HERE), "fixtures")
KERNEL = {"work": "resample", "images": "flyimg_images_processed_total"}
# 81.9 MB an image: 0.1 ms at the v5e's 819 GB/s
WORK = {"resample": {"flops": 0.0, "bytes": 819e9 / 10000}}


def planes_of(name="phase_trace.json"):
    return manifest.load_json(os.path.join(FIXTURES, name))


def window(planes, launches, lone=0):
    """What a reader is handed after a window of ``launches`` full launches of
    64 and ``lone`` launches of 1, each held 1.1 s by the program's timer."""
    sizes = {"64": launches, **({"1": lone} if lone else {})}
    return {"trace_planes": planes, "counters_before": {},
            "counters_after": {"flyimg_device_seconds_sum": 1.1 * (launches + lone),
                               "flyimg_device_seconds_count": float(launches + lone),
                               "flyimg_images_processed_total": 64.0 * launches + lone},
            "launch_sizes": sizes, "device": {"kind": "TPU v5 lite"}, "work_per_image": WORK}


def with_a_lone_launch(planes):
    """The fixture's slice with a launch of 1, seq 6, ahead of the full one:
    its program runs 4 ms, its hold lasts 40 ms."""
    planes = copy.deepcopy(planes)
    device, host = planes[0]["lines"], planes[1]["lines"]
    device[0]["events"].insert(0, ["jit_program(3)", 500000000, 4000000])
    host[0]["events"] += [["flyimg:batch:6:h2d", 470000000, 1000000],
                          ["flyimg:batch:6:dispatch", 480000000, 1000000]]
    host[1]["events"] += [["flyimg:batch:6:run", 495000000, 9000000],
                          ["flyimg:batch:6:d2h", 504000000, 16000000]]
    return planes


def read(metric, ctx):
    spec = manifest.load_metric(metric)
    return manifest.load_reader(spec["reader"])(ctx, **spec["args"])


@pytest.mark.parametrize("launches", [2, 6])
def test_idle_share_is_of_the_traced_launchs_own_hold(launches):
    ctx = window(planes_of(), launches)
    assert read("device_idle_share", ctx) == pytest.approx(100.0 * (1.0 - 0.206 / 0.456))
    assert ctx["notes"]["traced_launch_hold_s"] == pytest.approx(0.456)


@pytest.mark.parametrize("metric,alone", [
    ("device_idle_share", 100.0 * (1.0 - 0.206 / 0.456)),
    ("resample_roofline", 100.0 * 64 * 1e-4 / 0.206),
])
def test_a_lone_launch_in_the_slice_changes_neither_share(metric, alone):
    assert read(metric, window(planes_of(), 5)) == pytest.approx(alone)
    beside = read(metric, window(with_a_lone_launch(planes_of()), 5, lone=1))
    assert beside == pytest.approx(alone, rel=0.02)
    # nor does one the window held outside the slice
    assert read(metric, window(planes_of(), 5, lone=1)) == pytest.approx(alone)


@pytest.mark.parametrize("planes", [
    planes_of("small_trace.json"),     # a program that annotates its dispatch alone, no phases
    [p for p in planes_of() if p["name"].startswith("/device")],   # no host plane at all
    [],
])
def test_a_trace_without_the_annotations_reads_no_idle_share(planes):
    assert read("device_idle_share", window(planes, 5)) is None


def test_a_launch_cut_by_the_slices_opening_has_no_hold():
    """The dispatch annotation began before the profiler was on, so the trace
    holds the read-back alone: nothing read, where a guess would read low."""
    planes = planes_of()
    for line in planes[1]["lines"]:
        line["events"] = [e for e in line["events"] if not e[0].endswith(":dispatch")]
    assert trace.launch_holds(planes) == {}
    assert read("device_idle_share", window(planes, 5)) is None
    assert read("resample_roofline", window(planes, 5)) == pytest.approx(100.0 * 64 * 1e-4 / 0.206)


def test_margins_say_how_far_inside_the_slice_each_launch_sits():
    planes = with_a_lone_launch(planes_of())
    assert trace.slice_margins(planes) == []          # no mark of the harness's: nothing
    planes[1]["lines"].append({"name": "MainThread", "events": [[trace.SLICE_MARK, 100000000, 1800000000]]})
    planes[1]["lines"][0]["events"].append(["flyimg:batch:7:h2d", 880000000, 15000000])
    full, lone = trace.slice_margins(planes)
    assert (full["seq"], lone["seq"]) == (7, 6)       # the one held longest first
    assert full["staged_after_open_s"] == pytest.approx(0.78)
    assert full["readback_before_end_s"] == pytest.approx(1.9 - 1.356)
    assert full["hold_s"] == pytest.approx(0.456) and lone["hold_s"] == pytest.approx(0.04)
    # staged before the profiler was on: the hold is whole, the staging is not in the trace
    planes[1]["lines"][0]["events"].pop()
    assert trace.slice_margins(planes)[0]["staged_after_open_s"] is None
