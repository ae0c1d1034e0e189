"""A whole run, less the look for a chip, with the timed path broken
underneath: ``correct`` has to come out false. Of the faults the contract
lists, these cells can have one kind, an answer altered where it is produced
(they keep no state across steps, take no mean over a batch and use one
chip); it is planted three ways. The cells are those of the benchmark and the
one of the fixture deployment (``fixtures/``: semantics other than crop-fill,
with a reference, a configuration and a manifest of its own and nothing
else), which the same harness has to run end to end unedited."""

import io
import time

import numpy as np
import pytest
from conftest import MANIFESTS, every
from PIL import Image

from perfbench.harness import cell, system


def _run(which, name, seed=77, traced=False):
    return cell.run_cell(MANIFESTS[which], name, seed, 3.0, traced,
                         t_process=time.perf_counter(), toy=True, require_chip=False)


@pytest.mark.parametrize("which,name", every("workloads"))
def test_sound_run_is_correct_and_prints_no_device_metric(which, name):
    result = _run(which, name, 78, traced=True)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert {"decode_ms", "encode_ms", "images_per_launch"} <= set(result["metrics"])
    assert "resample_roofline" not in result["metrics"]
    assert "busy_s" not in result["device"]
    assert list(result)[-1] == "compared"


def _reencode(data, change):
    with Image.open(io.BytesIO(data)) as im:
        rgb = np.array(im.convert("RGB"))
    buf = io.BytesIO()
    Image.fromarray(change(rgb)).save(buf, format="JPEG", quality=95, subsampling=0)
    return buf.getvalue()


def _patch(rgb):
    rgb = rgb.copy()
    h, w = rgb.shape[:2]
    rgb[h // 3: h // 3 + 40, w // 3: w // 3 + 40] = np.clip(
        rgb[h // 3: h // 3 + 40, w // 3: w // 3 + 40].astype(np.int16) + 14, 0, 255).astype(np.uint8)
    return rgb


def _shift(rgb):
    return np.roll(rgb, 2, axis=1)


@pytest.mark.parametrize("fault", ["patch_brightened", "shifted_two_pixels", "another_images_answer"])
@pytest.mark.parametrize("which,name", every("workloads"))
def test_altered_answer_is_not_correct(monkeypatch, fault, which, name):
    sound = system.System.transform
    first = {}

    def broken(self, data):
        out, timings = sound(self, data)
        if fault == "patch_brightened":
            return _reencode(out, _patch), timings
        if fault == "shifted_two_pixels":
            return _reencode(out, _shift), timings
        # a member of the launch gets its neighbour's pixels
        first.setdefault("out", out)
        return first["out"], timings

    monkeypatch.setattr(system.System, "transform", broken)
    result = _run(which, name)
    assert not result["correct"], result["compared"]
