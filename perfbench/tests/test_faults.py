"""A whole run, less the look for a chip, with the timed path broken
underneath: ``correct`` has to come out false. Of the faults the contract
lists, these cells can have one kind, an answer altered where it is produced
(they keep no state across steps, take no mean over a batch and use one
chip); it is planted three ways."""

import io
import time

import numpy as np
import pytest
from PIL import Image

from perfbench.harness import cell, system

CELLS = ["dslr-backfill-saturated"]


def _run(doc, name):
    return cell.run_cell(doc, name, 77, 3.0, False, t_process=time.perf_counter(),
                         toy=True, require_chip=False)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct_and_prints_no_device_metric(doc, name):
    result = cell.run_cell(doc, name, 78, 3.0, True,
                           t_process=time.perf_counter(), toy=True, require_chip=False)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
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
@pytest.mark.parametrize("name", CELLS)
def test_altered_answer_is_not_correct(monkeypatch, doc, fault, name):
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
    result = _run(doc, name)
    assert not result["correct"], result["compared"]
