"""Offline bulk runner: a directory through the batch runtime
(BASELINE.md firehose-workload driver)."""

import json
import os

import numpy as np
import pytest
from PIL import Image

from flyimg_tpu.bulk import bulk_process, main


def _make_dir(tmp_path, n=6):
    src = tmp_path / "src"
    src.mkdir()
    rng = np.random.default_rng(0)
    for i in range(n):
        Image.fromarray(
            rng.integers(0, 255, (200 + 10 * (i % 3), 300, 3), dtype=np.uint8)
        ).save(src / f"img{i}.png")
    return src


def test_bulk_process_directory(tmp_path):
    src = _make_dir(tmp_path)
    out = tmp_path / "out"
    summary = bulk_process(
        str(src), str(out), "w_100,h_80,c_1", out_format="jpg", workers=4
    )
    assert summary["images"] == 6 and summary["failed"] == 0
    outs = sorted(os.listdir(out))
    assert outs == [f"img{i}.jpg" for i in range(6)]
    for name in outs:
        im = Image.open(out / name)
        assert im.size == (100, 80)
    # same-geometry files shared vmapped launches
    assert summary["batches"] <= summary["images"]


def test_bulk_cli_and_bad_file(tmp_path, capsys):
    src = _make_dir(tmp_path, n=3)
    (src / "broken.jpg").write_bytes(b"not an image")
    out = tmp_path / "o2"
    rc = main([
        "--src", str(src), "--out", str(out),
        "--options", "w_50", "--format", "png", "--workers", "2",
    ])
    assert rc == 1  # the broken file is reported as failed
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["images"] == 3 and summary["failed"] == 1
    assert sorted(os.listdir(out)) == [f"img{i}.png" for i in range(3)]


def test_bulk_matches_serving_transform_for_post_pass_options(tmp_path):
    """Bulk routes through ImageHandler.transform_bytes — the serving
    pipeline — so options the old bulk path silently skipped (smart-crop,
    st_0 metadata graft) now produce byte-identical output to serving."""
    from flyimg_tpu.appconfig import AppParameters
    from flyimg_tpu.service.handler import ImageHandler
    from flyimg_tpu.service.output_image import OutputSpec
    from flyimg_tpu.spec.options import OptionsBag

    src = _make_dir(tmp_path, n=1)
    out = tmp_path / "out"
    opts = "w_120,h_90,c_1,smc_1"
    summary = bulk_process(
        str(src), str(out), opts, out_format="jpg", workers=1
    )
    assert summary["failed"] == 0
    bulk_bytes = (out / "img0.jpg").read_bytes()

    handler = ImageHandler(storage=None, params=AppParameters())
    spec = OutputSpec(name="x.jpg", extension="jpg", mime="image/jpeg")
    serve_bytes = handler.transform_bytes(
        (src / "img0.png").read_bytes(), OptionsBag(opts), spec
    )
    assert bulk_bytes == serve_bytes


def test_bulk_retries_transient_timeouts_once(tmp_path, monkeypatch):
    """A device-wait timeout gets ONE sequential retry; a persistent timeout still counts as
    failed. Injects concurrent.futures.TimeoutError — the type
    Future.result(timeout=) actually raises, which is NOT the builtin
    TimeoutError on Python 3.10."""
    from concurrent.futures import TimeoutError as FuturesTimeout

    from flyimg_tpu.service.handler import ImageHandler

    src = _make_dir(tmp_path, n=3)
    out = tmp_path / "out"
    real = ImageHandler.transform_bytes
    calls: dict = {}

    def flaky(self, data, options, spec):
        n = calls[spec.name] = calls.get(spec.name, 0) + 1
        # img0 flakes once then recovers; img2 times out forever; img1
        # succeeds outright (if every first call timed out, the
        # all-timed-out bail below would correctly skip the retry pass)
        if (spec.name == "img0.png" and n == 1) or spec.name == "img2.png":
            raise FuturesTimeout("injected device wait expiry")
        return real(self, data, options, spec)

    monkeypatch.setattr(ImageHandler, "transform_bytes", flaky)
    summary = bulk_process(
        str(src), str(out), "w_50", out_format="png", workers=2
    )
    assert summary["failed"] == 1  # img2: timed out on retry too
    assert summary["images"] == 2
    assert sorted(os.listdir(out)) == ["img0.png", "img1.png"]
    assert calls["img0.png"] == 2  # flaked once, recovered on retry
    assert calls["img2.png"] == 2  # exactly one retry, no loops


def test_bulk_skips_retry_pass_when_every_job_times_out(tmp_path, monkeypatch):
    """All-timed-out means the device is down, not hiccuping: the retry
    pass must bail instead of serializing N more bounded waits."""
    from concurrent.futures import TimeoutError as FuturesTimeout

    from flyimg_tpu.service.handler import ImageHandler

    src = _make_dir(tmp_path, n=3)
    out = tmp_path / "out"
    calls: dict = {}

    def dead(self, data, options, spec):
        calls[spec.name] = calls.get(spec.name, 0) + 1
        raise FuturesTimeout("device down")

    monkeypatch.setattr(ImageHandler, "transform_bytes", dead)
    summary = bulk_process(
        str(src), str(out), "w_50", out_format="png", workers=2
    )
    assert summary["failed"] == 3 and summary["images"] == 0
    assert all(n == 1 for n in calls.values())  # no retry pass ran


# (options, container): a static extent, a fit whose output is bucketed and
# sliced, a quarter turn behind a resample, an extent pad with a conv post-op
_MIXED_JOBS = [
    ("w_100,h_80,c_1", "jpg"),
    ("w_90", "png"),
    ("r_90,w_80", "jpg"),
    ("w_64,h_64,ett_80x80,bg_red,sh_2x1", "png"),
]


@pytest.mark.parametrize("opts,fmt", _MIXED_JOBS)
def test_bulk_mixed_directory_matches_transform_bytes_one_by_one(
        tmp_path, opts, fmt):
    """A directory of mixed sizes and two source formats through launches
    of at most four, part-filled ones among them, gives for every file the
    bytes ``transform_bytes`` gives for it sent alone, a launch of one:
    what a file's launch holds beside it does not show. (Against
    ``run_plan`` a fit whose output is bucketed may differ by one level in
    a pixel of some ten thousand: another program shape, another
    contraction order. tests/test_batcher.py holds the two together.)"""
    from flyimg_tpu.appconfig import AppParameters
    from flyimg_tpu.runtime.batcher import BatchController
    from flyimg_tpu.service.handler import ImageHandler
    from flyimg_tpu.service.output_image import EXT_TO_MIME, OutputSpec
    from flyimg_tpu.spec.options import OptionsBag

    src = tmp_path / "src"
    src.mkdir()
    rng = np.random.default_rng(7)
    # five files share the 256 x 384 input bucket: more than one launch of four
    sizes = [(300, 200), (280, 210), (310, 190), (290, 205), (300, 200),
             (200, 300), (256, 256)]
    for i, (w, h) in enumerate(sizes):
        pixels = rng.integers(0, 255, (h, w, 3), dtype=np.uint8)
        if i % 2:
            Image.fromarray(pixels).save(src / f"f{i}.jpg", quality=92)
        else:
            Image.fromarray(pixels).save(src / f"f{i}.png")
    out = tmp_path / "out"
    # full at four, or whatever has gathered 50 ms after the oldest came
    ctl = BatchController(max_batch=4, deadline_ms=50.0)
    try:
        summary = bulk_process(str(src), str(out), opts, out_format=fmt,
                               workers=len(sizes), batcher=ctl)
    finally:
        ctl.close()
    assert summary["images"] == len(sizes) and summary["failed"] == 0
    assert 2 <= summary["batches"] <= len(sizes)

    # one caller, an idle executor: every file is a lone launch
    lone = BatchController(max_batch=4, deadline_ms=50.0)
    alone = ImageHandler(storage=None, params=AppParameters(), batcher=lone)
    try:
        for name in sorted(os.listdir(src)):
            stem = os.path.splitext(name)[0]
            spec = OutputSpec(name=f"{stem}.{fmt}", extension=fmt,
                              mime=EXT_TO_MIME[fmt])
            expected = alone.transform_bytes(
                (src / name).read_bytes(), OptionsBag(opts), spec)
            assert (out / f"{stem}.{fmt}").read_bytes() == expected, name
        assert lone.stats()["batches"] == len(sizes)
    finally:
        lone.close()
