"""Warmer ``smartcrop_aux``: the smart-crop scorer's two programs
(``models/smartcrop.py`` ``_batched_weighted`` and ``_batched_scores``) at
every batch size an aux launch can have, built before the first request: the
post-pass of ``smc_1`` submits one item per image to the device controller,
items that arrive while a launch runs form the next one, and a launch pads
its batch up the power-of-two ladder, so any size from 1 to the controller's
``batch_max_size`` can come up inside the window (``compiles_in_window`` has
the limit 0).

The programs are reached through the program's own path: a work item made
by ``prepare_work`` from a blank rendition of the size the configuration's
plan gives, handed ``n`` times to ``find_best_crops_batched``. So the work
bucket, the kernel bucket, the stride and the scale count are the program's,
not a copy of them. The scorer is a pair of ``jax.jit`` functions with no
handle to compile through, so each size is run once on the blank item: a few
milliseconds a size on the chip.

Before that, ``launches_apart`` sends one item through the device controller
as the post-pass does (``submit_aux``) and reads the transform launches'
series as the harness does (``cell.launch_sizes``). A program that observes
its aux launches there (before PR 31: ``controller="device"`` for both) cannot
be read in a cell of this configuration: 256 launches of one among four of
64, so ``images_per_launch`` and ``padded_slot_share`` read the mixture and
``resample_roofline`` reads nothing (``readers/trace_share.py`` takes the
other launches' sizes off the images done, and goes negative). Such a program
is refused, in the first seconds of set-up (list this warmer first in
``"warm"``), with a ``RuntimeError`` that ``run.py`` turns into exit code 4:
it cannot run this configuration as a cell, and says so where a half-read
line would stand.

Imports the program, inside its functions: loading this file imports nothing
of it (``manifest.bind`` loads it before the backend starts).
"""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List

import numpy as np

from perfbench.harness import system


def batch_sizes(params: Any) -> List[int]:
    """The padded sizes of an aux launch: powers of two up to the
    controller's ``batch_max_size``."""
    top = int(params.by_key("batch_max_size", 64))
    return [1 << k for k in range(top.bit_length()) if 1 << k <= top]


def blank_item(config: Dict[str, Any], params: Any):
    """The scorer's work item for a blank rendition of the configuration's
    frame under its options, as the handler's post-pass makes it."""
    from flyimg_tpu.models import smartcrop
    from flyimg_tpu.ops.compose import plan_layout
    from flyimg_tpu.spec.plan import build_plan

    frame = config["frame"]
    plan = build_plan(system.options_bag(config, params), frame["width"], frame["height"])
    if not plan.smart_crop:
        raise ValueError(f"options {config['options']['url']!r} have no smc_1: nothing for this warmer to build")
    out_h, out_w = (int(v) for v in plan_layout(plan).out_true)
    return smartcrop.prepare_work(np.zeros((out_h, out_w, 3), np.uint8))


def launches_apart(sut: "system.System", item: Any) -> bool:
    """One scoring item through the device controller, alone (it goes at
    once: nothing else is pending); whether the series the harness reads
    transform launches from stayed still."""
    from flyimg_tpu.models import smartcrop
    from perfbench.harness import cell

    before = sut.counters()
    sut.batcher.submit_aux(("perfbench", "probe"), item, smartcrop.find_best_crops_batched).result(timeout=600)
    return not cell.launch_sizes(before, sut.counters())


def warm(sut: "system.System", config: Dict[str, Any], mix: Dict[str, Any]) -> Dict[str, Any]:
    from flyimg_tpu.models import smartcrop

    item = blank_item(config, sut.params)
    if not launches_apart(sut, item):
        raise RuntimeError(
            "this program observes an aux (smart-crop scoring) launch in the transform launches' series "
            "(flyimg_batch_bucket_size{controller=\"device\"}): launch sizes, images_per_launch and "
            "resample_roofline cannot be read in a cell of configuration "
            f"{config['name']}; it needs aux launches observed under a label of their own")
    sizes = batch_sizes(sut.params)

    def one(batch: int) -> float:
        t = time.perf_counter()
        smartcrop.find_best_crops_batched([item] * batch)
        return time.perf_counter() - t

    with ThreadPoolExecutor(max_workers=len(sizes)) as pool:
        seconds = list(pool.map(one, sizes))
    return {"work": list(item.work.shape[:2]), "bucket": list(item.bucket), "scales": list(item.scales),
            "seconds_by_batch_size": dict(zip(map(str, sizes), seconds))}
