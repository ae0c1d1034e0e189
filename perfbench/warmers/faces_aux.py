"""Warmer ``faces_aux``: the two families of programs ``fb_1`` launches
beyond the batched transform, built before the first request: the BlazeFace
detector's forward pass (``models/blazeface.py`` ``_forward``) at every
padded batch a detection launch can have, and the pixelation program
(``ops/pixelate.py`` ``_pixelate_batch``) at every padded batch of the
rendition's bucket. The post-pass of ``fb_1`` submits one detection item an
image to the device controller and then, where a face was found, one
pixelation item; items that arrive while a launch runs form the next one,
so a launch of any size from 1 to the controller's ``batch_max_size`` can
come up inside the window (``compiles_in_window`` has the limit 0). A
detection launch of ``n`` images runs its ``6 n`` views in chunks of 64,
each padded up the power-of-two ladder; a pixelation launch runs in chunks
of ``pixelate.MAX_BATCH``.

The programs are reached through the program's own path: a detection item
made by the backend's ``prepare_face_work`` from a blank rendition of the
size the configuration's plan gives, handed ``n`` times to the handler's own
runner (``ImageHandler._face_detect_launch``), for the smallest ``n`` that
reaches each padded size; a pixelation item of the same rendition with one
box, handed to ``_face_pixelate_launch``. So the views, the chunking, the
bucket and the padding are the program's, not a copy of them. Both are plain
``jax.jit`` functions with no handle to compile through, so each size is run
once on the blank item. The checkpoint is loaded when the handler is built
(``face_backend: blazeface`` in the configuration's parameters), before this
warmer runs.

Before that, two refusals, each a ``RuntimeError`` that ``run.py`` turns
into exit code 4 in the first seconds of set-up (list this warmer first in
``"warm"``):

- a program without the batched path (no ``_face_detect_launch`` or
  ``_face_pixelate_launch`` on its handler: before PR 36 ``fb_1`` pixelated
  with eager operations on the caller's thread, outside the device
  controller, compiling per shape at first use): it cannot run this
  configuration as a cell;
- ``launches_apart`` sends one detection item through the device controller
  as the post-pass does and reads the transform launches' series as the
  harness does (``cell.launch_sizes``): a program that observes its aux
  launches there cannot be read in a cell of this configuration
  (``warmers/smartcrop_aux.py`` says why).

Imports the program, inside its functions: loading this file imports nothing
of it (``manifest.bind`` loads it before the backend starts).
"""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List

import numpy as np

from perfbench.harness import system

def blank_rendition(config: Dict[str, Any], params: Any) -> np.ndarray:
    """A blank frame of the size the configuration's plan renders to."""
    from flyimg_tpu.ops.compose import plan_layout
    from flyimg_tpu.spec.plan import build_plan

    frame = config["frame"]
    plan = build_plan(system.options_bag(config, params), frame["width"], frame["height"])
    if not plan.face_blur:
        raise ValueError(f"options {config['options']['url']!r} have no fb_1: nothing for this warmer to build")
    out_h, out_w = (int(v) for v in plan_layout(plan).out_true)
    return np.zeros((out_h, out_w, 3), np.uint8)


def items_for_every_padded_size(views_per_item: int, max_items: int, chunk: int) -> Dict[int, int]:
    """``{padded size: the fewest items whose launch runs a chunk of it}``
    over launches of 1 to ``max_items`` items of ``views_per_item`` inputs
    each, run in chunks of ``chunk`` padded up the program's ladder."""
    from flyimg_tpu.ops.compose import bucket_batch

    fewest: Dict[int, int] = {}
    for n in range(1, max_items + 1):
        total = n * views_per_item
        sizes = {chunk} if total >= chunk else set()
        if total % chunk:
            sizes.add(bucket_batch(total % chunk))
        for size in sizes:
            fewest.setdefault(size, n)
    return fewest


def launches_apart(sut: "system.System", item: Any) -> bool:
    """One detection item through the device controller, alone (it goes at
    once: nothing else is pending); whether the series the harness reads
    transform launches from stayed still."""
    from perfbench.harness import cell

    before = sut.counters()
    sut.batcher.submit_aux(("perfbench", "probe"), item, sut.handler._face_detect_launch).result(timeout=600)
    return not cell.launch_sizes(before, sut.counters())


def warm(sut: "system.System", config: Dict[str, Any], mix: Dict[str, Any]) -> Dict[str, Any]:
    handler = sut.handler
    if not (hasattr(handler, "_face_detect_launch") and hasattr(handler, "_face_pixelate_launch")):
        raise RuntimeError(
            "this program has no batched face path (ImageHandler._face_detect_launch / _face_pixelate_launch): "
            "its fb_1 pixelates with eager operations on the caller's thread, outside the device controller; "
            f"it cannot run configuration {config['name']} as a cell")
    from flyimg_tpu.ops import pixelate
    from flyimg_tpu.runtime.batcher import MAX_BATCH_BUCKET

    backend = handler._faces()
    if not hasattr(backend, "prepare_face_work"):
        raise RuntimeError(f"face backend {type(backend).__name__} has no batched detection: configuration "
                           f"{config['name']} names face_backend blazeface in its parameters")
    blank = blank_rendition(config, sut.params)
    item = backend.prepare_face_work(blank)
    if not launches_apart(sut, item):
        raise RuntimeError(
            "this program observes an aux (face detection) launch in the transform launches' series "
            "(flyimg_batch_bucket_size{controller=\"device\"}): launch sizes, images_per_launch and "
            "resample_roofline cannot be read in a cell of configuration "
            f"{config['name']}; it needs aux launches observed under a label of their own")
    top = int(sut.params.by_key("batch_max_size", 64))
    views = len(item.inputs)
    forwards = items_for_every_padded_size(views, top, MAX_BATCH_BUCKET)
    pixel_item = pixelate.prepare_work(blank, [(0, 0, pixelate.PIXELATE_FACTOR, pixelate.PIXELATE_FACTOR)])
    pixels = items_for_every_padded_size(1, top, pixelate.MAX_BATCH)

    def detect(n: int) -> float:
        t = time.perf_counter()
        handler._face_detect_launch([item] * n)
        return time.perf_counter() - t

    def blur(n: int) -> float:
        t = time.perf_counter()
        handler._face_pixelate_launch([pixel_item] * n)
        return time.perf_counter() - t

    jobs: List[Any] = [(detect, n) for n in forwards.values()] + [(blur, n) for n in pixels.values()]
    with ThreadPoolExecutor(max_workers=len(jobs)) as pool:
        seconds = list(pool.map(lambda job: job[0](job[1]), jobs))
    return {"rendition": list(blank.shape[:2]), "views_per_image": views, "pixelate_bucket": list(pixel_item.bucket),
            "forward_seconds_by_padded_views": dict(zip(map(str, forwards), seconds[:len(forwards)])),
            "pixelate_seconds_by_padded_batch": dict(zip(map(str, pixels), seconds[len(forwards):]))}


def rehearse(config: Dict[str, Any], batches, sharding):
    """For ``rehearse_compile.py``: the pixelation program of the
    configuration's rendition bucket and the detector's forward compiled
    for a described chip; one line of ``memory_analysis()`` each. A
    compile, not a run. ``batches`` above the programs' own ceilings are
    cut to them."""
    import jax

    from flyimg_tpu.appconfig import AppParameters
    from flyimg_tpu.models import blazeface
    from flyimg_tpu.ops import pixelate
    from flyimg_tpu.runtime.batcher import MAX_BATCH_BUCKET

    blank = blank_rendition(config, AppParameters(dict(config.get("parameters") or {})))
    bh, bw = pixelate.prepare_work(blank, []).bucket
    gib = 2.0 ** 30

    def line(what: str, compiled, t: float) -> str:
        mem = compiled.memory_analysis()
        return (f"{what}: arguments {mem.argument_size_in_bytes / gib:.3f} GiB, temporaries "
                f"{mem.temp_size_in_bytes / gib:.3f} GiB, output {mem.output_size_in_bytes / gib:.3f} GiB, "
                f"compile {time.perf_counter() - t:.0f} s on this machine")

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    for batch in sorted({min(int(b), pixelate.MAX_BATCH) for b in batches}):
        t = time.perf_counter()
        compiled = pixelate._pixelate_batch.lower(
            spec((batch, bh, bw * 3), np.uint8), spec((batch, 2), np.int32),
            spec((batch, pixelate.MAX_BOXES, 4), np.int32)).compile()
        yield line(f"pixelate batch {batch} [{bh}, {bw}x3]", compiled, t)
    params = jax.tree_util.tree_map(lambda leaf: spec(leaf.shape, leaf.dtype),
                                    jax.eval_shape(blazeface.init_params, jax.random.PRNGKey(0)))
    for batch in sorted({min(int(b), MAX_BATCH_BUCKET) for b in batches}):
        t = time.perf_counter()
        size = blazeface.INPUT_SIZE
        compiled = blazeface._forward.lower(params, spec((batch, size, size, 3), np.float32)).compile()
        yield line(f"blazeface forward of {batch} views", compiled, t)
