"""Warmer ``transform16``: the batched transform program of 16-bit samples
at every launch size the traffic can produce, compiled (or read from the
compile cache) without running it, several at once, as ``transform`` does
for 8-bit ones.

Before that, ``keeps_16_bits`` sends one small 16-bit frame through the
device controller as the handler does (``batcher.submit``) and reads what
comes back. A program that cannot stage a 16-bit launch (one that copies
the frame into an 8-bit block, or answers ``uint8``, as every program
before 16-bit samples did) cannot be run in a cell of a 16-bit
configuration: its answers would be 8-bit and its window wrong from the
first launch. Such a program is refused in the first seconds of set-up
(list this warmer first in ``"warm"``), with a ``RuntimeError`` that
``run.py`` turns into exit code 4.

The programs are built exactly as the batcher builds them: the same helpers
derive the group's shapes (``group_args``), and ``build_batched_program``
is called with every argument in the batcher's positions, the sample depth
last. Imports the program inside its functions: loading this file imports
nothing of it (``manifest.bind`` loads it before the backend starts).
"""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict

import numpy as np

from perfbench.harness import system

DEPTH = 16


def group_args(config: Dict[str, Any], params: Any):
    """The arguments ``BatchController.submit`` derives for a full frame of
    the configuration under its options: what keys the batched program.
    Mirrors ``submit`` with the program's own helpers; if it ever drifts,
    the pre-roll compiles and the run says so (``compiles_in_preroll``)."""
    from flyimg_tpu.ops.compose import _bucket_dim, plan_layout
    from flyimg_tpu.ops.resample import kernel_mode, select_band_taps
    from flyimg_tpu.spec.plan import build_plan

    width, height = config["frame"]["width"], config["frame"]["height"]
    plan = build_plan(system.options_bag(config, params), width, height)
    layout = plan_layout(plan)
    in_shape = (_bucket_dim(height), _bucket_dim(width))
    if plan.extent is not None:
        resample_out = layout.resample_out
    else:
        resample_out = (_bucket_dim(layout.resample_out[0], 64),
                        _bucket_dim(layout.resample_out[1], 64))
    band = select_band_taps(kernel_mode(), plan.filter_method, in_shape,
                            layout.span_y, layout.span_x, layout.out_true)
    return plan, layout, in_shape, resample_out, band


def _specs(batch: int, in_shape, **placed):
    """What ``ProgramHandle.stage`` is given, as abstract values: the images
    ``u16[batch, h, w, 3]`` and the four per-image pairs. ``placed`` is
    empty for the attached device, or ``sharding=`` a described one's."""
    import jax

    f32 = np.float32
    return (jax.ShapeDtypeStruct((batch,) + tuple(in_shape) + (3,), np.uint16, **placed),
            jax.ShapeDtypeStruct((batch, 2), f32, **placed),
            jax.ShapeDtypeStruct((batch, 2), f32, **placed),
            jax.ShapeDtypeStruct((batch, 2), f32, **placed),
            jax.ShapeDtypeStruct((batch, 2), f32, **placed))


def _build(batch: int, config: Dict[str, Any], params: Any):
    from flyimg_tpu.runtime.batcher import build_batched_program

    plan, layout, in_shape, resample_out, band = group_args(config, params)
    handle = build_batched_program(
        batch, in_shape, resample_out, layout.pad_canvas,
        layout.pad_offset, plan.device_plan(), None, False, band, DEPTH,
    )
    return handle, in_shape, resample_out


def keeps_16_bits(sut: "system.System") -> bool:
    """A 64x48 frame of 16-bit ramps, halved through the device controller:
    whether the answer is ``uint16`` and, away from the edges, within 8
    levels of 65,535 of the ramps' 2x2 means (a resample reproduces a
    linear ramp exactly; an 8-bit path answers ``uint8``, wraps, or is out
    by up to 128 levels)."""
    from flyimg_tpu.spec.options import OptionsBag
    from flyimg_tpu.spec.plan import build_plan

    y, x = np.mgrid[0:48, 0:64].astype(np.float64)
    frame = np.rint(np.stack([1000.0 + 700.0 * x + 300.0 * y,
                              62000.0 - 500.0 * x - 600.0 * y,
                              5000.0 + 37.0 * x + 1111.0 * y], axis=-1)).astype(np.uint16)
    plan = build_plan(OptionsBag("w_32,h_32"), 64, 48)
    out = sut.batcher.submit(frame, plan).result(timeout=600)
    if out.dtype != np.uint16 or out.shape[:2] != (24, 32):
        return False
    expect = frame.reshape(24, 2, 32, 2, 3).mean(axis=(1, 3))
    inner = (slice(3, -3), slice(3, -3))
    return float(np.abs(out[inner].astype(np.float64) - expect[inner]).max()) <= 8.0


def warm(sut: "system.System", config: Dict[str, Any], mix: Dict[str, Any]) -> Dict[str, Any]:
    """Refuses a program without 16-bit launches, then builds every size of
    the mix's ``warm_launch_sizes``, each through its own handle for what
    ``stage`` is given; returns what it built, for the run's log."""
    try:
        kept = keeps_16_bits(sut)
    except (TypeError, ValueError) as exc:
        kept, why = False, f"{type(exc).__name__}: {exc}"
    else:
        why = "its answer to a 16-bit frame is not a 16-bit resample of it"
    if not kept:
        raise RuntimeError(
            "this program cannot stage a 16-bit launch through its device controller "
            f"({why}): a cell of configuration {config['name']} would answer 8 bits")
    sizes = mix["warm_launch_sizes"]

    def one(batch: int) -> float:
        t = time.perf_counter()
        handle, in_shape, _ = _build(batch, config, sut.params)
        handle.precompile(_specs(batch, in_shape))
        return time.perf_counter() - t

    with ThreadPoolExecutor(max_workers=max(len(sizes), 1)) as pool:
        seconds = list(pool.map(one, sizes))
    _, in_shape, resample_out = _build(sizes[0], config, sut.params)
    return {"in_shape": list(in_shape), "resample_out": list(resample_out), "bits": DEPTH,
            "seconds_by_launch_size": dict(zip(map(str, sizes), seconds))}


def rehearse(config: Dict[str, Any], batches, sharding):
    """For ``rehearse_compile.py``: the same programs compiled for a
    described chip (``sharding``); yields one line of ``memory_analysis()``
    for each batch size. A compile, not a run."""
    from unittest import mock

    import jax

    from flyimg_tpu.appconfig import AppParameters

    params = AppParameters(dict(config.get("parameters") or {}))
    gib = 2.0 ** 30
    for batch in batches:
        t = time.perf_counter()
        # the program pins its output to the first local device, which is
        # the CPU here: the described chip stands in for it while it builds
        with mock.patch.object(jax, "local_devices", lambda *a, **k: sorted(
                sharding.device_set, key=lambda d: d.id)):
            handle, in_shape, resample_out = _build(batch, config, params)
        handle.precompile(_specs(batch, in_shape, sharding=sharding))
        mem = handle._compiled.memory_analysis()
        yield (f"batch {batch} u16{list(in_shape)}->{list(resample_out)} in {handle.pieces} pieces: "
               f"arguments {mem.argument_size_in_bytes / gib:.3f} GiB, temporaries "
               f"{mem.temp_size_in_bytes / gib:.3f} GiB, output {mem.output_size_in_bytes / gib:.3f} GiB, "
               f"compile {time.perf_counter() - t:.0f} s on this machine")
