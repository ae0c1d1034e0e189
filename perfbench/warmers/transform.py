"""Warmer ``transform``: the batched transform program of every launch size
the traffic can produce, compiled (or read from the compile cache) without
running it, several at once: a first request must never wait on a compile
longer than the program's own time limits.

Imports the program, inside its functions: loading this file imports nothing
of it (``manifest.bind`` loads it before the backend starts).
"""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict

import numpy as np

from perfbench.harness import system


def group_args(config: Dict[str, Any], params: Any):
    """The arguments ``BatchController.submit`` derives for a full frame of
    the configuration under its options: what keys the batched program.
    Mirrors ``submit`` with the program's own helpers; if it ever drifts, the
    pre-roll compiles and the run says so (``compiles_in_preroll``)."""
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
    ``[batch, h, w, 3]`` and the four per-image pairs. ``placed`` is empty
    for the attached device, or ``sharding=`` a described one's."""
    import jax

    f32 = np.float32
    return (jax.ShapeDtypeStruct((batch,) + tuple(in_shape) + (3,), np.uint8, **placed),
            jax.ShapeDtypeStruct((batch, 2), f32, **placed),
            jax.ShapeDtypeStruct((batch, 2), f32, **placed),
            jax.ShapeDtypeStruct((batch, 2), f32, **placed),
            jax.ShapeDtypeStruct((batch, 2), f32, **placed))


def warm(sut: "system.System", config: Dict[str, Any], mix: Dict[str, Any]) -> Dict[str, Any]:
    """Every size of the mix's ``warm_launch_sizes``, each compiled through
    its own handle for what ``stage`` is given; returns what it built, for
    the run's log."""
    from flyimg_tpu.runtime.batcher import build_batched_program

    sizes = mix["warm_launch_sizes"]
    plan, layout, in_shape, resample_out, band = group_args(config, sut.params)

    def one(batch: int) -> float:
        t = time.perf_counter()
        handle = build_batched_program(
            batch, in_shape, resample_out, layout.pad_canvas,
            layout.pad_offset, plan.device_plan(), None, False, band,
        )
        handle.precompile(_specs(batch, in_shape))
        return time.perf_counter() - t

    with ThreadPoolExecutor(max_workers=max(len(sizes), 1)) as pool:
        seconds = list(pool.map(one, sizes))
    return {"in_shape": list(in_shape), "resample_out": list(resample_out),
            "seconds_by_launch_size": dict(zip(map(str, sizes), seconds))}


def rehearse(config: Dict[str, Any], batches, sharding):
    """For ``rehearse_compile.py``: the same programs compiled for a
    described chip (``sharding``); yields one line of ``memory_analysis()``
    for each batch size. A compile, not a run."""
    from flyimg_tpu.appconfig import AppParameters
    from flyimg_tpu.runtime.batcher import build_batched_program

    plan, layout, in_shape, resample_out, band = group_args(
        config, AppParameters(dict(config.get("parameters") or {})))
    gib = 2.0 ** 30
    for batch in batches:
        t = time.perf_counter()
        handle = build_batched_program(
            batch, in_shape, resample_out, layout.pad_canvas,
            layout.pad_offset, plan.device_plan(), None, False, band,
        )
        handle.precompile(_specs(batch, in_shape, sharding=sharding))
        mem = handle._compiled.memory_analysis()
        yield (f"batch {batch} {list(in_shape)}->{list(resample_out)}: "
               f"arguments {mem.argument_size_in_bytes / gib:.3f} GiB, temporaries "
               f"{mem.temp_size_in_bytes / gib:.3f} GiB, output {mem.output_size_in_bytes / gib:.3f} GiB, "
               f"compile {time.perf_counter() - t:.0f} s on this machine")
