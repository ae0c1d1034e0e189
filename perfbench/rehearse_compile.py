#!/usr/bin/env python3
"""Rehearsal without the chip: compile each configuration's batched program
for a described TPU v5e and print ``memory_analysis()``.

    JAX_PLATFORMS=cpu python3 perfbench/rehearse_compile.py [batch ...]

A compile, not a run: it says whether a launch fits the chip and how long a
cold compile takes on this machine, and nothing about time on the chip.
"""

import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv) -> int:
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from flyimg_tpu.ops.compose import make_program_fn
    from perfbench.harness import manifest
    from perfbench.harness.system import System

    jax.config.update("jax_enable_compilation_cache", False)
    batches = [int(a) for a in argv] or [1, 64]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    doc = manifest.load_manifest()
    for cfg in doc["configs"]:
        config = manifest.load_json(os.path.join(ROOT, cfg["file"]))
        sut = System.__new__(System)  # the argument derivation only, no controllers
        from flyimg_tpu.appconfig import AppParameters

        params = AppParameters(dict(config.get("parameters") or {}))
        sut._options_keys = params.by_key("options_keys")
        sut._default_options = params.by_key("default_options")
        sut._separator = params.by_key("options_separator", ",")
        sut.options_str = config["options"]["url"]
        frame = config["frame"]
        plan, layout, in_shape, resample_out, band = sut._group_args(frame["width"], frame["height"])
        inner = make_program_fn(resample_out, layout.pad_canvas, layout.pad_offset,
                                plan.device_plan(), band_taps=band)
        for batch in batches:
            def sds(shape, dtype):
                return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
            args = (sds((batch,) + in_shape + (3,), np.uint8),) + tuple(
                sds((batch, 2), np.float32) for _ in range(4))
            t = time.perf_counter()
            compiled = jax.jit(jax.vmap(inner)).lower(*args).compile()
            mem = compiled.memory_analysis()
            gib = 2.0 ** 30
            print(f"{cfg['name']} batch {batch} {list(in_shape)}->{list(resample_out)}: "
                  f"arguments {mem.argument_size_in_bytes / gib:.3f} GiB, temporaries "
                  f"{mem.temp_size_in_bytes / gib:.3f} GiB, output {mem.output_size_in_bytes / gib:.3f} GiB, "
                  f"compile {time.perf_counter() - t:.0f} s on this machine", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
