#!/usr/bin/env python3
"""Rehearsal without the chip: compile each configuration's programs, as its
warmers build them, for a described TPU v5e and print ``memory_analysis()``.

    JAX_PLATFORMS=cpu python3 perfbench/rehearse_compile.py [batch ...]

A compile, not a run: it says whether a launch fits the chip and how long a
cold compile takes on this machine, and nothing about time on the chip. A
warmer takes part by having ``rehearse(config, batches, sharding)``.
"""

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv) -> int:
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from perfbench.harness import manifest

    jax.config.update("jax_enable_compilation_cache", False)
    batches = [int(a) for a in argv] or [1, 64]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    doc = manifest.load_manifest()
    for cfg in doc["configs"]:
        config = manifest.load_config(doc, cfg["name"])
        roots = manifest.plug_roots(doc, cfg["name"])
        for name in config["warm"]:
            warmer = manifest.load_plug("warmers", name, ("warm",), roots)
            if not hasattr(warmer, "rehearse"):
                continue
            for line in warmer.rehearse(config, batches, chip):
                print(f"{cfg['name']} warmer {name} {line}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
