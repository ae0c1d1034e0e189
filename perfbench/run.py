#!/usr/bin/env python3
"""The benchmark's command:

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on the machine that holds the chips. The last
line of standard output is the result; everything else goes to standard
error. Without an accelerator, or outside a checkout of the program, it
exits non-zero and prints no result.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ns = ap.parse_args(argv)

    from perfbench.harness import cell, manifest

    try:
        doc = manifest.load_manifest()
        config = manifest.workload(doc, ns.workload)["config"]
        # what the configuration names is loaded and held to its rules
        # before the program or the backend is
        manifest.bind(doc, config, manifest.load_config(doc, config))
        import flyimg_tpu  # noqa: F401  the system under test
    except (OSError, ImportError, manifest.ManifestError) as exc:
        print(f"perfbench: {exc}", file=sys.stderr)
        return 3
    try:
        result = cell.run_cell(doc, ns.workload, ns.seed, ns.seconds, bool(ns.trace),
                               t_process=T_PROCESS)
    except RuntimeError as exc:
        print(f"perfbench: {exc}", file=sys.stderr)
        return 4
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    # daemon caller threads and the program's drain threads may still hold
    # references; everything is stopped and joined above, so leave at once
    os._exit(code)
