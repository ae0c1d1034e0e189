#!/usr/bin/env python3
"""The controls of ``correct``, read at a configuration's own size:

    python3 perfbench/control.py --config <name> --seeds 1,2,3 [--images 16] [--toy]

``fp8``     the reference put in the program's place with the operands of its
            two resample passes in float8_e4m3fn, the nearest precision below
            the bfloat16 the configurations state; its answers are encoded by
            the reference's own encoder and judged as the program's are.
``bf16``, ``int8``  for the record: bfloat16 is what the program states, and
            int8 with per-tensor scales reads within the output JPEG's noise.

Needs no accelerator: the reference is numpy.
"""

import argparse
import copy
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from perfbench.harness import compare, corpus, manifest, reference  # noqa: E402
from perfbench.harness.cell import apply_toy  # noqa: E402


def answers_for(config, originals, operands):
    opts = reference.parse_options(config["options"]["url"])
    return {(i, operands): reference.encode_jpeg(
        reference.to_u8(reference.render(data, opts, operands)), 90)
        for i, data in enumerate(originals)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--images", type=int, default=None)
    ap.add_argument("--toy", action="store_true")
    ap.add_argument("--kinds", default="fp8,bf16,int8")
    ns = ap.parse_args(argv)
    doc = manifest.load_manifest()
    config = copy.deepcopy(manifest.load_config(doc, ns.config))
    if ns.toy:
        apply_toy(config)
    kinds = {"fp8": "float8_e4m3fn", "bf16": "bfloat16", "int8": "int8"}
    for seed in (int(s) for s in ns.seeds.split(",")):
        originals = corpus.make_corpus(seed, config["frame"], ns.images or config["corpus"]["images"])
        judge = compare.Judge(config, originals)
        for kind in ns.kinds.split(","):
            verdict = judge.judge(answers_for(config, originals, kinds[kind]))
            print(json.dumps({"config": ns.config, "seed": seed, "control": kind,
                              "correct": verdict["correct"],
                              "numbers": {k: v["value"] for k, v in verdict["numbers"].items()},
                              "rms_err": verdict["rms_err_not_compared"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
