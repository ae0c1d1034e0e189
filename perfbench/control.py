#!/usr/bin/env python3
"""The controls of ``correct``, read at a configuration's own size:

    python3 perfbench/control.py --config <name> --seeds 1,2,3 [--images 16] [--toy]

``fp8``     the configuration's reference (``references/<name>.py``) put in the
            program's place with the operands of its render in float8_e4m3fn,
            the nearest precision below the bfloat16 the configurations state; its answers are encoded by
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

from perfbench.harness import compare, corpus, manifest, plain  # noqa: E402


# the nearest precision below the one a configuration states
# (``guarantees.precision``), of those the quantiser of ``plain.py`` holds
BELOW = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn", "float16": "float8_e4m3fn"}


def answers_for(bound, originals, operands):
    """The configuration's reference put in the program's place: its render
    of every original with operands of ``operands``, encoded by the
    reference's own encoder."""
    return {(i, operands): plain.encode_jpeg(
        plain.to_u8(bound.reference.render(data, bound.options, operands)), 90)
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
        manifest.apply_toy(config)
    bound = manifest.bind(doc, ns.config, config)
    kinds = {"fp8": "float8_e4m3fn", "bf16": "bfloat16", "int8": "int8"}
    for seed in (int(s) for s in ns.seeds.split(",")):
        originals = corpus.make_corpus(bound.make_image, seed, config["frame"],
                                       ns.images or config["corpus"]["images"])
        judge = compare.Judge(bound, originals)
        for kind in ns.kinds.split(","):
            verdict = judge.judge(answers_for(bound, originals, kinds[kind]))
            print(json.dumps({"config": ns.config, "seed": seed, "control": kind,
                              "correct": verdict["correct"],
                              "numbers": {k: v["value"] for k, v in verdict["numbers"].items()},
                              "rms_err": verdict["rms_err_not_compared"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
