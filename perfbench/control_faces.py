#!/usr/bin/env python3
"""The controls of ``correct`` for a face-blur configuration, read at its own
size, beside those of ``control.py`` (which lowers the resample's operands):

    python3 perfbench/control_faces.py --config <name> --seeds 1,2,3 [--images 16] [--toy]

Each control is the configuration's reference put in the program's place
with one thing wrong, its answers encoded by the reference's own encoder and
judged as the program's are:

``sound``           nothing wrong: has to pass.
``detector_bf16``   the detector's products in bfloat16, which is what the
                    configuration states (``guarantees.detector_precision``):
                    has to pass.
``detector_fp8``    the detector's products in float8_e4m3fn, the step below.
``no_head8``        the detector without the anchors of its 8x8 map.
``shifted``         every box moved by one block (10 px) right and down.
``not_pixelated``   no pixelation at all.
``resample_fp8``    the resample's operands in float8_e4m3fn (``control.py``'s
                    ``fp8``), the boxes found in that render.

One render of each original serves every control but the last. Needs no
accelerator: the reference is numpy.
"""

import argparse
import copy
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from perfbench.harness import compare, corpus, manifest, plain  # noqa: E402

KINDS = ("sound", "detector_bf16", "detector_fp8", "no_head8", "shifted", "not_pixelated", "resample_fp8")


def controlled(ref, frame: np.ndarray, kind: str) -> np.ndarray:
    """The reference's answer to the float32 rendition ``frame`` with the
    fault ``kind`` in its face pass."""
    u8 = plain.to_u8(frame)
    if kind == "not_pixelated":
        return u8
    operands = {"detector_bf16": "bfloat16", "detector_fp8": "float8_e4m3fn"}.get(kind, "float32")
    boxes = [k["box"] for k in ref.detect(u8, operands=operands, head8=kind != "no_head8") if k["box"]]
    if kind == "shifted":
        boxes = [(x0 + ref.BLOCK, y0 + ref.BLOCK, x1 + ref.BLOCK, y1 + ref.BLOCK) for x0, y0, x1, y1 in boxes]
    return ref.pixelate(u8, boxes)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--images", type=int, default=None)
    ap.add_argument("--toy", action="store_true")
    ap.add_argument("--kinds", default=",".join(KINDS))
    ns = ap.parse_args(argv)
    doc = manifest.load_manifest()
    config = copy.deepcopy(manifest.load_config(doc, ns.config))
    if ns.toy:
        manifest.apply_toy(config)
    bound = manifest.bind(doc, ns.config, config)
    ref = bound.reference
    kinds = ns.kinds.split(",")
    for seed in (int(s) for s in ns.seeds.split(",")):
        originals = corpus.make_corpus(bound.make_image, seed, config["frame"],
                                       ns.images or config["corpus"]["images"])
        answers = {kind: {} for kind in kinds}
        for i, data in enumerate(originals):
            frame = ref.render_fill(data, bound.options)
            for kind in kinds:
                if kind == "resample_fp8":
                    out = plain.to_u8(ref.render(data, bound.options, "float8_e4m3fn"))
                else:
                    out = controlled(ref, frame, kind)
                answers[kind][(i, kind)] = plain.encode_jpeg(out, 90)
        judge = compare.Judge(bound, originals)
        for kind in kinds:
            verdict = judge.judge(answers[kind])
            print(json.dumps({"config": ns.config, "seed": seed, "control": kind,
                              "correct": verdict["correct"],
                              "numbers": {k: v["value"] for k, v in verdict["numbers"].items()},
                              "rms_err": verdict["rms_err_not_compared"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
