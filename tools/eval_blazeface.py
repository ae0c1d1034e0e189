"""Held-out BlazeFace evaluation against the Haar oracle.

The round-4 accuracy gate was two photos (tests/test_blazeface.py) — a
smoke test. This tool evaluates at corpus scale: it composes a few
hundred HELD-OUT scenes with the same machinery the distillation used
(tools/train_blazeface.py harvest/paste; reference fixture photos as
face/background material) but a disjoint seed, runs the Haar oracle and
BlazeFace on every scene, and sweeps the score threshold into a
precision/recall/IoU curve. "Truth" is the Haar oracle's detections on
each composite — parity with the reference's own detector family is the
serving contract, not absolute face-detection accuracy.

Writes one JSON artifact (default benchmarks/blazeface_eval_r5.json)
whose operating-point row backs the serving-default decision recorded in
models/faces.py.

Usage: python tools/eval_blazeface.py [--n 300] [--seed 9090]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

SCENE = 256  # composite side in px: typical thumbnail-serving scale


def iou(a, b) -> float:
    ax, ay, aw, ah = a
    bx, by, bw, bh = b
    ix = max(0, min(ax + aw, bx + bw) - max(ax, bx))
    iy = max(0, min(ay + ah, by + bh) - max(ay, by))
    inter = ix * iy
    union = aw * ah + bw * bh - inter
    return inter / union if union else 0.0


def compose_scene(rng, faces, backgrounds):
    """One held-out composite: background + 0..3 pasted face crops."""
    from PIL import Image

    from train_blazeface import _canvas

    canvas = _canvas(rng, backgrounds, SCENE).astype(np.float32)
    for _ in range(rng.integers(0, 4)):
        crop, (fx, fy, fw, fh) = faces[rng.integers(0, len(faces))]
        face_frac = rng.uniform(0.18, 0.5)
        scale = face_frac * SCENE / max(fw, fh)
        ch, cw = crop.shape[:2]
        sw, sh = max(int(cw * scale), 8), max(int(ch * scale), 8)
        patch = np.asarray(
            Image.fromarray(crop.astype(np.uint8)).resize((sw, sh)),
            np.float32,
        )
        px = rng.integers(0, max(SCENE - sw, 1))
        py = rng.integers(0, max(SCENE - sh, 1))
        x1, y1 = min(px + sw, SCENE), min(py + sh, SCENE)
        canvas[py:y1, px:x1] = patch[: y1 - py, : x1 - px]
    return canvas.astype(np.uint8)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=300)
    ap.add_argument("--seed", type=int, default=9090,
                    help="held out: training used seed 0 + mining rounds")
    ap.add_argument("--out", default="benchmarks/blazeface_eval_r5.json")
    ap.add_argument("--match-iou", type=float, default=0.35,
                    help="IoU at which a BlazeFace box matches a Haar box "
                         "(the serving gate's threshold)")
    ap.add_argument("--thresholds",
                    default="0.1,0.2,0.3,0.4,0.5,0.6,0.7,0.8",
                    help="comma list of score thresholds to sweep")
    args = ap.parse_args()

    from train_blazeface import DEFAULT_PHOTO_DIRS, harvest_faces

    from flyimg_tpu.models import blazeface as bf
    from flyimg_tpu.models import haar

    if not haar.available():
        print(json.dumps({"error": "haar cascades unavailable"}))
        return 1
    # the Haar harvest over the reference photo dirs costs ~30 min on this
    # host — cache it (material only depends on the fixture photos)
    cache = os.path.join(REPO, "var", "tmp", "bf_eval_harvest.npz")
    faces = backgrounds = None
    if os.path.exists(cache):
        try:
            z = np.load(cache, allow_pickle=True)
            faces = list(z["faces"])
            backgrounds = list(z["backgrounds"])
            print(f"# harvest cache hit: {len(faces)} faces", file=sys.stderr)
        except Exception:
            faces = None
    if not faces:
        faces, backgrounds = harvest_faces(DEFAULT_PHOTO_DIRS)
        os.makedirs(os.path.dirname(cache), exist_ok=True)
        np.savez_compressed(
            cache,
            faces=np.array(faces, dtype=object),
            backgrounds=np.array(backgrounds, dtype=object),
        )
    if not faces:
        print(json.dumps({"error": "no face material harvested"}))
        return 1
    params = bf.load_checkpoint(bf_packaged_checkpoint())

    rng = np.random.default_rng(args.seed)
    scenes = [compose_scene(rng, faces, backgrounds) for _ in range(args.n)]

    t0 = time.time()
    truth = []
    for i, s in enumerate(scenes):
        truth.append(haar.detect_faces(s))
        if (i + 1) % 50 == 0:
            print(f"# haar truth {i + 1}/{len(scenes)} "
                  f"({time.time() - t0:.0f}s)", file=sys.stderr, flush=True)
    t_haar = time.time() - t0

    # sweep runs the REAL serving entry point per threshold (no private
    # scored API): len(thresholds) x n jitted inferences, cheap at 256^2
    thresholds = [float(t) for t in args.thresholds.split(",")]
    t0 = time.time()
    per_thr = {
        thr: [bf.detect_faces(params, s, score_threshold=thr)
              for s in scenes]
        for thr in thresholds
    }
    t_bf = time.time() - t0

    curve = []
    for thr in thresholds:
        tp = fp = fn = 0
        matched_ious = []
        for hb, bb in zip(truth, per_thr[thr]):
            used = set()
            for t in hb:
                best, best_i = 0.0, None
                for i, b in enumerate(bb):
                    if i in used:
                        continue
                    v = iou(t, b)
                    if v > best:
                        best, best_i = v, i
                if best >= args.match_iou:
                    tp += 1
                    used.add(best_i)
                    matched_ious.append(best)
                else:
                    fn += 1
            fp += len(bb) - len(used)
        prec = tp / (tp + fp) if tp + fp else 1.0
        rec = tp / (tp + fn) if tp + fn else 1.0
        curve.append({
            "score_threshold": thr,
            "precision": round(prec, 4),
            "recall": round(rec, 4),
            "f1": round(2 * prec * rec / (prec + rec), 4)
            if prec + rec else 0.0,
            "mean_matched_iou": round(float(np.mean(matched_ious)), 4)
            if matched_ious else 0.0,
            "tp": tp, "fp": fp, "fn": fn,
        })
        print(curve[-1], file=sys.stderr)

    n_truth = sum(len(t) for t in truth)
    best = max(curve, key=lambda r: r["f1"])
    artifact = {
        "what": (
            "Held-out BlazeFace vs Haar-oracle parity at corpus scale "
            "(module docstring); truth = Haar detections on composites"
        ),
        "scenes": args.n,
        "seed": args.seed,
        "scene_px": SCENE,
        "oracle_boxes_total": n_truth,
        "match_iou": args.match_iou,
        "curve": curve,
        "best_operating_point": best,
        "runtime_s": {"haar": round(t_haar, 1), "blazeface": round(t_bf, 1),
                      "backend": "cpu (this build host)"},
    }
    with open(os.path.join(REPO, args.out), "w") as fh:
        json.dump(artifact, fh, indent=1)
        fh.write("\n")
    print(json.dumps({"wrote": args.out,
                      "best": best, "oracle_boxes": n_truth}))
    return 0


def bf_packaged_checkpoint() -> str:
    from flyimg_tpu.models.faces import PACKAGED_BLAZEFACE

    return PACKAGED_BLAZEFACE


if __name__ == "__main__":
    sys.exit(main())
