"""On-chip A/B experiment: where do the flagship's 16.6 us/img of scoring
tail go, and which formulation removes them?

Round-3 profile (builder capture, no longer on record): resample
~40 us/img, feature maps ~6.6, scoring conv tail ~16.6 — yet the SAME conv standalone measured
0.08 us/img (it im2col's onto the MXU fine in isolation). The tail is a
composition artifact: fusion or layout, not FLOPs. This script measures
the flagship with several tail formulations under bench.py's scan
methodology so one number per variant answers it:

  base       — the shipped program (__graft_entry__.entry)
  barrier    — jax.lax.optimization_barrier between weighted field and conv
               (blocks XLA from fusing the field computation into the conv's
               im2col gather, where it would recompute per-tap)
  prec_hi    — conv at HIGHEST precision (layout hint changes lowering)
  batch_ch   — batch-as-channels: weighted fields stacked on the lane dim
               [1, H, W, B], grouped conv feature_group_count=B (VPU path,
               lanes fully occupied)
  two_launch — features+field in one jit, conv in another (upper bound on
               what de-fusing buys: two dispatches, zero fusion)
  no_tail    — resample + features + field only (the floor the tail sits on)

Usage: python benchmarks/tail_experiment.py [--out benchmarks/tail_experiment_r4.json]
Requires the TPU backend; refuses to record CPU numbers as evidence.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BATCH = 256
SCAN_LEN = 10
LAUNCHES = 5
WARMUP = 2


def build_variants():
    import jax
    import jax.numpy as jnp

    import __graft_entry__ as graft
    from flyimg_tpu.models.smartcrop import (
        analyse_features,
        importance_kernel,
        weighted_field,
    )
    from flyimg_tpu.ops.compose import make_program_fn
    from flyimg_tpu.spec.options import OptionsBag
    from flyimg_tpu.spec.plan import build_plan

    plan = build_plan(OptionsBag("w_300,h_250,c_1"), 512, 512).device_plan()
    single = make_program_fn((250, 300), None, (0, 0), plan)
    kernel = jnp.asarray(importance_kernel(150.0, 150.0))
    kh, kw = kernel.shape

    def field_of(images, in_true, span_y, span_x, out_true):
        out = jax.vmap(single)(images, in_true, span_y, span_x, out_true)
        return out, weighted_field(jax.vmap(analyse_features)(out))

    def conv_nhwc(weighted, precision=None):
        inp = weighted[..., None]
        ker = kernel[:, :, None, None]
        dn = jax.lax.conv_dimension_numbers(
            inp.shape, ker.shape, ("NHWC", "HWIO", "NHWC")
        )
        return jax.lax.conv_general_dilated(
            inp, ker, (8, 8), "VALID", dimension_numbers=dn,
            precision=precision,
        )[..., 0]

    def base(*args):
        out, weighted = field_of(*args)
        return out, conv_nhwc(weighted)

    def barrier(*args):
        out, weighted = field_of(*args)
        weighted = jax.lax.optimization_barrier(weighted)
        return out, conv_nhwc(weighted)

    def prec_hi(*args):
        out, weighted = field_of(*args)
        return out, conv_nhwc(weighted, jax.lax.Precision.HIGHEST)

    def batch_ch(*args):
        out, weighted = field_of(*args)
        b = weighted.shape[0]
        # [B, H, W] -> [1, H, W, B]; one group per image on the lane dim
        inp = jnp.transpose(weighted, (1, 2, 0))[None]
        ker = jnp.broadcast_to(kernel[:, :, None, None], (kh, kw, 1, b))
        dn = jax.lax.conv_dimension_numbers(
            inp.shape, ker.shape, ("NHWC", "HWIO", "NHWC")
        )
        scores = jax.lax.conv_general_dilated(
            inp, ker, (8, 8), "VALID", dimension_numbers=dn,
            feature_group_count=b,
        )
        return out, jnp.transpose(scores[0], (2, 0, 1))

    def no_tail(*args):
        out, weighted = field_of(*args)
        # consume the field so it isn't DCE'd, skip the conv
        return out, weighted.sum(axis=(1, 2))[:, None, None]

    _, example = graft.entry()
    variants = {
        "base": base,
        "barrier": barrier,
        "prec_hi": prec_hi,
        "batch_ch": batch_ch,
        "no_tail": no_tail,
    }
    return variants, field_of, conv_nhwc, example


def measure(fn, device_args, batch):
    import jax
    import jax.numpy as jnp

    # inputs as jit parameters, not closure constants (bench.py's rule:
    # a zero-arg jit is eligible for whole-program constant folding)
    @jax.jit
    def launch(images, *rest):
        def body(carry, _):
            zero = jnp.isnan(carry).astype(jnp.uint8)
            out, scores = fn(images ^ zero, *rest)
            acc = scores.sum() + out[..., 0].astype(jnp.float32).sum()
            return carry + acc, None

        acc, _ = jax.lax.scan(body, jnp.float32(0.0), None, length=SCAN_LEN)
        return acc

    # sync via host read of the scalar — block_until_ready has been seen
    # returning early on the CPU backend in this environment (bench.py)
    float(launch(*device_args))
    times = []
    for step in range(WARMUP + LAUNCHES):
        t0 = time.perf_counter()
        float(launch(*device_args))
        dt = time.perf_counter() - t0
        if step >= WARMUP:
            times.append(dt)
    per_batch = float(np.median(times)) / SCAN_LEN
    return batch / per_batch, per_batch / batch * 1e6


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="benchmarks/tail_experiment_r4.json")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="debug only; refuses to write the artifact")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from flyimg_tpu.compilecache import enable_compile_cache

    enable_compile_cache()

    backend = jax.default_backend()
    if backend != "tpu" and not args.allow_cpu:
        print(json.dumps({"error": f"backend is {backend}, not tpu; refusing"}))
        return 1

    global BATCH, SCAN_LEN, LAUNCHES
    if backend != "tpu":
        BATCH, SCAN_LEN, LAUNCHES = 8, 2, 2

    variants, field_of, conv_nhwc, example = build_variants()
    reps = max(BATCH // example[0].shape[0], 1)
    batch = reps * example[0].shape[0]
    device_args = [
        jax.device_put(np.concatenate([np.asarray(a)] * reps, axis=0))
        for a in example
    ]

    results = {}
    for name, fn in variants.items():
        try:
            ips, us = measure(fn, device_args, batch)
            results[name] = {"images_per_sec": round(ips, 1),
                             "us_per_image": round(us, 2)}
        except Exception as exc:  # a variant failing must not kill the rest
            results[name] = {"error": f"{type(exc).__name__}: {exc}"[:300]}
        print(name, results[name], flush=True)

    # two_launch: features in one dispatch, conv in a second — measures the
    # de-fused upper bound (can't sit in the scan; measure per-call async
    # pipelined over the launches)
    try:
        f_field = jax.jit(lambda *a: field_of(*a))
        f_conv = jax.jit(conv_nhwc)
        out, w = f_field(*device_args)
        float(f_conv(w).sum())
        times = []
        for step in range(WARMUP + LAUNCHES):
            t0 = time.perf_counter()
            for _ in range(SCAN_LEN):
                out, w = f_field(*device_args)
                s = f_conv(w)
            # host read syncs the dependency chain (block_until_ready can
            # return early on this environment's CPU backend)
            float(s.sum() + out[..., 0].astype(jnp.float32).sum())
            dt = time.perf_counter() - t0
            if step >= WARMUP:
                times.append(dt)
        per_batch = float(np.median(times)) / SCAN_LEN
        results["two_launch"] = {
            "images_per_sec": round(batch / per_batch, 1),
            "us_per_image": round(per_batch / batch * 1e6, 2),
            "note": "includes real dispatch; pipelined, not scanned",
        }
    except Exception as exc:
        results["two_launch"] = {"error": f"{type(exc).__name__}: {exc}"[:300]}
    print("two_launch", results["two_launch"], flush=True)

    if backend == "tpu":
        with open(args.out, "w") as fh:
            json.dump({
                "what": ("flagship scoring-tail formulation A/B "
                         "(see module docstring)"),
                "hardware": f"backend={backend}, {len(jax.devices())} device(s)",
                "method": (f"lax.scan len={SCAN_LEN}, batch {batch}, "
                           f"median of {LAUNCHES}"),
                "results": results,
            }, fh, indent=1)
            fh.write("\n")
        print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
