"""On-chip A/B: can the windowed-einsum resample beat its 40 us/img?

After the round-4 lane-packing fix the flagship is nearly resample-bound
(resample ~40 of 58.4 us/img). The shipped form is two einsums over
[h, w, c] with C=3 riding the minor dim — a layout XLA must pad/permute
onto (8,128) tiles. Variants:

  base        — shipped resample_image (einsum "oh,hwc->owc" then
                "ow,hwc->hoc", DEFAULT precision)
  fold2d      — fold channels into plain 2D matmuls: H-pass as
                [out_h,h] @ [h, w*c], W-pass as [out_h*c? no —
                transpose to [out_h*c, w] is the shuffle] — concretely:
                wy @ img.reshape(h, w*c) -> [oh, w*c];
                then reshape/transpose to [oh*c, w] @ wx.T -> [oh*c, ow]
  bf16        — explicit bfloat16 cast of image + weights before the
                einsums (DEFAULT already multiplies in bf16; the explicit
                cast halves the HBM traffic of operands + intermediate),
                f32 accumulation via preferred_element_type
  fold2d_bf16 — both
  banded      — the dense [out, in] weight matrices are ~95% zeros
                (lanczos3 support is 10-13 taps at these scales): gather
                a static K=16-tap band per output row and contract over
                K — ~30x fewer MACs than the dense matmuls, traded
                against gather cost and a VPU (not MXU) reduction.
                Serving integration, if this wins on-chip: K cannot be a
                global constant (out_true can be far below the static
                bucket — a w_10 thumbnail of a 4000px source needs
                radius 3*scale taps), so K must be computed from the
                PLAN's true geometry at submit time and carried as a
                static component of the program cache key (the batcher
                then groups members by K bucket like it groups by shape)

Measured with the repo's hardened recipe: inputs as jit parameters,
host-read sync, two-scan differencing (see bench.py docstring). Each
variant is also checked for numeric equivalence against base at uint8
round-trip tolerance before it is timed.

Usage: python benchmarks/resample_experiment.py [--out benchmarks/resample_experiment_r4.json]
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
SCAN = 10
LAUNCHES = 5
WARMUP = 2


def build(small: bool = False):
    import jax
    import jax.numpy as jnp

    from flyimg_tpu.ops.resample import (
        band_taps,
        bucket_taps,
        resample_image,
        resample_image_banded,
        resample_matrix,
    )

    # CPU smoke shrinks the geometry too: a 512^2 f32 resample is seconds
    # per image on one host core
    src, oh, ow = (128, 62, 75) if small else (512, 250, 300)
    # crop-fill window for oh x ow out of src^2 (same proportions as the
    # flagship's 512 -> 300x250)
    span_y = jnp.array([src * 0.0832, src * 0.8334], jnp.float32)
    span_x = jnp.array([0.0, float(src)], jnp.float32)
    out_true = jnp.array([float(oh), float(ow)], jnp.float32)
    in_true = jnp.array([float(src), float(src)], jnp.float32)

    def mats():
        wy = resample_matrix(src, oh, span_y[0], span_y[1], out_true[0],
                             in_true[0], "lanczos3")
        wx = resample_matrix(src, ow, span_x[0], span_x[1], out_true[1],
                             in_true[1], "lanczos3")
        return wy, wx

    def base_one(img):
        return resample_image(img, (oh, ow), span_y, span_x, out_true,
                              in_true)

    def fold2d_one(img):
        wy, wx = mats()
        h, w, c = img.shape
        # H-pass: [oh, h] @ [h, w*c] — one clean MXU matmul
        tmp = (wy @ img.reshape(h, w * c)).reshape(oh, w, c)
        # W-pass: put w last-but-contracted: [oh*c? -> [oh, c, w] @ wx.T]
        t2 = jnp.transpose(tmp, (0, 2, 1)).reshape(oh * c, w)
        out = (t2 @ wx.T).reshape(oh, c, ow)
        return jnp.transpose(out, (0, 2, 1))

    def bf16_one(img):
        wy, wx = mats()
        imgb = img.astype(jnp.bfloat16)
        tmp = jax.lax.dot_general(
            wy.astype(jnp.bfloat16), imgb.reshape(img.shape[0], -1),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        ).reshape(oh, img.shape[1], 3)
        t2 = jnp.transpose(tmp.astype(jnp.bfloat16), (0, 2, 1)).reshape(
            oh * 3, img.shape[1]
        )
        out = jax.lax.dot_general(
            t2, wx.astype(jnp.bfloat16).T,
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        ).reshape(oh, 3, ow)
        return jnp.transpose(out, (0, 2, 1))

    # The dense [out, in] weight matrices are ~95% zeros (lanczos3
    # support at these scales is 10-13 taps of 512): gather a STATIC
    # K-tap band per output row instead and contract over K — ~30x
    # fewer MACs than the dense matmuls, traded against gather cost and
    # VPU (not MXU) reduction. K comes from THE shared serving-side
    # computation (ops/resample.py band_taps/bucket_taps — the same
    # figures select_band_taps keys programs by), so the experiment and
    # the serving kernel can never disagree about what K a geometry
    # needs. (The pre-promotion draft hard-coded K=16, valid only for
    # scale <= 1.71 — an upscale or deeper downscale would have dropped
    # contributing taps silently.)
    ky = bucket_taps(band_taps("lanczos3", float(span_y[1]) / oh))
    kx = bucket_taps(band_taps("lanczos3", float(span_x[1]) / ow))

    def banded_one(img):
        return resample_image_banded(
            img, (oh, ow), span_y, span_x, out_true, in_true, (ky, kx),
        )

    variants = {
        "base": base_one,
        "fold2d": fold2d_one,
        "bf16": bf16_one,
        "banded": banded_one,
    }
    return variants, (src, oh, ow)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="benchmarks/resample_experiment_r4.json")
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args()

    if args.allow_cpu:
        from flyimg_tpu.parallel.mesh import force_cpu_platform

        force_cpu_platform(1)

    import jax
    import jax.numpy as jnp

    from flyimg_tpu.compilecache import enable_compile_cache

    enable_compile_cache()

    backend = jax.default_backend()
    if backend != "tpu" and not args.allow_cpu:
        print(json.dumps({"error": f"backend is {backend}, not tpu"}))
        return 1

    global BATCH, SCAN, LAUNCHES
    if backend != "tpu":
        BATCH, SCAN, LAUNCHES = 8, 2, 2

    variants, (src, oh, ow) = build(small=backend != "tpu")
    rng = np.random.default_rng(0)
    imgs = jax.device_put(
        rng.integers(0, 255, (BATCH, src, src, 3), dtype=np.uint8)
    )

    # numeric gate: every variant must match base within one uint8 level
    # on the round-tripped output before its speed means anything
    fimgs = imgs[:4].astype(jnp.float32)
    ref = np.asarray(jax.jit(jax.vmap(variants["base"]))(fimgs))
    equiv = {}
    for name, fn in variants.items():
        out = np.asarray(jax.jit(jax.vmap(fn))(fimgs))
        equiv[name] = float(np.abs(out - ref).max())

    def steady(fn):
        def make_launch(length):
            @jax.jit
            def launch(images):
                def body(carry, _):
                    zero = jnp.isnan(carry).astype(jnp.uint8)
                    out = jax.vmap(fn)((images ^ zero).astype(jnp.float32))
                    return carry + out.sum(), None

                acc, _ = jax.lax.scan(body, jnp.float32(0.0), None,
                                      length=length)
                return acc

            return launch

        def timed(launch_fn):
            float(launch_fn(imgs))
            ts = []
            for _ in range(WARMUP + LAUNCHES):
                t0 = time.perf_counter()
                float(launch_fn(imgs))
                ts.append(time.perf_counter() - t0)
            return float(np.median(ts[WARMUP:]))

        t1 = timed(make_launch(SCAN))
        t7 = timed(make_launch(7 * SCAN))
        dt = t7 - t1
        if dt <= 0:
            return BATCH / (t1 / SCAN)
        return BATCH / (dt / (6 * SCAN))

    results = {}
    for name, fn in variants.items():
        try:
            ips = steady(fn)
            results[name] = {
                "images_per_sec": round(ips, 1),
                "us_per_image": round(1e6 / ips, 2),
                "max_abs_diff_vs_base": round(equiv[name], 4),
            }
        except Exception as exc:
            results[name] = {"error": f"{type(exc).__name__}: {exc}"[:300]}
        print(name, results[name], flush=True)

    if backend == "tpu":
        with open(args.out, "w") as fh:
            json.dump({
                "what": "resample formulation A/B (module docstring)",
                "method": (f"two-scan differencing {SCAN}/{7*SCAN}, batch "
                           f"{BATCH}, median of {LAUNCHES}, host-read sync"),
                "results": results,
            }, fh, indent=1)
            fh.write("\n")
        print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
