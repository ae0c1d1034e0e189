"""Per-operator device throughput: the compute-path surface behind the
single bench.py headline.

Measures each device operator family as batched steady-state launches —
crop-fill resample, fit resample, static-extent rotate, separable
gaussian blur, unsharp, grayscale, monochrome dither, and the smart-crop
saliency+scoring pass (lax.scan amortizes dispatch exactly like bench.py;
see its docstring for why that models real-hardware dispatch overlap).

Usage:  python benchmarks/bench_ops.py [--batch 256] [--scan 10] [--out f.json]
Writes one JSON document {platform, device_kind, device_count, batch,
results: [{op, images_per_sec}]}. One process, chip or fail like bench.py:
without an accelerator and without an explicit JAX_PLATFORMS=cpu pin it
exits non-zero with no document; under the pin it shrinks sizes to
smoke-test the harness itself (those rates are not device numbers).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _steady_state(fn, args, batch: int, scan: int, launches: int = 4):
    """Median images/sec of `fn(*args)` run `scan` times per device launch
    (carry-xor defeats LICM/CSE the same way bench.py does).

    The inputs MUST be real jit parameters, not closure captures: a
    zero-arg jit embeds them as program constants, and for small enough
    op chains XLA constant-folds the whole scan at compile time — the
    round-4 device_ops first capture recorded 75M img/s "rotate" that
    way (a fetch of a precomputed scalar, not a measurement)."""
    import jax
    import jax.numpy as jnp

    def make_launch(length):
        @jax.jit
        def launch(first_arg, *rest):
            def body(carry, _):
                zero = jnp.isnan(carry).astype(jnp.uint8)
                out = fn(first_arg ^ zero, *rest)
                if isinstance(out, tuple):
                    acc = sum(o.astype(jnp.float32).sum() for o in out)
                else:
                    acc = out.astype(jnp.float32).sum()
                return carry + acc, None

            acc, _ = jax.lax.scan(
                body, jnp.float32(0.0), None, length=length
            )
            return acc

        return launch

    # Two-scan differencing: each launch pays a fixed dispatch cost plus
    # scan x per-iteration work. For small ops the fixed part can swamp
    # the work at any one scan length, so measure at scan and 7*scan and
    # difference — the constant cancels and the rate is the op's own. The
    # 7x spread keeps the differenced work (6*scan iterations) well above
    # the constant's jitter.
    def timed(launch_fn):
        launch_fn(*args).block_until_ready()  # compile + warm
        ts = []
        for _ in range(max(launches, 6)):
            t = time.perf_counter()
            launch_fn(*args).block_until_ready()
            ts.append(time.perf_counter() - t)
        return float(np.median(ts))

    t1 = timed(make_launch(scan))
    t7 = timed(make_launch(7 * scan))
    dt = t7 - t1
    if dt <= 0:  # noise floor: fall back to the single-scan bound
        return batch / (t1 / scan)
    return batch / (dt / (6 * scan))


def host_codec_rows(quick: bool = False) -> list:
    """Host-side codec throughput: JPEG decode and plain/trellis encode,
    single-caller vs the native worker pool, at the serving shapes (the
    300x250 smart-crop output and a 512^2 source). The miss path is
    decode -> device -> encode, so BASELINE's end-to-end img/s claim is
    bounded by these host numbers as much as by the device rows above —
    an unmeasured host wall was round 3's #1 credibility gap."""
    import multiprocessing

    from flyimg_tpu.codecs import native_codec

    rows = []
    if not native_codec.available():
        return [{"op": "host_codec", "error": "fastcodec not built"}]

    rng = np.random.default_rng(7)
    n_imgs = 8 if quick else 64
    repeats = 2 if quick else 4
    n_threads = multiprocessing.cpu_count()
    pool = native_codec.DecodePool(n_threads)

    def median_rate(fn, n_items):
        times = []
        for _ in range(repeats):
            t = time.perf_counter()
            fn()
            times.append(time.perf_counter() - t)
        return n_items / float(np.median(times))

    try:
        for label, (h, w) in (("300x250", (250, 300)), ("512", (512, 512))):
            frames = [
                np.clip(
                    rng.normal(128, 44, (h, w, 3)), 0, 255
                ).astype(np.uint8)
                for _ in range(n_imgs)
            ]
            blobs = [native_codec.jpeg_encode(f, 90) for f in frames]

            def dec_single():
                for blob in blobs:
                    native_codec.jpeg_decode(blob)

            def dec_pool():
                pool.decode_batch(blobs)

            cases = [
                (f"jpeg_decode_{label}_1thread", dec_single),
                (f"jpeg_decode_{label}_pool{n_threads}", dec_pool),
                (
                    f"jpeg_encode_plain_{label}_1thread",
                    lambda: [native_codec.jpeg_encode(f, 90) for f in frames],
                ),
                (
                    f"jpeg_encode_plain_{label}_pool{n_threads}",
                    lambda: pool.encode_batch(frames, 90, trellis=False),
                ),
                (
                    f"jpeg_encode_trellis_{label}_1thread",
                    lambda: [
                        native_codec.jpeg_encode_trellis(f, 90) for f in frames
                    ],
                ),
                (
                    f"jpeg_encode_trellis_{label}_pool{n_threads}",
                    lambda: pool.encode_batch(frames, 90, trellis=True),
                ),
            ]
            for name, fn in cases:
                try:
                    rate = median_rate(fn, n_imgs)
                    rows.append(
                        {"op": name, "images_per_sec": round(rate, 1)}
                    )
                    print(f"{name:38s} {rate:10.1f} img/s", file=sys.stderr)
                except Exception as exc:
                    rows.append({"op": name, "error": str(exc)[:200]})
    finally:
        pool.close()
    return rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--scan", type=int, default=10)
    ap.add_argument("--out", default=None)
    ns = ap.parse_args()

    import jax

    from flyimg_tpu.compilecache import enable_compile_cache
    from flyimg_tpu.parallel.mesh import require_accelerator

    try:
        device = require_accelerator()
    except RuntimeError as exc:
        print(f"bench_ops: {exc}", file=sys.stderr)
        return 2
    enable_compile_cache()
    backend = device["platform"]
    import jax.numpy as jnp

    from flyimg_tpu.ops.compose import make_program_fn, plan_layout
    from flyimg_tpu.spec.options import OptionsBag
    from flyimg_tpu.spec.plan import build_plan
    batch, scan = ns.batch, ns.scan
    src = 512
    if backend != "tpu":  # CPU smoke: harness correctness, not numbers
        batch, scan, src = 8, 2, 128

    rng = np.random.default_rng(0)
    images = jax.device_put(
        rng.integers(0, 255, (batch, src, src, 3), dtype=np.uint8)
    )

    def vmapped(options: str):
        """One plan drives everything: device program, resample output
        shape (derived, never hand-synced), and traced geometry scalars."""
        plan = build_plan(OptionsBag(options), src, src)
        layout = plan_layout(plan)
        needs_resample = (
            plan.resize_to is not None
            or plan.extent is not None
            or plan.extract is not None
        )
        out_shape = layout.resample_out if needs_resample else None
        single = make_program_fn(
            out_shape, layout.pad_canvas, layout.pad_offset,
            plan.device_plan(),
        )
        n = images.shape[0]
        in_true = jnp.full((n, 2), float(src), jnp.float32)
        span_y = jnp.tile(jnp.asarray([layout.span_y], jnp.float32), (n, 1))
        span_x = jnp.tile(jnp.asarray([layout.span_x], jnp.float32), (n, 1))
        out_true = jnp.tile(
            jnp.asarray([layout.out_true], jnp.float32), (n, 1)
        )
        fn = jax.vmap(single)
        return lambda imgs: fn(imgs, in_true, span_y, span_x, out_true)

    half = src // 2
    cases = [
        ("crop_fill_resample", vmapped(f"w_{half + 44},h_{half - 6},c_1")),
        ("fit_resample", vmapped(f"w_{half}")),
        ("rotate_45", vmapped("r_45")),
        ("gaussian_blur", vmapped("blr_2x1")),
        ("unsharp", vmapped("unsh_0.25x0.25+8+0.065")),
        ("grayscale", vmapped("clsp_Gray")),
        ("monochrome_dither", vmapped("mnchr_1")),
    ]

    results = []
    for name, fn in cases:
        try:
            rate = _steady_state(fn, (images,), batch, scan)
            results.append({"op": name, "images_per_sec": round(rate, 1)})
            print(f"{name:22s} {rate:12.1f} img/s", file=sys.stderr)
        except Exception as exc:  # record, keep measuring the rest
            results.append({"op": name, "error": str(exc)[:200]})
            print(f"{name:22s} ERROR {exc}", file=sys.stderr)

    # smart-crop saliency+scoring on the post-resize shape (the bench.py
    # second stage), measured standalone
    try:
        from flyimg_tpu.models.smartcrop import (
            analyse_features,
            importance_kernel,
            weighted_field,
        )

        out_h, out_w = (250, 300) if backend == "tpu" else (64, 96)
        fields = jax.device_put(
            rng.integers(0, 255, (batch, out_h, out_w, 3), dtype=np.uint8)
        )
        kernel = jnp.asarray(
            importance_kernel(out_w / 2.0, out_h / 2.0)
        )

        def saliency(imgs):
            weighted = weighted_field(jax.vmap(analyse_features)(imgs))
            inp = weighted[..., None]
            ker = kernel[:, :, None, None]
            dn = jax.lax.conv_dimension_numbers(
                inp.shape, ker.shape, ("NHWC", "HWIO", "NHWC")
            )
            return jax.lax.conv_general_dilated(
                inp, ker, (8, 8), "VALID", dimension_numbers=dn
            )[..., 0]

        rate = _steady_state(saliency, (fields,), batch, scan)
        results.append(
            {"op": "saliency_score", "images_per_sec": round(rate, 1)}
        )
        print(f"{'saliency_score':22s} {rate:12.1f} img/s", file=sys.stderr)
    except Exception as exc:
        results.append({"op": "saliency_score", "error": str(exc)[:200]})

    results.extend(host_codec_rows(quick=backend != "tpu"))

    doc = {
        "platform": device["platform"],
        "device_kind": device["device_kind"],
        "device_count": device["count"],
        "batch": batch,
        "scan": scan,
        "src_size": src,
        "results": results,
    }
    text = json.dumps(doc, indent=1)
    if ns.out:
        with open(ns.out, "w") as fh:
            fh.write(text + "\n")
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
