"""Benchmark: steady-state throughput of the flagship fused program on one
chip, inputs already on the device.

The BASELINE.json headline workload ("images/sec/chip (resize+smart-crop)"):
batches of 512x512 uint8 images through the fused device program — windowed
crop-fill resample to 300x250 (MXU einsums, bf16 multiplies), the smart-crop
saliency field, and the candidate-scoring conv. It is a kernel-only figure:
dispatch, host<->device transfer, decode and encode are not in it.

One process, chip or fail. The backend initialises in this process; if JAX
lands on the CPU without an explicit ``JAX_PLATFORMS=cpu`` pin the script
exits non-zero and prints no number. Under the pin it runs the same program
at toy sizes as a smoke test of the code path and names its metric
``cpu smoke ...`` — a CPU rate is never printed under the device metric's
name. Every line carries platform, device_kind and device count.

Measurement: SCAN_LEN batches per launch via ``lax.scan`` (one dispatch,
SCAN_LEN sequential batch programs), each launch timed on the host clock
around ``block_until_ready``; the median launch over LAUNCHES gives the
per-batch time. An earlier version timed scans of length L and 3L and
differenced them, and synchronised by reading the scalar result to the host.
Both were re-checked on a TPU v5e at batch 256 (my chip run, PR 21) and
dropped: a launch of 10 took 0.14566 s blocked and 0.14592 s read back, a
launch of 30 took 0.43515 s (2.987x), so the direct per-batch time
(14.57 ms) and the differenced one (14.47 ms) agree to 0.6% — the
per-launch constant is about 1 ms against 146 ms of work.

vs_baseline: BASELINE.md's target is >= 10_000 images/sec on a v4-8 (8
chips) => 1_250 images/sec/chip; the printed ratio is value / 1250.

Prints exactly ONE JSON line on stdout.
"""

import json
import os
import sys
import time

import numpy as np

BATCH = 256
SCAN_LEN = 10          # batches per device launch
LAUNCHES = 6
WARMUP = 2
TARGET_PER_CHIP = 10_000 / 8.0
DEVICE_METRIC = "images/sec/chip resize(300x250 crop-fill)+smart-crop"
CPU_SMOKE_METRIC = "cpu smoke images/sec resize(300x250 crop-fill)+smart-crop"


def _telemetry_stamp(record: dict) -> None:
    """Traffic-shape attribution: when FLYIMG_BENCH_TELEMETRY_URL names a
    running app's base URL, scrape its debug-gated /debug/telemetry once
    and stamp the observed mix label + archive segment count into the
    record. Best-effort everywhere: no URL, a dead server, a 404 (debug
    off), or a non-JSON body all leave the record untouched — attribution
    must never fail a bench that already produced its number."""
    base = os.environ.get("FLYIMG_BENCH_TELEMETRY_URL", "").strip()
    if not base:
        return
    try:
        import urllib.request

        with urllib.request.urlopen(
            base.rstrip("/") + "/debug/telemetry", timeout=5
        ) as resp:
            doc = json.loads(resp.read().decode("utf-8"))
        if isinstance(doc, dict) and doc.get("enabled"):
            record["traffic_mix"] = (doc.get("mix") or {}).get("label")
            record["telemetry_segments"] = len(
                (doc.get("archive") or {}).get("segments") or []
            )
    except Exception:
        pass


def make_launch(fn, length):
    """One jitted device launch running ``fn`` over the same batch
    ``length`` times in a ``lax.scan``."""
    import jax
    import jax.numpy as jnp

    # The batch is a real jit PARAMETER, not a closure capture: zero-arg
    # jit embeds closed-over arrays as program constants, and XLA will
    # constant-fold a small enough constant program at compile time.
    @jax.jit
    def launch(images, *rest):
        def body(carry, _):
            # tie each iteration's INPUT to the carry so XLA cannot
            # hoist the loop-invariant pipeline out of the scan (LICM
            # would otherwise compute one batch and loop over scalar
            # adds). isnan(carry) is 0 at runtime but data-dependent,
            # so images ^ 0 defeats CSE/LICM, pixels untouched.
            zero = jnp.isnan(carry).astype(jnp.uint8)
            out, scores = fn(images ^ zero, *rest)
            # consume both outputs so no batch is dead-code-eliminated
            acc = scores.sum() + out[..., 0].astype(jnp.float32).sum()
            return carry + acc, None

        acc, _ = jax.lax.scan(body, jnp.float32(0.0), None, length=length)
        return acc

    return launch


def main() -> int:
    import jax

    import __graft_entry__ as graft
    from flyimg_tpu.compilecache import enable_compile_cache
    from flyimg_tpu.parallel.mesh import require_accelerator

    try:
        device = require_accelerator()
    except RuntimeError as exc:
        # no chip and no pin (or a pinned accelerator that failed to
        # initialise): no number
        print(f"bench: {exc}", file=sys.stderr)
        return 2
    enable_compile_cache()
    on_cpu = device["platform"] == "cpu"
    # the explicit CPU pin is a smoke test of the code path: toy sizes,
    # under a metric name that is not the device's
    batch, scan_len, launches = (16, 2, 2) if on_cpu else (
        BATCH, SCAN_LEN, LAUNCHES
    )
    stamp = {
        "platform": device["platform"],
        "device_kind": device["device_kind"],
        "device_count": device["count"],
    }

    t0 = time.perf_counter()

    def note(msg):
        print(
            f"# bench [{stamp['platform']} {stamp['device_kind']} "
            f"x{stamp['device_count']}]: {msg} "
            f"t={time.perf_counter() - t0:.1f}s",
            file=sys.stderr, flush=True,
        )

    fn, args = graft.entry()
    # scale example args up to the bench batch
    reps = max(batch // args[0].shape[0], 1)
    batch = reps * args[0].shape[0]
    note(f"transferring batch {batch}")
    device_args = [
        jax.device_put(np.concatenate([np.asarray(a)] * reps, axis=0))
        for a in args
    ]
    jax.block_until_ready(device_args)
    note("H2D done, compiling")
    launch = make_launch(fn, scan_len)
    t_compile = time.perf_counter()
    launch(*device_args).block_until_ready()
    compile_s = time.perf_counter() - t_compile
    note(f"compiled in {compile_s:.1f}s, measuring")

    times = []
    for step in range(WARMUP + launches):
        start = time.perf_counter()
        launch(*device_args).block_until_ready()
        elapsed = time.perf_counter() - start
        note(f"launch {step} {elapsed:.4f}s")
        if step >= WARMUP:
            times.append(elapsed)

    per_batch = float(np.median(times)) / scan_len
    images_per_sec = batch / per_batch
    from flyimg_tpu.ops.resample import kernel_mode

    record = {
        "metric": CPU_SMOKE_METRIC if on_cpu else DEVICE_METRIC,
        "value": round(images_per_sec, 1),
        "unit": "images/sec",
        "vs_baseline": (
            None if on_cpu else round(images_per_sec / TARGET_PER_CHIP, 3)
        ),
        **stamp,
        "batch": batch,
        "scan_len": scan_len,
        "launches": launches,
        "setup_compile_s": round(compile_s, 2),
        # which resample-kernel variant set this number (docs/kernels.md)
        "kernel": kernel_mode(),
    }
    _telemetry_stamp(record)
    try:
        import resource

        # ru_maxrss is KiB on Linux
        record["peak_rss_bytes"] = (
            int(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss) * 1024
        )
    except (ImportError, OSError):
        pass
    print(json.dumps(record))
    return 0


if __name__ == "__main__":
    sys.exit(main())
