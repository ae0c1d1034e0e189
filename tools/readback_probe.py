"""How fast the runtime reads a device array back to the host, by the dtype
it crosses the link in.

A batched transform program returns its output flat and pinned row-major
(``ops/compose.py`` ``flat_output_format``). This probe times ``np.asarray``
of such arrays at the benchmark cells' output shapes, in two forms that
hold the same bytes: the samples themselves (``u8`` or ``u16``) and the
same bytes as ``uint32`` words. Each read is of a fresh array (a
``jax.Array`` keeps its host copy after the first read), made on the device
and waited for before the clock starts; the forms take turns, one warm-up
read each, then ``--reads`` timed reads each.

    python3 tools/readback_probe.py [--reads 5] [--out FILE]

Needs the chip: it refuses to run on any other platform. Prints one JSON
line per form and a last line with the ratios (the sample form's median
over the word form's).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

#: (name, the samples' shape and dtype, the same bytes as words)
SHAPES = (
    ("dslr", (64, 1066, 4800), "uint8", (64, 1066, 1200)),
    ("portrait", (64, 1216, 2496), "uint8", (64, 1216, 624)),
    ("png16", (32, 1408, 6144), "uint16", (32, 1408, 3072)),
)


def _maker(shape, dtype, device):
    """A jitted function that fills a fresh device array of ``shape`` with
    random bits, laid out as the batched program lays its output."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from flyimg_tpu.ops.compose import flat_output_format

    fmt = flat_output_format(SingleDeviceSharding(device))
    return jax.jit(
        lambda key: jax.random.bits(key, shape, jnp.dtype(dtype)),
        out_shardings=fmt,
    )


def probe(reads: int):
    import jax
    import numpy as np

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"the probe reads a TPU's link; this is {device.platform}")
    key = jax.random.key(46)
    rows = []
    for name, shape, dtype, words in SHAPES:
        forms = ((dtype, shape), ("uint32", words))
        makers = [_maker(s, d, device) for d, s in forms]
        times = {d: [] for d, _ in forms}
        layouts = {}
        for i in range(reads + 1):
            for (dtype_, _), make in zip(forms, makers):
                key, sub = jax.random.split(key)
                arr = make(sub)
                arr.block_until_ready()
                layouts[dtype_] = list(arr.format.layout.major_to_minor)
                t0 = time.perf_counter()
                host = np.asarray(arr)
                seconds = time.perf_counter() - t0
                assert host.flags.c_contiguous and host.dtype == np.dtype(dtype_)
                if i:
                    times[dtype_].append(seconds)
                del arr, host
        for dtype_, shape_ in forms:
            nbytes = int(np.prod(shape_)) * np.dtype(dtype_).itemsize
            med = statistics.median(times[dtype_])
            rows.append({
                "cell": name, "dtype": dtype_, "shape": list(shape_),
                "layout": layouts[dtype_], "bytes": nbytes,
                "seconds": [round(t, 6) for t in times[dtype_]],
                "median_s": round(med, 6), "gb_per_s": round(nbytes / med / 1e9, 3),
            })
            print(json.dumps(rows[-1]), flush=True)
    ratios = {}
    for name, _, dtype, _ in SHAPES:
        by = {r["dtype"]: r["median_s"] for r in rows if r["cell"] == name}
        ratios[name] = round(by[dtype] / by["uint32"], 3)
    summary = {"device": device.device_kind, "reads": reads,
               "samples_over_words": ratios}
    print(json.dumps(summary), flush=True)
    return rows, summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reads", type=int, default=5)
    ap.add_argument("--out", default=None, help="also write the lines here")
    args = ap.parse_args(argv)
    rows, summary = probe(args.reads)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as fh:
            for row in rows + [summary]:
                fh.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
