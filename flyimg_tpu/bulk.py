"""Offline bulk runner: a directory of images through the batch runtime.

The serving path processes one HTTP request at a time; the BASELINE
workloads ("1k COCO batch resize", "4k->256 thumbnail firehose",
BASELINE.md configs 1 and 4) are offline sweeps. This driver feeds every
image in a directory through the handler's OWN transform pipeline
(``ImageHandler.transform_bytes``) — native DecodePool-backed decode, one
BatchController grouping frames into vmapped device launches, the full
post-pass chain (smart-crop, face ops, alpha flatten, st_0 metadata
graft), host encode — and writes outputs under the original file names.
Because bulk and serving share one code path, the same options string
produces the same bytes in both.

Usage:
    python -m flyimg_tpu.bulk --src photos/ --out thumbs/ \
        --options w_256,h_256,c_1 [--format jpg] [--workers 8] \
        [--trace-out spans.jsonl]

``--trace-out`` runs every file under its own trace and writes one JSON
line per file (the span tree of docs/observability.md "Tracing": decode
with its codec queue and run, the fill wait, the shared launch span with
its phases, encode): the operator's view of a job's launches.

Prints one JSON line: {images, failed, images_per_sec, batches,
mean_occupancy, padding_waste, queue_wait_share}. Library surface:
``bulk_process()``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import TimeoutError as FuturesTimeout
from typing import Dict, Optional

IMAGE_EXTENSIONS = (".jpg", ".jpeg", ".png", ".webp", ".gif")


def bulk_process(
    src_dir: str,
    out_dir: str,
    options_str: str,
    *,
    out_format: str = "jpg",
    workers: int = 8,
    batcher=None,
    quality: Optional[int] = None,
    trace_out: Optional[str] = None,
) -> Dict[str, float]:
    """Transform every image under ``src_dir`` (non-recursive) with the
    URL-DSL ``options_str``; outputs land in ``out_dir`` as
    ``<stem>.<out_format>``. Returns the summary dict the CLI prints.

    Decode runs on ``workers`` threads (the native codec releases the
    GIL); all frames funnel into ONE device BatchController plus one
    host-codec controller — the same two-controller split serving uses —
    so concurrent files with the same post-decode geometry share vmapped
    device launches and JPEG decodes batch on the native pool.

    ``--format`` governs the output container (there is no Accept header
    to negotiate against); an ``o_`` key in ``options_str`` is ignored.
    ``quality`` overrides the encode quality unless the options string
    itself carries an explicit ``q_``.

    ``trace_out``: a path; each file's ``transform_bytes`` then runs
    under a trace of its own (runtime/tracing.py, every trace kept) and
    the job ends by writing one JSON line per file there
    (``Trace.as_dict()`` plus the file's name). Without it no trace is
    ever created and the pipeline's spans stay no-ops."""
    from flyimg_tpu.appconfig import AppParameters
    from flyimg_tpu.runtime import tracing
    from flyimg_tpu.runtime.batcher import BatchController
    from flyimg_tpu.service.handler import ImageHandler
    from flyimg_tpu.service.output_image import EXT_TO_MIME, OutputSpec
    from flyimg_tpu.spec.options import OptionsBag

    os.makedirs(out_dir, exist_ok=True)
    names = sorted(
        n for n in os.listdir(src_dir)
        if n.lower().endswith(IMAGE_EXTENSIONS)
    )
    params = AppParameters()
    own_batcher = batcher is None
    from flyimg_tpu.ops.resample import set_kernel_mode
    from flyimg_tpu.runtime.batcher import containment_params

    # same resample-kernel selection serving applies (service/app.py):
    # an offline sweep must run the variant the config names
    set_kernel_mode(str(params.by_key("resample_kernel", "dense")))
    containment = containment_params(params)
    if own_batcher:
        # same tunables serving reads (service/app.py): an operator's
        # batching config must mean the same thing in offline sweeps —
        # including the blast-radius containment knobs
        batcher = BatchController(
            max_batch=int(params.by_key("batch_max_size", 64)),
            deadline_ms=float(params.by_key("batch_deadline_ms", 4.0)),
            pipeline_depth=int(params.by_key("batch_pipeline_depth", 2)),
            **containment,
        )
    # host codec work on its own controller so JPEG-decode pool batches
    # don't serialize against device launches (mirrors service/app.py)
    codec_batcher = BatchController(
        max_batch=int(params.by_key("decode_batch_max", 32)),
        deadline_ms=float(params.by_key("decode_deadline_ms", 1.0)),
        name="codec",
        **containment,
    )
    handler = ImageHandler(
        storage=None,  # transform_bytes never touches storage
        params=params,
        batcher=batcher,
        codec_batcher=codec_batcher,
        # face backend resolves lazily inside the handler (from the same
        # params) only when a face option actually runs — no cascade /
        # checkpoint load for plain resize sweeps
    )

    # the SAME OptionsBag configuration serving uses (handler.py): an
    # operator's options_keys/default_options/separator overrides must
    # mean the same thing in offline sweeps or byte-parity breaks
    options_keys = params.by_key("options_keys")
    default_options = params.by_key("default_options")
    separator = params.by_key("options_separator", ",")

    ext = "jpg" if out_format in ("jpg", "jpeg") else out_format
    explicit_quality = any(
        seg.startswith("q_") for seg in options_str.split(separator)
    )
    failed = 0
    # every trace is kept (no tail sampling offline) in a ring as long as
    # the job, and written out once the job is done
    tracer = (
        tracing.Tracer(buffer_size=max(len(names), 1), sample_rate=1.0)
        if trace_out else None
    )
    t0 = time.perf_counter()

    def run_one(name: str) -> None:
        trace = tracer.start(name=name) if tracer is not None else None
        try:
            with tracing.activate(trace):
                transform_one(name)
        except BaseException:
            if tracer is not None:
                tracer.finish(trace, "error")
            raise
        if tracer is not None:
            tracer.finish(trace)

    def transform_one(name: str) -> None:
        src = os.path.join(src_dir, name)
        with open(src, "rb") as fh:
            data = fh.read()
        # fresh bag per file: plan building and the transform read options
        # concurrently across worker threads, and some accessors mutate
        options = OptionsBag(
            options_str,
            options_keys=options_keys,
            default_options=default_options,
            separator=separator,
        )
        if quality is not None and not explicit_quality:
            options.set_option("quality", int(quality))
        stem = os.path.splitext(name)[0]
        spec = OutputSpec(
            name=f"{stem}.{ext}", extension=ext, mime=EXT_TO_MIME[ext]
        )
        content = handler.transform_bytes(data, options, spec)
        dst = os.path.join(out_dir, f"{stem}.{ext}")
        tmp = dst + ".part"
        with open(tmp, "wb") as fh:
            fh.write(content)
        os.replace(tmp, dst)

    try:
        with ThreadPoolExecutor(max_workers=workers) as pool:
            futures = {pool.submit(run_one, n): n for n in names}
            retry: list = []
            for fut, name in futures.items():
                try:
                    fut.result()
                except (TimeoutError, FuturesTimeout):
                    # transient device-wait expiry: retry once after the
                    # first pass drains, sequentially. FuturesTimeout is
                    # what Future.result(timeout=) raises; it only became
                    # the builtin TimeoutError in Python 3.11, and 3.10
                    # is supported.
                    retry.append(name)
                except Exception as exc:
                    failed += 1
                    print(f"# {name}: {type(exc).__name__}: {exc}",
                          file=sys.stderr)
            if retry and len(retry) == len(names):
                # EVERY job timed out: the device is down, not hiccuping.
                # Retrying would serialize len(names) more bounded waits
                # (hours on a big sweep) to learn the same thing.
                failed += len(retry)
                print(f"# all {len(retry)} jobs timed out; device down — "
                      "skipping retry pass", file=sys.stderr)
                retry = []
            for name in retry:
                try:
                    run_one(name)
                except Exception as exc:
                    failed += 1
                    print(f"# {name} (retry): {type(exc).__name__}: {exc}",
                          file=sys.stderr)
        elapsed = time.perf_counter() - t0
        stats = batcher.stats()
        if tracer is not None:
            with open(trace_out, "w", encoding="utf-8") as fh:
                for summary in reversed(tracer.list(limit=len(names))):
                    doc = tracer.get(summary["trace_id"]).as_dict()
                    doc["name"] = summary["name"]
                    fh.write(json.dumps(doc) + "\n")
    finally:
        handler.close()
        codec_batcher.close()
        if own_batcher:
            batcher.close()

    done = len(names) - failed
    return {
        "images": done,
        "failed": failed,
        "images_per_sec": round(done / elapsed, 1) if elapsed > 0 else 0.0,
        "batches": stats["batches"],
        "mean_occupancy": round(stats["mean_occupancy"], 2),
        # the same efficiency vocabulary the HTTP path serves at
        # /debug/perf (rolling window over this sweep's launches)
        "padding_waste": round(stats["padding_waste"], 2),
        "queue_wait_share": round(stats["queue_wait_share"], 2),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="flyimg-tpu-bulk", description=__doc__)
    ap.add_argument("--src", required=True, help="source image directory")
    ap.add_argument("--out", required=True, help="output directory")
    ap.add_argument("--options", required=True,
                    help="URL options DSL, e.g. w_256,h_256,c_1")
    ap.add_argument("--format", default="jpg",
                    choices=("jpg", "png", "webp", "gif"))
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--quality", type=int, default=None)
    ap.add_argument("--trace-out", default=None,
                    help="write one JSON line of spans per file here")
    ns = ap.parse_args(argv)

    summary = bulk_process(
        ns.src, ns.out, ns.options,
        out_format=ns.format, workers=ns.workers, quality=ns.quality,
        trace_out=ns.trace_out,
    )
    print(json.dumps(summary))
    return 1 if summary["failed"] else 0


if __name__ == "__main__":
    sys.exit(main())
