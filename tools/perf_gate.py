"""Perf-regression gate: a deterministic CPU-backend micro-suite with a
checked-in baseline and per-stage attribution on failure.

The BASELINE targets live in prose and bench artifacts; nothing gated a
PR that quietly made decode 2x slower. This tool closes that gap:

- ``--update`` runs the micro-suite and writes
  ``benchmarks/perf_baseline.json`` (stage medians + a host-calibration
  yardstick).
- ``--check`` re-runs the suite, **normalizes by the calibration ratio**
  (a faster/slower host shifts every stage together; the blake2b
  yardstick cancels that), and fails (exit 1) when any stage's median
  exceeds ``baseline * tolerance`` + an absolute jitter floor — printing
  WHICH stage regressed and by how much.

The workload is the handler's own cache-miss pipeline
(``ImageHandler.transform_bytes`` — the exact code path serving runs),
so the per-stage attribution (decode / device / encode / total) comes
from the same ``timings`` dict the serving path reports, plus the
cache-hit path via ``process_image``. Deterministic: seeded sources,
CPU backend, sequential submits (every batch is a lone flush).

``--inject device=0.05`` arms the fault harness with a latency spike at
the ``batcher.execute`` point — the self-test proving the gate actually
fails when a stage gets slower (tests/test_perf_gate.py runs it).

The baseline also carries the **per-plan cost snapshot** (schema 2): the
XLA cost ledger's FLOPs / bytes-accessed totals for the programs the
micro-suite compiles (runtime/costledger.py — the same figures
``/debug/plans`` serves). Latency bands absorb host noise; the cost
figures are *deterministic* for one jax version, so a kernel change that
silently multiplies device FLOPs fails ``--check`` even when this CPU
host can't see the latency difference — exactly the gate the
banded-resample promotion (ROADMAP item 1) is judged by.
``--inject-cost flops=3.0`` is the matching self-test: it scales the
measured FLOPs and must fail the gate.

**Schema 3** adds a per-kernel column: the suite runs once per resample
kernel variant (``dense`` and ``banded``, ops/resample.py kernel modes;
docs/kernels.md) and the baseline keys each measurement under
``kernels.<variant>`` — so a change to one variant can never silently
regress the *other* (the dense-only schema-2 gate would have waved a
banded regression through, and vice versa once banded is the default).
``--kernel dense|banded|both`` selects the legs; a baseline missing the
requested kernel section reports it as ``missing`` without failing, so
schema-1/2 baselines stay checkable until refreshed.

**Schema 4** adds the ``reuse_hit`` stage: the handler's own end-to-end
serve time for a cache miss answered by the derivative-reuse rewriter
(docs/caching.md) — a second handler with ``reuse_enable`` on renders
distinct targets from a seeded pure ancestor, and the measured
``timings["reuse_hit"]`` is gated like every other stage, so later PRs
cannot silently regress the reuse path. Pre-schema-4 baselines report
the row as ``missing`` without failing.

**Schema 5** splits the decode stage by decode mode: dedicated legs
measure ``decode_full`` (the PNG micro-suite's full-frame decode),
``decode_prescale`` (a JPEG source whose small target engages the DCT
prescale), and ``decode_roi`` (a crop-dominant plan on a handler with
``decode_roi`` on — the ROI window decode, docs/host-pipeline.md), each
gated like any other stage so a codec change cannot silently regress one
decode mode while another hides it. A pre-5 baseline's ``decode`` row
stands in for ``decode_full`` (the then-only mode measured); its missing
prescale/roi rows report ``missing`` without failing.

CI: the ``perf-gate`` job runs ``--check`` with wide, CI-noise-tolerant
bands (see .github/workflows/ci.yml). Baseline refresh policy:
benchmarks/README.md.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

DEFAULT_BASELINE = os.path.join(
    REPO_ROOT, "benchmarks", "perf_baseline.json"
)
STAGES = (
    "decode", "device", "encode", "total", "cache_hit", "reuse_hit",
    # per-decode-mode legs (schema 5): the handler stamps
    # timings["decode_<mode>"] per miss (service/handler.py _decode_mode)
    "decode_full", "decode_prescale", "decode_roi",
)
# per-plan cost figures gated alongside the latency stages (schema 2);
# cost analysis is deterministic per jax version, so its band is tight
COST_FIELDS = ("flops_total", "bytes_total")
# absolute per-stage slack added on top of the relative band: sub-ms
# stages on shared runners jitter by fractions of a ms that no relative
# band should be asked to absorb
ABS_SLACK_MS = 2.0
SCHEMA = 5
# the resample-kernel variants each baseline carries a column for
# (ops/resample.py KERNEL_MODES minus 'auto', which resolves to one of
# these per geometry and would gate nothing new)
KERNELS = ("dense", "banded")


def _calibrate(rounds: int = 5) -> float:
    """Host-speed yardstick: median seconds to blake2b-hash a fixed 4 MiB
    buffer. Purely CPU-bound and allocation-free, so the baseline/current
    ratio tracks single-core host speed — the factor every pipeline stage
    shares — without touching any of the code under test."""
    import hashlib

    buf = b"\xa5" * (4 << 20)
    times = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        hashlib.blake2b(buf).digest()
        times.append(time.perf_counter() - t0)
    return float(statistics.median(times))


def _parse_inject(spec: str):
    """'device=0.05' -> installs a latency spike at the stage's fault
    point (only the device stage has one; the point is the proof that a
    slowdown FAILS the gate, not a general stage simulator)."""
    stage, _, seconds = spec.partition("=")
    stage = stage.strip()
    if stage != "device":
        raise SystemExit(
            f"--inject supports 'device=<seconds>' (got {spec!r}); the "
            "device stage is the one with a batcher fault point"
        )
    return stage, float(seconds)


def _parse_inject_cost(spec: str) -> float:
    """'flops=3.0' -> multiply the measured FLOP total — the self-test
    proving an injected cost regression FAILS the gate (the cost-side
    twin of --inject's latency spike)."""
    field, _, factor = spec.partition("=")
    if field.strip() != "flops":
        raise SystemExit(
            f"--inject-cost supports 'flops=<factor>' (got {spec!r})"
        )
    return float(factor)


def measure(repeats: int = 30, warmup: int = 3,
            inject: str | None = None,
            inject_cost: str | None = None,
            kernel: str | None = None) -> dict:
    """Run the micro-suite for ONE resample-kernel leg; returns
    {kernel, stages: {name: {median_ms}}, plan_cost: {...},
    calibration_ms, repeats}. ``kernel`` (dense|banded) pins the
    process-wide resample formulation for the leg and restores the prior
    mode after — the program caches key on the variant, so both legs'
    programs coexist and each leg's cost snapshot diffs only its own
    newly-compiled programs. Import-heavy work happens here so --help
    stays instant."""
    from flyimg_tpu.ops.resample import kernel_mode, set_kernel_mode

    prev_kernel = kernel_mode()

    import numpy as np

    from flyimg_tpu.appconfig import AppParameters
    from flyimg_tpu.codecs import encode
    from flyimg_tpu.runtime.batcher import BatchController
    from flyimg_tpu.service.handler import ImageHandler
    from flyimg_tpu.service.output_image import EXT_TO_MIME, OutputSpec
    from flyimg_tpu.spec.options import OptionsBag
    from flyimg_tpu.storage.local import LocalStorage
    from flyimg_tpu.testing import faults

    tmp = tempfile.mkdtemp(prefix="flyimg-perf-gate-")
    params = AppParameters({
        "tmp_dir": os.path.join(tmp, "t"),
        "upload_dir": os.path.join(tmp, "u"),
        "batch_deadline_ms": 0.5,
    })
    storage = LocalStorage(params)
    batcher = BatchController(max_batch=8, deadline_ms=0.5)
    handler = ImageHandler(storage, params, batcher=batcher)

    from flyimg_tpu.runtime.costledger import get_ledger

    injector = None
    if inject:
        stage, seconds = _parse_inject(inject)
        injector = faults.FaultInjector()
        injector.plan("batcher.execute", faults.latency_spike(seconds))
        faults.install(injector)
    cost_factor = _parse_inject_cost(inject_cost) if inject_cost else 1.0

    # per-plan cost snapshot: diff the ledger around the run so only the
    # programs THIS suite compiles count (the ledger is process-wide)
    keys_before = {row["key"] for row in get_ledger().entries()}

    rng = np.random.default_rng(20260803)
    source = rng.integers(0, 255, (96, 128, 3), dtype=np.uint8)
    data = encode(source, "png")
    options_str = "w_48,h_36,c_1,o_png"

    rows: dict = {stage: [] for stage in STAGES}
    try:
        # pin the process-wide kernel mode INSIDE the try so any failure
        # (in-process callers: the pytest suite) restores prev_kernel —
        # the mode only matters at submit time, so pinning here still
        # covers every program build below
        if kernel is not None:
            set_kernel_mode(kernel)
        def run_miss(tag: str) -> dict:
            timings: dict = {}
            options = OptionsBag(options_str)
            spec = OutputSpec(
                name=f"gate-{tag}.png", extension="png",
                mime=EXT_TO_MIME["png"],
            )
            t0 = time.perf_counter()
            handler.transform_bytes(data, options, spec, timings)
            timings["total"] = time.perf_counter() - t0
            return timings

        for i in range(max(warmup, 1)):  # first run pays the XLA compile
            run_miss(f"warm-{i}")
        for i in range(repeats):
            timings = run_miss(f"run-{i}")
            # decode_full rides the main suite: the PNG source decodes
            # full-frame, so its per-mode stamp IS the full-mode figure
            for stage in ("decode", "decode_full", "device", "encode",
                          "total"):
                rows[stage].append(timings[stage])

        # cache-hit path: populate once, then time pure hits through the
        # full process_image choke point (security, options, storage)
        src_path = os.path.join(tmp, "hit-source.png")
        with open(src_path, "wb") as fh:
            fh.write(data)
        handler.process_image("w_40,h_30,o_png", src_path)
        for _ in range(repeats):
            t0 = time.perf_counter()
            result = handler.process_image("w_40,h_30,o_png", src_path)
            rows["cache_hit"].append(time.perf_counter() - t0)
            assert result.from_cache

        # cost-snapshot scope closes HERE: the plan_cost figures gate the
        # micro-suite's own device programs; the reuse leg below compiles
        # its own (ancestor + from-ancestor geometries) which are timed
        # but not cost-gated — its latency column is the gate
        keys_suite = {row["key"] for row in get_ledger().entries()}

        # reuse-hit path (schema 4; docs/caching.md): a second handler
        # with the rewriter on, one seeded pure ancestor, then distinct
        # targets (q_ varies the derived key) each served from the
        # ancestor's pixels — the handler's own timings["reuse_hit"] is
        # the gated figure
        params_reuse = AppParameters({
            "tmp_dir": os.path.join(tmp, "rt"),
            "upload_dir": os.path.join(tmp, "ru"),
            "batch_deadline_ms": 0.5,
            "reuse_enable": True,
        })
        handler_reuse = ImageHandler(
            LocalStorage(params_reuse), params_reuse, batcher=batcher
        )
        reuse_src = os.path.join(tmp, "reuse-source.png")
        with open(reuse_src, "wb") as fh:
            fh.write(data)
        handler_reuse.process_image("w_96,o_png", reuse_src)  # ancestor
        for i in range(repeats):
            result = handler_reuse.process_image(
                f"w_40,h_30,c_1,q_{88 - i},o_png", reuse_src
            )
            assert result.reused_from, "perf-gate reuse leg missed"
            rows["reuse_hit"].append(result.timings["reuse_hit"])

        # decode-mode legs (schema 5; docs/host-pipeline.md): a JPEG
        # source big enough that w_64 engages the 1/8 DCT prescale, and
        # an extract-dominant plan on a decode_roi handler engages the
        # ROI window decode. Timed but (like the reuse leg) outside the
        # plan-cost snapshot — their latency columns are the gate.
        jpeg_arr = rng.integers(0, 255, (768, 1024, 3), dtype=np.uint8)
        jpeg_data = encode(jpeg_arr, "jpg", quality=85, mozjpeg=False)
        params_roi = AppParameters({
            "tmp_dir": os.path.join(tmp, "dt"),
            "upload_dir": os.path.join(tmp, "du"),
            "batch_deadline_ms": 0.5,
            "decode_roi": True,
        })
        handler_roi = ImageHandler(
            LocalStorage(params_roi), params_roi, batcher=batcher
        )
        decode_legs = (
            ("decode_prescale", handler, "w_64,h_48,o_png"),
            (
                "decode_roi", handler_roi,
                "e_1,p1x_256,p1y_128,p2x_640,p2y_512,w_64,o_png",
            ),
        )
        for stage_name, leg_handler, leg_options in decode_legs:
            for i in range(max(warmup, 1)):
                leg_timings: dict = {}
                leg_handler.transform_bytes(
                    jpeg_data, OptionsBag(leg_options),
                    OutputSpec(
                        name=f"gate-{stage_name}-warm-{i}.png",
                        extension="png", mime=EXT_TO_MIME["png"],
                    ),
                    leg_timings,
                )
                assert stage_name in leg_timings, (
                    f"perf-gate {stage_name} leg did not engage its "
                    f"decode mode (got {sorted(leg_timings)})"
                )
            for i in range(repeats):
                leg_timings = {}
                leg_handler.transform_bytes(
                    jpeg_data, OptionsBag(leg_options),
                    OutputSpec(
                        name=f"gate-{stage_name}-{i}.png",
                        extension="png", mime=EXT_TO_MIME["png"],
                    ),
                    leg_timings,
                )
                rows[stage_name].append(leg_timings[stage_name])
    finally:
        if injector is not None:
            faults.clear()
        batcher.close()
        set_kernel_mode(prev_kernel)

    # the suite's per-plan cost snapshot (XLA cost analysis from the
    # ledger entries the run created): deterministic per jax version —
    # what makes a FLOP regression gateable on a noisy CPU host. Nulled
    # (and not gated) when the backend returned no cost analysis.
    suite_rows = [
        row for row in get_ledger().entries()
        if row["key"] not in keys_before and row["key"] in keys_suite
        and row["costed"]
    ]
    plan_cost = {
        "programs": len(suite_rows),
        "flops_total": (
            sum(row["flops"] for row in suite_rows) * cost_factor
            if suite_rows else None
        ),
        "bytes_total": (
            sum(row["bytes_accessed"] or 0.0 for row in suite_rows)
            * cost_factor
            if suite_rows else None
        ),
        "plans": {
            row["key"]: {
                "flops": row["flops"],
                "bytes_accessed": row["bytes_accessed"],
                "descriptor": row["descriptor"],
            }
            for row in suite_rows
        },
    }

    return {
        "kernel": kernel if kernel is not None else prev_kernel,
        "repeats": repeats,
        "calibration_ms": round(_calibrate() * 1000.0, 4),
        "stages": {
            stage: {
                "median_ms": round(
                    statistics.median(values) * 1000.0, 4
                )
            }
            for stage, values in rows.items()
        },
        "plan_cost": plan_cost,
    }


def measure_suite(kernels=KERNELS, repeats: int = 30, warmup: int = 3,
                  inject: str | None = None,
                  inject_cost: str | None = None) -> dict:
    """Run one measure() leg per resample-kernel variant and assemble
    the schema-3 document: ``kernels.<variant> = {stages, plan_cost}``
    with one shared host-calibration yardstick."""
    legs = {k: measure(repeats=repeats, warmup=warmup, inject=inject,
                       inject_cost=inject_cost, kernel=k)
            for k in kernels}
    first = next(iter(legs.values()))
    return {
        "schema": SCHEMA,
        "repeats": repeats,
        "calibration_ms": first["calibration_ms"],
        "kernels": {
            k: {"stages": leg["stages"], "plan_cost": leg["plan_cost"]}
            for k, leg in legs.items()
        },
    }


def kernel_sections(doc: dict) -> dict:
    """{variant: {stages, plan_cost}} from any baseline schema: schema-3
    docs carry ``kernels`` natively; schema-1/2 docs (and raw measure()
    legs) ARE the dense column — their top-level stages/plan_cost were
    measured with the then-only dense kernel."""
    if "kernels" in doc:
        return dict(doc["kernels"])
    return {"dense": {
        "stages": doc.get("stages", {}),
        "plan_cost": doc.get("plan_cost"),
    }}


def compare(baseline: dict, current: dict, tolerance: float,
            abs_slack_ms: float = ABS_SLACK_MS,
            cost_tolerance: float = 1.2):
    """-> (ok, report_rows). A stage regresses when its current median
    exceeds ``baseline * scale * tolerance + abs_slack_ms`` where
    ``scale`` is the host-calibration ratio (current / baseline hosts).
    Per-plan cost fields (schema 2) regress on
    ``current > baseline * cost_tolerance`` — NO host scaling: FLOPs and
    bytes are properties of the compiled programs, not the host. A
    schema-1 baseline (or an uncosted backend) reports the cost rows as
    ``missing`` without failing, so old baselines stay checkable.

    Schema 3: both docs resolve to per-kernel sections via
    ``kernel_sections`` and every current (kernel, stage) pair is gated
    against the baseline's same-kernel column. A kernel the baseline
    never measured (e.g. ``banded`` against a schema-2 baseline) reports
    every row as ``missing`` without failing — refresh policy in
    benchmarks/README.md. Report rows carry a ``kernel`` field."""
    cal_base = float(baseline.get("calibration_ms") or 0.0)
    cal_now = float(current.get("calibration_ms") or 0.0)
    scale = (cal_now / cal_base) if cal_base > 0 and cal_now > 0 else 1.0
    base_sections = kernel_sections(baseline)
    cur_sections = kernel_sections(current)
    rows = []
    cost_rows = []
    ok = True
    for kernel, cur_sec in cur_sections.items():
        base_sec = base_sections.get(kernel) or {}
        base_stages = base_sec.get("stages") or {}
        cur_stages = cur_sec.get("stages") or {}
        for stage in STAGES:
            base = base_stages.get(stage, {}).get("median_ms")
            cur = cur_stages.get(stage, {}).get("median_ms")
            if base is None and cur is not None and stage == "decode_full":
                # pre-schema-5 baselines measured exactly one decode
                # mode — their `decode` row reads as `full` (the
                # prescale/roi legs stay `missing`, non-failing)
                base = base_stages.get("decode", {}).get("median_ms")
            if base is None and cur is None:
                # neither side measured this stage (e.g. schema-4 docs
                # compared against each other never ran the decode-mode
                # legs): nothing to say, not even "missing"
                continue
            if base is None or cur is None:
                rows.append({
                    "kernel": kernel, "stage": stage, "verdict": "missing",
                    "baseline_ms": base, "current_ms": cur,
                })
                continue
            allowed = base * scale * tolerance + abs_slack_ms
            ratio = (
                cur / (base * scale) if base * scale > 0 else float("inf")
            )
            regressed = cur > allowed
            ok = ok and not regressed
            rows.append({
                "kernel": kernel,
                "stage": stage,
                "baseline_ms": base,
                "scaled_baseline_ms": round(base * scale, 4),
                "current_ms": cur,
                "ratio": round(ratio, 3),
                "allowed_ms": round(allowed, 4),
                "verdict": "REGRESSED" if regressed else "ok",
            })
        base_cost = base_sec.get("plan_cost") or {}
        cur_cost = cur_sec.get("plan_cost") or {}
        for field in COST_FIELDS:
            base = base_cost.get(field)
            cur = cur_cost.get(field)
            if base is None or cur is None or base <= 0:
                cost_rows.append({
                    "kernel": kernel, "field": field, "verdict": "missing",
                    "baseline": base, "current": cur,
                })
                continue
            ratio = cur / base
            regressed = cur > base * cost_tolerance
            ok = ok and not regressed
            cost_rows.append({
                "kernel": kernel,
                "field": field,
                "baseline": base,
                "current": cur,
                "ratio": round(ratio, 3),
                "allowed": round(base * cost_tolerance, 2),
                "verdict": "REGRESSED" if regressed else "ok",
            })
    return ok, {"scale": round(scale, 4), "tolerance": tolerance,
                "cost_tolerance": cost_tolerance, "rows": rows,
                "cost_rows": cost_rows}


def _print_report(report: dict, ok: bool) -> None:
    print(
        f"host-calibration scale {report['scale']}x, "
        f"tolerance {report['tolerance']}x"
    )
    print(
        f"{'kernel':<7} {'stage':<10} {'baseline':>10} {'scaled':>10} "
        f"{'current':>10} {'ratio':>7} {'allowed':>10}  verdict"
    )
    for row in report["rows"]:
        kern = row.get("kernel", "dense")
        if row["verdict"] == "missing":
            print(f"{kern:<7} {row['stage']:<10} {'-':>10} {'-':>10} "
                  f"{row['current_ms'] or '-':>10}  missing from baseline")
            continue
        print(
            f"{kern:<7} {row['stage']:<10} {row['baseline_ms']:>9.2f}m "
            f"{row['scaled_baseline_ms']:>9.2f}m {row['current_ms']:>9.2f}m "
            f"{row['ratio']:>6.2f}x {row['allowed_ms']:>9.2f}m  "
            f"{row['verdict']}"
        )
    for row in report.get("cost_rows", []):
        kern = row.get("kernel", "dense")
        if row["verdict"] == "missing":
            print(f"{kern:<7} cost {row['field']:<12} missing "
                  "(pre-schema-3 baseline or uncosted backend)")
            continue
        print(
            f"{kern:<7} cost {row['field']:<12} {row['baseline']:.3e} -> "
            f"{row['current']:.3e} ({row['ratio']}x, allowed "
            f"{row['allowed']:.3e})  {row['verdict']}"
        )
    if ok:
        print("perf gate: PASS")
    else:
        slowest = [
            r for r in report["rows"] if r.get("verdict") == "REGRESSED"
        ] + [
            r for r in report.get("cost_rows", [])
            if r.get("verdict") == "REGRESSED"
        ]
        attribution = ", ".join(
            f"{r.get('kernel', 'dense')}/"
            f"{r.get('stage') or r.get('field')} {r['ratio']}x over "
            "baseline"
            for r in slowest
        )
        print(f"perf gate: FAIL — {attribution}")


def main(argv=None) -> int:
    from flyimg_tpu.appconfig import AppParameters

    defaults = AppParameters()
    ap = argparse.ArgumentParser(prog="perf-gate", description=__doc__)
    mode = ap.add_mutually_exclusive_group(required=True)
    mode.add_argument(
        "--check", action="store_true",
        help="compare against the checked-in baseline; exit 1 on regression",
    )
    mode.add_argument(
        "--update", action="store_true",
        help="measure and (re)write the baseline file",
    )
    ap.add_argument("--baseline", default=DEFAULT_BASELINE)
    ap.add_argument(
        "--tolerance", type=float,
        default=float(defaults.by_key("perf_gate_tolerance", 1.6)),
        help="relative band: regression when current > baseline*scale*tol",
    )
    ap.add_argument(
        "--repeats", type=int,
        default=int(defaults.by_key("perf_gate_repeats", 30)),
    )
    ap.add_argument(
        "--warmup", type=int,
        default=int(defaults.by_key("perf_gate_warmup", 3)),
    )
    ap.add_argument(
        "--inject", default=None, metavar="STAGE=SECONDS",
        help="arm a latency-spike fault (device=0.05) to prove the gate "
             "fails on a real slowdown",
    )
    ap.add_argument(
        "--inject-cost", default=None, metavar="FIELD=FACTOR",
        help="multiply the measured plan-cost figures (flops=3.0) to "
             "prove the gate fails on a FLOP regression",
    )
    ap.add_argument(
        "--cost-tolerance", type=float,
        default=float(defaults.by_key("perf_gate_cost_tolerance", 1.2)),
        help="relative band for the per-plan FLOP/byte figures (no host "
             "scaling — cost analysis is deterministic per jax version)",
    )
    ap.add_argument(
        "--kernel", choices=(*KERNELS, "both"), default="both",
        help="which resample-kernel legs to run (schema-3 per-kernel "
             "columns; 'both' measures dense AND banded so neither "
             "variant can silently regress)",
    )
    ap.add_argument(
        "--json", action="store_true",
        help="also print the full current measurement as one JSON line",
    )
    ns = ap.parse_args(argv)

    kernels = KERNELS if ns.kernel == "both" else (ns.kernel,)
    current = measure_suite(
        kernels, repeats=ns.repeats, warmup=ns.warmup, inject=ns.inject,
        inject_cost=ns.inject_cost,
    )
    if ns.json:
        print(json.dumps(current))

    if ns.update:
        os.makedirs(os.path.dirname(ns.baseline), exist_ok=True)
        with open(ns.baseline, "w") as fh:
            json.dump(current, fh, indent=1)
            fh.write("\n")
        print(f"wrote {ns.baseline}")
        for kern, sec in kernel_sections(current).items():
            for stage, doc in sec["stages"].items():
                print(f"  {kern:<7} {stage:<10} {doc['median_ms']:9.2f} ms")
        return 0

    if not os.path.exists(ns.baseline):
        print(
            f"no baseline at {ns.baseline} — run --update first",
            file=sys.stderr,
        )
        return 2
    with open(ns.baseline) as fh:
        baseline = json.load(fh)
    ok, report = compare(
        baseline, current, ns.tolerance,
        cost_tolerance=ns.cost_tolerance,
    )
    _print_report(report, ok)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
