"""Banded K-tap resample (ISSUE 8): dense-vs-banded numerical parity
across the full option matrix (downscale 16x-1.05x, upscale, crop-fill,
extent pad, rotate, every supported f_ filter), the K-from-support math
shared with benchmarks/resample_experiment.py, program-cache/ledger key
separation (dense and banded programs must never collide), dense-default
byte stability behind the ``resample_kernel`` knob, the cost-ledger
proof of >=10x FLOP reduction on the canonical 4k -> 300x250 crop-fill
plan via /debug/plans, and the banded-enabled serving smoke leg."""

import asyncio
import io
import math
import os

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer
from PIL import Image

from flyimg_tpu.appconfig import AppParameters
from flyimg_tpu.codecs import encode
from flyimg_tpu.ops import compose, resample
from flyimg_tpu.ops.compose import build_program, run_plan
from flyimg_tpu.ops.resample import (
    FILTER_SUPPORT,
    band_taps,
    bucket_taps,
    select_band_taps,
    set_kernel_mode,
)
from flyimg_tpu.spec.options import OptionsBag
from flyimg_tpu.spec.plan import FILTER_METHODS, build_plan

from test_ops import make_test_image


@pytest.fixture(autouse=True)
def _restore_kernel_mode():
    """The kernel mode is process-wide (like the program caches it keys
    into); every test here must leave it as it found it."""
    before = resample.kernel_mode()
    yield
    set_kernel_mode(before)


# ---------------------------------------------------------------------------
# K-from-support math (shared with benchmarks/resample_experiment.py)


def test_band_taps_grows_with_downscale_factor():
    # upscales and identity: kernel at natural width
    assert band_taps("lanczos3", 0.25) == band_taps("lanczos3", 1.0) == 8
    # downscale antialiasing stretches the kernel by the scale factor
    assert band_taps("lanczos3", 2.0) == 2 * math.ceil(6.0) + 2 == 14
    assert band_taps("lanczos3", 16.0) == 2 * math.ceil(48.0) + 2 == 98
    # narrower kernels need fewer taps at the same scale
    assert band_taps("triangle", 4.0) < band_taps("lanczos3", 4.0)
    assert band_taps("box", 1.0) == 4


def test_bucket_taps_power_of_two_ladder():
    assert bucket_taps(3) == 8      # floor
    assert bucket_taps(8) == 8
    assert bucket_taps(9) == 16
    assert bucket_taps(14) == 16
    assert bucket_taps(98) == 128   # the 16x-downscale case: K > 16


def test_filter_support_covers_every_serving_method():
    """Every method the f_ vocabulary can resolve to has an explicit
    support radius — a new filter landing without one would silently ride
    the lanczos3 default width."""
    for method in set(FILTER_METHODS.values()):
        assert method in FILTER_SUPPORT, method


def test_select_band_taps_policy():
    in_hw = (1024, 1408)
    geom = dict(span_y=(0.0, 977.0), span_x=(0.0, 1303.0),
                out_true_hw=(250.0, 300.0))
    assert select_band_taps("dense", "lanczos3", in_hw, **geom) is None
    taps = select_band_taps("banded", "lanczos3", in_hw, **geom)
    assert taps is not None and taps[0] <= 32 and taps[1] <= 32
    # auto bands whenever the band is strictly narrower than the matrix
    assert select_band_taps("auto", "lanczos3", in_hw, **geom) == taps
    # ... and stays dense when the band would cover the axis (deep
    # downscale of a small axis: K buckets past the input size)
    assert select_band_taps(
        "auto", "lanczos3", (128, 128),
        span_y=(0.0, 128.0), span_x=(0.0, 128.0), out_true_hw=(4.0, 4.0),
    ) is None
    with pytest.raises(ValueError):
        select_band_taps("sparse", "lanczos3", in_hw, **geom)
    with pytest.raises(ValueError):
        set_kernel_mode("sparse")


def test_band_covering_whole_axis_degrades_to_dense_weights():
    """taps >= axis: the band is the full axis in index order — output
    must match the dense path exactly (the K == in_size clamp case)."""
    import jax.numpy as jnp

    img = make_test_image(24, 16).astype(np.float32)
    span_y = jnp.array([0.0, 16.0], jnp.float32)
    span_x = jnp.array([0.0, 24.0], jnp.float32)
    out_true = jnp.array([8.0, 12.0], jnp.float32)
    in_true = jnp.array([16.0, 24.0], jnp.float32)
    dense = np.asarray(resample.resample_image(
        jnp.asarray(img), (8, 12), span_y, span_x, out_true, in_true,
    ))
    banded = np.asarray(resample.resample_image_banded(
        jnp.asarray(img), (8, 12), span_y, span_x, out_true, in_true,
        (16, 24),
    ))
    np.testing.assert_allclose(banded, dense, atol=1e-3)


# ---------------------------------------------------------------------------
# parity sweep: dense vs banded through the real device program


def _render_both(options_str, src_w, src_h, seed=7):
    img = make_test_image(src_w, src_h, seed=seed)
    plan = build_plan(OptionsBag(options_str), src_w, src_h)
    set_kernel_mode("dense")
    dense = run_plan(img, plan)
    set_kernel_mode("banded")
    banded = run_plan(img, plan)
    return dense, banded


SWEEP = [
    # geometry matrix: downscale 16x .. 1.05x, upscale 1.05x .. 4x,
    # crop-fill window, extent pad, rotate
    ("w_100", 1600, 1200),            # 16x downscale -> K bucket 128 (>16)
    ("w_300", 420, 280),              # 1.4x downscale
    ("w_300", 315, 210),              # 1.05x downscale
    ("w_260,pns_0", 248, 166),        # ~1.05x upscale
    ("w_400,pns_0", 100, 80),         # 4x upscale
    ("w_150,h_125,c_1", 1303, 977),   # crop-fill (flagship proportions)
    ("ett_360x280,bg_blue,w_300", 500, 400),   # extent pad after resample
    ("r_45,w_200", 400, 300),         # rotate rides on the resample output
] + [
    # every supported f_ filter name through one common downscale
    (f"w_150,f_{name}", 640, 480) for name in sorted(FILTER_METHODS)
]


@pytest.mark.parametrize("options_str,src_w,src_h", SWEEP)
def test_banded_matches_dense_across_option_matrix(
    options_str, src_w, src_h
):
    """ISSUE 8 acceptance: parity at <= 1 u8 level (1e-3 of full scale
    survives the round-trip only as the rounding boundary) across the
    full option matrix, including geometries where K exceeds 16."""
    dense, banded = _render_both(options_str, src_w, src_h)
    assert dense.shape == banded.shape
    diff = np.abs(dense.astype(np.int16) - banded.astype(np.int16))
    assert diff.max() <= 1, (
        f"{options_str}: max diff {diff.max()} at "
        f"{np.unravel_index(diff.argmax(), diff.shape)}"
    )
    # the diff must be rounding noise, not a misplaced band: essentially
    # no pixel may sit on the boundary AND the images must correlate
    assert (diff > 0).mean() < 0.05, f"{options_str}: systematic drift"


def test_dense_default_is_byte_stable_behind_the_knob():
    """``resample_kernel: dense`` (the default until a chip measurement decides)
    reproduces the pre-banded outputs byte-for-byte: flipping the knob to
    banded and back must leave the dense render untouched."""
    assert AppParameters().by_key("resample_kernel") == "dense"
    img = make_test_image(421, 333, seed=3)
    plan = build_plan(OptionsBag("w_180,h_140,c_1"), 421, 333)
    set_kernel_mode("dense")
    first = run_plan(img, plan)
    set_kernel_mode("banded")
    run_plan(img, plan)
    set_kernel_mode("dense")
    again = run_plan(img, plan)
    assert first.tobytes() == again.tobytes()


# ---------------------------------------------------------------------------
# program-cache / cost-ledger key separation


def test_dense_and_banded_programs_get_distinct_keys_and_entries():
    """One plan, two kernel variants -> two program-cache entries and two
    cost-ledger entries; colliding would serve one variant under the
    other's key (and ledger costs would be unattributable)."""
    from flyimg_tpu.runtime.costledger import get_ledger

    img = make_test_image(259, 201, seed=9)   # unique geometry: fresh keys
    plan = build_plan(OptionsBag("w_97,h_81,c_1"), 259, 201)
    cache_before = build_program.cache_info().currsize
    set_kernel_mode("dense")
    run_plan(img, plan)
    set_kernel_mode("banded")
    run_plan(img, plan)
    assert build_program.cache_info().currsize == cache_before + 2

    rows = [
        row for row in get_ledger().entries()
        if (row["descriptor"] or {}).get("resample_out") == [81, 97]
        and (row["descriptor"] or {}).get("batch") is None
    ]
    kernels = {row["descriptor"]["kernel"]: row for row in rows}
    assert set(kernels) == {"dense", "banded"}
    assert kernels["dense"]["key"] != kernels["banded"]["key"]
    assert kernels["banded"]["descriptor"]["band_taps"] is not None


# ---------------------------------------------------------------------------
# the cost-ledger proof: canonical 4k -> 300x250 crop-fill, via /debug/plans


def _serve(tmp_path, coro_fn, **params_extra):
    from flyimg_tpu.service.app import make_app

    params = {
        "tmp_dir": str(tmp_path / "tmp"),
        "upload_dir": str(tmp_path / "uploads"),
        "batch_deadline_ms": 1.0,
        "debug": True,
    }
    params.update(params_extra)

    async def go():
        app = make_app(AppParameters(params))
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            return await coro_fn(client)
        finally:
            await client.close()

    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(go())
    finally:
        loop.close()


def test_debug_plans_proves_10x_flop_reduction_on_canonical_4k_plan(
    tmp_path,
):
    """ISSUE 8 acceptance: the cost ledger shows >=10x fewer FLOPs for
    the banded program of the canonical 4k -> 300x250 crop-fill plan,
    asserted through /debug/plans. The programs are AOT-compiled from
    abstract shapes (ProgramHandle.precompile) — cost analysis needs the
    compile, not an execution a CPU test host would take seconds on."""
    import jax
    import jax.numpy as jnp

    src_w, src_h = 3840, 2160
    plan = build_plan(OptionsBag("w_300,h_250,c_1"), src_w, src_h)
    layout = compose.plan_layout(plan)
    in_shape = (compose._bucket_dim(src_h), compose._bucket_dim(src_w))
    device_plan = plan.device_plan()
    band = select_band_taps(
        "banded", plan.filter_method, in_shape,
        layout.span_y, layout.span_x, layout.out_true,
    )
    assert band is not None
    handles = {
        "dense": build_program(
            in_shape, layout.resample_out, layout.pad_canvas,
            layout.pad_offset, device_plan, None,
        ),
        "banded": build_program(
            in_shape, layout.resample_out, layout.pad_canvas,
            layout.pad_offset, device_plan, band,
        ),
    }
    args = (
        jax.ShapeDtypeStruct((*in_shape, 3), jnp.uint8),
        *(jax.ShapeDtypeStruct((2,), jnp.float32) for _ in range(4)),
    )
    for handle in handles.values():
        handle.precompile(args)

    async def scenario(client):
        return await (await client.get("/debug/plans")).json()

    # /debug/plans serves the top rows by cumulative device seconds; in
    # a shared test process the ledger holds hundreds of LAUNCHED
    # entries that outrank these never-executed compiles. Shrink the
    # process-wide table to its newest entries (ours) for the scrape.
    from flyimg_tpu.runtime.costledger import get_ledger

    get_ledger().configure(max_entries=8)
    try:
        doc = _serve(tmp_path, scenario)
    finally:
        get_ledger().configure(max_entries=256)
    by_key = {row["key"]: row for row in doc["plans"]}
    dense_row = by_key[handles["dense"].ledger_key]
    banded_row = by_key[handles["banded"].ledger_key]
    assert dense_row["descriptor"]["kernel"] == "dense"
    assert banded_row["descriptor"]["kernel"] == "banded"
    assert dense_row["costed"] and banded_row["costed"]
    ratio = dense_row["flops"] / banded_row["flops"]
    assert ratio >= 10.0, (
        f"banded FLOP reduction only {ratio:.1f}x "
        f"({dense_row['flops']:.3e} -> {banded_row['flops']:.3e})"
    )


# ---------------------------------------------------------------------------
# banded-enabled serving smoke leg (tier-1's CI coverage of the knob)


def test_banded_serving_leg_parity_and_costed_ledger_entry(tmp_path):
    """Render the same source through a dense app and a banded app:
    outputs agree at <= 1 u8 level and the banded app's /debug/plans
    carries a launched, costed entry tagged with the banded variant."""
    rng = np.random.default_rng(17)
    img = rng.integers(0, 255, (144, 208, 3), dtype=np.uint8)
    src = tmp_path / "source.png"
    src.write_bytes(encode(img, "png"))

    async def scenario(client):
        from flyimg_tpu.runtime.costledger import get_ledger

        resp = await client.get(f"/upload/w_72,h_52,c_1,o_png/{src}")
        assert resp.status == 200
        body = await resp.read()
        # keep only the newest ledger entries (this render's) so the
        # device-seconds-ranked /debug/plans window can't truncate them
        # away in a shared test process (see the 4k test above)
        get_ledger().configure(max_entries=8)
        try:
            plans = await (await client.get("/debug/plans")).json()
        finally:
            get_ledger().configure(max_entries=256)
        return body, plans

    dense_body, _ = _serve(tmp_path, scenario, resample_kernel="dense")
    banded_body, plans = _serve(
        tmp_path, scenario, resample_kernel="banded"
    )
    dense_px = np.asarray(Image.open(io.BytesIO(dense_body)))
    banded_px = np.asarray(Image.open(io.BytesIO(banded_body)))
    diff = np.abs(dense_px.astype(np.int16) - banded_px.astype(np.int16))
    assert diff.max() <= 1

    banded_rows = [
        row for row in plans["plans"]
        if (row["descriptor"] or {}).get("kernel") == "banded"
        and row["launches"] >= 1
    ]
    assert banded_rows, plans["plans"]
    assert any(row["costed"] for row in banded_rows)


# ---------------------------------------------------------------------------
# satellite: unknown f_ filter names alias LOUDLY, not silently


def test_unknown_filter_alias_emits_counter_and_span_event():
    from flyimg_tpu.runtime import tracing
    from flyimg_tpu.runtime.metrics import MetricsRegistry
    from flyimg_tpu.runtime.tracing import Trace

    metrics = MetricsRegistry()
    trace = Trace()
    with tracing.activate(trace):
        plan = build_plan(
            OptionsBag("w_100,f_sinc"), 400, 300, metrics=metrics,
        )
    assert plan.filter_method == "lanczos3"  # the documented alias
    rendered = metrics.render_prometheus()
    assert 'flyimg_filter_aliased_total{filter="sinc"} 1' in rendered
    trace.finish()

    def events(node):
        yield from node.get("events", [])
        for child in node.get("children", []):
            yield from events(child)

    aliased = [
        e for s in trace.as_dict()["spans"] for e in events(s)
        if e["name"] == "filter.aliased"
    ]
    assert aliased and aliased[0]["filter"] == "sinc"
    assert aliased[0]["method"] == "lanczos3"


def test_known_filters_do_not_count_as_aliased():
    from flyimg_tpu.runtime.metrics import MetricsRegistry

    metrics = MetricsRegistry()
    for name in FILTER_METHODS:
        build_plan(
            OptionsBag(f"w_100,f_{name}"), 400, 300, metrics=metrics,
        )
    assert "flyimg_filter_aliased_total" not in metrics.render_prometheus()


def test_alias_counter_label_cardinality_is_bounded():
    """The filter label is client-controlled: past the per-process
    series cap, novel names collapse into one `_other` series so a
    crawler spraying random f_ values can't grow /metrics unboundedly."""
    import flyimg_tpu.spec.plan as plan_mod
    from flyimg_tpu.runtime.metrics import MetricsRegistry

    metrics = MetricsRegistry()
    saved = set(plan_mod._aliased_filter_names)
    plan_mod._aliased_filter_names.clear()
    try:
        for i in range(plan_mod._ALIASED_FILTER_SERIES_MAX + 20):
            build_plan(
                OptionsBag(f"w_100,f_novel{i}"), 400, 300, metrics=metrics,
            )
        rendered = metrics.render_prometheus()
        series = [
            line for line in rendered.splitlines()
            if line.startswith("flyimg_filter_aliased_total{")
        ]
        assert len(series) == plan_mod._ALIASED_FILTER_SERIES_MAX + 1
        assert 'filter="_other"} 20' in rendered
    finally:
        plan_mod._aliased_filter_names.clear()
        plan_mod._aliased_filter_names.update(saved)


# ---------------------------------------------------------------------------
# the benchmark and the serving kernel share ONE K computation


def test_experiment_imports_shared_k_computation():
    """benchmarks/resample_experiment.py must derive K from
    ops/resample.py's band_taps/bucket_taps (and run the serving
    resample_image_banded), not a hard-coded K=16 copy that silently
    drops taps past scale 1.71."""
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "resample_experiment.py",
    )
    with open(path) as fh:
        source = fh.read()
    assert "bucket_taps(band_taps(" in source
    assert "resample_image_banded" in source
    assert "K = 16" not in source
