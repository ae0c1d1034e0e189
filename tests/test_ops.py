"""Device-op conformance: exact output dims (the geometry oracle, end-to-end
through the XLA program) plus image-quality parity checks against PIL's
Lanczos resampler (an independent implementation of the same filter family
ImageMagick uses — per SURVEY.md section 4 we pin PSNR, not bytes)."""

import numpy as np
import pytest
from PIL import Image

from flyimg_tpu.ops.compose import run_plan
from flyimg_tpu.spec.options import OptionsBag
from flyimg_tpu.spec.plan import build_plan

from test_geometry import ALL_CASES


def make_test_image(w, h, seed=0):
    """Deterministic colorful gradient + texture image."""
    rng = np.random.default_rng(seed)
    yy, xx = np.mgrid[0:h, 0:w]
    r = (xx * 255 // max(w - 1, 1)).astype(np.uint8)
    g = (yy * 255 // max(h - 1, 1)).astype(np.uint8)
    b = ((xx + yy) % 256).astype(np.uint8)
    img = np.stack([r, g, b], axis=-1)
    noise = rng.integers(0, 32, size=img.shape, dtype=np.uint8)
    return np.clip(img.astype(np.int16) + noise, 0, 255).astype(np.uint8)


def psnr(a, b):
    mse = np.mean((a.astype(np.float64) - b.astype(np.float64)) ** 2)
    if mse == 0:
        return np.inf
    return 10 * np.log10(255.0**2 / mse)


@pytest.mark.parametrize("options_str,expected,src", ALL_CASES[::3])
def test_device_dims_match_oracle(options_str, expected, src):
    """Every third oracle case, executed through the real device program."""
    img = make_test_image(*src)
    plan = build_plan(OptionsBag(options_str), src[0], src[1])
    out = run_plan(img, plan)
    got = f"{out.shape[1]}x{out.shape[0]}"
    assert got == expected
    assert out.dtype == np.uint8


def test_resize_quality_vs_pil():
    img = make_test_image(900, 600, seed=1)
    plan = build_plan(OptionsBag("w_300"), 900, 600)
    ours = run_plan(img, plan)
    ref = np.asarray(
        Image.fromarray(img).resize((300, 200), Image.LANCZOS)
    )
    assert ours.shape == ref.shape
    assert psnr(ours, ref) > 35, psnr(ours, ref)


def test_upscale_quality_vs_pil():
    img = make_test_image(100, 80, seed=2)
    plan = build_plan(OptionsBag("w_300,pns_0"), 100, 80)
    ours = run_plan(img, plan)
    ref = np.asarray(Image.fromarray(img).resize((300, 240), Image.LANCZOS))
    assert ours.shape == ref.shape
    assert psnr(ours, ref) > 30, psnr(ours, ref)


def test_crop_fill_center_content():
    """Center crop of a landscape: output must come from the horizontal
    middle of the source (the left/right thirds are cut)."""
    w, h = 900, 600
    img = np.zeros((h, w, 3), dtype=np.uint8)
    img[:, : w // 3] = (255, 0, 0)
    img[:, w // 3 : 2 * w // 3] = (0, 255, 0)
    img[:, 2 * w // 3 :] = (0, 0, 255)
    plan = build_plan(OptionsBag("w_300,h_300,c_1"), w, h)
    out = run_plan(img, plan)
    assert out.shape == (300, 300, 3)
    # center column of output should be green (middle band of source)
    center = out[150, 150]
    assert center[1] > 200 and center[0] < 50 and center[2] < 50


def test_crop_gravity_west():
    w, h = 900, 600
    img = np.zeros((h, w, 3), dtype=np.uint8)
    img[:, : w // 2] = (255, 0, 0)
    plan = build_plan(OptionsBag("w_300,h_300,c_1,g_West"), w, h)
    out = run_plan(img, plan)
    # West gravity keeps the left (red) side
    assert out[150, 10, 0] > 200


def test_rotate_90_exact():
    img = make_test_image(300, 200, seed=3)
    plan = build_plan(OptionsBag("r_90"), 300, 200)
    out = run_plan(img, plan)
    assert out.shape == (300, 200, 3)
    # clockwise 90: first row of output = first column of source, reversed
    expected = np.flip(np.swapaxes(img, 0, 1), axis=1)
    np.testing.assert_array_equal(out, expected)


def test_rotate_45_fills_background():
    img = np.full((200, 200, 3), 128, dtype=np.uint8)
    plan = build_plan(OptionsBag("r_45,bg_red"), 200, 200)
    out = run_plan(img, plan)
    assert out.shape[0] == out.shape[1] == 283
    # corners are background red
    assert out[0, 0, 0] > 200 and out[0, 0, 1] < 50
    # center untouched
    assert abs(int(out[141, 141, 0]) - 128) <= 2


def test_grayscale():
    img = make_test_image(100, 100, seed=4)
    plan = build_plan(OptionsBag("clsp_gray"), 100, 100)
    out = run_plan(img, plan)
    np.testing.assert_array_equal(out[..., 0], out[..., 1])
    np.testing.assert_array_equal(out[..., 1], out[..., 2])


def test_monochrome_is_bilevel():
    img = make_test_image(64, 64, seed=5)
    plan = build_plan(OptionsBag("mnchr_1"), 64, 64)
    out = run_plan(img, plan)
    assert set(np.unique(out)) <= {0, 255}


def test_blur_reduces_variance():
    img = make_test_image(128, 128, seed=6)
    plan = build_plan(OptionsBag("blr_0x3"), 128, 128)
    out = run_plan(img, plan)
    assert out.shape == img.shape
    assert np.var(out.astype(float)) < np.var(img.astype(float))


def test_unsharp_increases_edge_contrast():
    img = make_test_image(128, 128, seed=7)
    plan = build_plan(OptionsBag("unsh_0x2"), 128, 128)
    out = run_plan(img, plan)
    grad_in = np.abs(np.diff(img.astype(float), axis=1)).mean()
    grad_out = np.abs(np.diff(out.astype(float), axis=1)).mean()
    assert grad_out > grad_in


def test_extract_prepass():
    img = make_test_image(640, 360, seed=8)
    plan = build_plan(OptionsBag("e_1,p1x_100,p1y_50,p2x_300,p2y_150"), 640, 360)
    out = run_plan(img, plan)
    assert out.shape == (100, 200, 3)
    # pure extract (no resize) == numpy slice, up to resample identity
    np.testing.assert_allclose(
        out.astype(int), img[50:150, 100:300].astype(int), atol=1
    )


def test_extent_pad_with_background():
    img = np.full((100, 100, 3), 40, dtype=np.uint8)
    plan = build_plan(OptionsBag("ett_200x120,bg_blue"), 100, 100)
    out = run_plan(img, plan)
    assert out.shape == (120, 200, 3)
    # corners padded blue, center original
    assert out[0, 0, 2] > 200 and out[0, 0, 0] < 50
    assert out[60, 100, 0] == 40


def test_pixelate_regions():
    from flyimg_tpu.ops.pixelate import pixelate_image

    img = make_test_image(100, 100, seed=9)
    out = pixelate_image(img, [(10, 10, 40, 40), (0, 0, 0, 0)])
    assert out.dtype == np.uint8 and out.shape == img.shape
    # outside box unchanged
    np.testing.assert_array_equal(out[60:, 60:], img[60:, 60:])
    # inside box is blockwise-constant (10x10 image-aligned blocks): the
    # block's mean, rounded half to even
    block = out[10:20, 10:20]
    assert (block == block[0, 0]).all()
    np.testing.assert_array_equal(
        block[0, 0], np.rint(img[10:20, 10:20].astype(np.float64).mean(axis=(0, 1)))
    )
    # no box: the image itself, no device trip
    assert pixelate_image(img, []) is img


def test_program_cache_reuse_across_sizes():
    """Same plan signature + same bucket -> one compiled program."""
    from flyimg_tpu.ops.compose import build_program

    build_program.cache_clear()
    # all three land in the same 128-px bucket (640 x 512)
    for w, h in [(600, 400), (630, 420), (520, 390)]:
        img = make_test_image(w, h)
        plan = build_plan(OptionsBag("w_300,h_200,c_1"), w, h)
        out = run_plan(img, plan)
        assert out.shape == (200, 300, 3)
    info = build_program.cache_info()
    assert info.misses == 1, info
    assert info.hits == 2, info


def test_gaussian_matrix_rows_match_numpy_oracle():
    # independent numpy re-derivation of the IM Gaussian row weights
    # (sigma 1/2, support 1.5, antialias stretch, renormalized) for a
    # plain full-span downscale
    import jax.numpy as jnp

    from flyimg_tpu.ops.resample import resample_matrix

    in_size, out_size = 40, 16
    m = np.asarray(resample_matrix(
        in_size, out_size, jnp.float32(0.0), jnp.float32(in_size),
        jnp.float32(out_size), jnp.float32(in_size), "gaussian",
    ))
    s = in_size / out_size  # downscale: kernel stretched by the scale
    for i in range(out_size):
        x = 0.0 + (i + 0.5) * (in_size / out_size) - 0.5
        d = (np.arange(in_size) - x) / s
        w = np.where(np.abs(d) < 1.5, np.exp(-2.0 * d * d), 0.0)
        w = w / w.sum()
        np.testing.assert_allclose(m[i], w, atol=1e-5)
    # every row is a proper partition of unity
    np.testing.assert_allclose(m.sum(axis=1), 1.0, atol=1e-5)


def test_flt_gaussian_resize_differs_from_triangle_and_blurs():
    img = make_test_image(600, 400, seed=5)
    gauss = run_plan(img, build_plan(OptionsBag("w_200,f_gaussian"), 600, 400))
    tri = run_plan(img, build_plan(OptionsBag("w_200,f_triangle"), 600, 400))
    lanc = run_plan(img, build_plan(OptionsBag("w_200"), 600, 400))
    assert gauss.shape == tri.shape == lanc.shape == (133, 200, 3)
    # true gaussian taps: no longer aliased to triangle
    assert np.abs(gauss.astype(int) - tri.astype(int)).max() > 0
    # gaussian is the softest of the three: less high-frequency energy
    # than lanczos on a noisy source
    def hf_energy(a):
        d = np.diff(a.astype(np.float64), axis=1)
        return float(np.mean(d * d))
    assert hf_energy(gauss) < hf_energy(lanc)


def test_fold2d_bf16_form_matches_einsum_within_one_level(monkeypatch):
    # the resample_experiment candidate wired into serving behind
    # FLYIMG_RESAMPLE_FORM: same weights, different contraction layout +
    # explicit bf16 operands with f32 accumulation — must round-trip to
    # within one uint8 level of the shipped einsum form
    import jax.numpy as jnp

    from flyimg_tpu.ops import resample as rs

    img = make_test_image(160, 200, seed=9).astype(np.float32)
    args = (
        jnp.asarray(img), (75, 62),
        jnp.array([10.0, 140.0], jnp.float32),
        jnp.array([0.0, 200.0], jnp.float32),
        jnp.array([75.0, 62.0], jnp.float32),
        jnp.array([160.0, 200.0], jnp.float32),
    )
    base = np.asarray(rs.resample_image(*args))
    monkeypatch.setattr(rs, "RESAMPLE_FORM", "fold2d_bf16")
    alt = np.asarray(rs.resample_image(*args))
    a = np.clip(base + 0.5, 0, 255).astype(np.uint8)
    b = np.clip(alt + 0.5, 0, 255).astype(np.uint8)
    # on CPU the einsum base runs FULL f32 (DEFAULT precision only means
    # bf16 on TPU), so this compares f32 vs explicit-bf16: two rounding
    # quanta is the honest bound. On TPU both forms multiply in bf16 and
    # the experiment gates the A/B at one level against the on-chip base.
    assert np.abs(a.astype(int) - b.astype(int)).max() <= 2
