"""Parallelism layer on the virtual 8-device CPU mesh (conftest.py forces
8 CPU devices — the standard fake-mesh trick, SURVEY.md section 4).

Covers: mesh construction + shardings, the spatially-tiled resample with
ppermute halo exchange (the image-domain analog of context parallelism,
SURVEY.md section 5) against the single-device resample oracle, and the
data-parallel serving fan-out."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flyimg_tpu.ops.resample import resample_image
from flyimg_tpu.parallel.mesh import batch_sharding, make_mesh, replicated
from flyimg_tpu.parallel.tiling import tiled_transform

RNG = np.random.default_rng(99)


def single_resize(image, out_h, out_w, method="lanczos3"):
    """Whole-image resample via the single-device op (full spans)."""
    in_h, in_w = int(image.shape[0]), int(image.shape[1])
    return resample_image(
        image,
        (out_h, out_w),
        jnp.asarray([0.0, float(in_h)], jnp.float32),
        jnp.asarray([0.0, float(in_w)], jnp.float32),
        jnp.asarray([out_h, out_w], jnp.float32),
        jnp.asarray([in_h, in_w], jnp.float32),
        method,
    )


def test_make_mesh_default_spans_all_devices():
    mesh = make_mesh()
    assert mesh.devices.size == 8
    assert mesh.axis_names == ("data",)


def test_make_mesh_2d():
    mesh = make_mesh((4, 2), ("data", "model"))
    assert mesh.shape == {"data": 4, "model": 2}


def test_make_mesh_too_many_devices_raises():
    with pytest.raises(ValueError):
        make_mesh((16,))


def test_batch_sharding_places_shards():
    mesh = make_mesh()
    batch = jnp.zeros((16, 8, 8, 3))
    sharded = jax.device_put(batch, batch_sharding(mesh))
    # each device holds 16/8 = 2 images
    shard_shapes = {s.data.shape for s in sharded.addressable_shards}
    assert shard_shapes == {(2, 8, 8, 3)}
    repl = jax.device_put(jnp.zeros((4,)), replicated(mesh))
    assert {s.data.shape for s in repl.addressable_shards} == {(4,)}


@pytest.mark.parametrize("out_h,out_w", [(128, 96), (64, 64)])
def test_tiled_resample_matches_single_device(out_h, out_w):
    """H-sharded resample with halo exchange == the one-device program."""
    mesh = make_mesh(axis_names=("sp",))
    img = RNG.integers(0, 256, size=(512, 384, 3), dtype=np.uint8)
    got = np.asarray(tiled_transform(jnp.asarray(img), (out_h, out_w), mesh))
    want = np.asarray(
        single_resize(
            jnp.asarray(img, jnp.float32), out_h, out_w, method="lanczos3"
        )
    )
    np.testing.assert_allclose(got, want, atol=0.75)


def test_tiled_resample_pads_indivisible_heights():
    """2161-row-style inputs (and indivisible out_h) must ride the tiled
    path via pad-to-divisible, matching the one-device program."""
    mesh = make_mesh(axis_names=("sp",))
    img = RNG.integers(0, 256, size=(515, 96, 3), dtype=np.uint8)
    got = np.asarray(tiled_transform(jnp.asarray(img), (123, 64), mesh))
    assert got.shape == (123, 64, 3)
    want = np.asarray(
        single_resize(
            jnp.asarray(img, jnp.float32), 123, 64, method="lanczos3"
        )
    )
    np.testing.assert_allclose(got, want, atol=0.75)


def test_data_parallel_serving_fanout():
    """The serving program jitted over the mesh: batch sharded on 'data',
    results identical to local execution — pure SPMD, no collectives."""
    mesh = make_mesh()
    batch = jnp.asarray(
        RNG.integers(0, 256, size=(8, 64, 64, 3), dtype=np.uint8), jnp.float32
    )

    def program(x):
        return single_resize(x, 32, 32, method="triangle")

    sharding = batch_sharding(mesh)
    jitted = jax.jit(
        jax.vmap(program),
        in_shardings=sharding,
        out_shardings=sharding,
    )
    got = np.asarray(jitted(jax.device_put(batch, sharding)))
    want = np.asarray(jax.vmap(program)(batch))
    np.testing.assert_allclose(got, want, atol=1e-3)


def test_tiled_resample_infeasible_halo_raises():
    """Extreme downscales whose halo would exceed a tile must refuse (the
    handler falls back to the batcher) instead of clamping and corrupting."""
    mesh = make_mesh(axis_names=("sp",))
    img = np.zeros((4001, 64, 3), dtype=np.uint8)
    with pytest.raises(ValueError, match="infeasible"):
        tiled_transform(jnp.asarray(img), (33, 64), mesh)


def test_force_cpu_platform_pins_env_config_and_device_count():
    """The one recipe conftest and dryrun_multichip share: after it the env
    and the jax config both say cpu and the virtual device count holds —
    also when a backend had already initialised (conftest's 8 devices)."""
    from flyimg_tpu.parallel.mesh import cpu_pinned, force_cpu_platform

    assert len(jax.devices()) == 8
    try:
        force_cpu_platform(4)
        assert cpu_pinned()
        assert jax.config.jax_platforms == "cpu"
        assert len(jax.devices()) == 4
    finally:
        force_cpu_platform(8)
    assert len(jax.devices()) == 8


# ---------------------------------------------------------------------------
# ring rotate: tile circulation (the ring-attention-style schedule)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("degrees", [-45.0, 30.0, 90.0, 180.0, 12.5])
def test_ring_rotate_matches_single_device(degrees):
    """n-step ppermute ring rotate == the one-device bilinear rotate: each
    clamped tap row is owned by exactly one visiting tile, so the ring
    accumulation reconstructs the identical sum."""
    from flyimg_tpu.ops.rotate import rotate_image
    from flyimg_tpu.parallel.tiling import tiled_rotate

    mesh = make_mesh(axis_names=("sp",))
    img = RNG.integers(0, 256, size=(256, 192, 3), dtype=np.uint8)
    got = np.asarray(tiled_rotate(jnp.asarray(img), degrees, mesh))
    want = np.asarray(rotate_image(jnp.asarray(img, jnp.float32), degrees))
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=0.51)


def test_ring_rotate_indivisible_height_and_background():
    from flyimg_tpu.ops.rotate import rotate_image
    from flyimg_tpu.parallel.tiling import tiled_rotate

    mesh = make_mesh(axis_names=("sp",))
    img = RNG.integers(0, 256, size=(203, 97, 3), dtype=np.uint8)
    got = np.asarray(
        tiled_rotate(jnp.asarray(img), -30.0, mesh, background=(10, 200, 30))
    )
    want = np.asarray(
        rotate_image(jnp.asarray(img, jnp.float32), -30.0,
                     background=(10, 200, 30))
    )
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=0.51)
    # corners really are the requested background
    assert tuple(np.round(got[0, 0]).astype(int)) == (10, 200, 30)


def test_ring_rotate_zero_degrees_is_identity():
    from flyimg_tpu.parallel.tiling import tiled_rotate

    mesh = make_mesh(axis_names=("sp",))
    img = RNG.integers(0, 256, size=(64, 48, 3), dtype=np.uint8)
    out = tiled_rotate(jnp.asarray(img), 0.0, mesh)
    np.testing.assert_array_equal(np.asarray(out), img)


def test_ring_rotate_tall_image_memory_shape():
    """The firehose case: a tall 4k-ish image rides the ring with per-device
    tiles, and the output matches the single-device result."""
    from flyimg_tpu.ops.rotate import rotate_image
    from flyimg_tpu.parallel.tiling import tiled_rotate

    mesh = make_mesh(axis_names=("sp",))
    img = RNG.integers(0, 256, size=(1024, 64, 3), dtype=np.uint8)
    got = np.asarray(tiled_rotate(jnp.asarray(img), 45.0, mesh))
    want = np.asarray(rotate_image(jnp.asarray(img, jnp.float32), 45.0))
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=0.51)


# ---------------------------------------------------------------------------
# tiled filters: bounded-neighborhood halo exchange
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("op,kwargs", [
    ("blur", {}),
    ("sharpen", {}),
    ("unsharp", {"gain": 1.5, "threshold": 0.02}),
])
def test_tiled_filter_matches_single_device(op, kwargs):
    from flyimg_tpu.ops import filters
    from flyimg_tpu.parallel.tiling import tiled_filter

    mesh = make_mesh(axis_names=("sp",))
    img = RNG.integers(0, 256, size=(256, 96, 3), dtype=np.uint8)
    x = jnp.asarray(img, jnp.float32)
    got = np.asarray(tiled_filter(x, mesh, op, 0.0, 2.0, **kwargs))
    if op == "blur":
        want = filters.gaussian_blur(x, 0.0, 2.0)
    elif op == "sharpen":
        want = filters.sharpen(x, 0.0, 2.0)
    else:
        want = filters.unsharp_mask(x, 0.0, 2.0, **kwargs)
    np.testing.assert_allclose(got, np.asarray(want), atol=1e-3)


def test_tiled_filter_indivisible_height():
    from flyimg_tpu.ops import filters
    from flyimg_tpu.parallel.tiling import tiled_filter

    mesh = make_mesh(axis_names=("sp",))
    img = RNG.integers(0, 256, size=(201, 64, 3), dtype=np.uint8)
    x = jnp.asarray(img, jnp.float32)
    got = np.asarray(tiled_filter(x, mesh, "blur", 0.0, 1.5))
    want = np.asarray(filters.gaussian_blur(x, 0.0, 1.5))
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=1e-3)


def test_tiled_filter_infeasible_kernel_raises():
    from flyimg_tpu.parallel.tiling import tiled_filter

    mesh = make_mesh(axis_names=("sp",))
    img = jnp.zeros((16, 16, 3), jnp.float32)  # tile_h = 2, sigma 8 -> half 24
    with pytest.raises(ValueError, match="infeasible"):
        tiled_filter(img, mesh, "blur", 0.0, 8.0)


def test_require_accelerator_accepts_explicit_cpu_pin(monkeypatch):
    """JAX_PLATFORMS=cpu is the one way to run without a chip on purpose:
    the backend is described, nothing is probed out of process."""
    import subprocess

    from flyimg_tpu.parallel import mesh as mesh_mod

    def boom(*a, **k):
        raise AssertionError("backend selection must stay in process")

    monkeypatch.setattr(subprocess, "Popen", boom)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert mesh_mod.require_accelerator() == {
        "platform": "cpu", "device_kind": "cpu", "count": 8,
    }


@pytest.mark.parametrize("selection", [None, "", "tpu,cpu"])
def test_require_accelerator_refuses_unpinned_cpu_backend(
    monkeypatch, selection
):
    """JAX lands on the CPU by itself when accelerator init fails — with
    no selection at all and under a ``tpu,cpu`` one (what the chip machine
    exports). Without the explicit cpu-only pin that is an error, never a
    degraded mode."""
    from flyimg_tpu.parallel import mesh as mesh_mod

    if selection is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", selection)
    assert not mesh_mod.cpu_pinned()
    with pytest.raises(RuntimeError, match="no accelerator"):
        mesh_mod.require_accelerator()


def test_boot_refuses_unpinned_cpu_backend_and_cpu_pin_boots(
    monkeypatch, tmp_path
):
    """make_app is chip-or-fail: a CPU backend nobody pinned fails the
    boot; the same process under the explicit pin boots and says what it
    runs on."""
    from flyimg_tpu.appconfig import AppParameters
    from flyimg_tpu.service.app import make_app

    params = AppParameters({
        "tmp_dir": str(tmp_path / "tmp"),
        "upload_dir": str(tmp_path / "uploads"),
    })
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(RuntimeError, match="no accelerator"):
        make_app(params)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert make_app(params) is not None


def test_serve_exits_nonzero_without_chip_or_pin(tmp_path):
    """The serve CLI in a fresh process with no chip and no pin: non-zero
    exit before the port binds, the reason on stderr."""
    import os
    import subprocess
    import sys

    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-m", "flyimg_tpu.service.app", "serve",
         "--port", "0"],
        cwd=repo, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert "no accelerator" in proc.stderr
