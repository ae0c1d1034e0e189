"""Device mesh construction + standard shardings."""

from __future__ import annotations

import os
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _cpu_only(selection: Optional[str]) -> bool:
    """A ``JAX_PLATFORMS`` value that names the CPU and nothing else."""
    names = {p.strip().lower() for p in (selection or "").split(",")}
    return names - {""} == {"cpu"}


def cpu_pinned() -> bool:
    """True when ``JAX_PLATFORMS`` pins the CPU — the one explicit way to
    ask this program to run without an accelerator."""
    return _cpu_only(os.environ.get("JAX_PLATFORMS"))


def force_cpu_platform(n_devices: int = 1) -> None:
    """Pin this process to the CPU platform with ``n_devices`` virtual
    devices (the test conftest, the driver's ``dryrun_multichip`` contract,
    the device supervisor's failover). Drops any backend that already
    initialized; the env var is set too so child processes and the boot
    check (``require_accelerator``) see the pin."""
    from jax.extend.backend import clear_backends

    os.environ["JAX_PLATFORMS"] = "cpu"
    clear_backends()
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n_devices)


def require_accelerator() -> dict:
    """Initialise the backend in process and describe it as
    ``{"platform", "device_kind", "count"}``. Raises ``RuntimeError`` when
    JAX landed on the CPU without an explicit ``JAX_PLATFORMS=cpu`` pin:
    JAX falls back to the CPU on its own when accelerator init fails (also
    under a ``tpu,cpu`` selection), and a server or benchmark that carried
    on would report CPU behaviour under the chip's name."""
    devices = jax.devices()
    info = {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "count": len(devices),
    }
    if info["platform"] == "cpu" and not cpu_pinned():
        raise RuntimeError(
            "JAX initialised the CPU backend but JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '')!r} does not pin it: no "
            "accelerator was found. Set JAX_PLATFORMS=cpu to run on the "
            "CPU deliberately."
        )
    return info


# a backend that cannot finish an 8x8 matmul on something other than the
# CPU is down, whatever jax.devices() or client init says
_PROBE_SNIPPET = (
    "import jax, jax.numpy as jnp;"
    "assert jax.default_backend() != 'cpu';"
    "assert float((jnp.ones((8,8)) @ jnp.ones((8,8))).sum()) == 512.0"
)


def probe_selected_backend(timeout_s: float, env_overrides=None) -> bool:
    """Run the compute probe in a disposable child under this process's
    platform selection, or under ``env_overrides`` (``None`` values unset
    the variable in the child). True iff the child finishes the matmul on
    a non-CPU backend within the deadline.

    The child needs the device to itself: while THIS process holds the
    chip the probe cannot pass, so it only answers after a failover
    dropped the accelerator backend (runtime/devicesupervisor.py).

    Popen + poll + abandon on expiry: a child stuck in device init can sit
    in uninterruptible I/O where a post-kill wait() would hang the caller
    this probe is guarding; the killable case is reaped by a daemon
    thread."""
    import subprocess
    import sys
    import threading
    import time

    child_env = None
    if env_overrides:
        child_env = dict(os.environ)
        for key, value in env_overrides.items():
            if value is None:
                child_env.pop(key, None)
            else:
                child_env[key] = value
    proc = subprocess.Popen(
        [sys.executable, "-c", _PROBE_SNIPPET],
        stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
        env=child_env,
    )
    deadline = time.monotonic() + timeout_s
    rc = proc.poll()
    while rc is None and time.monotonic() < deadline:
        time.sleep(0.25)
        rc = proc.poll()
    if rc is None:
        proc.kill()
        threading.Thread(target=proc.wait, daemon=True).start()
    return rc == 0


def probe_device_backend(
    timeout_s: float,
    selection=None,
) -> Tuple[bool, str]:
    """The device supervisor's re-probe (``runtime/devicesupervisor.py``):
    is the accelerator selection usable again?

    Returns ``(ok, detail)``; ``detail`` is one of:

    - ``"cpu"``        — a cpu-only ``JAX_PLATFORMS`` pin: nothing to
      probe, the selection is trivially healthy
    - ``"up"``         — the compute probe passed on a non-CPU backend
      within the deadline
    - ``"down"``       — it did not (no accelerator, init failed, hung)
    - ``"injected"``   — a ``device.backend`` fault plan overrode the
      verdict (flyimg_tpu/testing/faults.py)
    - ``"error:<T>"``  — the probe machinery itself raised ``<T>``

    ``selection``: probe under THIS saved ``{JAX_PLATFORMS, XLA_FLAGS}``
    mapping instead of the process env — after ``force_cpu_platform``
    the env says cpu, and trusting it would declare the dead backend
    healthy on the first probe and flap the replica between CPU and
    the dead device forever. ``None`` values mean "unset in the child".

    NEVER raises: a probe exception (including an injected one) is a
    recorded outcome — callers act on the verdict, they do not crash.
    """
    from flyimg_tpu.testing import faults

    try:
        injected = faults.fire("device.backend")
        if injected is not faults.PASS and injected is not None:
            return bool(injected), "injected"
        if selection is not None and "JAX_PLATFORMS" in selection:
            req = selection.get("JAX_PLATFORMS")
        else:
            req = os.environ.get("JAX_PLATFORMS")
        if _cpu_only(req):
            return True, "cpu"
        ok = probe_selected_backend(timeout_s, env_overrides=selection)
        return bool(ok), "up" if ok else "down"
    except Exception as exc:  # noqa: BLE001 - the contract IS catch-all
        return False, f"error:{type(exc).__name__}"


def make_mesh(
    axis_sizes: Optional[Tuple[int, ...]] = None,
    axis_names: Sequence[str] = ("data",),
    devices=None,
) -> Mesh:
    """Build a Mesh over the available devices. Default: all devices on one
    'data' axis (serving = SPMD fan-out over the batch)."""
    devices = list(devices if devices is not None else jax.devices())
    if axis_sizes is None:
        axis_sizes = (len(devices),)
    n = int(np.prod(axis_sizes))
    if n > len(devices):
        raise ValueError(
            f"mesh wants {n} devices, only {len(devices)} available"
        )
    grid = np.asarray(devices[:n]).reshape(axis_sizes)
    return Mesh(grid, axis_names)


def default_mesh() -> Mesh:
    return make_mesh()


def batch_sharding(mesh: Mesh, axis: str = "data") -> NamedSharding:
    """Shard the leading (batch) axis over ``axis``, replicate the rest."""
    return NamedSharding(mesh, P(axis))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
