"""Multi-host initialization.

The distributed-communication backend equivalent (SURVEY.md section 5): the
reference has no inter-node comms at all (share-nothing containers); at TPU
pod scale the same service becomes one SPMD program per host over ICI/DCN
with XLA-provided collectives. This module owns process bootstrap —
``jax.distributed.initialize`` wires the DCN coordination plane; after it,
``jax.devices()`` is the global pod view and every Mesh built on it spans
hosts transparently.
"""

from __future__ import annotations

import os
from typing import Optional

import jax


def initialize_multihost(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> bool:
    """Initialize the JAX distributed runtime when multi-host serving is
    configured; single-host (returns False) is the default.

    Multi-host is explicit: arguments fall back to the COORDINATOR_ADDRESS
    / NUM_PROCESSES / PROCESS_ID env vars, and with none of them set
    nothing is initialized. There is no autodetection — a single TPU v5e
    host sets the pod markers (``TPU_WORKER_ID=0``,
    ``TPU_WORKER_HOSTNAMES=localhost``) too, and a bare
    ``jax.distributed.initialize()`` there spends seconds asking a
    metadata server for peers before failing (measured 3.3 s on a sealed
    one-chip machine, PR 21). A misconfigured explicit setup raises.
    """
    coordinator_address = coordinator_address or os.environ.get(
        "COORDINATOR_ADDRESS"
    )
    env_np = os.environ.get("NUM_PROCESSES")
    env_pid = os.environ.get("PROCESS_ID")
    num_processes = num_processes if num_processes is not None else (
        int(env_np) if env_np else None
    )
    process_id = process_id if process_id is not None else (
        int(env_pid) if env_pid else None
    )
    if coordinator_address is None and num_processes is None:
        return False
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    return True


def local_batch_slice(global_batch: int) -> slice:
    """The slice of a global request batch this host owns (per-host
    BatchController shards the request stream; SPMD only below it)."""
    n = jax.process_count()
    idx = jax.process_index()
    per = global_batch // n
    return slice(idx * per, (idx + 1) * per)
