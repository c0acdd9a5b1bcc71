"""Device mesh construction for keyspace-parallel cracking.

The framework's only sharded axis is the keyspace (candidate-index)
dimension, so every mesh is 1-D with a single ``candidates`` axis
(``PartitionSpec('candidates')`` is the whole sharding story -- see
parallel/sharded.py, the one runtime every sharded step goes through).
On a pod slice the axis rides ICI; across hosts, `jax.distributed` +
the same mesh spans DCN with no code changes (XLA places the
collectives).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import Mesh

SHARD_AXIS = "candidates"


def make_mesh(n_devices: Optional[int] = None,
              devices: Optional[Sequence] = None) -> Mesh:
    """Build the 1-D keyspace mesh over `n_devices` (default: all)."""
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        if n_devices > len(devices):
            raise ValueError(
                f"requested {n_devices} devices, only {len(devices)} present")
        devices = devices[:n_devices]
    import numpy as np
    return Mesh(np.asarray(devices), (SHARD_AXIS,))


def init_multihost(coordinator: Optional[str] = None,
                   num_processes: Optional[int] = None,
                   process_id: Optional[int] = None) -> bool:
    """Form one device mesh ACROSS hosts (a pod slice spanning DCN).

    Wraps `jax.distributed.initialize`: after it, `jax.devices()` on
    every participating process reports the global device set, so the
    same `make_mesh()` + shard_map code shards a job over the whole
    slice with XLA placing the collectives (ICI within a host's chips,
    DCN across hosts).  This is the SINGLE-MESH multi-host mode; the
    WorkUnit RPC control plane (runtime/rpc.py) remains the loosely-
    coupled alternative where hosts lease independent keyspace ranges.

    On TPU pods the three arguments are auto-detected from the
    environment, so `init_multihost()` with no arguments is the normal
    call; on CPU/GPU fleets pass them explicitly.  Returns True if
    initialization ran, False if it was skipped because this process is
    already initialized (idempotent -- safe to call from the CLI on
    every invocation).
    """
    if jax.distributed.is_initialized():
        return False      # already initialized: idempotent no-op
    kwargs = {}
    if coordinator is not None:
        kwargs["coordinator_address"] = coordinator
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    jax.distributed.initialize(**kwargs)
    return True
