"""Multi-host mesh bootstrap: jax.distributed over DCN.

SURVEY.md §5.8 — the reference's distributed comm backend is gRPC
between daemons.  Here the traffic classes map to:

- intra-pod: ICI collectives under shard_map (sharded.py / meshglobal.py),
- multi-pod / multi-region: daemon-level peering over the reference
  wire protocol (peer_client.py, global_manager.py, multiregion.py),
- multi-HOST pods (one logical engine spanning hosts, e.g. a v5e-256):
  this module — `jax.distributed` process bootstrap + a global mesh
  whose collectives ride ICI within a host/pod slice and DCN across,
  exactly where XLA places them.

The single-host engines compose with this unchanged: a shard_map
program over `global_mesh()` runs SPMD on every participating process,
psum/pmax folds cross host boundaries transparently.  What stays
host-local is request ingest — each daemon feeds its addressable
shards (`process_local_batch`), which is the same "every daemon owns
its slice of the key space" contract the reference has, with the
collectives replacing its gRPC fan-out.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np

from .mesh import SHARD_AXIS


def initialize(coordinator_address: str, num_processes: int,
               process_id: int,
               local_device_count: Optional[int] = None) -> None:
    """Join (or form) a multi-process JAX cluster.

    ``coordinator_address`` is ``host:port`` of process 0 — the analog
    of the reference's peer-discovery seed.  For CPU-based tests, set
    ``local_device_count`` to force that many virtual devices per
    process (must happen before the backend initializes).
    """
    import os

    if local_device_count is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count="
                f"{local_device_count}").strip()
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id)


def global_mesh(axis: str = SHARD_AXIS) -> jax.sharding.Mesh:
    """1-D mesh over every device in the cluster (all hosts)."""
    return jax.sharding.Mesh(np.asarray(jax.devices()), (axis,))


def process_local_batch(mesh: jax.sharding.Mesh, host_cols, shape,
                        spec=None):
    """Assemble a globally-sharded array from THIS process's slice
    (jax.make_array_from_process_local_data) — the multi-host analog of
    the single-host ``device_put(batch, NamedSharding(...))``: every
    daemon contributes the sub-batch for the shards it hosts.
    ``spec`` overrides the default first-axis sharding (packed wire
    lanes are [cols, B] — sharded on axis 1, P(None, shard)).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(mesh, spec if spec is not None
                             else P(SHARD_AXIS))
    return jax.make_array_from_process_local_data(sharding, host_cols,
                                                  shape)
