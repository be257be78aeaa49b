"""Mesh construction and table shardings.

The key universe is ranged-sharded across the ``shard`` mesh axis by the
top bits of the key hash (hashing.shard_of) — the TPU-native equivalent
of the reference's consistent-hash key ownership (hash.go ›
ConsistantHash / replicated_hash.go — reconstructed).  Each device owns
one contiguous hash range; its table shard lives in its HBM.
"""
from __future__ import annotations

from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.table import TableState, init_table

SHARD_AXIS = "shard"

#: Process-wide gate around synchronous XLA executions.  This image's
#: XLA:CPU wedges indefinitely when SEVERAL engines (the in-process
#: multi-daemon test clusters) execute jitted programs concurrently
#: from different threads — observed as every daemon's handler stuck
#: inside the step call (tests/test_soak_wire.py, faulthandler dump).
#: Per-instance engine locks can't prevent that cross-engine overlap;
#: this mutex does.  Single-engine processes (the production topology)
#: already serialize device work on their own engine lock, so the gate
#: is uncontended there.
import contextlib as _contextlib
import threading as _threading

from ..tracing import phase

XLA_EXEC_MU = _threading.Lock()


@_contextlib.contextmanager
def exec_gate():
    """XLA_EXEC_MU round one wave's dispatch (``_launch_arrays``), with
    the wait for it and the dispatch itself timed as the `lock.xla_exec`
    and `wave.dispatch` phases (tracing.PHASE_CATALOG)."""
    wait = phase("lock.xla_exec").begin()
    with XLA_EXEC_MU:
        wait.end()
        with phase("wave.dispatch"):
            yield


def make_mesh(devices: Sequence[jax.Device] | None = None,
              n: int | None = None) -> Mesh:
    """1-D mesh over ``n`` devices (default: all local devices)."""
    devs = list(devices) if devices is not None else jax.devices()
    if n is not None:
        devs = devs[:n]
    return Mesh(np.array(devs), (SHARD_AXIS,))


def table_sharding(mesh: Mesh) -> NamedSharding:
    """Rows sharded across the mesh: row block d of the global table is
    device d's hash range."""
    return NamedSharding(mesh, P(SHARD_AXIS))


def shard_table(mesh: Mesh, capacity_per_shard: int) -> TableState:
    """Build a global table of n_shards × capacity_per_shard rows,
    sharded one block per device.  Built under jit with the output
    sharding, so each device materializes only its own block — a
    2^26-row table is 4.6 GB and must never exist whole on device 0."""
    n = mesh.shape[SHARD_AXIS]
    return jax.jit(lambda: init_table(n * capacity_per_shard),
                   out_shardings=table_sharding(mesh))()
