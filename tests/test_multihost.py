"""Multi-host bootstrap: two real OS processes form one JAX cluster
(CPU devices standing in for two hosts' chips) and run the decision
step + a psum fold across the process boundary — the DCN-analog of the
pod-local collectives (SURVEY.md §5.8)."""
import os
import socket
import subprocess
import sys

import pytest

_WORKER = r"""
import os, sys
proc_id = int(sys.argv[1]); coord = sys.argv[2]
os.environ.pop("JAX_PLATFORMS", None)
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
from gubernator_tpu.parallel import multihost

multihost.initialize(coord, num_processes=2, process_id=proc_id,
                     local_device_count=2)
assert jax.process_count() == 2, jax.process_count()
assert len(jax.devices()) == 4, jax.devices()  # 2 hosts x 2 devices

import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

mesh = multihost.global_mesh()

# per-"chip" consumption fold across the process boundary
def fold(d):
    return lax.psum(d, "shard")

folded = jax.jit(shard_map(fold, mesh=mesh, in_specs=P("shard"),
                           out_specs=P()))
local = np.full((2, 8), proc_id + 1, np.int64)  # this host's 2 shards
d = multihost.process_local_batch(mesh, local, (4, 8))
out = folded(d)
got = np.asarray(jax.device_get(
    out.addressable_shards[0].data)).reshape(-1)
# psum over shards: 1 + 1 + 2 + 2 = 6 everywhere
assert (got == 6).all(), got

# the decision step compiles and runs over the multi-host mesh
from gubernator_tpu.core.batch import pack_requests
from gubernator_tpu.parallel.mesh import shard_table
from gubernator_tpu.parallel.sharded import make_sharded_step
from gubernator_tpu.types import RateLimitRequest

step = make_sharded_step(mesh)
state = shard_table(mesh, 1 << 8)
B = 16  # per shard
reqs = [RateLimitRequest(name="mh", unique_key=f"k{proc_id}_{i}", hits=1,
                         limit=5, duration=60_000) for i in range(2 * B)]
batch, _ = pack_requests(reqs, 1_760_000_000_000, size=2 * B)
from jax.sharding import NamedSharding
sh = NamedSharding(mesh, P("shard"))
import jax.numpy as jnp2
dev_batch = type(batch)(*[
    multihost.process_local_batch(mesh, np.asarray(c),
                                  (4 * B,) + np.asarray(c).shape[1:])
    for c in batch])
state, outs, counters = step(state, dev_batch,
                             jnp.asarray(1_760_000_000_000, jnp.int64))
over, ins = int(counters[0]), int(counters[1])
assert ins == 4 * B // 2 * 2, ins  # every process's 2B keys inserted

# the pallas (Mosaic-kernel) serving step over the SAME multi-host
# mesh — the kernel mode's DCN-analog gate (interpret on CPU, same as
# its off-TPU serving path).  Raw packed lanes: the engine's host
# routing is single-process, but the device step is pure shard_map.
from gubernator_tpu.ops import pallas_step as pstep_mod
from gubernator_tpu.parallel.pallas_engine import make_pallas_step_packed

NBL = 2         # buckets per shard
PB = 32         # batch rows per shard
pkstep = make_pallas_step_packed(mesh, interpret=True)
rows = multihost.process_local_batch(
    mesh, np.zeros((2 * NBL, pstep_mod.WORDS, pstep_mod.SLOTS), np.int32),
    (4 * NBL, pstep_mod.WORDS, pstep_mod.SLOTS))
NOWP = 1_760_000_000_000
rngp = np.random.default_rng(100 + proc_id)
nreq = 2 * PB
alg = np.zeros(nreq, np.int32)
alg[::2] = 1  # half LEAKY
from gubernator_tpu.core.batch import RequestBatch as RB
from gubernator_tpu.parallel.sharded import pack_wave_host

pbatch = RB(
    key=rngp.integers(1, 1 << 62, nreq).astype(np.uint64),
    hits=np.ones(nreq, np.int64),
    limit=np.full(nreq, 5, np.int64),
    duration=np.full(nreq, 60_000, np.int64),
    eff_ms=np.full(nreq, 60_000, np.int64),
    greg_end=np.zeros(nreq, np.int64),
    behavior=np.zeros(nreq, np.int32), algorithm=alg,
    burst=np.full(nreq, 5, np.int64),
    valid=np.ones(nreq, bool),
    now=np.full(nreq, NOWP, np.int64))
a64_host, a32_host = pack_wave_host(pbatch)
a64 = multihost.process_local_batch(mesh, a64_host, (8, 4 * PB),
                                    spec=P(None, "shard"))
a32 = multihost.process_local_batch(mesh, a32_host, (3, 4 * PB),
                                    spec=P(None, "shard"))
rows, packed, (pover, pins) = pkstep(
    rows, a64, a32, jnp.asarray(NOWP, jnp.int64))
assert int(pins) == 4 * PB, int(pins)  # every key inserted, all shards
st_local = np.asarray(jax.device_get(
    packed.addressable_shards[0].data))
assert (st_local[0] == 0).all()        # fresh keys: UNDER_LIMIT
assert (st_local[1] == 4).all()        # remaining = 5 - 1

print(f"proc {proc_id} ok: psum fold + sharded step over 2 hosts, "
      f"inserted={ins}, pallas inserted={int(pins)}")
"""


@pytest.mark.skipif(os.environ.get("GUBER_SKIP_MULTIHOST") == "1",
                    reason="multihost test disabled")
def test_two_process_cluster_runs_step_and_fold(tmp_path):
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    coord = f"127.0.0.1:{port}"
    script = tmp_path / "worker.py"
    script.write_text(_WORKER)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # worker sets its own device count
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    procs = [subprocess.Popen(
        [sys.executable, str(script), str(i), coord],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        for i in range(2)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=300)
            outs.append(out.decode())
    finally:
        for p in procs:
            p.kill()
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {i} failed:\n{out[-3000:]}"
        assert f"proc {i} ok" in out
