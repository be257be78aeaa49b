"""What the XLA engine's device programs cost at the north-star size,
outside the benchmark (PERF.md §5–§7, PR 31).

    python tools/xla_engine_cost.py step [log2cap] [B]
        no chip needed: compiles the classic step for a DESCRIBED v5e
        (JAX_PLATFORMS=cpu) and prints XLA's own count of the bytes the
        module accesses (an upper bound: a gather or scatter counts its
        whole operand) and its memory_analysis.
    python tools/xla_engine_cost.py sweep [log2cap]
        on a TPU: the expiry sweep's DEVICE time alone on an idle chip,
        from a profile — what the benchmark's `sweep_ms` (a host span,
        mostly the waves queued ahead of the sweep) cannot give.
"""
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def step(cap: int, B: int) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from gubernator_tpu.core.table import TableState
    from gubernator_tpu.parallel.mesh import SHARD_AXIS
    from gubernator_tpu.parallel.sharded import make_sharded_step_packed

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:1]), (SHARD_AXIS,))
    row = NamedSharding(mesh, P(SHARD_AXIS))
    mat = NamedSharding(mesh, P(None, SHARD_AXIS))

    def sds(shape, dt, sh):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sh)

    state = TableState(
        key=sds((cap,), jnp.uint64, row), meta=sds((cap,), jnp.int32, row),
        **{f: sds((cap,), jnp.int64, row) for f in
           ("limit", "duration", "eff_ms", "burst", "remaining", "t_ms",
            "expire_at")})
    comp = make_sharded_step_packed(mesh, donate=True).lower(
        state, sds((8, B), jnp.int64, mat), sds((3, B), jnp.int32, mat),
        sds((), jnp.int64, NamedSharding(mesh, P()))).compile()
    ca = comp.cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca
    txt = comp.as_text()
    print("rows", cap, "B", B, "bytes accessed", ca.get("bytes accessed"))
    print("X64Combine", txt.count("X64Combine"),
          "X64Split", txt.count("X64Split"))
    print(comp.memory_analysis())


def sweep(cap: int) -> None:
    import jax

    from benchmark.harness import tracered
    from gubernator_tpu.parallel import ShardedEngine, make_mesh

    eng = ShardedEngine(make_mesh(n=1), capacity_per_shard=cap)
    now = int(time.time() * 1000)  # clock-ok: a sweep horizon, no bucket stamp
    eng.sweep(now)  # compiles
    trace_dir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    walls = []
    for i in range(5):
        t0 = time.perf_counter()
        eng.sweep(now + i)
        walls.append(1e3 * (time.perf_counter() - t0))
    jax.profiler.stop_trace()
    rows = [r for r in tracered.load_xplane(trace_dir)
            if r[0].startswith(tracered.DEVICE_PLANE)]
    plane = min((r[0] for r in rows), default=None)
    print("host wall ms a sweep, idle chip:", [round(w, 3) for w in walls])
    for r in rows:
        if r[0] == plane and r[1] == tracered.MODULES_LINE:
            print("module", r[2][:60], "device ms", r[4] / 1e6)
    print("custom calls, device ms:",
          [round(r[4] / 1e6, 3) for r in rows if r[0] == plane
           and r[1] == tracered.OPS_LINE and tracered.KERNEL_MARK in r[2]])
    table = cap * 68
    print("table bytes", table, "read+write at 819 GB/s, ms:",
          2e3 * table / 819e9)


if __name__ == "__main__":
    what = sys.argv[1] if len(sys.argv) > 1 else "step"
    cap = 1 << (int(sys.argv[2]) if len(sys.argv) > 2 else 26)
    if what == "step":
        step(cap, int(sys.argv[3]) if len(sys.argv) > 3 else 8192)
    else:
        sweep(cap)
