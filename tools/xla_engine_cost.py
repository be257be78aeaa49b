"""What the XLA engine's device programs cost at the north-star size,
outside the benchmark (PERF.md §5–§7, PR 31, PR 32).

    python tools/xla_engine_cost.py step [log2cap[,log2cap...]] [B]
        no chip needed: compiles the classic step AND the sweep's
        module for a DESCRIBED v5e (JAX_PLATFORMS=cpu) at each capacity
        and prints one JSON line a program: XLA's own count of the
        bytes the module accesses (an upper bound: a gather or scatter
        counts its whole operand), its temporaries, and the X64 split /
        combine calls the 64-bit rewriter left — `x64_table_shaped`
        lists those with a table-sized operand or result, which the
        table of 32-bit words (core/table.py) leaves none of
        (tests/test_lowering.py holds it).
    python tools/xla_engine_cost.py sweep [log2cap]
        on a TPU: the expiry sweep's DEVICE time alone on an idle chip,
        from a profile — what the benchmark's `sweep_ms` (a host span,
        mostly the waves queued ahead of the sweep) cannot give.
    python tools/xla_engine_cost.py ops [log2cap] [B] [out.json]
        on a TPU: EVERY device op of the step, ms a step, from a
        profile of 20 waves of B Zipf(1.1) rows over 2^20 resident
        keys on an idle chip — the benchmark's `breakdown.device_ops`
        holds the ten longest only.  The whole list and the compiled
        module's text (to tell which fusion is which) go to out.json.
    python tools/xla_engine_cost.py layouts [log2cap] [B]
        on a TPU: what ONE gather / donated scatter of B rows costs by
        the table's layout — a 1-D word column against `[K, cap]`
        arrays holding K words a row (PERF.md §5, §7.12) — device ms
        from a profile.
"""
import json
import os
import re
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _report(program: str, cap: int, B: int, comp) -> dict:
    ca = comp.cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca
    x64 = [ln for ln in comp.as_text().splitlines()
           if 'custom_call_target="X64' in ln]
    shaped = [ln.split(" custom-call(")[0].strip() for ln in x64
              if any(str(cap) in dims.split(",")
                     for dims in re.findall(r"\[([\d,]+)\]", ln))]
    return {"program": program, "rows": cap, "B": B,
            "x64_combine": sum("X64Combine" in ln for ln in x64),
            "x64_split": sum("X64Split" in ln for ln in x64),
            "x64_table_shaped": shaped,
            "temp_bytes": comp.memory_analysis().temp_size_in_bytes,
            "bytes_accessed": ca.get("bytes accessed")}


def step(caps, B: int) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from gubernator_tpu.core.table import init_table
    from gubernator_tpu.parallel.mesh import SHARD_AXIS
    from gubernator_tpu.parallel.sharded import (make_pallas_sweep,
                                                 make_sharded_step_packed)

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:1]), (SHARD_AXIS,))
    row = NamedSharding(mesh, P(SHARD_AXIS))
    mat = NamedSharding(mesh, P(None, SHARD_AXIS))

    def sds(shape, dt, sh):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sh)

    now = sds((), jnp.int64, NamedSharding(mesh, P()))
    for cap in caps:
        state = jax.tree.map(lambda x: sds(x.shape, x.dtype, row),
                             jax.eval_shape(lambda: init_table(cap)))
        for program, comp in (
                ("xla_step_packed",
                 make_sharded_step_packed(mesh, donate=True).lower(
                     state, sds((8, B), jnp.int64, mat),
                     sds((3, B), jnp.int32, mat), now).compile()),
                ("sweep", make_pallas_sweep(mesh).lower(state,
                                                         now).compile())):
            print(json.dumps(_report(program, cap, B, comp)), flush=True)


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


def ops(cap: int, B: int, out: str) -> None:
    import jax
    import numpy as np

    from benchmark.harness import tracered
    from gubernator_tpu.core.batch import empty_batch
    from gubernator_tpu.parallel import ShardedEngine, make_mesh

    eng = ShardedEngine(make_mesh(n=1), capacity_per_shard=cap,
                        batch_per_shard=B, wave_buckets=(B,))
    rng = np.random.default_rng(32)
    keys = rng.integers(1, 1 << 63, 1 << 20).astype(np.uint64)
    now = 1_790_000_000_000

    def wave(k, t):
        b = empty_batch(B)
        b.key[:], b.hits[:], b.limit[:] = k, 1, 100
        b.duration[:], b.eff_ms[:], b.valid[:], b.now[:] = 10_000, 10_000, True, t
        return eng._run_wave(b, t)

    for i in range(0, len(keys), B):  # resident first: no insert below
        wave(keys[i:i + B], now)
    draws = [keys[rng.zipf(1.1, B) % len(keys)] for _ in range(20)]
    wave(draws[0], now + 1)
    state = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=x.sharding), eng.state)
    text = eng._step.lower(
        state, np.zeros((8, B), np.int64), np.zeros((3, B), np.int32),
        np.int64(0)).compile().as_text()
    trace_dir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    for i, k in enumerate(draws):
        wave(k, now + 2 + i)
    jax.profiler.stop_trace()
    rows = [r for r in tracered.load_xplane(trace_dir)
            if r[0].startswith(tracered.DEVICE_PLANE)]
    if not rows:
        raise SystemExit("no device plane in the profile: run on a TPU")
    plane = min(r[0] for r in rows)
    steps = [r[4] for r in rows if r[0] == plane
             and r[1] == tracered.MODULES_LINE and "xla_step_packed" in r[2]]
    per_op: dict = {}
    for r in rows:
        if r[0] == plane and r[1] == tracered.OPS_LINE:
            name = tracered.short_name(r[2])
            per_op[name] = per_op.get(name, 0.0) + r[4]
    table = sorted(((v / 1e6 / len(steps), k) for k, v in per_op.items()),
                   reverse=True)
    print("rows", cap, "B", B, "steps", len(steps), "module ms a step",
          round(sum(steps) / 1e6 / len(steps), 3), "distinct keys a wave",
          round(float(np.mean([len(np.unique(k)) for k in draws])), 1))
    for ms, name in table[:25]:
        print(f"{ms:9.4f} ms  {name}")
    print("all", len(table), "ops, ms a step (nested ops count twice):",
          round(sum(ms for ms, _ in table), 3))
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(out, "w") as f:
        json.dump({"rows": cap, "B": B, "step_ms": [x / 1e6 for x in steps],
                   "ops_ms_a_step": table, "module": text}, f)


def layouts(cap: int, B: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import tracered

    rng = np.random.default_rng(1)
    idx = np.sort(rng.choice(cap, B, replace=False)).astype(np.int32)
    vals = rng.integers(0, 1 << 32, B).astype(np.uint32)

    def table(K):
        return jnp.zeros((K, cap) if K else (cap,), jnp.uint32)

    # the names are the jits': a profile's "XLA Modules" line has them
    def gather_1d(t, i):
        return t.at[i].get(mode="fill", fill_value=0)

    def gather_rows(t, i):
        return t[:, i]

    def scatter_1d(t, i, v):
        return t.at[i].set(v, mode="drop", unique_indices=True,
                           indices_are_sorted=True)

    def scatter_1d_unpromised(t, i, v):
        return t.at[i].set(v, mode="drop")

    def scatter_rows(t, i, v):
        return t.at[:, i].set(v, mode="drop", unique_indices=True,
                              indices_are_sorted=True)

    def case(name, fn, *args, donate=False):
        return (name, jax.jit(fn, donate_argnums=0 if donate else ()),
                list(args))

    cases = [case("gather 1-D", gather_1d, table(0), idx)]
    cases += [case(f"gather [{K}, cap]", gather_rows, table(K), idx)
              for K in (2, 8, 17)]
    cases += [case("scatter 1-D sorted+unique", scatter_1d, table(0), idx,
                   vals, donate=True),
              case("scatter 1-D unpromised", scatter_1d_unpromised,
                   table(0), idx, vals, donate=True)]
    cases += [case(f"scatter [{K}, cap]", scatter_rows, table(K), idx,
                   np.tile(vals, (K, 1)), donate=True) for K in (2, 8)]

    def run(fn, args):
        out = jax.block_until_ready(fn(*args))
        if len(args) == 3:
            args[0] = out  # the donated table threads through

    for _, fn, args in cases:
        run(fn, args)  # compile
    trace_dir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    for _, fn, args in cases:
        for _ in range(5):
            run(fn, args)
    jax.profiler.stop_trace()
    rows = [r for r in tracered.load_xplane(trace_dir)
            if r[0].startswith(tracered.DEVICE_PLANE)]
    if not rows:
        raise SystemExit("no device plane in the profile: run on a TPU")
    plane = min(r[0] for r in rows)
    by_jit: dict = {}
    for r in rows:
        if r[0] == plane and r[1] == tracered.MODULES_LINE:
            by_jit.setdefault(r[2].split("(")[0], []).append(r[4] / 1e6)
    print("rows", cap, "B", B, "device ms a call, five calls each:")
    for name, fn, _ in cases:
        ms = by_jit["jit_" + fn.__wrapped__.__name__]
        print(f"  {name:28s}", [round(x, 4) for x in ms[:5]])
        del ms[:5]


if __name__ == "__main__":
    what = sys.argv[1] if len(sys.argv) > 1 else "step"
    caps = [1 << int(c) for c in
            (sys.argv[2] if len(sys.argv) > 2 else "26").split(",")]
    B = int(sys.argv[3]) if len(sys.argv) > 3 else 8192
    if what == "step":
        step(caps, B)
    elif what == "ops":
        ops(caps[0], B, sys.argv[4] if len(sys.argv) > 4
            else "chiprun_out/xla_step_ops.json")
    elif what == "layouts":
        layouts(caps[0], B)
    else:
        sweep(caps[0])
