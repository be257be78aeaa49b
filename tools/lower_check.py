"""Offline TPU build check for every program the TPU default runs.

Two depths, both without a chip:

- **lower**: cross-platform AOT lowering (``trace().lower(
  lowering_platforms=("tpu",))``) runs the Pallas→Mosaic MLIR pipeline
  client-side on the CPU backend.  Catches tracing/legalization errors
  (block-shape rule, 64-bit converts in a kernel, unsupported ops).
- **compile**: where the installed libtpu offers a compile-only client
  (``jax.experimental.topologies``), the same programs are compiled
  for a TPU v5e — the real Mosaic/XLA:TPU compiler, in seconds.
  Lowering is not compiling: the former ``[CAP, 32]`` row table lowered
  fine and failed here ("Slice shape along dimension 1 must be aligned
  to tiling (128), but is 32").

Programs: the Mosaic decision kernel, the Mosaic sweep kernel, the
fused serving program (kernel under shard_map + packed wire layout +
device tap), and the three XLA step modes (copy, donated, K-split).
Only the programs with a kernel in them go to the compile depth; the
XLA step modes are plain XLA and are lowered.

Usage: python tools/lower_check.py   (exit 0 = all programs build; the
last line says which depth ran and why)
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
# a compile-only TPU client needs no chip, only a topology to target
os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)  # the engine's contract

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

N = 512  # request rows
CAP = 1 << 12


def _tpu_compile_devices():
    """(devices, "") of a compile-only TPU v5e topology, or
    (None, reason) when this installation has no TPU compiler."""
    try:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
        return list(topo.devices), ""
    except Exception as e:  # noqa: BLE001 - reported on the last line
        return None, (str(e) or repr(e)).splitlines()[0][:200]


def _programs(mesh):
    """[(name, jitted fn, args as ShapeDtypeStruct pytrees, has_kernel)]
    over ``mesh`` (1 device: the CPU for lowering, a topology device
    for compiling)."""
    from gubernator_tpu.core.batch import RequestBatch
    from gubernator_tpu.core.step import decide_batch, decide_batch_donated
    from gubernator_tpu.core.table import init_table
    from gubernator_tpu.ops import pallas_step as ps
    from gubernator_tpu.ops.pallas_sweep import sweep_expired_pallas
    from gubernator_tpu.parallel.pallas_engine import make_fused_step_packed

    i64, i32 = jnp.int64, jnp.int32

    def sds(shape, dt, spec=P()):
        return jax.ShapeDtypeStruct(shape, dt,
                                    sharding=NamedSharding(mesh, spec))

    batch = RequestBatch(
        # uint64 like every real caller: int64 keys would promote
        # int64>>uint64 to float64 in _probe_slots
        key=sds((N,), jnp.uint64), hits=sds((N,), i64),
        limit=sds((N,), i64), duration=sds((N,), i64),
        eff_ms=sds((N,), i64), greg_end=sds((N,), i64),
        behavior=sds((N,), i32), algorithm=sds((N,), i32),
        burst=sds((N,), i64), valid=sds((N,), jnp.bool_),
        now=sds((N,), i64))
    now = sds((), i64)

    def table(cap):
        return jax.tree.map(lambda x: sds(x.shape, x.dtype),
                            jax.eval_shape(lambda: init_table(cap)))

    buckets = sds((CAP // ps.SLOTS, ps.WORDS, ps.SLOTS), i32)
    progs = [
        ("pallas_step", ps.decide_batch_pallas,
         (ps.PallasTable(buckets=buckets), batch, now), True),
        ("pallas_sweep", sweep_expired_pallas, (table(CAP), now), True),
        ("pallas_fused_serving",
         make_fused_step_packed(mesh, flavor="pallas", tile=ps.TILE),
         (sds(buckets.shape, i32, P("shard", None, None)),
          sds((8, N), i64, P(None, "shard")),
          sds((3, N), i32, P(None, "shard")), now), True),
    ]
    if os.environ.get("GUBER_KSPLIT"):
        # the K-split rewrite only activates at CAP > 2^ksplit — a
        # genuinely split table (read at core.table import: own process)
        return [(f"xla_step_donated_ksplit{os.environ['GUBER_KSPLIT']}",
                 decide_batch_donated, (table(1 << 22), batch, now), False)]
    return progs + [
        ("xla_step", decide_batch, (table(CAP), batch, now), False),
        ("xla_step_donated", decide_batch_donated,
         (table(CAP), batch, now), False),
    ]


def main() -> int:
    devices, why_not = _tpu_compile_devices()
    mesh = Mesh(np.array((devices or jax.devices())[:1]), ("shard",))
    failures = 0
    for name, fn, args, has_kernel in _programs(mesh):
        try:
            # fn is already jitted (with donate_argnums where relevant)
            # — re-wrapping in jax.jit would drop the donation and lower
            # a copy-mode duplicate instead of the aliased program
            if devices is not None and has_kernel:
                fn.lower(*args).compile()
                print(f"{name}: compiles for TPU v5e")
            else:
                fn.trace(*args).lower(lowering_platforms=("tpu",))
                print(f"{name}: lowers for TPU")
        except Exception as e:  # noqa: BLE001 - the check's finding
            failures += 1
            print(f"{name}: FAILED: {str(e)[:600]}")
    if not os.environ.get("GUBER_KSPLIT"):
        import subprocess

        r = subprocess.run([sys.executable, os.path.abspath(__file__)],
                           env=dict(os.environ, GUBER_KSPLIT="21"))
        failures += 1 if r.returncode else 0
        print("depth: " + (
            "kernels compiled with the installed TPU compiler"
            if devices is not None else
            f"lowering only — no TPU compiler here ({why_not})"))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
