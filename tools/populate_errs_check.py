"""Offline zero-loss audit for candidate flagship shapes (round 5).

The on-chip A/B showed today's backend compiler serializes the step at
the CAP 2^25 + 16-probe flagship shape (0.35M dec/s) while 8-probe
shapes lower well clear up to CAP 2^27 (564M dec/s, bench cfg5).  To
move the flagship to an 8-probe shape WITHOUT giving back VERDICT-r3
item 9 (populate_errs == 0: the headline must serve 100% of its
working set), this script reproduces bench.py's EXACT populate — ids
0..N_KEYS-1 through _keyhash, inserted in B-sized chunks — on the CPU
backend (slot placement is backend-independent: same keys, same probe
sequence, same claim rounds) and reports the insert-failure count per
(CAP, probes) candidate.

    JAX_PLATFORMS=cpu python tools/populate_errs_check.py 25:8 26:8

Each argument is log2cap:probes.  Results → /tmp/populate_errs.json.
"""
import json
import os
import subprocess
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.abspath(os.path.join(_HERE, ".."))

OUT = "/tmp/populate_errs.json"


def run_one(log2cap: int, probes: int, n_keys: int, B: int) -> dict:
    """One candidate per child process, each with a step of its own
    jitted for the candidate's probe window (the table's own PROBES is
    a constant of core/step.py)."""
    code = f"""
import json, time
import numpy as np
import jax, jax.numpy as jnp
jax.config.update("jax_platforms", "cpu")
import sys
sys.path.insert(0, {_REPO!r})
from bench import _keyhash, pad_chunk, _mk_batch
from functools import partial
from gubernator_tpu.core.step import decide_batch_impl
from gubernator_tpu.core.table import init_table

decide_batch_donated = jax.jit(
    partial(decide_batch_impl, probes={probes}), donate_argnums=0)
i64 = jnp.int64
cap, n_keys, B = 1 << {log2cap}, {n_keys}, {B}
st = init_table(cap)
ids = np.arange(n_keys, dtype=np.uint64)
now = jnp.asarray(1_760_000_000_000, i64)
errs = 0
t0 = time.time()
for a in range(0, n_keys, B):
    chunk = pad_chunk(ids[a:a + B], B)
    st, out = decide_batch_donated(
        st, _mk_batch(jnp, _keyhash(chunk)), now)
    errs += int(np.asarray(out.err).sum())
print(json.dumps({{"errs": errs, "seconds": round(time.time() - t0, 1),
                   "load": round(n_keys / cap, 3)}}))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       stdout=subprocess.PIPE, timeout=7200)
    line = r.stdout.decode().strip().splitlines()[-1]
    return json.loads(line)


def main() -> int:
    n_keys = int(os.environ.get("GUBER_BENCH_KEYS", "10000000"))
    B = int(os.environ.get("GUBER_BENCH_B", "65536"))
    res = {"n_keys": n_keys, "B": B,
           "started": time.strftime("%Y-%m-%d %H:%M:%S")}
    for arg in sys.argv[1:] or ["25:8", "26:8"]:
        log2cap, probes = (int(x) for x in arg.split(":"))
        t = time.time()
        try:
            res[arg] = run_one(log2cap, probes, n_keys, B)
        except Exception as e:  # noqa: BLE001
            res[arg] = {"error": (str(e) or repr(e))[:300]}
        res[arg]["wall_s"] = round(time.time() - t, 1)
        with open(OUT, "w") as f:
            json.dump(res, f, indent=1)
        print(f"[populate_errs] {arg}: {res[arg]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
