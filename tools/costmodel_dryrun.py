"""Collective cost-model dryrun (ISSUE 11): fit α-β on live folds.

Runs the mesh-GLOBAL reconcile collective (parallel/meshglobal.py) on
a forced 8-device CPU mesh at several fold sizes (the fold moves the
replicated value columns + accumulator, so bytes scale with the tier
capacity), feeds the timed samples to ``analytics.CostModel``, and
validates the fitted ``T(bytes) = α + β·bytes`` against a HELD-OUT
fold size the fit never saw — prediction vs the median observed time
at that size, with the relative error stated in the artifact.

Writes ``MULTICHIP_r06.json``: the r05-compatible verdict keys
(``n_devices`` / ``rc`` / ``ok`` / ``skipped`` / ``tail``) plus a
``cost_model`` block with the fitted constants — the same α/β the
``12_mesh_global`` bench row records from its live folds, here
cross-validated.  The hierarchical-reconcile ROADMAP item prices
levels with these constants.

Usage::

    python tools/costmodel_dryrun.py [--devices 8] \
        [--json MULTICHIP_r06.json]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

NOW0 = 1_760_000_000_000

#: stated acceptance bound on the held-out relative error.  The α term
#: dominates on a host-CPU mesh (collective launch, not bandwidth), so
#: the model must land the held-out size well inside 2× even with
#: shared-host timer noise.
REL_ERR_BUDGET = 0.5


def _force_devices(n: int):
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()
    # a virtual-device dry run: CPU backend, pinned the standard way
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    return jax


def run(n_devices: int = 8, train_caps=(256, 1024, 4096),
        holdout_cap: int = 2048, reps: int = 12,
        warmup: int = 3) -> dict:
    jax = _force_devices(n_devices)
    if len(jax.devices()) < n_devices:
        raise RuntimeError(
            f"need {n_devices} devices, have {len(jax.devices())}; run "
            "with XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n_devices} and a cpu jax platform")
    import numpy as np

    from gubernator_tpu.analytics import CostModel
    from gubernator_tpu.hashing import hash_key
    from gubernator_tpu.parallel import make_mesh
    from gubernator_tpu.parallel.meshglobal import MeshGlobalEngine
    from gubernator_tpu.types import RateLimitRequest

    mesh = make_mesh(n=n_devices)
    cm = CostModel()

    def fold_samples(cap: int):
        """(fold_nbytes, per-fold seconds) at one tier capacity, with
        exact conservation re-asserted — a timing run that loses hits
        would be fitting a broken collective."""
        mge = MeshGlobalEngine(mesh, capacity=cap, batch_per_chip=32)
        req = RateLimitRequest(name="cost", unique_key="k", hits=1,
                               limit=10 ** 9, duration=600_000)
        kh = hash_key("cost", "k")
        assert mge.pin(req, kh, NOW0)
        times = []
        for i in range(warmup + reps):
            mge.check_batch([req] * n_devices, [kh] * n_devices,
                            NOW0 + i)
            t0 = time.perf_counter()
            mge.fold(mge.swap_accum())
            mge.drain()  # block until the collective fully resolves
            dt = time.perf_counter() - t0
            if i >= warmup:  # compile + first-touch excluded
                times.append(dt)
        s = mge.stats()
        assert s["folded_hits"] == s["injected_hits"], s
        return mge.fold_nbytes, times

    observed = {}
    for cap in sorted(set(train_caps) | {holdout_cap}):
        nbytes, times = fold_samples(cap)
        observed[cap] = (nbytes, times)
        if cap != holdout_cap:
            for dt in times:
                cm.add("global_fold", nbytes, n_devices, dt)

    fit = cm.fit("global_fold", n_devices)
    assert fit is not None and fit["n"] == reps * len(set(train_caps))
    hold_bytes, hold_times = observed[holdout_cap]
    actual_s = float(np.median(hold_times))
    pred_s = cm.predict("global_fold", n_devices, hold_bytes)
    rel_err = abs(pred_s - actual_s) / actual_s
    return {
        "phase": "global_fold",
        "ndev": n_devices,
        "model": "T = alpha + beta * bytes",
        "alpha_us": round(fit["alpha_s"] * 1e6, 3),
        "beta_ns_per_byte": round(fit["beta_s_per_byte"] * 1e9, 6),
        "train_samples": fit["n"],
        "train_fold_bytes": sorted(observed[c][0] for c in train_caps),
        "holdout_fold_bytes": hold_bytes,
        "holdout_pred_us": round(pred_s * 1e6, 3),
        "holdout_actual_us": round(actual_s * 1e6, 3),
        "holdout_rel_err": round(rel_err, 4),
        "rel_err_budget": REL_ERR_BUDGET,
        "within_budget": bool(rel_err <= REL_ERR_BUDGET),
        "buckets": cm.snapshot()["buckets"],
        "context": ("host-CPU mesh: α (collective launch + rendezvous) "
                    "dominates and β is small/noisy — on TPU hardware "
                    "the per-byte term carries the interconnect "
                    "bandwidth; the held-out check validates the FIT "
                    "DISCIPLINE, the constants are host-class-local"),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="fit + hold-out-validate the collective cost model")
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--json", default=os.path.join(REPO,
                                                   "MULTICHIP_r06.json"))
    args = ap.parse_args(argv)
    try:
        block = run(n_devices=args.devices)
        ok = block["within_budget"]
        tail = (f"costmodel_dryrun ok: {args.devices} shards, "
                f"global_fold alpha={block['alpha_us']}us "
                f"beta={block['beta_ns_per_byte']}ns/B, held-out "
                f"{block['holdout_fold_bytes']}B rel_err="
                f"{block['holdout_rel_err']} "
                f"(budget {block['rel_err_budget']})\n")
        verdict = {"n_devices": args.devices, "rc": 0 if ok else 1,
                   "ok": ok, "skipped": False, "tail": tail,
                   "cost_model": block}
    except Exception as e:  # noqa: BLE001 - verdict artifact, not a trace
        verdict = {"n_devices": args.devices, "rc": 1, "ok": False,
                   "skipped": False,
                   "tail": f"costmodel_dryrun failed: {e!r}\n"}
    doc = json.dumps(verdict, indent=2)
    print(doc)
    with open(args.json, "w", encoding="utf-8") as f:
        f.write(doc + "\n")
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
