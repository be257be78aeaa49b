"""Memory-advisor dryrun (ISSUE 13): does the advised split win?

The acceptance oracle for ``memledger.advise()``: a seeded
shifting-Zipf workload (the hot band drifts across the key domain
every phase, so yesterday's residents keep getting demoted) runs
against the DEFAULT static split of the device-row budget — half to
the hot table, half to the mesh-GLOBAL tier — while only a handful of
GLOBAL keys actually live in the mesh tier.  Phase 1 measures: the
ledger's demand vector (Space-Saving rank distribution for the hot
table, occupancy + fold rate for the mesh tier) feeds the
water-filling advisor, which recommends moving most of the mesh
tier's idle rows to the hot table.  Phase 2 validates: the SAME seeded
workload replayed against the default split and against the advised
split (recommendation applied as static config — there is no live
repartition), comparing hot-tier hit rate ``1 - cold_served/rows``.
The advised split must win STRICTLY, without spending more device
bytes than the default split (both asserted from the ledger itself).

Writes ``MEMADVISOR_r01.json``: the dryrun-verdict keys
(``n_devices`` / ``rc`` / ``ok`` / ``skipped`` / ``tail``) plus a
``14_memadvisor`` bench-row block carrying the demand vector, the
recommendation, and both measured splits.

Usage::

    python tools/memadvisor_dryrun.py [--keys 6000] \
        [--json MEMADVISOR_r01.json]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

NOW0 = 1_760_000_000_000
ZIPF_A = 1.1
#: device-row budget the two splits share: default gives half to the
#: hot table and half to the mesh-GLOBAL tier (the static-knob status
#: quo this PR's ROADMAP item wants replaced)
BUDGET_ROWS = 2048
DEFAULT_SPLIT = {"hot_table": 1024, "mesh_global": 1024}
N_GLOBAL_KEYS = 16


def _force_cpu():
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:  # noqa: BLE001
        pass
    return jax


def _workload(nkeys: int, phases: int, batch: int):
    """Deterministic shifting-Zipf batches: one full permutation pass
    (every key exists → the capped table MUST overflow into the cold
    tier), then ``phases`` hot bands that drift by nkeys//phases each
    phase — the demand a static split can only chase with spare rows."""
    import numpy as np

    rng = np.random.default_rng(1313)
    stream = [rng.permutation(nkeys)]
    shift = nkeys // max(phases, 1)
    for p in range(phases):
        draws = (rng.zipf(ZIPF_A, size=2 * batch) - 1 + p * shift) % nkeys
        stream.append(draws)
    flat = np.concatenate(stream)
    pad = (-len(flat)) % batch
    if pad:
        flat = np.concatenate([flat, flat[:pad]])
    return [flat[i:i + batch] for i in range(0, len(flat), batch)]


def _run_split(split: dict, batches, collect_advice: bool):
    """Serve the whole workload against one static split; returns the
    measured row (hit rate, ledger bytes) and — when asked — the
    demand-fed recommendation from this run's ledger."""
    from gubernator_tpu.config import Config
    from gubernator_tpu.instance import V1Instance
    from gubernator_tpu.parallel import make_mesh
    from gubernator_tpu.types import Behavior, RateLimitRequest

    greq = [RateLimitRequest(name="adv", unique_key=f"g{i}", hits=1,
                             limit=10 ** 9, duration=600_000,
                             behavior=Behavior.GLOBAL)
            for i in range(N_GLOBAL_KEYS)]
    hot_rows = int(split["hot_table"])
    prev_cap = os.environ.get("GUBER_MESH_GLOBAL_CAP")
    os.environ["GUBER_MESH_GLOBAL_CAP"] = str(int(split["mesh_global"]))
    try:
        inst = V1Instance(Config(cache_size=hot_rows,
                                 cache_autogrow_max=hot_rows,
                                 tier_cold=True,
                                 tier_promote_threshold=2,
                                 sweep_interval_ms=0,
                                 global_mode="mesh"),
                          mesh=make_mesh(n=1))
    finally:
        if prev_cap is None:
            os.environ.pop("GUBER_MESH_GLOBAL_CAP", None)
        else:
            os.environ["GUBER_MESH_GLOBAL_CAP"] = prev_cap
    local_rows = 0
    try:
        now = NOW0
        for keys in batches:
            reqs = [RateLimitRequest(
                name="adv", unique_key=f"k{int(k)}", hits=1,
                limit=10 ** 9, duration=86_400_000) for k in keys]
            local_rows += len(reqs)
            now += 1
            inst.get_rate_limits(reqs + greq, now_ms=now)
        ana = inst.analytics
        if ana is not None:
            ana.flush(timeout=5.0)
        st = inst._tier.stats()
        snap = inst.memledger.snapshot()
        row = {
            "split": dict(split),
            "rows_sent": local_rows,
            "cold_served": st["cold_served"],
            "cold_keys": st["cold_keys"],
            "promotions": st["promotions"],
            "hot_hit_rate": round(1 - st["cold_served"]
                                  / max(local_rows, 1), 4),
            "device_bytes": snap["device_bytes"],
            "mesh_occupied": snap["consumers"].get(
                "mesh_global", {}).get("occupied_rows", 0),
        }
        advice = None
        if collect_advice:
            advice = inst.memledger.advise()
        return row, advice
    finally:
        inst.close()


def run(nkeys: int = 6000, phases: int = 4, batch: int = 984) -> dict:
    # batch + N_GLOBAL_KEYS must stay within the 1000-row wire cap
    jax = _force_cpu()
    assert jax.default_backend() == "cpu", jax.default_backend()
    batches = _workload(nkeys, phases, batch)

    # phase 1: measure demand under the default split, take the advice
    default_row, advice = _run_split(DEFAULT_SPLIT, batches,
                                     collect_advice=True)
    assert advice is not None and advice["advised"], advice
    assert advice["total_rows"] == BUDGET_ROWS, advice
    advised_split = {
        "hot_table": advice["advised_pow2"]["hot_table"],
        "mesh_global": advice["advised_pow2"]["mesh_global"]}

    # phase 2: replay the identical workload against the advised split
    advised_row, _ = _run_split(advised_split, batches,
                                collect_advice=False)

    hit_gain = advised_row["hot_hit_rate"] - default_row["hot_hit_rate"]
    strictly_better = advised_row["hot_hit_rate"] \
        > default_row["hot_hit_rate"]
    # the recommendation must not buy its hit rate with MORE silicon:
    # the mesh tier's rows cost replica + two accumulators each, so
    # trading 960 of them for 1024 hot rows nets fewer device bytes
    no_more_bytes = (advised_row["device_bytes"]
                     <= default_row["device_bytes"])
    # trim the rank vector for the artifact; the full curve fed advise()
    demand = {k: (dict(v, ranks=v["ranks"][:32],
                       ranks_len=len(v["ranks"])) if "ranks" in v
                  else v)
              for k, v in advice["demand"].items()}
    return {
        "key_domain": nkeys,
        "phases": phases,
        "batch": batch,
        "budget_rows": BUDGET_ROWS,
        "demand": demand,
        "recommendation": {k: advice[k] for k in
                           ("total_rows", "floor_rows", "current",
                            "advised", "advised_pow2")},
        "default": default_row,
        "advised": advised_row,
        "hit_rate_gain": round(hit_gain, 4),
        "advised_strictly_better": bool(strictly_better),
        "advised_no_more_device_bytes": bool(no_more_bytes),
        "ok": bool(strictly_better and no_more_bytes),
        "context": ("CPU mesh (n=1): the A/B compares static splits of "
                    "the same device-row budget on identical seeded "
                    "shifting-Zipf traffic; the advisor only ever "
                    "recommends — nothing repartitions live"),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="validate memledger.advise() on shifting-Zipf "
                    "traffic (advised vs default static split)")
    ap.add_argument("--keys", type=int, default=6000)
    ap.add_argument("--phases", type=int, default=4)
    ap.add_argument("--json", default=os.path.join(
        REPO, "MEMADVISOR_r01.json"))
    args = ap.parse_args(argv)
    try:
        block = run(nkeys=args.keys, phases=args.phases)
        ok = block["ok"]
        tail = (f"memadvisor_dryrun ok={ok}: advised "
                f"{block['recommendation']['advised_pow2']} vs default "
                f"{block['default']['split']} -> hot hit rate "
                f"{block['advised']['hot_hit_rate']} vs "
                f"{block['default']['hot_hit_rate']} "
                f"(gain {block['hit_rate_gain']}), device bytes "
                f"{block['advised']['device_bytes']} vs "
                f"{block['default']['device_bytes']}\n")
        verdict = {"n_devices": 1, "rc": 0 if ok else 1, "ok": ok,
                   "skipped": False, "tail": tail,
                   "14_memadvisor": block}
    except Exception as e:  # noqa: BLE001 - verdict artifact, not a trace
        verdict = {"n_devices": 1, "rc": 1, "ok": False,
                   "skipped": False,
                   "tail": f"memadvisor_dryrun failed: {e!r}\n"}
    doc = json.dumps(verdict, indent=2)
    print(doc)
    with open(args.json, "w", encoding="utf-8") as f:
        f.write(doc + "\n")
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
