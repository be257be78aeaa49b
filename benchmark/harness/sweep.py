"""``run.py --sweep``: the builder's tool that settles the open cell
before the manifest is fixed.  One set-up, then windows of the open mix
at several rates under both arrival processes; for each window (as long
as ``--seconds``), and for its first half and three quarters, the latency
statistics from due time.  Run it as
several processes with several seeds and compare ACROSS processes: the
run-to-run regime, not the sample inside one run, is what a bound has
to cover.  Prints one JSON object per window and a table at the end.
"""
from __future__ import annotations

import json
import os

import numpy as np

PREFIX_SHARES = (0.5, 0.75, 1.0)
RATES = (30, 50, 70)
ARRIVALS = ("poisson", "grid")


def _stats(rec: dict, start_at: float, upto: float) -> dict:
    sel = (rec["ok"] & (rec["answered"] == rec["n"])
           & (rec["due"] < start_at + upto))
    lat = 1000.0 * (rec["done"][sel] - rec["due"][sel])
    late = 1000.0 * (rec["send"][sel] - rec["due"][sel])
    p = lambda q: float(np.percentile(lat, q))  # noqa: E731
    return {"calls": int(sel.sum()), "mean": float(lat.mean()),
            "p50": p(50), "p90": p(90), "p95": p(95), "p99": p(99),
            "gen_late_p99_ms": float(np.percentile(late, 99))}


def run(args, runpy) -> int:
    from benchmark.harness import check
    from benchmark.harness.scrape import hist_mean

    cell = runpy.load_cell(args.workload, args.cpu_rehearsal)
    if cell["traffic"]["loop"] != "open":
        raise SystemExit("--sweep wants an open-loop workload")
    seconds = args.seconds if not args.cpu_rehearsal else 4.0
    prefixes = [round(seconds * share) for share in PREFIX_SHARES]
    rates = RATES if not args.cpu_rehearsal else (10, 20)
    c, devices = runpy.set_up(args, cell)
    out = []
    try:
        setup_s = None
        v0 = c.v0
        dest = os.path.join(runpy.REPO, "chiprun_out")
        os.makedirs(dest, exist_ok=True)
        for k, (arrivals, rate) in enumerate(
                (a, r) for r in rates for a in ARRIVALS):
            w = c.window(seconds, f"s{k}", v0, override={
                "rate_calls_per_s": rate, "arrivals": arrivals})
            setup_s = setup_s or w["start_at"] - runpy.T_START
            pop = dict(c.pop, restore=c.pop.get("restore") and k == 0)
            win = check.window_violations(check.expand(w["rec"]), pop,
                                          c.seed, v0)
            row = {
                "seed": args.seed, "arrivals": arrivals, "rate": rate,
                "violations": win["violations"],
                "failed": int((~w["rec"]["ok"]).sum()),
                "compiles": w["compiles"], "stalls": w["stalls"],
                "rows_per_wave": hist_mean(
                    w["m0"], w["m1"], "gubernator_dispatcher_wave_size"),
                "wave_ms": 1000.0 * (hist_mean(
                    w["m0"], w["m1"],
                    "gubernator_dispatcher_wave_duration") or 0.0),
                "queue_wait_ms": 1000.0 * (hist_mean(
                    w["m0"], w["m1"],
                    "gubernator_dispatcher_queue_wait") or 0.0),
                **{f"first{p}s": _stats(w["rec"], w["start_at"], p)
                   for p in prefixes},
            }
            print(json.dumps(row), flush=True)
            out.append(row)
            # the next window starts after this one's buckets expired
            v0 += int(seconds * 1000) + 3 * c.pop["duration_ms"]
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices)}
    finally:
        c.close()
    with open(os.path.join(dest, f"sweep_{args.seed}.json"), "w") as f:
        json.dump({"device": device, "setup_s": setup_s, "rows": out}, f)
    print(json.dumps({"sweep": "done", "seed": args.seed, "device": device,
                      "setup_s": setup_s, "windows": len(out)}), flush=True)
    return 0
