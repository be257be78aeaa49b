"""Reproducible cProfile harness for the 1000-request wire call.

Decomposes one ``get_rate_limits_wire`` call into the PERF.md §4.2
buckets and prints them as JSON, so host-glue regressions (or wins —
ISSUE 2's overlapped wave pipeline) are measurable with one command:

    JAX_PLATFORMS=cpu python tools/hostpath_prof.py [--reqs 1000]
        [--reps 20]

Buckets (exclusive/tottime, summed per call):

- ``device_step``   — jax/XLA dispatch, transfers, and the blocking
                      result fetch (everything under the jax stack)
- ``parse_pack``    — C wire parse, key hashing, pack_columns, wave
                      routing + packed-buffer fill (core/batch.py,
                      hashing.py, parallel/sharded.py host helpers)
- ``dispatch_future`` — dispatcher machinery: queue/future/threading
                      handoffs, wave telemetry
- ``response_build`` — response serialization back to wire bytes
- ``other``         — everything else (pb2, instance routing, ...)

The split is by profile-entry attribution, so inclusive callers (e.g.
``get_rate_limits_wire`` itself) land in ``other`` only for their OWN
exclusive time — the buckets sum to the total.
"""
from __future__ import annotations

import argparse
import cProfile
import json
import os
import pstats
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

NOW0 = 1_760_000_000_000


def _bucket_of(key) -> str:
    """Map one pstats entry key (file, line, name) to a §4.2 bucket."""
    filename, _line, name = key
    f = filename.replace("\\", "/")
    if "_native" in name or "build_rate_limit_resps" in name \
            or "build_responses_from_columns" in name \
            or "parse_get_rate_limits" in name \
            or "split_resp_items" in name:
        # C entry points: parse is ingest, builders are egress
        if "parse" in name or "split" in name:
            return "parse_pack"
        if "build" in name:
            return "response_build"
        return "parse_pack"
    if "/jax/" in f or "/jaxlib/" in f or "jax" in name.lower() \
            or "xla" in name.lower():
        return "device_step"
    if f.endswith("parallel/sharded.py") and name in (
            "_launch_arrays", "_finish_wave", "_launch_wave"):
        # the jitted step call is C-dispatched (no Python frame of its
        # own), so its time lands in the launching helper's exclusive
        # time — that IS the device dispatch+compute+fetch cost
        return "device_step"
    if f.endswith("dispatcher.py") or f.endswith("queue.py") \
            or f.endswith("threading.py") or "concurrent/futures" in f \
            or f.endswith("telemetry.py") or f.endswith("tracing.py"):
        return "dispatch_future"
    if f.endswith("core/batch.py") or f.endswith("hashing.py") \
            or (f.endswith("parallel/sharded.py")
                and name in ("_fill", "_build_waves", "join_calls",
                             "lay_out", "pack_wave_host",
                             "lease", "_return")):
        return "parse_pack"
    if f.endswith("metrics.py") or "prometheus" in f:
        return "dispatch_future"
    return "other"


BUCKETS = ("device_step", "parse_pack", "dispatch_future",
           "response_build", "other")


def profile_wire_calls(inst, datas, reps: int, now0: int = NOW0 + 500
                       ) -> dict:
    """Profile ``reps`` wire calls on a WARM instance; returns the
    per-call §4.2 breakdown dict (bench.py folds this into the
    6_service_path row as ``host_glue``)."""
    prof = cProfile.Profile()
    prof.enable()
    for r in range(reps):
        inst.get_rate_limits_wire(datas[r % len(datas)],
                                  now_ms=now0 + r)
    prof.disable()
    st = pstats.Stats(prof)
    sums = {b: 0.0 for b in BUCKETS}
    for key, (_cc, _nc, tottime, _ct, _callers) in st.stats.items():
        sums[_bucket_of(key)] += tottime
    total = sum(sums.values())
    out = {"reps": reps,
           "total_ms_per_call": round(total / reps * 1e3, 3)}
    out["buckets_ms_per_call"] = {
        b: round(sums[b] / reps * 1e3, 3) for b in BUCKETS}
    host = total - sums["device_step"]
    out["host_glue_ms_per_call"] = round(host / reps * 1e3, 3)
    return out


def _mk_instance(cache_size: int):
    from gubernator_tpu.config import Config
    from gubernator_tpu.instance import V1Instance
    from gubernator_tpu.parallel import make_mesh

    return V1Instance(Config(cache_size=cache_size, sweep_interval_ms=0),
                      mesh=make_mesh(n=1))


def _mk_datas(n_reqs: int, n_batches: int = 4):
    import numpy as np

    from gubernator_tpu.proto import gubernator_pb2 as pb
    from gubernator_tpu.types import RateLimitRequest
    from gubernator_tpu.wire import req_to_pb

    rng = np.random.default_rng(7)
    datas = []
    for _ in range(n_batches):
        m = pb.GetRateLimitsReq()
        m.requests.extend(
            req_to_pb(RateLimitRequest(
                name="prof", unique_key=f"k{int(k)}", hits=1,
                limit=100, duration=60_000))
            for k in rng.zipf(1.1, size=n_reqs) % 100_000)
        datas.append(m.SerializeToString())
    return datas


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--reqs", type=int, default=1000,
                    help="requests per wire call (default 1000)")
    ap.add_argument("--reps", type=int, default=20,
                    help="profiled calls (default 20)")
    ap.add_argument("--cache-size", type=int, default=1 << 16)
    args = ap.parse_args(argv)

    inst = _mk_instance(args.cache_size)
    try:
        datas = _mk_datas(args.reqs)
        # warm: compile both wave-bucket programs outside the profile
        if hasattr(inst.engine, "warmup"):
            inst.engine.warmup()
        inst.get_rate_limits_wire(datas[0], now_ms=NOW0)
        inst.get_rate_limits_wire(datas[1], now_ms=NOW0 + 1)
        out = profile_wire_calls(inst, datas, args.reps)
        out["reqs_per_call"] = args.reqs
        out["pipeline_depth"] = inst.dispatcher.debug_stats()[
            "pipeline_depth"]
        pool = getattr(inst.engine, "wave_pool", None)
        if pool is not None:
            out["buffer_pool"] = pool.stats()
        print(json.dumps(out))
    finally:
        inst.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
