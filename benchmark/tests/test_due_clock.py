"""The open loop's clock starts when a call was DUE: a server that
stalls once must lengthen the latency of the calls behind the stall,
and the generator must go on sending on time while it lasts."""
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness import gen, traffic, wire  # noqa: E402

STALL_S, STALL_AT_CALL = 0.6, 5


def fake_server():
    """One worker thread: a stalled call holds up every call behind it."""
    import grpc

    seen = []

    def handler(request: bytes, context) -> bytes:
        seen.append(time.monotonic())
        if len(seen) == STALL_AT_CALL:
            time.sleep(STALL_S)
        return b""  # an empty GetRateLimitsResp

    server = grpc.server(ThreadPoolExecutor(max_workers=1))
    service, method = wire.METHOD.strip("/").split("/")
    server.add_generic_rpc_handlers((grpc.method_handlers_generic_handler(
        service, {method: grpc.unary_unary_rpc_method_handler(handler)}),))
    port = server.add_insecure_port("127.0.0.1:0")
    server.start()
    return server, f"127.0.0.1:{port}"


def test_a_stall_is_charged_to_the_calls_behind_it(tmp_path):
    server, addr = fake_server()
    try:
        spec = {"index": 0, "seed": 9, "population": {
            "name": "b", "keys": 100, "hits": 1, "limit": 5,
            "duration_ms": 1000},
            "traffic": {"loop": "open", "callers": 2, "generators": 1,
                        "requests_per_call": 3, "population": "resident",
                        "keys": {"dist": "zipf", "a": 1.1},
                        "rate_calls_per_s": 40, "arrivals": "grid"}}
        g = gen.Generator(spec)
        g.connect(addr)
        out = str(tmp_path / "w.npz")
        start = time.monotonic() + 0.2
        g.window(start_at=start, seconds=2.0, v0=1_900_000_000_000, out=out)
        g.close()
    finally:
        server.stop(0)
    rec = dict(np.load(out))
    assert rec["ok"].all() and len(rec["ok"]) == 80
    late = rec["send"] - rec["due"]
    assert np.percentile(late, 99) < 0.05, "the generator waited on the server"
    lat = rec["done"] - rec["due"]
    stalled_due = rec["due"][STALL_AT_CALL - 1]
    behind = (rec["due"] > stalled_due) & (rec["due"] < stalled_due + 0.3)
    ahead = rec["due"] < stalled_due - 0.05
    assert lat[ahead].max() < 0.1
    # a call due 0.3 s into a 0.6 s stall still waits ~0.3 s for it
    assert lat[behind].min() > 0.25, lat[behind]


def test_every_seed_offers_the_same_work():
    t = {"callers": 8, "rate_calls_per_s": 50, "arrivals": "poisson"}
    a, _ = traffic.open_schedule(t, 40.0, 1)
    b, _ = traffic.open_schedule(t, 40.0, 2_200_000_123)
    assert len(a) == len(b) == 2000 and a.max() < 40 and b.max() < 40
    gaps = lambda d: np.sort(np.diff(np.r_[0.0, d, 40.0]))  # noqa: E731
    assert np.allclose(gaps(a), gaps(b)) and not np.allclose(a, b)
