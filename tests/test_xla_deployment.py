"""The deployment ``region1-10m-xla`` (``BENCHMARK.json``, cell
``r1x-zipf-b1000-sat``) end to end at its rehearsal size: a daemon with
``GUBER_ENGINE=xla``, the population's rows restored, 1000-request calls
over the raw-bytes gRPC front door, EVERY answer against the benchmark's
own plain token-bucket reference (which imports nothing of the program),
none of them by the pb2 lane."""
import time

import grpc
import numpy as np

from benchmark import run
from benchmark.algorithms import token_bucket as tb
from benchmark.harness import plugins, traffic as tr, wire
from gubernator_tpu.config import DaemonConfig
from gubernator_tpu.daemon import spawn_daemon
from gubernator_tpu.netutil import free_port

CELL = "r1x-zipf-b1000-sat"
SEED = 3100000021  # a seed whose 10M keys lose one at 8 probes
CALLS, PER_CALL = 9, 1000


def test_the_xla_deployment_answers_as_the_plain_reference(monkeypatch):
    cell = run.load_cell(CELL, rehearsal=True)
    cfg, mix = cell["config"], cell["traffic"]
    pop = cfg["populations"][mix["population"]]
    for name in ("GUBER_ENGINE", "GUBER_STEP_IMPL", "GUBER_WAVE_BUCKETS"):
        monkeypatch.delenv(name, raising=False)
    for name, value in cfg["env"].items():
        monkeypatch.setenv(name, value)
    addr = f"127.0.0.1:{free_port()}"
    daemon = spawn_daemon(DaemonConfig(
        grpc_listen_address=addr,
        http_listen_address=f"127.0.0.1:{free_port()}", **cfg["daemon"]))
    chan = grpc.insecure_channel(addr)
    try:
        inst = daemon.instance
        assert inst.serving_info["engine"] == cfg["engine"]  # /healthz
        v0 = (int(time.time()) + 86_400) * 1000
        with inst._engine_mu:
            placed = inst.engine.restore(tb.snapshot_columns(pop, SEED, v0))
        assert placed == pop["keys"]
        ref = tb.reference(pop)
        tb.seed_reference(ref, np.arange(pop["keys"]), pop, SEED, v0)
        tpl = wire.RequestTemplate(
            name=pop["name"], hits=pop["hits"], limit=pop["limit"],
            duration=pop["duration_ms"], **tb.request_fields(pop))
        draw = plugins.load("keys", mix["keys"]["dist"]).sample
        call = chan.unary_unary(wire.METHOD)
        rng = tr.caller_rng(SEED, 0)
        over = 0
        for c in range(CALLS):
            # 2.6 s apart: restored rows answer, expire, and re-open
            stamp = v0 + c * 2_600
            idx = draw(rng, mix["keys"], PER_CALL, pop["keys"])
            got = wire.decode_responses(
                call(tpl.call(tr.key_id(idx, SEED), stamp), timeout=120))
            want = ref.call(idx, stamp)
            assert got["errors"] == 0
            for f in ("status", "limit", "remaining", "reset_time"):
                assert (got[f] == want[f]).all(), (c, f)
            over += int((want["status"] == tb.OVER).sum())
        assert over > 0, "the stream has to cross the limit"
        lanes = {k: v for k, v in (
            line.rsplit(" ", 1) for line in
            inst.metrics.render().decode().splitlines()
            if line.startswith("gubernator_wire_lane_requests_total{"))}
        assert sum(float(v) for v in lanes.values()) == CALLS * PER_CALL
        assert not any("pb2" in k and float(v) for k, v in lanes.items())
    finally:
        chan.close()
        daemon.close()
