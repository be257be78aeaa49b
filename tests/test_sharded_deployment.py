"""The deployment ``region4-10m`` (``BENCHMARK.json``, cell
``r4-zipf-b1000-sat``) at its rehearsal size on four of conftest's
virtual CPU devices: a mesh daemon (``global_mode: mesh``) whose table is
sharded over the mesh, the population's rows restored onto the four
shards, 1000-request LOCAL Zipf(1.1) calls over the raw-bytes gRPC front
door, EVERY answer against the benchmark's own plain token-bucket
reference (which imports nothing of the program), the first of them from
restored state, all by the numpy lane ``wire_local`` (1000 rows are
more than the rehearsal's bucket: the fused ingest's size gate; one
100-row call then rides the fused lane, ISSUE 38) and the sorted
route.  Beside it: a one-shard and a four-shard engine answer one seeded
Zipf stream alike whichever bucket its waves ride, and the shard route's
counters hold to a hand-made wave."""
import time

import grpc
import numpy as np
import pytest

from benchmark import run
from benchmark.algorithms import token_bucket as tb
from benchmark.harness import plugins, rows as bench_rows, traffic as tr, wire
from gubernator_tpu.config import DaemonConfig
from gubernator_tpu.core.batch import pack_columns
from gubernator_tpu.daemon import spawn_daemon
from gubernator_tpu.hashing import shard_of
from gubernator_tpu.metrics import Metrics
from gubernator_tpu.netutil import free_port
from gubernator_tpu.parallel import ShardedEngine, make_mesh, sharded
from gubernator_tpu.parallel.pallas_engine import PallasServingEngine

CELL = "r4-zipf-b1000-sat"
SEED = 3300000021
CALLS, PER_CALL = 9, 1000
NOW = 1_790_000_000_000


def scrape(inst, prefix: str) -> dict:
    return {k: float(v) for k, v in (
        line.rsplit(" ", 1) for line in
        inst.metrics.render().decode().splitlines()
        if line.startswith(prefix))}


def test_the_sharded_deployment_answers_as_the_plain_reference(
        monkeypatch, cpu_mesh):
    cell = run.load_cell(CELL, rehearsal=True)
    cfg, mix = cell["config"], cell["traffic"]
    pop = cfg["populations"][mix["population"]]
    assert cell["chips"] == cfg["chips"] == 4
    for name in ("GUBER_ENGINE", "GUBER_STEP_IMPL", "GUBER_WAVE_BUCKETS",
                 "GUBER_GLOBAL_MODE"):
        monkeypatch.delenv(name, raising=False)
    for name, value in cfg["env"].items():
        monkeypatch.setenv(name, value)
    addr = f"127.0.0.1:{free_port()}"
    daemon = spawn_daemon(DaemonConfig(
        grpc_listen_address=addr,
        http_listen_address=f"127.0.0.1:{free_port()}", **cfg["daemon"]),
        mesh=cpu_mesh)
    chan = grpc.insecure_channel(addr)
    try:
        inst = daemon.instance
        eng = inst.engine
        assert inst.serving_info["device_count"] == eng.n == 4
        assert inst._global_mode == "mesh"  # cell 4's daemon
        v0 = (int(time.time()) + 86_400) * 1000
        with inst._engine_mu:
            placed = eng.restore(tb.snapshot_columns(pop, SEED, v0))
        assert placed == pop["keys"]
        # every shard holds its part of the population
        per_shard = np.bincount(shard_of(bench_rows.key_hash(
            pop["name"], tr.key_id(np.arange(pop["keys"]), SEED)), eng.n))
        assert len(per_shard) == 4 and per_shard.min() > pop["keys"] // 8
        ref = tb.reference(pop)
        tb.seed_reference(ref, np.arange(pop["keys"]), pop, SEED, v0)
        fresh = tb.reference(pop)  # what an EMPTY table would answer
        tpl = wire.RequestTemplate(
            name=pop["name"], hits=pop["hits"], limit=pop["limit"],
            duration=pop["duration_ms"], **tb.request_fields(pop))
        draw = plugins.load("keys", mix["keys"]["dist"]).sample
        call = chan.unary_unary(wire.METHOD)
        rng = tr.caller_rng(SEED, 0)
        count = lambda name: scrape(inst, name + "_total").get(  # noqa: E731
            name + "_total", 0.0)
        counters = ("gubernator_wave_slots", "gubernator_wave_routed_rows",
                    "gubernator_wave_densest_shard_rows")
        before = [count(n) for n in counters]  # the daemon's warm-up waves
        over = 0
        for c in range(CALLS):
            # 2.6 s apart: restored rows answer, expire, and re-open
            stamp = v0 + c * 2_600
            idx = draw(rng, mix["keys"], PER_CALL, pop["keys"])
            got = wire.decode_responses(
                call(tpl.call(tr.key_id(idx, SEED), stamp), timeout=300))
            want = ref.call(idx, stamp)
            assert got["errors"] == 0
            for f in ("status", "limit", "remaining", "reset_time"):
                assert (got[f] == want[f]).all(), (c, f)
            if c == 0:  # answered from the restored rows, not from new ones
                empty = fresh.call(idx, stamp)
                assert (got["remaining"] != empty["remaining"]).any()
                assert (got["reset_time"] != empty["reset_time"]).any()
            over += int((want["status"] == tb.OVER).sum())
        assert over > 0, "the stream has to cross the limit"
        lanes = scrape(inst, "gubernator_wire_lane_requests_total{")
        assert lanes == {
            'gubernator_wire_lane_requests_total{lane="wire_local"}':
            CALLS * PER_CALL}
        # every wave went the sorted route, and what it cost is counted
        routes = scrape(inst, "gubernator_wave_route_total{")
        waves = routes['gubernator_wave_route_total{route="sorted"}']
        assert waves >= CALLS
        assert not routes.get(
            'gubernator_wave_route_total{route="identity"}')
        slots, routed, densest = (count(n) - b
                                  for n, b in zip(counters, before))
        assert routed == CALLS * PER_CALL
        assert slots % (4 * eng.wave_buckets[0]) == 0 and slots >= routed
        assert routed / 4 < densest <= routed  # skew in (1, 4]
        # 1000 rows a call are more than the rehearsal's largest bucket
        # (128 rows a shard), so the fused C++ ingest declined every one
        # of them — its size gate is per call, on any shard count — and
        # each was packed in its handler (`local.pack`) and split
        pack = scrape(inst, 'gubernator_phase_duration_count{phase="local.pack"')
        assert sum(pack.values()) == CALLS
        cpu = scrape(inst, "gubernator_phase_cpu_wall_seconds_total"
                           '{phase="local.pack"')
        assert sum(cpu.values()) > 0
        assert count("gubernator_wire_fused_requests") == 0
        # a call that fits the bucket rides the fused lane on the mesh
        # (ISSUE 38) and is answered from the same rows
        stamp = v0 + CALLS * 2_600
        idx = draw(rng, mix["keys"], 100, pop["keys"])
        got = wire.decode_responses(
            call(tpl.call(tr.key_id(idx, SEED), stamp), timeout=300))
        want = ref.call(idx, stamp)
        for f in ("status", "limit", "remaining", "reset_time"):
            assert (got[f] == want[f]).all(), f
        assert count("gubernator_wire_fused_requests") == 100
        assert sum(scrape(inst, 'gubernator_phase_duration_count'
                                '{phase="local.pack"').values()) == CALLS
    finally:
        chan.close()
        daemon.close()


# ---- one shard and four shards answer one stream alike ------------------

BUCKETS = (64, 512)
KEYS, LIMIT = 3000, 100
#: rows a call → what its densest of four shards does to the ladder
CASES = {"small_bucket": 120, "large_bucket": 700, "overflow_splits": 2300}


@pytest.fixture(scope="module")
def pair(cpu_mesh):
    """(one-shard engine, four-shard engine) over the Pallas table, the
    deployment's, with the same whole capacity."""
    return (PallasServingEngine(make_mesh(n=1), capacity_per_shard=1 << 14,
                                batch_per_shard=64, wave_buckets=BUCKETS),
            PallasServingEngine(cpu_mesh, capacity_per_shard=1 << 12,
                                batch_per_shard=64, wave_buckets=BUCKETS))


@pytest.mark.parametrize("case", CASES)
def test_one_shard_and_four_shards_answer_one_zipf_stream_alike(pair, case):
    one, four = pair
    per_call = CASES[case]
    seed = 3300000100 + per_call
    rng = tr.caller_rng(seed, 0)
    name = "p" + case  # keys of its own: the engines are shared
    draw = plugins.load("keys", "zipf").sample
    for c in range(4):
        now = NOW + c * 2_600
        idx = draw(rng, {"a": 1.1}, per_call, KEYS)
        kh = bench_rows.key_hash(name, tr.key_id(idx, seed))
        n = len(kh)
        col = lambda v: np.full(n, v, np.int64)  # noqa: E731
        cols = []
        for eng in (one, four):
            b, errs = pack_columns(kh, col(1), col(LIMIT), col(10_000),
                                   np.zeros(n, np.int32),
                                   np.zeros(n, np.int32), col(0), now)
            assert not errs
            cols.append(eng.check_packed(b, kh, now))
        for a, b in zip(*cols):
            assert a.dtype == b.dtype and a.tobytes() == b.tobytes()
        assert not cols[1][4].any()  # no `table full`
        # the case is what its name says, on the four-shard engine
        plan = four._build_waves(kh, np.arange(n))
        densest = int(np.bincount(shard_of(kh, 4)).max())
        if case == "small_bucket":
            assert [(w[2], w[3]) for w in plan] == [(BUCKETS[0], densest)]
        elif case == "large_bucket":
            assert BUCKETS[0] < densest <= BUCKETS[1]
            assert [(w[2], w[3]) for w in plan] == [(BUCKETS[1], densest)]
        else:
            assert densest > BUCKETS[1] and len(plan) == 2
            assert plan[0][2:] == (BUCKETS[1], BUCKETS[1])
            # the hottest key's rows lie in BOTH device waves and are
            # applied in arrival order across them: `remaining` counts
            # down once a row, whichever wave carried it
            hot = np.flatnonzero(idx == np.bincount(idx).argmax())
            both = [np.isin(hot, w[0]).any() for w in plan]
            assert all(both)
            if c == 0:
                rem = cols[1][2][hot]
                want = np.maximum(LIMIT - 1 - np.arange(len(hot)), 0)
                assert rem.tolist() == want.tolist()
                assert len(hot) > LIMIT  # and it crossed its limit


# ---- the counters, on a hand-made wave ----------------------------------

def keys_on(shard: int, count: int, n: int, salt: int) -> np.ndarray:
    """``count`` distinct key hashes that ``shard_of`` files under
    ``shard`` of ``n``."""
    h = np.arange(1, 4096, dtype=np.uint64) * np.uint64(
        0x9E3779B97F4A7C15) + np.uint64(salt)
    return h[shard_of(h, n) == shard][:count]


def counted(eng) -> tuple:
    m = eng.metrics_ref
    return tuple(int(c._value.get()) for c in (
        m.wave_slots, m.wave_routed_rows, m.wave_densest_shard_rows,
        m.wave_route.labels(route="sorted"),
        m.wave_route.labels(route="identity"), m.wave_native_route))


def check(eng, kh):
    n = len(kh)
    col = lambda v: np.full(n, v, np.int64)  # noqa: E731
    b, _ = pack_columns(kh, col(1), col(100), col(10_000),
                        np.zeros(n, np.int32), np.zeros(n, np.int32),
                        col(0), NOW)
    before = counted(eng)
    eng.check_packed(b, kh, NOW)
    return tuple(a - b for a, b in zip(counted(eng), before))


def test_the_route_counters_hold_to_a_hand_made_wave(cpu_mesh, monkeypatch):
    four = ShardedEngine(cpu_mesh, capacity_per_shard=1 << 10,
                         batch_per_shard=16, wave_buckets=(16, 64))
    four.metrics_ref = Metrics()
    # 10 + 3 + 2 + 1 rows on shards 0..3: the densest fits the small
    # bucket — 4 × 16 slots for 16 rows
    kh = np.concatenate([keys_on(s, c, 4, 7) for s, c in
                         enumerate((10, 3, 2, 1))])
    # — and the C++ extension planned and filled it (ISSUE 36)
    assert check(four, kh) == (4 * 16, 16, 10, 1, 0, 1)
    # 40 on shard 2: the large bucket — 4 × 64 slots for 46 rows
    kh = np.concatenate([keys_on(s, c, 4, 11) for s, c in
                         enumerate((3, 2, 40, 1))])
    assert check(four, kh) == (4 * 64, 46, 40, 1, 0, 1)
    # 70 on shard 1 overflow the largest bucket: a full large wave, then
    # the 6 rows left in the small one
    kh = np.concatenate([keys_on(s, c, 4, 13) for s, c in
                         enumerate((2, 70, 1, 1))])
    assert check(four, kh) == (4 * 64 + 4 * 16, 74, 64 + 6, 2, 0, 2)
    # one shard, clocks in order: the identity route — the lease is the
    # bucket, the rows are their shard's
    one = ShardedEngine(make_mesh(n=1), capacity_per_shard=1 << 10,
                        batch_per_shard=16, wave_buckets=(16, 64))
    one.metrics_ref = Metrics()
    assert check(one, keys_on(0, 10, 1, 17)) == (16, 10, 10, 0, 1, 0)
    assert check(one, keys_on(0, 40, 1, 19)) == (64, 40, 40, 0, 1, 0)
    # a checkout without the extension routes the same waves in numpy:
    # sorted, not native
    monkeypatch.setattr(sharded, "_wire_native", None)
    assert check(four, kh) == (4 * 64 + 4 * 16, 74, 64 + 6, 2, 0, 0)
