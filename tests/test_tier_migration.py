"""ONE migration pass a wave (ISSUE 46): ``TierController.migrate`` moves
all the keys a wave's admission handed over — their device buckets
fetched once, promotees placed and victims taken out on that image, the
image written back once, the cold store's side of it one batch call each
way — where a key at a time cost five blocking device round trips.

(a) the pass against the sequence of one-key migrations it replaces, on
both engines and both cold stores; (b) the waking-tenant deployment
(``region1-tier-100m-wake``, cell ``r1-drift-100m``) at its rehearsal
size over the raw-bytes gRPC door, answer for answer against the plain
token-bucket reference, and the non-token branches against ``oracle.py``
through a demotion and a promotion; (c) a wave launched BEFORE a pass
that demotes one of its keys and promotes another; (d) no compile in a
pass after ``warmup``; (e) the ``zipf_drift`` key draw; (f) the two new
readers; (g) the listing guard PR 45 failed; the fault controls on the
new cell."""
import json
import os
import subprocess
import sys
import threading
import time

import grpc
import numpy as np
import pytest

from benchmark import run
from benchmark.algorithms import token_bucket as tb
from benchmark.harness import plugins, rows as rows_mod, traffic as tr, wire
from gubernator_tpu.config import DaemonConfig
from gubernator_tpu.daemon import spawn_daemon
from gubernator_tpu.hashing import hash_request_keys
from gubernator_tpu.netutil import free_port
from gubernator_tpu.ops import pallas_step as ps
from gubernator_tpu.oracle import Oracle
from gubernator_tpu.parallel import ShardedEngine, make_mesh
from gubernator_tpu.parallel.pallas_engine import PallasServingEngine
from gubernator_tpu.parallel.sharded import ROW_OP_SIZES, padded
from gubernator_tpu.tiering import MIGRATE_MAX, ROW_COLS, TierController
from gubernator_tpu.types import (Algorithm, Behavior, GregorianDuration,
                                  RateLimitRequest)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL, CHURN = "r1-drift-100m", "r1-churn-100m"
SEED = 4100000021
NEW_READERS = ("tier_migrate_ms", "tier_rows_per_migration")
NOW = 1_790_000_000_000


# ---- (a) the pass against the sequence of one-key migrations -------------

class _Fault(Exception):
    pass


class _Rig:
    """A tiny full table with a tier bound: a rank feed the test writes,
    a fault that fires at the n-th call of a point, a pin set."""

    def __init__(self, kind: str):
        mesh = make_mesh(n=1)
        if kind == "pallas":  # 2 buckets of 128 slots
            self.eng = PallasServingEngine(mesh, capacity_per_shard=256,
                                           batch_per_shard=128)
            self.window = ps.SLOTS
        else:  # 64 rows, a probe window of 16
            self.eng = ShardedEngine(mesh, capacity_per_shard=64,
                                     batch_per_shard=64)
            self.window = 16
        self.ranks: dict = {}
        self.fire: dict = {}   # point -> the call (1-based) that raises
        self.calls: dict = {}
        self.pinned: set = set()
        self.tc = TierController(
            self.eng, rank_fn=lambda kh: self.ranks.get(int(kh), 0),
            rank_batch=lambda khs: np.array(
                [self.ranks.get(int(k), 0) for k in khs], np.int64),
            promote_threshold=8, fault=self._fault,
            skip_victim=lambda kh: kh in self.pinned)
        n = 256 if kind == "pallas" else 64
        self.resident = np.arange(1000, 1000 + n, dtype=np.uint64)
        assert self.eng.upsert_rows(self.resident,
                                    _cols(self.resident)) == n

    def _fault(self, point: str) -> None:
        n = self.calls[point] = self.calls.get(point, 0) + 1
        if self.fire.get(point) == n:
            raise _Fault(point)

    def put_cold(self, keys, rows=None) -> None:
        keys = np.asarray(keys, np.uint64)
        with self.tc._mu:
            self.tc._store.put_batch(
                keys, _rows(keys) if rows is None else rows)

    def tiers(self) -> tuple:
        """(device rows by key, cold rows by key)."""
        dev = self.eng.snapshot()
        live = np.asarray(dev["key"]) != 0
        vals = np.stack([np.asarray(dev[f], np.int64)[live]
                         for f in ROW_COLS], axis=1)
        device = {int(k): tuple(r) for k, r in zip(
            np.asarray(dev["key"])[live].tolist(), vals.tolist())}
        keys, rows = self.tc._store.snapshot()
        cold = {int(k): tuple(int(v) for v in r)
                for k, r in zip(keys, rows)}
        return device, cold


def _rows(keys: np.ndarray) -> np.ndarray:
    """A distinct live token row a key (burst = limit: the bucket table
    keeps no burst column and gives ``limit`` back)."""
    k = np.asarray(keys, np.int64)
    rows = np.zeros((len(k), len(ROW_COLS)), np.int64)
    rows[:, 1] = 100 + k % 7            # limit
    rows[:, 2] = rows[:, 3] = 10_000    # duration, eff_ms
    rows[:, 4] = rows[:, 1]             # burst
    rows[:, 5] = k % 50                 # remaining
    rows[:, 6] = NOW - 10 ** 6 + k      # t_ms: before any request here
    rows[:, 7] = NOW + 10 ** 7 + k      # expire_at: live throughout
    return rows


def _cols(keys: np.ndarray) -> dict:
    rows = _rows(keys)
    return {f: rows[:, j].astype(np.int32 if f == "meta" else np.int64)
            for j, f in enumerate(ROW_COLS)}


def _scenario(name: str, rig: _Rig) -> tuple:
    """(keys of the pass, their ranks, what the pass must report) — the
    same preparation on both rigs of a pair.  Promotees are even keys
    (the bucket engine's bucket 0; on the 64-row table every window is
    full anyway) with equal ranks, so that the one-key sequence never
    evicts a key it has just promoted."""
    res = rig.resident
    p = np.array([5000, 5002, 5004], np.uint64)
    want = {"promotions": 3, "demotions": 3, "migrations_aborted": 0}
    if name == "share_bucket":
        # three promotees into one full window: its three coldest go
        rig.ranks.update({int(k): 5 for k in res[::3]})
    elif name == "free_slot":
        gone = res[res % 2 == 0][:1] if rig.window == ps.SLOTS else res[8:9]
        rig.eng.remove_rows(gone)
        p = p[:1] if rig.window == ps.SLOTS else np.array([gone[0] + 64],
                                                           np.uint64)
        want.update(promotions=1, demotions=0)
    elif name == "full_no_colder":
        rig.ranks.update({int(k): 10 for k in res})
        want.update(promotions=0, demotions=0)
    elif name == "duplicate":
        p = np.array([5000, 5002, 5000, 5002], np.uint64)
        want.update(promotions=2, demotions=2)
    elif name == "out_of_domain":
        pass  # the row is written below
    elif name == "pinned_victim":
        # the coldest of every window is pinned: the next coldest goes
        rig.ranks.update({int(k): 3 for k in res})
        for k in res[:20]:
            rig.ranks[int(k)] = 0
            rig.pinned.add(int(k))
        for k in res[20:40]:
            rig.ranks[int(k)] = 1
    elif name == "fault_promote":
        rig.fire["tier_promote"] = 2
        want.update(promotions=2, demotions=2, migrations_aborted=1)
    elif name == "fault_demote":
        rig.fire["tier_demote"] = 2
        want.update(promotions=2, demotions=2, migrations_aborted=1)
    else:
        raise AssertionError(name)
    cold = np.unique(p)
    rows = _rows(cold)
    if name == "out_of_domain":
        rows[1, 1] = rows[1, 4] = ps.VALUE_BOUND + 5  # limit, burst
        if rig.window == ps.SLOTS:  # outside the kernel's words
            want.update(promotions=2, demotions=2)
    rig.put_cold(cold, rows)
    for k in cold:
        rig.ranks[int(k)] = 10
    return p, np.full(len(p), 10, np.int64), want


SCENARIOS = ("share_bucket", "free_slot", "full_no_colder", "duplicate",
             "out_of_domain", "pinned_victim", "fault_promote",
             "fault_demote")


@pytest.mark.parametrize("scenario", SCENARIOS)
@pytest.mark.parametrize("native", ["1", "0"])
@pytest.mark.parametrize("kind", ["pallas", "xla"])
def test_the_pass_is_the_sequence_of_one_key_migrations(monkeypatch, kind,
                                                        native, scenario):
    monkeypatch.setenv("GUBER_TIER_NATIVE", native)
    one, seq = _Rig(kind), _Rig(kind)
    assert one.tc.stats()["native"] == (native == "1")
    khs, ranks, want = _scenario(scenario, one)
    _scenario(scenario, seq)
    before_dev, before_cold = one.tiers()
    got = one.tc.migrate(one.eng, khs, ranks)
    for kh, r in zip(khs.tolist(), ranks.tolist()):
        seq.tc.promote(seq.eng, kh, r)
    st = one.tc.stats()
    assert {k: st[k] for k in want} == want and got == want["promotions"]
    assert {k: seq.tc.stats()[k] for k in want} == want
    dev, cold = one.tiers()
    assert (dev, cold) == seq.tiers()
    # exactly one tier a key, every row verbatim
    assert not set(dev) & set(cold)
    assert {**dev, **cold} == {**before_dev, **before_cold}
    moved_up = set(before_cold) - set(cold)
    moved_down = set(before_dev) - set(dev)
    assert len(moved_up) == want["promotions"] \
        and len(moved_down) == want["demotions"]
    assert not moved_down & one.pinned
    if scenario == "pinned_victim":
        assert all(one.ranks[k] == 1 for k in moved_down)
    if scenario == "fault_promote":
        assert 5002 in cold and one.calls["tier_promote"] == 3
    if scenario == "fault_demote":
        assert sum(k in cold for k in (5000, 5002, 5004)) == 1
    if scenario == "out_of_domain":
        assert (5002 in cold) == (kind == "pallas")


def test_one_key_demote_is_the_eviction_half_of_a_pass():
    rig = _Rig("pallas")
    victim = int(rig.resident[5])
    dev0, _ = rig.tiers()
    assert rig.tc.demote(rig.eng, victim)
    dev, cold = rig.tiers()
    assert cold == {victim: dev0[victim]} and victim not in dev
    assert not rig.tc.demote(rig.eng, victim)  # no longer on the device
    rig.fire["tier_demote"] = rig.calls.get("tier_demote", 0) + 1
    assert not rig.tc.demote(rig.eng, int(rig.resident[6]))
    st = rig.tc.stats()
    assert (st["demotions"], st["migrations_aborted"]) == (1, 1)


def test_a_pass_moves_at_most_its_bound_the_hottest_first():
    """``_admit`` over more admissible keys than ``MIGRATE_MAX``: the
    hottest go, in order of service; the rest are counted as put off and
    stay cold."""
    class _E:
        tier = None

    n = MIGRATE_MAX + 40
    khs = np.arange(1, n + 1, dtype=np.uint64)
    ranks = np.full(n, 9, np.int64)
    ranks[::7] = 50  # hotter: these must be in the pass
    passes = []
    tc = TierController(_E(), rank_fn=lambda kh: 0,
                        rank_batch=lambda ks: ranks)
    tc.migrate = lambda engine, k, r: passes.append((k.copy(), r.copy()))
    tc._admit(_E(), khs)
    (k, r), = passes
    assert len(k) == MIGRATE_MAX == ROW_OP_SIZES[-1]
    assert (np.diff(k.astype(np.int64)) > 0).all()  # order of service
    assert set(khs[::7].tolist()) <= set(k.tolist())
    assert tc.stats()["admissions_deferred"] == 40


# ---- (b) the deployment under the moving hot set -------------------------

def _cell(name=CELL):
    cell = run.load_cell(name, rehearsal=True)
    cfg, mix = cell["config"], cell["traffic"]
    return cell, cfg, mix, cfg["populations"][mix["population"]]


@pytest.mark.parametrize("native", ["1", "0"])
def test_the_waking_tenant_deployment_answers_as_the_plain_reference(
        monkeypatch, native):
    _, cfg, mix, pop = _cell()
    for name in [k for k in os.environ if k.startswith("GUBER_")]:
        monkeypatch.delenv(name)
    for name, value in cfg["env"].items():
        monkeypatch.setenv(name, value)
    monkeypatch.setenv("GUBER_TIER_NATIVE", native)
    addr = f"127.0.0.1:{free_port()}"
    daemon = spawn_daemon(DaemonConfig(
        grpc_listen_address=addr,
        http_listen_address=f"127.0.0.1:{free_port()}", **cfg["daemon"]),
        mesh=make_mesh(n=1))  # the cell's one chip: conftest shows eight
    chan = grpc.insecure_channel(addr)
    try:
        inst = daemon.instance
        tier = inst._tier
        v0 = (int(time.time()) + 86_400) * 1000
        snap = tb.snapshot_columns(pop, SEED, v0)
        with inst._engine_mu:
            assert inst.engine.restore(snap) == pop["keys"]
        cold0 = tier.resident_mask(snap["key"])
        keys = mix["keys"]
        # the drift's blocks are keys the restore left on the HOST
        woken = np.arange(keys["start"] + 1,
                          keys["start"] + 3 * keys["stride"] + 1)
        assert cold0[woken].mean() > 0.9
        ref = tb.reference(pop)
        tb.seed_reference(ref, np.arange(pop["keys"]), pop, SEED, v0)
        tpl = wire.RequestTemplate(
            name=pop["name"], hits=pop["hits"], limit=pop["limit"],
            duration=pop["duration_ms"], **tb.request_fields(pop))
        draw = plugins.load("keys", keys["dist"]).sample
        call = chan.unary_unary(wire.METHOD)
        rng = tr.caller_rng(SEED, 0)
        held = {int(k) for k in snap["key"]}
        for c in range(60):
            stamp = v0 + c * 150  # 9 s in all: inside the rows' lifetime
            idx = draw(rng, keys, 50, pop["keys"])
            got = wire.decode_responses(
                call(tpl.call(tr.key_id(idx, SEED), stamp), timeout=300))
            want = ref.call(idx, stamp)
            assert got["errors"] == 0
            for f in ("status", "limit", "remaining", "reset_time"):
                assert (got[f] == want[f]).all(), (c, f)
            # after every wave: the tiers are disjoint, and together
            # hold exactly the keys the reference does
            held |= {int(k) for k in rows_mod.key_hash(
                pop["name"], tr.key_id(idx, SEED))}
            with inst._engine_mu:
                dev = np.asarray(inst.engine.snapshot()["key"])
                cold, _ = tier._store.snapshot()
            dev = {int(k) for k in dev if k}
            cold = {int(k) for k in cold}
            assert not dev & cold, (c, len(dev & cold))
            # (the one key over is the daemon's own start-up request's)
            assert held <= dev | cold and len((dev | cold) - held) <= 1, \
                (c, len(held - dev - cold), len((dev | cold) - held))
            time.sleep(0.03)  # the sketch folds on its own thread
        st = tier.stats()
        assert st["promotions"] > 0 and st["demotions"] > 0, st
        assert st["migrations_aborted"] == 0
        # a woken key was promoted: it is on the device now
        assert (~tier.resident_mask(snap["key"][woken])).any()
        m = {k: float(v) for k, v in (
            line.rsplit(" ", 1) for line in
            inst.metrics.render().decode().splitlines()
            if line and not line.startswith("#"))}
        assert m["gubernator_tier_promotions_total"] == st["promotions"]
        assert m["gubernator_tier_demotions_total"] == st["demotions"]
        passes = m['gubernator_phase_duration_count{phase="tier.migrate"}']
        assert 0 < passes <= st["promotions"]
        for p in ("tier.fetch", "tier.write"):
            assert 0 < m[f'gubernator_phase_duration_count{{phase="{p}"}}'] \
                <= passes
        assert m.get("gubernator_table_full_rows_total", 0.0) == 0.0
    finally:
        chan.close()
        daemon.close()


def _req(kind: str, hits: int, now: int) -> RateLimitRequest:
    kw = dict(name="mig", unique_key=kind, hits=hits, limit=10,
              duration=60_000, created_at=now)
    if kind == "leaky":
        kw.update(algorithm=Algorithm.LEAKY_BUCKET, burst=10)
    elif kind == "reset_remaining":
        kw.update(behavior=Behavior.RESET_REMAINING if hits == 3 else 0)
    elif kind == "drain_over_limit":
        kw.update(behavior=Behavior.DRAIN_OVER_LIMIT)
    elif kind == "gregorian":
        kw.update(behavior=Behavior.DURATION_IS_GREGORIAN,
                  duration=int(GregorianDuration.HOURS))
    return RateLimitRequest(**kw)


@pytest.mark.parametrize("kind", ["leaky", "reset_remaining",
                                  "drain_over_limit", "gregorian"])
@pytest.mark.parametrize("engine", ["pallas", "xla"])
def test_a_row_migrated_both_ways_answers_as_the_oracle(engine, kind):
    """The non-token branches through a demotion and a promotion: every
    answer — on the device, on the host, on the device again — is
    ``oracle.py``'s."""
    rig = _Rig(engine)
    oracle = Oracle()
    kh = int(hash_request_keys(["mig"], [kind])[0])
    # make room for the key on the device
    if engine == "pallas":
        room = rig.resident[rig.resident % 2 == kh % 2][:1]
    else:
        room = rig.eng.probe_occupants(np.array([kh], np.uint64))[0][:1]
    rig.eng.remove_rows(room)
    where = []
    for step, hits in enumerate((4, 3, 9, 1, 3, 0, 2)):
        now = NOW + step * 1_700
        req = _req(kind, hits, now)
        got, = rig.eng.check_batch([req], now)
        want = oracle.check(req, now)
        assert (got.status, got.limit, got.remaining, got.reset_time,
                got.error) == (want.status, want.limit, want.remaining,
                               want.reset_time, want.error), (step, kind)
        where.append(bool(rig.tc.resident_mask(
            np.array([kh], np.uint64))[0]))
        if step == 1:
            assert rig.tc.demote(rig.eng, kh)
        if step == 3:
            rig.ranks[kh] = 10
            assert rig.tc.promote(rig.eng, kh, 10)
    # served on the device, then cold, then on the device again
    assert where == [False, False, True, True, False, False, False]
    st = rig.tc.stats()
    assert st["cold_served"] == 2 and st["promotions"] == 1


# ---- (c) a wave launched BEFORE the pass ---------------------------------

@pytest.mark.parametrize("engine", ["pallas", "xla"])
def test_a_wave_launched_before_a_pass_keeps_both_trails(engine):
    """Wave W is launched while key V is on the device and key P cold
    (P rides invalid).  Before W is synced a pass promotes P and evicts
    V — what the sync of the wave before W does.  V's cold row must
    hold what W's kernel did to it (the pass's fetch queues behind W),
    and W's re-dispatch must find P where it lives NOW; both keys'
    trails stay ``oracle.py``'s."""
    from gubernator_tpu.core.batch import pack_requests

    rig = _Rig(engine)
    oracle = Oracle()
    lock = threading.Lock()

    def kh_of(unique: str) -> np.ndarray:
        return hash_request_keys(["mig"], [unique])

    def wave(uniques, now, hits):
        reqs = [RateLimitRequest(name="mig", unique_key=u, hits=hits,
                                 limit=10, duration=60_000, created_at=now)
                for u in uniques]
        khs = hash_request_keys(["mig"] * len(uniques), list(uniques))
        batch, errs = pack_requests(reqs, now, size=len(reqs),
                                    key_hashes=khs)
        assert not any(errs)
        return batch, khs, oracle.check_batch(reqs, now)

    def same(cols, want):
        st, lim, rem, rst, full = cols
        assert not np.asarray(full).any()
        assert [(int(a), int(b), int(c), int(d)) for a, b, c, d
                in zip(st, lim, rem, rst)] == [
            (int(w.status), w.limit, w.remaining, w.reset_time)
            for w in want]

    # V takes the one slot its window is given
    v = int(kh_of("V")[0])
    if engine == "pallas":
        room = rig.resident[rig.resident % 2 == v % 2][:1]
    else:
        room = rig.eng.probe_occupants(kh_of("V"))[0][:1]
    rig.eng.remove_rows(room)
    batch, khs, want = wave(["V"], NOW, 1)
    same(rig.eng.check_packed(batch, khs, NOW), want)
    assert not rig.tc.resident_mask(khs)[0]
    # P: a key whose window holds V, and is full: created on the host
    pname = next(u for u in (f"P{i}" for i in range(400))
                 if v in rig.eng.probe_occupants(kh_of(u))[0].tolist())
    p = int(kh_of(pname)[0])
    both = ["V", pname]
    batch, khs, want = wave(both, NOW + 100, 1)
    same(rig.eng.check_packed(batch, khs, NOW + 100), want)
    assert rig.tc.resident_mask(khs).tolist() == [False, True]
    # every other resident of P's window is hotter than V
    rig.ranks.update({int(k): 50 for k in rig.resident})
    rig.ranks.update({v: 0, p: 60})
    t1 = NOW + 500
    batch, khs, want = wave(both, t1, 2)
    tok = rig.eng.launch_packed(batch, khs, t1)
    try:
        assert rig.tc.migrate(rig.eng, [p], [60]) == 1
        assert rig.tc.resident_mask(khs).tolist() == [True, False]
        # what W's kernel did to V is in its cold row: 10 - 1 - 1 - 2
        assert rig.tc.peek_row(v)["remaining"] == 6
        same(rig.eng.sync_packed(tok, engine_lock=lock), want)
    finally:
        rig.eng.drop_packed(tok)
    t2 = NOW + 900
    batch, khs, want = wave(both, t2, 3)
    same(rig.eng.check_packed(batch, khs, t2), want)
    st = rig.tc.stats()
    assert (st["promotions"], st["demotions"]) == (1, 1)


# ---- (d) no compile in a pass after warmup -------------------------------

@pytest.fixture(scope="module")
def warmed():
    import jax

    eng = PallasServingEngine(make_mesh(n=1), capacity_per_shard=1 << 15,
                              batch_per_shard=128, wave_buckets=(128,))
    tc = TierController(eng, rank_fn=lambda kh: 10,
                        rank_batch=lambda khs: np.full(len(khs), 10))
    eng.warmup_tier()
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append(name)
        if name == "/jax/core/compile/backend_compile_duration" else None)
    return eng, tc, compiles


@pytest.mark.parametrize("n", [1, 2, 15, 16, 17, 64, 65, 255, 256])
def test_a_pass_of_every_padded_length_compiles_nothing(warmed, n):
    eng, tc, compiles = warmed
    assert len(padded(np.zeros(n, np.int64))) in ROW_OP_SIZES
    keys = np.arange(1, n + 1, dtype=np.uint64) + np.uint64(1000 * n)
    assert len(np.unique(eng._bucket_ids(keys))) == n  # n distinct buckets
    with tc._mu:
        tc._store.put_batch(keys, _rows(keys))
    before = len(compiles)
    assert tc.migrate(eng, keys, np.full(n, 10)) == n
    found, cols = eng.gather_rows(keys)
    assert found.all() and (cols["remaining"] == _rows(keys)[:, 5]).all()
    assert len(compiles) == before
    assert tc.cold_keys() == 0


def test_the_xla_engines_row_programs_are_warmed_too():
    import jax

    eng = ShardedEngine(make_mesh(n=1), capacity_per_shard=1 << 10,
                        batch_per_shard=64)
    tc = TierController(eng, rank_fn=lambda kh: 10,
                        rank_batch=lambda khs: np.full(len(khs), 10))
    eng.warmup_tier()
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append(name)
        if name == "/jax/core/compile/backend_compile_duration" else None)
    keys = np.arange(1, 41, dtype=np.uint64)
    with tc._mu:
        tc._store.put_batch(keys, _rows(keys))
    assert tc.migrate(eng, keys, np.full(40, 10)) == 40
    assert eng.probe_occupants(keys[:5]).shape == (5, 16)
    assert not compiles


# ---- (e) the key draw ----------------------------------------------------

DRIFT = {"dist": "zipf_drift", "a": 1.1, "space": 100_000_000, "hot": 2048,
         "every_calls": 40, "start": 20_000_000, "stride": 2048}


def _draw(name="zipf_drift"):
    return plugins.load("keys", name).sample


def test_zipf_drift_is_seed_stable_and_counts_each_rng_on_its_own():
    s = _draw()
    a, b, c = (tr.caller_rng(SEED, 3), tr.caller_rng(SEED, 3),
               tr.caller_rng(SEED, 4))
    one = [s(a, DRIFT, 1000, 25_000_000) for _ in range(90)]
    # another caller's calls in between do not move this one's phase
    two = []
    for _ in range(90):
        s(c, DRIFT, 1000, 25_000_000)
        two.append(s(b, DRIFT, 1000, 25_000_000))
    assert all((x == y).all() for x, y in zip(one, two))
    assert one[0].dtype == np.int64 and one[0].shape == (1000,)
    assert all(0 <= x.min() and x.max() < DRIFT["space"] for x in one)
    with pytest.raises(ValueError):
        s(a, {**DRIFT, "space": 1000}, 10, 25_000_000)


def test_zipf_drift_steps_on_the_callers_own_count_where_the_formula_says():
    s, zs = _draw(), _draw("zipf_space")
    a, b = tr.caller_rng(SEED, 7), tr.caller_rng(SEED, 7)
    for call in range(3 * DRIFT["every_calls"] + 5):
        got = s(a, DRIFT, 1000, 25_000_000)
        z = zs(b, DRIFT, 1000, 25_000_000)  # the same stream, unmoved
        step = call // DRIFT["every_calls"]
        head = (z >= 1) & (z <= DRIFT["hot"])
        assert head.any()
        # the tail is zipf_space's draw for the same rng
        assert (got[~head] == z[~head]).all()
        if step == 0:
            assert (got == z).all()
        else:
            base = DRIFT["start"] + (step - 1) * DRIFT["stride"]
            assert (got[head] == base + z[head]).all()
            assert got[head].min() > base \
                and got[head].max() <= base + DRIFT["hot"]


def test_zipf_drift_head_share_is_what_the_traffic_file_says():
    s = _draw()
    rng = tr.caller_rng(SEED, 11)
    for _ in range(DRIFT["every_calls"]):  # step 1 from here on
        s(rng, DRIFT, 1, 25_000_000)
    got = np.concatenate([s(rng, DRIFT, 100_000, 25_000_000)
                          for _ in range(20)])
    base = DRIFT["start"]
    share = ((got > base) & (got <= base + DRIFT["hot"])).mean()
    assert abs(100 * share - 55.9) < 0.5, share


def test_zipf_drift_wraps_its_blocks_round_the_space():
    s = _draw()
    small = {**DRIFT, "space": 30_000, "hot": 16, "every_calls": 1,
             "start": 29_990, "stride": 16}
    rng = tr.caller_rng(SEED, 1)
    s(rng, small, 10, 3000)
    got = s(rng, small, 4000, 3000)  # step 1: base 29,990, wraps at 30,000
    assert got.max() < 30_000 and (got < 7).any() and (got > 29_990).any()


def test_the_traffic_file_and_the_config_say_what_the_issue_fixed():
    cell, cfg, mix, pop = _cell()
    full = run.load_cell(CELL, rehearsal=False)
    assert full["traffic"]["keys"] == DRIFT
    assert (full["traffic"]["callers"], full["traffic"]["generators"],
            full["traffic"]["requests_per_call"]) == (32, 4, 1000)
    assert mix["keys"] == {**DRIFT, "space": 30_000, "hot": 16,
                           "every_calls": 4, "start": 2400, "stride": 16}
    base = run.load_json(REPO, "benchmark/configs/region1-tier-100m.json")
    wake = run.load_json(REPO, "benchmark/configs/region1-tier-100m-wake.json")
    for k in ("deployment", "chips", "engine", "daemon", "env", "key_space",
              "sizes", "populations", "guarantees", "reduced", "rehearsal"):
        assert wake[k] == base[k], k
    assert wake["env"] == {"GUBER_TIER_COLD": "1"}
    assert len(wake["source"]) <= 200 and wake["source"] != base["source"]
    assert any(k.startswith("drift") for k in wake["assumed"])


# ---- (f) the two readers -------------------------------------------------

@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_reads_nothing_without_its_series(name):
    read = plugins.load("layer_metrics", name).read
    m = {"gubernator_dispatcher_wave_duration_count": 10.0,
         "gubernator_dispatcher_wave_size_count": 10.0}
    assert read({"m0": dict(m), "m1": dict(m), "seconds": 4.0}) is None
    # the counters alone (a tier that never migrated) are not enough
    m1 = {**m, "gubernator_tier_promotions_total": 0.0,
          "gubernator_tier_demotions_total": 0.0}
    assert read({"m0": dict(m), "m1": m1, "seconds": 4.0}) is None


def test_the_new_readers_on_canned_scrapes():
    d = "gubernator_phase_duration"
    lab = '{phase="tier.migrate"}'
    m0 = {"gubernator_dispatcher_wave_duration_count": 100.0,
          f"{d}_sum{lab}": 1.0, f"{d}_count{lab}": 10.0,
          f'{d}_sum{{phase="tier.resolve"}}': 5.0,
          "gubernator_tier_promotions_total": 50.0,
          "gubernator_tier_demotions_total": 40.0}
    m1 = {"gubernator_dispatcher_wave_duration_count": 300.0,
          f"{d}_sum{lab}": 1.5, f"{d}_count{lab}": 14.0,
          f'{d}_sum{{phase="tier.resolve"}}': 9.0,
          "gubernator_tier_promotions_total": 450.0,
          "gubernator_tier_demotions_total": 400.0}
    ctx = {"m0": m0, "m1": m1, "seconds": 4.0}
    ms = plugins.load("layer_metrics", "tier_migrate_ms").read(ctx)
    assert ms == pytest.approx(1000.0 * 0.5 / 200.0)
    rows = plugins.load("layer_metrics", "tier_rows_per_migration").read(ctx)
    assert rows == pytest.approx((400 + 360) / 4)
    # a window without a pass: the span reads 0 ms, the ratio nothing
    quiet = {"m0": m1, "m1": {**m1, "gubernator_dispatcher_wave_duration"
                              "_count": 400.0}, "seconds": 4.0}
    assert plugins.load("layer_metrics", "tier_migrate_ms").read(quiet) == 0.0
    assert plugins.load("layer_metrics",
                        "tier_rows_per_migration").read(quiet) is None


# ---- (g) the listing guard -----------------------------------------------

def test_every_reader_this_issue_adds_is_listed_for_the_new_cell_alone():
    manifest = run.load_json(REPO, "BENCHMARK.json")
    added = [m for m in manifest["per_layer"] if m["name"] in NEW_READERS]
    assert [m["name"] for m in added] == list(NEW_READERS)
    for m in added:
        assert m["workloads"] == [CELL] and m["layer"] == "cold tier"
    assert manifest["per_layer"][-2:] == added  # appended, in order
    assert manifest["workloads"][-1]["name"] == CELL
    assert manifest["configs"][-1]["name"] == "region1-tier-100m-wake"
    # the new cell is on every list the static cell is on, and no other
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        lists = m.get("workloads")
        if lists is not None and m["name"] not in NEW_READERS:
            assert (CELL in lists) == (CHURN in lists), m["name"]
    churn = {m["name"] for m in run.load_cell(CHURN, False)["per_layer"]}
    drift = {m["name"] for m in run.load_cell(CELL, False)["per_layer"]}
    assert drift - churn == set(NEW_READERS) and not churn - drift
    assert sum(c["chips"] == 4 for c in manifest["workloads"]) == 2
    assert len(manifest["workloads"]) == 11


def test_a_traced_rehearsal_prints_every_metric_listed_for_the_cell():
    """The check PR 45 failed: ``benchmark/run.py`` reports in a cell
    every per-layer metric whose ``workloads`` holds the cell or that has
    no list, and LEAVES OUT one whose reader returns ``None`` — the driver
    then finds a listed metric missing.  (A ``device_trace`` metric needs
    a device plane, which the CPU rehearsal's profile has none of.)  The
    static cell's twin, on its own rehearsal (two rehearsals of one cell
    at a time break each other): ``tests/test_tier_deployment.py``."""
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(SEED + 7), "--seconds", "4",
         "--trace", "1", "--cpu-rehearsal"],
        capture_output=True, text=True, cwd=REPO, timeout=1200)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, p.stderr[-3000:]
    assert all(got <= limit for got, which, limit
               in line["checks"].values() if which == "at most")
    listed = {m["name"] for m in run.load_cell(CELL, True)["per_layer"]
              if m["source"] != "device_trace"}
    assert listed - set(line["metrics"]) == set()
    got = line["metrics"]
    assert got["tier_rows_per_migration"]["value"] >= 1.0
    assert got["tier_migrate_ms"]["value"] > 0.0
    assert got["tier_migrations_per_s"]["value"] > 0.0
    assert 0 < got["tier_cold_rows_per_wave"]["value"] \
        < got["rows_per_wave"]["value"]


# ---- the fault controls on the new cell ----------------------------------

@pytest.mark.parametrize("fault", ["forget", "fork", "pass-forget",
                                   "pass-fork"])
def test_a_fault_of_the_tier_under_the_moving_hot_set_is_not_correct(fault):
    """``tools/tier_fault_control.py`` on the waking-tenant cell's
    rehearsal: a cold store that forgets or forks a held row, and a
    migration pass that forgets or forks a row it moves (``pass-``),
    must each end NOT correct."""
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "tier_fault_control.py"),
         fault, "2", "--", "--workload", CELL, "--seed", str(SEED + 2),
         "--seconds", "4", "--trace", "0", "--cpu-rehearsal"],
        capture_output=True, text=True, cwd=REPO, timeout=1200)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is False, p.stderr[-3000:]
    checks = line["checks"]
    assert checks["window_violations"][0] + checks["replay_mismatches"][0] > 0
    assert checks["responses_with_error"][0] == 0
    assert f"tier fault {fault!r}" in p.stderr
