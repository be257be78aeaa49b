"""Mesh-resident GLOBAL (ISSUE 7): collective hit reconciliation.

8-device CPU dryruns of the `GUBER_GLOBAL_MODE=mesh` backend
(parallel/meshglobal.py + the GlobalManager mesh tick): exact hit
conservation across shards (psum of the per-shard accumulators ==
injected hits), replica convergence through the all-reduce fold,
measured coherence staleness within the configured reconcile interval,
bit-identical decisions vs. the gRPC GLOBAL path on the same seeded
traffic, zero gRPC peer RPCs, and the chaos/degraded-fallback story
(collective faultpoints armed, nothing lost)."""
import time

import numpy as np
import pytest

from gubernator_tpu.config import BehaviorConfig, Config
from gubernator_tpu.core.table import to_host
from gubernator_tpu.hashing import hash_key
from gubernator_tpu.instance import V1Instance
from gubernator_tpu.parallel import make_mesh
from gubernator_tpu.proto import gubernator_pb2 as pb
from gubernator_tpu.types import Behavior, RateLimitRequest, Status

NOW = 1_781_000_000_000
SYNC_MS = 100


def ser(reqs):
    m = pb.GetRateLimitsReq()
    for r in reqs:
        q = m.requests.add()
        q.name, q.unique_key = r.name, r.unique_key
        q.hits, q.limit, q.duration = r.hits, r.limit, r.duration
        q.behavior = int(r.behavior)
        q.algorithm = int(r.algorithm)
        q.burst = r.burst
    return m.SerializeToString()


def greq(key, hits=1, name="mg", **kw):
    d = dict(limit=100_000, duration=600_000, behavior=Behavior.GLOBAL)
    d.update(kw)
    return RateLimitRequest(name=name, unique_key=key, hits=hits, **d)


def mesh_instance(monkeypatch, n=8, **cfg):
    monkeypatch.setenv("GUBER_MESH_GLOBAL_CAP", "256")
    d = dict(cache_size=1 << 12, sweep_interval_ms=0,
             global_mode="mesh", batch_rows=64,
             behaviors=BehaviorConfig(global_sync_wait_ms=SYNC_MS))
    d.update(cfg)
    return V1Instance(Config(**d), mesh=make_mesh(n=n))


def seeded_traffic(inst, waves=4, keys=5, hits=2, name="mg"):
    """Deterministic GLOBAL wire traffic; returns the response bytes."""
    outs = []
    for w in range(waves):
        reqs = [greq(f"k{i % keys}", hits=hits, name=name)
                for i in range(4 * keys)]
        outs.append(inst.get_rate_limits_wire(ser(reqs),
                                              now_ms=NOW + 1 + w))
    return outs


def test_conservation_convergence_staleness(monkeypatch):
    """The acceptance dryrun: GLOBAL hits reconcile over the mesh with
    exact conservation (sum of shard counters == injected hits), every
    replica converges after the fold, measured staleness stays within
    the configured reconcile interval, and NOTHING was ever queued for
    a gRPC peer."""
    inst = mesh_instance(monkeypatch)
    try:
        seeded_traffic(inst)
        # object lane rides the same tier
        r = inst.get_rate_limits([greq("k0", hits=3)], now_ms=NOW + 50)
        assert r[0].error == "" and r[0].status == Status.UNDER_LIMIT
        inst._mesh_reconcile_tick()
        mge = inst._meshglobal
        mge.drain()
        s = mge.stats()
        injected = 4 * 20 * 2 + 3
        assert s["injected_hits"] == injected
        assert s["folded_hits"] == injected, s  # exact conservation
        assert s["generation"] >= 1
        # staleness ≤ the configured reconcile interval
        assert s["last_staleness_s"] * 1000 <= SYNC_MS, s
        assert float(
            inst.metrics.mesh_global_staleness._value.get()) * 1000 \
            <= SYNC_MS
        # every replica of every pinned key agrees post-fold
        remaining = to_host(mge.state)["remaining"]
        for kh, slot in mge.slots.items():
            col = remaining[:, slot]
            assert len(set(col.tolist())) == 1, (kh, col)
        # k0: 4 waves × 4 occurrences × 2 hits + 3 object-lane hits
        kh0 = hash_key("mg", "k0")
        rem = remaining[0, mge.slots[kh0]]
        assert int(rem) == 100_000 - (4 * 4 * 2 + 3)
        # zero gRPC peer RPCs: no peers, and no hit aggregate was ever
        # queued for the gRPC lanes
        gm = inst.global_manager
        assert gm is not None and not gm._hits and not gm._hits_raw
        assert inst.metrics.check_error_counter.labels(
            error="global_hits_sync")._value.get() == 0
        # waves are stamped with the coherence epoch
        assert inst.dispatcher.reconcile_gen == mge.generation
    finally:
        inst.close()


def test_leaky_conservation_and_convergence(monkeypatch):
    """The same through an instance for LEAKY_BUCKET keys, on both
    lanes: every hit is folded, every replica of every key agrees after
    the fold, and the home row is the oracle's."""
    from gubernator_tpu.oracle import Oracle
    from gubernator_tpu.types import Algorithm

    inst = mesh_instance(monkeypatch)
    try:
        leaky = dict(limit=1000, burst=1500,
                     algorithm=Algorithm.LEAKY_BUCKET)
        oracle = Oracle()
        for w in range(4):
            reqs = [greq(f"lk{i % 5}", hits=2, **leaky) for i in range(20)]
            now = NOW + 1 + 7000 * w  # 7 s apart: the bucket leaks
            got = inst.get_rate_limits(reqs, now_ms=now) if w % 2 \
                else list(pb.GetRateLimitsResp.FromString(
                    inst.get_rate_limits_wire(ser(reqs),
                                              now_ms=now)).responses)
            for r, g, o in zip(reqs, got, oracle.check_batch(reqs, now)):
                assert (g.error, int(g.status), g.remaining,
                        g.reset_time) == ("", int(o.status), o.remaining,
                                          o.reset_time), (w, r.unique_key)
        inst._mesh_reconcile_tick()
        mge = inst._meshglobal
        s = mge.stats()
        assert s["pinned_keys"] == 5
        assert s["folded_hits"] == s["injected_hits"] == 4 * 20 * 2, s
        remaining = to_host(mge.state)["remaining"]
        for kh, slot in mge.slots.items():
            col = remaining[:, slot]
            assert len(set(col.tolist())) == 1, (kh, col)
            assert mge.row_state(kh)["meta"] & 1  # a leaky row
        gm = inst.global_manager
        assert gm is not None and not gm._hits and not gm._hits_raw
    finally:
        inst.close()


def test_fold_on_a_one_device_mesh_conserves(monkeypatch):
    """One chip is a 1-device mesh — the shape no CI mesh ever had.
    The fold's collectives must stay real there (an elided psum fails
    shard_map's replication check on every tick): the reconcile tick
    folds, conserves exactly, and the tier does not stand down."""
    inst = mesh_instance(monkeypatch, n=1)
    try:
        seeded_traffic(inst)
        inst._mesh_reconcile_tick()
        mge = inst._meshglobal
        mge.drain()
        s = mge.stats()
        assert s["n_shards"] == 1
        assert s["folded_hits"] == s["injected_hits"] == 4 * 20 * 2, s
        assert inst.metrics.mesh_global_folds._value.get() >= 1
        assert inst.metrics.mesh_global_fold_errors._value.get() == 0
        assert not inst._mesh_degraded
        kh0 = hash_key("mg", "k0")
        rem = to_host(mge.state)["remaining"][0, mge.slots[kh0]]
        assert int(rem) == 100_000 - 4 * 4 * 2
    finally:
        inst.close()


def test_bit_identical_vs_grpc_path(monkeypatch):
    """Same seeded traffic through mesh mode and through the gRPC-mode
    solo path (owner-sharded GLOBAL): response bytes
    must match bit for bit — home-shard routing makes the mesh
    replica's decisions exactly the owner-sharded decisions."""
    mi = mesh_instance(monkeypatch)
    try:
        mesh_outs = seeded_traffic(mi)
        m_obj = mi.get_rate_limits([greq("k1", hits=5)], now_ms=NOW + 60)
    finally:
        mi.close()
    gi = V1Instance(Config(cache_size=1 << 12, sweep_interval_ms=0,
                           batch_rows=64),
                    mesh=make_mesh(n=8))
    try:
        grpc_outs = seeded_traffic(gi)
        g_obj = gi.get_rate_limits([greq("k1", hits=5)], now_ms=NOW + 60)
        assert grpc_outs == mesh_outs
        assert (g_obj[0].status, g_obj[0].remaining, g_obj[0].reset_time,
                g_obj[0].limit) == \
               (m_obj[0].status, m_obj[0].remaining, m_obj[0].reset_time,
                m_obj[0].limit)
    finally:
        gi.close()


def test_chaos_collective_fault_conservation(monkeypatch):
    """A collective faultpoint armed mid-traffic: reconcile ticks abort
    (accumulators swap back — no hit stranded), and once the fault
    clears ONE clean fold recovers exact conservation."""
    inst = mesh_instance(monkeypatch)
    try:
        seeded_traffic(inst, waves=2)
        inst.faults.arm("global_psum:error", seed=11)
        inst._mesh_reconcile_tick()  # aborts; swap-back keeps the hits
        assert inst.metrics.mesh_global_fold_errors._value.get() >= 1
        seeded_traffic(inst, waves=2)  # more hits while degraded
        inst.faults.arm("global_accum_swap:error", seed=11)
        inst._mesh_reconcile_tick()  # aborts before the swap
        inst.faults.clear()
        inst._mesh_reconcile_tick()  # one clean fold recovers all
        mge = inst._meshglobal
        mge.drain()
        s = mge.stats()
        assert s["folded_hits"] == s["injected_hits"] == 4 * 20 * 2, s
    finally:
        inst.close()


def test_degraded_fallback_and_recovery(monkeypatch):
    """Consecutive fold failures stand the tier down: keys demote to
    the owner-sharded path EXACTLY (home-row migration needs no
    collective), traffic keeps serving, and a clean fold after the
    cooldown re-arms the tier."""
    monkeypatch.setenv("GUBER_MESH_FALLBACK_AFTER", "2")
    inst = mesh_instance(monkeypatch,
                         behaviors=BehaviorConfig(
                             global_sync_wait_ms=60_000))
    try:
        seeded_traffic(inst, waves=2, keys=3)
        inst._mesh_reconcile_tick()  # clean fold applies the backlog
        inst.faults.arm("global_psum:error", seed=3)
        inst._mesh_reconcile_tick()
        assert not inst._mesh_degraded
        inst._mesh_reconcile_tick()  # streak hits the threshold
        assert inst._mesh_degraded
        assert inst.metrics.mesh_global_degraded._value.get() == 1
        mge = inst._meshglobal
        assert not mge.pinned_keys()  # demoted to the sharded table
        # consumption survived the stand-down: the sharded row carries
        # every hit (2 waves × 4 occurrences × 2 hits = 16 on k0,
        # folded into the replica then migrated home)
        kh0 = hash_key("mg", "k0")
        found, cols = inst.engine.gather_rows(np.array([kh0], np.uint64))
        assert found[0]
        assert int(cols["remaining"][0]) == 100_000 - 16
        # degraded traffic serves from the sharded path, still exact
        out = pb.GetRateLimitsResp.FromString(
            inst.get_rate_limits_wire(ser([greq("k0", hits=1)]),
                                      now_ms=NOW + 200))
        assert out.responses[0].error == ""
        assert out.responses[0].remaining == 100_000 - 17
        # recovery: clean folds after the cooldown re-arm the tier
        inst.faults.clear()
        inst._mesh_down_until = time.monotonic() - 1
        inst._mesh_reconcile_tick()
        assert not inst._mesh_degraded
        assert inst.metrics.mesh_global_degraded._value.get() == 0
        # and routing resumes on the mesh tier
        inst.get_rate_limits_wire(ser([greq("k0", hits=1)]),
                                  now_ms=NOW + 300)
        assert mge.pinned_keys()
    finally:
        inst.close()


def test_config_change_demotes_with_state(monkeypatch):
    """A limit change on a mesh-pinned key demotes it (state intact)
    and the new config applies."""
    inst = mesh_instance(monkeypatch)
    try:
        inst.get_rate_limits([greq("cfg", hits=11, limit=100)],
                             now_ms=NOW)
        kh = hash_key("mg", "cfg")
        assert inst._meshglobal.is_pinned(kh)
        r = inst.get_rate_limits([greq("cfg", hits=1, limit=50)],
                                 now_ms=NOW + 1)[0]
        assert not inst._meshglobal.is_pinned(kh)
        assert r.limit == 50
        # 11 consumed at limit 100 → 89; limit 100→50 adjusts by -50
        # → clamp(39, 0, 50); this hit takes 1 → 38
        assert r.remaining == 38, r
    finally:
        inst.close()


def test_flagged_requests_bypass_mesh(monkeypatch):
    """RESET/DRAIN/Gregorian/MULTI_REGION-flagged GLOBAL rows never
    enter the mesh tier (instance.py › _REPLICA_EXCLUDED)."""
    inst = mesh_instance(monkeypatch)
    try:
        r = inst.get_rate_limits(
            [greq("flg", behavior=Behavior.GLOBAL
                  | Behavior.RESET_REMAINING)], now_ms=NOW)[0]
        assert r.error == ""
        mge = inst._meshglobal
        assert mge is None or not mge.pinned_keys()
    finally:
        inst.close()


def test_grpc_mode_untouched_by_default(monkeypatch):
    """The default mode stays grpc: no replica tier of any kind is ever
    built — a much-hit GLOBAL key is a row of the sharded table."""
    inst = V1Instance(Config(cache_size=1 << 10, sweep_interval_ms=0),
                      mesh=make_mesh(n=4))
    try:
        assert inst._global_mode == "grpc"
        for t in range(3):
            inst.get_rate_limits([greq("g0")] * 40, now_ms=NOW + t)
        assert inst._meshglobal is None and not inst._mesh_mode()
        assert "mesh_global" not in inst.memledger.consumers()
        found, cols = inst.engine.gather_rows(
            np.array([hash_key("mg", "g0")], np.uint64))
        assert found[0] and int(cols["remaining"][0]) == 100_000 - 120
    finally:
        inst.close()


def test_unknown_global_mode_is_loud():
    with pytest.raises(ValueError, match="global_mode"):
        V1Instance(Config(cache_size=1 << 10, global_mode="typo"),
                   mesh=make_mesh(n=1))


# ---- ISSUE 25: GLOBAL routing by the call, not by the distinct key -----
#
# _wire_mesh_runner groups a call's mesh rows ONCE (instance.py ›
# _group_key_configs): the answers, the fallbacks and the pins must be
# what the per-key loops gave, and the work must not grow with the
# number of distinct keys.

from gubernator_tpu import instance as instance_mod  # noqa: E402
from gubernator_tpu.core.batch import pack_columns  # noqa: E402
from gubernator_tpu.instance import _wire_native  # noqa: E402
from gubernator_tpu.types import Algorithm  # noqa: E402
from gubernator_tpu.wire import resp_to_pb  # noqa: E402

ROWS = 1000


def _mk_big(engine):
    return V1Instance(Config(
        cache_size=1 << 14, sweep_interval_ms=0, engine=engine,
        global_mode="mesh", batch_rows=256,
        behaviors=BehaviorConfig(global_sync_wait_ms=SYNC_MS)),
        mesh=make_mesh(n=8))


@pytest.fixture(scope="module")
def big_pair():
    """(wire, object) instances on the cell's engine (fused, mesh
    bound: run_fused + the mslot column), shared by the cases below —
    every case works in a key namespace of its own."""
    mp = pytest.MonkeyPatch()
    mp.setenv("GUBER_MESH_GLOBAL_CAP", "4096")
    wi, oi = _mk_big("pallas"), _mk_big("pallas")
    mp.undo()  # the tier is built (and bound) at construction
    assert wi.engine.mesh_bound
    yield wi, oi
    wi.close()
    oi.close()


@pytest.fixture(scope="module")
def xla_inst():
    """The classic engine: no mslot column, the two-dispatch `run`."""
    mp = pytest.MonkeyPatch()
    mp.setenv("GUBER_MESH_GLOBAL_CAP", "4096")
    inst = _mk_big("xla")
    mp.undo()
    assert not getattr(inst.engine, "mesh_bound", False)
    yield inst
    inst.close()


def empty_tier(*insts):
    """Hand the tier's slots out afresh (host maps only: a pin writes
    its whole row, so stale device rows are never read).  The cases
    below share instances, and 4,096 slots do not hold all their keys."""
    for inst in insts:
        mge = inst._meshglobal
        with mge._mu:
            for d in (mge.slots, mge.pinned_cfg, mge._retired,
                      mge._occupied):
                d.clear()


def lane(inst, name):
    return inst.metrics.wire_lane_counter.labels(lane=name)._value.get()


def key_draw(distinct):
    """Key index per row: `distinct` keys, every one present; 490 is
    the benchmark cell's own draw (numpy zipf(1.1) % 1024)."""
    if distinct == 490:
        ks = np.random.default_rng(25).zipf(1.1, ROWS) % 1024
        assert 440 <= len(set(ks.tolist())) <= 540
        return ks.tolist()
    return [(i * 7919) % distinct for i in range(ROWS)]


def call_of(shape, ns):
    """One 1,000-row call: `g<distinct>` all GLOBAL, `mixed` every
    other row a local key, `leaky` GLOBAL leaky buckets with a burst."""
    if shape.startswith("g"):
        return [greq(f"k{k}", hits=1 + i % 3, name=ns)
                for i, k in enumerate(key_draw(int(shape[1:])))]
    if shape == "mixed":
        return [greq(f"k{k}", hits=1, name=ns) if i % 2 else
                RateLimitRequest(name=ns, unique_key=f"loc{k % 40}",
                                 hits=1, limit=7, duration=60_000)
                for i, k in enumerate(key_draw(490))]
    assert shape == "leaky"
    return [greq(f"k{k}", hits=1, name=ns, limit=50 + k % 3, burst=k % 2 * 80,
                 algorithm=Algorithm.LEAKY_BUCKET)
            for k in key_draw(12)]


def obj_bytes(inst, reqs, now):
    out = pb.GetRateLimitsResp()
    out.responses.extend(resp_to_pb(r)
                         for r in inst.get_rate_limits(reqs, now_ms=now))
    return out.SerializeToString()


@pytest.mark.parametrize("state", ["cold", "warm"])
@pytest.mark.parametrize("shape", ["g1", "g12", "g490", "g1000", "mixed",
                                   "leaky"])
def test_wire_lane_byte_equal_to_object_path(big_pair, shape, state):
    """The wire lane's bytes equal the object path's for 1,000-row
    calls, first touch (the call pins its keys) and warm (all pinned)
    — and the wire lane really served them."""
    wi, oi = big_pair
    empty_tier(wi, oi)
    reqs = call_of(shape, f"eq-{shape}-{state}")
    data = ser(reqs)
    now = NOW
    if state == "warm":
        # the same first touch on both sides, then compare a warm call
        assert wi.get_rate_limits_wire(data, now_ms=now) == \
            oi.get_rate_limits_wire(data, now_ms=now)
        now += 1
    n_wire, n_pb2 = lane(wi, "wire_global"), lane(wi, "pb2_fallback")
    got = wi.get_rate_limits_wire(data, now_ms=now)
    assert got == obj_bytes(oi, reqs, now)
    assert lane(wi, "wire_global") - n_wire == ROWS
    assert lane(wi, "pb2_fallback") == n_pb2
    rs = pb.GetRateLimitsResp.FromString(got).responses
    assert len(rs) == ROWS and all(r.error == "" for r in rs)
    for inst in (wi, oi):  # every GLOBAL key of the call is pinned
        mge = inst._meshglobal
        assert all(mge.is_pinned(hash_key(r.name, r.unique_key))
                   for r in reqs if r.behavior & Behavior.GLOBAL)


def tier_state(inst):
    mge = inst._meshglobal
    with mge._mu:
        return (dict(mge.slots), dict(mge.pinned_cfg),
                dict(mge._retired), set(mge._occupied),
                mge.stats()["injected_hits"])


@pytest.mark.parametrize("case", ["cold-mid-batch", "warm-mid-batch",
                                  "warm-pinned-changed"])
def test_config_change_on_one_of_490_keys_returns_none(big_pair, case):
    """One key of ~490 changes its limit — between two of its rows, or
    on all of them against what the tier pinned: the runner returns
    None where the per-key loops did, BEFORE anything moved, and the
    object path serves the call."""
    wi, oi = big_pair
    empty_tier(wi, oi)
    ns = f"cc-{case}"
    reqs = call_of("g490", ns)
    if case.startswith("warm"):
        data = ser(reqs)
        assert wi.get_rate_limits_wire(data, now_ms=NOW) == \
            oi.get_rate_limits_wire(data, now_ms=NOW)
    # a key with several rows; the change lands on its LAST row only,
    # or on every row of it
    rows_of = {}
    for i, r in enumerate(reqs):
        rows_of.setdefault(r.unique_key, []).append(i)
    victim = next(k for k, rows in sorted(rows_of.items())
                  if 2 <= len(rows) <= 6)
    hit = rows_of[victim] if case == "warm-pinned-changed" \
        else rows_of[victim][-1:]
    for i in hit:
        reqs[i] = greq(victim, hits=reqs[i].hits, name=ns, limit=99_999)
    data = ser(reqs)
    before = tier_state(wi)
    runner = wi._wire_mesh_runner(
        _wire_native.parse_get_rate_limits(data), NOW + 1)
    assert runner is None
    assert tier_state(wi) == before
    n_pb2 = lane(wi, "pb2_fallback")
    assert wi.get_rate_limits_wire(data, now_ms=NOW + 1) == \
        obj_bytes(oi, reqs, NOW + 1)
    assert lane(wi, "pb2_fallback") - n_pb2 == ROWS


def mslots_of(inst, monkeypatch):
    """Capture the mslot column the runner hands the dispatcher."""
    seen = []
    real = inst.dispatcher.check_packed

    def spy(batch, kh, now, mslot=None):
        seen.append((np.array(kh), None if mslot is None
                     else np.array(mslot)))
        return real(batch, kh, now, mslot=mslot)

    monkeypatch.setattr(inst.dispatcher, "check_packed", spy)
    return seen


def test_key_unpinned_between_keys_and_slots_rides_sharded(big_pair,
                                                           monkeypatch):
    """A key unpinned after route.keys matched it and before
    route.slots reads the slot map: its rows take the sharded lane
    (mslot -1), the rest keep their slots, nobody errors."""
    wi, _ = big_pair
    empty_tier(wi)
    reqs = call_of("g490", "unpin")
    data = ser(reqs)
    wi.get_rate_limits_wire(data, now_ms=NOW)  # warm: all pinned
    gone = hash_key("unpin", reqs[0].unique_key)
    mge = wi._meshglobal
    real_phase = instance_mod.phase

    def phase_spy(name, *a, **kw):
        if name == "route.slots":
            mge.unpin(gone)
        return real_phase(name, *a, **kw)

    monkeypatch.setattr(instance_mod, "phase", phase_spy)
    seen = mslots_of(wi, monkeypatch)
    out = wi.get_rate_limits_wire(data, now_ms=NOW + 1)
    (kh, mslot), = seen
    mine = kh == np.uint64(gone)
    assert mine.sum() == sum(r.unique_key == reqs[0].unique_key
                             for r in reqs)
    assert (mslot[mine] == -1).all() and (mslot[~mine] >= 0).all()
    rs = pb.GetRateLimitsResp.FromString(out).responses
    assert all(r.error == "" for r in rs)


@pytest.mark.parametrize("engine", ["pallas", "xla"])
def test_full_probe_window_sends_only_that_keys_rows_sharded(
        big_pair, xla_inst, monkeypatch, engine):
    """First touch of ~490 keys, one of them with a full probe window
    and nothing colder to evict: that key's rows alone ride the
    sharded lane, every other key is pinned and served by the tier."""
    inst = big_pair[0] if engine == "pallas" else xla_inst
    empty_tier(inst)
    ns = f"full-{engine}"
    reqs = call_of("g490", ns)
    refused = hash_key(ns, reqs[0].unique_key)
    mge = inst._meshglobal
    with mge._mu:
        fake = set(mge._probe_slots_host(refused)) - mge._occupied
        mge._occupied |= fake
    monkeypatch.setattr(inst, "_mesh_overflow_victim", lambda kh: None)
    seen = mslots_of(inst, monkeypatch)
    mesh_kh = []
    real_cc = mge.check_columns
    monkeypatch.setattr(mge, "check_columns", lambda b, kh, now: (
        mesh_kh.append(np.array(kh)), real_cc(b, kh, now))[1])
    try:
        out = inst.get_rate_limits_wire(ser(reqs), now_ms=NOW)
    finally:
        with mge._mu:
            mge._occupied -= fake
    rs = pb.GetRateLimitsResp.FromString(out).responses
    assert len(rs) == ROWS and all(r.error == "" for r in rs)
    assert not mge.is_pinned(refused)
    others = {hash_key(ns, r.unique_key) for r in reqs} - {refused}
    assert all(mge.is_pinned(k) for k in others)
    (kh, mslot), = seen
    if engine == "pallas":  # one fused wave, the lane is the mslot
        mine = kh == np.uint64(refused)
        assert mine.any()
        assert (mslot[mine] == -1).all() and (mslot[~mine] >= 0).all()
    else:  # the sharded dispatch carries that key alone
        assert mslot is None and set(kh.tolist()) == {refused}
        (mk,), = [mesh_kh]
        assert set(mk.tolist()) == others


def test_routing_work_does_not_grow_with_distinct_keys(big_pair,
                                                       monkeypatch,
                                                       numpy_calls):
    """The complexity, not the clock: a warm 1,000-row call with ~490
    distinct pinned keys builds no RateLimitRequest in the runner and
    makes exactly the numpy calls a 12-key call makes."""
    wi, _ = big_pair
    empty_tier(wi)
    built = []
    real_req = instance_mod.RateLimitRequest
    counts = {}
    for shape in ("g12", "g490"):
        data = ser(call_of(shape, "cx"))
        wi.get_rate_limits_wire(data, now_ms=NOW)  # warm
        parsed = _wire_native.parse_get_rate_limits(data)
        monkeypatch.setattr(
            instance_mod, "RateLimitRequest",
            lambda *a, **kw: (built.append(1), real_req(*a, **kw))[1])
        with numpy_calls() as calls:
            runner = wi._wire_mesh_runner(parsed, NOW + 1)
        monkeypatch.setattr(instance_mod, "RateLimitRequest", real_req)
        assert runner is not None
        counts[shape] = calls.n
        assert runner()  # and it serves
    assert built == []
    assert counts["g12"] == counts["g490"] > 0, counts


@pytest.mark.parametrize("cfg", [
    dict(limit=100, duration=60_000),
    dict(limit=0, duration=0),
    dict(limit=-5, duration=-7, burst=-1),
    dict(limit=2**62, duration=2**62, burst=2**62),
    dict(limit=100, duration=60_000, algorithm=Algorithm.LEAKY_BUCKET),
    dict(limit=100, duration=60_000, burst=250,
         algorithm=Algorithm.LEAKY_BUCKET),
    dict(limit=2**62, duration=1, burst=2**62,
         algorithm=Algorithm.LEAKY_BUCKET),
    dict(limit=2**40, duration=2**50, burst=3,
         algorithm=Algorithm.LEAKY_BUCKET),
    dict(limit=10, duration=0, algorithm=Algorithm.LEAKY_BUCKET),
    dict(limit=10, duration=-3, burst=-2,
         algorithm=Algorithm.LEAKY_BUCKET),
    dict(limit=100, duration=1,
         behavior=Behavior.GLOBAL | Behavior.DURATION_IS_GREGORIAN),
    dict(limit=2**62, duration=4, burst=2**62,
         algorithm=Algorithm.LEAKY_BUCKET,
         behavior=Behavior.GLOBAL | Behavior.DURATION_IS_GREGORIAN),
    dict(limit=77, duration=5, burst=9, algorithm=Algorithm.LEAKY_BUCKET,
         behavior=Behavior.GLOBAL | Behavior.DURATION_IS_GREGORIAN),
], ids=lambda c: "-".join(f"{k[:3]}{int(v)}" for k, v in c.items()))
def test_packed_columns_equal_cfg_of(cfg):
    """What lets the runner compare a batch's columns with the tier's
    pinned_cfg tuples as they are: pack_columns clamps (alg, limit,
    duration, burst) exactly as clamp_config does — token and leaky,
    at the bounds, and under DURATION_IS_GREGORIAN (whose rows never
    reach the tier; the clamps agree there all the same)."""
    from gubernator_tpu.parallel import meshglobal

    req = greq("x", **cfg)
    col = lambda v, t=np.int64: np.array([v], t)  # noqa: E731
    batch, errs = pack_columns(
        col(7, np.uint64), col(req.hits), col(req.limit),
        col(req.duration), col(int(req.algorithm), np.int32),
        col(int(req.behavior), np.int32), col(req.burst), NOW)
    assert not errs
    got = tuple(int(np.asarray(c)[0]) for c in (
        batch.algorithm, batch.limit, batch.duration, batch.burst))
    assert got == meshglobal._cfg_of(req)


@pytest.mark.parametrize("limit", [10_000, (1 << 32) + 50, (1 << 45) + 7])
@pytest.mark.parametrize("n", [1, 4])
def test_fold_adopts_the_home_row_word_by_word(limit, n):
    """The replica map holds its 64-bit columns as two 32-bit words
    (core/table.py): the fold psums each word of the home-masked
    column, and exactly one replica is a slot's home — so every replica
    ends with the home's row, also where the value needs the high
    word."""
    from gubernator_tpu.parallel.meshglobal import MeshGlobalEngine

    mge = MeshGlobalEngine(make_mesh(n=n), capacity=256, batch_per_chip=16)
    r = RateLimitRequest(name="mgw", unique_key="wide", hits=3, limit=limit,
                         duration=600_000, behavior=Behavior.GLOBAL)
    kh = hash_key("mgw", "wide")
    assert mge.pin(r, kh, NOW)
    out = mge.check_batch([r] * 8, [kh] * 8, NOW + 1)
    assert [x.remaining for x in out] == [limit - 3 * (i + 1)
                                         for i in range(8)]
    mge.fold(mge.swap_accum())
    mge.drain()
    assert mge.stats()["folded_hits"] == mge.stats()["injected_hits"] == 24
    host = to_host(mge.state)
    slot = mge.slots[kh]
    assert (host["remaining"][:, slot] == limit - 24).all()
    assert (host["limit"][:, slot] == limit).all()
    assert (host["key"][:, slot] == np.uint64(kh)).all()
    assert mge.row_state(kh)["remaining"] == limit - 24
