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
        for kh, slot in mge.slots.items():
            col = np.asarray(mge.state.remaining)[:, slot]
            assert len(set(col.tolist())) == 1, (kh, col)
        # k0: 4 waves × 4 occurrences × 2 hits + 3 object-lane hits
        kh0 = hash_key("mg", "k0")
        rem = np.asarray(mge.state.remaining)[0, mge.slots[kh0]]
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
        rem = np.asarray(mge.state.remaining)[0, mge.slots[kh0]]
        assert int(rem) == 100_000 - 4 * 4 * 2
    finally:
        inst.close()


def test_bit_identical_vs_grpc_path(monkeypatch):
    """Same seeded traffic through mesh mode and through the gRPC-mode
    solo path (hot set off → owner-sharded GLOBAL): response bytes
    must match bit for bit — home-shard routing makes the mesh
    replica's decisions exactly the owner-sharded decisions."""
    mi = mesh_instance(monkeypatch)
    try:
        mesh_outs = seeded_traffic(mi)
        m_obj = mi.get_rate_limits([greq("k1", hits=5)], now_ms=NOW + 60)
    finally:
        mi.close()
    gi = V1Instance(Config(cache_size=1 << 12, sweep_interval_ms=0,
                           hot_set_capacity=0, batch_rows=64),
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
    and the new config applies — the hot set's contract, kept."""
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
    enter the mesh tier (the hot set's exclusion rule)."""
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
    """The default mode stays grpc: no mesh tier is ever built, and
    the hot set keeps its job."""
    inst = V1Instance(Config(cache_size=1 << 10, sweep_interval_ms=0),
                      mesh=make_mesh(n=4))
    try:
        assert inst._global_mode == "grpc"
        inst.get_rate_limits([greq("g0")], now_ms=NOW)
        assert inst._meshglobal is None
    finally:
        inst.close()


def test_unknown_global_mode_is_loud():
    with pytest.raises(ValueError, match="global_mode"):
        V1Instance(Config(cache_size=1 << 10, global_mode="typo"),
                   mesh=make_mesh(n=1))


def test_sketch_feeds_hotset_promotion(monkeypatch):
    """ISSUE 7 satellite (the PR-4 ROADMAP hook): the Space-Saving
    heavy-hitter ledger drives hot-set promotion.  A key made hot by
    NON-GLOBAL traffic (which never touched the ad-hoc promotion
    counter) promotes on its FIRST GLOBAL request, because the sketch
    already counts it past the threshold."""
    inst = V1Instance(
        Config(cache_size=1 << 10, sweep_interval_ms=0,
               hot_set_capacity=64, hot_promote_threshold=8,
               behaviors=BehaviorConfig(global_sync_wait_ms=25)),
        mesh=make_mesh(n=4))
    try:
        ana = inst.analytics
        if ana is None:
            pytest.skip("analytics disabled")
        plain = RateLimitRequest(name="mg", unique_key="skp", hits=1,
                                 limit=100_000, duration=600_000)
        for i in range(10):
            inst.get_rate_limits([plain], now_ms=NOW + i)
        assert ana.flush(), "analytics flush timed out"
        kh = hash_key("mg", "skp")
        assert ana.sketch_count(kh) >= 10
        assert inst._hot_counts.get(kh, 0) == 0  # ad-hoc never saw it
        inst.get_rate_limits([greq("skp")], now_ms=NOW + 20)
        assert inst._hotset is not None and inst._hotset.is_pinned(kh)
    finally:
        inst.close()
