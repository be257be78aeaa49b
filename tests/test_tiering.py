"""Tiered key store (ISSUE 10): HBM hot tier + host cold tier with
sketch-driven admission.

The acceptance battery: a device table capped far below the key domain
must serve every request EXACTLY — table-full stops being an error row
and becomes a cold-tier find-or-create — with decisions byte-identical
to an uncapped single-tier engine on the same traffic.  Covered lanes:
the classic blocking engine, the pipelined launch/sync split, the
fused serving engine, the mesh-GLOBAL replica tier's cap-overflow
demote, the two-tier snapshot/restore round trip, a 16-thread unwarmed
churn with exact conservation as the oracle, and native-vs-dict cold
store parity."""
import random
import threading

import numpy as np
import pytest

from gubernator_tpu.config import BehaviorConfig, Config
from gubernator_tpu.core.batch import pack_columns
from gubernator_tpu.hashing import hash_key
from gubernator_tpu.instance import V1Instance
from gubernator_tpu.parallel import make_mesh
from gubernator_tpu.parallel.sharded import ShardedEngine
from gubernator_tpu.proto import gubernator_pb2 as pb
from gubernator_tpu.store import MockLoader
from gubernator_tpu.tiering import ROW_COLS, TierController, _make_store
from gubernator_tpu.types import Behavior, RateLimitRequest

NOW = 1_790_000_000_000
DAY = 86_400_000
LIMIT = 10 ** 6


def _packed(keys, hits, now):
    kh = np.array([hash_key("tier", f"k{k}") for k in keys], np.uint64)
    n = len(keys)
    b, errs = pack_columns(kh, np.asarray(hits, np.int64),
                           np.full(n, 1000, np.int64),
                           np.full(n, DAY, np.int64),
                           np.zeros(n, np.int64), np.zeros(n, np.int64),
                           np.zeros(n, np.int64), now)
    assert not errs
    return b, kh


def _engine_pair(capped_cls=ShardedEngine, threshold=4):
    """64-row tiered engine + 16K-row uncapped control, same mesh."""
    mesh = make_mesh(n=1)
    ranks: dict = {}
    small = capped_cls(mesh, capacity_per_shard=64, batch_per_shard=64)
    big = ShardedEngine(mesh, capacity_per_shard=1 << 14,
                        batch_per_shard=64)
    tc = TierController(small, rank_fn=lambda kh: ranks.get(kh, 0),
                        promote_threshold=threshold)
    return small, big, tc, ranks


def _assert_wave_parity(r_tier, r_ctl, step):
    assert not np.asarray(r_tier[4]).any(), \
        f"step {step}: table-full rows leaked through the tier"
    for a, c, nm in zip(r_tier[:4], r_ctl[:4],
                        ("status", "limit", "remaining", "reset")):
        a, c = np.asarray(a), np.asarray(c)
        assert (a == c).all(), \
            (step, nm, np.nonzero(a != c)[0][:5].tolist())


def _audit_all_rows(small, big, tc, nkeys):
    """Every live control row exists in exactly one tier, bit-equal."""
    allk = np.array(sorted({hash_key("tier", f"k{k}")
                            for k in range(1, nkeys)}), np.uint64)
    f2, c2 = big.gather_rows(allk)
    f1, c1 = small.gather_rows(allk)
    for i in np.nonzero(f2)[0]:
        want = tuple(int(c2[f][i]) for f in ROW_COLS)
        if f1[i]:
            assert tc.peek_row(int(allk[i])) is None, \
                f"key {allk[i]} in BOTH tiers"
            got = tuple(int(c1[f][i]) for f in ROW_COLS)
        else:
            cold = tc.peek_row(int(allk[i]))
            assert cold is not None, f"key {allk[i]} lost from both tiers"
            got = tuple(cold[f] for f in ROW_COLS)
        assert got == want, (int(allk[i]), got, want)


def _pipelined(eng, b, kh, now, lock):
    tok = eng.launch_packed(b, kh, now)
    try:
        return eng.sync_packed(tok, engine_lock=lock)
    finally:
        eng.drop_packed(tok)


def _drive_parity(small, big, tc, ranks, *, steps=50, nkeys=2000,
                  pipelined=False, seed=5):
    rng = random.Random(seed)
    lock = threading.Lock()
    for step in range(steps):
        keys = [rng.randrange(1, nkeys) for _ in range(50)]
        hits = [rng.choice((0, 1, 2, 5)) for _ in keys]
        now = NOW + step * 1000
        b, kh = _packed(keys, hits, now)
        for k in kh:
            ranks[int(k)] = ranks.get(int(k), 0) + 1
        if pipelined:
            r1 = _pipelined(small, b, kh, now, lock)
        else:
            r1 = small.check_packed(b, kh, now)
        r2 = big.check_packed(b, kh, now)
        _assert_wave_parity(r1, r2, step)
    st = tc.stats()
    assert st["promotions"] > 0 and st["demotions"] > 0, \
        f"no migration traffic: {st}"
    assert st["cold_served"] > 0 and st["cold_keys"] > 0
    _audit_all_rows(small, big, tc, nkeys)
    return st


def _lane(monkeypatch, native: str) -> None:
    """``GUBER_TIER_NATIVE``: "1" = the native store, whose cold lane is
    the C++ pass (ISSUE 42); "0" = the dict store and the Python loop."""
    monkeypatch.setenv("GUBER_TIER_NATIVE", native)


def _assert_lane(tc, native: str) -> None:
    assert tc.stats()["native"] == (native == "1")
    assert (tc._store.apply_batch is not None) == (native == "1")


@pytest.mark.parametrize("native", ["1", "0"])
def test_engine_capped_parity_and_migration(monkeypatch, native):
    """Tentpole acceptance at engine level: 2000 keys through a 64-row
    table + cold tier are byte-identical to a 16K-row table, zero
    table-full rows, with real promote/demote traffic, and every row
    lives in exactly one tier afterwards — on both lanes of the cold
    tier."""
    _lane(monkeypatch, native)
    small, big, tc, ranks = _engine_pair()
    _assert_lane(tc, native)
    _drive_parity(small, big, tc, ranks)


@pytest.mark.parametrize("native", ["1", "0"])
def test_pipelined_lane_cold_serve_parity(monkeypatch, native):
    """The launch/sync split lane: cold rows ride the wave invalid and
    re-dispatch exactly at sync time (under the engine lock), so the
    pipelined dispatcher path keeps the same byte-identical contract."""
    _lane(monkeypatch, native)
    small, big, tc, ranks = _engine_pair()
    _assert_lane(tc, native)
    _drive_parity(small, big, tc, ranks, pipelined=True, seed=6)


def _count_launches(eng) -> list:
    """Every later ``_launch_arrays`` of ``eng`` appends to the list
    returned."""
    real = type(eng)._launch_arrays.__get__(eng)
    calls = []

    def spy(*a):
        calls.append(1)
        return real(*a)

    eng._launch_arrays = spy
    return calls


def _fill_table(engines, nkeys=400):
    """Keys 1..nkeys through every engine, 50 a wave: a 64-row table is
    full and its tier holds the rest."""
    for a in range(1, nkeys, 50):
        keys = list(range(a, min(a + 50, nkeys)))
        b, kh = _packed(keys, [1] * len(keys), NOW)
        cols = [e.check_packed(b, kh, NOW) for e in engines]
        for c in cols[1:]:
            _assert_wave_parity(c, cols[0], a)


def _keys_by_tier(small, tc, keys):
    """(device-resident, cold-resident) of ``keys``, which are all
    held."""
    kh = np.array([hash_key("tier", f"k{k}") for k in keys], np.uint64)
    cold = tc.resident_mask(kh)
    found, _ = small.gather_rows(kh)
    assert (cold ^ found).all()
    keys = np.asarray(keys)
    return keys[found].tolist(), keys[cold].tolist()


def _redispatch_engines(monkeypatch, native):
    """A full 64-row tiered engine for the pipelined lane and the
    uncapped control — the same 399 keys through both — with the
    tier's controller and the tiered engine's launch count."""
    _lane(monkeypatch, native)
    small, big, tc, _ = _engine_pair()
    _assert_lane(tc, native)
    _fill_table([big, small])
    assert small.occupancy() == 64 and tc.cold_keys() == 399 - 64
    return small, big, tc, _count_launches(small)


@pytest.mark.parametrize("native", ["1", "0"])
def test_sync_redispatches_erred_and_cold_rows_once(monkeypatch, native):
    """ISSUE 43 (a): a pipelined wave with erred AND cold-resident rows
    is TWO launches — the wave and ONE re-dispatch of both kinds of
    unanswered row together — and answers row for row what
    ``check_packed`` and the uncapped control answer, duplicate keys
    within the wave included."""
    small, big, tc, launches = _redispatch_engines(monkeypatch, native)
    twin = ShardedEngine(make_mesh(n=1), capacity_per_shard=64,
                         batch_per_shard=64)  # the blocking lane's
    TierController(twin, rank_fn=lambda kh: 0)
    _fill_table([twin])
    hot, cold = _keys_by_tier(small, tc, range(1, 400))
    first_seen = list(range(1000, 1012))  # every window is full: they err
    keys = (hot[:6] + cold[:6] + first_seen + cold[:3] + first_seen[:4]
            + hot[:2] + cold[4:9] + first_seen[2:5])
    random.Random(43).shuffle(keys)
    lock = threading.Lock()
    for step in range(3):  # step 2, 3: the first-seen keys are cold now
        now = NOW + 1000 * (step + 1)
        b, kh = _packed(keys, [(i + step) % 3 for i in range(len(keys))],
                        now)
        served = tc.stats()["cold_served"]
        del launches[:]
        r1 = _pipelined(small, b, kh, now, lock)
        assert len(launches) == (2 if step == 0 else 1), (step, launches)
        _assert_wave_parity(r1, big.check_packed(b, kh, now), step)
        _assert_wave_parity(r1, twin.check_packed(b, kh, now), step)
        # hot[:6] are 8 of the rows; the host answered all the others
        assert tc.stats()["cold_served"] - served == len(keys) - 8
    _audit_all_rows(small, big, tc, 1012)


@pytest.mark.parametrize("native", ["1", "0"])
def test_sync_launches_nothing_for_cold_rows_alone(monkeypatch, native):
    """ISSUE 43 (b): a wave whose only unanswered rows are
    cold-resident launches nothing at sync — no row of its re-dispatch
    would ride valid — and the tier answers them exactly."""
    small, big, tc, launches = _redispatch_engines(monkeypatch, native)
    hot, cold = _keys_by_tier(small, tc, range(1, 400))
    keys = hot[:10] + cold[:10] + cold[:5] + hot[3:6]
    now = NOW + 1000
    b, kh = _packed(keys, [1] * len(keys), now)
    r1 = _pipelined(small, b, kh, now, threading.Lock())
    assert len(launches) == 1
    _assert_wave_parity(r1, big.check_packed(b, kh, now), 0)
    _audit_all_rows(small, big, tc, 400)


@pytest.mark.parametrize("native", ["1", "0"])
def test_sync_retry_reads_a_key_the_next_wave_inserted(monkeypatch, native):
    """ISSUE 43 (c), the race the one retry launch exists for: a key
    errs in wave N, a slot of its window is freed and wave N+1 —
    launched before N's sync — INSERTS it.  N's sync must serve the row
    from the device, where its re-dispatch lands after N+1 and finds
    the key, and leave no host copy: the key stays in one tier."""
    small, _, tc, launches = _redispatch_engines(monkeypatch, native)
    b, kh = _packed([5000], [1], NOW + 1000)
    k = int(kh[0])
    lock = threading.Lock()
    tok_n = small.launch_packed(b, kh, NOW + 1000)  # errs: window full
    victim = next(int(v) for v in small.probe_occupants(
        np.array([k], np.uint64))[0] if v)
    assert tc.demote(small, victim)
    tok_n1 = small.launch_packed(b, kh, NOW + 1000)  # inserts the key
    assert len(launches) == 2
    r_n = small.sync_packed(tok_n, engine_lock=lock)
    assert len(launches) == 3  # N's row did err, and was launched ONCE more
    r_n1 = small.sync_packed(tok_n1, engine_lock=lock)
    assert len(launches) == 3
    small.drop_packed(tok_n)
    small.drop_packed(tok_n1)
    assert not r_n[4].any() and not r_n1[4].any()
    # N+1 reached the device first: the two hits, each applied once
    assert (int(r_n1[2][0]), int(r_n[2][0])) == (999, 998)
    assert not tc.resident_mask(kh).any() and tc.peek_row(k) is None
    found, cols = small.gather_rows(kh)
    assert found.all() and int(cols["remaining"][0]) == 998
    assert tc.stats()["cold_created"] == 399 - 64  # the fill's, no more


@pytest.mark.parametrize("pipelined", [True, False])
def test_no_tier_full_table_keeps_its_retries(pipelined):
    """ISSUE 43 (d): with NO tier bound a wave's erred rows keep every
    retry they had — a pipelined wave is its launch, the re-dispatch
    and the re-dispatch's own retry, a blocking one its launch and the
    retry — and what still errs is answered table_full."""
    eng = ShardedEngine(make_mesh(n=1), capacity_per_shard=64,
                        batch_per_shard=64)
    _fill_table([eng])
    assert eng.tier is None and eng.occupancy() == 64
    found, _ = eng.gather_rows(np.array(
        [hash_key("tier", f"k{k}") for k in range(1, 400)], np.uint64))
    keys = (np.nonzero(found)[0][:5] + 1).tolist() + [2000, 2001, 2000]
    now = NOW + 1000
    b, kh = _packed(keys, [1] * len(keys), now)
    launches = _count_launches(eng)
    cols = (_pipelined(eng, b, kh, now, threading.Lock()) if pipelined
            else eng.check_packed(b, kh, now))
    assert len(launches) == (3 if pipelined else 2)
    assert cols[4].tolist() == [False] * 5 + [True] * 3
    assert [int(v) for v in cols[2][:5]] == [998] * 5
    for c in cols[:4]:
        assert not np.asarray(c)[5:].any()
    assert eng.sweep_wanted


def test_fused_engine_overflow_parity():
    """Satellite: the fused serving engine (one device program per
    wave) routes its bucket-full rows through the same cold lane — its
    inherited resolve must match the classic engine byte-for-byte."""
    pallas_engine = pytest.importorskip(
        "gubernator_tpu.parallel.pallas_engine")
    small, big, tc, ranks = _engine_pair(
        capped_cls=pallas_engine.XlaFusedEngine)
    _drive_parity(small, big, tc, ranks, steps=40, seed=7)


def _seed_rank(inst, kh, weight):
    """Deterministically give ``kh`` sketch rank ``weight`` (the tap
    feed is async; tests must not sleep-and-hope)."""
    a = inst.analytics
    with a._mu:
        a.sketch.update(np.array([kh], np.uint64),
                        np.array([weight], np.int64),
                        np.zeros(1, bool), NOW)


def _greq(key, hits=1, name="mg", behavior=Behavior.GLOBAL):
    return RateLimitRequest(name=name, unique_key=key, hits=hits,
                            limit=LIMIT, duration=DAY, behavior=behavior)


def test_mesh_global_overflow_demotes(monkeypatch):
    """Satellite: a mesh-GLOBAL pin hitting a full probe window admits
    by sketch rank — the coldest occupant is demoted through the exact
    stand-down migration (its consumed hits land in the sharded row),
    the newcomer pins, and the overflow leaves a flight-recorder
    event."""
    monkeypatch.setenv("GUBER_MESH_GLOBAL_CAP", "16")
    inst = V1Instance(Config(cache_size=1 << 12, sweep_interval_ms=0,
                             global_mode="mesh", batch_rows=64,
                             behaviors=BehaviorConfig(
                                 global_sync_wait_ms=100)),
                      mesh=make_mesh(n=4))
    try:
        fill = {f"g{i}": hash_key("mg", f"g{i}") for i in range(64)}
        r = inst.get_rate_limits([_greq(k) for k in fill],
                                 now_ms=NOW + 1)
        assert all(x.error == "" for x in r)
        mge = inst._meshglobal
        assert mge is not None
        pinned = {k: kh for k, kh in fill.items() if mge.is_pinned(kh)}
        assert len(pinned) >= 8, "fill never saturated the 16-slot tier"
        # a newcomer whose whole probe window is occupied — the pin
        # that MUST overflow instead of silently declining
        occ = set(mge.slots.values())
        hot = next(f"h{c}" for c in range(500)
                   if all(s in occ for s in
                          mge._probe_slots_host(hash_key("mg",
                                                         f"h{c}"))))
        hot_kh = hash_key("mg", hot)
        _seed_rank(inst, hot_kh, 100)
        r = inst.get_rate_limits([_greq(hot)], now_ms=NOW + 2)
        assert r[0].error == ""
        assert mge.is_pinned(hot_kh), "hot newcomer was not admitted"
        evs = inst.recorder.events(kind="mesh_overflow_demote")
        ev = next(e for e in reversed(evs)
                  if int(e["admitted"]) == hot_kh)
        victim_kh = int(ev["khash"])
        assert not mge.is_pinned(victim_kh)
        victim_key = next(k for k, kh in fill.items()
                          if kh == victim_kh)
        # the demoted row must carry its consumed hit — a fresh-row
        # re-create here would read LIMIT and break conservation
        q = inst.get_rate_limits([_greq(victim_key, hits=0,
                                        behavior=Behavior(0))],
                                 now_ms=NOW + 3)
        assert q[0].error == ""
        assert q[0].remaining == LIMIT - 1, \
            f"demoted row lost its hit: remaining={q[0].remaining}"
    finally:
        inst.close()


def _tier_cfg(**kw):
    d = dict(cache_size=1024, cache_autogrow_max=1024, tier_cold=True,
             tier_promote_threshold=2, sweep_interval_ms=0,
             behaviors=BehaviorConfig())
    d.update(kw)
    return Config(**d)


def _fill_keys(inst, prefix, n, now, hits=0, name="tier", chunk=512):
    for base in range(0, n, chunk):
        reqs = [RateLimitRequest(name=name, unique_key=f"{prefix}{i}",
                                 hits=hits, limit=LIMIT, duration=DAY)
                for i in range(base, min(base + chunk, n))]
        for resp in inst.get_rate_limits(reqs, now_ms=now):
            assert resp.error == ""


def _live_rows(inst):
    """{khash: row-tuple} across BOTH tiers; asserts no key in both."""
    rows = {}
    arrays = inst.engine.snapshot()
    for i in range(len(arrays["key"])):
        rows[int(arrays["key"][i])] = tuple(int(arrays[f][i])
                                            for f in ROW_COLS)
    cold = inst._tier.snapshot_arrays()
    ncold = 0
    if cold is not None:
        ncold = len(cold["key"])
        for i in range(ncold):
            kh = int(cold["key"][i])
            assert kh not in rows, f"key {kh} present in BOTH tiers"
            rows[kh] = tuple(int(cold[f][i]) for f in ROW_COLS)
    return rows, ncold


def test_two_tier_snapshot_roundtrip():
    """Satellite: Loader snapshot covers BOTH tiers and restore places
    every row back into exactly one tier — byte-exact, no phantom rows,
    no dropped rows."""
    loader = MockLoader()
    inst = V1Instance(_tier_cfg(loader=loader), mesh=make_mesh(n=1))
    try:
        assert inst._tier is not None
        _fill_keys(inst, "s", 3000, NOW, hits=1)
        before, ncold = _live_rows(inst)
        assert ncold > 0, "fill never spilled into the cold tier"
    finally:
        inst.close()
    assert loader.called["save"] == 1
    assert len(loader.contents) == len(before), \
        "snapshot dropped or invented rows"
    inst2 = V1Instance(_tier_cfg(loader=loader), mesh=make_mesh(n=1))
    try:
        after, ncold2 = _live_rows(inst2)
        assert after == before, "restore is not byte-exact"
        assert ncold2 > 0, "restore overflow rows did not land cold"
    finally:
        inst2.close()


def _ser(reqs):
    m = pb.GetRateLimitsReq()
    for r in reqs:
        q = m.requests.add()
        q.name, q.unique_key = r.name, r.unique_key
        q.hits, q.limit, q.duration = r.hits, r.limit, r.duration
        q.behavior = int(r.behavior)
        q.algorithm = int(r.algorithm)
    return m.SerializeToString()


def test_tier_chaos_16_threads_unwarmed():
    """Satellite: 16 threads hammer brand-new keys through BOTH wire
    and object lanes against a saturated 1024-row table — every key
    lands cold first, some migrate mid-race, and the oracle is exact
    conservation: every hit sent is debited exactly once."""
    inst = V1Instance(_tier_cfg(), mesh=make_mesh(n=1))
    try:
        assert inst._tier is not None
        _fill_keys(inst, "pad", 2048, NOW)  # saturate the device table
        nkeys, reps, threads, hits = 64, 8, 16, 2
        keys = [f"race{i}" for i in range(nkeys)]
        errs: list = []
        barrier = threading.Barrier(threads)

        def worker(t):
            try:
                barrier.wait(timeout=60)
                for r in range(reps):
                    req = RateLimitRequest(
                        name="tier",
                        unique_key=keys[(t * reps + r) % nkeys],
                        hits=hits, limit=LIMIT, duration=DAY)
                    if t % 2:
                        out = pb.GetRateLimitsResp.FromString(
                            inst.get_rate_limits_wire(
                                _ser([req]), now_ms=NOW + 1 + r))
                        if out.responses[0].error:
                            raise RuntimeError(out.responses[0].error)
                    else:
                        resp = inst.get_rate_limits(
                            [req], now_ms=NOW + 1 + r)
                        if resp[0].error:
                            raise RuntimeError(resp[0].error)
            except Exception as e:  # noqa: BLE001 - audited below
                errs.append(repr(e))

        ths = [threading.Thread(target=worker, args=(t,))
               for t in range(threads)]
        for th in ths:
            th.start()
        for th in ths:
            th.join(timeout=120)
        assert not any(th.is_alive() for th in ths), "stuck threads"
        assert not errs, errs[:3]
        # force deterministic migration traffic: rank up a few keys
        # that are cold RIGHT NOW, then touch them (the async sketch
        # feed may or may not have promoted anyone during the race)
        cold_now = [k for k in keys
                    if inst._tier.peek_row(hash_key("tier", k))][:4]
        for k in cold_now:
            _seed_rank(inst, hash_key("tier", k), 50)
        if cold_now:
            reqs = [RateLimitRequest(name="tier", unique_key=k, hits=0,
                                     limit=LIMIT, duration=DAY)
                    for k in cold_now]
            for r in inst.get_rate_limits(reqs, now_ms=NOW + 100):
                assert r.error == ""
        st = inst._tier.stats()
        assert st["promotions"] + st["demotions"] > 0, st
        # exact conservation, cluster of one: sent == debited
        reqs = [RateLimitRequest(name="tier", unique_key=k, hits=0,
                                 limit=LIMIT, duration=DAY)
                for k in keys]
        debited = 0
        for r in inst.get_rate_limits(reqs, now_ms=NOW + 200):
            assert r.error == ""
            debited += LIMIT - r.remaining
        assert debited == threads * reps * hits, \
            f"lost hits: sent={threads * reps * hits} debited={debited}"
    finally:
        inst.close()


def test_cold_store_native_dict_parity(monkeypatch):
    """The native open-addressed cold table and the pure-Python dict
    reference agree on every operation, through growth and tombstone
    churn."""
    native = _make_store()
    if not native.native:
        pytest.skip("native cold_* primitives not built")
    monkeypatch.setenv("GUBER_TIER_NATIVE", "0")
    ref = _make_store()
    assert not ref.native
    rng = random.Random(3)
    keys = [rng.randrange(1, 1 << 62) for _ in range(3000)]
    for i, kh in enumerate(keys):
        row = tuple(i * 8 + j for j in range(len(ROW_COLS)))
        native.put(kh, row)
        ref.put(kh, row)
        probe = keys[rng.randrange(0, i + 1)]
        assert native.get(probe) == ref.get(probe)
        if i % 4 == 0:
            victim = keys[rng.randrange(0, i + 1)]
            assert native.pop(victim) == ref.pop(victim)
    assert len(native) == len(ref)
    arr = np.array(keys[:512] + [9_999_999_999], np.uint64)
    assert (native.contains_batch(arr) == ref.contains_batch(arr)).all()
    k1, r1 = native.snapshot()
    k2, r2 = ref.snapshot()
    s1 = {int(k): tuple(map(int, r)) for k, r in zip(k1, r1)}
    s2 = {int(k): tuple(map(int, r)) for k, r in zip(k2, r2)}
    assert s1 == s2


@pytest.mark.parametrize("state", ["empty", "partly_full", "evicting"])
def test_sketch_counts_of_an_array_is_count_of_key_by_key(state):
    """The admission's batched rank read (ISSUE 42): one lock, one
    reindex, one vectorised probe — the answers of ``count_of`` a key at
    a time, for tracked and untracked keys, in the caller's order, with
    repeats."""
    from gubernator_tpu.analytics import KeyAnalytics

    a = KeyAnalytics(k=16, width=64)
    try:
        rng = np.random.default_rng(9)
        pool = rng.integers(1, 1 << 63, 400).astype(np.uint64) * np.uint64(2)
        fed = {"empty": 0, "partly_full": 40, "evicting": 400}[state]
        with a._mu:
            for lo in range(0, fed, 50):
                kh = pool[rng.integers(0, fed, 200)]
                a.sketch.update(kh, rng.integers(1, 9, 200),
                                np.zeros(200, bool), NOW + lo)
        ask = np.concatenate([pool[:120], pool[:5], pool[300:],
                              np.array([0, 1, (1 << 64) - 1], np.uint64)])
        rng.shuffle(ask)
        got = a.sketch_counts(ask)
        assert got.dtype == np.int64 and got.shape == ask.shape
        want = [a.sketch_count(int(k)) for k in ask]
        assert got.tolist() == want
        assert a.sketch_counts(ask.tolist()).tolist() == want  # a list too
        assert (got > 0).any() == (fed > 0)
        assert (got == 0).any()
        assert a.sketch_counts([]).shape == (0,)
    finally:
        a.close()


def test_admission_reads_its_ranks_once_a_wave_in_order_of_service():
    """``_admit`` with a batched feed: ONE call for all the served keys,
    then ONE ``migrate`` pass (ISSUE 46) for those at or over the
    threshold, in the order they were served."""
    class _E:
        tier = None

    asked, promoted = [], []
    ranks = {11: 9, 12: 3, 13: 8, 14: 100}
    tc = TierController(
        _E(), rank_fn=lambda kh: 1 / 0, promote_threshold=8,
        rank_batch=lambda khs: (asked.append(list(map(int, khs)))
                                or np.array([ranks[int(k)] for k in khs])))
    tc.migrate = lambda engine, khs, ranks: promoted.append(
        list(zip(khs.tolist(), ranks.tolist())))
    tc._admit(_E(), np.array([14, 12, 11, 13], np.uint64))
    assert asked == [[14, 12, 11, 13]]
    assert promoted == [[(14, 100), (11, 9), (13, 8)]]
    assert all(type(v) is int for pair in promoted[0] for v in pair)
    tc._admit(_E(), [])
    assert len(asked) == 1
