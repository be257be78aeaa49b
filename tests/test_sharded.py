"""Multi-device tests on the 8-device virtual CPU mesh — the analog of
the reference's in-process cluster tests (cluster/cluster.go +
functional_test.go › TestGlobalRateLimits, SURVEY.md §4)."""
import numpy as np
import pytest

from gubernator_tpu import Algorithm, Oracle, RateLimitRequest
from gubernator_tpu.parallel import ShardedEngine, make_mesh

NOW = 1_760_000_000_000


def mk(key, **kw):
    d = dict(hits=1, limit=10, duration=60_000)
    d.update(kw)
    return RateLimitRequest(name="shard", unique_key=key, **d)


@pytest.fixture(scope="module")
def engine():
    mesh = make_mesh(n=4)
    return ShardedEngine(mesh, capacity_per_shard=1 << 10, batch_per_shard=64)


class TestShardedEngine:
    def test_parity_vs_oracle(self, engine):
        oracle = Oracle()
        rng = np.random.default_rng(3)
        now = NOW
        for _ in range(4):
            reqs = [mk(f"k{rng.integers(0, 50)}",
                       hits=int(rng.integers(0, 3)),
                       algorithm=Algorithm.LEAKY_BUCKET if rng.integers(2)
                       else Algorithm.TOKEN_BUCKET)
                    for _ in range(120)]
            want = oracle.check_batch(reqs, now)
            got = engine.check_batch(reqs, now)
            for i, (w, g) in enumerate(zip(want, got)):
                assert g.error == ""
                assert (int(g.status), g.remaining, g.reset_time, g.limit) == \
                    (int(w.status), w.remaining, w.reset_time, w.limit), (i, reqs[i])
            now += 7_000

    def test_keys_spread_across_shards(self, engine):
        # distribution sanity: hash-range ownership covers all shards
        from gubernator_tpu.hashing import hash_keys, shard_of
        ks = [mk(f"spread{i}").key for i in range(2000)]
        shards = shard_of(hash_keys(ks), engine.n)
        assert len(set(shards.tolist())) == engine.n

    def test_expired_rows_reclaimed_by_sweep(self):
        # key churn beyond capacity: expired rows must be swept so new
        # keys keep landing (lrucache.go eviction analog).  No wave
        # sweeps for itself (ISSUE 31): a key whose window is clogged
        # is answered table_full and the engine ASKS for the sweep,
        # which its owner runs between waves; after it the key lands
        eng = ShardedEngine(make_mesh(n=2), capacity_per_shard=64,
                            batch_per_shard=64)
        now = NOW
        asked = 0
        for gen in range(6):
            reqs = [mk(f"gen{gen}_{i}", duration=5_000) for i in range(60)]
            got = eng.check_batch(reqs, now)
            left = [q for q, r in zip(reqs, got) if r.error]
            assert eng.sweep_count == asked  # none ran inside the wave
            assert eng.sweep_wanted == bool(left)
            if left:
                assert {r.error for r in got if r.error} == {
                    "rate limit table full"}
                eng.sweep(now)
                eng.sweep_wanted = False
                asked += 1
                got = eng.check_batch(left, now)
            n_err = sum(1 for r in got if r.error)
            assert n_err == 0, f"gen {gen}: {n_err} table-full errors"
            now += 60_000  # previous generation fully expired
        assert asked > 0

    def test_overflow_wave_splitting(self, engine):
        # more same-shard requests than B: served in multiple waves
        reqs = [mk("hotkey", limit=1000) for _ in range(150)]
        got = engine.check_batch(reqs, NOW + 10**6)
        assert all(r.error == "" for r in got)
        assert [r.remaining for r in got] == list(range(999, 849, -1))


class TestOnDeviceGrow:
    def test_grow_preserves_every_row(self):
        eng = ShardedEngine(make_mesh(n=4), capacity_per_shard=1 << 9,
                            batch_per_shard=64)
        reqs = [mk(f"g{i}", limit=100) for i in range(600)]
        eng.check_batch(reqs, NOW)
        eng.check_batch(reqs[:200], NOW + 1)  # consume extra on some keys
        from gubernator_tpu.hashing import hash_request_keys

        khash = hash_request_keys(["shard"] * 600,
                                  [f"g{i}" for i in range(600)])
        found0, cols0 = eng.gather_rows(khash)
        assert found0.all()
        dropped = eng.grow(1 << 11)
        assert dropped == 0
        assert eng.cap_local == 1 << 11
        found1, cols1 = eng.gather_rows(khash)
        assert found1.all()
        for f in cols0:
            assert (cols0[f] == cols1[f]).all(), f
        # decisions continue against the migrated state
        got = eng.check_batch(reqs[:200], NOW + 2)
        assert [r.remaining for r in got] == [97] * 200

    def test_shrink_reports_drops_best_effort(self):
        eng = ShardedEngine(make_mesh(n=2), capacity_per_shard=1 << 9,
                            batch_per_shard=64)
        reqs = [mk(f"s{i}") for i in range(700)]
        got = eng.check_batch(reqs, NOW)
        live = sum(1 for r in got if not r.error)
        dropped = eng.grow(1 << 6)  # 128 slots total for ~700 keys
        assert dropped > 0
        from gubernator_tpu.core.table import occupancy

        assert int(occupancy(eng.state)) == live - dropped
        # surviving rows still serve correct decisions
        got2 = eng.check_batch(reqs, NOW + 1)
        assert any(not r.error and r.remaining == 8 for r in got2)

    def test_auto_grow_on_live_key_pressure(self):
        # tiny table + live keys only: without auto-grow this returns
        # "rate limit table full"; with it, capacity doubles on device
        # and every insert succeeds (the reference's LRU contract)
        eng = ShardedEngine(make_mesh(n=2), capacity_per_shard=1 << 6,
                            batch_per_shard=64,
                            auto_grow_limit=1 << 12)
        reqs = [mk(f"ag{i}", duration=10**7) for i in range(400)]
        got = eng.check_batch(reqs, NOW)
        assert all(r.error == "" for r in got)
        assert eng.cap_local > 1 << 6
        # and the packed lane takes the same path
        eng2 = ShardedEngine(make_mesh(n=2), capacity_per_shard=1 << 6,
                             batch_per_shard=64,
                             auto_grow_limit=1 << 12)
        from gubernator_tpu.core.batch import pack_columns
        from gubernator_tpu.hashing import hash_request_keys
        import numpy as np

        kh = hash_request_keys(["shard"] * 400,
                               [f"ag{i}" for i in range(400)])
        batch, errs = pack_columns(
            kh, np.ones(400, np.int64), np.full(400, 10, np.int64),
            np.full(400, 10**7, np.int64), np.zeros(400, np.int32),
            np.zeros(400, np.int32), np.zeros(400, np.int64), NOW)
        assert not errs
        _, _, _, _, full = eng2.check_packed(batch, kh, NOW)
        assert not full.any()
        assert eng2.cap_local > 1 << 6

    def test_proactive_grow_on_sweep_at_high_occupancy(self):
        import numpy as np

        from gubernator_tpu.hashing import hash_request_keys

        eng = ShardedEngine(make_mesh(n=2), capacity_per_shard=1 << 9,
                            batch_per_shard=64,
                            auto_grow_limit=1 << 12)
        # place ~68% live occupancy directly (upsert_rows never grows,
        # so this models traffic that built up between sweep ticks)
        n = 700
        kh = hash_request_keys(["shard"] * n,
                               [f"pg{i}" for i in range(n)])
        cols = {"meta": np.zeros(n, np.int32),
                "limit": np.full(n, 10, np.int64),
                "duration": np.full(n, 10**7, np.int64),
                "eff_ms": np.full(n, 10**7, np.int64),
                "burst": np.full(n, 10, np.int64),
                "remaining": np.full(n, 9, np.int64),
                "t_ms": np.full(n, NOW, np.int64),
                "expire_at": np.full(n, NOW + 10**7, np.int64)}
        placed = eng.upsert_rows(kh, cols)
        assert placed > 0.6 * eng.cap_local * eng.n
        cap0 = eng.cap_local
        eng.sweep(NOW + 1)
        assert eng.cap_local == cap0 * 2  # grew off the serving path
        found, got = eng.gather_rows(kh[:placed])
        # rows survive the proactive reshard with their values
        assert found.sum() >= placed - 5  # minus any upsert dup drops
        assert (got["remaining"][found] == 9).all()

    def test_grow_is_device_resident(self):
        # the whole point: no host column staging — state stays sharded
        eng = ShardedEngine(make_mesh(n=4), capacity_per_shard=1 << 8,
                            batch_per_shard=32)
        eng.check_batch([mk(f"d{i}") for i in range(100)], NOW)
        eng.grow(1 << 10)
        from jax.sharding import PartitionSpec as P

        import jax

        for word_column in jax.tree.leaves(eng.state):
            assert word_column.sharding.spec == P("shard")
            assert word_column.shape == (4 * (1 << 10),)


#: identities and values at the edges of the table's two-word columns
#: (at most three share a probe sequence: upsert has four claim rounds)
EDGE_KEYS = np.array(
    [0xDEADBEEF << 32, 1 << 32,                          # low word 0
     0xDEADBEEF, 1, 0xFFFFFFFF,                          # high word 0
     (7 << 32) | 300, (8 << 32) | 300, 300, (7 << 32) | 301,  # one shared
     (1 << 63) | 500, (1 << 64) - 1, 1 << 63,            # top bits
     (1 << 63) | (1 << 31) | 40, (1 << 31) | 90], np.uint64)
EDGE_VALUES = np.array(
    [0, 1, -1, 1 << 32, (1 << 32) - 1, -(1 << 32), (1 << 31), -(1 << 31),
     (1 << 63) - 1, -(1 << 63), 1 << 53, (0x7FFFFFFF << 32),
     0xFFFFFFFF, -(1 << 40) + 3], np.int64)


@pytest.mark.parametrize("n_shards", [1, 4])
@pytest.mark.parametrize("program", ["gather", "snapshot", "grow",
                                     "remove", "restore"])
def test_row_programs_at_word_edges(n_shards, program):
    """upsert → {gather, snapshot, grow, remove, restore}: every int64
    a column can hold comes back as it went in, under keys of which
    either word may be 0 (core/table.py › Words)."""
    eng = ShardedEngine(make_mesh(n=n_shards), capacity_per_shard=1 << 8,
                        batch_per_shard=16)
    n = len(EDGE_KEYS)
    cols = {f: np.roll(EDGE_VALUES, i) for i, f in enumerate(
        ("limit", "duration", "eff_ms", "burst", "remaining", "t_ms",
         "expire_at"))}
    cols["meta"] = (np.arange(n) % 4).astype(np.int32)
    assert eng.upsert_rows(EDGE_KEYS, cols) == n
    assert eng.occupancy() == n

    def same_rows(found, got, keys=EDGE_KEYS, want=cols):
        assert found.all()
        for f, col in want.items():
            assert (got[f] == col).all(), f
            assert got[f].dtype == col.dtype, f

    if program == "gather":
        same_rows(*eng.gather_rows(EDGE_KEYS))
        absent = EDGE_KEYS ^ np.uint64(1 << 32)  # one bit of the HIGH word
        absent = absent[~np.isin(absent, EDGE_KEYS)]
        assert not eng.gather_rows(absent)[0].any()
    elif program == "snapshot":
        snap = eng.snapshot()
        assert snap["key"].dtype == np.uint64
        order = np.argsort(snap["key"])
        by_key = np.argsort(EDGE_KEYS)
        assert (snap["key"][order] == EDGE_KEYS[by_key]).all()
        for f, col in cols.items():
            assert snap[f].dtype == col.dtype
            assert (snap[f][order] == col[by_key]).all(), f
    elif program == "grow":
        assert eng.grow(1 << 10) == 0
        same_rows(*eng.gather_rows(EDGE_KEYS))
        assert eng.occupancy() == n
    elif program == "remove":
        assert eng.remove_rows(EDGE_KEYS[::2]) == len(EDGE_KEYS[::2])
        found, got = eng.gather_rows(EDGE_KEYS)
        assert (found == (np.arange(n) % 2 == 1)).all()
        assert (got["remaining"][found] == cols["remaining"][1::2]).all()
        assert eng.occupancy() == n // 2
    else:
        other = ShardedEngine(make_mesh(n=n_shards),
                              capacity_per_shard=1 << 8, batch_per_shard=16)
        assert other.restore(eng.snapshot()) == n
        same_rows(*other.gather_rows(EDGE_KEYS))
        # the XLA sweep on the restored words: expire_at <= now, signed
        now = 1 << 32
        other.sweep(now)
        found, _ = other.gather_rows(EDGE_KEYS)
        assert (found == (cols["expire_at"] > now)).all()


def test_graft_entry_single():
    import __graft_entry__ as ge
    import jax

    fn, args = ge.entry()
    out_state, out = jax.jit(fn)(*args)
    assert int(out.status.sum()) >= 0


def test_graft_entry_multichip():
    import __graft_entry__ as ge

    ge.dryrun_multichip(8)


def test_aggregate_capacity_exceeds_any_single_shard():
    """The config-5 scale argument (SURVEY §5.7) at test scale: a key
    universe far beyond one shard's capacity fits the MESH because
    hash-range sharding spreads it across every shard's table —
    aggregate capacity is n x cap_local.  This is the mechanism that
    carries the 100M-key workload across chips when one HBM can't
    hold it."""
    import numpy as np

    from gubernator_tpu.core.batch import pack_columns
    from gubernator_tpu.hashing import mix64_np, shard_of

    n = 8
    cap_local = 1 << 11                      # 2048 rows per shard
    # auto-grow headroom: open addressing with 8 probes starts failing
    # inserts near 60% load, and the never-fail-insert contract answers
    # with growth (exactly how a production config-5 table is run)
    eng = ShardedEngine(make_mesh(n=n), capacity_per_shard=cap_local,
                        batch_per_shard=256,
                        auto_grow_limit=cap_local * 4)
    n_keys = int(n * cap_local * 0.6)        # 9830 keys: ~5x one shard
    assert n_keys > cap_local * 2

    ids = np.arange(1, n_keys + 1, dtype=np.uint64)
    kh = mix64_np(ids)
    kh = np.where(kh == 0, np.uint64(1), kh)
    B = 2048
    for a in range(0, n_keys, B):
        chunk = kh[a:a + B]
        m = len(chunk)
        batch, errs = pack_columns(
            chunk, np.ones(m, np.int64), np.full(m, 100, np.int64),
            np.full(m, 600_000, np.int64), np.zeros(m, np.int32),
            np.zeros(m, np.int32), np.zeros(m, np.int64),
            1_760_000_000_000)
        assert not errs
        st, lim, rem, rst, full = eng.check_packed(
            batch, chunk, 1_760_000_000_000)
        assert not full.any(), f"dropped rows at {a}"
        assert (np.asarray(rem) == 99).all()

    # every key is resident and readable (no silent resets)
    found, cols = eng.gather_rows(kh[:4096])
    assert found.all()
    assert (np.asarray(cols["remaining"])[:4096] == 99).all()

    # and genuinely spread: every shard holds a fair share
    shards = shard_of(kh, n)
    counts = np.bincount(shards, minlength=n)
    assert counts.min() > 0.6 * n_keys / n, counts.tolist()
    from gubernator_tpu.core.table import occupancy

    assert int(occupancy(eng.state)) == n_keys
