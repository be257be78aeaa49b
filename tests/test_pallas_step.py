"""Bit-parity of the Pallas decision-step kernel (interpret mode) vs
the XLA step on shared TOKEN_BUCKET request streams.

The kernel owns its table layout (bucketized AoS vs the XLA SoA), so
parity is asserted on DECISIONS (status/remaining/reset/limit/err) and
on the aggregate counters — exactly the contract the oracle-parity
suite pins for the XLA step itself (tests/test_step_parity.py).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gubernator_tpu.core.batch import RequestBatch
from gubernator_tpu.core.step import decide_batch
from gubernator_tpu.core.table import init_table
from gubernator_tpu.ops.pallas_step import (EFF_BOUND, SLOTS, VALUE_BOUND,
                                            decide_batch_pallas,
                                            init_pallas_table,
                                            pallas_qualifies)
from gubernator_tpu.types import Behavior

i64, i32 = jnp.int64, jnp.int32
NOW = 1_760_000_000_000
FIELDS = ("status", "remaining", "reset_time", "limit", "err")


def mk_batch(keys, **over):
    B = len(keys)
    cols = dict(
        key=jnp.asarray(np.asarray(keys, np.uint64)),
        hits=jnp.ones(B, i64), limit=jnp.full(B, 10, i64),
        duration=jnp.full(B, 10_000, i64),
        eff_ms=jnp.full(B, 10_000, i64), greg_end=jnp.zeros(B, i64),
        behavior=jnp.zeros(B, i32), algorithm=jnp.zeros(B, i32),
        burst=jnp.full(B, 10, i64), valid=jnp.ones(B, bool),
        now=jnp.zeros(B, i64))
    cols.update(over)
    return RequestBatch(**cols)


def keyify(ids):
    k = (np.asarray(ids, np.uint64) + np.uint64(1)) \
        * np.uint64(0x9E3779B97F4A7C15)
    return np.where(k == 0, np.uint64(1), k)


def run_both(batches, nows, cap=1 << 12):
    pt, st = init_pallas_table(cap), init_table(cap)
    for b, now in zip(batches, nows):
        assert pallas_qualifies(b)
        pt, po = decide_batch_pallas(pt, b, jnp.asarray(now, i64),
                                     interpret=True)
        st, xo = decide_batch(st, b, jnp.asarray(now, i64))
        for f in FIELDS:
            a, c = np.asarray(getattr(po, f)), np.asarray(getattr(xo, f))
            assert (a == c).all(), \
                (f, np.nonzero(a != c)[0][:5].tolist())
        assert int(po.over_count) == int(xo.over_count)
        assert int(po.insert_count) == int(xo.insert_count)
    return pt, st


class TestPallasStepParity:
    def test_zipf_duplicates_multi_batch(self):
        rng = np.random.default_rng(1)
        batches, nows = [], []
        for w in range(6):
            ids = rng.zipf(1.3, size=512) % 200
            hits = rng.integers(0, 4, size=512)  # includes queries
            batches.append(mk_batch(keyify(ids),
                                    hits=jnp.asarray(hits, i64)))
            nows.append(NOW + w * 700)
        run_both(batches, nows)

    def test_expiry_and_refresh(self):
        keys = keyify(np.arange(64))
        batches = [mk_batch(keys, hits=jnp.full(64, 3, i64)),
                   mk_batch(keys, hits=jnp.full(64, 3, i64)),
                   # past expiry: buckets refresh
                   mk_batch(keys, hits=jnp.full(64, 3, i64))]
        run_both(batches, [NOW, NOW + 5_000, NOW + 25_000])

    def test_limit_and_duration_change_in_place(self):
        keys = keyify(np.arange(40))
        b1 = mk_batch(keys, hits=jnp.full(40, 4, i64))
        b2 = mk_batch(keys, limit=jnp.full(40, 25, i64))  # limit up
        b3 = mk_batch(keys, limit=jnp.full(40, 25, i64),
                      duration=jnp.full(40, 60_000, i64),
                      eff_ms=jnp.full(40, 60_000, i64))  # duration change
        b4 = mk_batch(keys, limit=jnp.full(40, 3, i64))  # limit down
        run_both([b1, b2, b3, b4],
                 [NOW, NOW + 100, NOW + 200, NOW + 300])

    def test_reset_and_drain_flags(self):
        rng = np.random.default_rng(2)
        keys = keyify(rng.integers(0, 30, size=256))
        beh = np.zeros(256, np.int32)
        beh[::7] = int(Behavior.RESET_REMAINING)
        beh[3::11] = int(Behavior.DRAIN_OVER_LIMIT)
        hits = rng.integers(0, 6, size=256)
        batches = [mk_batch(keys, hits=jnp.asarray(hits, i64),
                            behavior=jnp.asarray(beh))
                   for _ in range(3)]
        run_both(batches, [NOW, NOW + 50, NOW + 90])

    def test_gregorian_expiry_column(self):
        keys = keyify(np.arange(32))
        greg = np.full(32, NOW + 3_600_000, np.int64)
        beh = np.full(32, int(Behavior.DURATION_IS_GREGORIAN), np.int32)
        b = mk_batch(keys, behavior=jnp.asarray(beh),
                     greg_end=jnp.asarray(greg),
                     eff_ms=jnp.full(32, 3_600_000, i64))
        b2 = mk_batch(keys, behavior=jnp.asarray(beh),
                      greg_end=jnp.asarray(greg + 3_600_000),
                      eff_ms=jnp.full(32, 3_600_000, i64))
        # second batch past the boundary: fresh window adopts new end
        run_both([b, b, b2], [NOW, NOW + 1000, NOW + 3_700_000])

    def test_mixed_per_request_now(self):
        rng = np.random.default_rng(3)
        keys = keyify(rng.integers(0, 20, size=256))
        nows = NOW + rng.integers(0, 3_000, size=256).astype(np.int64)
        b = mk_batch(keys, now=jnp.asarray(nows, i64))
        # XLA path orders by (row, now); the kernel applies in batch
        # order — parity requires per-key-sorted arrival, so sort the
        # batch by (key, now) first, which preserves per-key time order
        order = np.lexsort((np.asarray(nows), np.asarray(b.key)))
        b = RequestBatch(*[jnp.asarray(np.asarray(c)[order]) for c in b])
        run_both([b], [NOW + 5_000])

    def test_invalid_rows_masked(self):
        keys = keyify(np.arange(64))
        valid = np.ones(64, bool)
        valid[10:20] = False
        b = mk_batch(keys, valid=jnp.asarray(valid))
        pt, po = decide_batch_pallas(init_pallas_table(1 << 10), b,
                                     jnp.asarray(NOW, i64),
                                     interpret=True)
        assert (np.asarray(po.status)[10:20] == 0).all()
        assert (np.asarray(po.remaining)[10:20] == 0).all()
        st, xo = decide_batch(init_table(1 << 10), b,
                              jnp.asarray(NOW, i64))
        for f in FIELDS:
            assert (np.asarray(getattr(po, f))
                    == np.asarray(getattr(xo, f))).all(), f

    def test_invalid_first_occupant_does_not_starve_bucket(self):
        """An invalid row that would be a bucket's tile-first occurrence
        must not become its representative: the later VALID same-bucket
        request still gets a real gather + decision + writeback."""
        keys = keyify(np.arange(1, 9))
        # row 0: invalid, same key (→ same bucket) as valid row 5
        key_col = np.concatenate([[np.asarray(keys)[5]], keys[:8]])
        valid = np.ones(9, bool)
        valid[0] = False
        b = mk_batch(key_col, valid=jnp.asarray(valid),
                     hits=jnp.full(9, 4, i64))
        pt, po = decide_batch_pallas(init_pallas_table(1 << 10), b,
                                     jnp.asarray(NOW, i64),
                                     interpret=True)
        st, xo = decide_batch(init_table(1 << 10), b,
                              jnp.asarray(NOW, i64))
        for f in FIELDS:
            assert (np.asarray(getattr(po, f))
                    == np.asarray(getattr(xo, f))).all(), f
        # and the debit persisted to the table
        b2 = mk_batch(key_col, valid=jnp.asarray(valid),
                      hits=jnp.zeros(9, i64))
        pt, po2 = decide_batch_pallas(pt, b2, jnp.asarray(NOW + 1, i64),
                                      interpret=True)
        assert int(po2.remaining[6]) == 6  # 10 - 4, row persisted

    def test_bucket_full_errors_without_corruption(self):
        """> SLOTS distinct keys forced into one bucket: the overflow
        keys err ('table full' contract), the resident keys still
        serve correctly."""
        cap = 256
        nb = cap // SLOTS
        # same low bits → same bucket; distinct high bits
        keys = np.array([(j << 40) | 5 for j in range(1, SLOTS + 4)],
                        np.uint64)
        b = mk_batch(keys)
        pt = init_pallas_table(cap)
        pt, po = decide_batch_pallas(pt, b, jnp.asarray(NOW, i64),
                                     interpret=True)
        err = np.asarray(po.err)
        assert err.sum() == 3  # SLOTS + 3 keys, SLOTS slots
        assert (np.asarray(po.status)[~err] == 0).all()
        assert (np.asarray(po.remaining)[~err] == 9).all()
        # the survivors keep serving (their state was not clobbered)
        pt, po2 = decide_batch_pallas(pt, b, jnp.asarray(NOW + 1, i64),
                                      interpret=True)
        assert (np.asarray(po2.remaining)[~np.asarray(po2.err)] == 8).all()

    def test_sustained_stream_parity(self):
        """Longer adversarial stream: hot keys, queries, flag churn,
        limit churn, expiry windows — 10 sequential batches."""
        rng = np.random.default_rng(7)
        batches, nows = [], []
        t = NOW
        for w in range(10):
            n = 384
            ids = rng.zipf(1.2, size=n) % 100
            hits = rng.integers(0, 5, size=n)
            lim = np.full(n, 10 + (w % 3) * 5, np.int64)
            beh = np.where(rng.random(n) < 0.05,
                           int(Behavior.RESET_REMAINING), 0)
            beh = np.where(rng.random(n) < 0.05,
                           beh | int(Behavior.DRAIN_OVER_LIMIT), beh)
            batches.append(mk_batch(
                keyify(ids), hits=jnp.asarray(hits, i64),
                limit=jnp.asarray(lim),
                behavior=jnp.asarray(beh.astype(np.int32))))
            t += int(rng.integers(0, 6_000))
            nows.append(t)
        run_both(batches, nows)


def mk_leaky(keys, **over):
    base = dict(algorithm=jnp.ones(len(keys), i32),
                limit=jnp.full(len(keys), 10, i64),
                burst=jnp.full(len(keys), 10, i64),
                duration=jnp.full(len(keys), 10_000, i64),
                eff_ms=jnp.full(len(keys), 10_000, i64))
    base.update(over)
    return mk_batch(keys, **base)


class TestPallasLeakyParity:
    """LEAKY_BUCKET parity: the kernel's paired-i32 td fixed point
    (in-kernel 64÷32 restoring division + 32×32→64 multiplies) vs the
    XLA step's native int64 arithmetic — every decision field, every
    wave (mirrors oracle.apply_leaky through test_step_parity's
    XLA-vs-oracle contract)."""

    def test_drain_and_replenish_over_time(self):
        keys = keyify(np.arange(48))
        n = 48
        batches, nows = [], []
        # drain 3/step at rate limit=10 per 10s → leak 1 token/s
        for w in range(8):
            batches.append(mk_leaky(keys, hits=jnp.full(n, 3, i64)))
            nows.append(NOW + w * 700)  # partial-token replenish steps
        run_both(batches, nows)

    def test_burst_differs_from_limit(self):
        keys = keyify(np.arange(32))
        b_hi = mk_leaky(keys, burst=jnp.full(32, 25, i64),
                        hits=jnp.full(32, 4, i64))
        b_lo = mk_leaky(keys, burst=jnp.full(32, 3, i64),
                        hits=jnp.full(32, 2, i64))
        run_both([b_hi, b_hi, b_hi], [NOW, NOW + 100, NOW + 5_000])
        run_both([b_lo, b_lo], [NOW, NOW + 30_000])

    def test_queries_and_flags(self):
        rng = np.random.default_rng(5)
        keys = keyify(rng.integers(0, 24, size=192))
        beh = np.zeros(192, np.int32)
        beh[::5] = int(Behavior.RESET_REMAINING)
        beh[2::7] = int(Behavior.DRAIN_OVER_LIMIT)
        hits = rng.integers(0, 5, size=192)  # queries included
        batches = [mk_leaky(keys, hits=jnp.asarray(hits, i64),
                            behavior=jnp.asarray(beh))
                   for _ in range(4)]
        run_both(batches, [NOW, NOW + 400, NOW + 900, NOW + 12_000])

    def test_eff_change_rescales_td(self):
        keys = keyify(np.arange(40))
        b1 = mk_leaky(keys, hits=jnp.full(40, 4, i64))
        # same window, new denominator: td rescales, fraction kept
        b2 = mk_leaky(keys, duration=jnp.full(40, 60_000, i64),
                      eff_ms=jnp.full(40, 60_000, i64))
        # back down mid-window
        b3 = mk_leaky(keys, duration=jnp.full(40, 7_000, i64),
                      eff_ms=jnp.full(40, 7_000, i64),
                      hits=jnp.full(40, 2, i64))
        run_both([b1, b2, b3], [NOW, NOW + 333, NOW + 666])

    def test_limit_change_and_alg_switch(self):
        keys = keyify(np.arange(24))
        lk = mk_leaky(keys, hits=jnp.full(24, 5, i64))
        lk2 = mk_leaky(keys, limit=jnp.full(24, 30, i64),
                       burst=jnp.full(24, 30, i64))
        tok = mk_batch(keys, hits=jnp.full(24, 2, i64))
        # leaky → leaky(limit change) → TOKEN (alg switch = fresh)
        # → back to leaky (fresh again)
        run_both([lk, lk2, tok, lk],
                 [NOW, NOW + 50, NOW + 100, NOW + 150])

    def test_mixed_token_and_leaky_rows_one_batch(self):
        rng = np.random.default_rng(9)
        n = 256
        ids = rng.integers(0, 40, size=n)
        alg = (ids % 2).astype(np.int32)  # per-key algorithm (stable)
        b = mk_batch(keyify(ids), algorithm=jnp.asarray(alg),
                     hits=jnp.asarray(rng.integers(0, 4, size=n), i64),
                     burst=jnp.full(n, 10, i64))
        run_both([b, b], [NOW, NOW + 800])

    def test_gregorian_leaky_rate(self):
        """DURATION_IS_GREGORIAN leaky: eff is the fixed-width rate
        duration (precomputed eff_ms column), expiry = now + eff."""
        from gubernator_tpu.gregorian import gregorian_rate_duration_ms
        from gubernator_tpu.types import GregorianDuration

        eff = gregorian_rate_duration_ms(int(GregorianDuration.HOURS))
        keys = keyify(np.arange(16))
        beh = np.full(16, int(Behavior.DURATION_IS_GREGORIAN), np.int32)
        b = mk_leaky(keys, behavior=jnp.asarray(beh),
                     duration=jnp.full(16, int(GregorianDuration.HOURS),
                                       i64),
                     eff_ms=jnp.full(16, eff, i64),
                     greg_end=jnp.full(16, NOW + 3_600_000, i64),
                     hits=jnp.full(16, 2, i64))
        run_both([b, b], [NOW, NOW + 60_000])

    def test_td_bounds_stress_carry_paths(self):
        """Counters and eff at the domain edge: td products near 2^61
        drive carries through every paired-i32 primitive (mul halves,
        add/sub borrows, 32-step division with sign-wrapped words)."""
        big_v = VALUE_BOUND - 1       # 2^30 - 1
        big_e = EFF_BOUND - 1         # 2^31 - 1
        keys = keyify(np.arange(12))
        b = mk_leaky(keys, limit=jnp.full(12, big_v, i64),
                     burst=jnp.full(12, big_v, i64),
                     duration=jnp.full(12, big_e, i64),
                     eff_ms=jnp.full(12, big_e, i64),
                     hits=jnp.full(12, big_v // 2, i64))
        # second wave replenishes with a large elapsed × limit product
        run_both([b, b, b], [NOW, NOW + 1_000_000, NOW + big_e + 5])
        # odd eff/hits mixes: division remainders on every lane
        b2 = mk_leaky(keys, limit=jnp.full(12, 999_983, i64),
                      burst=jnp.full(12, 1_000_003, i64),
                      duration=jnp.full(12, 2_147_483_629, i64),
                      eff_ms=jnp.full(12, 2_147_483_629, i64),
                      hits=jnp.full(12, 7, i64))
        run_both([b2, b2], [NOW, NOW + 777_777])

    def test_leaky_bucket_full_errors(self):
        """Overflowing bucket: leaky rows err like token rows."""
        keys = np.array([(j << 40) | 9 for j in range(1, SLOTS + 3)],
                        np.uint64)
        b = mk_leaky(keys)
        pt, po = decide_batch_pallas(init_pallas_table(256), b,
                                     jnp.asarray(NOW, i64),
                                     interpret=True)
        err = np.asarray(po.err)
        assert err.sum() == 2
        assert (np.asarray(po.remaining)[~err] == 9).all()

    def test_sustained_mixed_stream(self):
        """10 waves of mixed token/leaky traffic with churn on every
        axis the kernel branches on."""
        rng = np.random.default_rng(11)
        batches, nows = [], []
        t = NOW
        for w in range(10):
            n = 256
            ids = rng.zipf(1.2, size=n) % 60
            alg = (ids % 2).astype(np.int32)
            beh = np.where(rng.random(n) < 0.06,
                           int(Behavior.RESET_REMAINING), 0)
            beh = np.where(rng.random(n) < 0.06,
                           beh | int(Behavior.DRAIN_OVER_LIMIT), beh)
            dur = np.where(ids % 5 == 0, 25_000, 10_000).astype(np.int64)
            batches.append(mk_batch(
                keyify(ids), algorithm=jnp.asarray(alg),
                hits=jnp.asarray(rng.integers(0, 5, size=n), i64),
                limit=jnp.full(n, 10 + (w % 4) * 7, i64),
                burst=jnp.full(n, 10 + (w % 4) * 7, i64),
                duration=jnp.asarray(dur),
                eff_ms=jnp.asarray(dur),
                behavior=jnp.asarray(beh.astype(np.int32))))
            t += int(rng.integers(0, 9_000))
            nows.append(t)
        run_both(batches, nows)


class TestPropertyParity:
    """Hypothesis fuzz: ANY token/leaky stream inside the kernel's
    domain must match the XLA step exactly (same pattern as
    test_property_parity.py, scaled by GUBER_FUZZ_X)."""

    def test_any_stream_matches_xla(self):
        import os as _os

        from hypothesis import HealthCheck, given, settings
        from hypothesis import strategies as st

        _FX = int(_os.environ.get("GUBER_FUZZ_X", "1"))

        _beh = st.sampled_from([0, int(Behavior.RESET_REMAINING),
                                int(Behavior.DRAIN_OVER_LIMIT),
                                int(Behavior.RESET_REMAINING
                                    | Behavior.DRAIN_OVER_LIMIT)])
        _row = st.tuples(
            st.integers(0, 11),     # key id (forced dups)
            st.integers(0, 6),      # hits
            st.integers(0, 30),     # limit
            st.integers(1, 50_000),  # duration
            _beh,
            st.integers(0, 1),      # algorithm (token/leaky)
            st.integers(0, 35),     # burst (leaky; 0 → limit upstream,
                                    # here passed through as-is)
        )
        _stream = st.lists(
            st.tuples(st.lists(_row, min_size=1, max_size=32),
                      st.integers(0, 40_000)),
            min_size=1, max_size=4)

        B = 32  # fixed batch shape → one compiled program per mode

        @settings(max_examples=_FX * 15, deadline=None,
                  suppress_health_check=[HealthCheck.too_slow])
        @given(_stream)
        def run(stream):
            pt, st_x = init_pallas_table(1 << 9), init_table(1 << 9)
            now = NOW
            for rows, dt in stream:
                now += dt
                n = len(rows)
                ids = np.array([r[0] for r in rows])
                pad = B - n
                b = mk_batch(
                    np.pad(keyify(ids), (0, pad), constant_values=1),
                    hits=jnp.asarray(np.pad(
                        [r[1] for r in rows], (0, pad)), i64),
                    limit=jnp.asarray(np.pad(
                        [r[2] for r in rows], (0, pad)), i64),
                    duration=jnp.asarray(np.pad(
                        [r[3] for r in rows], (0, pad),
                        constant_values=1), i64),
                    eff_ms=jnp.asarray(np.pad(
                        [r[3] for r in rows], (0, pad),
                        constant_values=1), i64),
                    behavior=jnp.asarray(np.pad(
                        [r[4] for r in rows], (0, pad)).astype(np.int32)),
                    algorithm=jnp.asarray(np.pad(
                        [r[5] for r in rows], (0, pad)).astype(np.int32)),
                    burst=jnp.asarray(np.pad(
                        [max(r[6], 1) for r in rows], (0, pad),
                        constant_values=1), i64),
                    valid=jnp.asarray(
                        np.arange(B) < n))
                assert pallas_qualifies(b)
                pt, po = decide_batch_pallas(
                    pt, b, jnp.asarray(now, i64), interpret=True)
                st_x, xo = decide_batch(st_x, b, jnp.asarray(now, i64))
                for f in FIELDS:
                    a, c = (np.asarray(getattr(po, f)),
                            np.asarray(getattr(xo, f)))
                    assert (a == c).all(), \
                        (f, rows, np.nonzero(a != c)[0].tolist())

        run()


class TestQualifier:
    def test_domain_bounds(self):
        keys = keyify(np.arange(8))
        assert pallas_qualifies(mk_batch(keys))
        # leaky now qualifies (round-4 kernel extension) …
        assert pallas_qualifies(
            mk_batch(keys, algorithm=jnp.ones(8, i32)))
        # … but unknown algorithm values do not
        assert not pallas_qualifies(
            mk_batch(keys, algorithm=jnp.full(8, 2, i32)))
        assert not pallas_qualifies(
            mk_batch(keys, limit=jnp.full(8, VALUE_BOUND, i64)))
        assert not pallas_qualifies(
            mk_batch(keys, hits=jnp.full(8, -1, i64)))
        # leaky eff must fit the one-word divisor bound
        assert not pallas_qualifies(
            mk_batch(keys, algorithm=jnp.ones(8, i32),
                     eff_ms=jnp.full(8, EFF_BOUND, i64)))
        assert not pallas_qualifies(
            mk_batch(keys, algorithm=jnp.ones(8, i32),
                     eff_ms=jnp.zeros(8, i64)))
        # a token row with huge eff is fine (eff is not divided there)
        assert pallas_qualifies(
            mk_batch(keys, eff_ms=jnp.full(8, EFF_BOUND * 16, i64),
                     duration=jnp.full(8, EFF_BOUND * 16, i64)))
        # invalid rows don't disqualify (they're masked anyway)
        bad_invalid = mk_batch(
            keys, algorithm=jnp.full(8, 2, i32),
            valid=jnp.zeros(8, bool))
        assert pallas_qualifies(bad_invalid)

    def test_rejects_time_inverted_duplicates(self):
        """Same key with DECREASING now in batch order serializes
        differently in the kernel (batch order) than in the XLA path
        (arrival order) — the qualifier must route it to XLA."""
        keys = keyify(np.array([1, 2, 1]))
        nows = np.array([NOW + 100, NOW, NOW + 50], np.int64)
        assert not pallas_qualifies(
            mk_batch(keys, now=jnp.asarray(nows, i64)))
        # sorted per key: qualifies
        nows_ok = np.array([NOW, NOW + 50, NOW + 100], np.int64)
        assert pallas_qualifies(
            mk_batch(keyify(np.array([1, 1, 2])),
                     now=jnp.asarray(nows_ok, i64)))
        # an INVALID row between two time-inverted valid duplicates
        # must not mask the inversion (adjacency check runs on valid
        # rows only)
        keys3 = keyify(np.array([1, 1, 1]))
        nows3 = np.array([NOW + 100, NOW, NOW + 50], np.int64)
        valid3 = np.array([True, False, True])
        assert not pallas_qualifies(
            mk_batch(keys3, now=jnp.asarray(nows3, i64),
                     valid=jnp.asarray(valid3)))
