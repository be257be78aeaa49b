"""Wave-bucket routing (ShardedEngine._build_waves): coalesced bursts
must ride one big launch with a small-launch overflow tail — never a
second nearly-empty big launch — while preserving per-shard request
order (duplicate-key sequential parity depends on it)."""
import numpy as np
import pytest

from gubernator_tpu.hashing import shard_of
from gubernator_tpu.parallel import ShardedEngine, make_mesh


@pytest.fixture(scope="module")
def eng():
    return ShardedEngine(make_mesh(n=2), capacity_per_shard=1 << 10,
                         batch_per_shard=64)


def keys_for_shard(eng, shard, count, rng):
    """Uniform random hashes filtered to one shard."""
    out = []
    while len(out) < count:
        h = rng.integers(1, 2**64, dtype=np.uint64)
        if int(shard_of(int(h), eng.n)) == shard:
            out.append(h)
    return np.array(out, np.uint64)


class TestBuildWaves:
    def test_small_batch_takes_small_bucket(self, eng):
        rng = np.random.default_rng(3)
        kh = rng.integers(1, 2**64, size=40, dtype=np.uint64)
        waves = eng._build_waves(kh, np.arange(40))
        assert len(waves) == 1
        idx, slots, bw, densest = waves[0]
        assert bw == eng.wave_buckets[0]
        assert densest == np.bincount(shard_of(kh, eng.n)).max()
        assert sorted(idx.tolist()) == list(range(40))
        assert slots.max() < eng.n * bw

    def test_burst_rides_big_bucket_with_small_tail(self, eng):
        big = eng.wave_buckets[-1]
        rng = np.random.default_rng(4)
        n = eng.n * big + 70  # overflow past one full big wave
        kh = rng.integers(1, 2**64, size=n, dtype=np.uint64)
        waves = eng._build_waves(kh, np.arange(n))
        assert len(waves) == 2
        assert waves[0][2] == big
        # the overflow tail (≤ ~70 per shard) must NOT pay a second
        # big-shaped launch
        assert waves[1][2] == eng.wave_buckets[0]

    def test_slots_unique_and_in_range(self, eng):
        rng = np.random.default_rng(5)
        n = eng.n * eng.wave_buckets[-1] + 200
        kh = rng.integers(1, 2**64, size=n, dtype=np.uint64)
        covered = set()
        for idx, slots, bw, densest in eng._build_waves(kh, np.arange(n)):
            assert densest == np.bincount(slots // bw).max()
            assert len(np.unique(slots)) == len(slots)
            assert slots.min() >= 0 and slots.max() < eng.n * bw
            # slot's shard block must match the key's shard
            assert np.array_equal(slots // bw, shard_of(kh[idx], eng.n))
            covered.update(idx.tolist())
        assert covered == set(range(n))

    def test_per_shard_request_order_preserved(self, eng):
        """Within a shard, earlier pending positions get earlier slots
        (and earlier waves): duplicate keys apply in submission order."""
        rng = np.random.default_rng(6)
        kh0 = keys_for_shard(eng, 0, 150, rng)  # one hot shard
        waves = eng._build_waves(kh0, np.arange(150))
        seen = []
        for idx, slots, bw, _ in waves:
            order = np.argsort(slots)
            seen.extend(idx[order].tolist())
        assert seen == list(range(150))

    def test_skewed_shard_picks_bucket_for_busiest(self, eng):
        """90 keys on one shard, 5 on the other: bucket must cover the
        busiest shard (90 > 64 → the 8× bucket on base 64)."""
        rng = np.random.default_rng(7)
        kh = np.concatenate([keys_for_shard(eng, 0, 90, rng),
                             keys_for_shard(eng, 1, 5, rng)])
        waves = eng._build_waves(kh, np.arange(95))
        assert len(waves) == 1
        assert waves[0][2:] == (next(b for b in eng.wave_buckets if b >= 90),
                                90)
