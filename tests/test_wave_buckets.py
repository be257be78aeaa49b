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


# ---- the ladder's top rung is what one launch carries (ISSUE 49) --------
#
# On ONE chip the Mosaic engine's default ladder goes on to 16·B rows
# (an operator's may go further: 32·B was measured on the chip and
# served no better), and a wave that the dispatcher no longer cuts at
# 8·B rides ONE launch of the smallest rung that covers it.  B = 8
# here: the ladder is 8 / 64 / 128 and the old top rung 64.

from gubernator_tpu.core.batch import pack_requests  # noqa: E402
from gubernator_tpu.hashing import hash_request_keys  # noqa: E402
from gubernator_tpu.oracle import Oracle  # noqa: E402
from gubernator_tpu.parallel.pallas_engine import (  # noqa: E402
    PallasServingEngine, XlaFusedEngine)
from gubernator_tpu.types import (Algorithm, Behavior,  # noqa: E402
                                  RateLimitRequest)

NOW = 1_790_000_000_000
B = 8
CALL_ROWS = 8


def _one_chip_engine(**kw):
    return PallasServingEngine(make_mesh(n=1), capacity_per_shard=1 << 10,
                               batch_per_shard=B, **kw)


def _calls_of(n_calls, seed):
    """``n_calls`` calls of CALL_ROWS requests, each with its own clock:
    keys that repeat inside a call and across calls, LEAKY_BUCKET rows
    among the TOKEN_BUCKET rows, and one RESET_REMAINING row on a key
    other rows of the wave spend from."""
    rng = np.random.default_rng(seed)
    calls = []
    for j in range(n_calls):
        reqs = []
        for i in range(CALL_ROWS):
            leaky = rng.random() < 0.3
            key = (f"hot{rng.integers(0, 5)}" if rng.random() < 0.5
                   else f"c{j}r{i}")
            reqs.append(RateLimitRequest(
                name="lk" if leaky else "tk", unique_key=key,
                hits=int(rng.integers(0, 4)), limit=20, duration=60_000,
                algorithm=(Algorithm.LEAKY_BUCKET if leaky
                           else Algorithm.TOKEN_BUCKET),
                burst=25 if leaky else 0))
        calls.append((reqs, NOW + 10 * j))
    reset_at = calls[n_calls // 2][0]
    reset_at[3] = RateLimitRequest(
        name="tk", unique_key="hot1", hits=1, limit=20, duration=60_000,
        behavior=Behavior.RESET_REMAINING)
    return calls


def _wave(eng, calls):
    """The calls as ONE dispatcher wave: each laid out by itself, their
    blocks joined, launched, synced.  Returns (response columns, the
    widths of the launches it made)."""
    widths = []
    real = type(eng)._launch_arrays.__get__(eng)

    def spy(a64, a32, *rest):
        widths.append(a64.shape[1])
        return real(a64, a32, *rest)

    eng._launch_arrays = spy
    laid, khs = [], []
    for reqs, now in calls:
        kh = hash_request_keys([r.name for r in reqs],
                               [r.unique_key for r in reqs])
        batch, errs = pack_requests(reqs, now, size=len(reqs),
                                    key_hashes=kh)
        assert not any(errs)
        laid.append(eng.lay_out(batch, kh, None))
        khs.append(kh)
    wave, khash, _ = eng.join_calls(laid, khs, [None] * len(laid))
    token = eng.launch_packed(wave.batch, khash, calls[-1][1])
    try:
        cols = eng.sync_packed(token)
    finally:
        eng.drop_packed(token)
        del eng._launch_arrays
    return [np.array(c) for c in cols], widths


LADDER_32B = (B, 8 * B, 16 * B, 32 * B)  # an operator's: one rung more


@pytest.mark.parametrize("mult,ladder,rung", [
    (1.5, None, 16 * B), (2, None, 16 * B), (4, LADDER_32B, 32 * B)])
@pytest.mark.parametrize("seed", [49, 4949])
def test_wave_past_the_old_top_rung_rides_one_launch(mult, ladder, rung,
                                                     seed):
    """(a) a wave of 1.5× and 2× the old top rung (8·B) is ONE launch on
    the default ladder's 16·B rung — and one of 4× on a 32·B rung, where
    the operator's ladder has one — and answers row for row what the
    same calls answer as 8·B-row waves and what ``oracle.py`` answers."""
    eng, twin = (_one_chip_engine(wave_buckets=ladder),
                 _one_chip_engine(wave_buckets=ladder))
    assert eng.wave_buckets == (ladder or (B, 8 * B, 16 * B))
    calls = _calls_of(int(mult * 8 * B) // CALL_ROWS, seed)
    cols, widths = _wave(eng, calls)
    assert widths == [rung]
    # the same calls as the parent coalesced them: 8·B rows a wave
    per_wave = 8 * B // CALL_ROWS
    old, old_widths = [], []
    for a in range(0, len(calls), per_wave):
        c, w = _wave(twin, calls[a:a + per_wave])
        old.append(c)
        old_widths += w
    assert set(old_widths) == {8 * B}
    for got, *parts in zip(cols, *old):
        assert got.tolist() == np.concatenate(parts).tolist()
    assert not cols[4].any()
    # and the plain reference, one request at a time in wave order
    oracle = Oracle()
    want = [oracle.check(r, now) for reqs, now in calls for r in reqs]
    assert cols[0].tolist() == [int(w.status) for w in want]
    assert cols[1].tolist() == [w.limit for w in want]
    assert cols[2].tolist() == [w.remaining for w in want]
    assert cols[3].tolist() == [w.reset_time for w in want]
    kinds = {(r.algorithm, int(r.behavior)) for reqs, _ in calls
             for r in reqs}
    assert (Algorithm.LEAKY_BUCKET, 0) in kinds
    assert (Algorithm.TOKEN_BUCKET,
            int(Behavior.RESET_REMAINING)) in kinds


def test_wave_past_the_top_rung_still_splits():
    """A wave over ``wave_capacity`` is several launches, as ever: the
    full top rung and the smallest rung that covers the tail."""
    eng = _one_chip_engine()
    calls = _calls_of(16 * B // CALL_ROWS + 1, 7)
    cols, widths = _wave(eng, calls)
    assert widths == [16 * B, B]
    oracle = Oracle()
    want = [oracle.check(r, now) for reqs, now in calls for r in reqs]
    assert cols[2].tolist() == [w.remaining for w in want]


@pytest.mark.parametrize("case,make,want", [
    ("pallas one chip: the ladder goes on to 16B",
     _one_chip_engine, (B, 8 * B, 16 * B)),
    ("pallas on a mesh keeps B, 8B",
     lambda: PallasServingEngine(make_mesh(n=2), capacity_per_shard=1 << 10,
                                 batch_per_shard=B), (B, 8 * B)),
    ("the XLA engine keeps B, 8B",
     lambda: ShardedEngine(make_mesh(n=1), capacity_per_shard=1 << 10,
                           batch_per_shard=B), (B, 8 * B)),
    ("the XLA engine on a mesh keeps B, 8B",
     lambda: ShardedEngine(make_mesh(n=2), capacity_per_shard=1 << 10,
                           batch_per_shard=B), (B, 8 * B)),
    ("xla-fused keeps its small ladder",
     lambda: XlaFusedEngine(make_mesh(n=1), capacity_per_shard=1 << 10,
                            batch_per_shard=B),
     XlaFusedEngine.SMALL_WAVE_BUCKETS),
    ("the constructor's ladder wins on one chip too",
     lambda: _one_chip_engine(wave_buckets=(16, 48)), (16, 48)),
])
def test_wave_capacity_is_the_ladders_top_rung(case, make, want,
                                               monkeypatch):
    """(b) ``wave_capacity`` is ``wave_buckets[-1]`` everywhere, and
    only the one-chip Mosaic engine's DEFAULT ladder is longer."""
    monkeypatch.delenv("GUBER_WAVE_BUCKETS", raising=False)
    eng = make()
    assert eng.wave_buckets == want, case
    assert eng.wave_capacity == want[-1]
    with pytest.raises(AttributeError):
        eng.wave_capacity = 1  # read-only


@pytest.mark.parametrize("make", [
    _one_chip_engine,
    lambda: ShardedEngine(make_mesh(n=1), capacity_per_shard=1 << 10,
                          batch_per_shard=B),
    lambda: XlaFusedEngine(make_mesh(n=1), capacity_per_shard=1 << 10,
                           batch_per_shard=B),
], ids=["pallas_n1", "xla_n1", "xla_fused_n1"])
def test_wave_buckets_env_is_the_one_override(make, monkeypatch):
    """(b) under GUBER_WAVE_BUCKETS every engine's ladder is the
    operator's, and its capacity that ladder's top rung."""
    monkeypatch.setenv("GUBER_WAVE_BUCKETS", "128, 32")
    eng = make()
    assert eng.wave_buckets == (32, 128)
    assert eng.wave_capacity == 128


def test_wave_rungs_tool_reads_two_scrapes(tmp_path):
    """tools/wave_rungs.py: rows, launches, slots and padding a wave and
    the share of waves on each rung, from two scrapes of /metrics."""
    import json
    import subprocess
    import sys

    from gubernator_tpu.metrics import Metrics

    eng = ShardedEngine.__new__(ShardedEngine)
    m = eng.metrics_ref = Metrics()
    m.wave_size.observe(500)  # before the first scrape: not counted
    eng._count_route("identity", 1024, 500, 500)
    first = tmp_path / "m0.txt"
    first.write_bytes(m.render())
    for rows, rung in ((1000, 1024), (7996, 8192), (12000, 16384),
                       (12001, 16384), (20000, 32768)):
        m.wave_size.observe(rows)
        eng._count_route("sorted", rung, rows, rows)
    eng._count_route("sorted", 8192, 1800, 1800)  # a tiered wave's retry
    second = tmp_path / "m1.txt"
    second.write_bytes(m.render())
    tool = [sys.executable, "tools/wave_rungs.py"]
    out = subprocess.run(tool + [str(first), str(second)], check=True,
                         capture_output=True, text=True).stdout
    rep = json.loads(out.splitlines()[-1])
    assert rep["waves"] == 5
    assert rep["rows_per_wave"] == round(52997 / 5, 1)
    assert rep["wave_share_by_rung_pct"] == {
        "le_1024": 20.0, "le_8192": 20.0, "le_16384": 40.0,
        "le_32768": 20.0, "over": 0.0}
    assert rep["launches_per_wave"] == 1.2
    slots = 1024 + 8192 + 2 * 16384 + 32768 + 8192
    assert rep["slots_per_wave"] == round(slots / 5, 1)
    assert rep["pad_share_pct"] == round(100 * (1 - 54797 / slots), 3)
    # no wave between the scrapes: nothing to report, exit 1
    r = subprocess.run(tool + [str(second), str(second)],
                       capture_output=True, text=True)
    assert r.returncode == 1 and r.stdout.strip() == "null"
