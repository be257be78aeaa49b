"""Pallas fused sweep kernel vs. the XLA reference implementation.

Runs in Pallas interpret mode on the CPU mesh (the sandbox's real-TPU
path uses the compiled kernel; semantics are identical by construction).
"""
import jax
import numpy as np
import pytest

from gubernator_tpu.core.table import (from_host, init_table, occupancy,
                                       sweep_expired, to_host)
from gubernator_tpu.ops.pallas_sweep import sweep_expired_pallas

NOW = 1_767_000_000_000


def with_columns(state, **cols):
    """``state`` with host (int64 / uint64) columns put in its place."""
    return jax.device_put(from_host({**to_host(state), **cols}))


def populated_table(cap=2048, n=500, seed=0, now=NOW):
    rng = np.random.default_rng(seed)
    state = init_table(cap)
    rows = rng.choice(cap, size=n, replace=False)
    key = np.zeros(cap, np.uint64)
    key[rows] = rng.integers(1, 2**63, size=n).astype(np.uint64)
    # include keys with high bit set (uint64 edge) and huge expiries
    key[rows[0]] = np.uint64(2**64 - 17)
    # a live key may have EITHER word 0: only both 0 is the empty mark
    key[rows[3]] = np.uint64(0xDEADBEEF) << np.uint64(32)
    key[rows[4]] = np.uint64(0xDEADBEEF)
    exp = np.zeros(cap, np.int64)
    exp[rows] = now + rng.integers(-50_000, 50_000, size=n)
    exp[rows[1]] = now  # boundary: expire_at == now is dead
    exp[rows[2]] = 2**62  # far future
    exp[rows[3]] = now + 1
    exp[rows[4]] = now + 1
    # the words' own boundaries: same high word with the low word's
    # top bit on either side, one high word below / above, a negative
    # high word, the int64 extremes
    exp[rows[5:13]] = [now ^ (1 << 31), now - (1 << 32), now + (1 << 32),
                       -1, -(1 << 40), -(2**63), 2**63 - 1,
                       (now >> 32) << 32]
    return with_columns(state, key=key, expire_at=exp), key, exp


#: sweep horizons: an epoch-ms clock, one whose low word's top bit is
#: set, one under 2^32 (high word 0), and negative ones (the high word
#: compares SIGNED)
HORIZONS = [NOW, NOW | (1 << 31), (1 << 31) + 5, 0, -1, -(1 << 33) - 7]


@pytest.mark.parametrize("sweep", [sweep_expired,
                                   lambda s, n: sweep_expired_pallas(
                                       s, n, interpret=True)[0]],
                         ids=["xla", "pallas"])
@pytest.mark.parametrize("now", HORIZONS)
def test_sweep_compares_on_words_as_int64_does(sweep, now):
    """``expire_at <= now`` on the words is the int64 comparison, for
    either sweep: exactly the dead rows lose key and expiry."""
    state, key, exp = populated_table(seed=3, now=now)
    got = to_host(sweep(state, np.int64(now)))
    dead = exp <= now
    assert (got["key"] == np.where(dead, np.uint64(0), key)).all()
    assert (got["expire_at"] == np.where(dead, 0, exp)).all()
    assert dead.any() and not dead[key != 0].all()


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_matches_xla_sweep(seed):
    state = populated_table(seed=seed)[0]
    want = sweep_expired(state, np.int64(NOW))
    got, live = sweep_expired_pallas(state, np.int64(NOW), interpret=True)
    got = to_host(got)
    for f, w in to_host(want).items():
        assert (got[f] == w).all(), f
    assert int(live) == int(occupancy(want))


def test_empty_and_full():
    state = init_table(1024)
    got, live = sweep_expired_pallas(state, np.int64(NOW), interpret=True)
    assert int(live) == 0
    # all live
    key = np.arange(1, 1025, dtype=np.uint64)
    exp = np.full(1024, NOW + 1, np.int64)
    state = with_columns(state, key=key, expire_at=exp)
    got, live = sweep_expired_pallas(state, np.int64(NOW), interpret=True)
    assert int(live) == 1024
    assert (to_host(got)["key"] == key).all()


def test_capacity_validation():
    state = init_table(512)  # < one (8,128) tile
    with pytest.raises(ValueError, match="multiple"):
        sweep_expired_pallas(state, np.int64(NOW), interpret=True)


def test_engine_pallas_sweep_path(monkeypatch, cpu_mesh):
    """GUBER_PALLAS_SWEEP=1: the engine's sweep runs the shard_map'd
    kernel and produces the same decisions as the XLA path."""
    from gubernator_tpu.parallel import ShardedEngine
    from gubernator_tpu.types import RateLimitRequest

    monkeypatch.setenv("GUBER_PALLAS_SWEEP", "1")
    eng = ShardedEngine(cpu_mesh, capacity_per_shard=1 << 10,
                        batch_per_shard=64)
    reqs = [RateLimitRequest(name="ps", unique_key=f"k{i}", hits=1,
                             limit=5, duration=5_000) for i in range(40)]
    eng.check_batch(reqs, NOW)
    eng.sweep(NOW + 1)  # nothing expired yet
    assert eng.live_rows == 40
    eng.sweep(NOW + 10_000)  # everything expired
    assert eng.live_rows == 0
    # swept rows behave as fresh on next access
    out = eng.check_batch(reqs, NOW + 20_000)
    assert all(r.remaining == 4 for r in out)
