"""M1 parity harness: device decide_batch vs the M0 oracle, bit-for-bit.

The north-star requires allow/deny parity with the reference semantics
(BASELINE.md); the oracle is the executable form of that contract, so
every stream here asserts exact equality of (status, remaining,
reset_time, limit) for every request.
"""
import numpy as np
import pytest

from gubernator_tpu import Algorithm, Behavior, GregorianDuration, Oracle, RateLimitRequest
from gubernator_tpu.core import decide_batch, init_table, pack_requests
from gubernator_tpu.core.batch import clamp_config
from gubernator_tpu.core.table import to_host
from gubernator_tpu.types import DURATION_MAX, VALUE_MAX

NOW = 1_760_000_000_000
CAP = 1 << 14


def run_stream(batches, cap=CAP, key_hash=None, final=None):
    """batches: list of (reqs, now_ms). Returns list of mismatches.
    ``key_hash`` maps a request's unique_key to the 64-bit identity it
    is filed under (default: the real hash); ``final`` receives the
    table after the last batch."""
    oracle = Oracle()
    state = init_table(cap)
    mismatches = []
    for bi, (reqs, now) in enumerate(batches):
        want = oracle.check_batch(reqs, now)
        hashes = None if key_hash is None else np.array(
            [key_hash[r.unique_key] for r in reqs], np.uint64)
        packed, errs = pack_requests(reqs, now, key_hashes=hashes)
        state, out = decide_batch(state, packed, now)
        status = np.asarray(out.status)
        rem = np.asarray(out.remaining)
        rst = np.asarray(out.reset_time)
        lim = np.asarray(out.limit)
        err = np.asarray(out.err)
        for i, w in enumerate(want):
            if errs[i]:
                continue  # host-side rejected (e.g. bad gregorian ordinal)
            if err[i]:
                mismatches.append((bi, i, "table-full", None, None))
                continue
            got = (int(status[i]), int(rem[i]), int(rst[i]), int(lim[i]))
            exp = (int(w.status), int(w.remaining), int(w.reset_time), int(w.limit))
            if got != exp:
                mismatches.append((bi, i, reqs[i], exp, got))
    if final is not None:
        final.update(to_host(state))
    return mismatches


def assert_parity(batches, cap=CAP, **kw):
    mm = run_stream(batches, cap, **kw)
    assert not mm, f"{len(mm)} mismatches; first 5: {mm[:5]}"


def mk(name="t", key="k", **kw):
    d = dict(hits=1, limit=10, duration=60_000, algorithm=Algorithm.TOKEN_BUCKET)
    d.update(kw)
    return RateLimitRequest(name=name, unique_key=key, **d)


class TestBasicParity:
    def test_single_key_token(self):
        batches = [([mk()] , NOW + i * 100) for i in range(15)]
        assert_parity(batches)

    def test_single_key_leaky(self):
        batches = [([mk(algorithm=Algorithm.LEAKY_BUCKET)], NOW + i * 700)
                   for i in range(30)]
        assert_parity(batches)

    def test_many_unique_keys(self):
        batches = []
        for t in range(5):
            reqs = [mk(key=f"k{i}", hits=1 + i % 3, limit=5 + i % 7)
                    for i in range(100)]
            batches.append((reqs, NOW + t * 1000))
        assert_parity(batches)

    def test_expiry_across_batches(self):
        batches = [
            ([mk(hits=10)], NOW),
            ([mk(hits=1)], NOW + 59_999),   # still over
            ([mk(hits=1)], NOW + 60_000),   # reset
            ([mk(hits=1)], NOW + 200_000),  # reset again
        ]
        assert_parity(batches)

    def test_hits_zero_queries(self):
        batches = [
            ([mk(hits=3)], NOW),
            ([mk(hits=0)], NOW + 1),
            ([mk(hits=100)], NOW + 2),
            ([mk(hits=0)], NOW + 3),  # stored OVER status
        ]
        assert_parity(batches)


class TestDuplicateKeyParity:
    def test_uniform_duplicates_closed_form(self):
        # 7 identical requests for one key in one batch: 5 admitted
        batches = [([mk(limit=5) for _ in range(7)], NOW)]
        assert_parity(batches)

    def test_uniform_duplicates_multi_hit(self):
        batches = [([mk(hits=3, limit=10) for _ in range(5)], NOW)]
        assert_parity(batches)

    def test_mixed_hits_loop_path(self):
        # remaining=5: [3,4,2] → ok, over, ok — the sequential trap
        batches = [
            ([mk(hits=5, limit=10)], NOW),
            ([mk(hits=3), mk(hits=4), mk(hits=2)], NOW + 1),
        ]
        assert_parity(batches)

    def test_mixed_flags_loop_path(self):
        reqs = [
            mk(hits=8),
            mk(hits=5),  # over
            mk(hits=1, behavior=Behavior.RESET_REMAINING),
            mk(hits=4, behavior=Behavior.DRAIN_OVER_LIMIT | Behavior.BATCHING),
            mk(hits=20, behavior=Behavior.DRAIN_OVER_LIMIT),  # over → drain
            mk(hits=0),
        ]
        assert_parity([(reqs, NOW)])

    def test_duplicates_among_many_keys(self):
        rng = np.random.default_rng(0)
        batches = []
        for t in range(4):
            reqs = []
            for _ in range(200):
                k = f"k{rng.integers(0, 30)}"
                reqs.append(mk(key=k, hits=int(rng.integers(0, 4)), limit=20))
            batches.append((reqs, NOW + t * 5_000))
        assert_parity(batches)

    def test_config_change_within_batch(self):
        batches = [(
            [mk(hits=1, limit=100), mk(hits=1, limit=50), mk(hits=1, limit=200)],
            NOW,
        )]
        assert_parity(batches)

    def test_new_key_duplicates_in_one_batch(self):
        # both duplicates miss, must resolve to the SAME row
        batches = [([mk(key="brand-new", limit=3) for _ in range(5)], NOW)]
        assert_parity(batches)


class TestBehaviorParity:
    def test_reset_remaining(self):
        batches = [
            ([mk(hits=10)], NOW),
            ([mk(hits=2, behavior=Behavior.RESET_REMAINING)], NOW + 1),
        ]
        assert_parity(batches)

    def test_drain_over_limit(self):
        batches = [
            ([mk(hits=7)], NOW),
            ([mk(hits=5, behavior=Behavior.DRAIN_OVER_LIMIT)], NOW + 1),
        ]
        assert_parity(batches)

    def test_gregorian_token(self):
        b = Behavior.DURATION_IS_GREGORIAN
        batches = [
            ([mk(hits=2, duration=GregorianDuration.MINUTES, behavior=b)], NOW),
            ([mk(hits=2, duration=GregorianDuration.MINUTES, behavior=b)], NOW + 30_000),
            ([mk(hits=2, duration=GregorianDuration.MINUTES, behavior=b)], NOW + 70_000),
        ]
        assert_parity(batches)

    def test_invalid_gregorian_is_host_error(self):
        reqs = [mk(duration=99, behavior=Behavior.DURATION_IS_GREGORIAN), mk(key="ok")]
        packed, errs = pack_requests(reqs, NOW)
        assert "invalid gregorian" in errs[0]
        assert errs[1] == ""
        assert not packed.valid[0] and packed.valid[1]

    def test_leaky_burst_and_duration_change(self):
        L = Algorithm.LEAKY_BUCKET
        batches = [
            ([mk(algorithm=L, hits=4, burst=20)], NOW),
            ([mk(algorithm=L, hits=0, duration=120_000, burst=20)], NOW + 500),
            ([mk(algorithm=L, hits=3, duration=120_000, burst=20)], NOW + 1_000),
        ]
        assert_parity(batches)

    def test_algorithm_switch(self):
        batches = [
            ([mk(hits=5)], NOW),
            ([mk(hits=1, algorithm=Algorithm.LEAKY_BUCKET)], NOW + 1),
            ([mk(hits=1)], NOW + 2),
        ]
        assert_parity(batches)


class TestRandomizedParity:
    @pytest.mark.parametrize("seed", [1, 2, 3])
    def test_random_stream(self, seed):
        rng = np.random.default_rng(seed)
        algs = [Algorithm.TOKEN_BUCKET, Algorithm.LEAKY_BUCKET]
        behs = [Behavior.BATCHING, Behavior.RESET_REMAINING,
                Behavior.DRAIN_OVER_LIMIT]
        batches = []
        now = NOW
        for _ in range(6):
            reqs = []
            for _ in range(int(rng.integers(1, 120))):
                reqs.append(RateLimitRequest(
                    name=f"n{rng.integers(0, 3)}",
                    unique_key=f"u{rng.integers(0, 40)}",
                    hits=int(rng.integers(0, 6)),
                    limit=int(rng.integers(1, 30)),
                    duration=int(rng.choice([1_000, 10_000, 60_000])),
                    algorithm=algs[int(rng.integers(0, 2))],
                    behavior=behs[int(rng.integers(0, 3))],
                    burst=int(rng.choice([0, 0, 15])),
                ))
            batches.append((reqs, now))
            now += int(rng.integers(0, 20_000))
        assert_parity(batches)

    def test_zipf_stream(self):
        rng = np.random.default_rng(7)
        batches = []
        now = NOW
        for _ in range(5):
            ks = rng.zipf(1.5, size=256) % 500
            reqs = [mk(key=f"z{k}", limit=50) for k in ks]
            batches.append((reqs, now))
            now += 3_000
        assert_parity(batches)


#: identities whose words are the edges of the two-word key column:
#: only BOTH words 0 is the empty mark, and two keys that share one
#: word are two keys
WORD_EDGE_KEYS = {
    "low word 0": [0xDEADBEEF << 32, 1 << 32, 0xFFFFFFFF << 32],
    "high word 0": [0xDEADBEEF, 1, 0xFFFFFFFF],
    "same low word": [(7 << 32) | 5, (8 << 32) | 5, 5],
    "same high word": [(9 << 32) | 1, (9 << 32) | 2, 9 << 32],
    "top bits": [(1 << 63) | 1, (1 << 63) | (1 << 31), (1 << 64) - 1,
                 (1 << 31), (1 << 63)],
}


class TestWordEdges:
    """The table holds 64-bit columns as two 32-bit words
    (core/table.py): parity at the edges that creates."""

    @pytest.mark.parametrize("case", WORD_EDGE_KEYS)
    @pytest.mark.parametrize("cap", [1 << 4, 1 << 10])
    def test_keys_at_word_edges(self, case, cap):
        # cap 16: every key shares a probe window with the others
        hashes = WORD_EDGE_KEYS[case]
        key_hash = {f"e{i}": h for i, h in enumerate(hashes)}
        final = {}
        batches = []
        for t in range(4):
            reqs = [mk(key=u, hits=1 + i, limit=6,
                       algorithm=(Algorithm.LEAKY_BUCKET if (i + t) % 2
                                  else Algorithm.TOKEN_BUCKET)
                       if t > 1 else Algorithm.TOKEN_BUCKET)
                    for i, u in enumerate(key_hash)]
            batches.append((reqs + reqs[:2], NOW + t * 500))
        assert_parity(batches, cap, key_hash=key_hash, final=final)
        held = final["key"][final["key"] != 0]
        assert sorted(held.tolist()) == sorted(hashes)

    @pytest.mark.parametrize("limit,hits,duration", [
        (1 << 32, 1, 60_000),                  # the high word's first bit
        ((1 << 32) - 1, (1 << 32) - 2, 60_000),  # the low word, full
        ((1 << 40) + 3, 1 << 33, 1 << 33),
        (VALUE_MAX, VALUE_MAX - 1, DURATION_MAX),  # the domain's extremes
        (VALUE_MAX, 0, 1),
    ])
    @pytest.mark.parametrize("alg", [Algorithm.TOKEN_BUCKET,
                                     Algorithm.LEAKY_BUCKET])
    def test_values_past_32_bits(self, limit, hits, duration, alg):
        r = mk(key="big", hits=hits, limit=limit, duration=duration,
               algorithm=alg, burst=limit)
        q = mk(key="big", hits=0, limit=limit, duration=duration,
               algorithm=alg, burst=limit)
        final = {}
        assert_parity([([r], NOW), ([r, q], NOW + 1), ([q, r], NOW + 7),
                       ([r], NOW + (1 << 33))], final=final)
        row = final["key"] != 0
        assert row.sum() == 1
        # what the words spell on the host is what the step stored
        assert final["limit"][row] == clamp_config(
            int(alg), limit, duration, limit, 0)[1]
        assert final["t_ms"][row] >= NOW and final["expire_at"][row] > NOW


def test_donated_step_matches_copy_step():
    """The SERVING default (decide_batch_donated: same impl, table
    donated in/out) must produce outputs and final state bit-identical
    to the non-donated step on the same stream — guards against any
    aliasing misuse at the call boundary (a donated input is dead after
    the call; nothing may re-read it)."""
    from gubernator_tpu.core.step import decide_batch_donated

    rng = np.random.default_rng(3)
    stc = init_table(1 << 12)
    std = init_table(1 << 12)
    for step_i in range(6):
        reqs = [RateLimitRequest(
            name="dm", unique_key=f"k{int(k)}",
            hits=int(rng.integers(0, 3)), limit=20, duration=60_000,
            algorithm=Algorithm.LEAKY_BUCKET if k % 3 == 0
            else Algorithm.TOKEN_BUCKET,
            behavior=Behavior.RESET_REMAINING if k % 17 == 0
            else Behavior.BATCHING)
            for k in rng.integers(0, 60, size=128)]
        now = NOW + step_i * 1000
        packed, _ = pack_requests(reqs, now)
        stc, outc = decide_batch(stc, packed, now)
        std, outd = decide_batch_donated(std, packed, now)
        for f in ("status", "remaining", "reset_time", "limit", "err"):
            np.testing.assert_array_equal(
                np.asarray(getattr(outc, f)), np.asarray(getattr(outd, f)),
                err_msg=f"step {step_i}: {f} diverged")
    for f, c in to_host(stc).items():
        np.testing.assert_array_equal(
            c, to_host(std)[f], err_msg=f"final state col {f} diverged")


def test_tpu_long_division_is_exact():
    """core/step.py › divmod_nn lowers to _long_divmod on TPU (XLA:TPU
    takes ~6 s to compile each native int64 divide).  The routine must
    agree with integer division on the whole domain the step feeds it:
    n in [0, 2^63), d in [1, 2^63), edges included."""
    import jax
    import jax.numpy as jnp

    from gubernator_tpu.core.step import _long_divmod, divmod_nn

    rng = np.random.default_rng(3)
    top = (1 << 63) - 1
    edge = np.array([0, 1, 2, 3, 1 << 31, (1 << 32) - 1, 1 << 32,
                     (1 << 61), (1 << 62) + 12345, top - 1, top],
                    np.int64)
    n = np.concatenate([
        np.repeat(edge, len(edge)),
        rng.integers(0, top, 4000),
        rng.integers(0, 1 << 40, 4000)]).astype(np.int64)
    d = np.concatenate([
        np.tile(np.maximum(edge, 1), len(edge)),
        rng.integers(1, top, 2000), rng.integers(1, 1 << 20, 2000),
        rng.integers(1, 1 << 33, 4000)]).astype(np.int64)
    q, r = jax.jit(_long_divmod)(jnp.asarray(n), jnp.asarray(d))
    assert (np.asarray(q) == n // d).all()
    assert (np.asarray(r) == n % d).all()
    # the public entry (native on this backend) agrees, scalars broadcast
    q2, r2 = divmod_nn(jnp.asarray(n), jnp.asarray(d))
    assert (np.asarray(q2) == n // d).all() and (np.asarray(r2) == n % d).all()
    q3, _ = divmod_nn((1 << 61), jnp.asarray(d))
    assert (np.asarray(q3) == (1 << 61) // d).all()
