"""The older LEAKY_BUCKET request: ONE answer in every copy of the
transition (oracle.py, "Leaky fixed point": *a leaky request stamped at
or before its row's clock leaks nothing, takes nothing back, does not
move the clock or the expiry, spends its hits from what the row holds —
and is answered reset_time = its OWN stamp + eff // limit*).

The referee here is NOT the program's own oracle but the benchmark's
plain reference (benchmark/algorithms/leaky_bucket.py › reference:
upstream's leakyBucket in exact integers, importing nothing of the
program), which is what the cell ``r1-leaky-b1000-sat`` holds the chip
to.  Four copies are served the same requests and compared with it
field by field, exactly: the oracle, the XLA step (core/step.py), the
Pallas kernel (interpret mode) and tiering.py's host mirror.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.algorithms import leaky_bucket as lb
from gubernator_tpu import Algorithm, Oracle, RateLimitRequest
from gubernator_tpu.core.batch import RequestBatch
from gubernator_tpu.core.step import decide_batch
from gubernator_tpu.core.table import init_table
from gubernator_tpu.ops.pallas_step import (decide_batch_pallas,
                                            init_pallas_table)
from gubernator_tpu.tiering import _host_apply

i64, i32 = jnp.int64, jnp.int32
T0 = 1_760_000_000_000
FIELDS = ("status", "remaining", "reset_time", "limit")
#: the fixture's numbers (10000 // 600 = 16 ms, 10000 / 600 is not
#: whole), and a pair whose burst is not its limit and whose duration
#: no multiple of the limit divides
POPS = {
    "fixture": {"name": "l", "keys": 7, "hits": 1, "limit": 600,
                "duration_ms": 10_000, "burst": 600},
    "nondividing": {"name": "n", "keys": 7, "hits": 1, "limit": 7,
                    "duration_ms": 1_000, "burst": 10},
}


def khash(keys) -> np.ndarray:
    k = (np.asarray(keys, np.uint64) + np.uint64(1)) \
        * np.uint64(0x9E3779B97F4A7C15)
    return np.where(k == 0, np.uint64(1), k)


def batch_of(pop: dict, keys, stamps) -> RequestBatch:
    n = len(keys)
    L, D, B = pop["limit"], pop["duration_ms"], lb.burst_of(pop)
    return RequestBatch(
        key=jnp.asarray(khash(keys)), hits=jnp.ones(n, i64),
        limit=jnp.full(n, L, i64), duration=jnp.full(n, D, i64),
        eff_ms=jnp.full(n, D, i64), greg_end=jnp.zeros(n, i64),
        behavior=jnp.zeros(n, i32),
        algorithm=jnp.full(n, int(Algorithm.LEAKY_BUCKET), i32),
        burst=jnp.full(n, B, i64), valid=jnp.ones(n, bool),
        now=jnp.asarray(np.asarray(stamps, np.int64)))


class OracleCopy:
    """oracle.py › apply_leaky, one request after another in the order
    given."""
    order = "batch"

    def __init__(self, pop):
        self.pop, self.o = pop, Oracle()

    def call(self, keys, stamps):
        p = self.pop
        out = [self.o.check(RateLimitRequest(
            name=p["name"], unique_key=str(int(k)), hits=1,
            limit=p["limit"], duration=p["duration_ms"],
            algorithm=Algorithm.LEAKY_BUCKET, burst=lb.burst_of(p)),
            int(s)) for k, s in zip(keys, stamps)]
        return {"status": [int(r.status) for r in out],
                "remaining": [r.remaining for r in out],
                "reset_time": [r.reset_time for r in out],
                "limit": [r.limit for r in out]}


class MirrorCopy:
    """tiering.py › _host_apply over a dict of cold rows."""
    order = "batch"

    def __init__(self, pop):
        self.pop, self.rows = pop, {}

    def call(self, keys, stamps):
        p, cols = self.pop, {f: [] for f in FIELDS}
        for k, s in zip(keys, stamps):
            st, rem, rst, lim, self.rows[int(k)] = _host_apply(
                self.rows.get(int(k)), 1, p["limit"], p["duration_ms"],
                p["duration_ms"], 0, 0, int(Algorithm.LEAKY_BUCKET),
                lb.burst_of(p), int(s))
            for f, v in zip(FIELDS, (st, rem, rst, lim)):
                cols[f].append(v)
        return cols


class _StepCopy:
    def __init__(self, pop):
        self.pop, self.state = pop, self.table()

    def call(self, keys, stamps):
        self.state, out = self.step(
            self.state, batch_of(self.pop, keys, stamps),
            jnp.asarray(int(max(stamps)), i64))
        assert not np.asarray(out.err).any()
        return {f: np.asarray(getattr(out, f)) for f in FIELDS}


class XlaCopy(_StepCopy):
    """core/step.py: a key's rows of one batch apply in (stamp, batch
    index) order — `_leaky_mixed_scan` where the stamps differ."""
    order = "stamp"
    table = staticmethod(lambda: init_table(1 << 10))
    step = staticmethod(decide_batch)


class PallasCopy(_StepCopy):
    """ops/pallas_step.py, interpret mode: strictly batch order."""
    order = "batch"
    table = staticmethod(lambda: init_pallas_table(1 << 10))

    @staticmethod
    def step(table, batch, now):
        return decide_batch_pallas(table, batch, now, interpret=True,
                                   tile=8)


COPIES = {"oracle": OracleCopy, "xla_step": XlaCopy,
          "pallas_step": PallasCopy, "tiering_mirror": MirrorCopy}


def assert_equal(got: dict, want: dict, ctx) -> None:
    for f in FIELDS:
        g, w = np.asarray(got[f], np.int64), np.asarray(want[f], np.int64)
        bad = np.flatnonzero(g != w)
        assert not len(bad), (ctx, f, bad[:5].tolist(), g[bad[:5]].tolist(),
                              w[bad[:5]].tolist())


def one_token_ms(pop) -> int:
    return -(-pop["duration_ms"] // pop["limit"])


# ---- (i) seeded random streams, one request a call ---------------------

def stream(pop: dict, seed: int, n: int):
    """n (key, stamp): mostly a few ms forward, so rows drain and both
    answers are common; now and then a pause (refills, the cap, a row
    remade past ``duration``); a fifth of the stamps step BACK by 1 ms
    to 20 tokens' leak."""
    rng = np.random.default_rng([seed, pop["limit"]])
    one = one_token_ms(pop)
    keys = rng.integers(0, pop["keys"], n)
    t, stamps = T0, []
    for _ in range(n):
        u = rng.random()
        if u < 0.2:  # one straggler: the clock it left goes on
            stamps.append(t - int(rng.integers(1, 20 * one + 1)))
            continue
        if u < 0.97:
            t += int(rng.integers(0, max(2, one // 4)))
        elif u < 0.995:
            t += int(rng.integers(one, pop["duration_ms"] // 2))
        else:
            t += pop["duration_ms"] + int(rng.integers(0, 3 * one))
        stamps.append(t)
    return keys, np.asarray(stamps, np.int64)


@pytest.mark.parametrize("seed", [27, 2_700_000_011])
@pytest.mark.parametrize("pop", list(POPS))
@pytest.mark.parametrize("copy", list(COPIES))
def test_random_stream_equals_the_plain_reference(copy, pop, seed):
    pop = POPS[pop]
    n = 1100
    keys, stamps = stream(pop, seed, n)
    ref, eng = lb.reference(pop), COPIES[copy](pop)
    for j in range(n):
        want = ref.call(keys[j:j + 1], int(stamps[j]))
        got = eng.call(keys[j:j + 1], stamps[j:j + 1])
        assert_equal(got, want, (copy, j, int(keys[j]), int(stamps[j])))
    c = ref.counts
    # the stream got where it was meant to
    assert c["older_requests"] >= n // 8 and c["older_admitted"] >= 1, c
    assert c["partial_refills"] and c["capped_refills"] \
        and c["remade_rows"], c


# ---- (ii) the benchmark's replay, whose tail steps back ----------------

@pytest.mark.parametrize("copy", list(COPIES))
def test_replay_tail_equals_the_plain_reference(copy):
    """The cell's own replay plan at the fixture's numbers (calls of 200
    requests over 2 keys, one stamp a call, so a key's rows of a call
    are served in batch order by every copy): drain; back 1 ms on the
    drained rows; back 20.5 tokens' leak and then 1.5 tokens' behind
    the clocks on rows that hold tokens; forward 1.5 tokens' leak from
    the clocks, which tells a clock that was turned back; back 1 ms."""
    pop = dict(POPS["fixture"], keys=1000)
    D, one = pop["duration_ms"], one_token_ms(pop)
    frac, back = one + one // 2, 20 * one + one // 2
    plan = lb.replay_plan(pop, {"requests_per_call": 200}, 1, None)
    at = [t for t, _ in plan]
    assert [b - a for a, b in zip(at[-7:], at[-6:])] == \
        [-1, 1 + 3 * D // 5, -back, back - frac, 2 * frac, -1]
    ref, eng = lb.reference(pop), COPIES[copy](pop)
    for c, (t, idx) in enumerate(plan):
        stamp = T0 + t
        assert_equal(eng.call(idx, np.full(len(idx), stamp)),
                     ref.call(idx, stamp), (copy, "call", c, t))
    assert all(got >= need for _, got, need in lb.replay_floors(
        {"reference_counts": ref.counts, "reference_over_limit": 1}))


# ---- (iii) one wave that merges two calls of different stamps ----------

@pytest.mark.parametrize("tokens", ["rows_hold_tokens", "rows_run_dry"])
@pytest.mark.parametrize("copy", ["xla_step", "pallas_step"])
def test_merged_wave_answers_each_call_from_its_own_stamp(copy, tokens):
    """An earlier wave left the rows' clocks at T0 + 40.  One wave then
    merges call A (stamp T0 + 10) and call B (stamp T0 + 25) on the same
    keys, so every row of it is older than its row's clock.  Each answer
    carries its OWN stamp + duration // limit, nothing leaks, and the
    state equals the reference served in the copy's serial order: the
    XLA step sorts a key's rows by stamp (`_leaky_mixed_scan` where all
    are admitted, its while_loop where a row runs dry), the kernel takes
    them in batch order — B's rows before A's here, stamps stepping back
    inside the wave too."""
    pop = POPS["fixture"]
    rate = pop["duration_ms"] // pop["limit"]
    ref, eng = lb.reference(pop), COPIES[copy](pop)
    keys = np.arange(5)
    # the earlier wave: a full row loses 3 tokens, or all but 4 of them
    first = np.repeat(keys, 3 if tokens == "rows_hold_tokens"
                      else pop["burst"] - 4)
    assert_equal(eng.call(first, np.full(len(first), T0 + 40)),
                 ref.call(first, T0 + 40), "first wave")
    call = np.tile(keys, 3)  # three hits a key a call: six of them want
    kk = np.concatenate([call, call])  # more than the 4 a dry row has
    ss = np.concatenate([np.full(len(call), T0 + 25),
                         np.full(len(call), T0 + 10)])
    got = eng.call(kk, ss)
    assert (np.asarray(got["reset_time"]) == ss + rate).all()
    serial = np.argsort(ss, kind="stable") if eng.order == "stamp" \
        else np.arange(len(kk))
    want = {f: np.zeros(len(kk), np.int64) for f in FIELDS}
    for j in serial:
        for f, v in ref.call(kk[j:j + 1], int(ss[j])).items():
            want[f][j] = v[0]
    assert_equal(got, want, "merged wave")
    over = int((want["status"] == lb.OVER).sum())
    assert over == (0 if tokens == "rows_hold_tokens" else 10), over
    # the state: the clocks still stand at T0 + 40, so 25 ms on they
    # have leaked 1.5 tokens (a clock turned back to T0 + 10: 3.3)
    assert_equal(eng.call(kk, np.full(len(kk), T0 + 65)),
                 ref.call(kk, T0 + 65), "state after the merged wave")
    assert ref.counts["older_requests"] == len(kk)
