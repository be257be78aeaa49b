"""int64 duration parity: calendar-scale millisecond durations (30 days,
1 year) must pass through un-truncated on both algorithms, with device ==
oracle bit-for-bit — including the in-kernel guards (rescale whole-token
clamp + fraction floor above FRAC_SAFE; replenish elapsed guard).

Lifts round-1's 2^31-1 ms (~24.8 day) input ceiling (VERDICT.md missing
item 4): reference algorithms.go takes int64 ms durations, so a plain
30-day TOKEN_BUCKET/LEAKY_BUCKET window is a first-class input.
"""
import numpy as np
import pytest

from gubernator_tpu import Algorithm, Behavior, Oracle, RateLimitRequest
from gubernator_tpu.core import decide_batch, init_table, pack_requests
from gubernator_tpu.types import DURATION_MAX, EFF_MAX, FRAC_SAFE, TD_BOUND

NOW = 1_772_000_000_000
DAY = 86_400_000
MONTH_30 = 30 * DAY            # 2_592_000_000 ms > 2^31-1
YEAR = 365 * DAY


def run_parity(batches, cap=1 << 12):
    oracle = Oracle()
    state = init_table(cap)
    for bi, (reqs, now) in enumerate(batches):
        want = oracle.check_batch(reqs, now)
        packed, errs = pack_requests(reqs, now)
        state, out = decide_batch(state, packed, now)
        for i, w in enumerate(want):
            assert not errs[i] and not bool(out.err[i]), (bi, i)
            got = (int(out.status[i]), int(out.remaining[i]),
                   int(out.reset_time[i]), int(out.limit[i]))
            exp = (int(w.status), int(w.remaining), int(w.reset_time),
                   int(w.limit))
            assert got == exp, (bi, i, reqs[i], exp, got)
    return state


def mk(key="k", **kw):
    d = dict(hits=1, limit=10, duration=MONTH_30,
             algorithm=Algorithm.TOKEN_BUCKET)
    d.update(kw)
    return RateLimitRequest(name="i64", unique_key=key, **d)


class TestThirtyDayDurations:
    def test_token_30d_reset_time_untruncated(self):
        """A 30-day token window expires at exactly now + 30d."""
        oracle = Oracle()
        state = init_table(1 << 10)
        packed, _ = pack_requests([mk()], NOW)
        state, out = decide_batch(state, packed, NOW)
        assert int(out.reset_time[0]) == NOW + MONTH_30
        w = oracle.check_batch([mk()], NOW)[0]
        assert int(w.reset_time) == NOW + MONTH_30

    def test_token_30d_stream(self):
        # spend the bucket across days inside one 30-day window, then
        # cross the boundary and watch it reset
        times = [NOW, NOW + DAY, NOW + 15 * DAY, NOW + MONTH_30 - 1,
                 NOW + MONTH_30, NOW + MONTH_30 + DAY]
        run_parity([([mk(hits=3)], t) for t in times])

    def test_leaky_30d_replenish(self):
        # limit 30 per 30 days = 1 token/day; drain the burst then watch
        # single tokens leak back at day granularity
        r = lambda h: mk(key="lk", hits=h, limit=30, duration=MONTH_30,
                         algorithm=Algorithm.LEAKY_BUCKET)
        batches = [([r(30)], NOW)]                  # drain the bucket
        batches += [([r(1)], NOW + i * DAY) for i in range(1, 8)]
        batches += [([r(0)], NOW + 8 * DAY)]        # query
        run_parity(batches)

    def test_year_long_token(self):
        run_parity([([mk(key="y", duration=YEAR, hits=2)],
                     NOW + i * 30 * DAY) for i in range(14)])


class TestRescaleGuards:
    def test_leaky_rescale_small_to_30d(self):
        """eff crosses FRAC_SAFE: the rescale floors to whole tokens —
        identically on device and oracle."""
        small = mk(key="rs", limit=100, duration=3_600_000,
                   algorithm=Algorithm.LEAKY_BUCKET)
        big = mk(key="rs", limit=100, duration=MONTH_30,
                 algorithm=Algorithm.LEAKY_BUCKET)
        assert MONTH_30 > FRAC_SAFE  # the guard is actually exercised
        run_parity([
            ([small], NOW), ([small], NOW + 1_000),
            ([big], NOW + 2_000),          # rescale up (frac dropped)
            ([big], NOW + DAY),
            ([small], NOW + DAY + 1_000),  # rescale back down
            ([small], NOW + DAY + 2_000),
        ])

    def test_leaky_elapsed_guard(self):
        """Duration shrinks 30d → 1s with a huge limit: elapsed × limit
        would overflow, so the guard must declare the bucket full."""
        big_lim = TD_BOUND // 1000 - 7  # near the 1s-duration ceiling
        first = mk(key="eg", limit=10, duration=MONTH_30,
                   algorithm=Algorithm.LEAKY_BUCKET)
        second = mk(key="eg", hits=5, limit=big_lim, duration=1000,
                    algorithm=Algorithm.LEAKY_BUCKET, burst=big_lim)
        run_parity([([first], NOW),
                    ([second], NOW + 20 * DAY),  # elapsed >> safe bound
                    ([second], NOW + 20 * DAY + 100)])

    def test_duration_above_max_clamps(self):
        """Past DURATION_MAX both sides clamp identically (no wrap)."""
        run_parity([([mk(key="dm", duration=2**60, hits=1)], NOW),
                    ([mk(key="dm", duration=2**60, hits=1)], NOW + 50)])
        assert min(2**60, DURATION_MAX) == DURATION_MAX

    def test_leaky_eff_ceiling(self):
        """Leaky eff clamps at EFF_MAX (~1.09y) — a 2-year leaky window
        behaves as an EFF_MAX window, same on both sides."""
        r = mk(key="ec", limit=100, duration=2 * YEAR,
               algorithm=Algorithm.LEAKY_BUCKET)
        assert 2 * YEAR > EFF_MAX
        run_parity([([r], NOW), ([r], NOW + DAY), ([r], NOW + 100 * DAY)])


class TestWordBoundaries:
    """The table holds every int64 column as two 32-bit words
    (core/table.py): clocks, expiries and counters that CROSS a word
    boundary between two batches must carry into the high word."""

    @pytest.mark.parametrize("alg", [Algorithm.TOKEN_BUCKET,
                                     Algorithm.LEAKY_BUCKET])
    @pytest.mark.parametrize("edge", [1 << 31, 1 << 32, 1 << 33, 1 << 40])
    def test_clock_crosses_a_word_boundary(self, edge, alg):
        from gubernator_tpu.core.table import to_host

        r = lambda h: mk(key="wb", hits=h, limit=50, duration=700,
                         algorithm=alg)
        times = [edge - 500, edge - 1, edge, edge + 1, edge + 150,
                 edge + 199, edge + 200, edge + 1000]
        state = run_parity([([r(1), r(2)], t) for t in times])
        host = to_host(state)
        row = host["key"] != 0
        assert host["t_ms"][row] >= edge  # the high word carried
        assert host["expire_at"][row] > edge

    @pytest.mark.parametrize("limit", [(1 << 32) + 5, (1 << 33) - 1,
                                       (1 << 53)])
    def test_remaining_counts_down_through_a_word_boundary(self, limit):
        """remaining starts above 2^32 and is spent to below it: the
        borrow out of the high word, then the query reads it back."""
        from gubernator_tpu.core.table import to_host

        spend = limit - (1 << 32) + 3  # leaves 2^32 - 3
        r = lambda h: mk(key="rb", hits=h, limit=limit, duration=DAY)
        state = run_parity([([r(1)], NOW), ([r(spend - 1)], NOW + 1),
                            ([r(0), r(5), r(0)], NOW + 2)])
        host = to_host(state)
        assert host["remaining"][host["key"] != 0] == (1 << 32) - 8


class TestFuzzInt64:
    def test_random_durations_parity(self):
        rng = np.random.default_rng(20260730)
        keys = [f"f{i}" for i in range(24)]
        batches = []
        now = NOW
        for _ in range(30):
            reqs = []
            for _ in range(16):
                dur = int(rng.integers(1, 2**40))
                lim = int(rng.integers(1, 2**45))
                reqs.append(RateLimitRequest(
                    name="i64f", unique_key=str(rng.choice(keys)),
                    hits=int(rng.integers(0, 4)),
                    limit=lim, duration=dur,
                    algorithm=(Algorithm.LEAKY_BUCKET
                               if rng.random() < 0.5
                               else Algorithm.TOKEN_BUCKET),
                    burst=int(rng.integers(0, lim + 1)),
                    behavior=(Behavior.RESET_REMAINING
                              if rng.random() < 0.05 else 0)))
            batches.append((reqs, now))
            now += int(rng.integers(1, 10**7))
        run_parity(batches)
