"""The fused wire ingest does the calendar itself (ISSUE 40):
``ops/_native.cpp › Period`` is ``gregorian.gregorian_expiration`` in
C++, and ``pack_wire_wave`` lays a ``DURATION_IS_GREGORIAN`` row out as
``core/batch.py › pack_columns`` does.

* the C++ period end against ``gregorian_expiration``, all six ordinals,
  on a seeded sweep of clocks and on the calendar's edges — 1 ms either
  side of a minute / hour / day / Monday / month / year boundary, 28 and
  29 Feb of 2024, 2100 (no leap year) and 2400, 31 Dec → 1 Jan, the
  epoch's first week — and ``None`` exactly where the pass declines;
* ``pack_wire_wave`` against ``pack_columns``, the pair byte for byte
  (``greg_end``, ``eff_ms``, the LEAKY clamp of a calendar row, ``now``
  from ``created_at``) and what the pass derives (``greg`` / ``leaky`` /
  ``ood``) against ``lay_out``, on mixed calls: plain + calendar rows,
  two ordinals, two stamps, stamped and unstamped;
* a call with a calendar row the pass cannot reproduce is refused
  WHOLE: an ordinal outside 0..5, a clock outside the calendar.
"""
import calendar

import numpy as np
import pytest

from gubernator_tpu import Algorithm, Behavior, RateLimitRequest
from gubernator_tpu.core.batch import pack_columns
from gubernator_tpu.gregorian import gregorian_expiration
from gubernator_tpu.hashing import mix64_np
from gubernator_tpu.ops import pallas_step as ps
from gubernator_tpu.parallel import ShardedEngine
from gubernator_tpu.parallel.pallas_engine import PallasServingEngine
from gubernator_tpu.types import (DURATION_MAX, GREGORIAN_APPROX_MS,
                                  GregorianDuration)
from gubernator_tpu.wire import req_to_tlv

native = pytest.importorskip("gubernator_tpu.ops.native")

GREG = int(Behavior.DURATION_IS_GREGORIAN)
DAY = 86_400_000
#: 2026-10-01 00:00:37.250 UTC, and a daemon's clock a day behind it
V0 = 1_790_812_837_250
WALL = V0 - DAY
#: the clocks the pass takes: [0001-01-01, 9999-01-01)
CLOCK_MIN = calendar.timegm((1, 1, 1, 0, 0, 0)) * 1000
CLOCK_MAX = calendar.timegm((9999, 1, 1, 0, 0, 0)) * 1000


def at(y, m, d, hh=0, mm=0) -> int:
    return calendar.timegm((y, m, d, hh, mm, 0)) * 1000


def edges() -> list:
    out = []
    bounds = [
        at(2026, 10, 1, 0, 1), at(2026, 10, 1, 13, 0),  # a minute, an hour
        at(2026, 10, 2), at(2026, 10, 5),               # a day, a Monday
        at(2026, 11, 1), at(2027, 1, 1),                # a month, a year
        at(1970, 1, 1), at(1970, 1, 5), at(1969, 12, 29),  # the first week
        at(1999, 12, 31), at(2000, 1, 1),
        CLOCK_MIN + 40 * DAY, CLOCK_MAX - 40 * DAY,
    ]
    for y in (2024, 2100, 2400):  # leap, not leap, leap
        bounds += [at(y, 2, 28), at(y, 2, 28) + DAY, at(y, 3, 1),
                   at(y, 12, 31), at(y + 1, 1, 1)]
    for b in bounds:
        out += [b - 1, b, b + 1]
    return out + [CLOCK_MIN, CLOCK_MAX - 1]


@pytest.mark.parametrize("ordinal", list(GregorianDuration))
def test_the_c_calendar_is_the_python_calendar(ordinal):
    rng = np.random.default_rng([40, int(ordinal)])
    clocks = rng.integers(-2_000_000_000_000, 4_200_000_000_000,
                          20_000).tolist()
    clocks += rng.integers(CLOCK_MIN, CLOCK_MAX, 2_000).tolist() + edges()
    for ms in clocks:
        assert native.gregorian_end(ms, ordinal) == \
            gregorian_expiration(ms, ordinal), (ms, ordinal)
    # a leap day exists in 2024 and 2400 and not in 2100
    if ordinal == GregorianDuration.MONTHS:
        assert [native.gregorian_end(at(y, 2, 28) + DAY, ordinal)
                for y in (2024, 2100, 2400)] == [
            at(2024, 3, 1), at(2100, 4, 1), at(2400, 3, 1)]
    # where the pass declines: a clock outside the calendar
    for ms in (CLOCK_MIN - 1, CLOCK_MAX, -(1 << 62), 1 << 62):
        assert native.gregorian_end(ms, ordinal) is None


@pytest.mark.parametrize("ordinal", [-1, 6, 7, 1 << 40, DURATION_MAX])
def test_an_ordinal_the_calendar_has_not_is_none(ordinal):
    assert native.gregorian_end(V0, ordinal) is None
    with pytest.raises(ValueError):
        gregorian_expiration(V0, ordinal)


# ---- pack_wire_wave against pack_columns --------------------------------

def row(key: str, *, ordinal=None, created=0, leaky=False, **kw):
    """A plain 60 s row, or with ``ordinal`` a calendar row."""
    base = dict(name="nc", unique_key=key, hits=1, limit=50,
                duration=60_000, created_at=created)
    if ordinal is not None:
        base.update(duration=int(ordinal), behavior=GREG)
    if leaky:
        base.update(algorithm=Algorithm.LEAKY_BUCKET, burst=60)
    base.update(kw)
    return RateLimitRequest(**base)


G = GregorianDuration
#: the mixed calls of the parity test, by what they mix
CALLS = {
    "plain_and_calendar": lambda: [
        row(f"k{i}", ordinal=G.MINUTES if i % 3 else None)
        for i in range(40)],
    "two_ordinals": lambda: [
        row(f"k{i}", ordinal=(G.MONTHS, G.HOURS)[i % 2], created=V0)
        for i in range(40)],
    # a minute apart and a month apart: the kept period is asked again
    "two_stamps": lambda: [
        row(f"k{i}", ordinal=G.MINUTES,
            created=(V0, V0 + 60_000, V0 + 31 * DAY)[i % 3])
        for i in range(40)],
    "stamped_and_unstamped": lambda: [
        row(f"k{i}", ordinal=G.DAYS, created=V0 if i % 4 == 0 else 0)
        for i in range(40)],
    # one stamp, one ordinal: a client's call
    "one_period": lambda: [
        row(f"k{i}", ordinal=G.YEARS, created=V0) for i in range(40)],
    # every ordinal TOKEN and LEAKY, clocks either side of every period
    # end, values the clamps take (TD_BOUND // eff of a calendar width)
    "every_ordinal_token_and_leaky": lambda: [
        row(f"k{o}{j}{int(lk)}", ordinal=o, leaky=lk,
            created=(0, V0, gregorian_expiration(V0, o) - 1,
                     gregorian_expiration(V0, o))[j],
            hits=(1, 1 << 40)[j % 2], limit=(50, 1 << 50, -3)[j % 3])
        for o in G for j in range(4) for lk in (False, True)],
    "leaky_plain_and_calendar": lambda: [
        row(f"k{i}", ordinal=(None, G.WEEKS, G.MONTHS)[i % 3],
            leaky=i % 2 == 0, created=V0 + i if i % 5 == 0 else 0)
        for i in range(40)],
}


def both_ways(reqs, domain):
    """(the C++ pass's pair and result, ``pack_columns`` + ``lay_out``'s
    rows and errors) of one call at the daemon's clock ``WALL``."""
    data = b"".join(req_to_tlv(r) for r in reqs)
    n = len(reqs)
    a64 = np.full((8, n), -7, np.int64)
    a32 = np.full((3, n), -7, np.int32)
    res = native.pack_wire_wave(data, WALL, a64, a32, domain)
    p = native.parse_get_rate_limits(data)
    kh = mix64_np(p["khash_raw"])
    b, errs = pack_columns(kh, p["hits"], p["limit"], p["duration"],
                           p["algorithm"], p["behavior"], p["burst"], WALL,
                           created_at=p["created_at"])
    eng = ShardedEngine.__new__(
        ShardedEngine if domain is None else PallasServingEngine)
    return a64, a32, res, eng.lay_out(b, kh), errs


@pytest.mark.parametrize("domain", [None, (ps.VALUE_BOUND, ps.EFF_BOUND)],
                         ids=["full_domain", "kernel_domain"])
@pytest.mark.parametrize("call", CALLS)
def test_the_pass_lays_a_calendar_row_out_as_pack_columns_does(call, domain):
    reqs = CALLS[call]()
    a64, a32, res, rows, errs = both_ways(reqs, domain)
    assert res is not None and not errs and res[0] == len(reqs)
    for r, name in enumerate(("key", "hits", "limit", "duration", "eff_ms",
                              "greg_end", "burst", "now")):
        assert a64[r].tolist() == rows.m64[r].tolist(), name
    assert a64.tobytes() == rows.m64.tobytes()
    assert a32.tobytes() == rows.m32.tobytes()
    # the columns by what they should hold, not only by each other
    cal = [bool(int(r.behavior) & GREG) for r in reqs]
    assert a64[5].tolist() == [
        gregorian_expiration(r.created_at or WALL, r.duration) if c else 0
        for r, c in zip(reqs, cal)]
    assert a64[7].tolist() == [r.created_at or WALL for r in reqs]
    assert a64[4].tolist() == [
        GREGORIAN_APPROX_MS[G(r.duration)] if c else r.duration
        for r, c in zip(reqs, cal)]  # every width here is under EFF_MAX
    ood, leaky, greg, now_lo, now_hi, monotone = res[-1]
    assert greg == rows.greg == sum(cal) and sum(cal) > 0
    assert (None if ood is None else ood.tolist()) == (
        None if rows.ood is None else rows.ood.tolist())
    assert (leaky, now_lo, now_hi, monotone) == (
        rows.leaky, rows.now_lo, rows.now_hi, rows.monotone)
    if domain is None:
        assert ood is None
        assert leaky == sum(int(r.algorithm) == 1 for r in reqs)
    elif "leaky" in call:
        # a LEAKY row of MONTHS / YEARS is outside the kernel's domain
        # by its width (≥ 2^31 ms), inside the XLA step's
        wide = [i for i, r in enumerate(reqs)
                if cal[i] and int(r.algorithm) == 1
                and r.duration >= G.MONTHS]
        assert wide and set(wide) <= set(ood.tolist())


#: calendar rows only the classic lane answers, and where they sit
REFUSED = {
    "ordinal_6": dict(ordinal=6),
    "ordinal_over_duration_max": dict(ordinal=DURATION_MAX + 9),
    "ordinal_negative": dict(ordinal=-1),
    "stamp_past_the_calendar": dict(ordinal=G.MINUTES, created=CLOCK_MAX),
}


@pytest.mark.parametrize("where", [0, 5, 10])
@pytest.mark.parametrize("what", REFUSED)
def test_a_calendar_row_the_pass_cannot_reproduce_refuses_the_call(what,
                                                                   where):
    good = [row(f"k{i}", ordinal=G.HOURS if i % 2 else None)
            for i in range(10)]
    reqs = good[:where] + [row("odd", **REFUSED[what])] + good[where:]
    a64, a32, res, rows, errs = both_ways(reqs, None)
    assert res is None
    if what.startswith("ordinal"):
        # the classic lane: an error on that row alone
        assert list(errs) == [where] and "ordinal" in errs[where]
        assert rows.valid.tolist() == [i != where for i in range(11)]
    # and without the odd row the pass serves the call
    assert both_ways(good, None)[2] is not None


def test_an_unstamped_row_outside_the_calendar_refuses_too():
    data = req_to_tlv(row("u", ordinal=G.MONTHS))
    a64, a32 = np.empty((8, 1), np.int64), np.empty((3, 1), np.int32)
    assert native.pack_wire_wave(data, CLOCK_MAX, a64, a32) is None
    assert native.pack_wire_wave(data, CLOCK_MIN - 1, a64, a32) is None
    n, *_ = native.pack_wire_wave(data, CLOCK_MAX - 1, a64, a32)
    assert n == 1 and a64[5, 0] == CLOCK_MAX
    # a plain row takes any clock, as before
    plain = req_to_tlv(row("p"))
    assert native.pack_wire_wave(plain, CLOCK_MAX, a64, a32) is not None
