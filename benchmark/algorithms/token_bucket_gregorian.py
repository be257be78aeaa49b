"""TOKEN_BUCKET with DURATION_IS_GREGORIAN: everything of the benchmark
that is specific to it.  The wire's algorithm is 0, as the plain token
bucket's; what makes a population this file's is ``behavior`` 4 and a
``duration_ms`` that is an ORDINAL (``harness/gen.py`` passes it as the
wire's ``duration``; 0 = MINUTES is a proto3 zero and stays off the
wire).  A population states the ordinal's name beside it
(``"gregorian": "MINUTES"``), and ``reference`` refuses a pair that
disagrees.

**The plain reference** (``GregorianTokenBucket``): upstream's token
bucket (``algorithms.go › tokenBucket``) for one fixed limit, in exact
integers, one request after another, whose bucket expires at the END of
the UTC calendar period that holds the stamp of the request that opened
it (upstream: ``GregorianExpiration(clock.Now(), duration)``; here the
clock is the request's own stamp, ``ASSUMED``).  It imports nothing of
the program and no ``datetime``: ``period_end`` is its OWN calendar for
all six ordinals in plain integers (days-from-civil), so a test can hold
the program's ``gregorian_expiration`` to it.

**The window check** (``window_violations``) — EVERY answer of the
measured window, whatever order the concurrent callers' requests reached
the table in.  Write ``s`` for a request's stamp (the whole milliseconds
of its send time, so never after it), ``d`` for the time its call was
answered (``done_ms``: on the stamps' clock, rounded up), ``e`` for the
answer's ``reset_time``, ``end(t)`` / ``start(t)`` for the end and the
start of the calendar period that holds ``t``.  A request is APPLIED at
some instant between ``s`` and ``d``, and the applications of one key's
requests form a serial order.  A LIFETIME is the answers of one key that
carry one ``reset_time``.  Each rule holds for every such serial order
of the reference, so one answer that breaks it is a violation (limit 0):

``limit``, ``status``  the limit is echoed; the status is one of two.
``over_with_tokens``  OVER_LIMIT ⇒ remaining = 0 (hits = 1: it is
    answered only from an empty bucket).
``remaining_range``  UNDER_LIMIT ⇒ 0 ≤ remaining < limit.
``reset_time_not_a_period_end``  ``e`` is the end of a period:
    ``end(e − 1) = e``.  Proof: a row's expiry is only ever written as
    ``end(stamp of its opener)`` or restored as ``end(v0)``.  ∎
``served_after_reset``  ``s < e``.  Proof: a request that finds
    ``s ≥ expiry`` opens a new row with expiry ``end(s) > s``; any other
    is answered from a row with ``expiry > s``.  ∎
``reset_time_ahead_of_the_clock``  ``start(e − 1) ≤ d``: the period
    that ``e`` ends had begun when the answer was given.  Proof: the
    lifetime was opened by a request with stamp ``s_o`` and ``e =
    end(s_o)``, so ``start(e − 1) ≤ s_o``; it was applied no later than
    this answer's request, which was applied by ``d``.  A restored
    lifetime has ``e = end(v0)`` and ``v0 ≤ s``.  ∎  (Not ``e ≤ s +
    period``: a request stamped 1 ms before a boundary may be applied
    after one stamped 1 ms past it has opened the next lifetime, and is
    then answered from that one — in the reference too.  The replay,
    whose order is known, holds every ``reset_time`` to ``end(s)``.)
``opener_reset_time``  the UNDER_LIMIT answer with remaining = limit − 1
    of a lifetime that is not a restored row's has ``e = end(s)``.
    Proof: a lifetime that starts full hands out limit − 1 once, to the
    request that opened it, and that request computed the expiry from
    its own stamp.  ∎  This is the rule that sees a period end read
    from another clock than the request's.
``remaining_repeats_or_skips``, ``lifetime_start``, ``over_before_empty``
    the trail of ``token_bucket.py`` with the calendar in duration's
    place: a lifetime's UNDER_LIMIT answers carry start − 1, start − 2,
    … each exactly once, OVER_LIMIT appears only once 0 was handed out;
    start is the restored ``remaining`` for the lifetime ``(key <
    keys, end(v0))`` — a resident row lives until ``end(v0)``, so no
    other lifetime of that key can carry that ``reset_time`` — and
    ``limit`` for every other: a key the daemon has not seen (index ≥
    ``keys``) opens at limit − 1, and so does every resident key after
    the boundary.
Two lifetimes of a key never overlap, and no answer comes from a
lifetime after the next one opened: both follow from the three
``reset_time`` rules above and need no rule of their own — distinct
period ends lie a whole period apart, and for lifetimes ``e1 < e2`` of a
key every answer ``a`` of the first has ``s_a < e1 ≤ start(e2 − 1) ≤
d_b`` for every answer ``b`` of the second.  Nothing here holds an array
of the key space's size: lifetimes are found by sorting.

**Resident rows** (``snapshot_columns``): row i of a ``restore: true``
population is part-used (``remaining`` 1..limit from the seed), created
inside the calendar period that holds ``v0`` (at or before ``v0``) and
expires at that period's end: the window's first answers come FROM the
restored state, and at the first boundary of the virtual clock every
resident key resets in the same millisecond.

**The replay** (``replay_plan``, written for MINUTES): one caller's
calls by the mix's own draw.  The first is 60,999 ms after the replay's
start (``run.py`` starts it at the window's end: whatever the window
touched has expired by then, and the reference starts empty); then a
pair of calls 1 ms apart at every whole second of the next minute — the
replay starts on a whole second of the stamps' clock, so one pair lies 1
ms either side of the boundary — then calls 7 s apart across the next
boundary.  ``replay_floors`` say that it got there.

**Controls** (``CONTROLS``), each of which the check has to call not
correct: ``wall_clock_period`` (the period end read from a wall clock a
day behind the stamps, which is where ``run.py`` keeps it: the program's
rule until PR 39), ``fixed_60s`` (stamp + the period's nominal length in
the calendar's place), ``float32`` (the time arithmetic in float32, the
nearest step below int64 epoch-ms: 24 bits of 41).
"""
from __future__ import annotations

import numpy as np

from benchmark.harness import rows
from benchmark.harness import traffic as tr
from benchmark.harness.wire import OVER_LIMIT as OVER
from benchmark.harness.wire import UNDER_LIMIT as UNDER

WIRE_ALGORITHM = 0
#: the Behavior bit a population of this file carries
BEHAVIOR = 4
CONTROLS = ("wall_clock_period", "fixed_60s", "float32")
ORDINALS = {"MINUTES": 0, "HOURS": 1, "DAYS": 2, "WEEKS": 3, "MONTHS": 4,
            "YEARS": 5}
#: what the sources leave open, and what this reference does
ASSUMED = {
    "clock": "a bucket's period is the one that holds the STAMP of the "
             "request that opened it (upstream reads clock.Now(); the "
             "program applies a stamped request at its stamp, so the two "
             "are one clock: gubernator_tpu/gregorian.py)",
    "weeks": "start on Monday 00:00 UTC",
    "expired": "stamp >= expiry re-opens the bucket, full",
}

_MIN, _HOUR, _DAY = 60_000, 3_600_000, 86_400_000
#: nominal length of each ordinal's period (``fixed_60s``; a row's eff_ms)
NOMINAL_MS = (_MIN, _HOUR, _DAY, 7 * _DAY, 30 * _DAY, 365 * _DAY)
#: how far ``run.py`` keeps the wall clock behind the stamps
WALL_BEHIND_MS = _DAY


# ---- the calendar, in plain integers ------------------------------------

def days_from_civil(y: int, m: int, d: int) -> int:
    """Days from 1970-01-01 to y-m-d (proleptic Gregorian)."""
    y -= m <= 2
    era = y // 400
    yoe = y - era * 400
    doy = (153 * (m - 3 if m > 2 else m + 9) + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146_097 + doe - 719_468


def civil_from_days(z: int) -> tuple[int, int, int]:
    """(year, month, day) of the day z days from 1970-01-01."""
    z += 719_468
    era = z // 146_097
    doe = z - era * 146_097
    yoe = (doe - doe // 1460 + doe // 36_524 - doe // 146_096) // 365
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = mp + 3 if mp < 10 else mp - 9
    return yoe + era * 400 + (m <= 2), m, d


def period_start(ms: int, ordinal: int) -> int:
    """Epoch-ms of the start of the UTC calendar period that holds ms."""
    if ordinal in (0, 1, 2):
        w = NOMINAL_MS[ordinal]
        return ms // w * w
    day = ms // _DAY
    if ordinal == 3:  # 1970-01-01 was a Thursday; weeks start on Monday
        return (day - (day + 3) % 7) * _DAY
    y, m, _ = civil_from_days(day)
    if ordinal == 4:
        return days_from_civil(y, m, 1) * _DAY
    if ordinal == 5:
        return days_from_civil(y, 1, 1) * _DAY
    raise ValueError(f"gregorian ordinal {ordinal}")


def period_end(ms: int, ordinal: int) -> int:
    """Epoch-ms of the end of the UTC calendar period that holds ms."""
    if ordinal in (0, 1, 2, 3):
        return period_start(ms, ordinal) + NOMINAL_MS[ordinal]
    y, m, _ = civil_from_days(ms // _DAY)
    if ordinal == 4:
        y, m = (y, m + 1) if m < 12 else (y + 1, 1)
        return days_from_civil(y, m, 1) * _DAY
    if ordinal == 5:
        return days_from_civil(y + 1, 1, 1) * _DAY
    raise ValueError(f"gregorian ordinal {ordinal}")


def period_ends(ms: np.ndarray, ordinal: int) -> np.ndarray:
    """``period_end`` of every element: the fixed-width ordinals by
    arithmetic, months and years once a distinct day."""
    ms = np.asarray(ms, np.int64)
    if ordinal in (0, 1, 2, 3):
        w = NOMINAL_MS[ordinal]
        shift = 3 * _DAY if ordinal == 3 else 0
        return (ms + shift) // w * w + w - shift
    days, inv = np.unique(ms // _DAY, return_inverse=True)
    return np.array([period_end(int(d) * _DAY, ordinal) for d in days],
                    np.int64)[inv]


def ordinal_of(pop: dict) -> int:
    o = pop["duration_ms"]
    if not pop.get("behavior", 0) & BEHAVIOR:
        raise ValueError(f"population {pop.get('name')!r}: behavior lacks "
                         "DURATION_IS_GREGORIAN (4)")
    if o not in ORDINALS.values() or ORDINALS.get(
            pop.get("gregorian"), o) != o:
        raise ValueError(f"population {pop.get('name')!r}: duration_ms {o} "
                         f"is no ordinal, or not {pop.get('gregorian')!r}")
    return o


def request_fields(pop: dict) -> dict:
    """Algorithm 0 and burst 0 are proto3 zeros; ``behavior`` and the
    ordinal (as ``duration``) are the template's own fields."""
    return {}


# ---- the plain reference ------------------------------------------------

class GregorianTokenBucket:
    """``control=None`` is the reference; a name of ``CONTROLS`` is that
    control, which differs from it in ``_expire`` (and ``float32`` in
    the comparison with the expiry) alone."""

    def __init__(self, limit: int, ordinal: int, resident: int = 0,
                 control: str | None = None):
        if control is not None and control not in CONTROLS:
            raise ValueError(control)
        period_end(0, ordinal)  # an unknown ordinal is an error here
        self.limit, self.ordinal, self.control = limit, ordinal, control
        self.resident = resident
        self.rows: dict = {}  # key -> [remaining, expire_at]
        self._last = None  # stamp of the call before
        #: what the walk came across (the replay's floors read them)
        self.counts = {"boundaries_crossed": 0,
                       "calls_1ms_either_side_of_a_boundary": 0,
                       "lifetimes_closed_by_a_boundary": 0,
                       "over_limit_answers": 0, "created_keys": 0}

    def seed_row(self, key: int, remaining: int, expire_at: int) -> None:
        self.rows[key] = [int(remaining), int(expire_at)]

    def _expire(self, now: int) -> int:
        ctl, o = self.control, self.ordinal
        if ctl == "wall_clock_period":
            return period_end(now - WALL_BEHIND_MS, o)
        if ctl == "fixed_60s":
            return now + NOMINAL_MS[o]
        if ctl == "float32":
            w = np.float32(NOMINAL_MS[o])
            return int((np.floor(np.float32(now) / w) + np.float32(1)) * w)
        return period_end(now, o)

    def call(self, keys, now: int) -> dict:
        """One call's requests of hits=1, one after another, all on the
        call's stamp → {status, limit, remaining, reset_time}."""
        now = int(now)
        if self._last is not None and now > self._last:
            ends, t = 0, self._last
            while (t := period_end(t, self.ordinal)) <= now:
                ends += 1
            self.counts["boundaries_crossed"] += ends
            self.counts["calls_1ms_either_side_of_a_boundary"] += (
                ends == 1 and now - self._last == 1)
        self._last = now
        fresh = self._expire(now)
        low = self.control == "float32"
        at = float(np.float32(now)) if low else now
        rows_, limit, counts = self.rows, self.limit, self.counts
        out = []
        for k in np.asarray(keys).tolist():
            row = rows_.get(k)
            if row is None or at >= (float(np.float32(row[1])) if low
                                     else row[1]):
                if row is None:
                    counts["created_keys"] += k >= self.resident
                else:
                    counts["lifetimes_closed_by_a_boundary"] += 1
                row = rows_[k] = [limit, fresh]
            if row[0] >= 1:
                row[0] -= 1
                out.append((UNDER, limit, row[0], row[1]))
            else:
                counts["over_limit_answers"] += 1
                out.append((OVER, limit, 0, row[1]))
        out = np.array(out, np.int64).reshape(-1, 4)
        return {"status": out[:, 0], "limit": out[:, 1],
                "remaining": out[:, 2], "reset_time": out[:, 3]}

    def hit(self, key: int, now: int) -> tuple[int, int, int, int]:
        """One request of hits=1 → (status, limit, remaining, reset)."""
        got = self.call([key], now)
        return tuple(int(got[f][0]) for f in
                     ("status", "limit", "remaining", "reset_time"))


def reference(pop: dict, control: str | None = None
              ) -> GregorianTokenBucket:
    if pop["hits"] != 1:
        raise ValueError("the reference is written for hits=1")
    return GregorianTokenBucket(pop["limit"], ordinal_of(pop),
                                pop["keys"], control)


# ---- resident rows ------------------------------------------------------

def remaining0(index: np.ndarray, pop: dict, seed: int) -> np.ndarray:
    """Restored ``remaining`` of key index i: 1..limit."""
    i = np.asarray(index, np.int64)
    return 1 + (i * 7919 + seed % 1000003) % pop["limit"]


def snapshot_columns(pop: dict, seed: int, v0: int) -> dict:
    """All rows of one population, ready for ``engine.restore``: each
    made inside the period that holds ``v0``, expiring at its end."""
    n, o = pop["keys"], ordinal_of(pop)
    i = np.arange(n, dtype=np.int64)
    start = period_start(v0, o)
    lim = np.full(n, pop["limit"], np.int64)
    return {
        "key": rows.key_hash(pop["name"], tr.key_id(i, seed)),
        "meta": np.zeros(n, np.int32),  # TOKEN_BUCKET, UNDER_LIMIT
        "limit": lim, "duration": np.full(n, o, np.int64),
        "eff_ms": np.full(n, NOMINAL_MS[o], np.int64),
        "burst": lim.copy(),
        "remaining": remaining0(i, pop, seed),
        "t_ms": start + (i * 104729 + seed % 1000003) % (v0 - start + 1),
        "expire_at": np.full(n, period_end(v0, o), np.int64),
    }


def seed_reference(ref: GregorianTokenBucket, index: np.ndarray, pop: dict,
                   seed: int, v0: int) -> None:
    """The restored state of those of these key indices that are
    resident, into a reference."""
    index = np.asarray(index, np.int64)
    index = index[index < pop["keys"]]
    e0 = period_end(v0, ordinal_of(pop))
    for i, rem in zip(index.tolist(),
                      remaining0(index, pop, seed).tolist()):
        ref.seed_row(i, rem, e0)


# ---- the window check ---------------------------------------------------

def window_violations(ans: dict, pop: dict, seed: int, v0: int) -> dict:
    """Counts of answers (or lifetimes) that no serial order of the
    reference explains, by rule (the module's docstring proves each)."""
    if pop["hits"] != 1:
        raise ValueError("the trail check is written for hits=1")
    limit, o = pop["limit"], ordinal_of(pop)
    k, t, d = ans["key_index"], ans["stamp"], ans.get("done_ms")
    s, lim, r, e = (ans["status"], ans["limit"], ans["remaining"],
                    ans["reset_time"])
    out = {"answers": int(len(k))}
    if not len(k):
        return {**out, "violations": 0}
    if d is None:  # records without clock readings: the weakest bound
        d = np.full(len(k), np.iinfo(np.int64).max)
    ends, which = np.unique(e, return_inverse=True)
    is_end = np.array([period_end(int(x) - 1, o) == int(x) for x in ends])
    began = np.array([period_start(int(x) - 1, o) for x in ends], np.int64)
    bad = {
        "limit": int((lim != limit).sum()),
        "status": int(((s != UNDER) & (s != OVER)).sum()),
        "served_after_reset": int((t >= e).sum()),
        "over_with_tokens": int(((s == OVER) & (r != 0)).sum()),
        "remaining_range": int(((s == UNDER)
                                & ((r < 0) | (r >= limit))).sum()),
        "reset_time_not_a_period_end": int((~is_end[which]).sum()),
        "reset_time_ahead_of_the_clock": int((began[which] > d).sum()),
    }
    order = np.lexsort((r, s, e, k))
    k, t, s, r, e = (a[order] for a in (k, t, s, r, e))
    newg = np.r_[True, (k[1:] != k[:-1]) | (e[1:] != e[:-1])]
    gs = np.flatnonzero(newg)
    gid = np.cumsum(newg) - 1
    under = s == UNDER
    n_under = np.add.reduceat(under.astype(np.int64), gs)
    n_all = np.diff(np.r_[gs, len(k)])
    gk, ge = k[gs], e[gs]
    restored = np.zeros(len(gs), bool)
    if pop.get("restore"):
        restored = (gk < pop["keys"]) & (ge == period_end(v0, o))
    start = np.where(restored, remaining0(gk, pop, seed), limit)
    # UNDER answers of a lifetime sort first, ascending by remaining
    rmin = np.where(n_under > 0, r[gs], -1)
    rmax = np.where(n_under > 0, r[np.maximum(gs + n_under - 1, 0)], -1)
    step = ~newg[1:] & under[1:] & under[:-1]
    bad["remaining_repeats_or_skips"] = int(
        (step & (r[1:] - r[:-1] != 1)).sum())
    bad["lifetime_start"] = int((rmax != start - 1).sum())
    bad["over_before_empty"] = int(((n_all > n_under) & (rmin != 0)
                                    & ~((n_under == 0) & (start == 0))
                                    ).sum())
    opener = under & (r == limit - 1) & ~restored[gid]
    bad["opener_reset_time"] = int(
        (period_ends(t[opener], o) != e[opener]).sum())
    out["lifetimes"] = int(len(gs))
    out["restored_lifetimes"] = int(restored.sum())
    out["lifetimes_opened_after_a_boundary"] = int(
        ((gk[1:] == gk[:-1]) & (ge[1:] > ge[:-1])).sum())
    out["created_keys"] = int(len(np.unique(gk[gk >= pop["keys"]])))
    out["over_limit_answers"] = int((s == OVER).sum())
    out["violations"] = int(sum(bad.values()))
    out["by_rule"] = {n: c for n, c in bad.items() if c}
    return out


# ---- the replay ---------------------------------------------------------

#: the first call's distance from the replay's start: a bucket the window
#: opened in its last millisecond has expired by then
REPLAY_LEAD_MS = 60_999


def replay_plan(pop: dict, traffic: dict, seed: int, draw) -> list:
    """[(ms after the replay's start, key indices)] of one caller's
    calls, keys by the mix's own draw (``draw(rng)``) from a stream no
    caller of the window uses: one call at 60,999 ms, then at 61,999 +
    1000 j and 62,000 + 1000 j for j = 0..59 (a pair 1 ms apart on
    every whole second of a minute: one of them straddles the
    boundary), then ten calls 7 s apart, across the next boundary."""
    if ordinal_of(pop) != ORDINALS["MINUTES"]:
        raise ValueError("the replay is planned for MINUTES")
    at = [REPLAY_LEAD_MS]
    for j in range(60):
        at += [61_999 + 1000 * j, 62_000 + 1000 * j]
    at += [at[-1] + 7000 * (j + 1) for j in range(10)]
    rng = tr.caller_rng(seed, 1 << 20)
    return [(ms, draw(rng)) for ms in at]


def replay_floors(rep: dict) -> list:
    """[(name, reading, at least)] beside ``replay_answers_compared``."""
    c = rep["reference_counts"]
    return [
        ("replay_boundaries_crossed", c.get("boundaries_crossed", 0), 2),
        ("replay_calls_1ms_either_side_of_a_boundary",
         c.get("calls_1ms_either_side_of_a_boundary", 0), 1),
        ("replay_lifetimes_closed_by_a_boundary",
         c.get("lifetimes_closed_by_a_boundary", 0), 1),
        ("replay_over_limit_answers", c.get("over_limit_answers", 0), 1),
        ("replay_created_keys", c.get("created_keys", 0), 1),
    ]


def summary(win: dict, rep: dict) -> str:
    c = rep["reference_counts"]
    return (f"checked: {win.get('lifetimes', 0)} bucket lifetimes in the "
            f"window ({win.get('restored_lifetimes', 0)} of restored rows, "
            f"{win.get('lifetimes_opened_after_a_boundary', 0)} opened "
            f"after a boundary closed the key's last), "
            f"{win.get('created_keys', 0)} keys the daemon had not seen, "
            f"{win.get('over_limit_answers', 0)} OVER_LIMIT answers; the "
            f"replay crossed {c.get('boundaries_crossed', 0)} boundaries "
            f"and the limit {rep['reference_over_limit']} times")
