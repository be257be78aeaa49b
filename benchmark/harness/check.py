"""The comparison that decides ``correct``.

Two comparisons, both exact (limit 0 on every count):

``window_violations`` — EVERY answer of the measured window, whatever
order the concurrent callers' requests reached the table in.  A token
bucket of hits=1 leaves a trail that any serial order must show: the
answers of one key that carry one ``reset_time`` are one bucket
lifetime; its UNDER_LIMIT answers carry ``remaining`` start−1, start−2,
… each exactly once; OVER_LIMIT answers carry 0 and appear only once 0
was reached; the answer that opened the lifetime (remaining = limit−1)
has ``reset_time`` = its own stamp + duration; a lifetime opens only
after the one before has expired; and a lifetime that is a RESTORED
row's starts from that row's restored ``remaining`` and ``expire_at``.

``replay_mismatches`` — a single caller's seeded stream after the
window, answer for answer against the reference walked in the same
order, crossing the bucket's expiry.
"""
from __future__ import annotations

import numpy as np

from . import reference, rows


def expand(rec: dict) -> dict:
    """Per-call records → one row per answer (usable calls only)."""
    usable = rec["ok"] & (rec["answered"] == rec["n"])
    per_call = np.repeat(usable, rec["n"])
    out = {k: rec[k][per_call] for k in
           ("key_index", "status", "limit", "remaining", "reset_time")}
    out["stamp"] = np.repeat(rec["stamp"], rec["n"])[per_call]
    return out


def window_violations(ans: dict, pop: dict, seed: int, v0: int) -> dict:
    """Counts of answers (or lifetimes) that no serial order explains."""
    if pop["hits"] != 1:
        raise ValueError("the trail check is written for hits=1")
    limit, dur = pop["limit"], pop["duration_ms"]
    k, t = ans["key_index"], ans["stamp"]
    s, lim, r, e = (ans["status"], ans["limit"], ans["remaining"],
                    ans["reset_time"])
    out = {"answers": int(len(k))}
    if not len(k):
        return {**out, "violations": 0}
    bad = {
        "limit": int((lim != limit).sum()),
        "status": int(((s != reference.UNDER)
                       & (s != reference.OVER)).sum()),
        "served_after_reset": int((t >= e).sum()),
        "over_with_tokens": int(((s == reference.OVER) & (r != 0)).sum()),
        "remaining_range": int(((s == reference.UNDER)
                                & ((r < 0) | (r >= limit))).sum()),
    }
    o = np.lexsort((r, s, e, k))
    k, t, s, r, e = k[o], t[o], s[o], r[o], e[o]
    newg = np.r_[True, (k[1:] != k[:-1]) | (e[1:] != e[:-1])]
    gs = np.flatnonzero(newg)
    gid = np.cumsum(newg) - 1
    under = s == reference.UNDER
    n_under = np.add.reduceat(under.astype(np.int64), gs)
    n_all = np.diff(np.r_[gs, len(k)])
    gk, ge = k[gs], e[gs]
    if pop.get("restore"):
        restored = ge == rows.expire0(gk, pop, seed, v0)
        start = np.where(restored, rows.remaining0(gk, pop, seed), limit)
    else:
        restored = np.zeros(len(gs), bool)
        start = np.full(len(gs), limit, np.int64)
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
    bad["reset_time"] = int((opener & (t + dur != e)).sum())
    bad["opened_before_expiry"] = int(((gk[1:] == gk[:-1])
                                       & (ge[1:] - dur < ge[:-1])).sum())
    out["lifetimes"] = int(len(gs))
    out["restored_lifetimes"] = int(restored.sum())
    out["over_limit_answers"] = int((s == reference.OVER).sum())
    out["violations"] = int(sum(bad.values()))
    out["by_rule"] = {n: c for n, c in bad.items() if c}
    return out


def replay_mismatches(rec: dict, pop: dict, precision: str = "int64",
                      served: bool = True) -> dict:
    """The single caller's stream against the reference, in order.  The
    replay starts a duration after everything before it has expired, so
    the reference starts empty.  ``served=False`` compares the
    lower-precision walk with the exact one instead (the control)."""
    ref = reference.TokenBucket(pop["limit"], pop["duration_ms"])
    low = (reference.TokenBucket(pop["limit"], pop["duration_ms"],
                                 precision) if not served else None)
    mism = compared = over = 0
    pos = 0
    for c in range(len(rec["n"])):
        n = int(rec["n"][c])
        sl = slice(pos, pos + n)
        pos += n
        want = ref.call(rec["key_index"][sl], int(rec["stamp"][c]))
        if low is not None:
            got = low.call(rec["key_index"][sl], int(rec["stamp"][c]))
        elif rec["ok"][c] and rec["answered"][c] == n:
            got = {f: rec[f][sl] for f in want}
        else:
            mism += n
            compared += n
            continue
        differ = np.zeros(n, bool)
        for f in want:
            differ |= want[f] != got[f]
        mism += int(differ.sum())
        compared += n
        over += int((want["status"] == reference.OVER).sum())
    return {"compared": compared, "mismatches": mism,
            "reference_over_limit": over}


def control_window(rec: dict, pop: dict, seed: int, v0: int,
                   precision: str) -> dict:
    """The CONTROL: the window's own requests, answered by the reference
    in the lower precision (in send order) instead of by the program,
    through the same trail check.  It has to come out with violations."""
    low = reference.TokenBucket(pop["limit"], pop["duration_ms"], precision)
    if pop.get("restore"):
        idx = np.unique(rec["key_index"])
        for i, rem, exp in zip(idx.tolist(),
                               rows.remaining0(idx, pop, seed).tolist(),
                               rows.expire0(idx, pop, seed, v0).tolist()):
            low.seed_row(i, rem, exp)
    cols = {f: [] for f in ("status", "limit", "remaining", "reset_time")}
    pos = 0
    for c in range(len(rec["n"])):
        n = int(rec["n"][c])
        got = low.call(rec["key_index"][pos:pos + n], int(rec["stamp"][c]))
        pos += n
        for f in cols:
            cols[f].append(got[f])
    ans = {f: np.concatenate(v) for f, v in cols.items()}
    ans["key_index"] = rec["key_index"]
    ans["stamp"] = np.repeat(rec["stamp"], rec["n"])
    return window_violations(ans, pop, seed, v0)
