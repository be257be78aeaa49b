"""The cold lane's C++ pass against the Python loop it replaces (ISSUE 42).

``ops/_native.cpp › cold_apply_batch`` serves a wave's cold rows over the
native store; ``TierController._apply_rows`` — ``store.get`` →
``_host_apply`` → ``store.put`` a row — is the lane of the dict store and
the semantic reference.  Here both run on a native store of their own,
wave after wave, and after EVERY wave the five response columns, what
the call returns and the whole store are equal — values outside the step
programs' domain included: Python's integers do not overflow and its
``//`` floors, so the pass must floor, must not wrap, and must raise
``OverflowError`` exactly where ``np.asarray(row, "<i8")`` or a column
assignment does, with the same rows stored and answered before it.
"""
import numpy as np
import pytest

from gubernator_tpu.core.batch import RequestBatch
from gubernator_tpu.tiering import (ROW_COLS, TierController,
                                    _NativeColdStore, _REQ_COLS)
from gubernator_tpu.types import FRAC_SAFE, TD_BOUND, Behavior

_native = pytest.importorskip("gubernator_tpu.ops._native")
if not hasattr(_native, "cold_apply_batch"):  # pragma: no cover - stale build
    pytest.skip("the built extension has no cold_apply_batch",
                allow_module_level=True)

NOW = 1_790_000_000_000
I64_MAX = (1 << 63) - 1
I64_MIN = -(1 << 63)
GREG = int(Behavior.DURATION_IS_GREGORIAN)
RESET = int(Behavior.RESET_REMAINING)
DRAIN = int(Behavior.DRAIN_OVER_LIMIT)
#: values at which 64-bit arithmetic wraps, truncates toward zero or
#: changes a clamp's branch where Python's does not
EXTREMES = np.array(
    [I64_MIN, I64_MIN + 1, -(1 << 62), -(1 << 31) - 1, -1000, -7, -1, 0, 1,
     7, FRAC_SAFE - 1, FRAC_SAFE, FRAC_SAFE + 1, TD_BOUND - 1, TD_BOUND,
     TD_BOUND + 1, 1 << 62, I64_MAX - 1, I64_MAX], np.int64)


def _pair():
    """(the pass's store, the loop's store): two native tables."""
    a, b = _NativeColdStore(_native), _NativeColdStore(_native)
    assert a.apply_batch is not None
    return a, b


def _snapshot(store) -> dict:
    keys, rows = store.snapshot()
    return {int(k): tuple(map(int, r)) for k, r in zip(keys, rows)}


def _wave(rng, n, w, *, nkeys=400, alg=0, behavior=0, durations=(60_000,),
          limits=(100,), hits=(1, 2, 5), step=7_000, zero_now=0.0,
          jitter=0):
    """n plain rows over ``nkeys`` keys at wave ``w``: the columns of
    ``_REQ_COLS`` (a dict) and the khash."""
    kh = (rng.integers(1, nkeys + 1, n).astype(np.uint64)
          * np.uint64(0x9E3779B97F4A7C15))
    dur = rng.choice(durations, n).astype(np.int64)
    lim = rng.choice(limits, n).astype(np.int64)
    now = np.full(n, NOW + w * step, np.int64)
    if jitter:
        now += rng.integers(-jitter, jitter + 1, n)
    if zero_now:
        now[rng.random(n) < zero_now] = 0
    c = {"hits": rng.choice(hits, n).astype(np.int64), "limit": lim,
         "duration": dur, "eff_ms": np.maximum(dur, 1),
         "greg_end": np.zeros(n, np.int64),
         "behavior": np.full(n, behavior, np.int32),
         "algorithm": (np.full(n, alg, np.int32) if alg in (0, 1)
                       else rng.integers(0, 2, n).astype(np.int32)),
         "burst": lim.copy(), "now": now}
    return c, kh


def _apply_both(stores, c, kh, idxs, now_ms):
    """One wave through the pass and through the loop.  Returns what
    each gave (the call's value or the OverflowError it raised) after
    holding the response columns and the stores to each other."""
    n = len(kh)
    req = [c[f] for f in _REQ_COLS]
    outs, got = [], []
    for lane, store in zip(("pass", "loop"), stores):
        # sentinels: a row the call does not serve keeps them
        cols = (np.full(n, -3, np.int32), np.full(n, -4, np.int64),
                np.full(n, -5, np.int64), np.full(n, -6, np.int64),
                np.ones(n, bool))
        try:
            if lane == "pass":
                served, created, keys = store.apply_batch(
                    kh, idxs, req, now_ms, cols)
            else:
                served, created, keys = TierController._apply_rows(
                    store, kh, idxs, req, now_ms, cols)
            got.append((served, created, [int(k) for k in keys]))
        except OverflowError:
            got.append(OverflowError)
        outs.append(cols)
    assert got[0] == got[1]
    for a, b, name in zip(outs[0], outs[1],
                          ("status", "limit", "remaining", "reset", "full")):
        assert (a == b).all(), (name, np.nonzero(a != b)[0][:5].tolist())
    assert _snapshot(stores[0]) == _snapshot(stores[1])
    untouched = np.ones(n, bool)
    untouched[idxs] = False
    assert outs[0][4][untouched].all() and (outs[0][0][untouched] == -3).all()
    return got[0], outs[0]


def _case_waves(rng, case):
    """(columns, khash, idxs) of every wave of a case."""
    n = 600
    for w in range(6):
        kw = {}
        if case == "leaky":
            # a rate change (eff_ms) now and then: the td rescale
            kw = dict(alg=1, limits=(60, 600), step=900, nkeys=40,
                      durations=(60_000, 60_000, 90_000), hits=(1, 5, 40))
        elif case == "gregorian":
            kw = dict(alg=2, behavior=GREG, durations=(1, 2), step=20_000)
        elif case == "reset_remaining":
            kw = dict(alg=2, hits=(0, 1, 50, 200))
        elif case == "drain_over_limit":
            kw = dict(alg=2, behavior=DRAIN, hits=(1, 60, 150), step=500)
        elif case == "duration_change":
            kw = dict(alg=2, durations=(1_000, 60_000, 3_600_000),
                      step=400)
        elif case == "limit_change":
            kw = dict(limits=(5, 100, 1000), hits=(1, 20), step=300)
        elif case == "algorithm_switch":
            kw = dict(alg=2, step=300)
        elif case == "expired_and_missing":
            kw = dict(nkeys=3000, durations=(5_000, 60_000), step=6_000)
        elif case == "hits_zero":
            kw = dict(alg=2, hits=(0,) if w % 2 else (0, 0, 3), nkeys=150,
                      step=300)
        elif case == "duplicates_out_of_order":
            kw = dict(nkeys=24, hits=(0, 1, 3), zero_now=0.3,
                      jitter=2_000, step=1_500, limits=(40,))
        c, kh = _wave(rng, n, w, **kw)
        if case == "gregorian":
            # the period end of the request's own stamp, as the packers
            # compute it: minutes and hours
            width = np.where(c["duration"] == 1, 60_000, 3_600_000)
            c["greg_end"] = (c["now"] // width + 1) * width
            c["eff_ms"] = width.astype(np.int64)
        if case == "reset_remaining":
            c["behavior"][rng.random(n) < 0.3] = RESET
            c["behavior"][rng.random(n) < 0.1] = RESET | DRAIN
        if case in ("leaky", "algorithm_switch", "hits_zero"):
            c["burst"] = np.where(c["algorithm"] == 1, c["limit"] * 2,
                                  c["limit"]).astype(np.int64)
        idxs = np.nonzero(rng.random(n) < 0.85)[0]
        yield c, kh, idxs


@pytest.mark.parametrize("case", [
    "token", "leaky", "gregorian", "reset_remaining", "drain_over_limit",
    "duration_change", "limit_change", "algorithm_switch",
    "expired_and_missing", "hits_zero", "duplicates_out_of_order"])
def test_the_pass_answers_and_stores_what_the_loop_does(case):
    rng = np.random.default_rng(
        4200 + sum(map(ord, case)))
    stores = _pair()
    over = created = 0
    for c, kh, idxs in _case_waves(rng, case):
        got, cols = _apply_both(stores, c, kh, idxs, NOW)
        assert got is not OverflowError
        assert got[0] == len(idxs) and not cols[4][idxs].any()
        created += got[1]
        over += int((cols[0][idxs] == 1).sum())
        if case == "duplicates_out_of_order":
            # 600 rows over 24 keys: each key 2-50 times a wave
            assert len(got[2]) <= 24 < got[0]
    assert created > 0 and len(stores[0]) > 0
    if case in ("drain_over_limit", "limit_change",
                "duplicates_out_of_order", "reset_remaining", "leaky"):
        assert over > 0, "the case never denied a request"


def test_negative_and_near_int64_columns_floor_and_raise_as_python_does():
    """Stored rows and inputs drawn from the edges of int64: most waves
    are answered (negative operands through ``//`` and ``%``, products
    past 64 bits clamped back), some raise — both lanes the same, with
    the same rows stored behind the exception."""
    rng = np.random.default_rng(42)
    stores = _pair()
    keys = (np.arange(1, 301, dtype=np.uint64)
            * np.uint64(0x9E3779B97F4A7C15))
    rows = rng.integers(-1000, 1000, (300, len(ROW_COLS))).astype(np.int64)
    edge = rng.random(rows.shape) < 0.2
    rows[edge] = rng.choice(EXTREMES, int(edge.sum()))
    for s in stores:
        s.put_batch(keys, rows)
    raised = answered = negative = 0
    for w in range(40):
        n = 120
        c, kh = _wave(rng, n, w, nkeys=450, alg=2, jitter=5_000,
                      zero_now=0.1, limits=(-5, 0, 1, 100),
                      durations=(-1, 0, 1, 60_000), hits=(-2, 0, 1, 9))
        c["behavior"] = rng.choice([0, GREG, RESET, DRAIN, RESET | DRAIN],
                                   n).astype(np.int32)
        c["greg_end"] = c["now"] + rng.integers(-90_000, 90_000, n)
        c["burst"] = rng.choice([-3, 0, 1, 200], n).astype(np.int64)
        for f in ("hits", "limit", "duration", "eff_ms", "greg_end",
                  "burst", "now"):
            edge = rng.random(n) < (0.004 if w % 2 else 0.02)
            c[f][edge] = rng.choice(EXTREMES, int(edge.sum()))
        got, cols = _apply_both(stores, c, kh, np.arange(n), NOW - w)
        if got is OverflowError:
            raised += 1
        else:
            answered += 1
            negative += int((cols[2] < 0).sum())
    assert raised >= 5 and answered >= 5, (raised, answered)
    assert negative > 0, "no negative remaining was ever answered"
    snap = _snapshot(stores[0])
    assert any(min(r) < -(1 << 40) for r in snap.values())
    assert any(max(r) > (1 << 61) for r in snap.values())


@pytest.mark.parametrize("field, value", [
    # leaky: burst x eff_ms past 64 bits, clamped nowhere -> remaining
    ("burst", I64_MAX),
    # token: expire = stamp + eff_ms past 64 bits
    ("eff_ms", I64_MAX),
])
def test_a_row_that_does_not_fit_raises_and_stops_the_wave_there(field,
                                                                 value):
    stores = _pair()
    rng = np.random.default_rng(7)
    c, kh = _wave(rng, 8, 0, alg=int(field == "burst"))
    c[field][5] = value
    got, cols = _apply_both(stores, c, kh, np.arange(8), NOW)
    assert got is OverflowError
    # rows 0-4 were served before it, 5-7 never
    assert not cols[4][:5].any() and cols[4][5:].all()
    assert 0 < len(stores[0]) <= 5


def test_a_negative_remaining_floors():
    """A stored leaky row 7 td units in debt, queried: ``-7 // 60000``
    is -1 in Python and 0 in C."""
    stores = _pair()
    rng = np.random.default_rng(7)
    c, kh = _wave(rng, 4, 0, alg=1, hits=(0,))
    row = np.array([[1, 100, 60_000, 60_000, 100, -7, NOW, NOW + 60_000]],
                   np.int64)
    for s in stores:
        s.put_batch(kh[2:3], row)
    got, cols = _apply_both(stores, c, kh, np.arange(4), NOW)
    assert got[0] == 4 and cols[2][2] == -1


def test_a_call_that_grows_the_table():
    """3,000 first-seen keys in ONE call on a 1,024-slot table (grown up
    front, once), then tombstones and a second call that rehashes."""
    rng = np.random.default_rng(11)
    stores = _pair()
    c, kh = _wave(rng, 3000, 0, nkeys=1 << 40)
    got, _ = _apply_both(stores, c, kh, np.arange(3000), NOW)
    assert got[1] == len(set(kh.tolist())) == len(stores[0])
    for k in kh[::3].tolist():
        assert stores[0].pop(k) == stores[1].pop(k)
    c2, kh2 = _wave(rng, 3000, 1, nkeys=1 << 40)
    kh2[:1500] = kh[:1500]
    got, _ = _apply_both(stores, c2, kh2, np.arange(3000), NOW)
    assert got[0] == 3000 and len(stores[0]) > 3000


def test_the_pass_refuses_columns_it_cannot_patch():
    store, _ = _pair()
    rng = np.random.default_rng(1)
    c, kh = _wave(rng, 16, 0)
    req = [c[f] for f in _REQ_COLS]
    cols = [np.zeros(16, np.int32), np.zeros(16, np.int64),
            np.zeros(16, np.int64), np.zeros(16, np.int64),
            np.zeros(16, bool)]
    with pytest.raises(ValueError):
        store.apply_batch(kh, np.array([16]), req, NOW, cols)
    with pytest.raises(ValueError):
        store.apply_batch(kh, np.array([-1]), req, NOW, cols)
    cols[0] = np.zeros(16, np.int64)  # status must be int32
    with pytest.raises(ValueError):
        store.apply_batch(kh, np.arange(16), req, NOW, cols)
    assert len(store) == 0


class _Engine:
    tier = None


@pytest.mark.parametrize("lane", ["pass", "loop"])
def test_resolve_leaves_pinned_and_device_rows_alone(lane):
    """``resolve`` on both lanes: rows with ``mslot`` >= 0 (mesh-GLOBAL
    pins) and rows no mask names keep their columns; the rest are served
    and counted, the native counter only by the pass."""
    from gubernator_tpu.metrics import Metrics

    rng = np.random.default_rng(3)
    m = Metrics()
    tc = TierController(_Engine(), metrics=m)
    if not tc._store.native:
        pytest.skip("native cold store not built")
    if lane == "loop":
        tc._store.apply_batch = None
    n = 400
    c, kh = _wave(rng, n, 0, nkeys=90, alg=2, zero_now=0.2, jitter=300)
    batch = RequestBatch(key=kh, valid=np.ones(n, bool), **{
        f: c[f] for f in _REQ_COLS})
    full = rng.random(n) < 0.4
    cold = ~full & (rng.random(n) < 0.5)
    orig_valid = rng.random(n) < 0.9
    mslot = np.where(rng.random(n) < 0.15, 3, -1)
    need = ((full & orig_valid) | cold) & (mslot < 0)
    cols = (np.full(n, -3, np.int32), np.full(n, -4, np.int64),
            np.full(n, -5, np.int64), np.full(n, -6, np.int64), full.copy())
    st, lim, rem, rst, out_full = tc.resolve(
        _Engine(), batch, kh, NOW, cols, cold, orig_valid, mslot=mslot)
    assert (st[~need] == -3).all() and (rst[~need] == -6).all()
    assert (out_full[~need] == full[~need]).all()
    assert not out_full[need].any() and (st[need] >= 0).all()
    assert (lim[need] == c["limit"][need]).all()
    stats = tc.stats()
    assert stats["cold_served"] == int(need.sum())
    assert stats["cold_created"] == stats["cold_keys"] == len(
        set(kh[need].tolist()))
    assert m.tier_cold_serves._value.get() == need.sum()
    assert m.tier_cold_native_serves._value.get() == (
        need.sum() if lane == "pass" else 0)
