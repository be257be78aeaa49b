"""The plug-ins PR 39 brought, through ``harness/plugins.py``'s seams:
``algorithms/token_bucket_gregorian.py``, ``keys/zipf_space.py``,
``arrivals/burst.py``.

Pure numpy: no daemon, no subprocess.

* the calendar window rules are SOUND (the reference itself, served in
  random serial orders of concurrent callers across a minute boundary,
  with restored rows and keys nobody had seen, never breaks one) and
  TIGHT (every control, and one altered answer a rule, does);
* the reference on hand-worked cases; resident rows and the state put
  into a reference agree; the replay reaches its floors from any phase
  of the minute and every control fails it;
* the key draw over a space larger than its population; the burst
  schedule offers the same work whatever the seed.
"""
import os
import re
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))

from benchmark.harness import check, plugins  # noqa: E402
from benchmark.harness import traffic as tr  # noqa: E402

cal = plugins.load("algorithms", "token_bucket_gregorian")

MIN = 60_000
#: 20 s before a minute's end, so that every window here crosses it
CAL_V0 = 1_900_000_020_000 // MIN * MIN + 40_000
CAL_POP = {"name": "t", "keys": 6, "restore": True,
           "algorithm": "TOKEN_BUCKET_GREGORIAN", "behavior": 4,
           "gregorian": "MINUTES", "hits": 1, "limit": 30, "duration_ms": 0}
CAL_SPACE = 14
E0 = cal.period_end(CAL_V0, 0)


def serve_calendar(seed: int, control=None, seconds: float = 45.0,
                   pop=CAL_POP, call_ms: float = 400.0) -> dict:
    """Window records of 8 concurrent callers whose calls take up to
    ``call_ms``, each REQUEST applied at a random instant between its
    call's send and done time — so a request stamped before the
    boundary is often applied after one stamped past it — served by the
    reference (or a control) in that serial order."""
    rng = np.random.default_rng([seed, 0xCA1])
    n = 10
    calls = []  # (send ms, done ms, key indices)
    for _ in range(8):
        t = rng.uniform(0.0, 50.0)
        while t < 1000.0 * seconds:
            took = rng.uniform(1.0, call_ms)
            calls.append((t, t + took, rng.integers(0, CAL_SPACE, n)))
            t += took + rng.uniform(0.0, 5.0)
    when = np.concatenate([rng.uniform(s, d, n) for s, d, _ in calls])
    ref = cal.reference(pop, control)
    cal.seed_reference(ref, np.arange(CAL_SPACE), pop, seed, CAL_V0)
    got = np.empty((len(when), 4), np.int64)
    for i in np.argsort(when, kind="stable"):
        send, _, idx = calls[i // n]
        got[i] = ref.hit(int(idx[i % n]), CAL_V0 + int(send))
    send = np.array([c[0] for c in calls]) / 1000.0
    return {"ok": np.ones(len(calls), bool), "n": np.full(len(calls), n),
            "answered": np.full(len(calls), n), "due": send, "send": send,
            "done": np.array([c[1] for c in calls]) / 1000.0,
            "stamp": CAL_V0 + np.array([int(c[0]) for c in calls]),
            "key_index": np.concatenate([c[2] for c in calls]),
            "status": got[:, 0], "limit": got[:, 1], "remaining": got[:, 2],
            "reset_time": got[:, 3]}


@pytest.mark.parametrize("seed", range(240))
def test_the_calendar_reference_in_any_serial_order_breaks_no_rule(seed):
    ans = check.expand(serve_calendar(seed))
    win = cal.window_violations(ans, CAL_POP, seed, CAL_V0)
    assert win["violations"] == 0, win
    # the orders are not tame: both answers common, restored rows met,
    # new keys made, and every key's bucket closed by the boundary —
    # with requests stamped before it answered from the bucket after it
    assert win["over_limit_answers"] > 100 < win["answers"] - \
        win["over_limit_answers"]
    assert win["restored_lifetimes"] == CAL_POP["keys"]
    assert win["created_keys"] == CAL_SPACE - CAL_POP["keys"]
    assert win["lifetimes_opened_after_a_boundary"] == CAL_SPACE


def test_the_serial_orders_hold_what_a_bound_on_the_stamp_would_fail():
    """A request stamped before the boundary and answered from the
    bucket of the minute after it (it was applied after a request
    stamped past the boundary): ``reset_time`` > stamp + a minute is
    SOUND, and most orders above hold such an answer."""
    seen = 0
    for seed in range(16):
        ans = check.expand(serve_calendar(seed))
        seen += bool(((ans["reset_time"] > E0) & (ans["stamp"] < E0)).any())
    assert seen >= 8, seen


@pytest.mark.parametrize("seed", [3, 2200000123, 11])
@pytest.mark.parametrize("control,rules", [
    ("wall_clock_period", {"served_after_reset", "opener_reset_time"}),
    ("fixed_60s", {"reset_time_not_a_period_end", "opener_reset_time"}),
    ("float32", {"reset_time_not_a_period_end"}),
])
def test_a_calendar_control_in_the_programs_place_breaks_a_rule(
        control, rules, seed):
    rec = serve_calendar(seed, control)
    win = cal.window_violations(check.expand(rec), CAL_POP, seed, CAL_V0)
    assert win["violations"] >= 1 and rules <= set(win["by_rule"]), win


def replay_records(pop: dict, v_start: int, seed: int = 1) -> dict:
    draw = plugins.load("keys", "zipf_space").sample
    plan = cal.replay_plan(
        pop, {"requests_per_call": 100}, seed,
        lambda rng: draw(rng, {"a": 1.1, "space": 30_000}, 100, pop["keys"]))
    return {"n": np.array([len(i) for _, i in plan]),
            "stamp": np.array([v_start + t for t, _ in plan]),
            "key_index": np.concatenate([i for _, i in plan])}


@pytest.mark.parametrize("control", cal.CONTROLS)
def test_a_calendar_control_fails_the_driver_and_the_replay(control):
    """``check.control_window`` (what ``--control`` runs) on a sound
    window's records, and the replay's plan walked by the control."""
    pop = dict(CAL_POP, keys=3000, limit=100)
    rep = check.replay_mismatches(replay_records(pop, CAL_V0 + 51_000), pop,
                                  control, served=False)
    assert rep["mismatches"] >= 1, rep
    assert all(got >= floor for _, got, floor in cal.replay_floors(rep))
    ctl = check.control_window(serve_calendar(5), CAL_POP, 5, CAL_V0,
                               control)
    assert ctl["violations"] >= 1, ctl


@pytest.mark.parametrize("second", range(0, 60, 7))
def test_the_replay_reaches_its_floors_from_any_second_of_the_minute(second):
    """``run.py`` starts the replay at the window's end, a whole second
    of the stamps' clock anywhere in its minute: the first call comes
    60,999 ms later (whatever the window opened has expired), one pair
    of calls lies 1 ms either side of a boundary, two are crossed."""
    pop = dict(CAL_POP, keys=3000, limit=100)
    v_start = CAL_V0 // MIN * MIN + 1000 * second
    rec = replay_records(pop, v_start, seed=second)
    assert rec["stamp"][0] - v_start >= MIN and len(rec["n"]) == 131
    assert (np.diff(rec["stamp"]) > 0).all()
    rep = check.replay_mismatches(rec, pop, None, served=False)
    assert rep["mismatches"] == 0
    floors = {name: (got, floor)
              for name, got, floor in cal.replay_floors(rep)}
    assert len(floors) == 5
    assert all(got >= floor for got, floor in floors.values()), floors
    assert floors["replay_calls_1ms_either_side_of_a_boundary"][0] == 1


def calendar_answers(rows_: list, pop: dict) -> dict:
    """[(key, stamp, done, status, remaining, reset)] (times in ms from
    ``CAL_V0``) → the check's columns, the limit as the reference
    echoes it."""
    a = np.array(rows_, np.int64).reshape(-1, 6)
    return {"key_index": a[:, 0], "stamp": CAL_V0 + a[:, 1],
            "done_ms": CAL_V0 + a[:, 2], "status": a[:, 3],
            "remaining": a[:, 4], "reset_time": CAL_V0 + a[:, 5],
            "limit": np.full(len(a), pop["limit"])}


def alter(column: str, where, value):
    def apply(ans: dict) -> None:
        i = np.flatnonzero(where(ans))[3]
        ans[column][i] = value(ans[column][i])
    return apply


#: three tokens a minute, nothing restored; times from CAL_V0, whose
#: minute ends at +20,000
THREE = dict(CAL_POP, limit=3, restore=False, keys=0)
END, NEXT = 20_000, 80_000


@pytest.mark.parametrize("rules,ans", [
    ({"limit"}, alter("limit", lambda a: a["status"] == 0, lambda v: v + 1)),
    ({"status"}, alter("status", lambda a: a["status"] == 1, lambda v: 2)),
    ({"over_with_tokens"}, alter("remaining", lambda a: a["status"] == 1,
                                 lambda v: 1)),
    # four admitted from a bucket of three
    ({"remaining_range"}, [(0, 0, 1, 0, 2, END), (0, 0, 1, 0, 1, END),
                           (0, 0, 1, 0, 0, END), (0, 0, 1, 0, -1, END)]),
    # a bucket that expires 1 ms before its minute does
    ({"reset_time_not_a_period_end", "opener_reset_time"},
     [(0, 0, 1, 0, 2, END - 1)]),
    # answered from a bucket 5 ms after it had expired
    ({"served_after_reset"}, [(0, 0, 1, 0, 2, END),
                              (0, END + 5, END + 6, 0, 1, END)]),
    # answered, 10 ms before the boundary, from the bucket of the minute
    # after it
    ({"reset_time_ahead_of_the_clock"},
     [(0, END + 1, END + 3, 0, 2, NEXT), (0, END - 20, END - 10, 0, 1, NEXT)]),
    # opened by a request of this minute, expiring with the next: a
    # period end read from another clock than the request's
    ({"opener_reset_time"}, [(0, END - 5, END + 10, 0, 2, NEXT)]),
    ({"remaining_repeats_or_skips"}, [(0, 0, 1, 0, 2, END),
                                      (0, 0, 1, 0, 1, END),
                                      (0, 0, 1, 0, 1, END)]),
    # a key nobody had seen that opens at 1, not at limit − 1
    ({"lifetime_start"}, [(0, 0, 1, 0, 1, END), (0, 0, 1, 0, 0, END)]),
    ({"over_before_empty"}, [(0, 0, 1, 0, 2, END), (0, 0, 1, 0, 1, END),
                             (0, 0, 1, 1, 0, END)]),
])
def test_one_altered_calendar_answer_breaks_exactly_its_rules(rules, ans):
    pop, seed = THREE, 8
    if callable(ans):
        pop, altered = CAL_POP, check.expand(serve_calendar(seed))
        ans(altered)
        ans = altered
    else:
        ans = calendar_answers(ans, pop)
    win = cal.window_violations(ans, pop, seed, CAL_V0)
    assert set(win["by_rule"]) == rules, win


def test_a_restored_row_that_answers_from_another_state_is_a_violation():
    seed = 4
    start = int(cal.remaining0(np.array([2]), CAL_POP, seed)[0])
    good = [(2, 0, 1, 0, start - 1, END), (2, 5, 6, 0, start - 2, END)]
    assert cal.window_violations(calendar_answers(good, CAL_POP), CAL_POP,
                                 seed, CAL_V0)["violations"] == 0
    fresh = [(2, 0, 1, 0, CAL_POP["limit"] - 1, END)]
    if start != CAL_POP["limit"]:
        win = cal.window_violations(calendar_answers(fresh, CAL_POP),
                                    CAL_POP, seed, CAL_V0)
        assert set(win["by_rule"]) == {"lifetime_start"}, win
    # after the boundary the same key opens full, and index 9 (not
    # resident) opens full before it
    after = [(2, END, END + 1, 0, CAL_POP["limit"] - 1, NEXT),
             (9, 0, 1, 0, CAL_POP["limit"] - 1, END)]
    assert cal.window_violations(calendar_answers(after, CAL_POP), CAL_POP,
                                 seed, CAL_V0)["violations"] == 0


@pytest.mark.parametrize("case,walk", [
    ("drain", [(0, 0, 2), (0, 0, 1), (0, 0, 0), (0, 1, 0), (19_999, 1, 0)]),
    # the minute's end re-opens the bucket, full, for the next minute
    ("boundary", [(19_999, 0, 2), (20_000, 0, 2), (20_001, 0, 1),
                  (79_999, 0, 0), (80_000, 0, 2)]),
    # an older request after the boundary was crossed: the new bucket's
    ("older_request", [(20_001, 0, 2), (19_999, 0, 1), (20_002, 0, 0),
                       (19_998, 1, 0)]),
])
def test_the_calendar_reference_on_hand_worked_cases(case, walk):
    ref = cal.reference(dict(THREE, keys=1))
    for now, status, remaining in walk:
        end = E0 if case == "drain" or now == 19_999 and case == "boundary" \
            else E0 + MIN * ((max(now, 20_000) - 20_000) // MIN + 1)
        assert ref.hit(0, CAL_V0 + now) == (status, 3, remaining, end), \
            (case, now)


def test_the_calendar_reference_is_exact_and_imports_nothing_of_the_program():
    src = open(os.path.join(BENCH, "algorithms",
                            "token_bucket_gregorian.py")).read()
    assert not re.search(r"^\s*(from|import)\s+(gubernator_tpu|datetime|"
                         r"calendar|time)\b", src, re.M)
    ref = cal.reference(CAL_POP)
    for now in range(0, 200_000, 977):
        out = ref.hit(1, CAL_V0 + now)
        assert all(type(v) is int for v in out) and 0 <= out[2] < 30
    assert all(type(v) is int for row in ref.rows.values() for v in row)
    with pytest.raises(ValueError):  # the name and the ordinal disagree
        cal.reference(dict(CAL_POP, gregorian="HOURS"))
    with pytest.raises(ValueError):  # milliseconds are no ordinal
        cal.reference(dict(CAL_POP, duration_ms=10_000, gregorian=None))
    with pytest.raises(ValueError):
        cal.reference(dict(CAL_POP, behavior=0))
    assert cal.request_fields(CAL_POP) == {}


def test_resident_rows_and_the_state_put_into_a_reference_agree():
    pop = dict(CAL_POP, keys=5000, limit=100)
    cols = cal.snapshot_columns(pop, 7, CAL_V0)
    assert set(cols) == {"key", "meta", "limit", "duration", "eff_ms",
                         "burst", "remaining", "t_ms", "expire_at"}
    assert all(len(c) == 5000 for c in cols.values())
    assert len(np.unique(cols["key"])) == 5000
    assert (cols["expire_at"] == E0).all() and (cols["duration"] == 0).all()
    assert (cols["eff_ms"] == MIN).all() and (cols["meta"] == 0).all()
    assert (cols["t_ms"] >= E0 - MIN).all() and (cols["t_ms"] <= CAL_V0).all()
    assert cols["remaining"].min() == 1 and cols["remaining"].max() == 100
    ref = cal.reference(pop)
    cal.seed_reference(ref, np.array([0, 17, 4999, 5000, 29_999]), pop, 7,
                       CAL_V0)
    assert sorted(ref.rows) == [0, 17, 4999]  # the others are not resident
    for i in (0, 17, 4999):
        assert ref.hit(i, CAL_V0) == (0, 100, cols["remaining"][i] - 1, E0)
    assert ref.hit(5000, CAL_V0) == (0, 100, 99, E0)


# ---- the key draw, the arrival process ----------------------------------

def test_the_zipf_space_draw_stays_inside_its_space_and_is_seed_stable():
    sample = plugins.load("keys", "zipf_space").sample
    params = {"dist": "zipf_space", "a": 1.1, "space": 100_000_000}
    a = sample(tr.caller_rng(7, 3), params, 400_000, 10_000_000)
    b = sample(tr.caller_rng(7, 3), params, 400_000, 10_000_000)
    c = sample(tr.caller_rng(8, 3), params, 400_000, 10_000_000)
    assert a.dtype == np.int64 and (a == b).all() and (a != c).any()
    assert a.min() >= 0 and a.max() < 100_000_000
    assert a.max() >= 10_000_000  # keys the daemon has not seen
    assert 0.15 < (a >= 10_000_000).mean() < 0.19
    assert 0.085 < (a == 1).mean() < 0.105
    small = sample(tr.caller_rng(7, 3), dict(params, space=30_000), 50_000,
                   3000)
    assert small.max() < 30_000 and (small >= 3000).any()
    with pytest.raises(ValueError):
        sample(tr.caller_rng(7, 3), dict(params, space=2999), 10, 3000)


BURST = {"rate_calls_per_s": 30, "callers": 8,
         "burst": {"factor": 3, "ms": 200, "every_ms": 1000}}


@pytest.mark.parametrize("seed", [0, 1, 2200000123, 2 ** 31 + 5, 39])
def test_the_burst_schedule_offers_the_same_work_whatever_the_seed(seed):
    schedule = plugins.load("arrivals", "burst").schedule
    due, conn = schedule(BURST, 51.0, seed)
    again, _ = schedule(BURST, 51.0, seed)
    other, _ = schedule(BURST, 51.0, seed + 1)
    assert len(due) == len(other) == 1530 and (due == again).all()
    assert (due != other).any()
    assert (np.diff(due) >= 0).all() and 0.0 <= due[0] and due[-1] < 51.0
    assert conn.min() >= 0 and conn.max() <= 7 and len(set(conn)) == 8
    # the phase of the bursts inside the second: the 200-ms arc of the
    # unit circle that holds the most calls
    frac = np.sort(due % 1.0)
    wrapped = np.r_[frac, frac + 1.0]
    inside = np.searchsorted(wrapped, frac + 0.2) - np.arange(len(frac))
    in_burst = inside.max()
    ratio = (in_burst / 0.2) / ((1530 - in_burst) / 0.8)
    assert 2.6 < ratio < 3.6, ratio  # 3× the base rate: 64.3 against 21.4
    assert 55.0 < in_burst / (0.2 * 51.0) < 74.0
    # half the window is half the calls: the same load all the way
    assert abs(int((due < 25.5).sum()) - 765) < 60


def test_the_burst_mix_names_its_process_and_the_open_cells_population():
    from benchmark import run

    burst = tr.load("zipf-b1000-burst")
    plain = tr.load("zipf-b1000-open")
    same = ("loop", "callers", "generators", "requests_per_call",
            "population", "keys", "rate_calls_per_s")
    assert {k: burst[k] for k in same} == {k: plain[k] for k in same}
    assert burst["arrivals"] == "burst" and burst["burst"] == BURST["burst"]
    cell = run.load_cell("r1-greg-zipf-b1000-sat", rehearsal=False)
    pop = cell["config"]["populations"][cell["traffic"]["population"]]
    assert plugins.algorithm(pop) is cal and cal.ordinal_of(pop) == 0
    assert cell["traffic"]["keys"]["space"] == cell["config"]["key_space"] \
        == 100_000_000
    small = run.load_cell("r1-greg-zipf-b1000-sat", rehearsal=True)
    assert small["traffic"]["keys"] == {"dist": "zipf_space", "a": 1.1,
                                        "space": 30_000}
    assert small["config"]["key_space"] == 30_000
