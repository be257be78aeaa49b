"""``correct`` has to come out false when the timed path is broken, and
the lower-precision control has to fail the comparison.  Run by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness import check, reference, rows  # noqa: E402

POP = {"name": "b", "keys": 500, "restore": True, "hits": 1, "limit": 20,
       "duration_ms": 2000, "behavior": 0}
V0 = 1_900_000_000_000


def served_window(precision: str, seed: int = 4, calls: int = 60,
                  n: int = 50) -> dict:
    """Records of a window as a sound (or lower-precision) system would
    answer it: callers' calls interleaved, restored rows seeded."""
    rng = np.random.default_rng(seed)
    tb = reference.TokenBucket(POP["limit"], POP["duration_ms"], precision)
    idx_all = np.arange(POP["keys"])
    for i, rem, exp in zip(idx_all, rows.remaining0(idx_all, POP, seed),
                           rows.expire0(idx_all, POP, seed, V0)):
        tb.seed_row(int(i), int(rem), int(exp))
    rec = {k: [] for k in ("key_index", "status", "limit", "remaining",
                           "reset_time")}
    stamps = V0 + np.sort(rng.integers(0, 6000, calls))
    for stamp in stamps:
        idx = (rng.zipf(1.1, n) % POP["keys"]).astype(np.int64)
        got = tb.call(idx, int(stamp))
        rec["key_index"].append(idx)
        for k in got:
            rec[k].append(got[k])
    out = {k: np.concatenate(v) for k, v in rec.items()}
    out.update(ok=np.ones(calls, bool), n=np.full(calls, n),
               answered=np.full(calls, n), stamp=stamps)
    # concurrent callers: the checker may not lean on the calls' order
    return out


def test_sound_window_has_no_violation():
    rec = served_window("int64")
    win = check.window_violations(check.expand(rec), POP, 4, V0)
    assert win["violations"] == 0, win
    assert win["restored_lifetimes"] > 50 and win["over_limit_answers"] > 0


def test_lower_precision_control_fails_the_window_check():
    rec = served_window("float32")
    win = check.window_violations(check.expand(rec), POP, 4, V0)
    assert win["violations"] > 0
    sound = served_window("int64")
    ctl = check.control_window(sound, POP, 4, V0, "float32")
    assert ctl["violations"] > 0, ctl


def test_lower_precision_control_fails_the_replay():
    rec = served_window("int64")
    assert check.replay_mismatches(
        rec, dict(POP, restore=False), "float32",
        served=False)["mismatches"] > 0


@pytest.mark.parametrize("rule,edit", [
    ("remaining_repeats_or_skips", lambda a: a["remaining"].__setitem__(
        np.flatnonzero(a["status"] == 0)[5], 3)),
    ("reset_time", lambda a: a["reset_time"].__iadd__(
        (a["remaining"] == POP["limit"] - 1) * 1)),
    ("over_with_tokens", lambda a: a["remaining"].__setitem__(
        np.flatnonzero(a["status"] == 1)[0], 2)),
    ("limit", lambda a: a["limit"].__setitem__(7, 21)),
])
def test_one_altered_answer_is_a_violation(rule, edit):
    ans = check.expand(served_window("int64"))
    edit(ans)
    win = check.window_violations(ans, POP, 4, V0)
    # the rule names which trail the edit aims at; another rule may be
    # the one that catches it (a changed remaining also moves a start)
    assert win["violations"] > 0 and win["by_rule"], (rule, win)


def run_broken(fault: str) -> dict:
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "tests",
                                      "broken_run.py"), fault,
         "--workload", "r1-zipf-b1000-sat", "--seed", "12", "--seconds", "3",
         "--trace", "0", "--cpu-rehearsal"],
        capture_output=True, text=True, cwd=REPO, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault,correct", [
    ("none", True), ("float32_reset", False), ("remaining_off", False)])
def test_a_broken_timed_path_makes_the_run_not_correct(fault, correct):
    assert run_broken(fault)["correct"] is correct
