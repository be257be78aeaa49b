"""A stamped request's calendar period is the one that holds its stamp
(ISSUE 39; ``gubernator_tpu/gregorian.py`` states the rule).

* the program's ``gregorian_expiration`` against the benchmark plug-in's
  own integer calendar (``benchmark/algorithms/token_bucket_gregorian.py
  › period_end``: days-from-civil, no ``datetime``) on seeded stamps and
  on the calendar's edges, all six ordinals;
* ``pack_requests``, ``pack_columns`` and the oracle agree on a row's
  period end, stamped (the stamp's period) and unstamped (the daemon's
  clock, as ever);
* stamped GREGORIAN_MINUTES calls through ``get_rate_limits_wire`` — the
  daemon's clock a day behind the stamps, as ``benchmark/run.py`` keeps
  it — against the plug-in's plain reference, answer for answer, across
  two minute boundaries with calls 1 ms either side of one, on restored
  rows and on rows the calls create, on both engines;
  and by both lanes: the fused C++ ingest, which does the calendar
  itself since ISSUE 40, and the classic numpy lane (``local.pack`` →
  ``pack_columns`` → ``_calendar_ends``) of an engine whose ingest
  declines every call, as a checkout without the extension serves;
* what ISSUE 39 counts: ``gubernator_wire_fused_declined{reason}``,
  ``gubernator_wave_gregorian_rows``, ``gubernator_wave_created_rows``
  and the phase ``pack.calendar`` on known calls, lane by lane.
"""
import numpy as np
import pytest

from benchmark.harness import plugins, wire
from benchmark.harness import traffic as tr
from gubernator_tpu import Behavior, RateLimitRequest
from gubernator_tpu.config import Config
from gubernator_tpu.core import batch as core_batch
from gubernator_tpu.core.batch import pack_columns, pack_requests
from gubernator_tpu.gregorian import gregorian_expiration
from gubernator_tpu.hashing import hash_request_keys
from gubernator_tpu.instance import V1Instance
from gubernator_tpu.oracle import Oracle
from gubernator_tpu.parallel import ShardedEngine, make_mesh
from gubernator_tpu.parallel.pallas_engine import PallasServingEngine
from gubernator_tpu.types import GregorianDuration
from gubernator_tpu.wire import req_to_tlv

greg = plugins.load("algorithms", "token_bucket_gregorian")

GREG = int(Behavior.DURATION_IS_GREGORIAN)
DAY = 86_400_000
#: 2026-10-01 00:00:37.250 UTC, and a daemon's clock a day behind it
V0 = 1_790_812_837_250
ENGINES = {"xla_classic": ShardedEngine, "pallas_fused": PallasServingEngine}
POP = {"name": "t", "keys": 300, "restore": True,
       "algorithm": "TOKEN_BUCKET_GREGORIAN", "behavior": GREG,
       "gregorian": "MINUTES", "hits": 1, "limit": 20, "duration_ms": 0}
SPACE, SEED = 900, 39


# ---- the calendar -------------------------------------------------------

def edges() -> list:
    """Stamps on and 1 ms either side of what calendars get wrong:
    month ends, leap days (1972, 2000, 2024; not 1900, 2100), year ends,
    week starts, the epoch itself and times before it."""
    out = [0, -1, 1, -DAY, 4 * DAY - 1, 4 * DAY]  # the first Monday
    for y in (1899, 1900, 1970, 1972, 1999, 2000, 2023, 2024, 2026, 2100):
        for m in range(1, 13):
            first = greg.days_from_civil(y, m, 1) * DAY
            out += [first - 1, first, first + 1, first + 28 * DAY,
                    first + 29 * DAY - 1]
    return out


@pytest.mark.parametrize("ordinal", list(GregorianDuration))
def test_the_programs_calendar_is_the_plugins_integer_calendar(ordinal):
    rng = np.random.default_rng([39, int(ordinal)])
    stamps = rng.integers(-2_000_000_000_000, 4_200_000_000_000, 10_000)
    every = stamps.tolist() + edges()
    assert greg.period_ends(every, int(ordinal)).tolist() == [
        greg.period_end(ms, int(ordinal)) for ms in every]
    for ms in every:
        end = greg.period_end(ms, int(ordinal))
        assert gregorian_expiration(ms, ordinal) == end, (ms, ordinal)
        assert greg.period_start(ms, int(ordinal)) <= ms < end
        assert greg.period_end(end - 1, int(ordinal)) == end
        assert greg.period_start(end, int(ordinal)) == end


def test_the_calendar_on_hand_worked_stamps():
    at = lambda *ymd_hms: (greg.days_from_civil(*ymd_hms[:3]) * DAY  # noqa: E731
                           + ymd_hms[3] * 3_600_000 + ymd_hms[4] * 60_000)
    t = at(2024, 2, 29, 23, 59) + 59_999  # a leap day's last millisecond
    want = {GregorianDuration.MINUTES: t + 1, GregorianDuration.HOURS: t + 1,
            GregorianDuration.DAYS: t + 1,
            GregorianDuration.WEEKS: at(2024, 3, 4, 0, 0),  # a Monday
            GregorianDuration.MONTHS: t + 1,
            GregorianDuration.YEARS: at(2025, 1, 1, 0, 0)}
    for ordinal, end in want.items():
        assert gregorian_expiration(t, ordinal) == end, ordinal
    assert greg.civil_from_days(greg.days_from_civil(2100, 2, 28) + 1) == \
        (2100, 3, 1)  # 2100 is no leap year
    with pytest.raises(ValueError):
        gregorian_expiration(t, 6)
    with pytest.raises(ValueError):
        greg.period_end(t, 6)


# ---- the packers and the oracle: one rule -------------------------------

def request(key: str, ordinal: int, created: int = 0) -> RateLimitRequest:
    return RateLimitRequest(name="g", unique_key=key, hits=1, limit=5,
                            duration=ordinal, behavior=GREG,
                            created_at=created)


@pytest.mark.parametrize("ordinal", [GregorianDuration.MINUTES,
                                     GregorianDuration.DAYS,
                                     GregorianDuration.MONTHS])
def test_packers_and_oracle_agree_on_a_rows_period_end(ordinal):
    """Stamped: the period that holds the stamp, a day ahead of the
    daemon's clock here, across a boundary of every ordinal tried.
    Unstamped: the period that holds the daemon's clock, as before."""
    wall = V0 - DAY
    stamps = [V0, 0, V0 + 31 * DAY, 0, V0 - 1]
    reqs = [request(f"k{i}", int(ordinal), s) for i, s in enumerate(stamps)]
    want = [gregorian_expiration(s or wall, ordinal) for s in stamps]
    assert len(set(want)) >= 3  # the rows do not share one period
    kh = hash_request_keys([r.name for r in reqs],
                           [r.unique_key for r in reqs])
    b, errs = pack_requests(reqs, wall, size=len(reqs), key_hashes=kh)
    assert not any(errs)
    assert b.greg_end.tolist() == want
    assert b.now.tolist() == [s or wall for s in stamps]
    n = len(reqs)
    z = np.zeros(n, np.int64)
    bc, errs = pack_columns(kh, z + 1, z + 5, z + int(ordinal), z.copy(),
                            np.full(n, GREG, np.int32), z.copy(), wall,
                            created_at=np.array(stamps, np.int64))
    assert not errs
    assert bc.greg_end.tolist() == want
    assert bc.now.tolist() == b.now.tolist() and bc.rows.greg == n
    oracle = Oracle()
    for r, end in zip(reqs, want):
        assert oracle.check(r, wall).reset_time == end


def test_pack_columns_asks_the_calendar_once_a_stamp_not_once_a_row(
        monkeypatch):
    """A client's call carries one stamp: one period end for its 1,000
    rows, and a second for the rows of another stamp."""
    asked = []
    monkeypatch.setattr(core_batch, "gregorian_expiration",
                        lambda t, d: asked.append((t, d))
                        or gregorian_expiration(t, d))
    n = 1000
    z = np.zeros(n, np.int64)
    created = np.full(n, V0, np.int64)
    created[700:] = V0 + 60_000
    beh = np.full(n, GREG, np.int32)
    beh[::10] = 0  # plain millisecond rows among them
    b, errs = pack_columns(np.arange(1, n + 1, dtype=np.uint64), z + 1,
                           z + 5, z.copy(), z.copy(), beh, z.copy(),
                           V0 - DAY, created_at=created)
    assert not errs and sorted(asked) == [(V0, 0), (V0 + 60_000, 0)]
    assert b.rows.greg == 900
    calendar_rows = beh != 0
    assert (b.greg_end[calendar_rows & (created == V0)]
            == greg.period_end(V0, 0)).all()
    assert (b.greg_end[~calendar_rows] == 0).all()


def test_an_invalid_ordinal_is_an_error_on_its_rows_alone():
    n = 6
    z = np.zeros(n, np.int64)
    dur = np.array([0, 9, 0, 9, 1, 0], np.int64)
    b, errs = pack_columns(np.arange(1, n + 1, dtype=np.uint64), z + 1,
                           z + 5, dur, z.copy(), np.full(n, GREG, np.int32),
                           z.copy(), V0, created_at=z + V0)
    assert sorted(errs) == [1, 3] and "ordinal: 9" in errs[1]
    assert b.valid.tolist() == [True, False, True, False, True, True]
    assert b.rows.greg == 4 and b.key[1] == 0


# ---- through the daemon's wire entry, against the plain reference -------

def calls_plan() -> list:
    """[(stamp, key indices)]: one caller's calls across two minute
    boundaries, one pair 1 ms either side of the first."""
    rng = np.random.default_rng(SEED)
    e0 = greg.period_end(V0, 0)
    stamps = [V0, V0 + 9_000, e0 - 1, e0, e0 + 1, e0 + 20_000,
              e0 + 59_999, e0 + 60_000, e0 + 100_000]
    return [(t, (rng.zipf(1.1, 160) % SPACE).astype(np.int64))
            for t in stamps]


LANES = [(e, lane) for e in ENGINES for lane in ("fused", "classic")]


@pytest.fixture(scope="module", params=LANES,
                ids=[f"{e}-{lane}" for e, lane in LANES])
def served(request):
    """(instance, [(got, want)] a call, reference, lane) after the plan
    ran: restored rows, stamped calls on a daemon whose clock is a day
    behind.  ``classic``: the engine's fused ingest declines every call,
    so each is parsed and packed in numpy (``_calendar_ends``)."""
    kind, lane = request.param
    eng = ENGINES[kind](make_mesh(n=1), capacity_per_shard=1 << 12,
                        batch_per_shard=64)
    if lane == "classic":
        eng.prepack_wire = lambda *a, **kw: None
    inst = V1Instance(Config(cache_size=1 << 12, sweep_interval_ms=0),
                      engine=eng)
    try:
        with inst._engine_mu:
            assert eng.restore(greg.snapshot_columns(POP, SEED, V0)) == \
                POP["keys"]
        ref = greg.reference(POP)
        greg.seed_reference(ref, np.arange(SPACE), POP, SEED, V0)
        tpl = wire.RequestTemplate(name=POP["name"], hits=1,
                                   limit=POP["limit"], duration=0,
                                   behavior=GREG)
        out = []
        for k, (stamp, idx) in enumerate(calls_plan()):
            data = tpl.call(tr.key_id(idx, SEED), stamp)
            got = wire.decode_responses(
                inst.get_rate_limits_wire(data, now_ms=V0 - DAY + 7 * k))
            out.append((got, ref.call(idx, stamp), stamp, idx))
        yield inst, out, ref, lane
    finally:
        inst.close()


def test_stamped_minutes_calls_equal_the_reference_answer_for_answer(served):
    _, out, ref, _ = served
    for got, want, stamp, idx in out:
        assert got["errors"] == 0 and len(got["status"]) == len(idx)
        for f in ("status", "limit", "remaining", "reset_time"):
            assert (got[f] == want[f]).all(), (f, stamp)
    c = ref.counts
    assert c["boundaries_crossed"] == 2
    assert c["calls_1ms_either_side_of_a_boundary"] == 2  # e0-1 | e0 | e0+1
    assert c["lifetimes_closed_by_a_boundary"] > 50
    assert c["over_limit_answers"] > 50 and c["created_keys"] > 50
    # restored rows answered from their restored state in the first call
    got, _, _, idx = out[0]
    i = int(np.flatnonzero((idx < POP["keys"]) & (idx > 5))[0])
    assert got["remaining"][i] == greg.remaining0(idx[i:i + 1], POP,
                                                  SEED)[0] - 1
    assert got["reset_time"][i] == greg.period_end(V0, 0)


def test_the_window_rules_pass_the_program_and_fail_the_old_rule(served):
    """The same answers through ``window_violations`` (one caller's
    order is one of the serial orders): 0 — and the wall-clock rule in
    the program's place, as the parent commit had it, breaks them."""
    _, out, _, _ = served
    cat = lambda f: np.concatenate([g[f] for g, *_ in out])  # noqa: E731
    n = [len(idx) for *_, idx in out]
    ans = {f: cat(f) for f in ("status", "limit", "remaining", "reset_time")}
    ans["key_index"] = np.concatenate([idx for *_, idx in out])
    ans["stamp"] = np.repeat([s for _, _, s, _ in out], n)
    ans["done_ms"] = ans["stamp"] + 3
    win = greg.window_violations(ans, POP, SEED, V0)
    assert win["violations"] == 0, win
    assert win["restored_lifetimes"] > 20 and win["created_keys"] > 50
    assert win["lifetimes_opened_after_a_boundary"] > 50
    old = greg.reference(POP, "wall_clock_period")
    greg.seed_reference(old, np.arange(SPACE), POP, SEED, V0)
    for f in ("status", "limit", "remaining", "reset_time"):
        ans[f] = np.concatenate([old.call(idx, s)[f]
                                 for _, _, s, idx in out])
    broke = greg.window_violations(ans, POP, SEED, V0)["by_rule"]
    assert {"served_after_reset", "opener_reset_time"} <= set(broke)


def test_what_the_calls_raised_the_counters_by(served):
    """Either lane carries the same calendar rows into the same waves;
    a call the fused ingest SERVES is not declined and passes neither
    ``local.pack`` nor ``pack.calendar``."""
    inst, out, _, lane = served
    m = inst.metrics
    rows_sent = sum(len(idx) for *_, idx in out)
    assert m.wave_gregorian_rows._value.get() == rows_sent
    fused = lane == "fused"
    assert m.wire_fused_declined.labels(
        reason="gregorian")._value.get() == (0 if fused else len(out))
    assert m.wire_fused_counter._value.get() == (rows_sent if fused else 0)
    created = len({int(i) for *_, idx in out for i in idx
                   if i >= POP["keys"]})
    assert m.wave_created_rows._value.get() == created
    text = inst.metrics.render().decode()
    for name in ("pack.calendar", "local.pack"):
        line = f'gubernator_phase_duration_count{{phase="{name}"}}'
        assert (line in text) == (not fused), name


def test_an_unstamped_calendar_row_follows_the_daemons_clock_and_a_plain_call_is_not_declined():
    inst = V1Instance(Config(cache_size=1 << 12, sweep_interval_ms=0),
                      mesh=make_mesh(n=1))
    try:
        m = inst.metrics
        wall = V0 - DAY
        r = request("u", 0)
        got = wire.decode_responses(
            inst.get_rate_limits_wire(req_to_tlv(r), now_ms=wall))
        assert got["reset_time"].tolist() == [greg.period_end(wall, 0)]
        declined = lambda why: m.wire_fused_declined.labels(  # noqa: E731
            reason=why)._value.get()
        # served by the fused ingest, its calendar the daemon's clock's
        assert declined("gregorian") == 0
        assert m.wire_fused_counter._value.get() == 1
        plain = RateLimitRequest(name="g", unique_key="p", hits=1, limit=5,
                                 duration=10_000)
        inst.get_rate_limits_wire(req_to_tlv(plain) * 3, now_ms=wall)
        assert m.wire_fused_counter._value.get() == 4
        # a MULTI_REGION row declines its call by policy, calendar row
        # or none; an invalid ordinal is what "gregorian" counts now
        mr = RateLimitRequest(name="g", unique_key="m", hits=1, limit=5,
                              duration=10_000,
                              behavior=Behavior.MULTI_REGION)
        inst.get_rate_limits_wire(req_to_tlv(mr) + req_to_tlv(r),
                                  now_ms=wall)
        assert (declined("gregorian"), declined("multi_region"),
                declined("other")) == (0, 1, 0)
        bad = request("b", 9)
        got = wire.decode_responses(inst.get_rate_limits_wire(
            req_to_tlv(r) + req_to_tlv(bad), now_ms=wall))
        assert got["errors"] == 1
        assert (declined("gregorian"), declined("multi_region"),
                declined("other")) == (1, 1, 0)
        assert m.wire_fused_counter._value.get() == 4
        assert m.wave_gregorian_rows._value.get() == 3
        assert m.wave_created_rows._value.get() == 3  # u, p, m
    finally:
        inst.close()

