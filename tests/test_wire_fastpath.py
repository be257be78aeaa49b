"""C++ wire-ingest lane parity (ops/_native.cpp parse/build +
instance.get_rate_limits_wire vs the pb2 object path).

The fast lane must be byte-behavior identical to the slow path for every
batch it accepts, and must fall back (not misbehave) for everything else.

Every test runs on BOTH columnar lanes and on 1, 2 and 4 shards (ISSUE
38): ``fused`` — the one C++ pass, ``ShardedEngine.prepack_wire``, which
serves a table of any shard count — and ``columns`` — the numpy lane
``_wire_check_columns``, by an engine whose ``prepack_wire`` declines
every call.  Which lane served a case is ASSERTED, by
``gubernator_wire_fused_requests_total``: until ISSUE 38 the two-shard
mesh these tests build silently tested the numpy lane alone.
"""
import numpy as np
import pytest

from gubernator_tpu.config import Config
from gubernator_tpu.instance import V1Instance, _wire_native
from gubernator_tpu.parallel import make_mesh
from gubernator_tpu.proto import gubernator_pb2 as pb
from gubernator_tpu.types import (
    Behavior,
    GregorianDuration,
    RateLimitRequest,
)
from gubernator_tpu.wire import req_to_pb

if _wire_native is None:  # pragma: no cover
    pytest.skip("native extension not built", allow_module_level=True)

NOW = 1_766_000_000_000

#: the lane and the shard count of the running case (``lane`` below)
LANE, SHARDS = "fused", 2


@pytest.fixture(autouse=True,
                params=[(lane, n) for lane in ("fused", "columns")
                        for n in (1, 2, 4)],
                ids=lambda p: f"{p[0]}-{p[1]}shards")
def lane(request):
    global LANE, SHARDS
    LANE, SHARDS = request.param


def decline_prepack(inst) -> None:
    """The engine of the ``columns`` lane: one whose ``prepack_wire``
    declines every call, as an engine without the C++ ingest would."""
    inst.engine.prepack_wire = lambda *a, **kw: None


def fused_rows(inst) -> int:
    """``gubernator_wire_fused_requests_total``: rows that came in by
    the one C++ pass."""
    return int(inst.metrics.wire_fused_counter._value.get())


def served_by_lane(inst, rows: int) -> None:
    """``rows`` rows of what ``inst`` served were eligible for the fused
    lane: on the fused lane it took exactly those, on the columns lane
    none."""
    assert fused_rows(inst) == (rows if LANE == "fused" else 0)


def mk_instance(**cfg):
    inst = V1Instance(
        Config(**{"cache_size": 1 << 12, "sweep_interval_ms": 0, **cfg}),
        mesh=make_mesh(n=SHARDS))
    assert inst.engine.n == SHARDS
    if LANE == "columns":
        decline_prepack(inst)
    return inst


def to_wire(reqs):
    m = pb.GetRateLimitsReq()
    m.requests.extend(req_to_pb(r) for r in reqs)
    return m.SerializeToString()


def run_both(reqs, now=NOW, fused=None):
    """Same request stream through a fast-lane instance and a slow-path
    instance; returns (fast pb2 responses, slow responses).  ``fused``:
    the rows of it the fused lane serves (default: all)."""
    fast, slow = mk_instance(), mk_instance()
    try:
        out = pb.GetRateLimitsResp.FromString(
            fast.get_rate_limits_wire(to_wire(reqs), now_ms=now))
        served_by_lane(fast, len(reqs) if fused is None else fused)
        slow_rs = slow.get_rate_limits(reqs, now_ms=now)
        return list(out.responses), slow_rs
    finally:
        fast.close()
        slow.close()


def assert_match(fast_pb, slow_rs):
    assert len(fast_pb) == len(slow_rs)
    for i, (f, s) in enumerate(zip(fast_pb, slow_rs)):
        assert (int(f.status), f.limit, f.remaining, f.reset_time,
                f.error) == (int(s.status), s.limit, s.remaining,
                             s.reset_time, s.error), f"request {i}"


def test_parity_random_stream():
    rng = np.random.default_rng(11)
    reqs = []
    for i in range(400):
        alg = int(rng.integers(0, 2))
        beh = int(rng.choice([0, int(Behavior.RESET_REMAINING),
                              int(Behavior.DRAIN_OVER_LIMIT),
                              int(Behavior.NO_BATCHING)]))
        reqs.append(RateLimitRequest(
            name=f"wf{int(rng.integers(0, 5))}",
            unique_key=f"k{int(rng.integers(0, 40))}",
            hits=int(rng.integers(0, 4)),
            limit=int(rng.integers(1, 50)),
            duration=int(rng.integers(1000, 100_000)),
            algorithm=alg, behavior=beh,
            burst=int(rng.choice([0, 10, 100]))))
    fast, slow = run_both(reqs)
    assert_match(fast, slow)


def test_parity_gregorian_and_invalid_ordinal():
    reqs = [
        RateLimitRequest(name="g", unique_key="a", hits=1, limit=100,
                         duration=int(GregorianDuration.HOURS),
                         behavior=Behavior.DURATION_IS_GREGORIAN),
        RateLimitRequest(name="g", unique_key="bad", hits=1, limit=100,
                         duration=999,  # invalid ordinal → error resp
                         behavior=Behavior.DURATION_IS_GREGORIAN),
        RateLimitRequest(name="g", unique_key="a", hits=1, limit=100,
                         duration=int(GregorianDuration.HOURS),
                         behavior=Behavior.DURATION_IS_GREGORIAN),
    ]
    fast, slow = run_both(reqs, fused=0)  # the C++ pass has no calendar
    assert fast[1].error and "gregorian" in fast[1].error
    assert_match(fast, slow)


def test_parity_duplicate_heavy_single_key():
    reqs = [RateLimitRequest(name="dup", unique_key="k", hits=1, limit=10,
                             duration=60_000) for _ in range(25)]
    fast, slow = run_both(reqs)
    assert_match(fast, slow)
    assert sum(1 for f in fast if int(f.status) == 0) == 10


def test_fallback_paths_still_correct():
    # metadata → pb2 fallback; empty unique_key → per-request error;
    # GLOBAL → slow path (solo: local + global manager)
    reqs = [
        RateLimitRequest(name="m", unique_key="k", hits=1, limit=5,
                         duration=10_000, metadata={"trace": "x"}),
        RateLimitRequest(name="e", unique_key="", hits=1, limit=5,
                         duration=10_000),
        RateLimitRequest(name="gl", unique_key="k", hits=1, limit=5,
                         duration=10_000, behavior=Behavior.GLOBAL),
    ]
    fast, slow = run_both(reqs, fused=0)
    assert fast[1].error  # empty unique_key surfaces as error response
    assert_match(fast, slow)


def test_wire_eligible_batch_parses_natively():
    # guard: the parity tests above exercise the fast lane only if this
    # payload actually qualifies for it
    data = to_wire([RateLimitRequest(name="q", unique_key="k", hits=1,
                                     limit=5, duration=1000)])
    assert _wire_native.parse_get_rate_limits(data) is not None


def test_empty_batch_returns_empty_response():
    inst = mk_instance()
    try:
        out = pb.GetRateLimitsResp.FromString(
            inst.get_rate_limits_wire(
                pb.GetRateLimitsReq().SerializeToString(), now_ms=NOW))
        assert len(out.responses) == 0
        served_by_lane(inst, 0)
    finally:
        inst.close()


def test_malformed_bytes_raise_value_error():
    inst = mk_instance()
    try:
        with pytest.raises(ValueError, match="invalid GetRateLimitsReq"):
            inst.get_rate_limits_wire(b"\x99\x99 not a proto", now_ms=NOW)
    finally:
        inst.close()


def test_invalid_utf8_falls_back_not_accepted():
    # name bytes 0xFF 0xFE are not UTF-8: pb2 rejects the message, so the
    # fast lane must not silently accept it (same request, same outcome,
    # regardless of which lane runs)
    bad = bytes([0x0A, 0x08, 0x0A, 0x02, 0xFF, 0xFE, 0x12, 0x02, 0x6B,
                 0x31])
    assert _wire_native.parse_get_rate_limits(bad) is None


def test_multibyte_utf8_accepted_on_fast_lane():
    reqs = [RateLimitRequest(name="名前", unique_key="ключ", hits=1,
                             limit=5, duration=60_000)]
    assert _wire_native.parse_get_rate_limits(to_wire(reqs)) is not None
    fast, slow = run_both(reqs)
    assert_match(fast, slow)


def test_oversize_batch_raises():
    inst = mk_instance()
    try:
        reqs = [RateLimitRequest(name="o", unique_key=f"k{i}", hits=1,
                                 limit=5, duration=1000)
                for i in range(1001)]
        with pytest.raises(ValueError, match="too large"):
            inst.get_rate_limits_wire(to_wire(reqs), now_ms=NOW)
        served_by_lane(inst, 0)
    finally:
        inst.close()


def test_sequential_state_carries_across_wire_calls():
    inst = mk_instance()
    try:
        data = to_wire([RateLimitRequest(name="s", unique_key="k", hits=1,
                                         limit=3, duration=60_000)])
        statuses = []
        for i in range(5):
            out = pb.GetRateLimitsResp.FromString(
                inst.get_rate_limits_wire(data, now_ms=NOW + i))
            statuses.append(int(out.responses[0].status))
        assert statuses == [0, 0, 0, 1, 1]
        served_by_lane(inst, 5)
    finally:
        inst.close()


def test_wire_lane_auto_grows_under_live_pressure():
    """The wire lane inherits auto-grow: a tiny table fills with live
    keys and capacity doubles instead of surfacing 'table full'."""
    inst = mk_instance(cache_size=1 << 8, cache_autogrow_max=1 << 14)
    try:
        reqs = [RateLimitRequest(name="wag", unique_key=f"k{i}", hits=1,
                                 limit=9, duration=10**7)
                for i in range(900)]
        out = pb.GetRateLimitsResp.FromString(
            inst.get_rate_limits_wire(to_wire(reqs), now_ms=NOW))
        assert all(r.error == "" for r in out.responses)
        assert inst.engine.cap_local * inst.engine.n >= 1024
        # every key re-findable at its consumed value
        out = pb.GetRateLimitsResp.FromString(
            inst.get_rate_limits_wire(to_wire(reqs), now_ms=NOW + 1))
        assert {r.remaining for r in out.responses} == {7}
        served_by_lane(inst, 1800)
    finally:
        inst.close()
