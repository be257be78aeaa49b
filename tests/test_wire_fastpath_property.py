"""Property-based wire-lane parity (hypothesis): for ANY wire-encodable
request stream, get_rate_limits_wire (C++ columnar lane when eligible,
pb2 fallback otherwise) must match the sequential oracle bit-for-bit —
the same referee the object path answers to in test_property_parity."""
import pytest
from hypothesis import HealthCheck, given, settings
import os as _os

#: deep-fuzz multiplier: GUBER_FUZZ_X=20 turns the quick CI
#: budgets into a long adversarial run (same strategies)
_FX = int(_os.environ.get("GUBER_FUZZ_X", "1"))
from hypothesis import strategies as st

from gubernator_tpu import Algorithm, Behavior, Oracle, RateLimitRequest
from gubernator_tpu.config import Config
from gubernator_tpu.instance import V1Instance, _wire_native
from gubernator_tpu.parallel import make_mesh
from gubernator_tpu.proto import gubernator_pb2 as pb
from gubernator_tpu.wire import req_to_pb

if _wire_native is None:  # pragma: no cover
    pytest.skip("native extension not built", allow_module_level=True)

NOW = 1_772_000_000_000

_behavior = st.sampled_from([
    Behavior.BATCHING, Behavior.NO_BATCHING, Behavior.RESET_REMAINING,
    Behavior.DRAIN_OVER_LIMIT,
    Behavior.RESET_REMAINING | Behavior.DRAIN_OVER_LIMIT,
])

_request = st.builds(
    RateLimitRequest,
    # unicode names exercise the C++ UTF-8 path against pb2's encoder
    name=st.sampled_from(["prop", "προπ", "属性"]),
    unique_key=st.integers(0, 11).map(lambda i: f"k{i}"),  # forced dups
    hits=st.integers(0, 6) | st.just(2**40),  # clamp coverage
    limit=st.integers(0, 30) | st.just(2**40),
    duration=st.integers(1, 50_000),
    algorithm=st.sampled_from([Algorithm.TOKEN_BUCKET,
                               Algorithm.LEAKY_BUCKET]),
    behavior=_behavior,
    burst=st.integers(0, 40),
)

_stream = st.lists(
    st.tuples(st.lists(_request, min_size=1, max_size=40),
              st.integers(0, 40_000)),
    min_size=1, max_size=4)


def _wire(reqs):
    m = pb.GetRateLimitsReq()
    m.requests.extend(req_to_pb(r) for r in reqs)
    return m.SerializeToString()


#: lane × shards (ISSUE 38): the fused C++ ingest serves any shard
#: count; ``columns`` is the numpy lane, by an engine whose
#: ``prepack_wire`` declines.  The budget of examples the one two-shard
#: case had (20, on the numpy lane alone) is spread over the six.
LANES = [(lane, n) for lane in ("fused", "columns") for n in (1, 2, 4)]


@pytest.mark.parametrize("lane,shards", LANES,
                         ids=[f"{la}-{n}shards" for la, n in LANES])
@settings(max_examples=_FX * 6, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(_stream)
def test_wire_lane_matches_oracle_on_any_stream(lane, shards, stream):
    inst = V1Instance(Config(cache_size=1 << 11, sweep_interval_ms=0),
                      mesh=make_mesh(n=shards))
    if lane == "columns":
        inst.engine.prepack_wire = lambda *a, **kw: None
    try:
        oracle = Oracle()
        now = NOW
        rows = 0
        for reqs, dt in stream:
            now += dt
            rows += len(reqs)
            want = oracle.check_batch(reqs, now)
            out = pb.GetRateLimitsResp.FromString(
                inst.get_rate_limits_wire(_wire(reqs), now_ms=now))
            assert len(out.responses) == len(want)
            for i, (w, g) in enumerate(zip(want, out.responses)):
                assert g.error == ""
                assert (int(g.status), g.remaining, g.reset_time,
                        g.limit) == (int(w.status), w.remaining,
                                     w.reset_time, w.limit), (i, reqs[i])
        # WHICH lane answered: every call of the strategy is eligible
        assert inst.metrics.wire_fused_counter._value.get() == (
            rows if lane == "fused" else 0)
    finally:
        inst.close()
