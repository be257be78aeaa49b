"""The fused C++ wire ingest on a table of more than one shard (ISSUE
38): ``ShardedEngine.prepack_wire`` serves any shard count, and a call
the fused lane refuses is refused BEFORE it is packed.

Held here, on 1, 2 and 4 shards of the CPU mesh and on both engines:

* one mixed call — TOKEN and LEAKY rows, negative numbers the clamps
  take, a limit outside the Mosaic kernel's 30-bit domain, one key five
  times whose copies must apply in order, keys on every shard, rows
  stamped ``created_at`` so the call's clock runs backwards — is
  answered byte for byte alike by the fused lane and by the numpy lane
  (``_wire_check_columns``, through an engine whose ``prepack_wire``
  declines), call after call, and ``gubernator_wire_fused_requests_total``
  says which lane answered;
* a call with a GLOBAL or a MULTI_REGION row — first, middle or last —
  declines in the pre-pass: no pair is allocated, the full pass never
  runs, and the classic lane answers as before;
* a call with a calendar row (``DURATION_IS_GREGORIAN``; ISSUE 40) in
  any of the three positions IS packed, in one pass, and answered byte
  for byte as the twin without a fused lane answers it, across its
  period's end; one whose calendar row has an invalid ordinal is refused
  whole, inside the pass, counted ``reason="gregorian"``, and its
  per-row error is the classic lane's."""
import pytest

from gubernator_tpu import Algorithm
from gubernator_tpu.config import Config
from gubernator_tpu.core.batch import Rows
from gubernator_tpu.gregorian import gregorian_expiration
from gubernator_tpu.hashing import shard_of
from gubernator_tpu.instance import V1Instance, _wire_native
from gubernator_tpu.ops import pallas_step as ps
from gubernator_tpu.parallel import ShardedEngine, make_mesh, sharded
from gubernator_tpu.parallel.pallas_engine import PallasServingEngine
from gubernator_tpu.proto import gubernator_pb2 as pb
from gubernator_tpu.types import (Behavior, GregorianDuration,
                                  RateLimitRequest)
from gubernator_tpu.wire import req_to_tlv

if _wire_native is None:  # pragma: no cover
    pytest.skip("native extension not built", allow_module_level=True)

NOW = 1_791_000_000_000
ENGINES = {"xla_classic": ShardedEngine, "pallas_fused": PallasServingEngine}
MESHES = [(e, n) for e in ENGINES for n in (1, 2, 4)]


def wire(reqs) -> bytes:
    return b"".join(req_to_tlv(r) for r in reqs)


def fused_rows(inst) -> int:
    return int(inst.metrics.wire_fused_counter._value.get())


@pytest.fixture(scope="module", params=MESHES,
                ids=[f"{e}-{n}shards" for e, n in MESHES])
def lanes(request):
    """(engine kind, shards, the instance the fused lane serves, its
    twin whose engine declines every prepack: the numpy lane)."""
    kind, n = request.param
    pair = []
    for _ in range(2):
        eng = ENGINES[kind](make_mesh(n=n), capacity_per_shard=1 << 10,
                            batch_per_shard=64)
        pair.append(V1Instance(
            Config(cache_size=n << 10, sweep_interval_ms=0), engine=eng))
    pair[1].engine.prepack_wire = lambda *a, **kw: None
    yield kind, n, pair[0], pair[1]
    for inst in pair:
        inst.close()


def mixed_call():
    """The one call of the first test, and the index of the row whose
    limit the Mosaic kernel cannot represent."""
    tok = dict(name="fm", hits=1, limit=9, duration=60_000)
    leaky = dict(name="fm", hits=2, limit=40, duration=30_000, burst=50,
                 algorithm=Algorithm.LEAKY_BUCKET)
    reqs = []
    for i in range(24):  # keys enough to land on every shard
        reqs.append(RateLimitRequest(unique_key=f"t{i}", **tok))
        reqs.append(RateLimitRequest(unique_key=f"l{i}", **leaky))
    # one key five times, limit 3: its copies must apply in call order
    for j in range(5):
        reqs.insert(5 + 7 * j, RateLimitRequest(
            name="fm", unique_key="five", hits=1, limit=3, duration=60_000))
    # what pack_columns clamps: negative hits, a negative limit
    reqs.append(RateLimitRequest(name="fm", unique_key="neg", hits=-4,
                                 limit=-1, duration=60_000))
    # outside the kernel's domain (2^30 and up), inside the XLA step's
    reqs.append(RateLimitRequest(name="fm", unique_key="big", hits=1,
                                 limit=ps.VALUE_BOUND + 5, duration=60_000))
    # rows a forwarding peer stamped: the call's clock runs backwards
    for i, dt in enumerate((-40, 25, -3)):
        reqs.insert(11 * (i + 1), RateLimitRequest(
            name="fm", unique_key=f"t{i}", hits=1, limit=9,
            duration=60_000, created_at=NOW + dt))
    five = [i for i, r in enumerate(reqs) if r.unique_key == "five"]
    ood = next(i for i, r in enumerate(reqs) if r.unique_key == "big")
    assert len(five) == 5 and len(reqs) == 58
    return reqs, five, ood


def test_both_lanes_answer_one_mixed_call_byte_for_byte(lanes):
    kind, n, fused, columns = lanes
    reqs, five, ood = mixed_call()
    data = wire(reqs)
    pre = fused.engine.prepack_wire(data, NOW)
    assert pre is not None and pre.n == len(reqs)
    assert set(shard_of(pre.khash, n).tolist()) == set(range(n))
    assert not pre.rows.monotone and pre.rows.leaky == 24
    assert (None if pre.rows.ood is None else pre.rows.ood.tolist()) == (
        [ood] if kind == "pallas_fused" else None)
    before = fused_rows(fused)
    for call in range(3):  # state carries from call to call
        now = NOW + 1000 * call
        got = fused.get_rate_limits_wire(data, now_ms=now)
        want = columns.get_rate_limits_wire(data, now_ms=now)
        assert got == want, call
        out = pb.GetRateLimitsResp.FromString(got).responses
        assert len(out) == len(reqs)
        if call == 0:
            assert [int(out[f].status) for f in five] == [0, 0, 0, 1, 1]
            assert [out[f].remaining for f in five] == [2, 1, 0, 0, 0]
        # the row the kernel cannot hold is unservable there, and there
        # alone; every other row is answered
        errs = [i for i, r in enumerate(out) if r.error]
        assert errs == ([ood] if kind == "pallas_fused" else [])
    assert fused_rows(fused) - before == 3 * len(reqs)
    assert fused_rows(columns) == 0
    lane = 'gubernator_wire_lane_requests_total{lane="wire_local"}'
    for inst in (fused, columns):  # both lanes wear the one label
        assert lane.encode() in inst.metrics.render()


#: the behaviours a call declines for in the pre-pass: the two the
#: instance's policy hands it (_FUSED_EXCLUDED)
DECLINED = {
    "global": dict(behavior=Behavior.GLOBAL, duration=60_000),
    "multi_region": dict(behavior=Behavior.MULTI_REGION, duration=60_000),
}
AT = {"first": 0, "middle": 4, "last": 9}


def call_with(what: str, where: str, **odd):
    """Nine plain rows and the odd one at ``where``, as wire bytes."""
    plain = [RateLimitRequest(name="fd", unique_key=f"{what}{where}{i}",
                              hits=1, limit=5, duration=60_000)
             for i in range(9)]
    odd = RateLimitRequest(name="fd", unique_key=f"{what}{where}odd",
                           hits=1, limit=5, **odd)
    at = AT[where]
    return wire(plain[:at] + [odd] + plain[at:]), wire(plain)


@pytest.fixture
def spied(monkeypatch):
    """Counts of the pairs allocated and of the full passes run."""
    calls = {"empty": 0, "pass": 0}

    def count(name, fn):
        def spy(*a, **k):
            calls[name] += 1
            return fn(*a, **k)
        return spy

    monkeypatch.setattr(Rows, "empty", count("empty", Rows.empty))
    monkeypatch.setattr(sharded._wire_native, "pack_wire_wave",
                        count("pass", _wire_native.pack_wire_wave))
    return calls


def declined(inst, why: str) -> int:
    return int(inst.metrics.wire_fused_declined.labels(
        reason=why)._value.get())


@pytest.mark.parametrize("where", list(AT))
@pytest.mark.parametrize("what", DECLINED)
def test_a_declined_call_is_declined_before_it_is_packed(lanes, what, where,
                                                         spied):
    kind, n, fused, columns = lanes
    data, plain = call_with(what, where, **DECLINED[what])
    calls = spied
    excluded = int(V1Instance._FUSED_EXCLUDED)
    assert fused.engine.prepack_wire(data, NOW, excluded) is None
    assert calls == {"empty": 0, "pass": 0}
    # the engine alone (no policy handed in) declines only what its
    # pass cannot model
    assert fused.engine.prepack_wire(data, NOW) is not None
    calls.update(empty=0)
    calls["pass"] = 0
    before = fused_rows(fused)
    got = fused.get_rate_limits_wire(data, now_ms=NOW)
    assert calls["pass"] == 0  # Rows.empty: the classic lane's stack_rows
    assert fused_rows(fused) == before
    # answered by the classic lane as before: what the twin, which
    # never had a fused lane, answers
    assert got == columns.get_rate_limits_wire(data, now_ms=NOW)
    out = pb.GetRateLimitsResp.FromString(got).responses
    assert [(int(r.status), r.remaining, r.error) for r in out] == \
        [(0, 4, "")] * 10
    # and the same rows without the odd one ride the fused lane
    fused.get_rate_limits_wire(plain, now_ms=NOW + 1)
    assert fused_rows(fused) - before == 9 and calls["pass"] == 1


HOUR = 3_600_000


@pytest.mark.parametrize("where", list(AT))
def test_a_calendar_call_is_packed_in_one_pass_and_answered_as_the_classic_lane_does(
        lanes, where, spied):
    """A ``DURATION_IS_GREGORIAN`` row first, in the middle or last: the
    pre-pass lets it by, the ONE pass does its calendar, and call after
    call — six in its hour (limit 5: the sixth is OVER_LIMIT), one past
    the hour's end (a new bucket) — the answer is the classic lane's."""
    kind, n, fused, columns = lanes
    data, _ = call_with("gregorian", where,
                        behavior=Behavior.DURATION_IS_GREGORIAN,
                        duration=int(GregorianDuration.HOURS))
    at = AT[where]
    end = gregorian_expiration(NOW, GregorianDuration.HOURS)
    pre = fused.engine.prepack_wire(data, NOW,
                                    int(V1Instance._FUSED_EXCLUDED))
    assert pre is not None and pre.n == 10 and pre.rows.greg == 1
    assert pre.rows.batch.greg_end.tolist() == [
        end * (i == at) for i in range(10)]
    spied.update({"empty": 0, "pass": 0})
    before = fused_rows(fused), declined(fused, "gregorian")
    greg0 = [int(i.metrics.wave_gregorian_rows._value.get())
             for i in (fused, columns)]
    clocks = [NOW + 7 * k for k in range(6)] + [end + 5]
    for k, now in enumerate(clocks):
        got = fused.get_rate_limits_wire(data, now_ms=now)
        assert got == columns.get_rate_limits_wire(data, now_ms=now), k
        odd = pb.GetRateLimitsResp.FromString(got).responses[at]
        assert not odd.error
        assert (int(odd.status), odd.remaining, odd.reset_time) == (
            (0, 4, end), (0, 3, end), (0, 2, end), (0, 1, end),
            (0, 0, end), (1, 0, end), (0, 4, end + HOUR))[k]
    assert spied["pass"] == len(clocks)  # one pass a call, no more
    assert fused_rows(fused) - before[0] == 10 * len(clocks)
    assert declined(fused, "gregorian") == before[1]
    assert fused_rows(columns) == 0
    assert [int(i.metrics.wave_gregorian_rows._value.get()) - g
            for i, g in zip((fused, columns), greg0)] == [len(clocks)] * 2


@pytest.mark.parametrize("ordinal", [GregorianDuration.MINUTES,
                                     GregorianDuration.MONTHS])
def test_forwarded_calendar_rows_take_the_period_of_their_stamp(lanes,
                                                                ordinal):
    """The peer wire (``_wire_peer_fused``): forwarded rows carry
    ``created_at``, a month ahead of the owner's clock here, and their
    period is the stamp's by the same line of the pass — answered as
    the classic lane answers, a row past the period's end included."""
    kind, n, fused, columns = lanes
    stamp = NOW + 31 * 86_400_000
    end = gregorian_expiration(stamp, ordinal)
    assert end != gregorian_expiration(NOW, ordinal)
    reqs = [RateLimitRequest(
        name="fp", unique_key=f"{int(ordinal)}k{i % 4}", hits=1, limit=5,
        duration=int(ordinal), behavior=Behavior.DURATION_IS_GREGORIAN,
        algorithm=Algorithm.LEAKY_BUCKET if i % 2 else Algorithm.TOKEN_BUCKET,
        created_at=end + 3 if i == 11 else stamp + i) for i in range(12)]
    data = wire(reqs)
    before = fused_rows(fused), declined(fused, "gregorian")
    for call in range(2):
        got = fused.get_peer_rate_limits_wire(data, now_ms=NOW + call)
        assert got == columns.get_peer_rate_limits_wire(data,
                                                        now_ms=NOW + call)
        out = pb.GetRateLimitsResp.FromString(got).responses
        # a LEAKY row a month wide is outside the Mosaic kernel's
        # domain (eff ≥ 2^31 ms) and unservable there, in either lane
        wide = kind == "pallas_fused" and ordinal == GregorianDuration.MONTHS
        assert [bool(r.error) for r in out] == [
            wide and i % 2 == 1 for i in range(12)]
        if call == 0:  # TOKEN rows: key 0 three times, then a new period
            assert [out[i].reset_time for i in (0, 4, 8)] == [end] * 3
            assert [out[i].remaining for i in (0, 4, 8)] == [4, 3, 2]
    assert fused_rows(fused) - before[0] == 24
    assert declined(fused, "gregorian") == before[1]


@pytest.mark.parametrize("where", list(AT))
def test_an_invalid_ordinal_declines_its_call_whole(lanes, where, spied):
    """The pre-pass cannot know (it reads behaviour bits, not
    durations): the pass refuses at the row, nothing of the call is
    served from it, and the classic lane answers — that ROW with its
    error, the others as ever."""
    kind, n, fused, columns = lanes
    data, plain = call_with("badordinal", where,
                            behavior=Behavior.DURATION_IS_GREGORIAN,
                            duration=9)
    at = AT[where]
    assert fused.engine.prepack_wire(
        data, NOW, int(V1Instance._FUSED_EXCLUDED)) is None
    assert spied["pass"] == 1
    before = fused_rows(fused), declined(fused, "gregorian")
    got = fused.get_rate_limits_wire(data, now_ms=NOW)
    assert fused_rows(fused) == before[0]
    assert declined(fused, "gregorian") == before[1] + 1
    assert got == columns.get_rate_limits_wire(data, now_ms=NOW)
    out = pb.GetRateLimitsResp.FromString(got).responses
    assert [(int(r.status), r.remaining, r.error) for r in out] == [
        (0, 0, "invalid gregorian duration ordinal: 9") if i == at
        else (0, 4, "") for i in range(10)]
    # the same rows without the odd one ride the fused lane
    fused.get_rate_limits_wire(plain, now_ms=NOW + 1)
    assert fused_rows(fused) - before[0] == 9


def test_the_pre_pass_reads_what_the_full_parse_reads():
    """``count_req_items`` with a mask: the last behavior of a request
    wins (proto3), LEN payloads are skipped by their length, and without
    a mask no payload is read at all."""
    glob = int(Behavior.GLOBAL)
    plain = req_to_tlv(RateLimitRequest(name="pp", unique_key="k", hits=1,
                                        limit=5, duration=1000))
    g = req_to_tlv(RateLimitRequest(name="pp", unique_key="g", hits=1,
                                    limit=5, duration=1000,
                                    behavior=Behavior.GLOBAL))

    def appended(tlv: bytes, field: bytes) -> bytes:
        payload = tlv[2:] + field
        return b"\x0a" + bytes([len(payload)]) + payload

    cnt = _wire_native.count_req_items
    assert cnt(plain * 3) == cnt(plain * 3, glob) == 3
    assert cnt(plain + g + plain) == 3 and cnt(plain + g + plain, glob) is None
    # a second behavior field overrides the first, either way round
    assert cnt(appended(g, b"\x38\x00"), glob) == 1
    assert cnt(appended(plain, b"\x38\x02"), glob) is None
    assert cnt(appended(plain, b"\x38\x02"),
               int(Behavior.MULTI_REGION)) == 1
    # a name that holds the bytes of a behavior field is a name
    named = req_to_tlv(RateLimitRequest(name="\x38\x02", unique_key="k",
                                        hits=1, limit=5, duration=1000))
    assert cnt(named, glob) == 1
    # framing the full parse refuses is refused here too — only where
    # the payload is read at all
    fixed = appended(plain, b"\x3d\x01\x00\x00\x00")  # field 7, fixed32
    assert cnt(fixed) == 1 and cnt(fixed, glob) is None
    assert _wire_native.parse_get_rate_limits(fixed) is None
    torn = appended(plain, b"\x38")  # a tag with no value
    assert cnt(torn) == 1 and cnt(torn, glob) is None
    assert cnt(b"\x12\x00", glob) is None and cnt(b"", glob) == 0
