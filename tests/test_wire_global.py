"""Solo GLOBAL in the default ``grpc`` mode: a daemon without peers
serves a GLOBAL row from its OWNER row in the sharded table — no replica
tier, nothing to reconcile — on the wire lane
(instance._wire_global_runner → _wire_check_columns) and on the object
path alike, and every answer is ``oracle.py``'s."""
import numpy as np
import pytest

from gubernator_tpu.config import BehaviorConfig, Config
from gubernator_tpu.hashing import hash_key
from gubernator_tpu.instance import V1Instance, _wire_native
from gubernator_tpu.oracle import Oracle
from gubernator_tpu.parallel import make_mesh
from gubernator_tpu.proto import gubernator_pb2 as pb
from gubernator_tpu.types import (Algorithm, Behavior, PeerInfo,
                                  RateLimitRequest)
from gubernator_tpu.wire import req_to_pb, resp_to_pb

if _wire_native is None:  # pragma: no cover
    pytest.skip("native extension not built", allow_module_level=True)

NOW = 1_773_000_000_000


def mk_instance(n=4, cache_size=1 << 10, **kw):
    # the sync tick held off: whatever a window could over-admit, it
    # would over-admit here
    return V1Instance(
        Config(cache_size=cache_size, sweep_interval_ms=0,
               behaviors=BehaviorConfig(global_sync_wait_ms=10**9), **kw),
        mesh=make_mesh(n=n))


def greq(key="wg", hits=1, limit=1000, duration=600_000, **kw):
    kw.setdefault("behavior", Behavior.GLOBAL)
    return RateLimitRequest(name="wgl", unique_key=key, hits=hits,
                            limit=limit, duration=duration, **kw)


def no_replica_tier(inst):
    return inst._meshglobal is None and not inst._mesh_mode()


def wire(reqs):
    m = pb.GetRateLimitsReq()
    m.requests.extend(req_to_pb(r) for r in reqs)
    return m.SerializeToString()


def send(inst, reqs, now):
    return list(pb.GetRateLimitsResp.FromString(
        inst.get_rate_limits_wire(wire(reqs), now_ms=now)).responses)


def test_wire_vs_object_path_parity():
    """The same solo-GLOBAL stream through the wire lane and the object
    path lands on identical decisions (same engines, same routing)."""
    wi, oi = mk_instance(), mk_instance()
    try:
        streams = [[greq(key=f"k{i % 3}") for i in range(12)]
                   for _ in range(4)]
        for t, reqs in enumerate(streams):
            got_w = send(wi, reqs, NOW + t)
            got_o = oi.get_rate_limits(reqs, now_ms=NOW + t)
            for i, (w, o) in enumerate(zip(got_w, got_o)):
                assert (int(w.status), w.remaining, w.reset_time,
                        w.limit, w.error) == \
                    (int(o.status), o.remaining, o.reset_time, o.limit,
                     o.error), (t, i)
        assert no_replica_tier(wi) and no_replica_tier(oi)
    finally:
        wi.close()
        oi.close()


def test_wire_mixed_global_and_local_batch():
    inst = mk_instance()
    try:
        reqs = [greq(key="mix") if i % 2 == 0 else
                RateLimitRequest(name="wgl", unique_key="loc", hits=1,
                                 limit=5, duration=60_000)
                for i in range(8)]
        rs = send(inst, reqs, NOW)
        assert all(r.error == "" for r in rs)
        # local key consumed 4 of 5
        assert rs[7].remaining == 1
    finally:
        inst.close()


# ---- 1,000-row calls: the wire lane against the object path -----------

ROWS = 1000


@pytest.fixture(scope="module")
def big_pair():
    """(wire, object) instances that take 1,000-key calls."""
    wi, oi = (mk_instance(cache_size=1 << 14) for _ in range(2))
    yield wi, oi
    wi.close()
    oi.close()


def lane(inst, name):
    return inst.metrics.wire_lane_counter.labels(lane=name)._value.get()


def key_draw(distinct):
    if distinct == 490:  # the benchmark cell's draw
        ks = np.random.default_rng(25).zipf(1.1, ROWS) % 1024
        assert 440 <= len(set(ks.tolist())) <= 540
        return ks.tolist()
    return [(i * 7919) % distinct for i in range(ROWS)]


def call_of(shape, ns):
    if shape.startswith("g"):
        return [greq(f"{ns}{k}", hits=1 + i % 3)
                for i, k in enumerate(key_draw(int(shape[1:])))]
    if shape == "mixed":
        return [greq(f"{ns}{k}") if i % 2 else
                RateLimitRequest(name="wgl", unique_key=f"{ns}loc{k % 40}",
                                 hits=1, limit=7, duration=60_000)
                for i, k in enumerate(key_draw(490))]
    assert shape == "leaky"
    return [greq(f"{ns}{k}", limit=50 + k % 3, burst=k % 2 * 80,
                 algorithm=Algorithm.LEAKY_BUCKET)
            for k in key_draw(12)]


def obj_bytes(inst, reqs, now):
    out = pb.GetRateLimitsResp()
    out.responses.extend(resp_to_pb(r)
                         for r in inst.get_rate_limits(reqs, now_ms=now))
    return out.SerializeToString()


@pytest.mark.parametrize("state", ["cold", "warm"])
@pytest.mark.parametrize("shape", ["g1", "g12", "g490", "g1000", "mixed",
                                   "leaky"])
def test_wire_lane_byte_equal_to_object_path(big_pair, shape, state):
    """1,000-row solo-GLOBAL calls, first touch and warm, give the
    object path's bytes on the ``wire_global`` lane: no pb2 fallback,
    no pin — the rows are the sharded table's."""
    wi, oi = big_pair
    reqs = call_of(shape, f"eq-{shape}-{state}-")
    data = wire(reqs)
    now = NOW
    if state == "warm":
        assert wi.get_rate_limits_wire(data, now_ms=now) == \
            oi.get_rate_limits_wire(data, now_ms=now)
        now += 1
    n_wire, n_pb2 = lane(wi, "wire_global"), lane(wi, "pb2_fallback")
    got = wi.get_rate_limits_wire(data, now_ms=now)
    assert got == obj_bytes(oi, reqs, now)
    assert lane(wi, "wire_global") - n_wire == ROWS
    assert lane(wi, "pb2_fallback") == n_pb2
    rs = pb.GetRateLimitsResp.FromString(got).responses
    assert len(rs) == ROWS and all(r.error == "" for r in rs)
    khs = np.array([hash_key(r.name, r.unique_key) for r in reqs
                    if r.behavior & Behavior.GLOBAL], np.uint64)
    for inst in (wi, oi):
        assert no_replica_tier(inst)
        assert inst.engine.gather_rows(khs)[0].all()


# ---- the owner row is exact: oracle.py, hit for hit ---------------------
#
# What the parent gave up at a key's 64th hit (it pinned the key into a
# per-chip replica that diverged until the next sync tick) and the owner
# row never does.


def same_as(want, got):
    return (int(got.status), got.remaining, got.reset_time, got.limit,
            got.error) == (int(want.status), want.remaining,
                           want.reset_time, want.limit, want.error)


def drive(inst, oracle, calls, t0=NOW):
    """``calls`` against the instance — even calls on the wire lane, odd
    ones on the object path — and against the oracle: every answer the
    same.  Returns the number of rows admitted."""
    admitted = 0
    for t, reqs in enumerate(calls):
        now = t0 + t
        got = send(inst, reqs, now) if t % 2 == 0 \
            else inst.get_rate_limits(reqs, now_ms=now)
        want = oracle.check_batch(reqs, now)
        for i, (w, g) in enumerate(zip(want, got)):
            assert same_as(w, g), (t, i, w, g)
            admitted += int(g.status) == 0 and reqs[i].hits > 0
    return admitted


@pytest.mark.parametrize("limit", [1000, (1 << 32) + 50, (1 << 45) + 7])
@pytest.mark.parametrize("algorithm", [Algorithm.TOKEN_BUCKET,
                                       Algorithm.LEAKY_BUCKET],
                         ids=["token", "leaky"])
@pytest.mark.parametrize("n_devices", [4, 1])
def test_solo_global_equals_the_oracle_past_64_hits(n_devices, algorithm,
                                                    limit):
    """70 single hits (the parent promoted the key at its 64th), then
    ten 40-row calls that together ask for more than the limit: every
    answer is the oracle's, so no window over-admits, whatever the
    limit's width (the table holds it as two 32-bit words)."""
    inst = mk_instance(n=n_devices)
    try:
        step = max(1, limit // 300)
        calls = [[greq("ex", limit=limit, algorithm=algorithm)]
                 for _ in range(70)]
        calls += [[greq("ex", hits=step, limit=limit, algorithm=algorithm)
                   for _ in range(40)] for _ in range(10)]
        admitted = drive(inst, Oracle(), calls)
        assert 70 < admitted < 470  # the limit was reached, and held
        assert no_replica_tier(inst)
    finally:
        inst.close()


@pytest.mark.parametrize("lane_of", ["wire", "object"])
def test_470_hits_on_a_limit_of_200_admit_exactly_200(lane_of):
    """One GLOBAL key of limit 200 on a 4-device mesh, the sync tick held
    off: 70 single hits, then ten 40-row calls.  The parent admitted 470
    (each chip's replica started at the 130 that were left)."""
    inst = mk_instance(n=4)
    try:
        calls = [[greq("x200", limit=200)] for _ in range(70)]
        calls += [[greq("x200", limit=200)] * 40 for _ in range(10)]
        admitted = 0
        for t, reqs in enumerate(calls):
            got = send(inst, reqs, NOW + t) if lane_of == "wire" \
                else inst.get_rate_limits(reqs, now_ms=NOW + t)
            admitted += sum(int(r.status) == 0 for r in got)
        assert admitted == 200
    finally:
        inst.close()


@pytest.mark.parametrize("flag", [Behavior.RESET_REMAINING,
                                  Behavior.DRAIN_OVER_LIMIT],
                         ids=["reset_remaining", "drain_over_limit"])
def test_flagged_request_on_a_much_hit_global_key(flag):
    """100 hits, then a flagged request (what demoted a pinned key), then
    more hits: the oracle's answers throughout."""
    inst = mk_instance(n=4)
    try:
        calls = [[greq("fl", limit=150)] * 25 for _ in range(4)]
        calls += [[greq("fl", hits=70, limit=150,
                        behavior=Behavior.GLOBAL | flag)]]
        calls += [[greq("fl", limit=150)] * 10 for _ in range(2)]
        oracle = Oracle()
        drive(inst, oracle, calls)
        r = send(inst, [greq("fl", hits=0, limit=150)], NOW + 99)[0]
        # RESET: full again, less its own 70 and the 20 after it;
        # DRAIN: 70 > the 50 left drains the row, and it stays drained
        assert r.remaining == (60 if flag == Behavior.RESET_REMAINING
                               else 0)
    finally:
        inst.close()


def test_limit_and_duration_change_mid_stream_keep_the_consumption():
    inst = mk_instance(n=4)
    try:
        calls = [[greq("cfg", limit=1000)] * 50 for _ in range(2)]
        calls += [[greq("cfg", limit=500)]]  # 900 → 400, −1
        calls += [[greq("cfg", limit=500, duration=300_000)] * 9]
        drive(inst, Oracle(), calls)
        r = send(inst, [greq("cfg", hits=0, limit=500,
                             duration=300_000)], NOW + 50)[0]
        assert r.limit == 500 and r.remaining == 390
    finally:
        inst.close()


def test_peers_joining_after_100_solo_hits_keep_the_consumed_row():
    """A peer joins after 100 solo hits: there is no tier to demote, the
    consumed row is where it always was, and the daemon — still the
    key's owner — answers the next hit from it."""
    me = "127.0.0.1:1"
    inst = mk_instance(n=4, advertise_address=me)
    try:
        peers = [PeerInfo(grpc_address=me),
                 PeerInfo(grpc_address="127.0.0.1:2")]
        inst.set_peers(peers)
        key = next(k for k in (f"join{i}" for i in range(64))
                   if inst.is_self(inst.owner_of(greq(k).key)))
        inst.set_peers([peers[0]])
        for t in range(4):
            send(inst, [greq(key, limit=100_000)] * 25, NOW + t)
        inst.set_peers(peers)
        found, cols = inst.engine.gather_rows(
            np.array([hash_key("wgl", key)], np.uint64))
        assert found[0] and int(cols["remaining"][0]) == 100_000 - 100
        n_clustered = lane(inst, "wire_clustered")
        r = send(inst, [greq(key, limit=100_000)], NOW + 5)[0]
        assert r.error == "" and r.remaining == 100_000 - 101
        assert lane(inst, "wire_clustered") - n_clustered == 1
        assert no_replica_tier(inst)
    finally:
        inst.close()
