"""Columnar solo-GLOBAL wire lane: the hot-set psum tier driven from
wire bytes (instance._wire_global_runner), vs the object path."""
import pytest

from gubernator_tpu.config import BehaviorConfig, Config
from gubernator_tpu.hashing import hash_key
from gubernator_tpu.instance import V1Instance, _wire_native
from gubernator_tpu.parallel import make_mesh
from gubernator_tpu.proto import gubernator_pb2 as pb
from gubernator_tpu.types import Behavior, RateLimitRequest
from gubernator_tpu.wire import req_to_pb

if _wire_native is None:  # pragma: no cover
    pytest.skip("native extension not built", allow_module_level=True)

NOW = 1_773_000_000_000


def mk_instance(threshold=4):
    # sync_wait effectively infinite: these tests assert exact
    # replica-local values, so the periodic psum fold must only run
    # when called explicitly (a tick mid-test legally changes
    # remaining — GLOBAL is eventually consistent)
    return V1Instance(
        Config(cache_size=1 << 10, sweep_interval_ms=0,
               hot_set_capacity=64, hot_promote_threshold=threshold,
               behaviors=BehaviorConfig(global_sync_wait_ms=10**9)),
        mesh=make_mesh(n=4))


def greq(key="wg", hits=1, limit=1000, duration=600_000, **kw):
    kw.setdefault("behavior", Behavior.GLOBAL)
    return RateLimitRequest(name="wgl", unique_key=key, hits=hits,
                            limit=limit, duration=duration, **kw)


def wire(reqs):
    m = pb.GetRateLimitsReq()
    m.requests.extend(req_to_pb(r) for r in reqs)
    return m.SerializeToString()


def send(inst, reqs, now):
    return list(pb.GetRateLimitsResp.FromString(
        inst.get_rate_limits_wire(wire(reqs), now_ms=now)).responses)


def test_wire_global_promotes_then_serves_hot():
    inst = mk_instance(threshold=4)
    try:
        kh = hash_key("wgl", "wg")
        rs = send(inst, [greq() for _ in range(6)], NOW)
        assert all(r.error == "" and int(r.status) == 0 for r in rs)
        # threshold crossed inside the batch → pinned after the drain
        assert inst._hotset is not None and inst._hotset.is_pinned(kh)
        # hot serving: replicas answer; one sync folds consumption
        rs = send(inst, [greq() for _ in range(40)], NOW + 1)
        assert all(r.error == "" and int(r.status) == 0 for r in rs)
        inst._hotset.sync()
        rs = send(inst, [greq(hits=0)] * 4, NOW + 2)
        assert len({r.remaining for r in rs}) == 1
        # 6 pre-promotion hits survive in the seed + 40 hot hits
        assert rs[0].remaining == 1000 - 46
    finally:
        inst.close()


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
        assert wi._hotset is not None and len(wi._hotset.slots) == 3
        assert len(oi._hotset.slots) == 3
    finally:
        wi.close()
        oi.close()


def test_wire_global_config_change_demotes():
    inst = mk_instance(threshold=1)
    try:
        kh = hash_key("wgl", "cfg")
        send(inst, [greq(key="cfg", limit=100)], NOW)
        send(inst, [greq(key="cfg", limit=100) for _ in range(10)],
             NOW + 1)
        assert inst._hotset.is_pinned(kh)
        # changed limit → object-path fallback demotes and re-limits
        r = send(inst, [greq(key="cfg", limit=50)], NOW + 2)[0]
        assert not inst._hotset.is_pinned(kh)
        assert r.limit == 50
        # 11 consumed at limit 100 → 89; 100→50 adjust → 39; −1 → 38
        assert r.remaining == 38
    finally:
        inst.close()


def test_wire_global_flagged_pinned_key_falls_back():
    inst = mk_instance(threshold=1)
    try:
        kh = hash_key("wgl", "flg")
        send(inst, [greq(key="flg")], NOW)
        send(inst, [greq(key="flg")], NOW + 1)
        assert inst._hotset.is_pinned(kh)
        r = send(inst, [greq(
            key="flg",
            behavior=Behavior.GLOBAL | Behavior.RESET_REMAINING)],
            NOW + 2)[0]
        assert not inst._hotset.is_pinned(kh)  # demoted by object path
        assert r.remaining == 999  # RESET_REMAINING → full minus 1
    finally:
        inst.close()


def test_wire_mixed_global_and_local_batch():
    inst = mk_instance(threshold=2)
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


def test_wire_global_leaky_rides_hot_tier():
    from gubernator_tpu.types import Algorithm

    inst = mk_instance(threshold=2)
    try:
        kh = hash_key("wgl", "lk")
        lr = [greq(key="lk", algorithm=Algorithm.LEAKY_BUCKET)
              for _ in range(10)]
        rs = send(inst, lr, NOW)
        assert all(int(r.status) == 0 for r in rs)
        assert inst._hotset.is_pinned(kh)
        rs = send(inst, lr, NOW + 1)
        assert all(int(r.status) == 0 for r in rs)
        inst._hotset.sync()
        rs = send(inst, [greq(key="lk", hits=0,
                              algorithm=Algorithm.LEAKY_BUCKET)],
                  NOW + 2)
        assert rs[0].remaining == 1000 - 20
    finally:
        inst.close()


# ---- ISSUE 25: the pinned-key pass shared with _wire_mesh_runner -------
#
# _wire_global_runner's config match over pinned keys is the mesh
# runner's (instance.py › _group_key_configs): the parameters of
# tests/test_mesh_global.py, over the hot set.

import numpy as np  # noqa: E402

from gubernator_tpu import instance as instance_mod  # noqa: E402
from gubernator_tpu.types import Algorithm  # noqa: E402
from gubernator_tpu.wire import resp_to_pb  # noqa: E402

ROWS = 1000


@pytest.fixture(scope="module")
def big_pair():
    """(wire, object) instances whose hot set holds a 1,000-key call;
    every case empties it first (HotSetEngine.unpin_all)."""
    mk = lambda: V1Instance(  # noqa: E731
        Config(cache_size=1 << 14, sweep_interval_ms=0,
               hot_set_capacity=4096, hot_promote_threshold=1,
               behaviors=BehaviorConfig(global_sync_wait_ms=10**9)),
        mesh=make_mesh(n=4))
    wi, oi = mk(), mk()
    yield wi, oi
    wi.close()
    oi.close()


def empty_hot_set(*insts):
    for inst in insts:
        if inst._hotset is not None:
            inst._hotset.unpin_all()
        with inst._hot_mu:
            inst._hot_counts.clear()
            inst._promote_pending.clear()


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
    """1,000-row solo-GLOBAL calls: first touch (the call promotes its
    keys) and warm (all pinned) give the object path's bytes, on the
    wire lane."""
    wi, oi = big_pair
    empty_hot_set(wi, oi)
    reqs = call_of(shape, f"eq-{shape}-{state}-")
    data = wire(reqs)
    now = NOW
    if state == "warm":
        assert wi.get_rate_limits_wire(data, now_ms=now) == \
            oi.get_rate_limits_wire(data, now_ms=now)
        now += 1
    n_wire, n_pb2 = lane(wi, "wire_hotset"), lane(wi, "pb2_fallback")
    got = wi.get_rate_limits_wire(data, now_ms=now)
    assert got == obj_bytes(oi, reqs, now)
    assert lane(wi, "wire_hotset") - n_wire == ROWS
    assert lane(wi, "pb2_fallback") == n_pb2
    rs = pb.GetRateLimitsResp.FromString(got).responses
    assert len(rs) == ROWS and all(r.error == "" for r in rs)
    for inst in (wi, oi):  # threshold 1: every GLOBAL key is pinned now
        assert all(inst._hotset.is_pinned(hash_key(r.name, r.unique_key))
                   for r in reqs if r.behavior & Behavior.GLOBAL)


@pytest.mark.parametrize("case", ["mid-batch", "pinned-changed"])
def test_config_change_on_one_of_490_pinned_keys_returns_none(big_pair,
                                                              case):
    """One pinned key of ~490 changes its limit — on its last row, or
    on all of them: None, before anything moved; the object path
    demotes the key and serves the call."""
    wi, oi = big_pair
    empty_hot_set(wi, oi)
    ns = f"cc-{case}-"
    reqs = call_of("g490", ns)
    data = wire(reqs)
    assert wi.get_rate_limits_wire(data, now_ms=NOW) == \
        oi.get_rate_limits_wire(data, now_ms=NOW)
    rows_of = {}
    for i, r in enumerate(reqs):
        rows_of.setdefault(r.unique_key, []).append(i)
    victim = next(k for k, rows in sorted(rows_of.items())
                  if 2 <= len(rows) <= 6)
    hit = rows_of[victim] if case == "pinned-changed" \
        else rows_of[victim][-1:]
    for i in hit:
        reqs[i] = greq(victim, hits=reqs[i].hits, limit=999)
    data = wire(reqs)
    hs = wi._hotset

    def state():
        with hs._mu, wi._hot_mu:
            return (dict(hs.slots), dict(hs.pinned_cfg),
                    dict(wi._hot_counts), list(wi._promote_pending))

    before = state()
    assert wi._wire_global_runner(
        _wire_native.parse_get_rate_limits(data), NOW + 1) is None
    assert state() == before
    n_pb2 = lane(wi, "pb2_fallback")
    assert wi.get_rate_limits_wire(data, now_ms=NOW + 1) == \
        obj_bytes(oi, reqs, NOW + 1)
    assert lane(wi, "pb2_fallback") - n_pb2 == ROWS
    kh = hash_key("wgl", victim)  # demoted, then promoted anew
    assert hs.pinned_cfg.get(kh) == oi._hotset.pinned_cfg.get(kh) != \
        before[1][kh]


def test_pinned_key_pass_does_not_grow_with_distinct_keys(big_pair,
                                                          monkeypatch,
                                                          numpy_calls):
    """Warm 1,000-row calls with 12 and ~490 distinct pinned keys make
    the same numpy calls in the runner (profiler's count of numpy
    functions and ndarray methods), and build no RateLimitRequest."""
    wi, _ = big_pair
    empty_hot_set(wi)
    built = []
    real_req = instance_mod.RateLimitRequest
    counts = {}
    datas = {shape: wire(call_of(shape, "cx-")) for shape in ("g12", "g490")}
    for data in datas.values():  # promotes: both calls are warm below,
        wi.get_rate_limits_wire(data, now_ms=NOW)  # over ONE pinned set
    for shape, data in datas.items():
        parsed = _wire_native.parse_get_rate_limits(data)
        monkeypatch.setattr(
            instance_mod, "RateLimitRequest",
            lambda *a, **kw: (built.append(1), real_req(*a, **kw))[1])
        with numpy_calls() as calls:
            runner = wi._wire_global_runner(parsed, NOW + 1)
        monkeypatch.setattr(instance_mod, "RateLimitRequest", real_req)
        assert runner is not None
        counts[shape] = calls.n
        assert runner()
    assert built == []
    assert counts["g12"] == counts["g490"] > 0, counts
