"""The tenant learn of the wire lanes (ISSUE 28): learning which
tenant a khash belongs to costs O(rows) in numpy / C++ and O(distinct
unknown NAMES) in Python — and assigns what the per-name walk it
replaced assigned, conserves every row, and stays under its cap."""
import numpy as np
import pytest

from gubernator_tpu import analytics as A
from gubernator_tpu.analytics import KeyAnalytics, TenantLedger
from gubernator_tpu.hashing import hash_request_keys, mix64_np
from gubernator_tpu.types import RateLimitRequest, RateLimitResponse
from gubernator_tpu.wire import req_to_tlv

native = pytest.importorskip("gubernator_tpu.ops.native")

MAX_TENANTS = 8
N = 1000


def _reqs(rng, n=N):
    """``n`` requests over mixed names: two tenants by prefix, a name
    without the delimiter, more tenants than the ledger holds, and rows
    a limiter calls invalid (no hits, negative hits, limit 0)."""
    names = (["acme/x", "acme/z", "globex/y", "plain"]
             + [f"t{i:02d}/lim" for i in range(2 * MAX_TENANTS)])
    out = []
    for i in range(n):
        # the first rows name the tenants the asserts look for,
        # before the ledger is full
        name = names[i if i < 4 else int(rng.integers(0, len(names)))]
        key = f"k{int(rng.integers(0, 700))}"
        hits, limit = 1, 100
        if i % 97 == 0:
            hits = 0
        elif i % 101 == 0:
            hits = -3
        elif i % 103 == 0:
            limit = 0
        out.append(RateLimitRequest(name=name, unique_key=key, hits=hits,
                                    limit=limit, duration=10_000))
    return out


def _message(reqs) -> bytes:
    return b"".join(req_to_tlv(r) for r in reqs)


def _analytics(cap=None):
    ka = KeyAnalytics(metrics=None)
    ka._tenants = TenantLedger(max_tenants=MAX_TENANTS)
    if cap is not None:
        ka._kh.cap = cap
    return ka


def _learn_item(reqs):
    data = _message(reqs)
    _n, kh, _bo, toff, tlen, nh, _derived = native.pack_wire_wave(
        data, 1, np.empty((8, 1024), np.int64),
        np.empty((3, 1024), np.int32))
    return ("learn", data, kh, nh, toff, tlen, False)


def _tap(ka, lane, reqs, over):
    """One call through a lane's learn tap, then its wave's fold tap."""
    hits = np.array([r.hits for r in reqs], np.int64)
    if lane == "object":
        resps = [RateLimitResponse(status=int(o)) for o in over]
        assert ka.tap_reqs(reqs, resps)
        return
    if lane == "mixed":  # the fused ingest: pack_wire_wave
        _, data, kh, nh, toff, tlen, _raw = _learn_item(reqs)
        assert ka.tap_wire_names(data, kh, nh, toff, tlen)
    else:  # the classic lanes: parse_get_rate_limits, pre-mix khash
        data = _message(reqs)
        p = native.parse_get_rate_limits(data)
        assert ka.tap_wire_names(data, p["khash_raw"], p["name_hash"],
                                 p["tlv_off"], p["tlv_len"], raw=True)
        kh = mix64_np(p["khash_raw"])
    assert ka.tap_packed(kh, hits, over.astype(np.int64))


def _walk(ref: TenantLedger, data: bytes, hits, over) -> None:
    """What the parent did: every request's name, in row order."""
    tidx = np.array([ref.index_of(name)
                     for name, _ in A.iter_wire_names(data)], np.int64)
    ref.fold(tidx, hits, over)


@pytest.mark.parametrize("lane", ["mixed", "raw", "object"])
def test_buckets_equal_the_per_name_walk(lane):
    rng = np.random.default_rng(28)
    ka = _analytics()
    ref = TenantLedger(max_tenants=MAX_TENANTS)
    try:
        for _ in range(3):
            reqs = _reqs(rng)
            over = rng.integers(0, 2, len(reqs)).astype(bool)
            _tap(ka, lane, reqs, over)
            _walk(ref, _message(reqs),
                  np.array([r.hits for r in reqs], np.int64), over)
        assert ka.flush()
        got, want = ka.tenants_snapshot(), ref.snapshot()
        assert got["tenants"] == want["tenants"]
        assert got["overflowed"] and want["overflowed"]
        assert got["totals"]["requests"] == 3 * N
        assert set(got["tenants"]) >= {"acme", "globex", "plain",
                                       TenantLedger.OTHER}
    finally:
        ka.close()


def test_every_row_in_one_bucket_under_churn():
    """50 calls of fresh keys over a 64-key table: totals conserved,
    the table never above its cap, and a shed key learns again."""
    rng = np.random.default_rng(5)
    ka = _analytics(cap=64)
    sent = 0
    try:
        first = None
        for c in range(50):
            reqs = [RateLimitRequest(name="acme/x", unique_key=f"c{c}_{i}",
                                     hits=1, limit=9, duration=1000)
                    for i in range(100)]
            first = first or reqs
            _tap(ka, "mixed", reqs, rng.integers(0, 2, 100).astype(bool))
            assert ka.flush()
            sent += 100
            assert len(ka._kh) <= 64
            snap = ka.tenants_snapshot()
            assert sum(t["requests"] for t in snap["tenants"].values()) \
                == snap["totals"]["requests"] == sent
            assert set(snap["tenants"]) == {"acme", TenantLedger.OTHER}
        # the first call's keys were shed long ago: unknown now ...
        kh = hash_request_keys([r.name for r in first[:20]],
                               [r.unique_key for r in first[:20]])
        assert not ka._kh.known(kh).any()
        before = ka.tenants_snapshot()["tenants"]["acme"]["requests"]
        # ... and learnt again on their next appearance
        _tap(ka, "mixed", first[:20], np.zeros(20, bool))
        assert ka.flush()
        assert ka._kh.known(kh).all()
        assert ka.tenants_snapshot()["tenants"]["acme"]["requests"] \
            == before + 20
        assert len(ka._kh) <= 64
    finally:
        ka.close()


def _under(name, lo, n):
    return [RateLimitRequest(name=name, unique_key=f"u{lo + i}", hits=1,
                             limit=9, duration=1000) for i in range(n)]


def test_python_work_is_by_the_new_name_not_by_the_row(monkeypatch,
                                                       numpy_calls):
    ka = _analytics()
    ka.close()  # the learn runs in THIS thread, where it is counted
    walked, asked = [], []
    walk, index_of = A.iter_wire_names, TenantLedger.index_of
    monkeypatch.setattr(
        A, "iter_wire_names",
        lambda data: walked.append(len(data)) or walk(data))
    monkeypatch.setattr(
        TenantLedger, "index_of",
        lambda self, name, pre_split=False:
            asked.append(name) or index_of(self, name, pre_split))

    ka._learn([_learn_item(_under("acme/x", 0, 10))])
    assert len(walked) == 1 and asked == ["acme/x"]

    def count(item):
        del walked[:], asked[:]
        with numpy_calls() as c:
            ka._learn([item])
        return c.n

    # 1,000 never-seen keys under the ONE known name: no name is read,
    # and numpy is asked the same whatever the rows
    big = _learn_item(_under("acme/x", 1000, 1000))
    n_new = count(big)
    assert walked == [] and asked == []
    assert ka._kh.known(big[2]).all()
    n_new_small = count(_learn_item(_under("acme/x", 5000, 200)))
    assert walked == [] and asked == []
    assert n_new == n_new_small
    # the same call again: all known, one probe
    n_known = count(big)
    assert walked == [] and asked == []
    assert 0 < n_known < n_new <= n_known + 60  # the merge's constant

    # K new names: K single-TLV walks, K bucket assignments
    reqs = _under("acme/x", 9000, 300)
    for k in range(5):
        for r in reqs[50 * k + 7: 50 * k + 17]:
            r.name = f"new{k}/lim"
    item = _learn_item(reqs)
    count(item)
    assert len(walked) == 5 and len(asked) == 5
    assert max(walked) < len(item[1]) // 100  # one request, not the call
    assert asked == [f"new{k}/lim" for k in range(5)]
    assert ka._kh.known(item[2]).all()


def test_learn_counts_rows_by_outcome():
    from gubernator_tpu.metrics import Metrics

    m = Metrics()
    ka = KeyAnalytics(metrics=m)
    ka._tenants = TenantLedger(max_tenants=1)
    ka.close()
    ka._safe_learn([_learn_item(_under("acme/x", 0, 30)
                                + _under("late/x", 0, 5))])
    ka._safe_learn([_learn_item(_under("acme/x", 0, 30))])
    rows = {o: m.analytics_learn_rows.labels(outcome=o)._value.get()
            for o in ("known", "learned", "other")}
    assert rows == {"known": 30, "learned": 30, "other": 5}
    assert ka.phases.snapshot()["analytics.learn"]["count"] == 2


def test_tenant_hint_and_flags_read_the_published_table():
    ka = _analytics()
    try:
        reqs = _under("acme/x", 0, 4)
        _tap(ka, "mixed", reqs, np.zeros(4, bool))
        assert ka.flush()
        kh = int(hash_request_keys(["acme/x"], ["u0"])[0])
        assert ka.tenant_hint(khash=kh) == "acme"
        assert ka.tenant_hint(khash=kh ^ 1) is None
        assert ka.tenant_hint(khash=kh ^ 1, name="globex/q") == "globex"
        assert ka.tap_flag("errors", 2, khash=kh)
        assert ka.tap_flag("shed", 1, khash=kh ^ 1)
        assert ka.flush()
        t = ka.tenants_snapshot()["tenants"]
        assert t["acme"]["errors"] == 2
        assert t[TenantLedger.OTHER]["shed"] == 1
    finally:
        ka.close()
