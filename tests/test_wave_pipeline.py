"""Overlapped wave pipeline semantics (ISSUE 2 tentpole).

The depth-K launch/sync pipeline + pooled wave buffers + caller-thread
response build must be INVISIBLE at the contract level: per-request
response bytes identical to the pure-Python oracle and to depth-1
(no-overlap) execution under 16 concurrent callers and ≥3 overlapped
waves; a mid-stream engine exception resolves only the affected wave's
jobs; buffer-pool leases come back on every path.
"""
import gc
import threading
import time

import numpy as np
import pytest

pytest.importorskip("gubernator_tpu.ops.native")

from gubernator_tpu.core.batch import WaveBufferPool, pack_columns
from gubernator_tpu.dispatcher import Dispatcher, ResultView
from gubernator_tpu.hashing import hash_request_keys
from gubernator_tpu.parallel import ShardedEngine, make_mesh

NOW = 1_781_000_000_000
N_THREADS = 16
N_CALLS = 4


def _mk_instance(monkeypatch, depth: str, engine=None):
    from gubernator_tpu.config import Config
    from gubernator_tpu.instance import V1Instance

    monkeypatch.setenv("GUBER_PIPELINE_DEPTH", depth)
    mesh = None if engine is not None else make_mesh(n=1)
    return V1Instance(Config(cache_size=1 << 12, sweep_interval_ms=0),
                      mesh=mesh, engine=engine)


def _thread_datas():
    """Per-thread wire batches over THREAD-PRIVATE key namespaces, so
    results are deterministic under any caller interleaving (the shared
    engine applies each request at its own per-request now)."""
    from gubernator_tpu.proto import gubernator_pb2 as pb
    from gubernator_tpu.types import RateLimitRequest
    from gubernator_tpu.wire import req_to_pb

    datas = {}
    for t in range(N_THREADS):
        per_call = []
        for r in range(N_CALLS):
            m = pb.GetRateLimitsReq()
            m.requests.extend(
                req_to_pb(RateLimitRequest(
                    name="pipe", unique_key=f"t{t}k{i % 7}", hits=1,
                    limit=50, duration=60_000))
                for i in range(25))
            per_call.append(m.SerializeToString())
        datas[t] = per_call
    return datas


def _drive(inst, datas):
    """16 threads × N_CALLS wire calls; returns {(thread, call): bytes}."""
    out = {}
    lock = threading.Lock()

    def worker(t):
        for r in range(N_CALLS):
            raw = inst.get_rate_limits_wire(datas[t][r],
                                            now_ms=NOW + r)
            with lock:
                out[(t, r)] = raw

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(N_THREADS)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    return out


def test_overlapped_pipeline_byte_parity_oracle_and_depth1(monkeypatch):
    """≥3 overlapped waves under 16 concurrent callers: response bytes
    equal the oracle's and depth-1's, per request."""
    datas = _thread_datas()

    inst2 = _mk_instance(monkeypatch, depth="2")
    try:
        got2 = _drive(inst2, datas)
        stats = inst2.dispatcher.debug_stats()
        assert stats["pipeline_depth"] == 2
        events = inst2.recorder.events()
        piped = [e for e in events if e["kind"] == "wave_launched"
                 and e.get("wave_kind") == "packed_pipelined"]
        assert len(piped) >= 3, (
            f"expected >=3 pipelined waves, got {len(piped)}")
        # the pipeline actually overlapped: some launch entered the
        # ring while an older wave was still in flight (slot > 0)
        assert any(e.get("slot", 0) > 0 for e in piped), piped[:5]
        pool = inst2.engine.wave_pool.stats()
        assert pool["outstanding"] == 0 and pool["leaks"] == 0, pool
    finally:
        inst2.close()

    inst1 = _mk_instance(monkeypatch, depth="1")
    try:
        got1 = _drive(inst1, datas)
    finally:
        inst1.close()
    assert got1 == got2, "depth-1 vs depth-2 wire bytes diverged"

    # oracle reference: the pure-Python engine through the object path,
    # serialized with pb2 — must match the native-built wire bytes
    from gubernator_tpu.oracle import OracleEngine
    from gubernator_tpu.proto import gubernator_pb2 as pb
    from gubernator_tpu.wire import req_from_pb, resp_to_pb

    oracle_inst = _mk_instance(monkeypatch, depth="1",
                               engine=OracleEngine())
    try:
        for (t, r), raw in sorted(got2.items()):
            msg = pb.GetRateLimitsReq.FromString(datas[t][r])
            reqs = [req_from_pb(m) for m in msg.requests]
            want = oracle_inst.get_rate_limits(reqs, now_ms=NOW + r)
            ref = pb.GetRateLimitsResp()
            ref.responses.extend(resp_to_pb(x) for x in want)
            assert raw == ref.SerializeToString(), (t, r)
    finally:
        oracle_inst.close()


@pytest.mark.parametrize("branch", ["pipelined", "serial"])
def test_midstream_engine_exception_fails_only_its_wave(
        branch, monkeypatch, serial_only):
    """An engine raise mid-stream resolves ONLY the affected wave's
    jobs with the error; earlier and later waves are untouched, on
    both worker branches (an engine with ``launch_packed`` and one
    without).  Deterministic: the worker is held inside wave A while
    jobs B1/B2 queue into wave B, whose sync/check raises."""
    monkeypatch.setenv("GUBER_PIPELINE_DEPTH", "2")
    eng = ShardedEngine(make_mesh(n=1), capacity_per_shard=1 << 9,
                        batch_per_shard=64)
    release = threading.Event()
    entered = threading.Event()
    calls = {"n": 0}
    orig_launch = eng.launch_packed
    orig_sync = eng.sync_packed
    orig_cp = eng.check_packed

    def gated_launch(batch, kh, now):
        calls["n"] += 1
        tag = calls["n"]
        if tag == 1:
            entered.set()
            release.wait(timeout=30)
        return (tag, orig_launch(batch, kh, now))

    def tagged_sync(token, engine_lock=None):
        tag, inner = token
        if tag == 2:
            raise RuntimeError("device on fire (wave B)")
        return orig_sync(inner, engine_lock=engine_lock)

    def gated_cp(batch, kh, now):
        calls["n"] += 1
        if calls["n"] == 1:
            entered.set()
            release.wait(timeout=30)
        if calls["n"] == 2:
            raise RuntimeError("device on fire (wave B)")
        return orig_cp(batch, kh, now)

    if branch == "pipelined":
        eng.launch_packed = gated_launch
        eng.sync_packed = tagged_sync
        orig_drop = eng.drop_packed
        eng.drop_packed = lambda token: orig_drop(token[1])
        disp = Dispatcher(eng, max_delay_ms=0.2)
    else:
        eng.check_packed = gated_cp
        disp = Dispatcher(serial_only(eng), max_delay_ms=0.2)
    assert disp._pipelined == (branch == "pipelined")

    def cols(tag, now):
        kh = hash_request_keys(["pw"] * 4,
                               [f"{tag}{i}" for i in range(4)])
        b, _ = pack_columns(kh, np.ones(4, np.int64),
                            np.full(4, 50, np.int64),
                            np.full(4, 60_000, np.int64),
                            np.zeros(4, np.int32), np.zeros(4, np.int32),
                            np.zeros(4, np.int64), now)
        return b, kh

    results = {}

    def call(tag, now):
        b, kh = cols(tag, now)
        try:
            results[tag] = disp.check_packed(b, kh, now)
        except Exception as e:  # noqa: BLE001
            results[tag] = e

    # wave A blocks the worker inside the engine; B1/B2 queue behind it
    try:
        threads = [threading.Thread(target=call, args=("a", NOW))]
        threads[0].start()
        assert entered.wait(timeout=30)
        threads.append(threading.Thread(target=call, args=("b1", NOW + 1)))
        threads.append(threading.Thread(target=call, args=("b2", NOW + 2)))
        for th in threads[1:]:
            th.start()
        deadline = time.monotonic() + 30
        while disp._queue.qsize() < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert disp._queue.qsize() >= 2
    finally:
        release.set()
    for th in threads:
        th.join(timeout=60)
    # wave A resolved cleanly, both wave-B jobs carry THE error
    assert not isinstance(results["a"], Exception)
    assert results["a"][0].shape == (4,)
    for tag in ("b1", "b2"):
        assert isinstance(results[tag], RuntimeError), results[tag]
        assert "wave B" in str(results[tag])
    # the pipeline recovered: a later wave serves normally
    call("c", NOW + 3)
    assert not isinstance(results["c"], Exception), results["c"]
    # no lease stranded by the raise
    stats = eng.wave_pool.stats()
    assert stats["outstanding"] == 0 and stats["leaks"] == 0, stats
    disp.close()


def test_result_view_unpacks_like_tuple():
    cols = tuple(np.arange(10) + i for i in range(5))
    v = ResultView(cols, 2, 5)
    st, lim, rem, rst, full = v
    assert st.tolist() == [2, 3, 4]
    assert full.tolist() == [6, 7, 8]
    assert len(v) == 5
    assert v.sliced()[1].tolist() == [3, 4, 5]


def test_buffer_pool_reuse_error_release_and_leak_detection():
    pool = WaveBufferPool(max_per_width=2)
    l1 = pool.lease(128)
    l1.a64[0, 0] = 99
    l1.release()
    l2 = pool.lease(128)
    # pooled buffer comes back zeroed to empty-batch padding semantics
    assert l2.a64[0, 0] == 0 and l2.a64.shape == (8, 128)
    l2.release()
    l2.release()  # idempotent
    assert pool.stats()["hits"] == 1 and pool.stats()["misses"] == 1
    # a dropped lease is a counted leak, and its buffers are reclaimed
    l3 = pool.lease(128)
    del l3
    gc.collect()
    s = pool.stats()
    assert s["leaks"] == 1 and s["outstanding"] == 0, s


def test_engine_raise_releases_lease():
    eng = ShardedEngine(make_mesh(n=1), capacity_per_shard=1 << 9,
                        batch_per_shard=64)

    def boom(a64, a32, now):
        raise RuntimeError("launch failed")

    eng._launch_arrays = boom
    kh = hash_request_keys(["lr"] * 4, [f"k{i}" for i in range(4)])
    b, _ = pack_columns(kh, np.ones(4, np.int64),
                        np.full(4, 50, np.int64),
                        np.full(4, 60_000, np.int64),
                        np.zeros(4, np.int32), np.zeros(4, np.int32),
                        np.zeros(4, np.int64), NOW)
    with pytest.raises(RuntimeError, match="launch failed"):
        eng.check_packed(b, kh, NOW)
    s = eng.wave_pool.stats()
    assert s["outstanding"] == 0 and s["leaks"] == 0, s


def test_drain_wave_never_overshoots_max_wave():
    """A job that would push the wave past max_wave leads the NEXT wave
    (no sparse tail launch at the small bucket)."""

    class NopEngine:
        def check_packed(self, batch, khash, now):
            m = len(khash)
            return (np.zeros(m, np.int32), np.zeros(m, np.int64),
                    np.zeros(m, np.int64), np.zeros(m, np.int64),
                    np.zeros(m, bool))

    eng = NopEngine()
    sizes = []
    orig = eng.check_packed

    def spy(batch, kh, now):
        sizes.append(len(kh))
        return orig(batch, kh, now)

    eng.check_packed = spy
    disp = Dispatcher(eng, max_wave=2048, max_delay_ms=0.2)
    n = 1000
    kh = hash_request_keys(["ow"] * n, [f"k{i}" for i in range(n)])
    b, _ = pack_columns(kh, np.ones(n, np.int64),
                        np.full(n, 50, np.int64),
                        np.full(n, 60_000, np.int64),
                        np.zeros(n, np.int32), np.zeros(n, np.int32),
                        np.zeros(n, np.int64), NOW)
    # stall the worker's first wave until the other three are queued
    release = threading.Event()
    entered = threading.Event()

    def gated(batch, khash, now):
        entered.set()
        release.wait(timeout=30)
        return spy(batch, khash, now)

    eng.check_packed = gated
    threads = []
    try:
        for t in range(4):
            th = threading.Thread(
                target=lambda t=t: disp.check_packed(b, kh, NOW + t))
            th.start()
            threads.append(th)
            if t == 0:
                assert entered.wait(timeout=30)
        deadline = time.monotonic() + 30
        while disp._queue.qsize() < 3 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert disp._queue.qsize() >= 3
    finally:
        release.set()
    for th in threads:
        th.join(timeout=60)
    # wave 1: the blocker alone; wave 2: exactly two jobs (2000 rows,
    # within max_wave 2048); wave 3: the carried job
    assert sizes == [1000, 2000, 1000], sizes
    disp.close()


def test_coalesce_window_env_override(monkeypatch):
    class E:
        def check_batch(self, reqs, now):
            return []

    monkeypatch.setenv("GUBER_COALESCE_US", "50000")
    d = Dispatcher(E())
    try:
        assert d.max_delay_s == pytest.approx(0.05)
    finally:
        d.close()
    monkeypatch.setenv("GUBER_COALESCE_US", "0")
    d = Dispatcher(E())
    try:
        assert d.max_delay_s == 0.0
    finally:
        d.close()
    monkeypatch.setenv("GUBER_COALESCE_US", "junk")
    d = Dispatcher(E())
    try:
        assert d.max_delay_s == pytest.approx(0.0002)
    finally:
        d.close()


def test_pipeline_depth_env_parsing(monkeypatch):
    class E:
        def check_batch(self, reqs, now):
            return []

    for raw, want in (("4", 4), ("1", 1), ("0", 1), ("-3", 1),
                      ("junk", 2), ("", 2)):
        monkeypatch.setenv("GUBER_PIPELINE_DEPTH", raw)
        d = Dispatcher(E())
        try:
            assert d.pipeline_depth == want, raw
        finally:
            d.close()


def test_launched_wave_keeps_its_upload_buffers_until_synced():
    """A launch is asynchronous: the runtime may read a wave's host
    operands after launch_packed returns (the CPU backend aliases
    them).  Back-to-back launches of same-width waves must therefore
    not share a pooled buffer — wave 1 must still answer for ITS rows
    (its own limit) after wave 2 was packed and launched."""
    eng = ShardedEngine(make_mesh(n=1), capacity_per_shard=1 << 18,
                        batch_per_shard=4096)  # load stays < 0.16
    n = 4000

    def cols(tag, limit, now):
        kh = hash_request_keys(["pw"] * n,
                               [f"{tag}{i}" for i in range(n)])
        b, _ = pack_columns(kh, np.ones(n, np.int64),
                            np.full(n, limit, np.int64),
                            np.full(n, 60_000, np.int64),
                            np.zeros(n, np.int32), np.zeros(n, np.int32),
                            np.zeros(n, np.int64), now)
        return b, kh

    eng.warmup()
    for rep in range(5):
        waves = [cols(f"r{rep}a", 50, NOW + rep), cols(f"r{rep}b", 70,
                                                       NOW + rep)]
        tokens = [eng.launch_packed(b, kh, NOW + rep) for b, kh in waves]
        assert eng.wave_pool.stats()["outstanding"] == 2
        for tok, limit in zip(tokens, (50, 70)):
            st, lim, rem, rst, full = eng.sync_packed(tok)
            assert (lim == limit).all() and (rem == limit - 1).all(), \
                (rep, limit, np.unique(lim).tolist())
            # synced, not dead: the token's batch is views of its lease
            # (one shard, one clock: joined straight into it) until the
            # launcher drops it
            assert tok[0].rows.lease is not None
            assert (tok[0].limit == limit).all()
        assert eng.wave_pool.stats()["outstanding"] == 2
        for tok in tokens:
            eng.drop_packed(tok)
    assert eng.wave_pool.stats()["outstanding"] == 0
    assert eng.wave_pool.stats()["leaks"] == 0


# ---- the wave cap follows the engine's largest launch (ISSUE 49) --------

def _cap_instance(monkeypatch, case):
    from gubernator_tpu.config import Config
    from gubernator_tpu.instance import V1Instance
    from gubernator_tpu.oracle import OracleEngine

    for name in ("GUBER_ENGINE", "GUBER_STEP_IMPL", "GUBER_WAVE_BUCKETS",
                 "GUBER_ADMISSION_LIMIT"):
        monkeypatch.delenv(name, raising=False)
    cfg = Config(cache_size=1 << 12, sweep_interval_ms=0)
    mesh, engine = make_mesh(n=1), None
    if case.startswith("pallas"):
        monkeypatch.setenv("GUBER_STEP_IMPL", "pallas")
    if case == "pallas_small_ladder":
        monkeypatch.setenv("GUBER_WAVE_BUCKETS", "128")
    elif case == "pallas_small_batch_rows":
        cfg = Config(cache_size=1 << 12, sweep_interval_ms=0, batch_rows=64)
    elif case == "pallas_mesh":
        mesh = make_mesh(n=2)
    elif case == "oracle":
        mesh, engine = None, OracleEngine()
    return V1Instance(cfg, mesh=mesh, engine=engine)


@pytest.mark.parametrize("case,capacity,max_wave", [
    ("pallas_one_chip", 16384, 16384),   # 1,024 / 8,192 / 16,384
    ("pallas_small_ladder", 128, 8192),  # every rehearsal block's ladder
    ("pallas_small_batch_rows", 1024, 8192),
    ("pallas_mesh", 8192, 8192),
    ("xla_one_chip", 8192, 8192),
    ("oracle", None, 8192),              # an engine without a ladder
])
def test_instance_caps_its_waves_at_what_one_launch_holds(
        monkeypatch, case, capacity, max_wave):
    """(c) the instance's dispatcher has ``max_wave == max(8192,
    engine.wave_capacity)`` and an admission default of 65,536 rows
    whatever that is."""
    inst = _cap_instance(monkeypatch, case)
    try:
        assert getattr(inst.engine, "wave_capacity", None) == capacity
        assert inst.dispatcher.max_wave == max_wave
        assert inst.dispatcher.admission_limit == 65536
        assert inst.dispatcher.debug_stats()["admission"][
            "limit_rows"] == 65536
    finally:
        inst.close()


@pytest.mark.parametrize("max_wave", [8192, 32768, 4])
def test_admission_default_is_rows_not_waves(monkeypatch, max_wave):
    """A bare dispatcher's ingress bound is 65,536 rows at any cap, and
    GUBER_ADMISSION_LIMIT is still the one override."""
    from gubernator_tpu.oracle import OracleEngine

    monkeypatch.delenv("GUBER_ADMISSION_LIMIT", raising=False)
    d = Dispatcher(OracleEngine(), max_wave=max_wave)
    try:
        assert d.admission_limit == Dispatcher.ADMISSION_LIMIT_ROWS == 65536
    finally:
        d.close()
    monkeypatch.setenv("GUBER_ADMISSION_LIMIT", "777")
    d = Dispatcher(OracleEngine(), max_wave=max_wave)
    try:
        assert d.admission_limit == 777
    finally:
        d.close()


def _token_cols(tag, n, now, limit=50):
    kh = hash_request_keys(["cap"] * n, [f"{tag}{i}" for i in range(n)])
    b, _ = pack_columns(kh, np.ones(n, np.int64),
                        np.full(n, limit, np.int64),
                        np.full(n, 60_000, np.int64),
                        np.zeros(n, np.int32), np.zeros(n, np.int32),
                        np.zeros(n, np.int64), now)
    return b, kh


def _spy_widths(eng):
    widths = []
    real = type(eng)._launch_arrays.__get__(eng)

    def spy(a64, a32, *rest):
        widths.append(a64.shape[1])
        return real(a64, a32, *rest)

    eng._launch_arrays = spy
    return widths


@pytest.mark.parametrize("ladder,want", [
    (None, [64, 128, 64]),            # 8 / 64 / 128: two calls a wave
    ((8, 64, 128, 256), [64, 256]),   # an operator's: the door in ONE wave
    ((8, 64), [64, 64, 64, 64]),      # the old ladder under the old cap
])
def test_saturated_wave_takes_what_the_door_holds(ladder, want):
    """A dispatcher capped at its engine's ``wave_capacity`` drains the
    calls that queued behind a wave up to what ONE launch holds, and
    that wave is ONE launch of the rung that covers it (the call that
    would pass the cap leads the next wave); under the old ladder the
    same calls are a wave each."""
    from gubernator_tpu.parallel.pallas_engine import PallasServingEngine

    eng = PallasServingEngine(make_mesh(n=1), capacity_per_shard=1 << 10,
                              batch_per_shard=8, wave_buckets=ladder)
    widths = _spy_widths(eng)
    disp = Dispatcher(eng, max_wave=eng.wave_capacity, max_delay_ms=0.2)
    release, entered = threading.Event(), threading.Event()
    real_launch = eng.launch_packed

    def gated(*a, **kw):
        if not entered.is_set():
            entered.set()
            release.wait(timeout=60)
        return real_launch(*a, **kw)

    eng.launch_packed = gated
    got, threads = {}, []
    try:
        for t in range(4):
            b, kh = _token_cols(f"t{t}", 64, NOW + t)
            th = threading.Thread(target=lambda t=t, b=b, kh=kh: got.update(
                {t: disp.check_packed(b, kh, NOW + t)}))
            th.start()
            threads.append(th)
            if t == 0:
                assert entered.wait(timeout=60)
        deadline = time.monotonic() + 30
        while disp._queue.qsize() < 3 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert disp._queue.qsize() >= 3
    finally:
        release.set()
    for th in threads:
        th.join(timeout=120)
    disp.close()
    assert widths == want
    for t in range(4):
        st, lim, rem, rst, full = got[t]
        assert (rem == 49).all() and (lim == 50).all() and not full.any()
    s = eng.wave_pool.stats()
    assert s["outstanding"] == 0 and s["leaks"] == 0, s


@pytest.mark.parametrize("native", ["1", "0"])
def test_tiered_wave_past_the_old_top_rung_is_two_launches(monkeypatch,
                                                           native):
    """(d) with a tier bound, a wave of 2× the old top rung is still
    exactly TWO launches — the wave on its 16·B rung and ONE
    re-dispatch of its erred and cold-resident rows together, which
    rides the ladder like any wave — and answers what an uncapped
    table answers (``tier_launches_per_wave`` reads the same counter:
    Δ``gubernator_wave_route_total`` a dispatcher wave)."""
    from gubernator_tpu.metrics import Metrics
    from gubernator_tpu.parallel.pallas_engine import PallasServingEngine
    from gubernator_tpu.tiering import TierController

    monkeypatch.setenv("GUBER_TIER_NATIVE", native)
    mesh = make_mesh(n=1)
    # one 128-slot bucket: full after 128 keys, the tier holds the rest
    small = PallasServingEngine(mesh, capacity_per_shard=128,
                                batch_per_shard=8)
    big = ShardedEngine(mesh, capacity_per_shard=1 << 14,
                        batch_per_shard=64)
    small.metrics_ref = Metrics()
    tc = TierController(small, rank_fn=lambda kh: 0)
    assert tc.stats()["native"] == (native == "1")
    assert small.wave_buckets == (8, 64, 128)
    b_fill, kh_all = _token_cols("fill", 300, NOW)
    for a in range(0, 300, 50):
        b = type(b_fill)(*[np.asarray(c)[a:a + 50] for c in b_fill])
        for e in (small, big):
            assert not e.check_packed(b, kh_all[a:a + 50], NOW)[4].any()
    assert small.occupancy() == 128 and tc.cold_keys() == 300 - 128
    cold = tc.resident_mask(kh_all)
    hot_i, cold_i = np.nonzero(~cold)[0], np.nonzero(cold)[0]
    # 128 rows = 2 × the old top rung: device-resident keys, cold keys,
    # keys nobody has seen (they err: the bucket is full), duplicates
    pick = np.concatenate([hot_i[:60], cold_i[:30], hot_i[:10],
                           cold_i[:8]])
    rng = np.random.default_rng(49)
    b_all, _ = _token_cols("fill", 300, NOW + 1000)
    b_new, kh_new = _token_cols("new", 20, NOW + 1000)
    rows = [np.concatenate([np.asarray(c)[pick], np.asarray(n)])
            for c, n in zip(b_all, b_new)]
    kh = np.concatenate([kh_all[pick], kh_new])
    order = rng.permutation(len(kh))
    batch = type(b_all)(*[r[order] for r in rows])
    kh = kh[order]
    assert len(kh) == 128
    widths = _spy_widths(small)
    route = small.metrics_ref.wave_route
    routed0 = sum(route.labels(route=r)._value.get()
                  for r in ("identity", "sorted"))
    tok = small.launch_packed(batch, kh, NOW + 1000)
    try:
        cols = small.sync_packed(tok, engine_lock=threading.Lock())
    finally:
        small.drop_packed(tok)
    assert widths == [128, 64]  # the wave; its 58 unanswered rows, once
    assert sum(route.labels(route=r)._value.get()
               for r in ("identity", "sorted")) - routed0 == 2
    want = big.check_packed(batch, kh, NOW + 1000)
    assert not cols[4].any()
    for got, ref in zip(cols[:4], want[:4]):
        assert np.asarray(got).tolist() == np.asarray(ref).tolist()


@pytest.mark.parametrize("queued,windows", [
    (9, 0),  # 9,000 rows behind the blocker: past the default cap
    (3, 1),  # 3,000 rows: a small wave still waits for stragglers
])
def test_wave_past_the_default_cap_skips_the_straggler_window(queued,
                                                              windows):
    """A wave whose backlog alone fills the default cap (8,192 rows)
    launches when the queue runs empty, as it did when that cap cut it;
    the coalescing window is armed for smaller waves only."""

    class NopEngine:
        def check_packed(self, batch, khash, now):
            m = len(khash)
            return (np.zeros(m, np.int32), np.zeros(m, np.int64),
                    np.zeros(m, np.int64), np.zeros(m, np.int64),
                    np.zeros(m, bool))

    eng = NopEngine()
    disp = Dispatcher(eng, max_wave=32768, max_delay_ms=0.2)
    b, kh = _token_cols("sw", 1000, NOW)
    sizes, timed = [], []
    release, entered = threading.Event(), threading.Event()
    plain = eng.check_packed

    def gated(batch, khash, now):
        if not entered.is_set():
            entered.set()
            release.wait(timeout=30)
        sizes.append(len(khash))
        return plain(batch, khash, now)

    eng.check_packed = gated
    real_get = disp._queue.get

    def get(block=True, timeout=None):
        if block and timeout is not None and timeout <= disp.max_delay_s:
            timed.append(timeout)
        return real_get(block, timeout)

    disp._queue.get = get
    threads = []
    try:
        for t in range(queued + 1):
            th = threading.Thread(
                target=lambda t=t: disp.check_packed(b, kh, NOW + t))
            th.start()
            threads.append(th)
            if t == 0:
                assert entered.wait(timeout=30)
                del timed[:]  # the blocker's own window
        deadline = time.monotonic() + 30
        while disp._queue.qsize() < queued and time.monotonic() < deadline:
            time.sleep(0.01)
        assert disp._queue.qsize() >= queued
    finally:
        release.set()
    for th in threads:
        th.join(timeout=60)
    disp.close()
    assert sizes == [1000, queued * 1000], sizes
    assert len(timed) == windows, timed
