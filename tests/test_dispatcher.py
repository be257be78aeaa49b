"""Dispatcher (worker-pool analog) tests: coalescing, correctness under
concurrency, error propagation."""
import threading
import time

import pytest

from gubernator_tpu.dispatcher import Dispatcher
from gubernator_tpu.parallel import ShardedEngine, make_mesh
from gubernator_tpu.types import RateLimitRequest

NOW = 1_763_000_000_000


def req(key, **kw):
    d = dict(hits=1, limit=1000, duration=600_000)
    d.update(kw)
    return RateLimitRequest(name="disp", unique_key=key, **d)


@pytest.fixture()
def engine():
    return ShardedEngine(make_mesh(n=2), capacity_per_shard=1 << 10,
                        batch_per_shard=64)


def test_single_caller(engine):
    d = Dispatcher(engine)
    try:
        r = d.check_batch([req("a")], NOW)
        assert len(r) == 1 and r[0].remaining == 999
    finally:
        d.close()


def test_concurrent_callers_share_waves_and_conserve(engine):
    d = Dispatcher(engine)
    results = []
    lock = threading.Lock()

    def worker(w):
        got = []
        for i in range(10):
            got.extend(d.check_batch([req("shared"), req(f"own_{w}_{i}")],
                                     NOW + i))
        with lock:
            results.append(got)

    try:
        threads = [threading.Thread(target=worker, args=(w,))
                   for w in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # every caller got a response for each request
        assert all(len(g) == 20 for g in results)
        # the shared key must have exactly 60 hits recorded
        check = d.check_batch([req("shared", hits=0)], NOW + 100)[0]
        assert check.remaining == 1000 - 60
        # waves were actually merged (fewer launches than callers×batches)
        # — smoke: the dispatcher survived; merging is probabilistic here
    finally:
        d.close()


def test_error_propagates_to_all_callers(engine):
    d = Dispatcher(engine)

    def boom(reqs, now):
        raise RuntimeError("device on fire")

    d.engine = type("E", (), {"check_batch": staticmethod(boom)})()
    try:
        with pytest.raises(RuntimeError, match="device on fire"):
            d.check_batch([req("x")], NOW)
    finally:
        d.close()


def test_close_rejects_new_and_drains(engine):
    d = Dispatcher(engine)
    d.check_batch([req("pre")], NOW)
    d.close()
    with pytest.raises(RuntimeError):
        d.check_batch([req("post")], NOW)


def test_close_resolves_in_flight_and_starts_nothing_after(engine):
    """close() while one wave is in flight on the device and more jobs
    are queued behind it: the in-flight wave resolves with its answers,
    every queued job either resolved before close() returned or failed
    with "dispatcher closed" (none is left hanging), a submit after it
    is refused, and NO engine call starts once close() has returned —
    instance.close snapshots the engine's state right after."""
    import numpy as np

    from gubernator_tpu.core.batch import pack_columns
    from gubernator_tpu.hashing import hash_request_keys

    calls = []  # (entry, started-at) of every engine call
    in_launch = threading.Event()
    release = threading.Event()
    orig_launch = engine.launch_packed

    def gated_launch(batch, kh, now, **kw):
        calls.append(("launch_packed", time.monotonic()))
        in_launch.set()
        release.wait(timeout=30)
        return orig_launch(batch, kh, now, **kw)

    for name in ("sync_packed", "check_packed", "check_batch"):
        def entry(*a, _f=getattr(engine, name), _n=name, **kw):
            calls.append((_n, time.monotonic()))
            return _f(*a, **kw)
        setattr(engine, name, entry)
    engine.launch_packed = gated_launch
    d = Dispatcher(engine)
    assert d._pipelined

    def cols(tag):
        kh = hash_request_keys(["cl"] * 4, [f"{tag}{i}" for i in range(4)])
        b, _ = pack_columns(kh, np.ones(4, np.int64),
                            np.full(4, 50, np.int64),
                            np.full(4, 60_000, np.int64),
                            np.zeros(4, np.int32), np.zeros(4, np.int32),
                            np.zeros(4, np.int64), NOW)
        return b, kh

    outcomes = {}

    def call(tag):
        b, kh = cols(tag)
        try:
            outcomes[tag] = d.check_packed(b, kh, NOW)
        except BaseException as e:  # noqa: BLE001 - the verdict
            outcomes[tag] = e

    threads = [threading.Thread(target=call, args=("first",))]
    threads[0].start()
    assert in_launch.wait(timeout=30)  # the worker is inside the engine
    for tag in ("q1", "q2"):
        threads.append(threading.Thread(target=call, args=(tag,)))
        threads[-1].start()
    deadline = time.monotonic() + 30
    while d._queue.qsize() < 2 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert d._queue.qsize() == 2
    closer = threading.Thread(target=d.close)
    closer.start()
    assert d._closing.wait(timeout=30)
    release.set()
    closer.join(timeout=60)
    assert not closer.is_alive()
    closed_at = time.monotonic()
    for th in threads:
        th.join(timeout=60)
        assert not th.is_alive()
    # the in-flight wave resolved with real answers
    st, lim, rem, rst, full = outcomes["first"]
    assert rem.tolist() == [49] * 4 and not full.any()
    # queued jobs: served before close() returned, or failed by it
    for tag in ("q1", "q2"):
        got = outcomes[tag]
        if isinstance(got, BaseException):
            assert "dispatcher closed" in str(got)
        else:
            assert got[2].tolist() == [49] * 4
    with pytest.raises(RuntimeError, match="dispatcher is closed"):
        d.check_packed(*cols("late"), NOW)
    time.sleep(0.05)
    assert calls and all(t <= closed_at for _, t in calls), calls
    assert not d._thread.is_alive()


def test_merged_cross_now_batch_matches_sequential_oracle():
    """Per-request arrival times: a single launch holding requests from
    three different wall-clock instants (interleaved, out of order in
    the block) must produce exactly what sequential per-time execution
    would — the (row, now) segment sort orders same-key requests by
    arrival time."""
    import numpy as np

    from gubernator_tpu import Oracle, RateLimitRequest
    from gubernator_tpu.core.batch import pack_columns
    from gubernator_tpu.hashing import hash_request_keys
    from gubernator_tpu.parallel import ShardedEngine, make_mesh

    NOW = 1_776_000_000_000
    eng = ShardedEngine(make_mesh(n=2), capacity_per_shard=1 << 9,
                        batch_per_shard=64)

    def cols(now):
        kh = hash_request_keys(["dn"] * 8, [f"k{i % 4}" for i in range(8)])
        b, _ = pack_columns(kh, np.ones(8, np.int64),
                            np.full(8, 50, np.int64),
                            np.full(8, 60_000, np.int64),
                            np.zeros(8, np.int32), np.zeros(8, np.int32),
                            np.zeros(8, np.int64), now)
        return b, kh

    # concatenate three instants SHUFFLED (T+2, T, T+1): the launch must
    # still apply each key's requests in time order
    parts = [cols(NOW + 2), cols(NOW), cols(NOW + 1)]
    batch = type(parts[0][0])(*[
        np.concatenate([np.asarray(p[0][f]) for p in parts])
        for f in range(len(parts[0][0]))])
    khash = np.concatenate([p[1] for p in parts])
    st, lim, rem, rst, full = eng.check_packed(batch, khash, NOW + 2)
    assert not full.any()

    oracle = Oracle()
    want = {}
    for t in (NOW, NOW + 1, NOW + 2):
        reqs = [RateLimitRequest(name="dn", unique_key=f"k{i % 4}",
                                 hits=1, limit=50, duration=60_000)
                for i in range(8)]
        want[t] = oracle.check_batch(reqs, t)
    for j, t in enumerate((NOW + 2, NOW, NOW + 1)):  # block order
        for i in range(8):
            g = j * 8 + i
            w = want[t][i]
            assert (int(st[g]), int(rem[g]), int(rst[g])) == \
                (int(w.status), w.remaining, w.reset_time), (t, i)


import pytest


@pytest.mark.parametrize("entry", ["launch_packed", "check_packed"])
def test_dispatcher_merges_packed_jobs_across_nows(entry, serial_only):
    """Queued packed jobs with different now_ms share one launch (the
    old dispatcher quantized by timestamp and could not merge them).
    Deterministic: the engine is blocked while the jobs queue up.
    Covers BOTH worker branches, chosen from the engine's capability:
    the launch/sync pipeline (an engine with ``launch_packed``) and the
    serial check_packed (an engine without)."""
    import threading

    import numpy as np

    from gubernator_tpu.core.batch import pack_columns
    from gubernator_tpu.dispatcher import Dispatcher
    from gubernator_tpu.hashing import hash_request_keys
    from gubernator_tpu.parallel import ShardedEngine, make_mesh

    NOW = 1_777_000_000_000
    eng = ShardedEngine(make_mesh(n=2), capacity_per_shard=1 << 9,
                        batch_per_shard=64)
    launches = []
    release = threading.Event()
    # gate whichever entry the selected branch uses
    orig = getattr(eng, entry)

    entered = threading.Event()

    def gated(batch, kh, now):
        entered.set()
        release.wait(timeout=30)
        launches.append(len(kh))
        return orig(batch, kh, now)

    setattr(eng, entry, gated)
    disp = Dispatcher(eng if entry == "launch_packed" else serial_only(eng),
                      max_delay_ms=0.2)
    assert disp._pipelined == (entry == "launch_packed")

    def cols(now):
        kh = hash_request_keys(["dm"] * 4, [f"q{i}" for i in range(4)])
        b, _ = pack_columns(kh, np.ones(4, np.int64),
                            np.full(4, 50, np.int64),
                            np.full(4, 60_000, np.int64),
                            np.zeros(4, np.int32), np.zeros(4, np.int32),
                            np.zeros(4, np.int64), now)
        return b, kh

    # the first job blocks the WORKER inside the engine call and the
    # other two queue up behind it, merging into ONE later launch
    threads = []
    for t in range(3):
        b, kh = cols(NOW + t)

        def call(b=b, kh=kh, t=t):
            disp.check_packed(b, kh, NOW + t)

        th = threading.Thread(target=call)
        th.start()
        threads.append(th)
        if t == 0:
            assert entered.wait(timeout=30)
    deadline = time.monotonic() + 30
    while disp._queue.qsize() < 2 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert disp._queue.qsize() >= 2
    release.set()
    for th in threads:
        th.join(timeout=60)
    assert launches[0] == 4  # the blocked first job
    assert launches[1:] == [8]  # jobs 2 and 3 merged despite nows
    disp.close()


def test_mixed_wave_cross_now_merges_list_and_packed_jobs():
    """A wave holding object-lane jobs at different nows plus a packed
    job merges into one launch, with exact sequential-oracle results."""
    import threading

    import numpy as np

    from gubernator_tpu import Oracle, RateLimitRequest
    from gubernator_tpu.core.batch import pack_columns
    from gubernator_tpu.dispatcher import Dispatcher
    from gubernator_tpu.hashing import hash_request_keys
    from gubernator_tpu.parallel import ShardedEngine, make_mesh

    NOW = 1_779_000_000_000
    eng = ShardedEngine(make_mesh(n=2), capacity_per_shard=1 << 9,
                        batch_per_shard=64)
    launches = []
    release = threading.Event()
    entered = threading.Event()  # the blocker reached the engine
    orig_cp = eng.check_packed
    orig_cb = eng.check_batch

    def gated_cp(batch, kh, now):
        entered.set()
        release.wait(timeout=30)
        launches.append(("packed", len(kh)))
        return orig_cp(batch, kh, now)

    def gated_cb(reqs_, now):
        entered.set()
        release.wait(timeout=30)
        launches.append(("list", len(reqs_)))
        return orig_cb(reqs_, now)

    eng.check_packed = gated_cp
    eng.check_batch = gated_cb
    disp = Dispatcher(eng, max_delay_ms=0.2)

    def reqs(tag):
        return [RateLimitRequest(name="mw", unique_key=f"k{i % 3}",
                                 hits=1, limit=50, duration=60_000)
                for i in range(6)]

    def packed_cols(now):
        kh = hash_request_keys(["mw"] * 6, [f"k{i % 3}" for i in range(6)])
        b, _ = pack_columns(kh, np.ones(6, np.int64),
                            np.full(6, 50, np.int64),
                            np.full(6, 60_000, np.int64),
                            np.zeros(6, np.int32), np.zeros(6, np.int32),
                            np.zeros(6, np.int64), now)
        return b, kh

    results = {}
    # job 0 blocks the WORKER inside the engine; the rest queue up
    # behind it.  The try starts immediately so any assert in the setup
    # still releases the blocker.
    try:
        threads = [threading.Thread(
            target=lambda: results.setdefault(
                "blocker", disp.check_batch(reqs(0), NOW)))]
        threads[0].start()
        assert entered.wait(timeout=30)  # worker is held in the engine
        threads.append(threading.Thread(
            target=lambda: results.setdefault(
                "list1", disp.check_batch(reqs(1), NOW + 1))))
        threads.append(threading.Thread(
            target=lambda: results.setdefault(
                "list2", disp.check_batch(reqs(2), NOW + 2))))
        b, kh = packed_cols(NOW + 3)
        threads.append(threading.Thread(
            target=lambda: results.setdefault(
                "packed", disp.check_packed(b, kh, NOW + 3))))
        for t in threads[1:]:
            t.start()
        # deterministic: all three jobs must be IN the queue pre-release
        import time as _t

        deadline = _t.monotonic() + 30
        while disp._queue.qsize() < 3 and _t.monotonic() < deadline:
            _t.sleep(0.01)
        assert disp._queue.qsize() >= 3
    finally:
        release.set()
    for t in threads:
        t.join(timeout=60)
    # blocker launched alone (it held the dispatcher while the rest
    # queued; engine.check_batch delegates to check_packed internally,
    # so its one launch trips both gates); the remaining three instants
    # merged into ONE launch
    assert launches[:2] == [("list", 6), ("packed", 6)]
    assert launches[2:] == [("packed", 18)], launches
    # exact parity with sequential per-time application
    oracle = Oracle()
    want = {t: oracle.check_batch(reqs(0), NOW + t) for t in range(4)}
    for tag, t in (("blocker", 0), ("list1", 1), ("list2", 2)):
        got = results[tag]
        for i, (w, g) in enumerate(zip(want[t], got)):
            assert (int(g.status), g.remaining) == \
                (int(w.status), w.remaining), (tag, i)
    st, lim, rem, rst, full = results["packed"]
    for i, w in enumerate(want[3]):
        assert (int(st[i]), int(rem[i])) == (int(w.status), w.remaining)
    disp.close()


def test_result_timeout_env_override(engine, monkeypatch):
    """GUBER_RESULT_TIMEOUT_S must override the per-instance wait cap
    (a cold on-chip wave compile takes minutes and can outlast the
    120 s default), and a
    malformed value must fall back to the class default."""
    monkeypatch.setenv("GUBER_RESULT_TIMEOUT_S", "900")
    d = Dispatcher(engine)
    try:
        assert d.RESULT_TIMEOUT_S == 900.0
        assert Dispatcher.RESULT_TIMEOUT_S == 120.0  # class untouched
    finally:
        d.close()
    for bad in ("not-a-number", "0", "-5", "nan", "inf", "-inf",
                "Infinity"):
        monkeypatch.setenv("GUBER_RESULT_TIMEOUT_S", bad)
        d = Dispatcher(engine)
        try:
            # malformed/zero/negative/NaN all keep the default — a 0 s
            # wait would fail every queued wave instantly
            assert d.RESULT_TIMEOUT_S == 120.0, bad
        finally:
            d.close()
