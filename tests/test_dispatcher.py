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


def test_inline_never_starts_after_close(engine):
    """ADVICE r4 (low): a caller that passes _try_inline's first
    closing check and is then preempted across a full close() must NOT
    win the inline path — close()'s drain guarantee is that no
    dispatcher-initiated engine call STARTS after it returns (the
    close-time checkpoint snapshot depends on it).  The preemption is
    simulated deterministically: the inline mutex's acquire runs
    close() to completion before actually acquiring."""
    d = Dispatcher(engine)
    real_mu = d._inline_mu

    class RacingLock:
        def acquire(self, blocking=True):
            if not d._closing.is_set():
                d.close()  # completes fully: sets closing + drains
            return real_mu.acquire(blocking)

        def release(self):
            real_mu.release()

        def __enter__(self):
            real_mu.acquire()
            return self

        def __exit__(self, *exc):
            real_mu.release()

    d._inline_mu = RacingLock()
    assert d._try_inline() is False
    # the mutex was released on the refusal path
    assert real_mu.acquire(blocking=False)
    real_mu.release()


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


@pytest.mark.parametrize("pipeline", ["0", "1"])
def test_dispatcher_merges_packed_jobs_across_nows(pipeline, monkeypatch):
    """Queued packed jobs with different now_ms share one launch (the
    old dispatcher quantized by timestamp and could not merge them).
    Deterministic: the engine is blocked while the jobs queue up.
    Covers BOTH dispatcher paths: synchronous check_packed (CPU
    default) and the launch/sync pipeline (TPU default, forced here
    via GUBER_PIPELINE=1)."""
    import threading

    import numpy as np

    from gubernator_tpu.core.batch import pack_columns
    from gubernator_tpu.dispatcher import Dispatcher
    from gubernator_tpu.hashing import hash_request_keys
    from gubernator_tpu.parallel import ShardedEngine, make_mesh

    monkeypatch.setenv("GUBER_PIPELINE", pipeline)
    NOW = 1_777_000_000_000
    eng = ShardedEngine(make_mesh(n=2), capacity_per_shard=1 << 9,
                        batch_per_shard=64)
    launches = []
    release = threading.Event()
    # gate whichever entry the selected path uses
    orig = eng.launch_packed if pipeline == "1" else eng.check_packed

    entered = threading.Event()

    def gated(batch, kh, now):
        entered.set()
        release.wait(timeout=30)
        launches.append(len(kh))
        return orig(batch, kh, now)

    if pipeline == "1":
        eng.launch_packed = gated
    else:
        eng.check_packed = gated
    disp = Dispatcher(eng, max_delay_ms=0.2)

    def cols(now):
        kh = hash_request_keys(["dm"] * 4, [f"q{i}" for i in range(4)])
        b, _ = pack_columns(kh, np.ones(4, np.int64),
                            np.full(4, 50, np.int64),
                            np.full(4, 60_000, np.int64),
                            np.zeros(4, np.int32), np.zeros(4, np.int32),
                            np.zeros(4, np.int64), now)
        return b, kh

    # Force the queue path for every caller (the idle-inline fast path
    # would otherwise run job 1 in its caller's thread and leave the
    # worker free to drain jobs 2/3 early): with _inline_mu held, the
    # first job blocks the WORKER inside the engine call and the other
    # two queue up behind it, merging into ONE later launch.
    disp._inline_mu.acquire()
    try:
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
    finally:
        disp._inline_mu.release()
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
    # Force the queue path for ALL callers (see the inline-fast-path
    # note in the merge test above): job 0 blocks the WORKER inside the
    # engine; the rest queue up behind it.  _inline_mu stays held until
    # every job is IN the queue — the try starts immediately so any
    # assert in the setup still releases the mutex and the blocker.
    disp._inline_mu.acquire()
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
        disp._inline_mu.release()
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
