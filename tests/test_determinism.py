"""Determinism: the race-detector analog (SURVEY.md §5.2).

The reference keeps `go test -race` clean via its locking design; the
TPU design's equivalent guarantee is *determinism* — the same request
stream (same now_ms values) must produce bit-identical decisions and
table state on every run, on any shard count, with any batch
composition, including under concurrent client threads hitting one
instance."""
import threading

import numpy as np
import pytest

from gubernator_tpu import Algorithm, Behavior, RateLimitRequest
from gubernator_tpu.core.table import to_host
from gubernator_tpu.parallel import ShardedEngine, make_mesh

NOW = 1_761_000_000_000


def _stream(seed, n_batches=4, batch=96, n_keys=40):
    rng = np.random.default_rng(seed)
    out = []
    for b in range(n_batches):
        reqs = []
        for _ in range(batch):
            k = int(rng.integers(0, n_keys))
            reqs.append(RateLimitRequest(
                name="det", unique_key=f"k{k}",
                hits=int(rng.integers(0, 4)),
                limit=int(rng.integers(1, 20)),
                duration=int(rng.integers(1000, 100_000)),
                algorithm=Algorithm.LEAKY_BUCKET if rng.integers(2)
                else Algorithm.TOKEN_BUCKET,
                behavior=Behavior.RESET_REMAINING if rng.integers(13) == 0
                else Behavior.BATCHING))
        out.append((reqs, NOW + b * 3_000))
    return out


def _run(mesh_n, stream, engine_cls=ShardedEngine):
    eng = engine_cls(make_mesh(n=mesh_n), capacity_per_shard=1 << 10,
                     batch_per_shard=64)
    results = []
    for reqs, now in stream:
        results.extend((int(r.status), r.remaining, r.reset_time, r.limit)
                       for r in eng.check_batch(reqs, now))
    return results, eng


def test_identical_streams_identical_decisions():
    s = _stream(11)
    r1, e1 = _run(4, s)
    r2, e2 = _run(4, s)
    assert r1 == r2
    # table state must match bit-for-bit too
    for f, col in to_host(e1.state).items():
        assert (col == to_host(e2.state)[f]).all(), f


def test_shard_count_does_not_change_decisions():
    """1-shard vs 4-shard engines agree on every decision (the layout is
    an implementation detail, not a semantic)."""
    s = _stream(12)
    r1, _ = _run(1, s)
    r4, _ = _run(4, s)
    assert r1 == r4


def test_pallas_mode_is_deterministic_and_layout_independent():
    """The same contract for step_impl=pallas: identical streams →
    bit-identical decisions AND table words; and the kernel engine
    agrees with the XLA engine decision-for-decision on the stream
    (the serving mode is a layout choice, not a semantic).  Domain
    note: _stream's limits/durations all sit inside the kernel's
    value bounds, so no row is domain-dropped here."""
    from gubernator_tpu.parallel.pallas_engine import PallasServingEngine

    s = _stream(13)
    r1, e1 = _run(2, s, engine_cls=PallasServingEngine)
    r2, e2 = _run(2, s, engine_cls=PallasServingEngine)
    assert r1 == r2
    assert (np.asarray(e1.state) == np.asarray(e2.state)).all()
    rx, _ = _run(2, s)
    assert r1 == rx


def test_concurrent_clients_conserve_hits():
    """Threaded access to one instance: total admitted hits must equal
    the bucket capacity exactly — no lost or double-counted updates."""
    from gubernator_tpu.config import Config
    from gubernator_tpu.instance import V1Instance

    inst = V1Instance(Config(cache_size=1 << 10, sweep_interval_ms=0),
                      mesh=make_mesh(n=2))
    admitted = []
    lock = threading.Lock()

    def worker(w):
        got = 0
        for _ in range(30):
            r = inst.get_rate_limits(
                [RateLimitRequest(name="conserve", unique_key="one",
                                  hits=1, limit=100, duration=600_000)],
                now_ms=NOW)[0]
            if int(r.status) == 0:
                got += 1
        with lock:
            admitted.append(got)

    threads = [threading.Thread(target=worker, args=(w,)) for w in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # 8×30 = 240 attempts against capacity 100: exactly 100 admitted
    assert sum(admitted) == 100
    inst.close()


def test_concurrent_wire_clients_conserve_hits():
    """The C++ wire lane under threaded load: coalesced packed jobs in
    the dispatcher must conserve hits exactly like the object path."""
    import pytest

    from gubernator_tpu.config import Config
    from gubernator_tpu.instance import V1Instance, _wire_native
    from gubernator_tpu.proto import gubernator_pb2 as pb
    from gubernator_tpu.wire import req_to_pb

    if _wire_native is None:  # pragma: no cover
        pytest.skip("native extension not built")
    inst = V1Instance(Config(cache_size=1 << 10, sweep_interval_ms=0),
                      mesh=make_mesh(n=2))
    m = pb.GetRateLimitsReq()
    m.requests.extend(req_to_pb(RateLimitRequest(
        name="conserve", unique_key="wire", hits=1, limit=100,
        duration=600_000)) for _ in range(5))
    data = m.SerializeToString()
    admitted = []
    lock = threading.Lock()

    def worker(w):
        got = 0
        for _ in range(10):
            out = pb.GetRateLimitsResp.FromString(
                inst.get_rate_limits_wire(data, now_ms=NOW))
            got += sum(1 for r in out.responses if int(r.status) == 0)
        with lock:
            admitted.append(got)

    threads = [threading.Thread(target=worker, args=(w,))
               for w in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # 6×10×5 = 300 attempts against capacity 100: exactly 100 admitted
    assert sum(admitted) == 100
    inst.close()
