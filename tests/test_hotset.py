"""Replicated hot-set GLOBAL engine tests (SURVEY.md §2.3 — the psum
replacement for global.go's hit-queue + broadcast machinery)."""
import numpy as np
import pytest

from gubernator_tpu.hashing import hash_key
from gubernator_tpu.parallel import make_mesh
from gubernator_tpu.parallel.hotset import HotSetEngine
from gubernator_tpu.types import RateLimitRequest, Status

NOW = 1_764_000_000_000


def req(key="hk", limit=100, hits=1, duration=60_000):
    return RateLimitRequest(name="hot", unique_key=key, hits=hits,
                            limit=limit, duration=duration)


def kh(key="hk"):
    return hash_key("hot", key)


@pytest.fixture(scope="module")
def mesh4():
    return make_mesh(n=4)


def test_pin_and_serve_single_requests(mesh4):
    eng = HotSetEngine(mesh4, capacity=256, batch_per_chip=32)
    assert eng.pin(req(), kh(), NOW)
    assert eng.pin(req(), kh(), NOW)  # idempotent
    r = eng.check_batch([req(hits=3)], [kh()], NOW)[0]
    assert r.error == ""
    assert (int(r.status), r.remaining) == (0, 97)


def test_replicas_diverge_then_psum_converges(mesh4):
    """Each chip consumes locally; one sync() folds all consumption."""
    eng = HotSetEngine(mesh4, capacity=256, batch_per_chip=32)
    eng.pin(req(limit=1000), kh("c"), NOW)
    # 40 hits spread round-robin over 4 replicas (10 each)
    rs = eng.check_batch([req("c", limit=1000) for _ in range(40)],
                         [kh("c")] * 40, NOW + 1)
    assert all(r.status == Status.UNDER_LIMIT for r in rs)
    # before sync, each replica only saw its own 10 hits
    per_replica_rem = {r.remaining for r in rs}
    assert min(per_replica_rem) >= 1000 - 40 // eng.n - 1
    eng.sync()
    # after sync every replica agrees on the merged count
    rs = eng.check_batch([req("c", limit=1000, hits=0)
                          for _ in range(eng.n)], [kh("c")] * eng.n, NOW + 2)
    assert {r.remaining for r in rs} == {960}


def test_sync_on_a_one_device_mesh():
    """One chip is a 1-device mesh: the sync's psum/pmax must run there
    too, folding the lone replica's consumption into its base."""
    eng = HotSetEngine(make_mesh(n=1), capacity=256, batch_per_chip=32)
    eng.pin(req("one", limit=1000), kh("one"), NOW)
    rs = eng.check_batch([req("one", limit=1000) for _ in range(40)],
                         [kh("one")] * 40, NOW + 1)
    assert all(r.status == Status.UNDER_LIMIT for r in rs)
    eng.sync()
    r = eng.check_batch([req("one", limit=1000, hits=0)], [kh("one")],
                        NOW + 2)[0]
    assert r.remaining == 960


def test_conservation_across_syncs(mesh4):
    """Total admitted ≤ limit once syncs run between windows."""
    eng = HotSetEngine(mesh4, capacity=256, batch_per_chip=32)
    eng.pin(req("cons", limit=50), kh("cons"), NOW)
    admitted = 0
    for wave in range(10):
        rs = eng.check_batch([req("cons", limit=50) for _ in range(10)],
                             [kh("cons")] * 10, NOW + wave)
        admitted += sum(1 for r in rs if r.status == Status.UNDER_LIMIT)
        eng.sync()
    assert admitted == 50  # exact: sync after every wave removes any window
    rs = eng.check_batch([req("cons", limit=50, hits=0)], [kh("cons")],
                         NOW + 100)
    assert rs[0].remaining == 0


def test_bounded_over_admission_within_window(mesh4):
    """Without syncs, over-admission is bounded by n_chips × limit —
    the documented GLOBAL eventual-consistency window."""
    eng = HotSetEngine(mesh4, capacity=256, batch_per_chip=64)
    eng.pin(req("w", limit=10), kh("w"), NOW)
    rs = eng.check_batch([req("w", limit=10) for _ in range(200)],
                         [kh("w")] * 200, NOW + 1)
    admitted = sum(1 for r in rs if r.status == Status.UNDER_LIMIT)
    assert 10 <= admitted <= 10 * eng.n
    eng.sync()
    rs = eng.check_batch([req("w", limit=10, hits=0)], [kh("w")], NOW + 2)
    assert rs[0].remaining == 0  # clamped at zero after the fold


def test_expiry_refresh_merges(mesh4):
    eng = HotSetEngine(mesh4, capacity=256, batch_per_chip=32)
    eng.pin(req("e", limit=20, duration=1_000), kh("e"), NOW)
    eng.check_batch([req("e", limit=20, duration=1_000)] * 8,
                    [kh("e")] * 8, NOW + 1)
    eng.sync()
    # past expiry: replicas refresh; merged state adopts the refresh
    rs = eng.check_batch([req("e", limit=20, duration=1_000)] * 8,
                         [kh("e")] * 8, NOW + 5_000)
    assert all(r.status == Status.UNDER_LIMIT for r in rs)
    eng.sync()
    rs = eng.check_batch([req("e", limit=20, duration=1_000, hits=0)],
                         [kh("e")], NOW + 5_001)
    assert rs[0].remaining == 20 - 8


def lreq(key="lk", limit=1000, hits=1, duration=60_000, burst=0):
    from gubernator_tpu.types import Algorithm

    return RateLimitRequest(name="hot", unique_key=key, hits=hits,
                            limit=limit, duration=duration, burst=burst,
                            algorithm=Algorithm.LEAKY_BUCKET)


def test_leaky_pin_and_serve(mesh4):
    eng = HotSetEngine(mesh4, capacity=256, batch_per_chip=32)
    assert eng.pin(lreq(), kh("lk"), NOW)
    r = eng.check_batch([lreq(hits=3)], [kh("lk")], NOW + 1)[0]
    assert r.error == ""
    assert (int(r.status), r.remaining) == (0, 997)


def test_leaky_replicas_diverge_then_psum_converges(mesh4):
    """Leaky consumption folds across replicas like token consumption;
    the merge measures each replica against the replenished base."""
    eng = HotSetEngine(mesh4, capacity=256, batch_per_chip=32)
    eng.pin(lreq("lc"), kh("lc"), NOW)
    rs = eng.check_batch([lreq("lc") for _ in range(40)],
                         [kh("lc")] * 40, NOW + 1)
    assert all(r.status == Status.UNDER_LIMIT for r in rs)
    # pre-sync each replica only saw its own share
    assert min(r.remaining for r in rs) >= 1000 - 40 // eng.n - 1
    eng.sync()
    rs = eng.check_batch([lreq("lc", hits=0) for _ in range(eng.n)],
                         [kh("lc")] * eng.n, NOW + 2)
    # 1ms of replenish at 1000/60s is < 1 token: floor stays at 960
    assert {r.remaining for r in rs} == {960}


def test_leaky_conservation_across_syncs(mesh4):
    """Sync after every wave ⇒ exactly burst admissions while replenish
    rounds to zero tokens."""
    eng = HotSetEngine(mesh4, capacity=256, batch_per_chip=32)
    eng.pin(lreq("lcons", limit=50), kh("lcons"), NOW)
    admitted = 0
    for wave in range(10):
        rs = eng.check_batch([lreq("lcons", limit=50) for _ in range(10)],
                             [kh("lcons")] * 10, NOW + wave)
        admitted += sum(1 for r in rs if r.status == Status.UNDER_LIMIT)
        eng.sync()
    assert admitted == 50
    rs = eng.check_batch([lreq("lcons", limit=50, hits=0)], [kh("lcons")],
                         NOW + 100)
    assert rs[0].remaining == 0


def test_leaky_replenish_after_merged_drain(mesh4):
    """Post-sync the merged bucket leaks at limit/duration: half the
    duration replenishes half the limit."""
    eng = HotSetEngine(mesh4, capacity=256, batch_per_chip=64)
    eng.pin(lreq("lr", limit=100, duration=1_000), kh("lr"), NOW)
    rs = eng.check_batch([lreq("lr", limit=100, duration=1_000)] * 100,
                         [kh("lr")] * 100, NOW + 1)
    assert all(r.status == Status.UNDER_LIMIT for r in rs)
    eng.sync()
    rs = eng.check_batch([lreq("lr", limit=100, duration=1_000, hits=0)],
                         [kh("lr")], NOW + 1)
    assert rs[0].remaining == 0  # fold drained the shared bucket
    rs = eng.check_batch([lreq("lr", limit=100, duration=1_000, hits=0)],
                         [kh("lr")], NOW + 501)
    assert rs[0].remaining == 50  # 500 ms × (100 per 1000 ms)


def test_mixed_algorithms_one_sync(mesh4):
    """Token and leaky rows coexist; one psum folds both correctly."""
    eng = HotSetEngine(mesh4, capacity=256, batch_per_chip=32)
    eng.pin(req("mt", limit=500), kh("mt"), NOW)
    eng.pin(lreq("ml"), kh("ml"), NOW)
    eng.check_batch([req("mt", limit=500)] * 20 + [lreq("ml")] * 20,
                    [kh("mt")] * 20 + [kh("ml")] * 20, NOW + 1)
    eng.sync()
    rs = eng.check_batch([req("mt", limit=500, hits=0), lreq("ml", hits=0)],
                         [kh("mt"), kh("ml")], NOW + 2)
    assert rs[0].remaining == 480
    assert rs[1].remaining == 980


def test_probe_window_exhaustion():
    mesh = make_mesh(n=2)
    eng = HotSetEngine(mesh, capacity=8, batch_per_chip=8)
    pinned = 0
    for i in range(64):
        if eng.pin(req(f"x{i}"), kh(f"x{i}"), NOW):
            pinned += 1
    assert 0 < pinned <= 8
    eng.unpin_all()
    assert eng.pin(req("x0"), kh("x0"), NOW)


@pytest.mark.parametrize("limit", [1000, (1 << 32) + 50, (1 << 45) + 7])
def test_sync_merges_consumption_past_the_low_word(mesh4, limit):
    """The replica map holds 64-bit columns as two 32-bit words
    (core/table.py): the sync joins them at replica size, merges in
    int64 and splits the merged row back."""
    eng = HotSetEngine(mesh4, capacity=256, batch_per_chip=32)
    assert eng.pin(req(key="w", limit=limit), kh("w"), NOW)
    out = eng.check_batch([req(key="w", limit=limit, hits=2)] * 8,
                          [kh("w")] * 8, NOW + 1)
    assert all(r.error == "" for r in out)
    eng.sync()
    merged = eng.row_state(kh("w"))
    assert merged["remaining"] == limit - 16
    assert merged["limit"] == limit
    q = eng.check_batch([req(key="w", limit=limit, hits=0)] * 4,
                        [kh("w")] * 4, NOW + 2)
    assert {r.remaining for r in q} == {limit - 16}
