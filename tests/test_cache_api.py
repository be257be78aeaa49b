"""Cache-interface parity: each/remove over the device table
(reference: cache.go › Cache{Each, Remove} — SURVEY.md §2.1)."""

from gubernator_tpu.config import Config
from gubernator_tpu.hashing import hash_keys
from gubernator_tpu.instance import V1Instance
from gubernator_tpu.parallel import ShardedEngine, make_mesh
from gubernator_tpu.store import MockStore
from gubernator_tpu.types import Behavior, RateLimitRequest

NOW = 1_769_500_000_000


def req(key, **kw):
    d = dict(hits=1, limit=9, duration=60_000)
    d.update(kw)
    return RateLimitRequest(name="cache", unique_key=key, **d)


def test_each_iterates_live_rows(cpu_mesh):
    eng = ShardedEngine(cpu_mesh, capacity_per_shard=1 << 10,
                        batch_per_shard=64)
    eng.check_batch([req(f"k{i}") for i in range(12)], NOW)
    items = list(eng.each())
    assert len(items) == 12
    assert all(i.remaining == 8 for i in items)
    want = set(hash_keys([f"cache_k{i}" for i in range(12)]).tolist())
    assert {i.key_hash for i in items} == want


def test_remove_rows(cpu_mesh):
    eng = ShardedEngine(cpu_mesh, capacity_per_shard=1 << 10,
                        batch_per_shard=64)
    eng.check_batch([req(f"k{i}") for i in range(10)], NOW)
    kh = hash_keys([f"cache_k{i}" for i in range(10)])
    assert eng.remove_rows(kh[:4]) == 4
    assert eng.remove_rows(kh[:4]) == 0  # already gone
    # removed keys start fresh; the rest keep their state
    out = eng.check_batch([req(f"k{i}", hits=0) for i in range(10)], NOW + 5)
    assert [r.remaining for r in out] == [9] * 4 + [8] * 6


def test_instance_remove_including_hot_and_store():
    store = MockStore()
    inst = V1Instance(Config(cache_size=1 << 10, sweep_interval_ms=0,
                             store=store), mesh=make_mesh(n=2))
    try:
        inst.get_rate_limits([req("gone")], now_ms=NOW)
        assert inst.remove("cache", "gone") is True
        assert store.called["remove"] == 1
        assert inst.remove("cache", "gone") is False
        r = inst.get_rate_limits([req("gone", hits=0)], now_ms=NOW + 1)[0]
        assert r.remaining == 9  # fresh after removal
    finally:
        inst.close()


def test_instance_remove_of_a_much_hit_global_key():
    """A GLOBAL key hit 120 times on a solo daemon is one row of the
    sharded table: ``remove`` deletes it there, and the next request
    opens a fresh bucket."""
    inst = V1Instance(Config(cache_size=1 << 10, sweep_interval_ms=0),
                      mesh=make_mesh(n=4))
    try:
        g = dict(limit=500, behavior=Behavior.GLOBAL)
        for t in range(3):
            out = inst.get_rate_limits([req("much", **g)] * 40,
                                       now_ms=NOW + t)
        assert out[-1].remaining == 500 - 120
        assert inst.remove("cache", "much") is True
        assert inst.remove("cache", "much") is False
        r = inst.get_rate_limits([req("much", **g)], now_ms=NOW + 3)[0]
        assert r.remaining == 499  # fresh after removal
    finally:
        inst.close()
