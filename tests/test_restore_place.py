"""``ShardedEngine.restore`` places a snapshot's rows in numpy rounds
(``_place_rows``); the row-at-a-time walk it replaced is kept HERE as
its plain reference, and the two have to build the same table, byte
for byte.  Then the probe window itself (ISSUE 31): a key whose first
8 probe slots hold other live keys is restored, found, inserted and
answered as ``oracle.py`` answers it; a window that IS full is answered
table_full without a sweep inside its wave (the sweep it asks for runs
between waves, once an interval, under its own cause), and a row
answered table_full is counted once, whichever way it went."""
import threading

import numpy as np
import pytest

from gubernator_tpu import Oracle, RateLimitRequest
from gubernator_tpu.config import Config
from gubernator_tpu.core.step import PROBES, REPLICA_PROBES
from gubernator_tpu.core.table import to_host
from gubernator_tpu.hashing import hash_request_keys, shard_of
from gubernator_tpu.instance import V1Instance
from gubernator_tpu.metrics import Metrics
from gubernator_tpu.parallel import ShardedEngine, make_mesh

NOW = 1_790_000_000_000
FIELDS = ("meta", "limit", "duration", "eff_ms", "burst", "remaining",
          "t_ms", "expire_at")


def walk_rows(table: dict, arrays: dict, n_shards: int, cap: int,
              probes: int = PROBES):
    """The plain reference: the loop over rows that ``restore`` was —
    each row, in row order, to the first slot of its window that is
    free or holds its key.  → (rows placed, rows left over)."""
    keys = arrays["key"].astype(np.uint64)
    shard = shard_of(keys, n_shards)
    placed, left = 0, []
    for i in range(len(keys)):
        k = int(keys[i])
        if k == 0:
            continue
        stride = (k >> 17) | 1
        for p in range(probes):
            slot = int(shard[i]) * cap + ((k + p * stride) & (cap - 1))
            if table["key"][slot] in (0, k):
                for f in FIELDS:
                    table[f][slot] = arrays[f][i]
                table["key"][slot] = k
                placed += 1
                break
        else:
            left.append(i)
    return placed, left


def rows_of(keys: np.ndarray, seed: int) -> dict:
    rng = np.random.default_rng(seed)
    n = len(keys)
    out = {f: rng.integers(1, 1 << 40, n).astype(np.int64) for f in FIELDS}
    out["meta"] = rng.integers(0, 4, n).astype(np.int32)
    out["key"] = keys.astype(np.uint64)
    return out


def host_table(eng) -> dict:
    return to_host(eng.state)


class Tier:
    """What ``restore`` hands the rows it cannot place to."""

    def __init__(self):
        self.adopted = []

    def adopt_rows(self, arrays, idx) -> int:
        self.adopted.extend(idx)
        return len(idx)


# (shards, rows a shard, keys, key bits, duplicates, seed): keys of few
# bits all have stride 1 and one shard, so their windows overlap and
# rows oust one another down long chains; 64 random bits spread thin
CASES = [
    (1, 1 << 10, 300, 64, 0, 1),
    (1, 1 << 10, 300, 64, 40, 2),  # a key comes twice
    (2, 1 << 9, 500, 64, 25, 3),  # two shards
    (1, 1 << 6, 90, 64, 0, 4),  # more keys than slots: windows fill
    (2, 1 << 6, 200, 64, 30, 5),
    (1, 1 << 8, 200, 10, 10, 6),  # clustered keys: long displacement
    (1, 1 << 8, 250, 9, 0, 7),
    (2, 1 << 7, 300, 40, 60, 8),
]


@pytest.mark.parametrize("n,cap,m,bits,dups,seed", CASES)
def test_restore_builds_the_table_the_row_walk_builds(n, cap, m, bits,
                                                      dups, seed):
    rng = np.random.default_rng(seed)
    hi = (1 << bits) - 1 if bits < 64 else np.iinfo(np.uint64).max
    keys = rng.integers(1, hi, m, dtype=np.uint64)
    if dups:
        at = rng.integers(0, m, dups)
        keys = np.concatenate([keys, keys[at]])[rng.permutation(m + dups)]
    arrays = rows_of(keys, seed)
    eng = ShardedEngine(make_mesh(n=n), capacity_per_shard=cap,
                        batch_per_shard=64)
    eng.metrics_ref = Metrics()
    want = host_table(eng)
    placed_want, left_want = walk_rows(want, arrays, n, cap)
    placed = eng.restore(arrays)
    got = host_table(eng)
    for f in want:
        assert (got[f] == want[f]).all(), f
    assert placed == placed_want
    text = eng.metrics_ref.render().decode()
    assert f"gubernator_restore_unplaced_rows {float(len(left_want))}" \
        in text
    assert 'gubernator_phase_duration_count{phase="restore.place"} 1.0' \
        in text
    if cap <= 1 << 6:
        assert left_want, "the case is there for windows that fill"


@pytest.mark.parametrize("n_shards", [1, 4])
def test_a_snapshot_the_parent_wrote_restores_to_the_same_answers(n_shards):
    """The host side of the table is the parent's: a file of int64 /
    uint64 columns written BEFORE the table was held as 32-bit words
    (``tests/snapshot_history.py`` says by whom and how) restores, comes
    back from ``snapshot`` as it went in, and every answer after it is
    the one an engine that served the whole history gives."""
    import snapshot_history as sh

    snap = dict(np.load(sh.PATH, allow_pickle=False))
    assert {f: v.dtype for f, v in snap.items()} == {
        **{f: np.int64 for f in FIELDS}, "key": np.uint64, "meta": np.int32}
    assert {0xDEADBEEF << 32, 0xDEADBEEF, 1} <= set(snap["key"].tolist())

    def engine():
        return ShardedEngine(make_mesh(n=n_shards), capacity_per_shard=1 << 8,
                             batch_per_shard=64)

    whole = engine()
    sh.serve(whole, sh.BEFORE)
    restored = engine()
    assert restored.restore(snap) == len(snap["key"])
    back = restored.snapshot()
    a, b = np.argsort(snap["key"]), np.argsort(back["key"])
    for f in snap:
        assert back[f].dtype == snap[f].dtype
        assert (back[f][b] == snap[f][a]).all(), f
    for got, want in zip(sh.serve(restored, sh.AFTER),
                         sh.serve(whole, sh.AFTER)):
        for g, w in zip(got, want):
            assert (g == w).all()
        assert not got[4].any()  # no row table_full
    assert len({tuple(c[0].tolist()) for c in sh.serve(whole, sh.AFTER)}) > 1


def test_restore_into_a_table_that_holds_rows_and_leaves_the_rest_to_the_tier():
    """A second restore finds the first one's rows (same key: the row is
    overwritten where it sits; another key's slot is taken), and what
    no window holds goes to the tier, by row index, in row order."""
    n, cap = 2, 1 << 6
    rng = np.random.default_rng(11)
    first = rows_of(rng.integers(1, 1 << 63, 70, dtype=np.uint64), 1)
    again = np.concatenate([first["key"][:30],
                            rng.integers(1, 1 << 63, 120, dtype=np.uint64)])
    second = rows_of(again[rng.permutation(len(again))], 2)
    eng = ShardedEngine(make_mesh(n=n), capacity_per_shard=cap,
                        batch_per_shard=64)
    eng.tier = Tier()
    want = host_table(eng)
    walk_rows(want, first, n, cap)
    placed_want, left_want = walk_rows(want, second, n, cap)
    eng.restore(first)
    eng.tier.adopted.clear()
    placed = eng.restore(second)
    got = host_table(eng)
    for f in want:
        assert (got[f] == want[f]).all(), f
    assert left_want and eng.tier.adopted == left_want
    assert placed == placed_want + len(left_want)


# ---- the probe window ---------------------------------------------------

def window(kh: int, cap: int, probes: int) -> list:
    stride = (kh >> 17) | 1
    return [(kh + p * stride) & (cap - 1) for p in range(probes)]


def crowd_out(name: str, cap: int, depth: int, others: int = 30000):
    """(unique_key, its hash, [unique keys]): a key and, for each of its
    first ``depth`` probe slots, ANOTHER key whose own first probe is
    that slot — so that restoring or serving the others first leaves
    the key no slot in a window of ``depth``."""
    pool = [f"o{i}" for i in range(others)]
    kh = hash_request_keys([name] * others, pool)
    by_first = {}
    for u, h in zip(pool, kh.tolist()):
        by_first.setdefault(h & (cap - 1), (u, h))
    for u, h in zip(pool, kh.tolist()):
        slots = window(h, cap, depth)
        if len(set(slots)) == depth and all(
                s in by_first and by_first[s][1] != h for s in slots):
            return u, h, [by_first[s][0] for s in slots]
    raise AssertionError("no such key among the candidates")


def req(key, **kw):
    d = dict(hits=1, limit=5, duration=600_000)
    d.update(kw)
    return RateLimitRequest(name="w", unique_key=key, **d)


def counter(inst, name: str) -> float:
    for line in inst.metrics.render().decode().splitlines():
        if line.startswith(name + " ") or line.startswith(name + "{"):
            if name + "_created" not in line:
                return float(line.rpartition(" ")[2])
    return 0.0


@pytest.fixture()
def xla_instance(monkeypatch):
    monkeypatch.setenv("GUBER_ENGINE", "xla")
    monkeypatch.delenv("GUBER_STEP_IMPL", raising=False)
    cap = 1 << 12
    inst = V1Instance(Config(cache_size=cap, sweep_interval_ms=0),
                      mesh=make_mesh(n=1))
    yield inst, cap
    inst.close()


def test_the_table_window_is_longer_than_the_replica_maps(xla_instance):
    """8 probes lose a key in one 10M key set in four (core/step.py);
    the 4,096-slot replica map keeps its 8 (its slots are pinned
    from the host, and cell 4's routing walks them)."""
    from gubernator_tpu.parallel import meshglobal

    assert PROBES >= 16 and REPLICA_PROBES == 8
    assert meshglobal.REPLICA_PROBES == 8


def test_a_key_whose_first_8_slots_are_taken_is_served_as_the_oracle_serves_it(
        xla_instance):
    """The test that fails with a window of 8: the others are live in
    the key's first 8 slots; the key is INSERTED by the step beyond
    them, found again, restored into a fresh engine beyond them, and
    answered as the oracle answers — and no row is table_full, so no
    sweep runs inside a wave."""
    inst, cap = xla_instance
    key, kh, others = crowd_out("w", cap, 8)
    oracle = Oracle()
    now = NOW
    warm = [req(u) for u in others]
    assert [r.error for r in inst.get_rate_limits(warm, now_ms=now)] \
        == [""] * 8
    oracle.check_batch(warm, now)
    # the others sit in the key's first eight slots
    held = host_table(inst.engine)["key"][window(kh, cap, 8)]
    assert (held != 0).all() and kh not in held.tolist()
    for step in range(7):  # inserted, found, over its limit
        now += 1000
        got = inst.get_rate_limits([req(key)], now_ms=now)[0]
        want = oracle.check_batch([req(key)], now)[0]
        assert got.error == ""
        assert (int(got.status), got.remaining, got.reset_time) == \
            (int(want.status), want.remaining, want.reset_time), step
    slot = np.flatnonzero(host_table(inst.engine)["key"] == kh)
    assert len(slot) == 1 and slot[0] in window(kh, cap, PROBES)[8:]
    # restored beyond the eight, and found by the step there
    snap = inst.engine.snapshot()
    order = np.argsort(snap["key"] == np.uint64(kh), kind="stable")
    snap = {f: v[order] for f, v in snap.items()}  # the key comes last
    fresh = ShardedEngine(make_mesh(n=1), capacity_per_shard=cap)
    assert fresh.restore(snap) == len(snap["key"]) == 9
    now += 1000
    got = fresh.check_batch([req(key)], now)[0]
    want = oracle.check_batch([req(key)], now)[0]
    assert got.error == "" and got.remaining == want.remaining == 0
    assert int(got.status) == int(want.status) == 1
    assert counter(inst, "gubernator_table_full_rows_total") == 0
    assert counter(inst, 'gubernator_sweep_total{cause="table_full"}') == 0


def test_a_full_window_is_answered_table_full_and_no_wave_sweeps_for_it(
        xla_instance):
    """All PROBES slots live: the row is table_full (one row counted a
    request) and NO sweep runs inside its wave, however often it comes.
    It asks for one: ``_maybe_sweep`` runs it after the wave, in the
    caller's thread, once an interval, under its own cause.  While the
    occupants live it frees nothing; once they have expired the next
    sweep (the tick's, here) does, and the key inserts."""
    inst, cap = xla_instance
    key, kh, others = crowd_out("w", cap, PROBES)
    now = NOW
    swept = []
    inst.engine.sweep = lambda t, _f=inst.engine.sweep: (
        swept.append(threading.current_thread().name), _f(t))[1]
    inst.config.sweep_interval_ms = 30_000
    inst._last_sweep = now  # the tick is 30 s away
    inst.get_rate_limits([req(u, duration=5_000) for u in others],
                         now_ms=now)
    me = threading.current_thread().name
    for i in (1, 2, 3, 6_000):  # at 6 s the occupants have expired
        got = inst.get_rate_limits([req(key)], now_ms=now + i)[0]
        assert got.error == "rate limit table full"
        # one sweep in all: after the first such wave, not on the worker
        assert swept == [me]
        assert counter(inst, "gubernator_table_full_rows_total") == \
            (1, 2, 3, 4)[(1, 2, 3, 6_000).index(i)]
    assert inst.engine.sweep_wanted
    assert counter(inst, 'gubernator_sweep_total{cause="table_full"}') == 1
    assert counter(inst, 'gubernator_sweep_total{cause="tick"}') == 0
    assert counter(
        inst, 'gubernator_phase_duration_count{phase="sweep"}') == 1
    # the tick comes round: the expired occupants go, the key inserts
    inst._maybe_sweep(now + 30_000)
    assert swept == [me, me] and not inst.engine.sweep_wanted
    assert counter(inst, 'gubernator_sweep_total{cause="tick"}') == 1
    assert counter(
        inst, 'gubernator_phase_duration_count{phase="sweep"}') == 2
    got = inst.get_rate_limits([req(key)], now_ms=now + 30_001)[0]
    assert got.error == "" and got.remaining == 4
    assert counter(inst, "gubernator_table_full_rows_total") == 4


@pytest.mark.parametrize("kind", ["xla", "pallas", "xla_cold_tier"])
def test_a_table_full_row_is_counted_once(kind):
    """``gubernator_table_full_rows_total`` moves by exactly the rows
    answered ``table full``, over the pipelined wire lane (launch, then
    ``sync_packed``'s re-dispatch of the erred and the cold rows) and
    over the object lane (``check_packed``): rows whose window or
    bucket is full, rows outside the Mosaic step's value domain, and —
    with the cold tier on — none, since the tier serves them."""
    pytest.importorskip("gubernator_tpu.ops.native")
    from gubernator_tpu.ops import pallas_step as ps
    from gubernator_tpu.parallel.pallas_engine import PallasServingEngine
    from gubernator_tpu.proto import gubernator_pb2 as pb
    from gubernator_tpu.wire import req_to_tlv

    mesh = make_mesh(n=1)
    if kind == "pallas":
        eng = PallasServingEngine(mesh, capacity_per_shard=256,
                                  batch_per_shard=64)
    else:
        eng = ShardedEngine(mesh, capacity_per_shard=64,
                            batch_per_shard=64)
    inst = V1Instance(Config(cache_size=eng.cap_local, sweep_interval_ms=0,
                             tier_cold=kind == "xla_cold_tier",
                             tier_promote_threshold=4), engine=eng)
    try:
        n = 600 if kind == "pallas" else 150
        reqs = [req(f"k{i}", limit=50) for i in range(n)]
        if kind == "pallas":  # past the kernel's 30-bit counters
            for i in (3, 77, 401):
                reqs[i] = req(f"k{i}", limit=50, hits=ps.VALUE_BOUND + 5)
        data = b"".join(req_to_tlv(r) for r in reqs)
        out = pb.GetRateLimitsResp.FromString(
            inst.get_rate_limits_wire(data, now_ms=NOW)).responses
        full = sum(r.error == "rate limit table full" for r in out)
        assert {r.error for r in out} <= {"", "rate limit table full"}
        assert counter(inst, "gubernator_table_full_rows_total") == full
        assert (full == 0) == (kind == "xla_cold_tier"), full
        if kind == "pallas":
            assert all(out[i].error for i in (3, 77, 401)) and full > 3
        got = inst.get_rate_limits(reqs, now_ms=NOW + 1)
        again = sum(r.error == "rate limit table full" for r in got)
        assert counter(inst, "gubernator_table_full_rows_total") == \
            full + again
        assert (again == 0) == (kind == "xla_cold_tier"), again
    finally:
        inst.close()
