"""The shard route of a device wave in C++ (ISSUE 36): ``ops/_native.cpp
› route_plan`` and ``route_fill`` are ``ShardedEngine._build_waves`` and
``._fill`` in one pass each that keeps the GIL.  The numpy pair stays in
the engine — the route of a checkout without the extension — and is the
reference here: for seeded inputs the plan is the same lists and the
fill leaves the same bytes in every cell of the upload pair and of the
mesh-slot block, into a lease whose last holder dirtied every cell."""
import numpy as np
import pytest

from gubernator_tpu.core.batch import Rows
from gubernator_tpu.hashing import mix64_np, shard_of
from gubernator_tpu.ops import native
from gubernator_tpu.parallel import ShardedEngine, make_mesh

SMALL, REAL = (16, 64), (1024, 8192)
U64 = np.uint64


def keys_on(shard: int, count: int, n: int, salt: int) -> np.ndarray:
    """``count`` distinct key hashes that ``shard_of`` files under
    ``shard`` of ``n``."""
    h = np.arange(1, 4096, dtype=U64) * U64(0x9E3779B97F4A7C15) + U64(salt)
    return h[shard_of(h, n) == shard][:count]


def on_shards(counts, salt, rng) -> np.ndarray:
    """Key hashes with ``counts[s]`` rows on shard s, shuffled."""
    kh = np.concatenate([keys_on(s, c, len(counts), salt)
                         for s, c in enumerate(counts)])
    return rng.permutation(kh)


def zipf(rng) -> np.ndarray:
    return mix64_np((rng.zipf(1.1, 8000) % 10_000_000).astype(U64))


#: case → (shards, buckets, rng → dict(khash, pending, valid, mslot),
#: [(rows, bucket) of each device wave expected])
CASES = {
    # the benchmark's wave: ~8,000 Zipf(1.1) rows, the hot key's shard
    # over 1,024 — the large bucket
    "zipf_four_shards_large_bucket": (4, REAL, lambda r: dict(
        khash=zipf(r)), [(8000, 8192)]),
    "every_shard_within_the_small_bucket": (4, SMALL, lambda r: dict(
        khash=on_shards((10, 3, 16, 1), 7, r)), [(30, 16)]),
    "densest_shard_over_the_largest_bucket": (4, SMALL, lambda r: dict(
        khash=on_shards((2, 70, 20, 1), 13, r)), [(64 + 23, 64), (6, 16)]),
    "three_device_waves": (4, SMALL, lambda r: dict(
        khash=on_shards((130, 0, 65, 64), 17, r)),
        [(64 * 3, 64), (64 + 1, 64), (2, 16)]),
    "one_shard": (1, SMALL, lambda r: dict(
        khash=on_shards((40,), 19, r)), [(40, 64)]),
    "one_shard_overflow": (1, SMALL, lambda r: dict(
        khash=on_shards((70,), 19, r)), [(64, 64), (6, 16)]),
    # the retry: the erred rows, sorted
    "pending_a_sorted_subset": (4, SMALL, lambda r: dict(
        khash=on_shards((20, 9, 30, 5), 23, r),
        pending=np.sort(r.choice(64, 21, replace=False))), None),
    # clocks that run backwards: a stable argsort of the arrival times
    "pending_an_arrival_order": (4, SMALL, lambda r: dict(
        khash=on_shards((20, 9, 30, 5), 29, r),
        pending=np.argsort(r.integers(0, 5, 64), kind="stable")),
        [(64, 64)]),
    "pending_with_a_row_twice": (4, SMALL, lambda r: dict(
        khash=on_shards((4, 4, 4, 4), 31, r),
        pending=np.array([5, 3, 5, 0, 15, 3])), None),
    "a_valid_override": (4, SMALL, lambda r: dict(
        khash=on_shards((20, 9, 30, 5), 37, r),
        valid=r.integers(0, 2, 64).astype(bool)), [(64, 64)]),
    "a_strided_valid_override": (4, SMALL, lambda r: dict(
        khash=on_shards((20, 9, 30, 5), 37, r),
        valid=r.integers(0, 2, 4 * 64).astype(bool)[::4]), [(64, 64)]),
    "an_mslot_column_with_pinned_rows": (4, SMALL, lambda r: dict(
        khash=on_shards((20, 9, 30, 5), 41, r),
        mslot=np.where(r.integers(0, 3, 64) == 0, r.integers(0, 4096, 64),
                       -1).astype(np.int32)), [(64, 64)]),
    "mslot_valid_and_overflow_together": (4, SMALL, lambda r: dict(
        khash=on_shards((2, 70, 20, 1), 43, r),
        valid=r.integers(0, 2, 93).astype(bool),
        mslot=r.integers(-1, 9, 93).astype(np.int32)),
        [(64 + 23, 64), (6, 16)]),
    "empty_pending": (4, SMALL, lambda r: dict(
        khash=on_shards((3, 3, 3, 3), 47, r),
        pending=np.empty(0, np.int64)), []),
    "no_rows": (4, SMALL, lambda r: dict(khash=np.empty(0, U64)), []),
    # the ends of the hash range: shard 0 and shard n - 1
    "khash_zero_and_all_ones": (4, SMALL, lambda r: dict(
        khash=np.array([0, 2**64 - 1, 0, 2**64 - 1, 2**63], U64)),
        [(5, 16)]),
    "a_khash_view_every_other": (4, SMALL, lambda r: dict(
        khash=on_shards((20, 18, 30, 10), 53, r)[::2]), None),
}


@pytest.fixture(scope="module")
def engine(cpu_mesh):
    made = {}

    def get(n, buckets):
        if (n, buckets) not in made:
            made[n, buckets] = ShardedEngine(
                cpu_mesh if n == 4 else make_mesh(n=n),
                capacity_per_shard=1 << 10, batch_per_shard=buckets[0],
                wave_buckets=buckets)
        return made[n, buckets]

    return get


def joined_rows(rng, n: int) -> Rows:
    """n rows of noise in the upload layout, ``valid`` a 0/1 word."""
    m32 = rng.integers(-2**31, 2**31, (3, n)).astype(np.int32)
    m32[2] = rng.integers(0, 2, n)
    return Rows(rng.integers(-2**62, 2**62, (8, n)), m32)


def scribble(lease, mblk=None) -> None:
    """Its last holder wrote every cell."""
    lease.a64[:] = -0x0123456789ABCDEF
    lease.a32[:] = 0x7EADBEEF
    if mblk is not None:
        mblk[:] = 77


@pytest.mark.parametrize("case", CASES)
def test_the_native_route_is_the_numpy_route_byte_for_byte(case, engine):
    n, buckets, make, waves = CASES[case]
    rng = np.random.default_rng(sorted(CASES).index(case))
    arg = dict(pending=None, valid=None, mslot=None) | make(rng)
    khash, pending, valid, mslot = (arg[k] for k in (
        "khash", "pending", "valid", "mslot"))
    eng = engine(n, buckets)
    assert eng.wave_buckets == buckets and eng.n == n
    want = eng._build_waves(
        khash, np.arange(len(khash)) if pending is None else pending)
    got = native.route_plan(khash, pending, n, buckets)
    assert len(got) == len(want)
    for (gi, gs, gb, gc), (wi, ws, wb, wc) in zip(got, want):
        assert gi.dtype == gs.dtype == np.int64
        assert gi.tolist() == wi.tolist() and gs.tolist() == ws.tolist()
        assert (gb, gc) == (wb, wc) and type(gb) is type(gc) is int
    if waves is not None:
        assert [(len(i), b) for i, _, b, _ in got] == waves
    wave = joined_rows(rng, len(khash))
    for idx, slots, bw_w, _ in got:
        # the pool's clean lease + the numpy scatter ...
        lease, mblk = eng._fill(wave, mslot, valid, idx, slots, bw_w)
        a64, a32 = lease.a64, lease.a32
        ref = (a64.tobytes(), a32.tobytes(),
               None if mblk is None else mblk.tobytes())
        scribble(lease)
        lease.release()
        # ... and the C++ pass into that very pair, every cell dirty
        lease, mblk = eng._fill_native(wave, mslot, valid, idx, slots, bw_w)
        assert lease.a64 is a64 and lease.a32 is a32
        assert (a64.tobytes(), a32.tobytes()) == ref[:2]
        assert (mblk is None) == (mslot is None)
        if mblk is not None:
            assert mblk.dtype == np.int32 and mblk.tobytes() == ref[2]
            scribble(lease, mblk)
            native.route_fill(wave.m64, wave.m32, valid, mslot, idx, slots,
                              a64, a32, mblk)
            assert (a64.tobytes(), a32.tobytes(), mblk.tobytes()) == ref
        lease.release()
    # a wave whose rows are views of a wider pair (the retry of a wave
    # joined into its lease): the same bytes
    if got:
        idx, slots, bw_w, _ = got[0]
        wide = joined_rows(rng, len(khash) + 9)
        narrow = Rows(wide.m64[:, :len(khash)], wide.m32[:, :len(khash)])
        copy = Rows(narrow.m64.copy(), narrow.m32.copy())
        pairs = []
        for rows in (narrow, copy):
            lease, mblk = eng._fill_native(rows, mslot, valid, idx, slots,
                                           bw_w)
            pairs.append((lease.a64.tobytes(), lease.a32.tobytes()))
            lease.release()
        assert pairs[0] == pairs[1]


def _fill_args(rng, n=12, m=32):
    rows = joined_rows(rng, n)
    idx = np.arange(n, dtype=np.int64)
    return dict(m64=rows.m64, m32=rows.m32, valid=None, mslot=None, idx=idx,
                slots=idx * 2, a64=np.empty((8, m), np.int64),
                a32=np.empty((3, m), np.int32), mblk=None)


#: case → (what to change of a good call, the error)
WRONG_FILL = {
    "m32_one_row_short": (lambda a: a | dict(m32=a["m32"][:, :-1]),
                          ValueError),
    "m64_seven_rows": (lambda a: a | dict(m64=a["m64"][:7]), ValueError),
    "a64_of_32_bit_items": (lambda a: a | dict(
        a64=np.empty((8, 32), np.int32)), ValueError),
    "a32_of_64_bit_items": (lambda a: a | dict(
        a32=np.empty((3, 32), np.int64)), ValueError),
    "m64_of_floats": (lambda a: a | dict(
        m64=a["m64"].astype(np.float64)), ValueError),
    "pair_widths_differ": (lambda a: a | dict(
        a32=np.empty((3, 31), np.int32)), ValueError),
    "rows_not_contiguous": (lambda a: a | dict(
        m64=np.empty((8, 24), np.int64)[:, ::2]), ValueError),
    "pair_rows_not_contiguous": (lambda a: a | dict(
        a64=np.empty((8, 64), np.int64)[:, ::2]), ValueError),
    "idx_of_32_bit_items": (lambda a: a | dict(
        idx=a["idx"].astype(np.int32)), ValueError),
    "slots_shorter_than_idx": (lambda a: a | dict(slots=a["slots"][:-1]),
                               ValueError),
    "valid_of_the_wrong_length": (lambda a: a | dict(
        valid=np.ones(11, bool)), ValueError),
    "valid_of_words": (lambda a: a | dict(valid=np.ones(12, np.int32)),
                       ValueError),
    "mslot_without_mblk": (lambda a: a | dict(
        mslot=np.zeros(12, np.int32)), ValueError),
    "mblk_without_mslot": (lambda a: a | dict(
        mblk=np.empty(32, np.int32)), ValueError),
    "mblk_narrower_than_the_pair": (lambda a: a | dict(
        mslot=np.zeros(12, np.int32), mblk=np.empty(31, np.int32)),
        ValueError),
    "a_row_outside_the_wave": (lambda a: a | dict(
        idx=np.where(a["idx"] == 11, 12, a["idx"])), IndexError),
    "a_negative_row": (lambda a: a | dict(
        idx=np.where(a["idx"] == 0, -1, a["idx"])), IndexError),
    "a_slot_outside_the_pair": (lambda a: a | dict(
        slots=np.where(a["idx"] == 11, 32, a["slots"])), IndexError),
    "slots_not_ascending": (lambda a: a | dict(slots=a["slots"][::-1].copy()),
                            IndexError),
    "a_slot_twice": (lambda a: a | dict(
        slots=np.where(a["idx"] == 5, 8, a["slots"])), IndexError),
    "a_read_only_pair": (lambda a: a | dict(
        a32=np.frombuffer(bytes(3 * 32 * 4), np.int32).reshape(3, 32)),
        ValueError),
}


@pytest.mark.parametrize("case", WRONG_FILL)
def test_a_wrong_fill_argument_raises_and_writes_nothing(case):
    change, error = WRONG_FILL[case]
    good = _fill_args(np.random.default_rng(5))
    native.route_fill(**good)  # the good call goes through
    assert good["a32"][2, ::2][:12].tolist() == good["m32"][2].tolist()
    arg = change(good)
    for k in ("a64", "a32", "mblk"):
        if arg[k] is not None and arg[k].flags.writeable:
            arg[k][...] = 99
    with pytest.raises(error):
        native.route_fill(**arg)
    for k in ("a64", "a32", "mblk"):
        if arg[k] is not None and arg[k].flags.writeable:
            assert (arg[k] == 99).all()


KH = np.arange(1, 9, dtype=U64) * U64(0x9E3779B97F4A7C15)

WRONG_PLAN = {
    "khash_of_32_bit_items": ((KH.astype(np.uint32), None, 4, SMALL),
                              ValueError),
    "khash_of_floats": ((KH.astype(np.float64), None, 4, SMALL), ValueError),
    "khash_two_dimensional": ((KH.reshape(2, 4), None, 4, SMALL),
                              ValueError),
    "pending_of_32_bit_items": ((KH, np.arange(4, dtype=np.int32), 4, SMALL),
                                ValueError),
    "pending_not_contiguous": ((KH, np.arange(8)[::2], 4, SMALL),
                               ValueError),
    "pending_past_the_rows": ((KH, np.array([0, 8]), 4, SMALL), IndexError),
    "pending_negative": ((KH, np.array([-1]), 4, SMALL), IndexError),
    "no_shard": ((KH, None, 0, SMALL), ValueError),
    "more_shards_than_any_mesh": ((KH, None, 1 << 20, SMALL), ValueError),
    "no_bucket": ((KH, None, 4, ()), ValueError),
    "buckets_not_ascending": ((KH, None, 4, (64, 16)), ValueError),
    "a_bucket_of_nothing": ((KH, None, 4, (0, 16)), ValueError),
    "a_bucket_not_a_number": ((KH, None, 4, (16, "64")), TypeError),
    "khash_not_an_array": (([1, 2, 3], None, 4, SMALL), TypeError),
}


@pytest.mark.parametrize("case", WRONG_PLAN)
def test_a_wrong_plan_argument_raises(case):
    args, error = WRONG_PLAN[case]
    assert len(native.route_plan(KH, None, 4, SMALL)) == 1
    with pytest.raises(error):
        native.route_plan(*args)


def test_a_build_older_than_the_source_is_refused_not_taken_for_none(
        monkeypatch):
    """``sharded.py``, ``instance.py`` and ``hashing.py`` read an
    ImportError of ``ops/native.py`` as "no extension" and fall back to
    numpy.  A ``_native*.so`` from before the newest entry point
    (``native.NEWEST``; ISSUE 36 brought the check with ``route_*``)
    must not pass for that: the import fails with another error, naming
    the cure."""
    import importlib.util
    import sys
    import types

    from gubernator_tpu import ops
    from gubernator_tpu.ops import _native

    stale = types.ModuleType(_native.__name__)
    stale.__file__ = "_native.stale.so"
    for name in dir(_native):
        if not name.startswith("__") and name != native.NEWEST:
            setattr(stale, name, getattr(_native, name))
    monkeypatch.setitem(sys.modules, _native.__name__, stale)
    monkeypatch.setattr(ops, "_native", stale)
    # a second copy of the face, so the real one is left as it is
    spec = importlib.util.spec_from_file_location(
        "gubernator_tpu.ops.native_probe", native.__file__)
    probe = importlib.util.module_from_spec(spec)
    with pytest.raises(RuntimeError, match="make native") as err:
        spec.loader.exec_module(probe)
    assert not isinstance(err.value, ImportError)
    assert "_native.stale.so" in str(err.value)
    # with the entry point the same copy imports
    setattr(stale, native.NEWEST, getattr(_native, native.NEWEST))
    spec.loader.exec_module(probe)
    assert len(probe.route_plan(KH, None, 4, SMALL)) == 1


# ---- ISSUE 38: fused blocks on the sorted route --------------------------

def test_fused_and_columns_calls_join_into_one_sorted_wave(cpu_mesh):
    """Two calls the fused C++ ingest packed (``prepack_wire``, which
    serves any shard count since ISSUE 38) and one the numpy lane packed
    (``pack_columns`` + ``lay_out``) are joined by the dispatch worker
    into ONE wave that takes the sorted route on four shards as ONE
    device wave — and every row is answered as three calls run one by
    one answer it: a key that all three calls hit is debited in their
    order."""
    from gubernator_tpu.core.batch import pack_columns
    from gubernator_tpu.dispatcher import Dispatcher, _PackedJob
    from gubernator_tpu.metrics import Metrics
    from gubernator_tpu.types import RateLimitRequest
    from gubernator_tpu.wire import req_to_tlv

    now = 1_792_000_000_000

    def call(tag: str, n: int) -> bytes:
        reqs = [RateLimitRequest(name="fj", unique_key=f"{tag}{i}", hits=1,
                                 limit=7, duration=60_000) for i in range(n)]
        # the key every call shares: limit 4, two hits a call
        reqs[n // 2:n // 2] = [RateLimitRequest(
            name="fj", unique_key="shared", hits=1, limit=4,
            duration=60_000)] * 2
        return b"".join(req_to_tlv(r) for r in reqs)

    calls = [call("a", 30), call("b", 41), call("c", 23)]

    def engine():
        eng = ShardedEngine(cpu_mesh, capacity_per_shard=1 << 10,
                            batch_per_shard=64, wave_buckets=(64, 512))
        eng.metrics_ref = Metrics()
        return eng

    def columns_job(eng, data, at):
        p = native.parse_get_rate_limits(data)
        kh = mix64_np(p["khash_raw"])
        b, errs = pack_columns(kh, p["hits"], p["limit"], p["duration"],
                               p["algorithm"], p["behavior"], p["burst"],
                               at, created_at=p["created_at"])
        assert not errs
        return eng.lay_out(b, kh), kh

    def fused_job(eng, data, at):
        pre = eng.prepack_wire(data, at)
        return pre.rows, pre.khash

    # one by one, each call a wave of its own, all on the numpy lane
    ref = engine()
    want = []
    for i, data in enumerate(calls):
        rows, kh = columns_job(ref, data, now + i)
        want.append(ref.check_packed(rows.batch, kh, now + i))
    # one wave: fused, columns, fused
    eng = engine()
    disp = Dispatcher(eng)
    try:
        jobs = [_PackedJob(*make(eng, data, now + i), now + i)
                for i, (make, data) in enumerate(zip(
                    (fused_job, columns_job, fused_job), calls))]
        # the fused blocks are the call's own right-sized pair
        assert [len(j.rows) for j in jobs] == [32, 43, 25]
        batch, kh, ms, at = disp._concat_jobs(jobs)
        assert batch.rows.lease is None and ms is None  # four shards
        token = eng.launch_packed(batch, kh, at)
        assert len(token[3]) == 1  # ONE device wave
        got = eng.sync_packed(token)
        eng.drop_packed(token)
    finally:
        disp.close()
    route = eng.metrics_ref.wave_route
    assert (route.labels(route="sorted")._value.get(),
            route.labels(route="identity")._value.get()) == (1, 0)
    assert eng.metrics_ref.wave_native_route._value.get() == 1
    a = 0
    for w in want:
        b = a + len(w[0])
        for col_got, col_want in zip(got, w):
            assert col_got[a:b].tolist() == col_want.tolist()
        a = b
    assert a == len(got[0]) == 100
    # the shared key: six hits on a limit of 4, in the calls' order
    shared = [i for i, k in enumerate(kh.tolist())
              if kh.tolist().count(k) == 6]
    assert len(shared) == 6
    assert [int(got[0][i]) for i in shared] == [0, 0, 0, 0, 1, 1]
