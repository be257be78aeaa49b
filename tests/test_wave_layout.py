"""A call's rows are laid out once (ISSUE 30): the call's thread stacks
them into ONE block in the upload layout and derives what the launch
needs to know of them (``ShardedEngine.lay_out``; the C++ ingest does
both in its one pass), and the dispatch worker joins the calls' blocks
into the wave (``join_calls``) — straight into the pooled upload pair
where the route is the identity.

Held here, for seeded random job sets: whichever way the rows travel,
every device wave uploads byte for byte what the PARENT's per-column
layout uploaded (``parent_uploads`` below keeps that algorithm: domain
mask → arrival order → ``_build_waves`` → one scatter a column), the
answers, ``gubernator_wave_leaky_rows`` and the out-of-domain rows are
those ``check_packed`` gives for the concatenated loose columns, the
worker's numpy work does not grow with the jobs it joins, and a wave's
lease goes back exactly when its token is dead."""
import gc
import threading
import zlib

import numpy as np
import pytest

from gubernator_tpu.core.batch import (PACK32, PACK64, RequestBatch,
                                       WaveBufferPool, pack_columns,
                                       stack_rows)
from gubernator_tpu.dispatcher import Dispatcher
from gubernator_tpu.faults import FaultInjected, FaultSet
from gubernator_tpu.hashing import hash_request_keys
from gubernator_tpu.metrics import Metrics
from gubernator_tpu.ops import native
from gubernator_tpu.ops import pallas_step as ps
from gubernator_tpu.parallel import ShardedEngine, make_mesh, sharded
from gubernator_tpu.parallel.pallas_engine import PallasServingEngine
from gubernator_tpu.types import RateLimitRequest
from gubernator_tpu.wire import req_to_tlv

NOW = 1_790_000_000_000
BUCKETS = (16, 64)  # small: 8 jobs of ~12 rows overflow the largest


def job_of(rng, tag, n, kind, now, *, invalid=False, ood=False,
           inverted=False, mslot=False):
    """One call's (loose-column RequestBatch, khash, mslot): ~half the
    keys repeat inside the job, some across jobs."""
    keys = [f"k{rng.integers(0, 6)}" if rng.random() < 0.4
            else f"{tag}:{i}" for i in range(n)]
    kh = hash_request_keys(["wl"] * n, keys)
    alg = {"token": np.zeros(n, np.int32), "leaky": np.ones(n, np.int32),
           "mixed": rng.integers(0, 2, n).astype(np.int32)}[kind]
    hits = rng.integers(0, 3, n)
    if ood:  # past the kernel's counters (token) / divisor (leaky eff)
        hits[rng.integers(0, n)] = ps.VALUE_BOUND + 5
    dur = np.full(n, 10_000, np.int64)
    if ood and alg.any():
        dur[np.nonzero(alg)[0][0]] = ps.EFF_BOUND + 9
    created = np.zeros(n, np.int64)
    if inverted:  # an older stamp in the middle of the job
        created[n // 2] = now - 500
    b, errs = pack_columns(
        kh, hits, np.full(n, 50, np.int64), dur, alg,
        np.zeros(n, np.int32), np.where(alg == 1, 60, 0), now,
        created_at=created)
    assert not errs
    # loose columns (what a producer that stacks nothing hands over)
    b = RequestBatch(*[np.array(c) for c in b])
    if invalid:
        b.valid[rng.integers(0, n, 2)] = False
    ms = None
    if mslot:
        ms = np.where(rng.random(n) < 0.5, rng.integers(0, 8, n),
                      -1).astype(np.int32)
    return b, kh, ms


def jobs_of(seed, n_jobs, kind, feature):
    rng = np.random.default_rng(seed)
    jobs = []
    for j in range(n_jobs):
        now = NOW + 10 * j
        if feature == "inverted_between" and j == n_jobs - 1:
            now = NOW - 40  # the last job's clock is behind the others
        n = int(rng.integers(9, 15)) if feature == "overflow" else \
            int(rng.integers(3, 8))
        jobs.append(job_of(
            rng, f"j{j}", n, kind, now,
            invalid=feature == "invalid", ood=feature == "ood",
            inverted=feature == "inverted_inside" and j == 0,
            mslot=feature.startswith("mslot") and (
                feature == "mslot_all" or j % 2 == 0)))
    return jobs


def concat(jobs):
    """The jobs' loose columns end to end, as ONE call's."""
    batch = RequestBatch(*[np.concatenate([np.asarray(b[f]) for b, _, _ in jobs])
                           for f in range(len(RequestBatch._fields))])
    kh = np.concatenate([k for _, k, _ in jobs])
    if all(m is None for _, _, m in jobs):
        return batch, kh, None
    return batch, kh, np.concatenate(
        [m if m is not None else np.full(len(k), -1, np.int32)
         for _, k, m in jobs])


def parent_uploads(eng, batch, khash, mslot):
    """What the parent commit uploaded for these rows: its domain mask,
    arrival order and per-column fill, kept as the reference.  Returns
    ([(a64, a32, mblk)], ood indices or None, leaky rows counted)."""
    valid = np.asarray(batch.valid).copy()
    alg = np.asarray(batch.algorithm)
    ood = None
    if eng.value_domain is not None:
        mask, _ = ps.pallas_value_domain_mask(batch)
        if mslot is not None:
            mask = mask | (mslot >= 0)
        bad = valid & ~mask
        if bad.any():
            ood = np.nonzero(bad)[0]
            valid &= mask
    leaky = int(np.count_nonzero((alg == 1) & valid))
    now = np.asarray(batch.now)
    order = (np.arange(len(now)) if (now[1:] >= now[:-1]).all()
             else np.argsort(now, kind="stable"))
    cols = batch._replace(valid=valid)
    out = []
    for idx, slots, bw, _ in eng._build_waves(khash, order):
        a64 = np.zeros((8, eng.n * bw), np.int64)
        a32 = np.zeros((3, eng.n * bw), np.int32)
        a64[PACK64.index("eff_ms")] = 1
        a64[0][slots] = np.asarray(cols.key).view(np.int64)[idx]
        for i, f in enumerate(PACK64[1:], start=1):
            a64[i][slots] = np.asarray(getattr(cols, f))[idx]
        for i, f in enumerate(PACK32):
            a32[i][slots] = np.asarray(getattr(cols, f))[idx]
        mblk = None
        if mslot is not None:
            mblk = np.full(eng.n * bw, -1, np.int32)
            mblk[slots] = mslot[idx]
        out.append((a64, a32, mblk))
    return out, ood, leaky


def spy_uploads(eng):
    """Every later ``_launch_arrays`` operand of ``eng``, copied."""
    seen = []
    real = type(eng)._launch_arrays.__get__(eng)

    def spy(a64, a32, now_ms, mblk=None):
        seen.append((np.array(a64), np.array(a32),
                     None if mblk is None else np.array(mblk)))
        return real(a64, a32, now_ms) if mblk is None \
            else real(a64, a32, now_ms, mblk)

    eng._launch_arrays = spy
    return seen


def same_uploads(got, want):
    assert len(got) == len(want)
    for (g64, g32, gm), (w64, w32, wm) in zip(got, want):
        assert g64.dtype == np.int64 and g32.dtype == np.int32
        assert g64.tobytes() == w64.tobytes()
        assert g32.tobytes() == w32.tobytes()
        assert (gm is None) == (wm is None)
        assert gm is None or (gm.dtype == np.int32
                              and gm.tobytes() == wm.tobytes())


def counter(eng, name, **labels):
    c = getattr(eng.metrics_ref, name)
    return (c.labels(**labels) if labels else c)._value.get()


def engine_pair(cls, mesh):
    """[(the worker's engine, its uploads), (the reference engine, its
    uploads), the worker's dispatcher]: two engines of one kind fed the
    same history."""
    pair = []
    for _ in range(2):
        eng = cls(mesh, capacity_per_shard=1 << 10, batch_per_shard=64,
                  wave_buckets=BUCKETS)
        eng.metrics_ref = Metrics()
        pair.append((eng, spy_uploads(eng)))
    return pair + [Dispatcher(pair[0][0])]


@pytest.fixture(scope="module")
def engines(cpu_mesh):
    made = {}

    def get(kind):
        if kind not in made:
            made[kind] = {
                "xla_n1": lambda: engine_pair(ShardedEngine, make_mesh(n=1)),
                "xla_n4": lambda: engine_pair(ShardedEngine, cpu_mesh),
                "pallas_n1": lambda: engine_pair(PallasServingEngine,
                                                 make_mesh(n=1)),
            }[kind]()
        return made[kind]

    yield get
    for _, _, disp in made.values():
        disp.close()


FEATURES = ["plain", "invalid", "ood", "inverted_between",
            "inverted_inside", "mslot_some", "mslot_all", "overflow"]


#: every case on the XLA step (one shard and the four-device mesh); on
#: the interpret-mode kernel, which alone has a value domain, those
#: that differ by it
CASES = [(e, j, k, f) for e in ("xla_n1", "xla_n4", "pallas_n1")
         for j in (1, 8) for k in ("token", "leaky", "mixed")
         for f in FEATURES
         if e != "pallas_n1" or f not in ("invalid", "inverted_inside",
                                          "mslot_all")]


@pytest.mark.parametrize("engine,n_jobs,kind,feature", CASES)
def test_joined_wave_uploads_and_answers_what_the_columns_would(
        engines, engine, n_jobs, kind, feature):
    (eng, seen), (ref, ref_seen), disp = engines(engine)
    seed = zlib.crc32(f"{engine}/{n_jobs}/{kind}/{feature}".encode())
    jobs = jobs_of(seed, n_jobs, kind, feature)
    batch, kh, mslot = concat(jobs)
    now = max(int(np.asarray(b.now).max()) for b, _, _ in jobs)
    want, want_ood, want_leaky = parent_uploads(ref, batch, kh, mslot)

    # the reference: ONE call of loose columns through check_packed
    del ref_seen[:]
    leaky0 = counter(ref, "wave_leaky_rows")
    ref_cols = ref.check_packed(batch, kh, now, mslot=mslot)
    same_uploads(ref_seen, want)
    assert counter(ref, "wave_leaky_rows") - leaky0 == want_leaky

    # the engine's join keeps the calls in the order it is given them:
    # clocks that run backwards, between two or inside one, take the
    # sorted arm; so do four shards and rows past the largest bucket
    clock = np.asarray(batch.now)
    fits = eng.n == 1 and len(kh) <= BUCKETS[-1]
    calls = [eng.lay_out(b, k, m) for b, k, m in jobs]
    wave, _, _ = eng.join_calls(calls, [k for _, k, _ in jobs],
                                [m for _, _, m in jobs])
    assert (wave.lease is not None) == (
        fits and bool((clock[1:] >= clock[:-1]).all()))
    if wave.lease is not None:
        wave.lease.release()

    # the dispatcher's way: each call laid out by itself (its own
    # thread's work), the worker puts the jobs in the order of their
    # clocks, joins their blocks, launches, syncs, drops
    del seen[:]
    leaky0 = counter(eng, "wave_leaky_rows")
    routes0 = {r: counter(eng, "wave_route", route=r)
               for r in ("identity", "sorted")}
    queued = [disp_job(disp, b, k, now, m) for b, k, m in jobs]
    packed = list(queued)
    wbatch, wkh, wms, wnow = disp._concat_jobs(packed)
    assert sorted(map(id, packed)) == sorted(map(id, queued))
    token = eng.launch_packed(wbatch, wkh, wnow, mslot=wms)
    cols = eng.sync_packed(token)
    assert eng.wave_pool.stats()["outstanding"] == len(token[3])
    same_uploads(seen, want)
    # each job's rows of the wave answer what its rows of the
    # reference's one call did, wherever the wave holds them
    at = {id(j): (sum(len(q.khash) for q in queued[:i]), len(j.khash))
          for i, j in enumerate(queued)}
    a = 0
    ood = [] if token[6] is None else token[6].tolist()
    want_at = []
    for j in packed:
        lo, n = at[id(j)]
        assert wkh[a:a + n].tobytes() == kh[lo:lo + n].tobytes()
        assert token[0].hits[a:a + n].tolist() == \
            np.asarray(batch.hits)[lo:lo + n].tolist()
        for got, ref_col in zip(cols, ref_cols):
            assert got.dtype == ref_col.dtype
            assert got[a:a + n].tobytes() == ref_col[lo:lo + n].tobytes()
        want_at += [] if want_ood is None else [
            a + i - lo for i in want_ood.tolist() if lo <= i < lo + n]
        a += n
    eng.drop_packed(token)
    assert counter(eng, "wave_leaky_rows") - leaky0 == want_leaky
    assert sorted(ood) == sorted(want_at)
    assert cols[4][ood].all()  # answered `table full`
    if feature == "ood" and eng.value_domain is not None:
        assert ood
    # which arm ran depends on what was observed, nothing else: whole
    # jobs in clock order are joined straight into the lease
    identity = fits and feature != "inverted_inside"
    assert (wbatch.rows.lease is not None) == identity
    routed = {r: counter(eng, "wave_route", route=r) - routes0[r]
              for r in routes0}
    assert routed == ({"identity": 1, "sorted": 0} if identity
                      else {"identity": 0, "sorted": len(want)})
    s = eng.wave_pool.stats()
    assert s["outstanding"] == 0 and s["leaks"] == 0, s


@pytest.mark.parametrize("domain", [None, (ps.VALUE_BOUND, ps.EFF_BOUND)])
@pytest.mark.parametrize("kind", ["token", "leaky", "mixed"])
def test_the_ingest_derives_what_lay_out_derives(kind, domain, monkeypatch):
    """``pack_wire_wave`` lays a call out and derives its out-of-domain
    rows, leaky rows and clocks in ONE pass: the same block and the
    same values ``lay_out`` gives for the parsed columns."""
    rng = np.random.default_rng(7)
    n = 40
    alg = {"token": [0] * n, "leaky": [1] * n,
           "mixed": rng.integers(0, 2, n).tolist()}[kind]
    reqs = [RateLimitRequest(
        name="wl", unique_key=f"u{i}", hits=int(rng.integers(0, 3)),
        limit=50, duration=10_000, algorithm=alg[i], burst=60 * alg[i],
        created_at=(NOW - 7 if i == 11 else NOW + i if i % 5 == 0 else 0))
        for i in range(n)]
    reqs[3].hits = ps.VALUE_BOUND + 1
    reqs[17].limit = ps.VALUE_BOUND
    reqs[23].duration = ps.EFF_BOUND + 4
    data = b"".join(req_to_tlv(r) for r in reqs)
    a64, a32 = np.empty((8, n), np.int64), np.empty((3, n), np.int32)
    res = native.pack_wire_wave(data, NOW, a64, a32, domain)
    ood, leaky, _greg, now_lo, now_hi, monotone = res[-1]

    eng = ShardedEngine.__new__(
        ShardedEngine if domain is None else PallasServingEngine)
    parsed = native.parse_get_rate_limits(data)
    b, _ = pack_columns(res[1], parsed["hits"], parsed["limit"],
                        parsed["duration"], parsed["algorithm"],
                        parsed["behavior"], parsed["burst"], NOW,
                        created_at=parsed["created_at"])
    rows = eng.lay_out(b, res[1])
    # and what lay_out derives WITHOUT the extension (numpy, the
    # engine's own mask): the same again
    plain = stack_rows(RequestBatch(*b))
    monkeypatch.setattr(sharded, "_wire_native", None)
    eng.lay_out(plain.batch, res[1])
    assert [None if r.ood is None else r.ood.tolist() for r in (rows, plain)
            ] == [None if ood is None else ood.tolist()] * 2
    assert (plain.leaky, plain.now_lo, plain.now_hi, plain.monotone) == (
        rows.leaky, rows.now_lo, rows.now_hi, rows.monotone)
    assert a64.tobytes() == rows.m64.tobytes()
    assert a32.tobytes() == rows.m32.tobytes()
    assert (ood is None) == (rows.ood is None) == (domain is None)
    if ood is not None:
        assert ood.tolist() == rows.ood.tolist() == (
            [3, 17, 23] if alg[23] else [3, 17])
    assert (leaky, now_lo, now_hi, monotone) == (
        rows.leaky, rows.now_lo, rows.now_hi, rows.monotone)
    assert (now_lo, now_hi, monotone) == (NOW - 7, NOW + 35, False)


# ---- the worker's numpy work is O(jobs), not a pass per column ----------

#: numpy calls the worker may make from ``_concat_jobs`` through
#: ``launch_packed`` for one identity-route wave: three joins (a64, a32,
#: khash) and the launch's scalar clock make 4.  The parent made ~70
#: (12 concatenates, ~17 for the mask, ~15 to route, 22 scatters).
#: On four shards the wave takes the sorted route, planned and filled by
#: the C++ extension (ISSUE 36): the joins, the two views of the plan's
#: lists, the sharded put of the pair and the token's batch views make
#: 9 call events.  The numpy route made 16 — and ~25 array operators
#: (shifts, gathers, ``==``, ``%``, scatters) this profiler cannot see,
#: each of which the C++ pass took with them.
CEILING = {1: 6, 4: 9}


def fused_calls(n_jobs: int):
    """``n_jobs`` wire calls of 3–7 plain rows, some keys shared."""
    rng = np.random.default_rng(n_jobs)
    return [b"".join(
        req_to_tlv(RateLimitRequest(
            name="wl", unique_key=(f"k{rng.integers(0, 6)}"
                                   if rng.random() < 0.4 else f"j{j}:{i}"),
            hits=1, limit=50, duration=10_000))
        for i in range(int(rng.integers(3, 8)))) for j in range(n_jobs)]


# ``producer``: who laid the jobs' blocks out — ``lay_out`` over loose
# columns (the numpy lane) or the C++ ingest (``prepack_wire``: the
# call's own right-sized pair, on any shard count since ISSUE 38).  The
# worker's ceiling is the same: it joins blocks, whoever made them.
@pytest.mark.parametrize("producer", ["lay_out", "fused"])
@pytest.mark.parametrize("shards", CEILING)
def test_worker_numpy_calls_do_not_grow_with_jobs(numpy_calls, shards,
                                                  producer, cpu_mesh):
    from gubernator_tpu.dispatcher import _PackedJob

    eng = ShardedEngine(cpu_mesh if shards == 4 else make_mesh(n=shards),
                        capacity_per_shard=1 << 10,
                        batch_per_shard=64, wave_buckets=(64, 512))
    eng.warmup()  # a first launch traces and compiles: not the worker's
    disp = Dispatcher(eng)
    try:
        counts = {}
        for n_jobs in (2, 2, 8):  # the first wave allocates its lease
            if producer == "fused":
                pres = [eng.prepack_wire(data, NOW + 10 * i)
                        for i, data in enumerate(fused_calls(n_jobs))]
                packed = [_PackedJob(pre.rows, pre.khash, NOW + 10 * i)
                          for i, pre in enumerate(pres)]
            else:
                jobs = jobs_of(n_jobs, n_jobs, "mixed", "plain")
                packed = [disp_job(disp, b, k, NOW + 10 * i)
                          for i, (b, k, _) in enumerate(jobs)]
            with numpy_calls() as calls:
                batch, kh, ms, now = disp._concat_jobs(packed)
                token = eng.launch_packed(batch, kh, now)
            counts[n_jobs] = calls.n
            # one shard: the identity arm; four: routed by shard
            assert (batch.rows.lease is not None) == (shards == 1)
            assert not eng.sync_packed(token)[4].any()
            eng.drop_packed(token)
        assert counts[2] == counts[8] <= CEILING[shards], counts
    finally:
        disp.close()


def disp_job(disp, batch, khash, now, mslot=None):
    """The job ``check_packed_view`` would queue (laid out here, in the
    caller's thread)."""
    from gubernator_tpu.dispatcher import _PackedJob

    return _PackedJob(disp.lay_out(batch, khash, mslot), khash, now,
                      mslot=mslot)


# ---- lifetimes ----------------------------------------------------------

def test_a_lease_taken_while_a_token_is_unsynced_is_another_buffer():
    """The identity arm's token batch IS the lease: whoever leases the
    same width while the token lives — a handler, the next wave — never
    receives its buffer, synced or not; after the drop it may."""
    eng = ShardedEngine(make_mesh(n=1), capacity_per_shard=1 << 10,
                        batch_per_shard=64, wave_buckets=BUCKETS)
    (b, kh, _), = jobs_of(5, 1, "token", "plain")
    eng.drop_packed(eng.launch_packed(b, kh, NOW))  # a buffer is pooled
    token = eng.launch_packed(b, kh, NOW + 1)
    mine = token[3][0][-1]
    assert token[0].rows.lease is mine
    before = token[0].rows.m64.tobytes()
    got = []

    def handler():
        other = eng.wave_pool.lease(mine.a64.shape[1])
        other.a64[:] = -1  # scribble, as a fill would
        got.append(other)

    for when in ("unsynced", "synced"):
        t = threading.Thread(target=handler)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
        assert got[-1].a64 is not mine.a64, when
        assert token[0].rows.m64.tobytes() == before, when
        if when == "unsynced":
            eng.sync_packed(token)
    eng.drop_packed(token)
    for other in got:
        other.release()
    s = eng.wave_pool.stats()
    assert s["outstanding"] == 0 and s["leaks"] == 0, s


@pytest.mark.parametrize("point", ["dispatch_launch", "device_step",
                                   "dispatch_sync", "dispatch_splice"])
def test_a_faulted_wave_returns_its_lease(point):
    """Every failure path of the pipelined worker — before the launch,
    under the engine lock, before and after the sync — ends with the
    wave's lease back in the pool: none outstanding, none leaked."""
    eng = ShardedEngine(make_mesh(n=1), capacity_per_shard=1 << 10,
                        batch_per_shard=64, wave_buckets=BUCKETS)
    faults = FaultSet()
    disp = Dispatcher(eng, faults=faults)
    try:
        (b, kh, _), = jobs_of(9, 1, "mixed", "plain")
        assert not disp.check_packed(b, kh, NOW)[4].any()  # warm
        faults.arm(f"{point}:error")
        with pytest.raises(FaultInjected):
            disp.check_packed(b, kh, NOW + 1)
        faults.clear()
        assert not disp.check_packed(b, kh, NOW + 2)[4].any()
    finally:
        disp.close()
    gc.collect()
    s = eng.wave_pool.stats()
    assert s["outstanding"] == 0 and s["leaks"] == 0, s


def test_pool_cleans_only_what_its_last_holder_wrote():
    """``lease(m, rows=k)``: columns [k, m) read as padding whatever the
    buffer held before; [0, k) are the caller's to overwrite."""
    pool = WaveBufferPool()
    a = pool.lease(32)
    a.a64[:] = 7
    a.a32[:] = 7
    a.release()
    b = pool.lease(32, rows=20)
    assert b.a64 is a.a64
    assert not b.a64[[0, 1, 2, 3, 5, 6, 7], 20:].any()
    assert (b.a64[4, 20:] == 1).all() and not b.a32[:, 20:].any()
    b.a64[:, :20] = 9
    b.release()
    c = pool.lease(32, rows=8)  # a shorter wave: [8, 20) cleaned too
    assert not c.a64[0, 8:].any() and (c.a64[4, 8:] == 1).all()
    c.release()
    d = pool.lease(32)  # anyone may write anywhere: all padding
    assert not d.a64[0].any() and (d.a64[4] == 1).all()
    d.release()
