"""Host-side request packing: wire requests → fixed-shape device arrays.

The analog of the reference's request batching (peer_client.go › run()
flush loop + gubernator.go › GetRateLimits fan-out): requests are
coalesced into padded fixed-shape arrays so every batch reuses the same
compiled program (SURVEY.md §7.3 — bucketed batch sizes avoid
recompilation storms).

Everything calendar- or string-shaped happens here, on the host: key
hashing, Gregorian period-end computation, input clamps.  The device only
ever sees integers.
"""
from __future__ import annotations

import sys
from typing import List, NamedTuple, Sequence

import jax
import numpy as np

from ..gregorian import gregorian_expiration, gregorian_rate_duration_ms
from ..hashing import hash_keys
from ..tracing import phase
from ..types import (DURATION_MAX, EFF_MAX, TD_BOUND, VALUE_MAX, Behavior,
                     RateLimitRequest)

#: Batch sizes are rounded up to one of these to bound compile cache size.
BATCH_BUCKETS = (64, 256, 1024, 4096)

#: Back-compat alias for the old global input ceiling; the real bounds
#: are algorithm-aware now (types.py: DURATION_MAX / VALUE_MAX / EFF_MAX
#: / TD_BOUND — see oracle.py "Input clamps").
MAX_INPUT = VALUE_MAX


def clamp_config(algorithm, limit, duration, burst, behavior=0):
    """Scalar mirror of the packer clamps for (alg, limit, duration, burst).

    Used by the mesh tier's pin path (parallel/meshglobal.py) so pinned rows agree
    bit-for-bit with every packed request carrying the same config — a
    disagreement reads as a config change on the device and resets the
    row.  Must stay in lockstep with pack_requests/pack_columns and the
    oracle's _clamp_token/_clamp_leaky.
    """
    alg = 1 if int(algorithm) == 1 else 0
    duration = min(int(duration), DURATION_MAX)
    if alg == 1:
        if int(behavior) & int(Behavior.DURATION_IS_GREGORIAN):
            eff = gregorian_rate_duration_ms(duration)
        else:
            eff = max(duration, 1)
        cap_v = min(TD_BOUND // min(eff, EFF_MAX), VALUE_MAX)
    else:
        cap_v = VALUE_MAX
    limit = min(max(int(limit), 0), cap_v)
    burst = min(int(burst), cap_v) if int(burst) > 0 else limit
    return alg, limit, duration, burst


class RequestBatch(NamedTuple):
    """Fixed-shape [B] device view of a GetRateLimitsReq batch.

    ``now`` is per-request arrival time (epoch ms) — the device honors
    it per position, so batches packed at different wall-clock instants
    coalesce into one launch without quantizing time (the reference's
    sequential loop also reads the clock per request).  The packers
    always fill it; None (or a 0 entry) falls back to the scalar
    ``now_ms`` argument in ``decide_batch_impl`` ONLY — the serving
    paths (check_packed / check_columns / pack_wave_host) require the
    column.
    """

    key: jax.Array | np.ndarray  # uint64, 0 = padding
    hits: jax.Array | np.ndarray  # int64, clamped ≥ 0
    limit: jax.Array | np.ndarray  # int64, clamped ≥ 0
    duration: jax.Array | np.ndarray  # int64, as given
    eff_ms: jax.Array | np.ndarray  # int64, ≥ 1
    greg_end: jax.Array | np.ndarray  # int64, calendar period end (0 if n/a)
    behavior: jax.Array | np.ndarray  # int32 flags
    algorithm: jax.Array | np.ndarray  # int32
    burst: jax.Array | np.ndarray  # int64, already defaulted to limit
    valid: jax.Array | np.ndarray  # bool
    now: jax.Array | np.ndarray | None = None  # int64 epoch ms, 0 = unset


#: The step programs' upload layout: every RequestBatch int64 column
#: rides one [8, B] int64 matrix (key bit-viewed; row 7 is the
#: per-request arrival time), the int32/bool columns one [3, B] int32
#: matrix, and all five outputs one [5, B] int64 download.  A device
#: call then costs 2 uploads + 1 download instead of 10 + 5 —
#: per-transfer latency (PCIe doorbells) dominates these tiny arrays,
#: not bandwidth.
PACK64 = ("key", "hits", "limit", "duration", "eff_ms", "greg_end",
          "burst", "now")
PACK32 = ("behavior", "algorithm", "valid")
_EFF = PACK64.index("eff_ms")
_NOW = PACK64.index("now")
_ALG = PACK32.index("algorithm")
_VALID = PACK32.index("valid")

# ``Rows.batch`` reads the int32 ``valid`` row as bool through its low
# bytes
assert sys.byteorder == "little"


class Rows:
    """Request rows laid out ONCE in the upload layout: ``m64`` [8, n]
    i64 in PACK64 order, ``m32`` [3, n] i32 in PACK32 order.  A call's
    handler builds one (``pack_columns``, the C++ ingest, or
    ``stack_rows`` over loose columns) and the dispatch worker joins
    the calls' blocks into a wave — so no column is copied per wave.

    The rest is what an engine derives from the rows, once a call
    (``ShardedEngine.lay_out``; ``monotone is None`` = not derived):
    ``ood`` the indices of valid rows outside its step program's value
    domain (None = none), ``leaky`` the count of LEAKY_BUCKET rows that
    stay valid, ``greg`` the count of valid DURATION_IS_GREGORIAN rows
    (None = not counted yet; ``pack_columns`` knows it for free),
    ``now_lo`` / ``now_hi`` / ``monotone`` the range of the
    arrival-time row and whether it never decreases.  A wave joined
    straight into a pooled upload pair carries its ``lease`` (and the
    mesh-slot block ``mblk``); ``m64`` / ``m32`` are then views of it
    and die with it."""

    __slots__ = ("m64", "m32", "ood", "leaky", "greg", "now_lo", "now_hi",
                 "monotone", "lease", "mblk")

    def __init__(self, m64, m32):
        self.m64 = m64
        self.m32 = m32
        self.ood = None
        self.leaky = 0
        self.greg = None
        self.now_lo = self.now_hi = 0
        self.monotone = None
        self.lease = None
        self.mblk = None

    @classmethod
    def empty(cls, n: int) -> "Rows":
        """An uninitialised pair for n rows (the caller writes every
        cell)."""
        return cls(np.empty((len(PACK64), n), np.int64),
                   np.empty((len(PACK32), n), np.int32))

    def __len__(self) -> int:
        return self.m64.shape[1]

    @property
    def valid(self) -> np.ndarray:
        """The ``valid`` row as a bool VIEW (writes land in ``m32``)."""
        return self.m32[_VALID].view(np.bool_)[::4]

    @property
    def now(self) -> np.ndarray:
        return self.m64[_NOW]

    @property
    def algorithm(self) -> np.ndarray:
        return self.m32[_ALG]

    @property
    def batch(self) -> "PackedBatch":
        """The RequestBatch every other reader sees: row views, zero
        copy.  Built per call — the batch points at its rows, never
        the rows at a batch (no reference cycle to keep a lease
        alive)."""
        m64, m32 = self.m64, self.m32
        b = PackedBatch(
            key=m64[0].view(np.uint64), hits=m64[1], limit=m64[2],
            duration=m64[3], eff_ms=m64[4], greg_end=m64[5],
            behavior=m32[0], algorithm=self.algorithm, burst=m64[6],
            valid=self.valid, now=self.now)
        b.rows = self
        return b

    def take(self, idx) -> "Rows":
        """Rows ``idx`` as a block of their own (nothing derived)."""
        return Rows(np.take(self.m64, idx, axis=1),
                    np.take(self.m32, idx, axis=1))


class PackedBatch(RequestBatch):
    """A RequestBatch whose columns are row views of ONE ``Rows`` pair
    (``rows``).  ``_replace`` and ``type(b)(*cols)`` give a batch with
    ``rows`` None: loose columns again, stacked when next laid out."""

    rows: "Rows | None" = None


def stack_rows(b: RequestBatch) -> Rows:
    """A batch's rows: the pair it is a view of, else its loose numpy
    columns stacked into one."""
    rows = getattr(b, "rows", None)
    if rows is not None:
        return rows
    rows = Rows.empty(len(b.key))
    rows.m64[0] = np.asarray(b.key).view(np.int64)
    for i, f in enumerate(PACK64[1:], start=1):
        rows.m64[i] = getattr(b, f)
    for i, f in enumerate(PACK32):
        rows.m32[i] = getattr(b, f)
    return rows


def clock_order(calls: Sequence[Rows]) -> "List[int] | None":
    """The order of the calls' whole blocks in which the joined rows'
    clocks never run backwards and equal clocks keep the calls' own
    order — exactly what a stable sort of the joined rows by arrival
    time gives — or None where no order of whole blocks does (a call
    whose own clock runs backwards, two calls whose ranges overlap, or
    rows nobody derived the clocks of).  Calls that each carry ONE
    stamp, as a client's do, always have one."""
    if not all(c.monotone for c in calls):
        return None
    order = sorted(range(len(calls)), key=lambda i: calls[i].now_lo)
    for i, j in zip(order, order[1:]):
        a, b = calls[i], calls[j]
        if a.now_hi > b.now_lo or (a.now_hi == b.now_lo and i > j):
            return None
    return order


def join_calls(calls: Sequence[Rows], khashes, mslots=None,
               into: "WaveLease | None" = None):
    """Several calls' blocks → one wave's (rows, khash, mslot), nothing
    derived.  ``into``: a leased upload pair wide enough — the blocks
    are joined STRAIGHT into its first columns and the wave's rows are
    views of it (``rows.lease``; the mesh-slot column then a view of
    ``rows.mblk``).  Otherwise fresh matrices; one call alone is its
    own wave.  ``mslots``: per-call mesh-GLOBAL slot columns (a call
    without one fills -1 = sharded lane), None when no call has one."""
    total = sum(len(c) for c in calls)
    mparts = mslot = None
    if mslots is not None and any(m is not None for m in mslots):
        mparts = [m if m is not None else np.full(len(c), -1, np.int32)
                  for c, m in zip(calls, mslots)]
    if into is not None:
        wave = Rows(into.a64[:, :total], into.a32[:, :total])
        wave.lease = into
        np.concatenate([c.m64 for c in calls], axis=1, out=wave.m64)
        np.concatenate([c.m32 for c in calls], axis=1, out=wave.m32)
        if mparts is not None:
            wave.mblk = np.full(into.a64.shape[1], -1, np.int32)
            mslot = wave.mblk[:total]
            np.concatenate(mparts, out=mslot)
    elif len(calls) == 1:
        wave = calls[0]
        mslot = mparts[0] if mparts is not None else None
    else:
        wave = Rows(np.concatenate([c.m64 for c in calls], axis=1),
                    np.concatenate([c.m32 for c in calls], axis=1))
        if mparts is not None:
            mslot = np.concatenate(mparts)
    khash = khashes[0] if len(khashes) == 1 else np.concatenate(khashes)
    return wave, khash, mslot


class WaveLease:
    """One leased pair of packed upload matrices (a64 [8,m] i64,
    a32 [3,m] i32) from a :class:`WaveBufferPool`.

    The holder must call :meth:`release` on EVERY path (success, engine
    raise, close) once the wave's RESULTS are on the host — a launch is
    asynchronous and the runtime may read the host operands until then
    (the CPU backend aliases them outright), so a buffer returned right
    after the launch is a data race with the next wave's fill.  A lease
    dropped without release is detected by the GC
    hook: the pool counts it as a leak (``gubernator_wave_buffer_leaks``)
    and reclaims the buffers, so a bug degrades to a counter, not an
    unbounded allocation regression."""

    __slots__ = ("a64", "a32", "_pool", "_dirty", "_released",
                 "__weakref__")

    def __init__(self, pool: "WaveBufferPool", a64, a32, dirty: int):
        self._pool = pool
        self.a64 = a64
        self.a32 = a32
        #: columns [0, _dirty) may hold rows; the rest reads as padding
        self._dirty = dirty
        self._released = False

    def release(self) -> None:
        if self._released:
            return
        self._released = True
        self._pool._return(self.a64, self.a32, self._dirty)

    def __del__(self):  # pragma: no cover - exercised via gc in tests
        if not self._released:
            self._released = True
            self._pool._record_leak()
            self._pool._return(self.a64, self.a32, self._dirty)


class WaveBufferPool:
    """Ring of reusable packed wave-upload matrices, keyed by padded
    wave width ``m`` (= n_shards × wave bucket).

    The serving loop used to allocate a fresh [8,m] i64 + [3,m] i32
    pair (~0.7 MB at the default big bucket) for EVERY device wave;
    under the overlapped wave pipeline the same few shapes recur every
    couple hundred microseconds, so the allocator/page-fault churn is
    pure host-glue overhead (PERF.md §4.2).  ``lease(m)`` hands back a
    pooled pair (``empty_batch`` padding semantics: all zeros,
    ``eff_ms`` row = 1) or allocates on miss; ``WaveLease.release``
    returns it.  Thread-safe; the per-width ring is bounded (pipeline
    depth + a small margin) so a burst of odd widths cannot grow the
    pool without bound.

    ``metrics`` may be bound post-construction (V1Instance does) to a
    ``Metrics`` registry carrying ``wave_buffer_pool_hit`` /
    ``wave_buffer_pool_miss`` / ``wave_buffer_leaks`` counters.
    """

    #: pooled buffers kept per width — covers pipeline depth K plus the
    #: wave being packed while K are in flight
    MAX_PER_WIDTH = 4

    def __init__(self, max_per_width: int | None = None):
        import threading

        self._mu = threading.Lock()
        #: m → [(a64, a32, dirty columns), ...]
        self._free: dict[int, list] = {}  # guarded-by: self._mu
        self.max_per_width = (max_per_width if max_per_width is not None
                              else self.MAX_PER_WIDTH)
        self.hits = 0  # guarded-by: self._mu
        self.misses = 0  # guarded-by: self._mu
        self.leaks = 0  # guarded-by: self._mu
        self.outstanding = 0  # guarded-by: self._mu
        self.metrics = None  # bound by V1Instance after construction

    def lease(self, m: int, rows: int | None = None) -> WaveLease:
        """Lease a (a64 [8,m] i64, a32 [3,m] i32) pair that reads as
        padding — ``empty_batch`` semantics: zeros everywhere, eff_ms 1
        — in every column the caller will not write.  ``rows``: the
        caller overwrites every cell of columns [0, rows) and touches
        nothing else, so only [rows, m) is made padding, and of those
        only what the buffer's last holder wrote (in a steady stream of
        equal waves: nothing).  Without it the caller may write
        anywhere and gets the whole pair clean."""
        with self._mu:
            ring = self._free.get(m)
            buf = ring.pop() if ring else None
            if buf is not None:
                self.hits += 1
            else:
                self.misses += 1
            self.outstanding += 1
        lo = rows or 0
        if buf is not None:
            a64, a32, dirty = buf
            if dirty > lo:
                a64[:, lo:dirty] = 0
                a64[_EFF, lo:dirty] = 1
                a32[:, lo:dirty] = 0
            if self.metrics is not None:
                self.metrics.wave_buffer_pool_hit.inc()
        else:
            a64 = np.zeros((8, m), np.int64)
            a64[_EFF] = 1
            a32 = np.zeros((3, m), np.int32)
            if self.metrics is not None:
                self.metrics.wave_buffer_pool_miss.inc()
        return WaveLease(self, a64, a32, m if rows is None else rows)

    def _return(self, a64, a32, dirty: int) -> None:
        m = a64.shape[1]
        with self._mu:
            self.outstanding -= 1
            ring = self._free.setdefault(m, [])
            if len(ring) < self.max_per_width:
                ring.append((a64, a32, dirty))

    def _record_leak(self) -> None:
        with self._mu:
            self.leaks += 1
        if self.metrics is not None:
            self.metrics.wave_buffer_leaks.inc()

    def stats(self) -> dict:
        with self._mu:
            return {"hits": self.hits, "misses": self.misses,
                    "leaks": self.leaks, "outstanding": self.outstanding,
                    "pooled": sum(len(v) for v in self._free.values())}

    def mem_stats(self) -> dict:
        """Memory-ledger probe feed (ISSUE 13): host bytes the idle
        rings hold right now — summed from the live arrays, so an
        odd-width burst or a shrunk ring stays exact."""
        with self._mu:
            pooled = nbytes = 0
            for ring in self._free.values():
                for a64, a32, _dirty in ring:
                    pooled += 1
                    nbytes += int(a64.nbytes) + int(a32.nbytes)
            return {"pooled": pooled, "pooled_bytes": nbytes,
                    "hits": self.hits}


def bucket_size(n: int) -> int:
    for b in BATCH_BUCKETS:
        if n <= b:
            return b
    return ((n + BATCH_BUCKETS[-1] - 1) // BATCH_BUCKETS[-1]) * BATCH_BUCKETS[-1]


def empty_batch(size: int) -> RequestBatch:
    return RequestBatch(
        key=np.zeros(size, np.uint64),
        hits=np.zeros(size, np.int64),
        limit=np.zeros(size, np.int64),
        duration=np.zeros(size, np.int64),
        eff_ms=np.ones(size, np.int64),
        greg_end=np.zeros(size, np.int64),
        behavior=np.zeros(size, np.int32),
        algorithm=np.zeros(size, np.int32),
        burst=np.zeros(size, np.int64),
        valid=np.zeros(size, bool),
        now=np.zeros(size, np.int64),
    )


def pack_requests(
    reqs: Sequence[RateLimitRequest],
    now_ms: int,
    size: int | None = None,
    key_hashes: np.ndarray | None = None,
) -> tuple[RequestBatch, List[str]]:
    """Pack wire requests into a padded RequestBatch.

    Returns (batch, errors) where errors[i] is a per-request error string
    ("" if OK).  Requests with errors (e.g. invalid Gregorian ordinal —
    the reference surfaces these as resp.Error) are marked invalid in the
    batch and skipped by the device.

    ``key_hashes`` lets a dispatcher that already hashed the keys (for
    shard routing) skip re-hashing — string hashing is the host-side
    bottleneck.
    """
    n = len(reqs)
    b = empty_batch(size if size is not None else bucket_size(n))
    errors = [""] * n
    b.key[:n] = key_hashes if key_hashes is not None else hash_keys(
        [r.key for r in reqs])
    GREG = int(Behavior.DURATION_IS_GREGORIAN)  # hot loop: plain-int flags
    b.now[:n] = now_ms
    for i, r in enumerate(reqs):
        at = now_ms
        if r.created_at:
            # caller's accepted-at clock (forward hop, types.py): the
            # request applies at ITS time base, so a key served through
            # two daemons never mixes bases in one bucket row — and its
            # calendar period is the one that holds that clock
            # (gregorian.py, the rule)
            b.now[i] = at = r.created_at
        behavior = int(r.behavior)
        leaky = int(r.algorithm) == 1
        duration = min(int(r.duration), DURATION_MAX)
        if behavior & GREG:
            try:
                b.greg_end[i] = gregorian_expiration(at, duration)
                eff = gregorian_rate_duration_ms(duration)
            except (ValueError, KeyError):
                errors[i] = f"invalid gregorian duration ordinal: {duration}"
                b.key[i] = 0
                continue
        else:
            eff = max(duration, 1)
        # leaky td bounds: eff ≤ EFF_MAX, values ≤ TD_BOUND // eff
        # (oracle.py › _clamp_leaky); token values ≤ VALUE_MAX
        if leaky:
            eff = min(eff, EFF_MAX)
            cap_v = min(TD_BOUND // eff, VALUE_MAX)
        else:
            cap_v = VALUE_MAX
        limit = min(max(int(r.limit), 0), cap_v)
        b.eff_ms[i] = eff
        b.hits[i] = min(max(int(r.hits), 0), cap_v)
        b.limit[i] = limit
        b.duration[i] = duration
        b.behavior[i] = behavior
        # clamp to {0,1}: any other wire value must mean TOKEN_BUCKET
        # (like the oracle's `== LEAKY_BUCKET` test) — an unclamped
        # value would never equal the stored alg&1 and the row would
        # re-create fresh on every request, bypassing the limit
        b.algorithm[i] = 1 if leaky else 0
        b.burst[i] = min(int(r.burst), cap_v) if int(r.burst) > 0 else limit
        b.valid[i] = True
    return b, errors


def pack_columns(
    khash: np.ndarray,
    hits: np.ndarray,
    limit: np.ndarray,
    duration: np.ndarray,
    algorithm: np.ndarray,
    behavior: np.ndarray,
    burst: np.ndarray,
    now_ms: int,
    created_at: np.ndarray | None = None,
    sink=None,
) -> tuple[RequestBatch, dict]:
    """Vectorized pack of already-columnar requests (the C++ wire-ingest
    lane, ops/_native.cpp › parse_get_rate_limits) → RequestBatch.

    Same clamps and semantics as ``pack_requests``, applied as array ops
    — no per-request Python.  Returns (batch, errors) where errors maps
    request index → error string (invalid Gregorian ordinals, as on the
    pb2 path).  ``khash`` must already be mixed and zero-remapped.

    ``created_at`` (optional i64[n], 0 = unset) is the caller's
    accepted-at clock from the forward hop: rows carrying it take it as
    their ``now`` so they apply at the CALLER's time base, and a
    Gregorian row's period is the one that holds that clock
    (gregorian.py, the rule) — one period end per distinct (ordinal,
    period) of the call, never a loop over rows.  ``sink`` (the
    dispatcher) takes the ``pack.calendar`` phase round that
    arithmetic; the batch's ``rows.greg`` is the count of its valid
    Gregorian rows.
    """
    n = len(khash)
    # ONE pair in the upload layout, filled in place: the batch handed
    # back is row views of it, so the dispatch worker joins this call's
    # block into its wave without copying a column again
    rows = Rows.empty(n)
    b = rows.batch
    b.key[:] = khash
    b.greg_end[:] = 0
    rows.m32[_VALID] = 1
    b.behavior[:] = behavior
    dur = np.minimum(np.asarray(duration, np.int64), DURATION_MAX,
                     out=b.duration)
    eff = np.maximum(dur, 1)
    errors: dict = {}
    b.now[:] = now_ms
    if created_at is not None:
        created = np.asarray(created_at, np.int64)
        np.copyto(b.now, created, where=created > 0)
    rows.greg = 0
    greg = np.flatnonzero(b.behavior & int(Behavior.DURATION_IS_GREGORIAN))
    if len(greg):
        with phase("pack.calendar", sink, cpu=True,
                   every=getattr(sink, "call_sample", 1)):
            rows.greg = _calendar_ends(b, greg, eff, errors)
    # leaky td bounds (oracle.py › _clamp_leaky): eff ≤ EFF_MAX and
    # hits/limit/burst ≤ TD_BOUND // eff; token values ≤ VALUE_MAX
    leaky = np.asarray(algorithm) == 1
    b.algorithm[:] = leaky
    b.eff_ms[:] = eff = np.where(leaky, np.minimum(eff, EFF_MAX), eff)
    cap_v = np.where(leaky, np.minimum(TD_BOUND // eff, VALUE_MAX),
                     VALUE_MAX)
    lim = np.minimum(np.clip(np.asarray(limit, np.int64), 0, None), cap_v,
                     out=b.limit)
    np.minimum(np.clip(np.asarray(hits, np.int64), 0, None), cap_v,
               out=b.hits)
    b.burst[:] = np.where(burst > 0, np.minimum(burst, cap_v), lim)
    return b, errors


def _one_value(col: np.ndarray) -> bool:
    """Whether a non-empty column holds one value throughout — as bytes
    (a copy and a compare that keep the GIL), not as a reduction."""
    raw = col.tobytes()
    return raw == raw[:col.itemsize] * len(col)


def _calendar_ends(b: "PackedBatch", at: np.ndarray, eff: np.ndarray,
                   errors: dict) -> int:
    """``greg_end`` and ``eff`` of a call's Gregorian rows (indices
    ``at``), each row's period the one that holds ITS clock (``b.now``),
    and the count of them that stay valid.  One period end a distinct
    (ordinal, clock) pair — a client's call carries one stamp, so one
    pair — broadcast to the pair's rows; rows of an invalid ordinal are
    made invalid and reported.

    The pairs are found by comparing bytes, else in a ``set`` over
    ``tolist()``, not by ``np.unique``: this runs in ~30 handler threads
    under one GIL, and every numpy call that gives the GIL up (sorts,
    reductions, ufuncs over a call's 1,000 rows) has to win it back
    from the others (PERF.md §6, PR 25 and PR 39: 12.7 ms a call with
    ``np.unique``, 0.8 without)."""
    whole = len(at) == len(b.key)
    sel = slice(None) if whole else at
    dur, now = b.duration[sel], b.now[sel]
    if _one_value(dur) and _one_value(now):
        pairs = {(int(dur[0]), int(now[0]))}
    else:
        pairs = set(zip(dur.tolist(), now.tolist()))
    valid = len(at)
    for d, t in pairs:
        rows = sel if len(pairs) == 1 else at[(dur == d) & (now == t)]
        try:
            end = gregorian_expiration(t, d)
            eff[rows] = gregorian_rate_duration_ms(d)
            b.greg_end[rows] = end
        except (ValueError, KeyError):
            bad = np.arange(len(b.key))[rows]
            valid -= len(bad)
            b.valid[bad] = False
            b.key[bad] = 0
            errors.update(dict.fromkeys(
                bad.tolist(), f"invalid gregorian duration ordinal: {d}"))
    return valid
