"""Sharded multi-chip engine: key-ranged tables under shard_map.

The reference forwards non-owned keys to their owner over gRPC
(gubernator.go › GetRateLimits fan-out → peer_client.go batches —
reconstructed).  Here every chip owns a hash range; the host routes each
request to its owner's sub-batch and one shard_map program applies all
sub-batches simultaneously — the "forwarding hop" is a host-side array
permutation plus one ICI-synchronized step instead of N² RPC streams.

Decision semantics are identical to single-chip: each key's state lives
on exactly one shard, so owner-applies-hits parity is exact.
"""
from __future__ import annotations

import contextlib
import logging
import time
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..hashing import shard_of
from ..types import (Behavior, RateLimitRequest, RateLimitResponse,
                     Status)
from ..core.batch import (PACK32, PACK64, RequestBatch, Rows,  # noqa: F401
                          WaveBufferPool, clock_order, empty_batch,
                          join_calls, pack_requests, stack_rows)
from ..core.step import decide_batch_impl, _insert, _lookup, _probe_slots
from ..core.table import (COLUMN_DTYPES, TableState, column_to_host,
                          from_host, init_table, is_empty, put_rows,
                          split64, take_rows)
from ..tracing import phase
from .mesh import (SHARD_AXIS, XLA_EXEC_MU, exec_gate, make_mesh,
                   shard_table, table_sharding)

log = logging.getLogger("gubernator_tpu.sharded")

try:  # fused C++ wire ingest (ops/_native.cpp); optional
    from ..ops import native as _wire_native
except ImportError:  # pragma: no cover - unbuilt extension
    _wire_native = None

#: the Behavior bit the C++ ingest cannot model (calendar period ends
#: are computed in Python): its pre-pass declines such a call
_GREGORIAN = int(Behavior.DURATION_IS_GREGORIAN)

#: TableState value columns addressable by row programs (all but `key`).
VALUE_COLS = tuple(f for f in TableState._fields if f != "key")


class PrepackedWave:
    """One fused-ingest call: its rows parsed/clamped/hashed AND laid
    out by C++ in one pass (pack_wire_wave) into a right-sized pair,
    with what the engine derives of them (``Rows``), plus its size and
    the per-request views the analytics tap reads.  Which calls the
    lane serves is settled before one exists (``prepack_wire``)."""

    __slots__ = ("rows", "n", "khash", "tlv_off", "tlv_len", "name_hash")

    def __init__(self, rows, n, khash, tlv_off, tlv_len, name_hash):
        self.rows = rows
        self.n = n
        self.khash = khash
        self.tlv_off = tlv_off
        self.tlv_len = tlv_len
        self.name_hash = name_hash


def autogrow_limit_per_shard(total_rows: int, n_shards: int,
                             cap_local: int) -> int:
    """Config's cache_autogrow_max (TOTAL rows, an upper bound) → the
    per-shard ceiling ShardedEngine takes: rounded DOWN to a power of
    two (a memory bound must never be exceeded), floored at the current
    capacity (a bound below it just disables growth)."""
    if total_rows <= 0:
        return 0
    agl = max(total_rows // n_shards, cap_local)
    return 1 << (agl.bit_length() - 1)


def make_gather_rows(mesh):
    """jit program: probe-lookup a [n·B] key block per shard, return
    (found mask, value columns) — the owner-side read for GLOBAL
    broadcasts (global.go › runBroadcasts collecting changed items)."""

    def _gather(state, keys):
        kw = split64(keys)
        row = _lookup(state.key, _probe_slots(kw, state.capacity), kw)
        found = (keys != 0) & (row >= 0)
        at = jnp.where(found, row, 0)
        return found, tuple(take_rows(getattr(state, f), at)
                            for f in VALUE_COLS)

    return jax.jit(shard_map(
        _gather, mesh=mesh, in_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
        out_specs=P(SHARD_AXIS)))


def make_remove_rows(mesh):
    """jit program: probe-lookup a [n·B] key block and clear matched
    rows (key + expire → 0).  The Cache.Remove analog (cache.go) —
    used by the Store-backed admin path."""

    def _remove(state, keys):
        kw = split64(keys)
        row = _lookup(state.key, _probe_slots(kw, state.capacity), kw)
        found = (keys != 0) & (row >= 0)
        wrow = jnp.where(found, row, state.capacity)
        zero = jnp.zeros(keys.shape, jnp.int64)
        return state._replace(
            key=put_rows(state.key, wrow, zero, unique=False),
            expire_at=put_rows(state.expire_at, wrow, zero, unique=False),
        ), found

    return jax.jit(shard_map(
        _remove, mesh=mesh, in_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS))))


def make_upsert_rows(mesh):
    """jit program: find-or-insert a [n·B] key block per shard and
    overwrite the value columns — the replica-side write for GLOBAL
    broadcasts (gubernator.go › UpdatePeerGlobals → cache.Add analog).
    Returns (new_state, placed mask)."""

    def _upsert(state, keys, cols):
        cap = state.capacity
        valid = keys != 0
        kw = split64(keys)
        tkey, row, _ = _insert(state.key, _probe_slots(kw, cap), kw, valid,
                               jnp.full(keys.shape, -1, jnp.int32))
        placed = valid & (row >= 0)
        wrow = jnp.where(placed, row, cap)
        new = {"key": tkey}
        for f, col in zip(VALUE_COLS, cols):
            new[f] = put_rows(getattr(state, f), wrow, col, unique=False)
        return TableState(**new), placed

    sharded = shard_map(
        _upsert, mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS)),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS)))
    return jax.jit(sharded)


def make_grow(mesh, cap_new: int):
    """jit program: re-place every live row of a [cap_old] shard table
    into a fresh [cap_new] table, entirely on device — the reshard path
    for capacity changes (ROUND_NOTES gap: the host-mediated
    snapshot/restore loop is shard-count independent but streams the
    whole table through host memory; this is one device program).
    The old table IS the batch here, and it stays words: the probe
    sequence, the claims and the row moves all work on the halves.

    Key→shard ownership depends only on the mesh size (hashing.shard_of),
    so capacity changes never move rows across shards: the program is a
    per-shard probe re-insertion plus a psum'd dropped-row count (rows
    whose probe window in the target is exhausted — common when
    shrinking into high occupancy, rare but possible even when growing
    from a full table; best-effort like restore, and callers surface
    the count: a dropped key resets, which is inside the reference's
    LRU-eviction contract but must be observable).
    """

    def _grow(state):
        cap_old = state.capacity
        key = state.key
        valid = ~is_empty(key)
        # init_table is shard_map-safe (no device placement; its guards
        # are host-side trace-time checks) and the single source of
        # truth for column defaults
        fresh = init_table(cap_new)
        tkey, row, _ = _insert(fresh.key, _probe_slots(key, cap_new), key,
                               valid, jnp.full(cap_old, -1, jnp.int32))
        placed = valid & (row >= 0)
        wrow = jnp.where(placed, row, cap_new)

        def move(to, frm):
            return to.at[wrow].set(frm, mode="drop")

        new = fresh._replace(key=tkey, **{
            f: jax.tree.map(move, getattr(fresh, f), getattr(state, f))
            for f in VALUE_COLS})
        dropped = lax.psum((valid & (~placed)).sum(dtype=jnp.int64),
                           SHARD_AXIS)
        return new, dropped

    return jax.jit(shard_map(
        _grow, mesh=mesh, in_specs=P(SHARD_AXIS),
        out_specs=(P(SHARD_AXIS), P())))


def responses_from_columns(cols, errors=None):
    """(status, limit, remaining, reset, full) columns + optional
    per-request error strings → RateLimitResponse objects.  THE response
    contract, shared by the engine's object lane and the dispatcher's
    merged-wave path."""
    st, lim, rem, rst, full = cols
    # one bulk conversion to Python ints: per-element numpy scalar
    # indexing costs ~µs each and this loop runs per request
    st_l = np.asarray(st).tolist()
    lim_l = np.asarray(lim).tolist()
    rem_l = np.asarray(rem).tolist()
    rst_l = np.asarray(rst).tolist()
    full_l = np.asarray(full).tolist()
    out: List[RateLimitResponse] = []
    for i in range(len(st_l)):
        if errors is not None and errors[i]:
            out.append(RateLimitResponse(error=errors[i]))
        elif full_l[i]:
            # probe window full even after the retry (and auto-grow,
            # if enabled) inside check_packed
            out.append(RateLimitResponse(error="rate limit table full"))
        else:
            out.append(RateLimitResponse(
                # attribute lookup, not Status(...): the enum
                # constructor costs ~µs and this is per request
                status=Status.OVER_LIMIT if st_l[i]
                else Status.UNDER_LIMIT,
                limit=lim_l[i], remaining=rem_l[i],
                reset_time=rst_l[i]))
    return out


def make_sharded_step(mesh, donate: bool = False):
    """jit-compiled sharded step: (state, batch, now) → (state, outputs).

    state/batch arrays are globally [n·cap_local] / [n·B] with block d on
    device d; outputs keep that layout; counters are psum-reduced across
    the mesh (the only collective on the hot path — metrics, not data).

    ``donate`` aliases the table in/out (see core/step.py ›
    decide_batch_donated for the trade-off); callers must then thread
    state linearly.
    """
    S = SHARD_AXIS

    def _step(state, batch, now):
        state, out = decide_batch_impl(state, batch, now)
        over = lax.psum(out.over_count, S)
        ins = lax.psum(out.insert_count, S)
        return state, (out.status, out.remaining, out.reset_time, out.limit,
                       out.err), (over, ins)

    sharded = shard_map(
        _step, mesh=mesh,
        in_specs=(P(S), P(S), P()),
        out_specs=(P(S), P(S), P()),
    )
    return jax.jit(sharded, donate_argnums=(0,) if donate else ())


def pack_wave_host(b: RequestBatch) -> tuple[np.ndarray, np.ndarray]:
    """RequestBatch of numpy columns → ([8,B] i64, [3,B] i32), the
    upload layout (core/batch.py › PACK64 / PACK32)."""
    rows = stack_rows(b)
    return rows.m64, rows.m32


def make_sharded_step_packed(mesh, donate: bool = False):
    """The serving twin of make_sharded_step over the packed wire layout
    (see PACK64/PACK32): (state, a64, a32, now) → (state, [5,B] i64
    outputs, (over, insert) counters)."""
    S = SHARD_AXIS

    # the name is the jit's, and a profile's "XLA Modules" line tells
    # this program from the fused engines' `_step` by it
    def xla_step_packed(state, a64, a32, now):
        batch = RequestBatch(
            key=lax.bitcast_convert_type(a64[0], jnp.uint64),
            hits=a64[1], limit=a64[2], duration=a64[3], eff_ms=a64[4],
            greg_end=a64[5], burst=a64[6], now=a64[7],
            behavior=a32[0], algorithm=a32[1], valid=a32[2] != 0)
        state, out = decide_batch_impl(state, batch, now)
        packed = jnp.stack([
            out.status.astype(jnp.int64), out.remaining, out.reset_time,
            out.limit, out.err.astype(jnp.int64)])
        over = lax.psum(out.over_count, S)
        ins = lax.psum(out.insert_count, S)
        return state, packed, (over, ins)

    sharded = shard_map(
        xla_step_packed, mesh=mesh,
        in_specs=(P(S), P(None, S), P(None, S), P()),
        out_specs=(P(S), P(None, S), P()),
    )
    return jax.jit(sharded, donate_argnums=(0,) if donate else ())


def make_pallas_sweep(mesh, interpret: bool = False):
    """jit program: per-shard fused Pallas sweep (ops/pallas_sweep.py)
    + psum'd live count, (state, now) → (state, live)."""
    from ..ops.pallas_sweep import sweep_expired_pallas

    def _one(state, now):
        st, live = sweep_expired_pallas(state, now, interpret=interpret)
        return st, lax.psum(live, SHARD_AXIS)

    # check_vma=False: pallas_call's out_shape carries no
    # varying-mesh-axes annotation
    return jax.jit(shard_map(
        _one, mesh=mesh, in_specs=(P(SHARD_AXIS), P()),
        out_specs=(P(SHARD_AXIS), P()), check_vma=False))


#: the lengths the key (or bucket) list of a tier migration pass's row
#: programs is padded to, its last entry repeated: a pass moves at most
#: ``tiering.MIGRATE_MAX`` keys (the largest here), and every length is
#: compiled by ``warmup_tier`` when a tier is bound — a gather of a new
#: length would compile inside a served wave.  A longer list (a GLOBAL
#: broadcast's, a demote-all's) runs at its own length, as it always did.
ROW_OP_SIZES = (1, 16, 64, 256)


def padded(ids: np.ndarray) -> np.ndarray:
    """``ids`` at the smallest length of ``ROW_OP_SIZES`` that holds it,
    its last entry repeated; as it is where none does (or it is
    empty)."""
    m = len(ids)
    size = next((z for z in ROW_OP_SIZES if z >= m), m)
    if size == m or not m:
        return ids
    return np.concatenate([ids, np.full(size - m, ids[-1], ids.dtype)])


class _TableImage:
    """A tier migration pass's view of the open-addressing table
    (tiering.py › TierController.migrate; the bucket engine's is a host
    image fetched once, pallas_engine.py › _BucketImage).  Here every
    step IS a batched device program over the pass's keys — at most
    five launches a pass whatever its size, where a key at a time cost
    five a key — and ``commit`` has nothing left to write."""

    def __init__(self, eng, keys: np.ndarray):
        self.eng, self.keys = eng, keys

    def place(self, sel: np.ndarray, rows: np.ndarray) -> np.ndarray:
        """Keys ``sel`` upserted with their i64[k, 8] rows (VALUE_COLS
        order): bool[k], False where the probe window is full."""
        keys = self.keys[sel]
        cols = {f: rows[:, j].astype(COLUMN_DTYPES[f])
                for j, f in enumerate(VALUE_COLS)}
        if self.eng.upsert_rows(keys, cols) == len(keys):
            return np.ones(len(keys), bool)
        return self.eng.gather_rows(keys)[0]

    def occupants(self, sel: np.ndarray) -> np.ndarray:
        return self.eng.probe_occupants(self.keys[sel])

    def take(self, sel: np.ndarray, vkeys: np.ndarray) -> tuple:
        """Rows ``vkeys`` gathered and removed: (found bool[k], rows
        i64[k, 8])."""
        found, cols = self.eng.gather_rows(vkeys)
        found &= vkeys != 0
        rows = np.stack([np.asarray(cols[f], np.int64)
                         for f in VALUE_COLS], axis=1)
        if found.any():
            self.eng.remove_rows(vkeys[found])
        return found, rows

    def commit(self) -> None:
        pass


class ShardedEngine:
    """Host dispatcher over a sharded table: the multi-chip analog of the
    reference's V1Instance request router (gubernator.go ›
    GetRateLimits → picker.Get → local/forward split)."""

    #: capability flags the dispatcher reads (ISSUE 8): fused engines
    #: (parallel/pallas_engine.py › FusedServingMixin) flip both — the
    #: wave's pack mark collapses into the `device` phase and the
    #: dispatcher's host-side column taps are skipped (the fused step
    #: emits the tap columns on device).  The classic engine keeps the
    #: classic phase partition and host taps.
    fused_serving = False
    fused_tap = False

    def __init__(self, mesh=None, capacity_per_shard: int = 1 << 16,
                 batch_per_shard: int = 1024,
                 auto_grow_limit: int = 0,
                 wave_buckets: Sequence[int] | None = None):
        self.mesh = mesh if mesh is not None else make_mesh()
        self.n = self.mesh.shape[SHARD_AXIS]
        self.cap_local = capacity_per_shard
        self.B = batch_per_shard
        #: Wave-size buckets for check_packed: a pass picks the smallest
        #: bucket covering its busiest shard, so a lone client batch
        #:  rides the small fast program while dispatcher-coalesced
        #: bursts amortize launch cost in one big wave instead of
        #: ceil(n/B) small ones (the front-door throughput lever —
        #: VERDICT r1 item 5).  Each bucket is one compiled program;
        #: warmup() pre-compiles them all.  The top rung is what ONE
        #: launch can carry (``wave_capacity``): the instance caps its
        #: dispatcher's waves by it.
        import os as _os
        env_buckets = _os.environ.get("GUBER_WAVE_BUCKETS", "")
        if wave_buckets:
            self.wave_buckets = tuple(sorted(set(wave_buckets)))
        elif env_buckets:
            self.wave_buckets = tuple(sorted(
                {int(x) for x in env_buckets.split(",") if x.strip()}))
        else:
            self.wave_buckets = self._default_wave_buckets()
        #: per-shard capacity ceiling for on-device auto-grow when probe
        #: windows stay exhausted after a sweep (0 = disabled).  The
        #: reference's LRU never fails an insert; with auto-grow on,
        #: neither do we until this bound.
        self.auto_grow_limit = auto_grow_limit
        #: the instance's Metrics registry (gubernator_wave_leaky_rows;
        #: the fused engines' wave counters): single-assigned at
        #: instance wiring BEFORE serving starts, read-only after
        self.metrics_ref = None  # lock-free: set once pre-serving, read-only after
        self._init_table_and_step()
        self._batch_sharding = NamedSharding(self.mesh, P(SHARD_AXIS))
        self._mat_sharding = NamedSharding(self.mesh, P(None, SHARD_AXIS))
        self._repl = NamedSharding(self.mesh, P())
        self.over_count = 0
        self.insert_count = 0
        self.sweep_count = 0
        #: a wave answered a row table_full since the last sweep: its
        #: probe window (or bucket) was full.  If expired rows clog it,
        #: a sweep frees it — the instance runs one BETWEEN waves
        #: (``_maybe_sweep``), never a wave for itself: a window full of
        #: live keys would buy a pass over the table on every wave
        self.sweep_wanted = False
        self.live_rows = -1  # set by the fused Pallas sweep
        self._gather = None  # lazily-built row programs
        self._upsert = None
        self._remove = None
        self._pallas_sweep_fn = None
        self._grow_fns: dict = {}  # cap_new → compiled grow program
        self.dropped_rows = 0  # rows lost to grow/restore re-placement
        #: reusable packed-upload matrices, one ring per wave width
        #: (core/batch.py): leased in join_calls / _fill, released when
        #: the wave's token is dropped (the token's batch may be views
        #: of them).  V1Instance binds its Metrics here for the
        #: hit/miss/leak counters.
        self.wave_pool = WaveBufferPool()
        self._iota = np.arange(self.n * self.wave_buckets[-1],
                               dtype=np.int64)  # see _first
        #: bound TierController (tiering.py) when GUBER_TIER_COLD=1 —
        #: check_packed pre-masks cold-resident rows out of the device
        #: wave and serves them (plus residual table-full rows) from
        #: the host cold tier on the way out
        self.tier = None  # lock-free: set once at instance wiring, read-only after

    def _default_wave_buckets(self) -> tuple:
        """The ladder where the operator set none (neither the
        constructor's ``wave_buckets`` nor GUBER_WAVE_BUCKETS): a lone
        call's rung and the coalesced wave's.  The XLA step keeps the
        wave at 8·B: it is bound by the device, costs per index, and
        its ``while`` walks the longest duplicate segment of a wave —
        a longer wave can only lengthen it (PERF.md §5, cell 6)."""
        return (self.B, self.B * 8)

    @property
    def wave_capacity(self) -> int:
        """Rows ONE launch can carry: the ladder's top rung.  A wave
        over it splits into several launches (``_build_waves``), so it
        is what the instance caps its dispatcher's waves at."""
        return self.wave_buckets[-1]

    def _init_table_and_step(self) -> None:
        """Build self.state + self._step (subclass hook: the Pallas
        serving engine swaps in its bucketized table + kernel step).

        The serving step aliases the table in/out by default
        (GUBER_STEP_DONATE=0 opts out): clean-step cold columns pass
        through copy-free and row scatters update in place (see
        core/step.py › decide_batch_donated; what the step costs on
        the chip: PERF.md §5, cell 6)."""
        import os as _os

        self.state = shard_table(self.mesh, self.cap_local)
        self._step = make_sharded_step_packed(
            self.mesh,
            donate=_os.environ.get("GUBER_STEP_DONATE", "1") == "1")

    def sweep(self, now_ms: int) -> None:
        """Reclaim expired rows on every shard (elementwise on the
        sharded arrays — no collective).  The eviction analog of the
        reference's LRU + expired-entry handling (lrucache.go).

        The fused Pallas kernel (same semantics + live count in one
        streaming pass, validated bit-exact on v5e; ops/pallas_sweep.py)
        runs by default on TPU backends; GUBER_PALLAS_SWEEP=1/0 forces
        it on/off (off-TPU it would run in the slow interpret mode)."""
        import os

        use_pallas = os.environ.get(
            "GUBER_PALLAS_SWEEP",
            "1" if jax.default_backend() == "tpu" else "0") == "1"
        if use_pallas and self.cap_local % 1024 == 0:
            self.state, live = self._pallas_sweep(now_ms)
            self.live_rows = int(live)
        else:
            from ..core.table import occupancy, sweep_expired

            with XLA_EXEC_MU:
                self.state = sweep_expired(self.state, np.int64(now_ms))
                if self.auto_grow_limit:
                    self.live_rows = int(occupancy(self.state))
        self.sweep_count += 1
        # Proactive growth: open-addressing probe windows start
        # exhausting on unlucky keys well before the table is full
        # (~2% per insert at 60% load with 8 probes), so with auto-grow
        # enabled double capacity once LIVE occupancy crosses 60% on
        # the sweep tick — off the serving path, so request latency
        # never pays for the grow (reactive growth in check_* stays as
        # the backstop when traffic outruns the sweep interval).
        if (self.auto_grow_limit
                and self.cap_local * 2 <= self.auto_grow_limit
                and self.live_rows > 0.6 * self.cap_local * self.n):
            dropped = self.grow(self.cap_local * 2)
            if dropped:
                log.warning("proactive grow to %d/shard dropped %d "
                            "live rows", self.cap_local, dropped)

    def _pallas_sweep(self, now_ms: int):
        """The fused sweep (``make_pallas_sweep``); interpret mode
        off-TPU (Mosaic kernels are TPU-only)."""
        if self._pallas_sweep_fn is None:
            self._pallas_sweep_fn = make_pallas_sweep(
                self.mesh, interpret=jax.default_backend() != "tpu")
        with XLA_EXEC_MU:
            return self._pallas_sweep_fn(self.state,
                                         jnp.asarray(now_ms, jnp.int64))

    #: (VALUE_BOUND, EFF_BOUND) of the step program's value domain, for
    #: the C++ ingest, which derives a call's out-of-domain rows in its
    #: one pass; None = the full int64 domain (the XLA step)
    value_domain = None

    def _first(self, n: int) -> np.ndarray:
        """arange(n) as a view of one shared array — READ-ONLY: the
        identity route's indices and slots, and the arrival order of a
        wave whose clock never runs backwards."""
        if n > len(self._iota):
            self._iota = np.arange(2 * n, dtype=np.int64)
        return self._iota[:n]

    # ---- a call's rows, laid out once (ISSUE 30) ------------------------
    #
    # Who lays out what: the CALL's thread stacks its rows into one
    # pair in the upload layout (core/batch.py › Rows) and derives,
    # once, what the launch needs to know of them (``lay_out``); the
    # dispatch worker — the one thread the device waits for — joins
    # the calls' blocks into the wave (``join_calls``) and launches.
    # Its work a wave is O(calls), not a pass per column.

    def lay_out(self, batch: RequestBatch, khash, mslot=None) -> Rows:
        """A call's rows with what this engine derives of them: the
        rows outside the step program's domain, the LEAKY rows that
        stay valid, and the range and order of their clocks."""
        rows = stack_rows(batch)
        if rows.monotone is not None:
            return rows  # derived already (the C++ ingest, an earlier call)
        if _wire_native is not None:
            # one C++ pass that keeps the GIL: this runs in ~30 handler
            # threads at once, and every numpy call of the fallback
            # below would have to win the GIL back from the others
            (rows.ood, rows.leaky, rows.greg, rows.now_lo, rows.now_hi,
             rows.monotone) = _wire_native.derive_rows(
                 rows.m64, rows.m32, mslot, self.value_domain)
            return rows
        if rows.greg is None:  # loose columns: pack_columns counts its own
            rows.greg = int(np.count_nonzero(
                rows.valid & ((rows.m32[0] & _GREGORIAN) != 0)))
        rows.ood, rows.leaky = self._out_of_domain(rows, mslot)
        now = rows.now
        rows.monotone = bool((now[1:] >= now[:-1]).all())
        if len(now):
            rows.now_lo, rows.now_hi = int(now.min()), int(now.max())
        return rows

    def _out_of_domain(self, rows: Rows, mslot=None):
        """``lay_out`` without the C++ extension: (indices of the valid
        rows this engine's step program cannot represent — None where
        none is — and the count of LEAKY_BUCKET rows that stay valid).
        The XLA step has the full int64 domain: nothing to mask."""
        alg = rows.algorithm
        if not alg.any():
            return None, 0
        return None, int(np.count_nonzero((alg == 1) & rows.valid))

    def join_calls(self, calls: Sequence[Rows], khashes, mslots=None):
        """The dispatch worker's `wave.concat`: the calls' blocks → ONE
        wave, (rows, khash, mslot).

        Where the route would be the identity — one shard, no cold
        tier, the rows fit the largest bucket and their clocks never
        run backwards, so ``_build_waves`` would put row i in slot i of
        one device wave — the blocks are joined STRAIGHT into a pooled
        upload pair: the concat is the fill, and the wave's rows are
        views of its lease.  Otherwise they are joined into fresh
        matrices that ``_fill`` scatters by shard.  Which of the two
        depends only on what is observed here."""
        total = sum(len(c) for c in calls)
        mono = clock_order(calls) == list(range(len(calls)))
        lease = None
        if (self.n == 1 and self.tier is None and mono
                and 0 < total <= self.wave_capacity):
            lease = self.wave_pool.lease(
                next(b for b in self.wave_buckets if total <= b),
                rows=total)
        try:
            wave, khash, mslot = join_calls(calls, khashes, mslots,
                                            into=lease)
        except BaseException:
            if lease is not None:
                lease.release()
            raise
        # what the calls derived, offset and summed in plain Python
        oods, off = [], 0
        for c in calls:
            if c.ood is not None:
                oods.append(c.ood + off)
            off += len(c)
        wave.ood = (None if not oods else oods[0] if len(oods) == 1
                    else np.concatenate(oods))
        wave.leaky = sum(c.leaky for c in calls)
        wave.greg = sum(c.greg or 0 for c in calls)
        wave.monotone = mono
        return wave, khash, mslot

    def _wave_of(self, batch: RequestBatch, khash, mslot):
        """The wave of a launch: the one ``join_calls`` joined into a
        lease (the dispatcher's), else this one call's rows joined
        now."""
        rows = getattr(batch, "rows", None)
        if rows is not None and rows.lease is not None:
            return rows, khash, mslot
        return self.join_calls([self.lay_out(batch, khash, mslot)],
                               [khash], [mslot])

    def _ride_invalid(self, wave: Rows, khash, mslot):
        """`wave.route`, first half: (the wave's valid column with the
        out-of-domain and the cold-tier rows cleared — None where no
        row is —, cold mask, the valid rows before it), and the counts
        of ``gubernator_wave_leaky_rows`` and
        ``gubernator_wave_gregorian_rows``.

        Tiered store (tiering.py): cold-resident rows must NOT hit the
        device table (a non-full table would insert them fresh — a
        state fork); they ride the wave invalid.  Mesh-pinned rows
        (mslot >= 0) are never cold: the pin seed pops the cold copy."""
        valid = None
        if wave.ood is not None:
            valid = wave.valid.copy()
            valid[wave.ood] = False
        leaky, greg = wave.leaky, wave.greg or 0
        cold = orig_valid = None
        tier = self.tier
        if tier is not None:
            kh = np.asarray(khash)
            orig_valid = (wave.valid if valid is None else valid) & (kh != 0)
            # inside wave.route: its own ticks at both ends
            timed = phase("tier.premask", self.metrics_ref).begin(
                at=time.perf_counter())
            cold = tier.resident_mask(kh) & orig_valid
            timed.end(at=time.perf_counter())
            if mslot is not None:
                cold &= np.asarray(mslot) < 0
            if cold.any():
                valid = (wave.valid if valid is None else valid) & ~cold
                if leaky:
                    leaky -= int(np.count_nonzero(
                        wave.algorithm[cold] == 1))
                if greg:
                    greg -= int(np.count_nonzero(
                        wave.m32[0][cold] & _GREGORIAN))
        self._count_wave_rows(leaky, greg)
        return valid, cold, orig_valid

    def _device_waves(self, wave: Rows, khash, mslot, valid, pending=None):
        """Yield (idx, slots, lease, mblk) for each device wave of
        ``wave``'s rows ``pending`` (None = all, in arrival order),
        its rows in a leased upload pair.  The ONE fill: a wave joined
        into its lease is there already (`wave.fill` marks the rows
        that ride invalid, nothing more); any other is routed by shard
        (`wave.route`) and scattered.  The caller releases each lease.

        Earliest requests take the earliest device waves: same-key
        requests split across waves then apply in arrival-time order
        (within a wave the device's (row, now) sort handles it)."""
        if pending is None and wave.lease is not None:
            with phase("wave.fill"):
                if valid is not None:
                    wave.valid[:] = valid
            # one shard: the lease is the bucket, every row is its shard's
            self._count_route("identity", wave.lease.a64.shape[1],
                              len(wave), len(wave))
            idx = self._first(len(wave))
            yield idx, idx, wave.lease, wave.mblk
            return
        # with the C++ extension the plan and the fill are ONE pass
        # each that keeps the GIL (ops/_native.cpp › route_plan,
        # route_fill); _build_waves and _fill are the same route in
        # numpy, for a checkout without it
        native = _wire_native is not None
        with phase("wave.route"):
            if pending is None and not wave.monotone:
                pending = np.argsort(wave.now, kind="stable")
            if native:
                plan = _wire_native.route_plan(khash, pending, self.n,
                                               self.wave_buckets)
            else:
                plan = self._build_waves(
                    khash, self._first(len(wave)) if pending is None
                    else pending)
        fill = self._fill_native if native else self._fill
        for idx, slots, bw_w, wcnt in plan:
            with phase("wave.fill"):
                lease, mblk = fill(wave, mslot, valid, idx, slots, bw_w)
            self._count_route("sorted", self.n * bw_w, len(idx), wcnt,
                              native)
            yield idx, slots, lease, mblk

    def _build_waves(self, khash: np.ndarray, pending: np.ndarray):
        """Route ``pending`` request indices into device waves.

        Returns [(idx, slots, bw_w, wcnt)]: original indices, block
        slots, the wave's bucket size and the rows of its densest shard
        (what chose the bucket).  Stable sorts keep request order inside
        a shard (sequential parity for duplicate keys).  Waves split at
        the largest bucket per shard; each wave then rides the smallest
        bucket covering its own densest shard, so a coalesced burst
        takes one big launch and its overflow tail a small one — never
        a second nearly-empty big launch (see wave_buckets)."""
        shard = shard_of(khash[pending], self.n)
        order = np.argsort(shard, kind="stable")
        s_sorted = shard[order]
        starts = np.searchsorted(s_sorted, np.arange(self.n), "left")
        posin = np.arange(len(pending)) - starts[s_sorted]
        Bw = self.wave_buckets[-1]
        wave_id = posin // Bw
        waves = []
        for w in range(int(wave_id.max()) + 1 if len(pending) else 0):
            m = wave_id == w
            idx = pending[order[m]]
            wcnt = int(np.bincount(s_sorted[m], minlength=self.n).max())
            bw_w = next((b for b in self.wave_buckets if wcnt <= b),
                        self.wave_buckets[-1])
            slots = s_sorted[m].astype(np.int64) * bw_w + posin[m] % Bw
            waves.append((idx, slots, bw_w, wcnt))
        return waves

    def _fill(self, wave: Rows, mslot, valid, idx, slots, bw_w):
        """Scatter a device wave's rows out of the joined matrices into
        a LEASED upload pair ([8, n·Bw] i64 + [3, n·Bw] i32 from
        ``wave_pool``), one assignment a matrix.  Returns (lease,
        mblk); the caller releases the lease once the wave's results
        are on the host, on every path.  ``mslot`` (ISSUE 8, fused
        engines only) is the per-request mesh-GLOBAL slot column; it
        rides a plain -1-filled block array (``mblk``), not the lease —
        mesh waves are the GLOBAL minority, pooling them would tax
        every wave.  Padding keeps empty_batch semantics (the pool's)."""
        lease = self.wave_pool.lease(self.n * bw_w)
        lease.a64[:, slots] = wave.m64[:, idx]
        lease.a32[:, slots] = wave.m32[:, idx]
        if valid is not None:
            lease.a32[PACK32.index("valid"), slots] = valid[idx]
        mblk = None
        if mslot is not None:
            mblk = np.full(self.n * bw_w, -1, np.int32)
            mblk[slots] = np.asarray(mslot)[idx]
        return lease, mblk

    def _fill_native(self, wave: Rows, mslot, valid, idx, slots, bw_w):
        """``_fill`` as one C++ pass that writes EVERY cell of the pair
        — the rows, the padding between them, ``mblk`` — so the pool
        clears nothing (``rows=m``: the caller overwrites it all)."""
        m = self.n * bw_w
        lease = self.wave_pool.lease(m, rows=m)
        mblk = None if mslot is None else np.empty(m, np.int32)
        _wire_native.route_fill(wave.m64, wave.m32, valid, mslot, idx,
                                slots, lease.a64, lease.a32, mblk)
        return lease, mblk

    def launch_packed(self, batch: RequestBatch, khash: np.ndarray,
                      now_ms: int, mslot=None):
        """Pipeline phase 1 of check_packed: route and LAUNCH the waves
        without blocking on device results, so the dispatcher can
        overlap the next wave's host work with this one's device time.
        Returns an opaque token for ``sync_packed``; ``drop_packed``
        ends it.  State threads through the launches, so later launches
        are ordered after these device-side regardless of when anyone
        syncs.  ``mslot`` rides the token so the sync-side retry keeps
        the rows' lanes.

        A token's UNANSWERED rows are the ones these launches leave
        without an answer: the rows that err (no slot in their probe
        window: found at sync) and, with a tier bound (tiering.py), the
        rows that are cold-resident NOW — they ride the wave invalid
        and their indices ride the token.  The SYNC side re-dispatches
        both together, once, under the engine lock (``sync_packed``) —
        serving a cold row here would let a promotion that lands
        between launch and sync read a row this lane already consumed.
        Rows outside the step program's value domain
        (``_out_of_domain``) ride invalid too; the sync side marks them
        unservable.

        The token's batch is row views of the wave — of its LEASE where
        the blocks were joined straight into one — so every lease rides
        the token until it is dropped."""
        wave, khash, mslot = self._wave_of(batch, khash, mslot)
        launched = []
        leases = [] if wave.lease is None else [wave.lease]
        try:
            with phase("wave.route"):
                valid, cold, _ = self._ride_invalid(wave, khash, mslot)
            for idx, slots, lease, mblk in self._device_waves(
                    wave, khash, mslot, valid):
                # the lease rides the token until it is dropped: the
                # launch is asynchronous, and the runtime may still be
                # reading the host operands (the CPU backend aliases
                # them outright) — a pooled buffer handed to the next
                # wave before the results are here is a data race
                leases.append(lease)
                # positional mblk only when a mesh lane exists: tests
                # and profilers wrap _launch_arrays with the classic
                # 3-arg signature
                packed, counters = (
                    self._launch_arrays(lease.a64, lease.a32, now_ms)
                    if mblk is None
                    else self._launch_arrays(lease.a64, lease.a32, now_ms,
                                             mblk))
                launched.append((idx, slots, packed, counters, lease))
        except BaseException:
            for lease in leases:
                lease.release()
            raise
        cold_idx = (np.nonzero(cold)[0]
                    if cold is not None and cold.any() else None)
        return (wave.batch, khash, now_ms, launched, mslot, cold_idx,
                wave.ood)

    def _count_route(self, route: str, slots: int, rows: int,
                     densest: int, native: bool = False) -> None:
        """``gubernator_wave_route_total{route}``: one device wave that
        was joined straight into its lease ("identity") or routed by
        shard and scattered ("sorted") — and what it cost: the slots it
        uploads (``gubernator_wave_slots_total``: the lease's width,
        padding included), the rows it carries
        (``gubernator_wave_routed_rows_total``) and the rows of its
        densest shard (``gubernator_wave_densest_shard_rows_total``:
        what chose its bucket).  Plain integers the route already
        holds.  ``native``: the C++ pass planned and filled it
        (``gubernator_wave_native_route_total``)."""
        m = self.metrics_ref
        if m is not None:
            m.wave_route.labels(route=route).inc()
            if native:
                m.wave_native_route.inc()
            m.wave_slots.inc(slots)
            m.wave_routed_rows.inc(rows)
            m.wave_densest_shard_rows.inc(densest)

    def _serve_out_of_domain(self, cols, ood, batch, khash, now_ms,
                             mslot):
        """check_packed's response columns with the out-of-domain rows
        answered: the XLA step masks none."""
        return cols

    def _count_table_full(self, full: np.ndarray) -> None:
        """``gubernator_table_full_rows_total``: the rows one call of
        ``check_packed`` / ``sync_packed`` answers table_full
        (unservable), whatever made them so — counted where the column
        is final, once a row."""
        m = self.metrics_ref
        if m is not None and (n := int(np.count_nonzero(full))):
            m.table_full_rows.inc(n)

    def _count_wave_rows(self, leaky: int, greg: int) -> None:
        """``gubernator_wave_leaky_rows`` and
        ``gubernator_wave_gregorian_rows``: the LEAKY_BUCKET and the
        DURATION_IS_GREGORIAN rows of one wave that go on to the device
        program."""
        m = self.metrics_ref
        if m is not None:
            if leaky:
                m.wave_leaky_rows.inc(leaky)
            if greg:
                m.wave_gregorian_rows.inc(greg)

    def sync_packed(self, token, engine_lock=None) -> tuple:
        """Pipeline phase 2: block on the launched waves and assemble
        the response columns (same contract as check_packed).  The
        token stays alive — ``drop_packed`` ends it.  Reading
        launched outputs needs no lock (state isn't touched).

        The token's unanswered rows — the rows that erred and the rows
        that rode invalid because they were cold-resident at launch
        (``launch_packed``) — are re-dispatched TOGETHER, in arrival
        order, through ONE ``_check_rows``, which mutates state, so it
        runs under ``engine_lock`` when one is given: one premask read
        NOW (a key promoted, or created on the host, since the launch
        goes where it lives now), one launch of the rows the device may
        hold, one ``tier.resolve`` of the rest.  That launch stays even
        with a tier to answer what errs: the next wave was launched
        before this sync and may have INSERTED an erred key into a slot
        freed meanwhile — the re-dispatch lands after it and reads the
        device's truth; creating the key on the host unasked would fork
        its bucket.  How many launches an erred row costs after it
        depends on whether something answers the residue
        (``_check_wave``).  A re-dispatched row applies after any wave
        launched meanwhile — acceptable: unanswered rows never mutated
        state, and the device clamps per-key time monotonically."""
        batch, khash, now_ms, launched, mslot, cold_idx, ood = token
        finished = [self._finish_wave(packed, counters)
                    for _idx, _slots, packed, counters, _lease in launched]
        n = len(khash)
        err_idx: List[int] = []
        with phase("wave.scatter"):
            status = np.zeros(n, np.int32)
            rem_o = np.zeros(n, np.int64)
            rst_o = np.zeros(n, np.int64)
            lim_o = np.zeros(n, np.int64)
            full = np.zeros(n, bool)
            for (idx, slots, *_), (o_st, o_rem, o_rst, o_lim,
                                   o_err) in zip(launched, finished):
                status[idx] = o_st[slots]
                rem_o[idx] = o_rem[slots]
                rst_o[idx] = o_rst[slots]
                lim_o[idx] = o_lim[slots]
                werr = o_err[slots]
                if werr.any():
                    err_idx.extend(idx[werr].tolist())
            if ood is not None:
                # out-of-domain rows rode invalid (never erred, never
                # cold): unservable, the shape a full probe window has
                full[ood] = True
        if cold_idx is not None:
            # disjoint: a cold row rode invalid, so it cannot have erred
            err_idx.extend(cold_idx.tolist())
        if err_idx:
            # the re-dispatch runs _check_rows, phases and all
            ui = np.asarray(sorted(err_idx))
            sub = batch.rows.take(ui).batch
            if cold_idx is not None:
                sub.valid[:] = True  # erred or cold: each was valid
            msub = None if mslot is None else np.asarray(mslot)[ui]
            with (engine_lock if engine_lock is not None
                  else contextlib.nullcontext()):
                r_st, r_lim, r_rem, r_rst, r_full = self._check_rows(
                    sub, khash[ui], now_ms, msub, redispatch=True)
            status[ui] = r_st
            lim_o[ui] = r_lim
            rem_o[ui] = r_rem
            rst_o[ui] = r_rst
            full[ui] = r_full
        self._count_table_full(full)
        return status, lim_o, rem_o, rst_o, full

    def drop_packed(self, token) -> None:
        """The end of a launched token, synced or not: return its
        upload buffers to the pool.  Whoever launched calls it once the
        token's batch is no longer read (it may be views of a lease) —
        on every path.  Idempotent; the device work itself already
        happened — state threads through the launches."""
        for wave in token[3]:
            wave[-1].release()

    def warmup(self, now_ms: int = 1) -> None:
        """Pre-compile every wave-bucket step program (all-invalid rows:
        no state change).  Daemons call this before serving so a first
        coalesced burst never eats a cold compile inside an RPC."""
        for bw in self.wave_buckets:
            self._run_wave(empty_batch(self.n * bw), now_ms)
        if self.tier is not None:
            self.warmup_tier()

    def warmup_tier(self) -> None:
        """The row programs a tier migration pass runs (``_TableImage``),
        at every padded length, on keys no row holds (no state change):
        a pass's first use must not compile inside a served wave.
        Part of ``warmup`` when a tier is bound; a daemon off a TPU,
        which skips ``warmup``, calls it alone."""
        zero = np.zeros(1, np.uint64)
        self.gather_rows(zero)
        self.upsert_rows(zero, {f: np.zeros(1, COLUMN_DTYPES[f])
                                for f in VALUE_COLS})
        self.remove_rows(zero)
        for m in ROW_OP_SIZES:
            self.probe_occupants(np.zeros(m, np.uint64))

    def _launch_arrays(self, a64: np.ndarray, a32: np.ndarray,
                       now_ms: int, mblk=None):
        """Dispatch one packed wave without blocking on its results: 2
        uploads + the step (async on the device stream; state threads
        through, so later launches are ordered after this one
        device-side).  ``mblk`` (mesh-GLOBAL slot block) is a fused-
        engine operand — the classic step has no mesh lane and ignores
        it (only fused engines are ever handed mesh-routed rows).

        On a 1-shard mesh the packed matrices go to the jitted call as
        raw numpy: explicit device_put with a NamedSharding pays
        ~0.5 ms of shard_args machinery per call (measured, CPU) for a
        placement that is identical anyway.  Multi-shard meshes keep
        the explicit sharded put — there it is what makes each device
        receive 1/n of the bytes instead of a full replica."""
        with exec_gate():
            if self.n > 1:
                a64 = jax.device_put(a64, self._mat_sharding)
                a32 = jax.device_put(a32, self._mat_sharding)
            self.state, packed, counters = self._step(
                self.state, a64, a32, np.int64(now_ms))
        return packed, counters

    def _launch_wave(self, glob: RequestBatch, now_ms: int):
        """RequestBatch form of _launch_arrays (warmup, row programs)."""
        return self._launch_arrays(*pack_wave_host(glob), now_ms)

    def _finish_wave(self, packed, counters):
        """Block on a launched wave's outputs (1 download) and fold its
        counters.  Returns (status, remaining, reset, limit, table_full)
        host arrays in [n·Bw] block order."""
        with phase("wave.sync"):
            return self._download_wave(packed, counters)

    def _download_wave(self, packed, counters):
        out = np.asarray(packed)
        self.over_count += int(counters[0])
        created = int(counters[1])
        self.insert_count += created
        m = self.metrics_ref
        if created and m is not None:
            m.wave_created_rows.inc(created)
        return out[0], out[1], out[2], out[3], out[4] != 0

    def _run_wave(self, glob: RequestBatch, now_ms: int):
        """One device launch over the packed wire layout: 2 uploads, the
        step, 1 download.  Returns (status, remaining, reset, limit,
        table_full) host arrays in [n·B] block order."""
        return self._finish_wave(*self._launch_wave(glob, now_ms))

    # ---- fused wire lane (ops/_native.cpp › pack_wire_wave) ------------

    def prepack_wire(self, data: bytes, now_ms: int, excluded: int = 0):
        """Fused C++ wire ingest: one pass from request wire bytes to
        the call's ``Rows`` — parse, validate, clamp (bit-identical to
        pack_columns), key-hash (mixed, zero-remapped), lay out in a
        right-sized pair and derive what ``lay_out`` would, with zero
        intermediate numpy columns and the GIL kept throughout.

        A DURATION_IS_GREGORIAN row's period end is the pass's own
        arithmetic too (``_native.cpp › Period``, gregorian.py's twin).

        Any shard count: the block is rows in the call's own order, and
        the dispatch worker's route (``_device_waves``) is the one place
        that knows about shards.  None — the caller takes the classic
        parse → pack_columns path — for what the C++ lane can't model
        (pb2 framing, a calendar row of an invalid ordinal or a clock
        outside the calendar — the classic lane builds that row's
        error —, n over the largest bucket: the classic path splits)
        and for a call with a row that carries one of the caller's
        ``excluded`` Behavior bits: the pre-pass stops at the first
        such row, before the pair exists."""
        if _wire_native is None:
            return None
        cnt = _wire_native.count_req_items(data, excluded)
        if not cnt or cnt > self.wave_capacity:
            return None  # oversize: classic path splits into waves
        rows = Rows.empty(cnt)
        res = _wire_native.pack_wire_wave(data, now_ms, rows.m64, rows.m32,
                                          self.value_domain)
        if res is None:
            return None
        n, khash, _behavior_or, tlv_off, tlv_len, name_hash, derived = res
        (rows.ood, rows.leaky, rows.greg, rows.now_lo, rows.now_hi,
         rows.monotone) = derived
        return PrepackedWave(rows, n, khash, tlv_off, tlv_len, name_hash)

    def check_batch(self, reqs: Sequence[RateLimitRequest], now_ms: int
                    ) -> List[RateLimitResponse]:
        """Object-lane entry: pack, run the columnar path, assemble
        RateLimitResponse objects.  One wave/retry/auto-grow code path
        for both lanes (check_packed is the single implementation)."""
        from ..hashing import hash_request_keys

        khash = hash_request_keys([r.name for r in reqs],
                                  [r.unique_key for r in reqs])
        batch, errs = pack_requests(reqs, now_ms, size=len(reqs),
                                    key_hashes=khash)
        cols = self.check_packed(batch, khash, now_ms)
        return responses_from_columns(cols, errs)

    def check_packed(self, batch: RequestBatch, khash: np.ndarray,
                     now_ms: int, mslot=None) -> tuple:
        """Columnar twin of ``check_batch``: full-length numpy columns in,
        response columns out — no per-request Python objects (the C++
        wire-ingest lane).  Returns (status i32[n], limit i64[n],
        remaining i64[n], reset_time i64[n], table_full bool[n]).

        Invalid rows (batch.valid False) come back zeroed; the caller
        owns their error strings.  Same wave routing, duplicate-order,
        and retry semantics as check_batch.  ``mslot`` (ISSUE 8):
        per-request mesh-GLOBAL replica slot, -1 for sharded rows —
        only fused engines receive it (instance.py gates on
        ``engine.mesh_bound``).
        """
        cols = self._check_rows(batch, khash, now_ms, mslot)
        self._count_table_full(cols[4])
        return cols

    def _check_rows(self, batch: RequestBatch, khash: np.ndarray,
                    now_ms: int, mslot, redispatch: bool = False) -> tuple:
        """``check_packed`` without the count of its table_full rows:
        what ``sync_packed`` re-dispatches a token's unanswered rows
        through (``redispatch``), and counts with the rest of its
        wave."""
        wave, khash, mslot = self._wave_of(batch, khash, mslot)
        try:
            return self._check_wave(wave, khash, now_ms, mslot, redispatch)
        finally:
            # a lease joined HERE goes back now that nothing reads the
            # wave's rows (views of it); one the caller joined (the
            # dispatcher, who reads the batch afterwards) is the
            # caller's to release
            if (wave.lease is not None
                    and wave is not getattr(batch, "rows", None)):
                wave.lease.release()

    def _check_wave(self, wave: Rows, khash, now_ms: int, mslot,
                    redispatch: bool = False) -> tuple:
        """Answer every row of ``wave``, blocking: launch the rows the
        device may hold, launch the rows that err once more — a row
        that lost every claim round finds its slot alone —, then grow
        the table if that is allowed, and give what still errs to the
        tier (find-or-create on the host) or, with none bound, answer
        it table_full.

        ``redispatch``: the rows are a synced token's unanswered rows
        (``sync_packed``), launched once already.  With a tier bound
        that launch was the first try and this wave's one launch IS the
        retry: nothing can launch between it and ``tier.resolve`` under
        the engine lock, so a second would re-read a device that cannot
        have changed; and a re-dispatch none of whose rows would ride
        valid (its rows are all cold NOW) launches nothing — the
        columns stay the zeros they start as and the tier serves them.
        With no tier a re-dispatch retries as any wave does: there the
        second launch is the difference between an answer and a
        table_full error."""
        n = len(khash)
        status = np.zeros(n, np.int32)
        rem_o = np.zeros(n, np.int64)
        rst_o = np.zeros(n, np.int64)
        lim_o = np.zeros(n, np.int64)
        full = np.zeros(n, bool)
        with phase("wave.route"):
            valid, cold_mask, orig_valid = self._ride_invalid(wave, khash,
                                                              mslot)
        retried = redispatch and self.tier is not None
        pending = None  # every row, in arrival order
        if retried and not (wave.valid if valid is None else valid).any():
            pending = ()  # every row is cold NOW: nothing to launch
        while pending is None or len(pending):
            err_idx: List[int] = []
            for idx, slots, lease, mblk in self._device_waves(
                    wave, khash, mslot, valid, pending):
                try:
                    # see launch_packed: 3-arg call when no mesh lane
                    launched = (
                        self._launch_arrays(lease.a64, lease.a32, now_ms)
                        if mblk is None
                        else self._launch_arrays(lease.a64, lease.a32,
                                                 now_ms, mblk))
                    o_st, o_rem, o_rst, o_lim, o_err = self._finish_wave(
                        *launched)
                finally:
                    if lease is not wave.lease:
                        lease.release()  # results are here
                with phase("wave.scatter"):
                    status[idx] = o_st[slots]
                    rem_o[idx] = o_rem[slots]
                    rst_o[idx] = o_rst[slots]
                    lim_o[idx] = o_lim[slots]
                    werr = o_err[slots]
                    if werr.any():
                        err_idx.extend(idx[werr].tolist())
            if err_idx and not retried:
                # a row that lost every claim round to the wave's other
                # inserts finds its slot alone: launch those once more.
                # A window that IS full is not helped, and no wave
                # sweeps the table for it (see ``sweep_wanted``)
                retried = True
                pending = np.asarray(sorted(err_idx))
            elif err_idx and self._try_auto_grow([False]):
                pending = np.asarray(sorted(err_idx))
            else:
                if err_idx:
                    self.sweep_wanted = True
                full[err_idx] = True
                for i in err_idx:
                    status[i] = 0
                    rem_o[i] = 0
                    rst_o[i] = 0
                    lim_o[i] = 0
                pending = np.empty(0, np.int64)
        cols = (status, lim_o, rem_o, rst_o, full)
        batch = wave.batch
        tier = self.tier
        # the cold lane's host work is scatter time, not worker.gap
        with (phase("wave.scatter") if tier is not None
              else contextlib.nullcontext()):
            if tier is not None:
                # cold lane: pre-masked cold-resident rows plus residual
                # table-full rows (brand-new keys, device table
                # saturated — the tier turns table-full into
                # find-or-create on host)
                cols = tier.resolve(self, batch, khash, now_ms, cols,
                                    cold_mask, orig_valid, mslot=mslot)
            return self._serve_out_of_domain(cols, wave.ood, batch, khash,
                                             now_ms, mslot)

    def _try_auto_grow(self, grew: list) -> bool:
        """Grow 2× (once per wave) if under auto_grow_limit.  Returns
        True when the caller should retry at the larger capacity."""
        if not self.auto_grow_limit \
                or self.cap_local * 2 > self.auto_grow_limit:
            return False
        if not grew[0]:
            dropped = self.grow(self.cap_local * 2)
            if dropped:
                # a dropped row is a silent counter reset — allowed by
                # the LRU-eviction contract, never allowed to be quiet
                log.warning("auto-grow to %d/shard dropped %d live rows "
                            "(probe-window exhaustion)",
                            self.cap_local, dropped)
            grew[0] = True
        return True

    def grow(self, new_cap_per_shard: int) -> int:
        """Re-place all live rows into a [new_cap_per_shard] table on
        device (see make_grow).  Returns the dropped-row count (non-zero
        only when shrinking into high occupancy).  Subsequent step/row
        programs recompile automatically for the new shape."""
        if new_cap_per_shard & (new_cap_per_shard - 1) \
                or new_cap_per_shard <= 0:
            raise ValueError(
                f"capacity must be a power of two, got {new_cap_per_shard}")
        fn = self._grow_fns.get(new_cap_per_shard)
        if fn is None:
            fn = make_grow(self.mesh, new_cap_per_shard)
            self._grow_fns[new_cap_per_shard] = fn
        with XLA_EXEC_MU:
            self.state, dropped = fn(self.state)
        self.cap_local = new_cap_per_shard
        self.dropped_rows += int(dropped)
        return int(dropped)

    # ---- row-level access (GLOBAL replication + Store hooks) -----------

    def _route_waves(self, khash: np.ndarray):
        """Yield (indices, block_slots) waves: each wave maps ≤B keys per
        shard into the [n·B] block layout."""
        shard = shard_of(khash, self.n)
        pending = list(range(len(khash)))
        while pending:
            fill = [0] * self.n
            wave, rest, slots = [], [], []
            for i in pending:
                s = int(shard[i])
                if fill[s] < self.B:
                    slots.append(s * self.B + fill[s])
                    fill[s] += 1
                    wave.append(i)
                else:
                    rest.append(i)
            yield wave, slots
            pending = rest

    def gather_rows(self, khash: np.ndarray) -> tuple[np.ndarray, dict]:
        """(found mask, value-column dict) for the given key hashes."""
        if self._gather is None:
            self._gather = make_gather_rows(self.mesh)
        m = len(khash)
        found = np.zeros(m, bool)
        out = {f: np.zeros(m, COLUMN_DTYPES[f]) for f in VALUE_COLS}
        for wave, slots in self._route_waves(khash):
            keys = np.zeros(self.n * self.B, np.uint64)
            keys[slots] = khash[wave]
            with XLA_EXEC_MU:
                f, cols = self._gather(
                    self.state,
                    jax.device_put(keys, self._batch_sharding))
            f = np.asarray(f)
            found[wave] = f[slots]
            for name, col in zip(VALUE_COLS, cols):
                out[name][wave] = np.asarray(col)[slots]
        return found, out

    def upsert_rows(self, khash: np.ndarray, cols: dict) -> int:
        """Find-or-insert rows and overwrite their state; returns the
        number of rows placed (others dropped: shard probe window full)."""
        if self._upsert is None:
            self._upsert = make_upsert_rows(self.mesh)
        placed_total = 0
        for wave, slots in self._route_waves(khash):
            keys = np.zeros(self.n * self.B, np.uint64)
            keys[slots] = khash[wave]
            block_cols = []
            for f in VALUE_COLS:
                dt = np.asarray(cols[f]).dtype
                blk = np.zeros(self.n * self.B, dt)
                blk[slots] = cols[f][wave]
                block_cols.append(jax.device_put(blk, self._batch_sharding))
            with XLA_EXEC_MU:
                self.state, placed = self._upsert(
                    self.state,
                    jax.device_put(keys, self._batch_sharding),
                    tuple(block_cols))
            placed_total += int(np.asarray(placed)[slots].sum())
        return placed_total

    def remove_rows(self, khash: np.ndarray) -> int:
        """Delete rows by key hash (Cache.Remove analog); returns the
        number of rows actually removed."""
        if self._remove is None:
            self._remove = make_remove_rows(self.mesh)
        removed = 0
        for wave, slots in self._route_waves(khash):
            keys = np.zeros(self.n * self.B, np.uint64)
            keys[slots] = khash[wave]
            with XLA_EXEC_MU:
                self.state, found = self._remove(
                    self.state,
                    jax.device_put(keys, self._batch_sharding))
            removed += int(np.asarray(found)[slots].sum())
        return removed

    def occupancy(self) -> int:
        """Live (non-empty) rows right now — health/metrics surface."""
        from ..core.table import occupancy

        # under XLA_EXEC_MU: an eager device reduction; health checks
        # and the memory-ledger probes call this from their own threads
        # while other in-process engines serve (see mesh.py)
        with XLA_EXEC_MU:
            return int(occupancy(self.state))

    def occupancy_nowait(self) -> int | None:
        """Non-blocking occupancy for tick-cadence samplers (the memory
        ledger): None when the device gate is contended.  A sampler
        holding the engine lock must never WAIT on XLA_EXEC_MU — in
        multi-engine processes that convoys every serving wave behind
        another engine's in-flight program; the caller reuses its last
        sample instead."""
        if not XLA_EXEC_MU.acquire(blocking=False):
            return None
        try:
            from ..core.table import occupancy

            return int(occupancy(self.state))
        finally:
            XLA_EXEC_MU.release()

    def probe_occupants(self, khash: np.ndarray) -> np.ndarray:
        """u64[k, PROBES]: the resident key hashes in the probe window
        of each key of ``khash`` (0 = free slot), in ONE device gather
        at a padded length (``padded``) —
        the tier controller's eviction candidate read: any of a key's
        occupants, once demoted, frees a slot that key itself can take
        (same probe formula as the device kernel, core/step.py ›
        _probe_slots)."""
        from ..core.step import PROBES

        k = padded(np.asarray(khash, np.uint64))
        stride = (k >> np.uint64(17)) | np.uint64(1)
        local = ((k[:, None] + np.arange(PROBES, dtype=np.uint64)
                  * stride[:, None]) & np.uint64(self.cap_local - 1))
        shard = shard_of(k, self.n).astype(np.int64)
        slots = (shard[:, None] * self.cap_local
                 + local.astype(np.int64)).reshape(-1)
        with XLA_EXEC_MU:
            keys = np.asarray(take_rows(self.state.key, jnp.asarray(slots)))
        return keys.view(np.uint64).reshape(len(k), PROBES)[:len(khash)]

    def tier_image(self, khash: np.ndarray) -> "_TableImage":
        """What a migration pass of the tier works on (tiering.py ›
        TierController.migrate) — on this engine the table itself,
        through its batched row programs."""
        return _TableImage(self, np.asarray(khash, np.uint64))

    def each(self):
        """Iterate live rows as store.CacheItem objects (Cache.Each
        analog) — a host-side snapshot walk, for admin/debug tooling."""
        from ..store import items_from_arrays

        yield from items_from_arrays(self.snapshot())

    # ---- checkpoint/resume (store.py › Loader array fast path) ---------

    def snapshot(self) -> dict:
        """Device table → host column dict of live rows (Loader.save
        input).  The analog of the reference's cache.Each() drain at
        shutdown (store.go › Loader — reconstructed)."""
        from ..store import table_to_arrays

        return table_to_arrays(self.state)

    def restore(self, arrays: dict) -> int:
        """Insert snapshot rows into the (fresh) sharded table.

        Host-side cold path: routes each row to its owner shard, places
        it at its first free probe slot (same probe sequence as the
        device kernel), then uploads the table once.  Returns rows
        restored; rows that don't fit (capacity shrank) are dropped with
        a count, mirroring the reference's best-effort Loader.Load.

        The table is the one a row-at-a-time walk in row order builds
        (``tests/test_restore_place.py`` keeps that walk): a row takes
        the first slot of its window that is free, holds its own key,
        or is held by a LATER row — placed in numpy rounds over the
        rows still moving, see ``_place_rows``.  A key that comes twice
        keeps its first row's slot and its last row's values; rows of
        key 0 (the empty slot's mark) are no rows and are skipped.
        """
        # the table's word columns, writable on the host; the
        # snapshot's 64-bit columns are read as words through views
        host = jax.tree.map(np.array, self.state)
        keys = np.ascontiguousarray(arrays["key"], dtype=np.uint64)
        rows_of = from_host({**arrays, "key": keys})
        with phase("restore.place", self.metrics_ref):
            slot_of = self._place_rows(
                column_to_host(host.key, np.uint64), keys)
            fit = slot_of >= 0
            # in row order, so a key's last row writes last; every row
            # fits as a rule, and then no column is copied to be masked
            rows = slice(None) if fit.all() else fit
            slots = slot_of[rows]
            for to, frm in zip(jax.tree.leaves(host),
                               jax.tree.leaves(rows_of)):
                to[slots] = frm[rows]
        placed = int(np.count_nonzero(fit))
        lost = np.flatnonzero(~fit & (keys != 0))
        if len(lost) and self.tier is not None:
            # tiered restore: rows the device table can't hold land in
            # the cold tier instead of being dropped — the snapshot
            # round-trip keeps every row in exactly one tier
            adopted = self.tier.adopt_rows(arrays, lost)
            placed += adopted
            lost = lost[adopted:]
        if self.metrics_ref is not None:
            self.metrics_ref.restore_unplaced_rows.set(len(lost))
        self.state = jax.device_put(host, table_sharding(self.mesh))
        if jax.default_backend() == "cpu":
            # device_put of an aligned host column is zero-copy on this
            # image's XLA:CPU without pinning the numpy owner — once
            # `host` dies the allocator reuses the table's backing
            # memory and live rows turn into heap garbage (state lost
            # across restart, and worse: ~1.6k phantom rows evicting
            # real ones).  Pin the columns for the engine's lifetime;
            # the donated step keeps writing the state into these same
            # buffers, so the cost is one table copy (~cap×68 bytes),
            # not a leak per wave.  Other backends copy to the device.
            self._restore_host_pin = host
        return placed

    def _place_rows(self, table_key: np.ndarray, keys: np.ndarray
                    ) -> np.ndarray:
        """Slot of every row (-1: its window is full, or its key is 0)
        over the host copy of the key column — first-free placement in
        row order, without walking the rows.

        Row i's slot is the first of its PROBES slots that no EARLIER
        row's key takes (a slot the table already holds counts as
        taken, unless it holds row i's own key).  That recursion has
        one fixed point, and rounds reach it: every key still moving
        claims the slot at its own depth; the earliest row wins a slot,
        also from a later row that sat there, and whoever loses goes
        one slot on.  A slot's holder only ever gets earlier, so a key
        never passes a slot it would have kept.  A round is one numpy
        pass over the keys still moving: at load 0.15 a tenth of them
        go on each time."""
        from ..core.step import PROBES

        n = len(keys)
        # one contender a distinct key, in the order of its first row
        order = np.argsort(keys, kind="stable")
        sk = keys[order]
        head = np.ones(n, bool)
        head[1:] = sk[1:] != sk[:-1]
        first = order[head]  # first row of each distinct key
        ids = np.sort(first[sk[head] != 0])
        k = keys[ids]
        base = (shard_of(k, self.n).astype(np.int64)
                * np.int64(self.cap_local))
        stride = (k >> np.uint64(17)) | np.uint64(1)
        depth = np.zeros(len(ids), np.uint64)
        where = np.full(len(ids), -1, np.int64)
        #: the contender (index into ``ids``: earlier row, lower index)
        #: that holds each slot; the table's own rows hold theirs
        #: before any (-1), ``free`` after all
        free = len(ids)
        holder = np.where(table_key != 0, np.int64(-1), np.int64(free))
        moving = np.arange(free)
        while len(moving):
            slot = base[moving] + (
                (k[moving] + depth[moving] * stride[moving])
                & np.uint64(self.cap_local - 1)).astype(np.int64)
            found = table_key[slot] == k[moving]  # the table holds it
            where[moving[found]] = slot[found]
            claims = ~found & (holder[slot] > moving)
            c_who, c_slot = moving[claims], slot[claims]
            ousted = holder[c_slot]
            # ascending, written backwards: a slot's earliest stays
            holder[c_slot[::-1]] = c_who[::-1]
            won = holder[c_slot] == c_who
            where[c_who[won]] = c_slot[won]
            ousted = np.unique(ousted[ousted < free])
            where[ousted] = -1
            moving = np.sort(np.concatenate(
                [moving[where[moving] < 0], ousted]))
            depth[moving] += np.uint64(1)
            moving = moving[depth[moving] < PROBES]
        # a row's slot is its key's: the slot of the key's first row
        by_first = np.full(n, -1, np.int64)
        by_first[ids] = where
        out = np.empty(n, np.int64)
        out[order] = by_first[first[np.cumsum(head) - 1]]
        return out
