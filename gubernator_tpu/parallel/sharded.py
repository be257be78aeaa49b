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

import logging
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..hashing import shard_of
from ..types import RateLimitRequest, RateLimitResponse, Status
from ..core.batch import (RequestBatch, WaveBufferPool, empty_batch,
                          pack_requests)
from ..core.step import decide_batch_impl, _insert, _lookup, _probe_slots
from ..core.table import TableState, init_table
from ..tracing import phase
from .mesh import (SHARD_AXIS, XLA_EXEC_MU, exec_gate, make_mesh,
                   shard_table, table_sharding)

log = logging.getLogger("gubernator_tpu.sharded")

try:  # fused C++ wire ingest (ops/_native.cpp); optional
    from ..ops import native as _wire_native
except ImportError:  # pragma: no cover - unbuilt extension
    _wire_native = None

#: TableState value columns addressable by row programs (all but `key`).
VALUE_COLS = tuple(f for f in TableState._fields if f != "key")


class PrepackedWave:
    """One fused-ingest call: a leased packed upload pair with rows
    [0, n) already parsed/clamped/hashed in C++ (pack_wire_wave), plus
    the per-request metadata the serving lanes gate on.  The holder
    owns the lease and must release it on every path (instance.py ›
    _run_fused copies the rows out and releases it)."""

    __slots__ = ("lease", "n", "khash", "behavior_or", "tlv_off",
                 "tlv_len", "name_hash")

    def __init__(self, lease, n, khash, behavior_or, tlv_off, tlv_len,
                 name_hash):
        self.lease = lease
        self.n = n
        self.khash = khash
        self.behavior_or = behavior_or
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
        slots = _probe_slots(keys, state.key.shape[0])
        row, _ = _lookup(state.key, slots, keys)
        found = (keys != 0) & (row >= 0)
        cols = tuple(
            getattr(state, f).at[jnp.where(found, row, 0)].get()
            for f in VALUE_COLS)
        return found, cols

    return jax.jit(shard_map(
        _gather, mesh=mesh, in_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
        out_specs=P(SHARD_AXIS)))


def make_remove_rows(mesh):
    """jit program: probe-lookup a [n·B] key block and clear matched
    rows (key + expire → 0).  The Cache.Remove analog (cache.go) —
    used by the Store-backed admin path."""

    def _remove(state, keys):
        slots = _probe_slots(keys, state.key.shape[0])
        row, _ = _lookup(state.key, slots, keys)
        found = (keys != 0) & (row >= 0)
        wrow = jnp.where(found, row, state.key.shape[0])
        return state._replace(
            key=state.key.at[wrow].set(jnp.uint64(0), mode="drop"),
            expire_at=state.expire_at.at[wrow].set(jnp.int64(0),
                                                   mode="drop"),
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
        cap = state.key.shape[0]
        valid = keys != 0
        slots = _probe_slots(keys, cap)
        tkey, row, _ = _insert(state.key, slots, keys, valid,
                               jnp.full(keys.shape, -1, jnp.int32))
        placed = valid & (row >= 0)
        wrow = jnp.where(placed, row, cap)
        new = {"key": tkey}
        for f, col in zip(VALUE_COLS, cols):
            new[f] = getattr(state, f).at[wrow].set(col, mode="drop")
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
        cap_old = state.key.shape[0]
        key = state.key
        valid = key != 0
        slots = _probe_slots(key, cap_new)
        tkey, row, _ = _insert(jnp.zeros(cap_new, jnp.uint64), slots, key,
                               valid, jnp.full(cap_old, -1, jnp.int32))
        placed = valid & (row >= 0)
        wrow = jnp.where(placed, row, cap_new)
        # init_table is shard_map-safe (no device placement; its guards
        # are host-side trace-time checks) and the single source of
        # truth for column defaults
        fresh = init_table(cap_new)
        new = {"key": tkey}
        for f in VALUE_COLS:
            new[f] = getattr(fresh, f).at[wrow].set(getattr(state, f),
                                                    mode="drop")
        dropped = lax.psum((valid & (~placed)).sum(dtype=jnp.int64),
                           SHARD_AXIS)
        return TableState(**new), dropped

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
            # probe window exhausted by LIVE keys even after the sweep
            # retry (and auto-grow, if enabled) inside check_packed
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


#: Packed-transfer wire layout for the serving step: every RequestBatch
#: int64 column rides one [8, B] int64 upload (key bit-viewed; row 7 is
#: the per-request arrival time), the int32/bool columns one [3, B]
#: int32 upload, and all five outputs one [5, B] int64 download.  A
#: device call then costs 2 uploads + 1 download instead of 10 + 5 —
#: per-transfer latency (PCIe doorbells) dominates these tiny arrays,
#: not bandwidth.
PACK64 = ("key", "hits", "limit", "duration", "eff_ms", "greg_end",
          "burst", "now")
PACK32 = ("behavior", "algorithm", "valid")


def pack_wave_host(b: RequestBatch) -> tuple[np.ndarray, np.ndarray]:
    """RequestBatch of numpy columns → ([8,B] i64, [3,B] i32)."""
    B = len(b.key)
    a64 = np.empty((len(PACK64), B), np.int64)
    a64[0] = np.asarray(b.key).view(np.int64)
    for i, f in enumerate(PACK64[1:], start=1):
        a64[i] = getattr(b, f)
    a32 = np.empty((len(PACK32), B), np.int32)
    a32[0] = b.behavior
    a32[1] = b.algorithm
    a32[2] = b.valid
    return a64, a32


def make_sharded_step_packed(mesh, donate: bool = False):
    """The serving twin of make_sharded_step over the packed wire layout
    (see PACK64/PACK32): (state, a64, a32, now) → (state, [5,B] i64
    outputs, (over, insert) counters)."""
    S = SHARD_AXIS

    def _step(state, a64, a32, now):
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
        _step, mesh=mesh,
        in_specs=(P(S), P(None, S), P(None, S), P()),
        out_specs=(P(S), P(None, S), P()),
    )
    return jax.jit(sharded, donate_argnums=(0,) if donate else ())


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
        #: warmup() pre-compiles them all.
        import os as _os
        env_buckets = _os.environ.get("GUBER_WAVE_BUCKETS", "")
        if wave_buckets:
            self.wave_buckets = tuple(sorted(set(wave_buckets)))
        elif env_buckets:
            self.wave_buckets = tuple(sorted(
                {int(x) for x in env_buckets.split(",") if x.strip()}))
        else:
            self.wave_buckets = (batch_per_shard, batch_per_shard * 8)
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
        self.live_rows = -1  # set by the fused Pallas sweep
        self._gather = None  # lazily-built row programs
        self._upsert = None
        self._remove = None
        self._pallas_sweep_fn = None
        self._grow_fns: dict = {}  # cap_new → compiled grow program
        self.dropped_rows = 0  # rows lost to grow/restore re-placement
        #: reusable packed-upload matrices, one ring per wave width
        #: (core/batch.py): leased in _fill_packed, released right
        #: after the launch consumes them (jax copies host operands at
        #: dispatch).  V1Instance binds its Metrics here for the
        #: hit/miss/leak counters.
        self.wave_pool = WaveBufferPool()
        #: bound TierController (tiering.py) when GUBER_TIER_COLD=1 —
        #: check_packed pre-masks cold-resident rows out of the device
        #: wave and serves them (plus residual table-full rows) from
        #: the host cold tier on the way out
        self.tier = None  # lock-free: set once at instance wiring, read-only after

    def _init_table_and_step(self) -> None:
        """Build self.state + self._step (subclass hook: the Pallas
        serving engine swaps in its bucketized table + kernel step).

        The serving step aliases the table in/out by default
        (GUBER_STEP_DONATE=0 opts out): clean-step cold columns pass
        through copy-free and row scatters update in place (see
        core/step.py › decide_batch_donated).  Measured on a real v5e
        (tools/tpu_session.py, 2026-07-31): donate 0.573 ms/step vs
        copy 209 ms at CAP 2^21 — non-donated scatters serialize on
        TPU — and donate also wins 6.3× on CPU (PERF.md §5)."""
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
        """shard_map'd fused sweep: per-shard Pallas pass + psum'd live
        count.  Interpret mode off-TPU (Mosaic kernels are TPU-only)."""
        if self._pallas_sweep_fn is None:
            from ..ops.pallas_sweep import sweep_expired_pallas

            interpret = jax.default_backend() != "tpu"

            def _one(state, now):
                st, live = sweep_expired_pallas(state, now,
                                                interpret=interpret)
                return st, lax.psum(live, SHARD_AXIS)

            # check_vma=False: pallas_call's out_shape carries no
            # varying-mesh-axes annotation
            self._pallas_sweep_fn = jax.jit(shard_map(
                _one, mesh=self.mesh, in_specs=(P(SHARD_AXIS), P()),
                out_specs=(P(SHARD_AXIS), P()), check_vma=False))
        with XLA_EXEC_MU:
            return self._pallas_sweep_fn(self.state,
                                         jnp.asarray(now_ms, jnp.int64))

    @staticmethod
    def _arrival_order(batch: RequestBatch) -> np.ndarray:
        """Request indices in arrival-time order (earliest requests
        take the earliest waves, so same-key requests split across
        waves apply in time order).  The common serving shape — a wave
        whose ``now`` column is already non-decreasing (one caller, or
        dispatcher-merged jobs queued in clock order) — skips the
        argsort: an O(n) monotonicity check replaces the O(n log n)
        sort on the per-wave host path."""
        now_col = np.asarray(batch.now)
        n = len(now_col)
        if n <= 1 or (now_col[1:] >= now_col[:-1]).all():
            return np.arange(n, dtype=np.int64)
        return np.argsort(now_col, kind="stable")

    def _build_waves(self, khash: np.ndarray, pending: np.ndarray):
        """Route ``pending`` request indices into device waves.

        Returns [(idx, slots, bw_w)]: original indices, block slots, and
        the wave's bucket size.  Stable sorts keep request order inside
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
            waves.append((idx, slots, bw_w))
        return waves

    def _fill_packed(self, batch: RequestBatch, idx, slots, bw_w,
                     mslot=None):
        """Scatter a wave's requests straight into a LEASED pair of
        packed wire matrices (one [8, n·Bw] i64 + one [3, n·Bw] i32
        from ``wave_pool``): fuses the old glob-fill + pack_wave_host
        into a single set of writes, without the per-wave allocation
        the old path paid (at a fast device step — TPU: ~0.2 ms — the
        host-side copies and allocator churn ARE the serving ceiling).
        Returns (a64, a32, lease, mblk); the caller must
        ``lease.release()`` once the launch has consumed the buffers,
        on every path.  ``mslot`` (ISSUE 8, fused engines only) is the
        per-request mesh-GLOBAL slot column; it rides a plain -1-filled
        block array (``mblk``), not the lease — mesh waves are the
        GLOBAL minority, pooling them would tax every wave.
        Padding rows keep empty_batch semantics: zeros everywhere,
        eff_ms 1, valid false."""
        lease = self.wave_pool.lease(self.n * bw_w)
        a64, a32 = lease.a64, lease.a32
        a64[PACK64.index("eff_ms")] = 1
        a64[0][slots] = np.asarray(batch.key).view(np.int64)[idx]
        for i, f in enumerate(PACK64[1:], start=1):
            a64[i][slots] = np.asarray(getattr(batch, f))[idx]
        for i, f in enumerate(PACK32):
            a32[i][slots] = np.asarray(getattr(batch, f))[idx]
        mblk = None
        if mslot is not None:
            mblk = np.full(self.n * bw_w, -1, np.int32)
            mblk[slots] = np.asarray(mslot)[idx]
        return a64, a32, lease, mblk

    def launch_packed(self, batch: RequestBatch, khash: np.ndarray,
                      now_ms: int, mslot=None):
        """Pipeline phase 1 of check_packed: route and LAUNCH the waves
        without blocking on device results, so the dispatcher can
        overlap the next wave's host work with this one's device time.
        Returns an opaque token for ``sync_packed``.  State threads
        through the launches, so later launches are ordered after these
        device-side regardless of when anyone syncs.  ``mslot`` rides
        the token so the sync-side retry keeps the rows' lanes.

        Cold-tier rows (tiering.py) ride the wave invalid and their
        indices ride the token: the SYNC side re-dispatches them
        through check_packed under the engine lock — serving them here
        would let a promotion that lands between launch and sync read
        a row this lane already consumed.  Rows outside the step
        program's value domain (``_mask_out_of_domain``) ride invalid
        too; the sync side marks them unservable."""
        with phase("wave.route"):
            batch, ood, leaky = self._mask_out_of_domain(batch, mslot)
            tier = self.tier
            cold_idx = None
            if tier is not None:
                kh = np.asarray(khash)
                ov = np.asarray(batch.valid) & (kh != 0)
                cm = tier.resident_mask(kh) & ov
                if mslot is not None:
                    cm &= np.asarray(mslot) < 0
                if cm.any():
                    cold_idx = np.nonzero(cm)[0]
                    batch = batch._replace(
                        valid=np.asarray(batch.valid) & ~cm)
            if leaky is not None:
                self._count_leaky_rows(
                    np.count_nonzero(leaky & np.asarray(batch.valid)))
            waves = self._build_waves(khash, self._arrival_order(batch))
        launched, leases = [], []
        try:
            for idx, slots, bw_w in waves:
                with phase("wave.fill"):
                    a64, a32, lease, mblk = self._fill_packed(
                        batch, idx, slots, bw_w, mslot)
                # the lease rides the token until sync_packed has the
                # wave's results: the launch is asynchronous, and the
                # runtime may still be reading the host operands (the
                # CPU backend aliases them outright) — a pooled buffer
                # handed to the next wave before that is a data race
                leases.append(lease)
                # positional mblk only when a mesh lane exists: tests
                # and profilers wrap _launch_arrays with the classic
                # 3-arg signature
                packed, counters = (
                    self._launch_arrays(a64, a32, now_ms) if mblk is None
                    else self._launch_arrays(a64, a32, now_ms, mblk))
                launched.append((idx, slots, packed, counters, lease))
        except BaseException:
            for lease in leases:
                lease.release()
            raise
        return (batch, khash, now_ms, launched, mslot, cold_idx, ood)

    def _mask_out_of_domain(self, batch: RequestBatch, mslot=None):
        """(batch with the rows this engine's step program cannot
        represent made invalid, their indices or None, the wave's
        ``algorithm == LEAKY_BUCKET`` column or None where no row is
        leaky).  The XLA step has the full int64 domain: nothing to
        mask."""
        alg = np.asarray(batch.algorithm)
        return batch, None, alg == 1 if alg.any() else None

    def _serve_out_of_domain(self, cols, ood, batch, khash, now_ms,
                             mslot):
        """check_packed's response columns with the out-of-domain rows
        answered: the XLA step masks none."""
        return cols

    def _count_leaky_rows(self, n: int) -> None:
        """``gubernator_wave_leaky_rows``: the LEAKY_BUCKET rows of one
        wave that go on to the device program."""
        m = self.metrics_ref
        if n and m is not None:
            m.wave_leaky_rows.inc(n)

    def sync_packed(self, token, engine_lock=None) -> tuple:
        """Pipeline phase 2: block on the launched waves and assemble
        the response columns (same contract as check_packed).  Reading
        launched outputs needs no lock (state isn't touched); the
        table-full RETRY path re-enters check_packed, which mutates
        state, so it runs under ``engine_lock`` when one is given.  A
        retried row applies after any wave launched meanwhile —
        acceptable: erred rows never mutated state, retries are the
        table-full corner, and the device clamps per-key time
        monotonically."""
        batch, khash, now_ms, launched, mslot, cold_idx, ood = token
        finished = []
        for _idx, _slots, packed, counters, lease in launched:
            try:
                finished.append(self._finish_wave(packed, counters))
            except BaseException:
                self.drop_packed(token)
                raise
            lease.release()  # results are here: the operands were read
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
        # the re-dispatches below run check_packed, phases and all
        if err_idx:
            import contextlib

            ei = np.asarray(sorted(err_idx))
            sub = type(batch)(*[np.asarray(c)[ei] for c in batch])
            msub = None if mslot is None else np.asarray(mslot)[ei]
            with (engine_lock if engine_lock is not None
                  else contextlib.nullcontext()):
                r_st, r_lim, r_rem, r_rst, r_full = self.check_packed(
                    sub, khash[ei], now_ms, mslot=msub)
            status[ei] = r_st
            lim_o[ei] = r_lim
            rem_o[ei] = r_rem
            rst_o[ei] = r_rst
            full[ei] = r_full
        if cold_idx is not None and len(cold_idx):
            import contextlib

            # cold-tier rows rode the waves invalid (see launch_packed):
            # re-dispatch just them through check_packed, which serves
            # from whichever tier the key is in NOW — exact even when a
            # promotion landed between our launch and this sync
            ci = np.asarray(cold_idx)
            sub = type(batch)(*[np.asarray(c)[ci] for c in batch])
            sub = sub._replace(valid=np.ones(len(ci), bool))
            msub = None if mslot is None else np.asarray(mslot)[ci]
            with (engine_lock if engine_lock is not None
                  else contextlib.nullcontext()):
                c_st, c_lim, c_rem, c_rst, c_full = self.check_packed(
                    sub, khash[ci], now_ms, mslot=msub)
            status[ci] = c_st
            lim_o[ci] = c_lim
            rem_o[ci] = c_rem
            rst_o[ci] = c_rst
            full[ci] = c_full
        return status, lim_o, rem_o, rst_o, full

    def drop_packed(self, token) -> None:
        """Give up a launched token that will never be synced (the
        dispatcher's failure paths): return its upload buffers to the
        pool.  Idempotent; the device work itself already happened —
        state threads through the launches."""
        for wave in token[3]:
            wave[-1].release()

    def warmup(self, now_ms: int = 1) -> None:
        """Pre-compile every wave-bucket step program (all-invalid rows:
        no state change).  Daemons call this before serving so a first
        coalesced burst never eats a cold compile inside an RPC."""
        for bw in self.wave_buckets:
            self._run_wave(empty_batch(self.n * bw), now_ms)

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
        self.insert_count += int(counters[1])
        return out[0], out[1], out[2], out[3], out[4] != 0

    def _run_wave(self, glob: RequestBatch, now_ms: int):
        """One device launch over the packed wire layout: 2 uploads, the
        step, 1 download.  Returns (status, remaining, reset, limit,
        table_full) host arrays in [n·B] block order."""
        return self._finish_wave(*self._launch_wave(glob, now_ms))

    # ---- fused wire lane (ops/_native.cpp › pack_wire_wave) ------------

    def prepack_wire(self, data: bytes, now_ms: int):
        """Fused C++ wire ingest: one pass from request wire bytes to a
        LEASED pair of packed wave-upload matrices — parse, validate,
        clamp (bit-identical to pack_columns), key-hash (mixed,
        zero-remapped) and fill, with zero intermediate numpy columns.

        Single-shard meshes only (block order == request order, so the
        wave needs no shard routing or slot scatter); multi-shard and
        anything the C++ lane can't model (pb2 framing, Gregorian rows,
        n over the largest bucket) returns None and the caller takes
        the classic parse → pack_columns path.

        Returns a PrepackedWave whose lease the caller OWNS: every
        return path must end in ``pre.lease.release()``."""
        if self.n != 1 or _wire_native is None:
            return None
        cnt = _wire_native.count_req_items(data)
        if not cnt:
            return None
        bw = next((b for b in self.wave_buckets if cnt <= b), None)
        if bw is None:
            return None  # oversize: classic path splits into waves
        lease = self.wave_pool.lease(bw)
        res = _wire_native.pack_wire_wave(data, now_ms, lease.a64,
                                          lease.a32)
        if res is None:
            lease.release()
            return None
        return PrepackedWave(lease, *res)

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
        and sweep-retry semantics as check_batch.  ``mslot`` (ISSUE 8):
        per-request mesh-GLOBAL replica slot, -1 for sharded rows —
        only fused engines receive it (instance.py gates on
        ``engine.mesh_bound``).
        """
        n = len(khash)
        status = np.zeros(n, np.int32)
        rem_o = np.zeros(n, np.int64)
        rst_o = np.zeros(n, np.int64)
        lim_o = np.zeros(n, np.int64)
        full = np.zeros(n, bool)
        # tiered store (tiering.py): cold-resident rows must NOT hit
        # the device table (a non-full table would insert them fresh —
        # a state fork); ride the wave invalid and serve from the cold
        # tier in the resolve below.  Mesh-pinned rows (mslot >= 0) are
        # never cold: the pin seed pops the cold copy.
        with phase("wave.route"):
            batch, ood, leaky = self._mask_out_of_domain(batch, mslot)
            tier = self.tier
            cold_mask = None
            orig_valid = None
            if tier is not None:
                kh = np.asarray(khash)
                orig_valid = np.asarray(batch.valid) & (kh != 0)
                cold_mask = tier.resident_mask(kh) & orig_valid
                if mslot is not None:
                    cold_mask &= np.asarray(mslot) < 0
                if cold_mask.any():
                    batch = batch._replace(
                        valid=np.asarray(batch.valid) & ~cold_mask)
            if leaky is not None:
                self._count_leaky_rows(
                    np.count_nonzero(leaky & np.asarray(batch.valid)))
            # earliest requests take the earliest waves: same-key
            # requests split across waves then apply in arrival-time
            # order (within a wave the device's (row, now) sort handles
            # it)
            pending = self._arrival_order(batch)
            waves = self._build_waves(khash, pending)
        retried = False
        while len(pending):
            err_idx: List[int] = []
            for idx, slots, bw_w in waves:
                with phase("wave.fill"):
                    a64, a32, lease, mblk = self._fill_packed(
                        batch, idx, slots, bw_w, mslot)
                try:
                    # see launch_packed: 3-arg call when no mesh lane
                    launched = (
                        self._launch_arrays(a64, a32, now_ms)
                        if mblk is None
                        else self._launch_arrays(a64, a32, now_ms, mblk))
                finally:
                    lease.release()  # launch copied the host operands
                o_st, o_rem, o_rst, o_lim, o_err = self._finish_wave(
                    *launched)
                with phase("wave.scatter"):
                    status[idx] = o_st[slots]
                    rem_o[idx] = o_rem[slots]
                    rst_o[idx] = o_rst[slots]
                    lim_o[idx] = o_lim[slots]
                    werr = o_err[slots]
                    if werr.any():
                        err_idx.extend(idx[werr].tolist())
            if err_idx and not retried:
                # probe windows clogged with expired rows: sweep once and
                # retry those requests (check_batch does the same)
                retried = True
                self.sweep(now_ms)
                pending = np.asarray(sorted(err_idx))
            elif err_idx and self._try_auto_grow([False]):
                pending = np.asarray(sorted(err_idx))
            else:
                full[err_idx] = True
                for i in err_idx:
                    status[i] = 0
                    rem_o[i] = 0
                    rst_o[i] = 0
                    lim_o[i] = 0
                pending = np.empty(0, np.int64)
            if len(pending):
                with phase("wave.route"):
                    waves = self._build_waves(khash, pending)
        cols = (status, lim_o, rem_o, rst_o, full)
        if tier is not None:
            # cold lane: pre-masked cold-resident rows plus residual
            # table-full rows (brand-new keys, device table saturated —
            # the tier turns table-full into find-or-create on host)
            cols = tier.resolve(self, batch, khash, now_ms, cols,
                                cold_mask, orig_valid, mslot=mslot)
        return self._serve_out_of_domain(cols, ood, batch, khash, now_ms,
                                         mslot)

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
        out = {f: np.zeros(m, np.asarray(getattr(self.state, f)).dtype)
               for f in VALUE_COLS}
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

    def probe_occupant_keys(self, kh: int) -> np.ndarray:
        """The resident key hashes in ``kh``'s probe window (up to
        PROBES entries, 0 = free slot) — the tier controller's eviction
        candidate read: any of these keys, once demoted, frees a slot
        ``kh`` itself can take (same probe formula as the device kernel,
        core/step.py › _probe_slots)."""
        from ..core.step import PROBES

        k = np.uint64(kh)
        stride = (k >> np.uint64(17)) | np.uint64(1)
        local = ((k + np.arange(PROBES, dtype=np.uint64) * stride)
                 & np.uint64(self.cap_local - 1))
        shard = int(shard_of(np.array([k], np.uint64), self.n)[0])
        slots = (shard * self.cap_local + local).astype(np.int64)
        with XLA_EXEC_MU:
            keys = np.asarray(
                jnp.take(self.state.key, jnp.asarray(slots), axis=0))
        return keys.astype(np.uint64)

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
        """
        from ..core.step import PROBES

        host = {f: np.asarray(getattr(self.state, f)).copy()
                for f in self.state._fields}
        cap = self.cap_local
        keys = arrays["key"].astype(np.uint64)
        shard = shard_of(keys, self.n)
        stride = (keys >> np.uint64(17)) | np.uint64(1)
        placed = 0
        unplaced: List[int] = []
        for i in range(len(keys)):
            base = int(shard[i]) * cap
            k = keys[i]
            for p in range(PROBES):
                slot = base + int((k + np.uint64(p) * stride[i])
                                  & np.uint64(cap - 1))
                if host["key"][slot] == 0 or host["key"][slot] == k:
                    for f in host:
                        if f != "key":
                            host[f][slot] = arrays[f][i]
                    host["key"][slot] = k
                    placed += 1
                    break
            else:
                unplaced.append(i)
        if unplaced and self.tier is not None:
            # tiered restore: rows the device table can't hold land in
            # the cold tier instead of being dropped — the snapshot
            # round-trip keeps every row in exactly one tier
            placed += self.tier.adopt_rows(arrays, unplaced)
        sh = table_sharding(self.mesh)
        from ..core.table import TableState, init_table

        self.state = TableState(**{
            f: jax.device_put(v, sh) for f, v in host.items()})
        # device_put of an aligned host column is zero-copy on this
        # image's XLA:CPU without pinning the numpy owner — once `host`
        # dies the allocator reuses the table's backing memory and live
        # rows turn into heap garbage (state lost across restart, and
        # worse: ~1.6k phantom rows evicting real ones).  Pin the
        # columns for the engine's lifetime; the donated step keeps
        # writing the state into these same buffers, so the cost is one
        # table copy (~cap×9×8 bytes), not a leak per wave.
        self._restore_host_pin = host
        return placed
