"""Pallas serving engine: the hand-scheduled decision kernel as a
deployable step mode (SURVEY §2.2; VERDICT r3 item 1's escalation —
"make the Pallas kernel the serving mode at large CAP: it owns its
scatters").

``PallasServingEngine`` is a drop-in ``ShardedEngine`` whose per-shard
table is the kernel's bucket layout (``[n_buckets, 16, 128] int32``,
128-slot buckets — ops/pallas_step.py) instead of SoA columns, and
whose step is the Mosaic kernel under ``shard_map``.  Everything above the
step — wave routing, dispatcher coalescing, the wire lanes, metrics —
is inherited unchanged; the engine protocol (gather/upsert/remove
rows, snapshot/restore, sweep) is re-implemented on the bucket layout
so V1Instance features (Store read/write-through, stateful handover,
checkpoint/resume) keep working.

Domain: the kernel serves TOKEN and LEAKY rows whose counters are
< 2^30 and (leaky) eff < 2^31.  Out-of-domain rows are scoped PER ROW
(``pallas_value_domain_mask``): they are excluded from the device step
and surfaced as unservable (``table_full`` True) — never silently
truncated into wrong decisions, and never allowed to fail the other
callers the dispatcher coalesced into the same wave.  The gate covers
both serving paths (check_packed and the pipelined launch/sync pair).
(Per-key time monotonicity is guaranteed upstream: the engine's wave
builder sorts pending requests by arrival time.)

Not supported in this mode (documented trade-offs, not gaps a caller
can trip silently): on-device auto-grow (bucket-full rows err and
surface as table_full exactly like a full SoA probe window; callers
see the same retry semantics), and the fused SoA Pallas sweep (this
mode's sweep is a plain vectorized expire-clear over rows).
"""
from __future__ import annotations

import logging

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.batch import RequestBatch
from ..core.step import REPLICA_PROBES, decide_batch_impl
from ..ops import pallas_step as ps
from ..tracing import phase
from .mesh import SHARD_AXIS, XLA_EXEC_MU, exec_gate
from .sharded import ROW_OP_SIZES, VALUE_COLS, ShardedEngine, padded

log = logging.getLogger("gubernator_tpu.pallas_engine")

#: SoA column → (word extractor) mapping used by snapshot/gather.
_I64_PAIRS = {"duration": (ps.W_DLO, ps.W_DHI),
              "eff_ms": (ps.W_ELO, ps.W_EHI),
              "t_ms": (ps.W_TLO, ps.W_THI),
              "expire_at": (ps.W_XLO, ps.W_XHI)}


def _join_u64(hi: np.ndarray, lo: np.ndarray) -> np.ndarray:
    return ((hi.astype(np.uint32).astype(np.uint64) << np.uint64(32))
            | lo.astype(np.uint32).astype(np.uint64))


def _join_i64(hi: np.ndarray, lo: np.ndarray) -> np.ndarray:
    return _join_u64(hi, lo).astype(np.int64)


def _split_np(x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    u = x.astype(np.uint64)
    return ((u >> np.uint64(32)).astype(np.uint32).astype(np.int32),
            u.astype(np.uint32).astype(np.int32))


def _rows_to_columns(rows: np.ndarray) -> dict:
    """[N, WORDS] int32 slot rows → SoA column dict (live rows only),
    in the store/Loader format (store.py › table_to_arrays).

    ``burst`` is emitted as ``limit``: the kernel does not store burst
    because oracle.apply_leaky overwrites item.burst from the request
    before every read — the column is dead state everywhere except a
    snapshot round-trip, and limit is its every-step value for token
    rows (leaky rows re-adopt the request burst on first touch).
    """
    key = _join_u64(rows[:, ps.W_KHI], rows[:, ps.W_KLO])
    live = key != 0
    r = rows[live]
    key = key[live]
    alg = r[:, ps.W_ALG].astype(np.int64)
    status = r[:, ps.W_STATUS].astype(np.int64)
    limit = r[:, ps.W_LIMIT].astype(np.int64)
    remaining = np.where(
        alg == 1,
        _join_i64(r[:, ps.W_TDHI], r[:, ps.W_TDLO]),
        r[:, ps.W_REM].astype(np.int64))
    out = {"key": key,
           "meta": (alg | ((status & 1) << 1)).astype(np.int32),
           "limit": limit, "burst": limit, "remaining": remaining}
    for name, (wlo, whi) in _I64_PAIRS.items():
        out[name] = _join_i64(r[:, whi], r[:, wlo])
    return out


def _columns_to_words_batch(arrays: dict, keys: np.ndarray
                            ) -> tuple[np.ndarray, np.ndarray]:
    """All snapshot rows at once → ([n, WORDS] int32 kernel rows,
    [n] bool in-domain mask).  Vectorized: restore/upsert at serving
    scale (1M–10M rows) must not walk rows in Python (VERDICT r4
    weak #2 — the old per-row loop made checkpoint-resume minutes).

    A row is out of domain (mask False; the caller drops it with a
    count, mirroring best-effort Loader.Load) when limit >= 2^30,
    token remaining >= 2^30, leaky eff outside [1, 2^31), or leaky
    remaining outside [0, 2^30 * eff).  The last check exists because
    leaky remaining is stored in td units (remaining x eff) and feeds
    the kernel's restoring divider, whose quotient is only one-word
    when td < 2^30 * eff; an XLA-engine snapshot clamps leaky burst
    only to TD_BOUND // eff (oracle.py), so its td can reach ~2^61 —
    such rows must drop here, not serve garbage quotients (ADVICE r4)."""
    n = len(keys)
    meta = np.asarray(arrays["meta"], np.int64)
    alg = meta & 1
    limit = np.asarray(arrays["limit"], np.int64)
    rem = np.asarray(arrays["remaining"], np.int64)
    eff = np.asarray(arrays["eff_ms"], np.int64)
    leaky = alg == 1
    valid = limit < ps.VALUE_BOUND
    valid &= ~leaky | ((eff >= 1) & (eff < ps.EFF_BOUND))
    valid &= leaky | (rem < ps.VALUE_BOUND)
    # max(eff, 1): dodge a 0-multiply only on rows already invalid
    valid &= ~leaky | ((rem >= 0)
                       & (rem < ps.VALUE_BOUND * np.maximum(eff, 1)))
    w = np.zeros((n, ps.WORDS), np.int32)
    khi, klo = _split_np(keys.astype(np.uint64))
    w[:, ps.W_KLO], w[:, ps.W_KHI] = klo, khi
    w[:, ps.W_STATUS] = ((meta >> 1) & 1).astype(np.int32)
    # invalid rows are filtered before placement; zeroing their values
    # here just keeps the int64→int32 casts in-range
    w[:, ps.W_LIMIT] = np.where(valid, limit, 0).astype(np.int32)
    w[:, ps.W_ALG] = alg.astype(np.int32)
    tdhi, tdlo = _split_np(np.where(valid & leaky, rem, 0))
    w[:, ps.W_TDLO], w[:, ps.W_TDHI] = tdlo, tdhi
    w[:, ps.W_REM] = np.where(valid & ~leaky, rem, 0).astype(np.int32)
    for name, (wlo, whi) in _I64_PAIRS.items():
        hi, lo = _split_np(np.asarray(arrays[name], np.int64))
        w[:, wlo], w[:, whi] = lo, hi
    return w, valid


def _dedupe_last(keys: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(keep indices, occurrence counts): each key's LAST occurrence's
    values at its FIRST occurrence's position — exactly a sequential
    walk's outcome: the first occurrence claims the slot (bucket-full
    priority), later occurrences overwrite it in place.  ``counts``
    lets callers keep the sequential placed/dropped accounting, where
    EVERY occurrence of a key counts (an operator reading 'restored
    N/M' must not see collapsed duplicates as data loss).  Callers
    pass only IN-DOMAIN rows: a sequential walk validates per
    occurrence, so an invalid late duplicate must not shadow an
    earlier valid write."""
    _, first_idx, counts = np.unique(keys, return_index=True,
                                     return_counts=True)
    _, last_rev = np.unique(keys[::-1], return_index=True)
    last_idx = len(keys) - 1 - last_rev  # aligned: both sorted by key
    order = np.argsort(first_idx)
    return last_idx[order], counts[order]


def _place_into_buckets(buckets: np.ndarray, group_id: np.ndarray,
                        klo: np.ndarray, khi: np.ndarray,
                        words: np.ndarray) -> np.ndarray:
    """Insert-or-update each row into its bucket, fully vectorized.

    ``buckets`` is [g, SLOTS, WORDS] — one host copy per DISTINCT
    bucket — mutated in place; ``group_id[i]`` names row i's bucket.
    Keys must be distinct (callers dedupe last-write-wins).  Existing
    keys update their slot; new keys take empty slots in caller order,
    rows sharing a bucket getting distinct empties via rank-in-group.
    Returns the [n] bool mask of rows that found a slot.  Per-row work
    is O(1) lookups into per-BUCKET tables (a key lives in exactly one
    bucket, so residents are matched by key alone), so restore stays
    linear in rows + slots even with 128-slot buckets at 10M rows."""
    n = len(group_id)
    keys = _join_u64(khi, klo)
    res = _join_u64(buckets[:, :, ps.W_KHI], buckets[:, :, ps.W_KLO])
    empty = res == 0
    placed = np.zeros(n, bool)
    slot = np.zeros(n, np.int64)
    occ = np.flatnonzero(~empty.reshape(-1))  # flat (bucket, slot)
    if occ.size:
        rk = res.reshape(-1)[occ]
        order = np.argsort(rk, kind="stable")
        rk, occ = rk[order], occ[order]
        pos = np.minimum(np.searchsorted(rk, keys), len(rk) - 1)
        hit = (rk[pos] == keys) & (occ[pos] // ps.SLOTS == group_id)
        placed[hit] = True
        slot[hit] = occ[pos[hit]] % ps.SLOTS
    new = np.nonzero(~placed)[0]
    if new.size:
        order = new[np.argsort(group_id[new], kind="stable")]
        sg = group_id[order]
        start = np.r_[True, sg[1:] != sg[:-1]]
        rank = np.arange(sg.size) - np.nonzero(start)[0][
            np.cumsum(start) - 1]
        # each bucket's empty slots, ascending, listed first: the row
        # with rank r in its bucket takes the r-th of them
        free = np.argsort(~empty, axis=1, kind="stable")
        got = rank < empty.sum(axis=1)[sg]
        placed[order[got]] = True
        slot[order[got]] = free[sg[got], rank[got]]
    # all (group_id, slot) pairs are distinct — hits sit at distinct
    # occupied slots (distinct keys), news at distinct empties — so
    # this fancy assignment has no write collisions
    buckets[group_id[placed], slot[placed]] = words[placed]
    return placed


def _row_columns(rows: np.ndarray) -> dict:
    """i64[n, 8] rows in ``VALUE_COLS`` order (tiering.ROW_COLS: a
    cold row's value columns) → the SoA column dict
    ``_columns_to_words_batch`` takes."""
    cols = {f: rows[:, j] for j, f in enumerate(VALUE_COLS)}
    cols["meta"] = cols["meta"].astype(np.int32)
    return cols


class _BucketImage:
    """The host image ONE migration pass of the tier works on
    (tiering.py › TierController.migrate): the distinct buckets of the
    pass's keys, fetched from the device ONCE (phase `tier.fetch`; the
    fetch queues behind whatever wave is already launched, so the image
    holds what that wave did to every row in it), mutated here on the
    host — promotees placed, victims taken out, two keys that share a
    bucket resolved on the one copy — and written back ONCE by
    ``commit`` (phase `tier.write`), if anything changed.  Nothing else
    may touch the table between the two: the pass runs under the engine
    lock on the thread that launches."""

    def __init__(self, eng, keys: np.ndarray):
        self.eng, self.keys = eng, keys
        self.ubids, self.gid = eng._grouped_bucket_view(keys)
        with phase("tier.fetch", eng.metrics_ref):
            self.buckets = eng._fetch_buckets(self.ubids)
        self.dirty = False

    def place(self, sel: np.ndarray, rows: np.ndarray) -> np.ndarray:
        """Keys ``sel`` (indices into the image's keys) placed with
        their i64[k, 8] ``rows``: bool[k], False where the key's bucket
        has no free slot.  Rows are inside the kernel's domain
        (``tier_rows_admissible``: the caller's gate)."""
        keys = self.keys[sel]
        words, _ = _columns_to_words_batch(_row_columns(rows), keys)
        khi, klo = _split_np(keys)
        placed = _place_into_buckets(self.buckets, self.gid[sel], klo, khi,
                                     words)
        self.dirty |= bool(placed.any())
        return placed

    def occupants(self, sel: np.ndarray) -> np.ndarray:
        """u64[k, SLOTS]: the resident keys of the bucket of each key
        ``sel`` — its probe window, so its eviction candidates — as the
        image holds them NOW (0 = free slot)."""
        res = _join_u64(self.buckets[:, :, ps.W_KHI],
                        self.buckets[:, :, ps.W_KLO])
        return res[self.gid[sel]]

    def take(self, sel: np.ndarray, vkeys: np.ndarray) -> tuple:
        """Resident ``vkeys[j]`` of the bucket of key ``sel[j]`` taken
        out of the image: (found bool[k], rows i64[k, 8], zeros where
        not found) — all eight value columns as the kernel left them."""
        g = self.gid[sel]
        hit = self.occupants(sel) == vkeys[:, None]
        hit &= (vkeys != 0)[:, None]
        found = hit.any(axis=1)
        slot = hit.argmax(axis=1)
        rows = np.zeros((len(sel), len(VALUE_COLS)), np.int64)
        if found.any():
            at = (g[found], slot[found])
            cols = _rows_to_columns(self.buckets[at])
            rows[found] = np.stack(
                [np.asarray(cols[f], np.int64) for f in VALUE_COLS], axis=1)
            self.buckets[at] = 0
            self.dirty = True
        return found, rows

    def commit(self) -> None:
        if self.dirty:
            with phase("tier.write", self.eng.metrics_ref):
                self.eng._write_buckets(self.ubids, self.buckets)
            self.dirty = False


#: the bucket table's partition: bucket axis over the mesh
_BUCKET_SPEC = P(SHARD_AXIS, None, None)


def _batch_from_packed(a64, a32) -> RequestBatch:
    """Packed wire matrices → RequestBatch (the PACK64/PACK32 layout)."""
    return RequestBatch(
        key=lax.bitcast_convert_type(a64[0], jnp.uint64),
        hits=a64[1], limit=a64[2], duration=a64[3], eff_ms=a64[4],
        greg_end=a64[5], burst=a64[6], now=a64[7],
        behavior=a32[0], algorithm=a32[1], valid=a32[2] != 0)


def _pack_outputs(out) -> jax.Array:
    return jnp.stack([
        out.status.astype(jnp.int64), out.remaining, out.reset_time,
        out.limit, out.err.astype(jnp.int64)])


def make_pallas_step_packed(mesh, interpret: bool = False):
    """shard_map twin of make_sharded_step_packed over the kernel:
    (rows, a64, a32, now) → (rows, [5,B] i64 outputs, counters).  The
    table is always donated — the kernel owns its scatters in-place."""
    S = SHARD_AXIS

    def _step(buckets, a64, a32, now):
        batch = _batch_from_packed(a64, a32)
        tbl, out = ps.decide_batch_pallas_impl(
            ps.PallasTable(buckets=buckets), batch, now,
            interpret=interpret)
        packed = _pack_outputs(out)
        over = lax.psum(out.over_count, S)
        ins = lax.psum(out.insert_count, S)
        return tbl.buckets, packed, (over, ins)

    sharded = shard_map(
        _step, mesh=mesh,
        in_specs=(_BUCKET_SPEC, P(None, S), P(None, S), P()),
        out_specs=(_BUCKET_SPEC, P(None, S), P()),
        check_vma=False)  # pallas_call out_shape carries no vma
    return jax.jit(sharded, donate_argnums=(0,))


# ---- the fused serving step (ISSUE 8) ----------------------------------
#
# ONE device program per wave: hash-probe/slot-resolve, token- and
# leaky-bucket update, over-limit decision, the heavy-hitter tap columns
# (ops/pallas_step.py › fused_tap_columns — analytics drains the device
# array, no host-side column copies), and — when the mesh-GLOBAL tier is
# bound — the home-shard replica decision PLUS the scatter-add into the
# shard's active hit accumulator, which deletes meshglobal's separate
# serving dispatch: a wave that mixes plain and mesh-GLOBAL rows costs
# one launch instead of two.
#
# ``flavor`` picks the decision kernel the program embeds:
#   "pallas" — the Mosaic bucket-table kernel (the TPU serving engine;
#              interpret-mode off-TPU, parity/testing only);
#   "xla"    — core/step.py's compiled XLA step over the SoA table (the
#              CPU opt-in: compiled — not interpret — small-shape
#              kernels with identical decisions by construction).


def _make_serve(flavor: str, interpret: bool, tile: int):
    if flavor == "pallas":
        def _serve(state, batch, now):
            tbl, out = ps.decide_batch_pallas_impl(
                ps.PallasTable(buckets=state), batch, now,
                interpret=interpret, tile=tile)
            return tbl.buckets, out
        return _serve
    if flavor != "xla":
        raise ValueError(f"unknown fused-step flavor {flavor!r}")

    def _serve(state, batch, now):
        return decide_batch_impl(state, batch, now)

    return _serve


def make_fused_step_packed(mesh, *, flavor: str, interpret: bool = False,
                           tile: int = 0, donate: bool = True):
    """(state, a64, a32, now) → (state, packed [5,B] i64, tap [4,B]
    i64, (over, insert)) — the fused program for waves with no
    mesh-GLOBAL rows.  State layout follows ``flavor`` (bucket rows vs
    SoA TableState)."""
    S = SHARD_AXIS
    serve = _make_serve(flavor, interpret, tile)

    def _step(state, a64, a32, now):
        batch = _batch_from_packed(a64, a32)
        state, out = serve(state, batch, now)
        packed = _pack_outputs(out)
        tap = ps.fused_tap_columns(batch, out)
        over = lax.psum(out.over_count, S)
        ins = lax.psum(out.insert_count, S)
        return state, packed, tap, (over, ins)

    state_spec = _BUCKET_SPEC if flavor == "pallas" else P(S)
    sharded = shard_map(
        _step, mesh=mesh,
        in_specs=(state_spec, P(None, S), P(None, S), P()),
        out_specs=(state_spec, P(None, S), P(None, S), P()),
        check_vma=False)
    return jax.jit(sharded, donate_argnums=(0,) if donate else ())


def make_fused_mesh_step_packed(mesh, *, flavor: str, mesh_cap: int,
                                interpret: bool = False, tile: int = 0):
    """The mesh-GLOBAL fused program (GUBER_GLOBAL_MODE=mesh with a
    fused engine): rows whose ``mslot`` is >= 0 decide on the key's
    HOME-shard replica of the mesh-GLOBAL table and scatter-add their
    applied hits into that shard's ACTIVE accumulator (the conservation
    ledger meshglobal's reconcile fold psums); all other rows take the
    serving kernel.  One launch serves both lanes — the separate
    meshglobal serving dispatch is deleted.

    Host routing already sends every request to ``shard_of(khash)``,
    which IS the mesh tier's home-shard function, so a mesh row always
    lands on the shard whose replica row is exact.

    (state, mstate, acc, a64, a32, mslot, now) →
    (state, mstate, acc, packed, tap, (over, insert, mesh_hits)).
    """
    S = SHARD_AXIS
    serve = _make_serve(flavor, interpret, tile)

    def _step(state, mstate, acc, a64, a32, mslot, now):
        batch = _batch_from_packed(a64, a32)
        mesh_rows = mslot >= 0
        main = batch._replace(valid=batch.valid & (~mesh_rows))
        state, out = serve(state, main, now)
        # mesh lane: home replica decide (bit-identical to the
        # owner-sharded path — same decide_batch_impl, same row state)
        mst = jax.tree.map(lambda x: x[0], mstate)
        a = acc[0]
        mb = batch._replace(valid=batch.valid & mesh_rows)
        mst, mout = decide_batch_impl(mst, mb, now, REPLICA_PROBES)
        ok = mb.valid & (~mout.err)
        applied = jnp.where(ok, jnp.maximum(batch.hits, 0),
                            jnp.int64(0))
        # pinned slot comes straight from the host slot map (mslot) —
        # no re-probe; erred rows never mutated state so they don't
        # accumulate either (exactly meshglobal's step contract)
        a = a.at[jnp.where(ok, mslot, mesh_cap)].add(applied,
                                                     mode="drop")
        # merge the two lanes row-wise
        from ..core.step import StepOutput

        merged = StepOutput(
            status=jnp.where(mesh_rows, mout.status, out.status),
            remaining=jnp.where(mesh_rows, mout.remaining,
                                out.remaining),
            reset_time=jnp.where(mesh_rows, mout.reset_time,
                                 out.reset_time),
            limit=jnp.where(mesh_rows, mout.limit, out.limit),
            err=jnp.where(mesh_rows, mout.err, out.err),
            over_count=out.over_count + mout.over_count,
            insert_count=out.insert_count + mout.insert_count)
        packed = _pack_outputs(merged)
        tap = ps.fused_tap_columns(batch, merged)
        over = lax.psum(merged.over_count, S)
        ins = lax.psum(merged.insert_count, S)
        mesh_hits = lax.psum(applied.sum(), S)
        return (state, jax.tree.map(lambda x: x[None], mst), a[None],
                packed, tap, (over, ins, mesh_hits))

    state_spec = _BUCKET_SPEC if flavor == "pallas" else P(S)
    sharded = shard_map(
        _step, mesh=mesh,
        in_specs=(state_spec, P(S), P(S), P(None, S), P(None, S),
                  P(S), P()),
        out_specs=(state_spec, P(S), P(S), P(None, S), P(None, S),
                   P()),
        check_vma=False)
    return jax.jit(sharded, donate_argnums=(0,))


class FusedServingMixin:
    """Fused gather–decide–scatter serving (ISSUE 8): the engine's step
    is ONE device program per wave that also emits the heavy-hitter tap
    columns on device and, when the mesh-GLOBAL tier is bound, folds
    the replica decision + accumulator scatter into the same launch.

    The dispatcher reads ``fused_tap``: it suppresses the host-side
    per-wave column copies (the engine delivered the device tap at
    launch).  ``fused_serving`` names the engine family (health and
    bench output); the wave's pack/device/resolve partition is the same
    as any engine's — fusion deleted device programs, not the host's
    routing and fill, which the chip showed to be the larger part.
    """

    #: one device program per wave (decisions + tap + mesh tier)
    fused_serving = True
    #: dispatcher skips host-side column taps (device tap instead)
    fused_tap = True
    #: decision-kernel flavor the fused program embeds (subclass sets)
    _flavor = "xla"

    def _fused_setup(self) -> None:
        #: analytics sink for device taps + instance metrics registry:
        #: both single-assigned at instance wiring BEFORE serving
        #: starts, then read-only on the launch path
        self.tap_sink = None  # lock-free: set once pre-serving, read-only after
        #: bound MeshGlobalEngine (GUBER_GLOBAL_MODE=mesh): a single
        #: reference swap — a wave racing an unbind serves one more
        #: mesh wave, which the tier's state lock keeps exact
        self._mge = None  # lock-free: single ref swap; state mutations under mge._state_mu
        self._mesh_step = None  # lock-free: launch path only (engine lock serializes)
        self._tap_mute = False  # lock-free: engine calls serialized by the engine lock
        self.fused_wave_count = 0  # lock-free: launch path only (engine lock serializes)
        self.mesh_fused_hits = 0  # lock-free: sync path only (engine lock serializes)

    # ---- mesh-GLOBAL binding -------------------------------------------

    def bind_mesh(self, mge) -> None:
        """Attach the mesh-GLOBAL tier: waves whose ``mslot`` column
        marks pinned rows serve them on the home replica + accumulator
        INSIDE the fused program (instance.py wires this when
        GUBER_GLOBAL_MODE=mesh and the engine is fused)."""
        if mge.n != self.n:
            raise ValueError("mesh-GLOBAL tier and serving engine must "
                             "share the device mesh")
        self._mesh_step = None
        self._mge = mge

    def unbind_mesh(self) -> None:
        """Detach (mesh stand-down): subsequent waves serve every row
        on the sharded path; a wave already launched finishes under the
        tier's state lock first."""
        self._mge = None

    @property
    def mesh_bound(self) -> bool:
        return self._mge is not None

    def _ensure_mesh_step(self, mge):
        if self._mesh_step is None:
            self._mesh_step = make_fused_mesh_step_packed(
                self.mesh, flavor=self._flavor, mesh_cap=mge.capacity,
                interpret=getattr(self, "_interpret", False),
                tile=getattr(self, "_tile", 0))
        return self._mesh_step

    def warmup_mesh_fused(self, now_ms: int = 1) -> None:
        """Pre-compile the fused mesh program for every wave bucket —
        an all-invalid wave whose one marked mesh row is invalid
        (nothing moves, the scatter drops) — so the first GLOBAL
        caller never pays the compile (same contract as warmup)."""
        mge = self._mge
        if mge is None:
            return
        from ..core.batch import empty_batch
        from .sharded import pack_wave_host

        for bw in self.wave_buckets:
            a64, a32 = pack_wave_host(empty_batch(self.n * bw))
            mblk = np.full(self.n * bw, -1, np.int32)
            mblk[0] = 0  # invalid row: compiles the mesh lane only
            self._finish_wave(*self._launch_arrays(a64, a32, now_ms,
                                                   mblk))

    # ---- fused launch ---------------------------------------------------

    def _deliver_tap(self, tap) -> None:
        """Hand the device tap array to analytics (no host copy here:
        np.asarray happens on the analytics worker thread)."""
        self.fused_wave_count += 1
        m = self.metrics_ref
        if m is not None:
            m.pallas_fused_waves.inc()
        sink = self.tap_sink
        if sink is not None and not self._tap_mute:
            try:
                sink(tap)
            except Exception:  # noqa: BLE001 - analytics only
                log.exception("fused tap delivery")

    def check_batch(self, reqs, now_ms: int):
        # object-lane waves are tapped by the dispatcher WITH key names
        # (the sketch's name side table); mute the device tap for this
        # call so the wave isn't double-counted.  Engine calls are
        # serialized by the dispatcher's engine lock, so the plain
        # attribute is effectively single-threaded.
        self._tap_mute = True
        try:
            return super().check_batch(reqs, now_ms)
        finally:
            self._tap_mute = False

    def _launch_arrays(self, a64, a32, now_ms: int, mblk=None):
        """One fused launch: decisions + device tap (+ mesh-GLOBAL
        replica decide and accumulator scatter when bound and the wave
        carries pinned rows)."""
        mge = self._mge
        if (mge is None or mblk is None
                or not bool((np.asarray(mblk) >= 0).any())):
            with exec_gate():
                if self.n > 1:
                    a64 = jax.device_put(a64, self._mat_sharding)
                    a32 = jax.device_put(a32, self._mat_sharding)
                self.state, packed, tap, counters = self._step(
                    self.state, a64, a32, np.int64(now_ms))
                self._deliver_tap(tap)
            return packed, counters
        step = self._ensure_mesh_step(mge)

        def _go(mstate, acc):
            nonlocal a64, a32, mblk
            with exec_gate():
                if self.n > 1:
                    a64 = jax.device_put(a64, self._mat_sharding)
                    a32 = jax.device_put(a32, self._mat_sharding)
                    mblk = jax.device_put(mblk, self._batch_sharding)
                (st, mst, acc2, packed, tap,
                 counters) = step(self.state, mstate, acc, a64, a32,
                                  mblk, np.int64(now_ms))
            self.state = st
            return mst, acc2, (packed, tap, counters)

        packed, tap, counters = mge.run_fused(_go)
        self._deliver_tap(tap)
        return packed, counters

    def _download_wave(self, packed, counters):
        cols = super()._download_wave(packed, counters[:2])
        if len(counters) > 2:
            mh = int(counters[2])
            if mh:
                # conservation ledger: the fused scatter's applied mesh
                # hits ARE the injected side of meshglobal's
                # folded == injected oracle
                self.mesh_fused_hits += mh
                mge = self._mge
                if mge is not None:
                    mge.note_injected(mh)
                m = self.metrics_ref
                if m is not None:
                    m.pallas_mesh_fused_hits.inc(mh)
        return cols


class PallasServingEngine(FusedServingMixin, ShardedEngine):
    """ShardedEngine over the kernel's bucketized table (module doc)."""

    _flavor = "pallas"

    def _default_wave_buckets(self) -> tuple:
        """On ONE chip the ladder goes on to 16·B rows: a saturated
        daemon's wave takes the calls queued when the worker drains
        (10–11 of 32 callers' 1,000-row calls), so what a wave costs
        before its first row — a launch's round trip, the worker's
        fixed work — is paid once for them, on a device that idles four
        fifths of the time.  A 32·B rung was measured too and served no
        better: a third of a tiered daemon's waves rode it half empty
        (PERF.md §6, PR 49).  On a mesh the 8·B program already holds
        n·8·B slots and the GLOBAL fold shares the worker's loop: it
        keeps the base ladder."""
        if self.n == 1:
            return (self.B, self.B * 8, self.B * 16)
        return super()._default_wave_buckets()

    def _init_table_and_step(self) -> None:
        if self.cap_local < ps.SLOTS or (self.cap_local
                                         & (self.cap_local - 1)):
            raise ValueError("rows per shard must be a power of two "
                             f">= {ps.SLOTS}")
        #: buckets per shard; global bucket id = shard·nb_local + local
        self.nb_local = self.cap_local // ps.SLOTS
        sh = NamedSharding(self.mesh, _BUCKET_SPEC)
        # built under jit with the output sharding: each device
        # materializes only its own shard (see mesh.shard_table)
        self.state = jax.jit(
            lambda: jnp.zeros((self.n * self.nb_local, ps.WORDS,
                               ps.SLOTS), jnp.int32),
            out_shardings=sh)()
        # interpret everywhere the Mosaic kernel can't compile natively
        # (same gate as sharded.py's fused sweep)
        self._interpret = jax.default_backend() != "tpu"
        self._tile = ps.pallas_tile()
        self._step = make_fused_step_packed(
            self.mesh, flavor="pallas", interpret=self._interpret,
            tile=self._tile)
        self._fused_setup()
        self._rows_sharding = sh

        # ONE fused program serves occupancy AND the saturation
        # watermark, compiled (and warmed) here so the first
        # health_check doesn't pay a jit under the engine lock while
        # serving waves wait on it
        def _occ_sat(b):
            live = (b[:, ps.W_KLO] != 0) | (b[:, ps.W_KHI] != 0)
            per_bucket = live.sum(axis=1, dtype=jnp.int32)
            return (per_bucket.sum(dtype=jnp.int64),
                    (per_bucket == ps.SLOTS).sum(dtype=jnp.int64))

        self._occ_sat_fn = jax.jit(_occ_sat)
        jax.block_until_ready(self._occ_sat_fn(self.state))

    # ---- serving -------------------------------------------------------

    value_domain = (ps.VALUE_BOUND, ps.EFF_BOUND)

    def _out_of_domain(self, rows, mslot=None):
        """``lay_out`` without the C++ extension, which is given
        ``value_domain`` and applies the same rule: (indices of the
        valid rows outside the kernel's value domain or None, the count
        of LEAKY rows that stay valid).  Mesh-GLOBAL
        rows (mslot >= 0) are exempt: they decide on the replica
        table's XLA math inside the fused program, which has the full
        int64 domain."""
        mask, leaky = ps.pallas_value_domain_mask(rows.batch)
        if mslot is not None:
            mask |= np.asarray(mslot) >= 0
        v = rows.valid
        ood = v & ~mask
        return (np.nonzero(ood)[0] if ood.any() else None,
                0 if leaky is None
                else int(np.count_nonzero(leaky & v & mask)))

    @staticmethod
    def _merge_ood(cols, ood):
        """Out-of-domain rows come back as unservable (table_full) with
        zeroed outputs — scoped to the offending rows, the same shape a
        full probe window presents."""
        if ood is None:
            return cols
        st, lim, rem, rst, full = cols
        full = np.array(full, copy=True)
        full[ood] = True
        return st, lim, rem, rst, full

    def _serve_out_of_domain(self, cols, ood, batch, khash, now_ms,
                             mslot):
        cols = self._merge_ood(cols, ood)
        tier = self.tier
        if tier is None or ood is None:
            return cols
        # tiered store: the kernel can't serve out-of-domain values but
        # the host cold tier can — exactly.  Only keys with NO device
        # row are eligible (cold-serving a device-resident key would
        # fork its state); the rest keep the table_full error.
        kh = np.asarray(khash)
        found, _ = self.gather_rows(kh[ood])
        elig = ood[~found]
        if not len(elig):
            return cols
        need = np.zeros(len(kh), bool)
        need[elig] = True
        return tier.resolve(self, batch, khash, now_ms, cols,
                            None, need, mslot=mslot)

    def _try_auto_grow(self, grew: list) -> bool:
        return False  # no on-device grow for the bucket layout (doc)

    def grow(self, new_cap_per_shard: int) -> int:
        raise NotImplementedError(
            "pallas serving mode has no on-device grow; size rows up "
            "front (bucket-full rows err as table_full)")

    # ---- tiered store hooks (tiering.py) -------------------------------

    def warmup_tier(self) -> None:
        """The row programs a tier migration pass runs (``_BucketImage``)
        at every padded length (``ROW_OP_SIZES``): the buckets fetched,
        and written back as they were."""
        for m in ROW_OP_SIZES:
            bid = np.zeros(m, np.int64)
            self._write_buckets(bid, self._fetch_buckets(bid))

    def tier_rows_admissible(self, rows: np.ndarray) -> np.ndarray:
        """Admission domain gate, bool[n] for i64[n, 8] cold rows
        (tiering.ROW_COLS order): a cold row whose values exceed the
        kernel's packed-word domain must STAY cold — placing it would
        truncate it, and the migration would lose the row."""
        rows = np.asarray(rows, np.int64).reshape(-1, len(VALUE_COLS))
        _, valid = _columns_to_words_batch(
            _row_columns(rows), np.ones(len(rows), np.uint64))
        return valid

    def tier_image(self, khash: np.ndarray) -> "_BucketImage":
        """The host image a migration pass works on (tiering.py ›
        TierController.migrate): the distinct buckets of ``khash``,
        fetched ONCE here and written back ONCE by its ``commit``."""
        return _BucketImage(self, np.asarray(khash, np.uint64))

    def probe_occupants(self, khash: np.ndarray) -> np.ndarray:
        """u64[k, SLOTS] eviction-candidate read: the bucketized
        layout's probe window IS the key's bucket, so a key's occupants
        are its bucket's resident keys (0 = free slot)."""
        b = self._fetch_buckets(
            self._bucket_ids(np.asarray(khash, np.uint64)))
        return _join_u64(b[:, :, ps.W_KHI], b[:, :, ps.W_KLO])

    # ---- sweep ---------------------------------------------------------

    def sweep(self, now_ms: int) -> None:
        """Expire-clear over the buckets: zero every slot whose
        expire_at <= now (all its words, so leaky td state can't leak
        into a future occupant — the kernel relies on empty slots being
        all-zero).  Elementwise per shard — no collective."""
        if not hasattr(self, "_sweep_fn"):
            S = SHARD_AXIS

            def _one(b, now):
                exp = (b[:, ps.W_XHI].astype(jnp.int64) << 32) | (
                    b[:, ps.W_XLO].astype(jnp.int64)
                    & jnp.int64(0xFFFFFFFF))
                live = (b[:, ps.W_KLO] != 0) | (b[:, ps.W_KHI] != 0)
                expired = live & (now >= exp)
                b = jnp.where(expired[:, None, :], jnp.int32(0), b)
                n_live = lax.psum((live & ~expired).sum(dtype=jnp.int64),
                                  S)
                return b, n_live

            self._sweep_fn = jax.jit(shard_map(
                _one, mesh=self.mesh, in_specs=(_BUCKET_SPEC, P()),
                out_specs=(_BUCKET_SPEC, P())),
                donate_argnums=(0,))
        self.state, live = self._sweep_fn(
            self.state, jnp.asarray(now_ms, jnp.int64))
        self.live_rows = int(live)
        self.sweep_count += 1

    # ---- row ops (bucket-level, cold path) -----------------------------

    def _bucket_ids(self, khash: np.ndarray) -> np.ndarray:
        """[m] global bucket id of each key (owner shard's block, then
        the kernel's own ``key & (n_buckets - 1)``)."""
        from ..hashing import shard_of

        shard = shard_of(khash, self.n).astype(np.int64)
        local = (khash & np.uint64(self.nb_local - 1)).astype(np.int64)
        return shard * self.nb_local + local

    def _fetch_buckets(self, bids: np.ndarray) -> np.ndarray:
        """Gather [m, SLOTS, WORDS] slot-row copies of buckets ``bids``
        to host (writable: callers mutate them in place).  The device
        gather runs at a padded length (``sharded.padded``), so that a
        batch of any size up to the largest runs a program
        ``warmup_tier`` compiled."""
        got = np.asarray(jnp.take(self.state,
                                  jnp.asarray(padded(bids)), axis=0))
        return np.ascontiguousarray(ps.buckets_to_rows(got[:len(bids)]))

    def _write_buckets(self, bids: np.ndarray, rows: np.ndarray) -> None:
        # duplicate buckets in one call carry identical content (the
        # caller mutates a shared host copy per bucket; the padding
        # repeats the last bucket), so last-write equivalence holds
        # even without a uniqueness promise
        if not hasattr(self, "_write_fn"):
            # cached: a fresh lambda per call would retrace+recompile
            # the scatter on every store write-through
            self._write_fn = jax.jit(lambda s, i, r: s.at[i].set(r),
                                     donate_argnums=(0,))
        pad = padded(bids)
        got = np.ascontiguousarray(ps.buckets_to_rows(rows))
        if len(pad) != len(bids):
            got = np.concatenate(
                [got, np.broadcast_to(got[-1], (len(pad) - len(bids),)
                                      + got.shape[1:])])
        self.state = self._write_fn(self.state, jnp.asarray(pad),
                                    jnp.asarray(got))

    def gather_rows(self, khash: np.ndarray) -> tuple[np.ndarray, dict]:
        m = len(khash)
        found = np.zeros(m, bool)
        cols = {f: np.zeros(m, np.int64) for f in
                ("meta", "limit", "duration", "eff_ms", "burst",
                 "remaining", "t_ms", "expire_at")}
        cols["meta"] = cols["meta"].astype(np.int32)
        if m == 0:
            return found, cols
        buckets = self._fetch_buckets(self._bucket_ids(khash))
        khi, klo = _split_np(khash)
        for i in range(m):
            b = buckets[i]
            hit = np.nonzero((b[:, ps.W_KLO] == klo[i])
                             & (b[:, ps.W_KHI] == khi[i]))[0]
            if not hit.size:
                continue
            found[i] = True
            cvt = _rows_to_columns(b[hit[:1]])
            for f in cols:
                cols[f][i] = cvt[f][0]
        return found, cols

    def _prepared_rows(self, khash: np.ndarray, cols: dict,
                       ood_too: bool = False) -> tuple:
        """Shared upsert/restore front half: convert all rows, drop
        (and count) out-of-domain ones, then dedupe the survivors
        (keeping per-key occurrence counts for sequential-equivalent
        accounting).  Validate-before-dedupe order matters: a
        sequential walk checks each occurrence, so an invalid late
        duplicate never shadows an earlier valid write.  Returns (keys,
        words, counts, src, ood): ``src[i]`` is the caller's row that
        ``words[i]`` was made from (a key's LAST valid occurrence).

        ``ood_too`` (a restore into a bound cold tier, which holds what
        the kernel's domain does not): no row is dropped; the dedupe
        runs over ALL rows, so that a key's last row decides its tier,
        and ``ood`` is (src, counts) of the keys whose last row is out
        of domain — empty otherwise."""
        keys = np.asarray(khash).astype(np.uint64)
        words, valid = _columns_to_words_batch(cols, keys)
        if ood_too:
            src = np.arange(len(keys))
        else:
            self.dropped_rows += int((~valid).sum())
            src = np.flatnonzero(valid)
        counts = np.ones(len(src), np.int64)
        if src.size:
            keep, counts = _dedupe_last(keys[src])
            if len(keep) != len(src):
                src = src[keep]
        ok = valid[src]
        ood = src[~ok], counts[~ok]
        if len(ood[0]):
            src, counts = src[ok], counts[ok]
        return keys[src], words[src], counts, src, ood

    def _grouped_bucket_view(self, keys: np.ndarray
                             ) -> tuple[np.ndarray, np.ndarray]:
        """(ubids [g] distinct bucket ids, group_id [n]) — so keys
        sharing a bucket resolve against ONE image."""
        return np.unique(self._bucket_ids(keys), return_inverse=True)

    def upsert_rows(self, khash: np.ndarray, cols: dict) -> int:
        if len(khash) == 0:
            return 0
        keys, words, counts, _, _ = self._prepared_rows(khash, cols)
        if keys.size == 0:
            return 0
        # ONE batched device fetch of the distinct buckets (a per-key
        # fetch would cost a blocking device round trip per bucket)
        ubids, group_id = self._grouped_bucket_view(keys)
        buckets = self._fetch_buckets(ubids)
        khi, klo = _split_np(keys)
        placed = _place_into_buckets(buckets, group_id, klo, khi, words)
        self.dropped_rows += int(counts[~placed].sum())  # bucket full
        if not placed.any():
            return 0  # saturated buckets: skip the no-op device write
        self._write_buckets(ubids, buckets)
        return int(counts[placed].sum())

    def remove_rows(self, khash: np.ndarray) -> int:
        if len(khash) == 0:
            return 0
        bids = self._bucket_ids(khash)
        buckets = self._fetch_buckets(bids)
        khi, klo = _split_np(khash)
        removed = 0
        dirty = []
        for i in range(len(khash)):
            b = buckets[i]
            hit = np.nonzero((b[:, ps.W_KLO] == klo[i])
                             & (b[:, ps.W_KHI] == khi[i]))[0]
            if hit.size:
                b[hit] = 0
                removed += 1
                dirty.append(i)
        if dirty:
            d = np.asarray(dirty)
            self._write_buckets(bids[d], buckets[d])
        return removed

    def occupancy(self) -> int:
        with XLA_EXEC_MU:
            return int(self._occ_sat_fn(self.state)[0])

    def occupancy_nowait(self) -> int | None:
        """See ShardedEngine.occupancy_nowait — bucket-layout flavor."""
        if not XLA_EXEC_MU.acquire(blocking=False):
            return None
        try:
            return int(self._occ_sat_fn(self.state)[0])
        finally:
            XLA_EXEC_MU.release()

    def bucket_saturation(self) -> tuple[int, int]:
        """(full_buckets, total_buckets) — the capacity-safety
        watermark for this mode.  A FULL 128-slot bucket is the unit of
        unservability here: with no on-device grow, any NEW key hashing
        into one errs as table_full, so 'how many buckets are full' is
        the operative early warning, not total occupancy (a table can
        be 40% occupied yet have hot buckets saturated).  Exported as
        gubernator_pallas_bucket_saturation; VERDICT r4 item 6."""
        total = (self.n * self.cap_local) // ps.SLOTS
        with XLA_EXEC_MU:
            return int(self._occ_sat_fn(self.state)[1]), total

    def occupancy_and_saturation(self) -> tuple[int, int, int]:
        """(live_rows, full_buckets, total_buckets) in ONE device call
        — health_check refreshes both gauges under the engine lock, so
        it must not pay two round trips there."""
        with XLA_EXEC_MU:
            occ, full = self._occ_sat_fn(self.state)
        return (int(occ), int(full),
                (self.n * self.cap_local) // ps.SLOTS)

    # ---- checkpoint/resume ---------------------------------------------

    def snapshot(self) -> dict:
        return _rows_to_columns(ps.buckets_to_rows(
            np.asarray(self.state)).reshape(-1, ps.WORDS))

    def restore(self, arrays: dict) -> int:
        """Vectorized (no per-row Python): a 1M-row snapshot restores
        in seconds, not minutes — bounded by tests/test_pallas_engine
        TestSnapshotRestore.test_restore_1m_rows_is_fast.

        A bucket's free slots go to its rows in snapshot order.  With a
        cold tier bound, rows that find their bucket full and rows
        outside the kernel's value domain (which must STAY cold:
        ``tier_rows_admissible``) go to it in ONE batch (tiering.py ›
        adopt_rows), so that every snapshot row lands in exactly one
        tier, as on the XLA engine.  Without one both are dropped.
        Returns rows placed + adopted; ``dropped_rows`` and the gauge
        ``restore_unplaced_rows`` count what no tier took."""
        if len(arrays["key"]) == 0:
            return 0
        tier, dropped0 = self.tier, self.dropped_rows
        keys, words, counts, src, (ood, ood_counts) = self._prepared_rows(
            arrays["key"], arrays, ood_too=tier is not None)
        placed = np.zeros(0, bool)
        if keys.size:  # else no host copy / re-upload for a no-op
            with phase("restore.place", self.metrics_ref):
                host = np.ascontiguousarray(
                    ps.buckets_to_rows(np.asarray(self.state)))
                ubids, group_id = self._grouped_bucket_view(keys)
                buckets = host[ubids]
                khi, klo = _split_np(keys)
                placed = _place_into_buckets(buckets, group_id, klo, khi,
                                             words)
        restored = int(counts[placed].sum())
        lost = int(counts[~placed].sum())  # bucket full
        if tier is not None and (lost or len(ood)):
            # a key's last row, as on the device; every row of it counts
            tier.adopt_rows(arrays, np.concatenate([src[~placed], ood]))
            restored, lost = restored + lost + int(ood_counts.sum()), 0
        self.dropped_rows += lost
        if self.metrics_ref is not None:
            self.metrics_ref.restore_unplaced_rows.set(
                self.dropped_rows - dropped0)
        if placed.any():  # else saturated buckets: no no-op re-upload
            host[ubids] = buckets
            self.state = jax.device_put(
                np.ascontiguousarray(ps.buckets_to_rows(host)),
                self._rows_sharding)
        return restored


class XlaFusedEngine(FusedServingMixin, ShardedEngine):
    """The fused serving engine's off-TPU flavor (GUBER_ENGINE=pallas
    on a CPU backend): the SAME one-launch-per-wave fused program —
    decisions + device tap + optional mesh-GLOBAL replica decide and
    accumulator scatter — with core/step.py's COMPILED XLA step as the
    embedded decision kernel instead of the Mosaic bucket kernel.

    This is the "compiled — not interpret — small-shape kernels"
    opt-in: interpret-mode Pallas on CPU measures nothing (orders
    slower by construction), so the CPU flavor serves from compiled
    XLA kernels at small wave shapes (default wave buckets 256/2048 —
    fast compiles, the 1-core host's coalescing sweet spot) while
    keeping decisions bit-identical to ``ShardedEngine`` by
    construction (same decide_batch_impl, same SoA table, so the full
    engine protocol — grow, sweep, snapshot — is inherited unchanged).
    """

    _flavor = "xla"

    #: small-shape default wave buckets (GUBER_WAVE_BUCKETS overrides):
    #: top bucket 1024 matches the classic engine's FIRST bucket, so a
    #: 1000-row wire batch rides the same wave width both ways — the
    #: A/B compares fusion, not wave quantization
    SMALL_WAVE_BUCKETS = (256, 1024)

    def __init__(self, mesh=None, capacity_per_shard: int = 1 << 16,
                 batch_per_shard: int = 1024, auto_grow_limit: int = 0,
                 wave_buckets=None):
        import os as _os

        if wave_buckets is None \
                and not _os.environ.get("GUBER_WAVE_BUCKETS", ""):
            wave_buckets = self.SMALL_WAVE_BUCKETS
        super().__init__(mesh, capacity_per_shard, batch_per_shard,
                         auto_grow_limit=auto_grow_limit,
                         wave_buckets=wave_buckets)

    def _init_table_and_step(self) -> None:
        import os as _os

        from .mesh import shard_table

        self.state = shard_table(self.mesh, self.cap_local)
        # same donation default/opt-out as the classic engine (the
        # bucket-kernel flavor always donates: the kernel owns its
        # scatters in place)
        self._step = make_fused_step_packed(
            self.mesh, flavor="xla",
            donate=_os.environ.get("GUBER_STEP_DONATE", "1") == "1")
        self._fused_setup()


def resolve_engine_kind(selector: str, step_impl: str,
                        backend: str) -> str:
    """GUBER_ENGINE / Config.engine → concrete engine kind.

    - ``auto`` (or unset): the fused Pallas engine on TPU, the classic
      XLA sharded engine elsewhere (the pre-ISSUE-8 default);
    - ``pallas``: fused serving everywhere — the Mosaic bucket kernel
      on TPU, the compiled XLA fused flavor off-TPU;
    - ``xla`` / ``sharded``: the classic engine, explicitly.

    The legacy ``GUBER_STEP_IMPL=pallas`` knob keeps meaning "the
    bucket-kernel engine, even off-TPU (interpret)" — the kernel-parity
    mode tests and the probe drive; GUBER_ENGINE wins when both are
    set.  Unknown values raise: a typo must not silently serve a mode
    whose domain restrictions the operator believes are live.
    """
    sel = (selector or "").strip().lower()
    if sel not in ("", "auto", "pallas", "xla", "sharded"):
        raise ValueError(
            f"unknown GUBER_ENGINE {selector!r} (want auto, pallas, "
            "xla or sharded)")
    if sel in ("", "auto"):
        if step_impl == "pallas":
            return "pallas-kernel"
        return "pallas-fused" if backend == "tpu" else "xla-classic"
    if sel == "pallas":
        return "pallas-fused" if backend == "tpu" else "xla-fused"
    return "xla-classic"
