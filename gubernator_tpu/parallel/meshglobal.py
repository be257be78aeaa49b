"""Mesh-resident GLOBAL tier: collective hit reconciliation (ISSUE 7).

The SNIPPETS.md north star, made the GLOBAL serving mode: "the
`globalManager` async-hits broadcast is replaced by an ICI all-reduce
over the counter tensor so a TPU pod acts as a single coherent
rate-limit region without gRPC peer fan-out".

Layout: every shard holds a full replica of a bounded GLOBAL counter
table ([n, C] with leading device axis), and a pair of per-shard **hit accumulators** living on device right
next to it.  Requests route to their key's HOME shard (the same
hash-range ownership `hashing.shard_of` gives the sharded table), so
the home replica sees every hit and its row is always EXACT — decisions
are bit-identical to the owner-sharded path.  The serving step is one
fused program per wave: decide on the home replica AND scatter-add the
applied hits into that shard's active accumulator (no collectives on
the request path).

The reconcile tick then replaces the reference's hit-queue flush +
owner broadcast round trip with ONE collective program:

- every value column adopts its home shard's row via a psum of
  home-masked columns (the all-reduce over the counter tensor — the
  broadcast replacement; "Revisiting the Time Cost Model of AllReduce"
  is the schedule XLA lowers this to on a real pod ring),
- the retired accumulator buffer psums into per-slot hit totals — the
  conservation ledger (`sum of shard counters == injected hits` is the
  oracle tests assert),
- the retired buffer comes back zeroed for its next active term.

Double buffering (TokenWeave-style overlap): accumulators swap between
two buffers at the tick, so the fold reads a RETIRED buffer while new
hits land in the fresh one, and the fold launch is asynchronous — the
host never blocks on the collective; its results drain lazily on the
next tick (serving waves order after it device-side through the state
threading).  Staleness is therefore bounded by the reconcile interval
and measured per fold (`gubernator_mesh_global_staleness_seconds`).

Scope: TOKEN/LEAKY keys without RESET/DRAIN/Gregorian flags
(instance.py › _REPLICA_EXCLUDED); everything else (and every key once the
tier stands down — see V1Instance's degraded fallback) takes the
owner-sharded path, which is coherent by construction.  Cross-pod /
multi-region traffic keeps the gRPC lanes (`global_manager.py`).
"""
from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.batch import RequestBatch, clamp_config, empty_batch, pack_requests
from ..core.step import (REPLICA_PROBES, _lookup, _probe_slots,
                         decide_batch_impl)
from ..core.table import (TableState, from_host, init_table, is_empty,
                          split64, to_host)
from ..hashing import shard_of
from ..tracing import phase
from ..types import EFF_MAX, RateLimitRequest, RateLimitResponse, Status
from .mesh import SHARD_AXIS, XLA_EXEC_MU
from .sharded import pack_wave_host

#: TableState value columns (all but `key`) — the fold adopts the home
#: shard's copy of each of these per slot; keys never fold (pins write
#: the key column identically on every replica, and rows never move).
_VALUE_COLS = tuple(f for f in TableState._fields if f != "key")


def _rep(mesh):
    return NamedSharding(mesh, P(SHARD_AXIS))


def _cfg_of(req: RateLimitRequest) -> tuple:
    """(alg, limit, duration, burst) exactly as pack_requests clamps
    them: a pinned row must agree bit-for-bit with every packed
    request of the same config, or the device reads a config change
    and resets the row."""
    return clamp_config(req.algorithm, req.limit, req.duration,
                        req.burst, req.behavior)


def make_mesh_global_step(mesh, cap: int):
    """Fused serving step over the packed wire layout: decide on each
    shard's replica AND accumulate the wave's applied hits into the
    shard's active accumulator — the "hit accumulators next to the
    bucket table" half of the design.  No collectives here."""

    def _step(state, acc, a64, a32, now):
        st = jax.tree.map(lambda x: x[0], state)
        a = acc[0]
        bt = RequestBatch(
            key=lax.bitcast_convert_type(a64[0], jnp.uint64),
            hits=a64[1], limit=a64[2], duration=a64[3], eff_ms=a64[4],
            greg_end=a64[5], burst=a64[6], now=a64[7],
            behavior=a32[0], algorithm=a32[1], valid=a32[2] != 0)
        st, out = decide_batch_impl(st, bt, now, REPLICA_PROBES)
        # per-slot accumulation: re-probe the (post-step) key column so
        # each applied request's hits land on its row's accumulator
        # slot.  Erred rows (probe window exhausted) never mutated
        # state, so they don't accumulate either.
        kw = split64(bt.key)
        row = _lookup(st.key, _probe_slots(kw, cap, REPLICA_PROBES), kw)
        ok = bt.valid & (row >= 0) & (~out.err)
        wrow = jnp.where(ok, row, cap)
        a = a.at[wrow].add(jnp.where(ok, jnp.maximum(bt.hits, 0), 0),
                           mode="drop")
        packed = jnp.stack([
            out.status.astype(jnp.int64), out.remaining, out.reset_time,
            out.limit, out.err.astype(jnp.int64)])
        return (jax.tree.map(lambda x: x[None], st), a[None], packed)

    return jax.jit(shard_map(
        _step, mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(None, SHARD_AXIS),
                  P(None, SHARD_AXIS), P()),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(None, SHARD_AXIS))))


def make_mesh_global_fold(mesh):
    """The reconcile collective: every replica adopts its home shard's
    row (psum of home-masked columns — the all-reduce that replaces
    the owner broadcast), the retired accumulator psums into per-slot
    hit totals (the conservation ledger), and comes back zeroed."""
    S = SHARD_AXIS
    n = mesh.shape[S]

    def _fold(state, acc):
        st = jax.tree.map(lambda x: x[0], state)
        a = acc[0]
        my = lax.axis_index(S)
        # home shard from the key column itself (hashing.shard_of):
        # ((h >> 32) * n) >> 32 — the exact host formula, on device
        home = ((st.key.hi.astype(jnp.uint64) * jnp.uint64(n))
                >> jnp.uint64(32)).astype(jnp.int32)
        mine = (home == my) & ~is_empty(st.key)

        def adopt(col):
            # word by word: exactly one replica is a slot's home, so
            # the sum IS the home's word
            return lax.psum(jnp.where(mine, col, jnp.zeros_like(col)), S)

        # keys never fold: identical on every replica by pinning
        folded = st._replace(**{f: jax.tree.map(adopt, getattr(st, f))
                                for f in _VALUE_COLS})
        slot_tot = lax.psum(a, S)
        return (jax.tree.map(lambda x: x[None], folded),
                jnp.zeros_like(a)[None], slot_tot)

    return jax.jit(shard_map(
        _fold, mesh=mesh,
        in_specs=(P(S), P(S)),
        out_specs=(P(S), P(S), P())))


class MeshGlobalEngine:
    """Host manager of the mesh-resident GLOBAL tier.

    Pins keys to fixed probe-path slots (deterministic across
    replicas), routes each request to its HOME
    shard's sub-batch, and runs the reconcile collective on the
    GlobalSyncWait tick (driven by GlobalManager's mesh backend).
    """

    def __init__(self, mesh, capacity: int = 4096,
                 batch_per_chip: int = 512):
        self.mesh = mesh
        self.n = mesh.shape[SHARD_AXIS]
        self.capacity = capacity
        self.B = batch_per_chip
        #: serializes pin/unpin mutations of the slot maps (reads of
        #: the dicts are GIL-atomic snapshots)
        self._mu = threading.Lock()
        self.slots: Dict[int, int] = {}
        #: key_hash → (alg, limit, duration, burst)
        self.pinned_cfg: Dict[int, tuple] = {}
        #: demoted keys keep their slot + device row (clearing the key
        #: would let an in-flight request insert a phantom fresh
        #: bucket)
        self._retired: Dict[int, int] = {}
        self._occupied: set = set()
        #: serializes every state/accumulator read-modify-write
        #: (request steps, the fold, pins)
        self._state_mu = threading.Lock()
        base = init_table(capacity)
        rep = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (self.n,) + x.shape),
            base)
        sh = _rep(mesh)
        self.state: TableState = jax.tree.map(
            lambda x: jax.device_put(x, sh), rep)
        #: double-buffered per-shard hit accumulators: serving writes
        #: the ACTIVE buffer; the fold reads the retired one
        self._acc = [
            jax.device_put(jnp.zeros((self.n, capacity), jnp.int64), sh),
            jax.device_put(jnp.zeros((self.n, capacity), jnp.int64), sh)]
        self._active = 0  # guarded-by: self._state_mu
        self._step = make_mesh_global_step(mesh, capacity)
        self._fold = make_mesh_global_fold(mesh)
        #: reconcile bookkeeping (host side)
        self.generation = 0  # guarded-by: self._state_mu
        self.folded_hits = 0  # guarded-by: self._state_mu
        self.injected_hits = 0  # guarded-by: self._state_mu
        self.last_staleness_s = 0.0  # guarded-by: self._state_mu
        self._first_unfolded_t: Optional[float] = None  # guarded-by: self._state_mu
        #: pending async fold results: (slot_totals array, launch time)
        self._pending: List[tuple] = []  # guarded-by: self._state_mu

    @property
    def fold_nbytes(self) -> int:
        """Per-replica bytes the reconcile collective moves: every
        TableState value column (int64) plus the retired accumulator
        — the cost model's (bytes, ndev) feature for global_fold."""
        return (len(_VALUE_COLS) + 1) * self.capacity * 8

    # ---- host slot management ------------------------------------------

    def _probe_slots_host(self, key_hash: int) -> List[int]:
        k = np.uint64(key_hash)
        stride = int((k >> np.uint64(17)) | np.uint64(1))
        return [int((int(k) + p * stride) & (self.capacity - 1))
                for p in range(REPLICA_PROBES)]

    def is_pinned(self, key_hash: int) -> bool:
        return key_hash in self.slots

    def matches_pinned(self, key_hash: int, req: RateLimitRequest) -> bool:
        return self.pinned_cfg.get(key_hash) == _cfg_of(req)

    def probe_occupants(self, key_hash: int) -> List[int]:
        """Pinned keys whose slots occupy ``key_hash``'s probe window —
        the overflow-admission read: a cap overflow demotes the coldest
        of these (by sketch rank) instead of silently declining."""
        with self._mu:
            window = set(self._probe_slots_host(key_hash))
            return [k for k, s in self.slots.items() if s in window]

    def pin_many(self, entries: Sequence[tuple], now_ms: int) -> List[bool]:
        """Pin several keys in ONE device upload set.  ``entries``:
        (req, key_hash, seed-or-None) — seed carries the key's sharded
        row so pre-tier consumption survives promotion into the mesh.
        Returns per-entry success (False: probe window full — the
        request stays on the sharded path, which is always correct)."""
        ok = [False] * len(entries)
        placed: List[tuple] = []  # (slot, host row dict)
        with self._mu:
            for j, (req, kh, seed) in enumerate(entries):
                if kh in self.slots:
                    ok[j] = True
                    continue
                if kh in self._retired:
                    slot = self._retired.pop(kh)
                else:
                    probes = self._probe_slots_host(kh)
                    slot = next((s for s in probes
                                 if s not in self._occupied), None)
                    if slot is None:
                        retired_by_slot = {s: k for k, s in
                                           self._retired.items()}
                        slot = next((s for s in probes
                                     if s in retired_by_slot), None)
                        if slot is None:
                            continue  # window full → sharded path
                        del self._retired[retired_by_slot[slot]]
                    else:
                        self._occupied.add(slot)
                self.slots[kh] = slot
                self.pinned_cfg[kh] = _cfg_of(req)
                placed.append((slot, self._fresh_row(req, kh, now_ms,
                                                     seed)))
                ok[j] = True
        if not placed:
            return ok
        with self._state_mu:
            cols = to_host(self.state)
            for slot, host in placed:
                for f, col in cols.items():
                    col[:, slot] = host[f]
            self.state = jax.device_put(from_host(cols), _rep(self.mesh))
        return ok

    def pin(self, req: RateLimitRequest, key_hash: int, now_ms: int,
            seed: Optional[dict] = None) -> bool:
        return self.pin_many([(req, key_hash, seed)], now_ms)[0]

    @staticmethod
    def _fresh_row(req: RateLimitRequest, key_hash: int, now_ms: int,
                   seed: Optional[dict]) -> dict:
        """Initial replica row — the packer-exact eff/burst math
        (core/batch.py clamps)."""
        alg, limit, dur, burst = _cfg_of(req)
        eff = max(int(dur), 1)
        if alg:
            eff = min(eff, EFF_MAX)
        rem0 = burst * eff if alg else limit
        host = {
            "key": np.uint64(key_hash), "meta": np.int32(alg),
            "limit": np.int64(limit), "duration": np.int64(dur),
            "eff_ms": np.int64(eff), "burst": np.int64(burst),
            "remaining": np.int64(rem0), "t_ms": np.int64(now_ms),
            "expire_at": np.int64(now_ms + eff),
        }
        if seed is not None:
            for f in ("remaining", "t_ms", "expire_at", "meta"):
                host[f] = host[f].dtype.type(seed[f])
        return host

    def unpin(self, key_hash: int) -> None:
        with self._mu:
            slot = self.slots.pop(key_hash, None)
            self.pinned_cfg.pop(key_hash, None)
            if slot is not None:
                self._retired[key_hash] = slot

    def pinned_keys(self) -> List[int]:
        with self._mu:
            return list(self.slots)

    def row_state(self, key_hash: int) -> Optional[dict]:
        """The key's HOME replica row — exact without any collective
        (home routing means only the home shard's copy ever moves), so
        demotion/stand-down migrate state even when the fold is the
        thing that is broken."""
        slot = self.slots.get(key_hash)
        if slot is None:
            return None
        home = int(shard_of(int(key_hash), self.n))
        with self._state_mu:
            return {f: col[home, slot]
                    for f, col in to_host(self.state).items() if f != "key"}

    # ---- request path ---------------------------------------------------

    def warmup(self, now_ms: int = 1) -> None:
        """Pre-compile the serving step AND the fold (all-invalid wave,
        zero accumulators: no state change).  Without this the
        first-touch compile lands inside a caller's GLOBAL request —
        on CPU long enough that a short-duration bucket idle-expires
        between the first and second call (observed: a 5 s bucket
        reset by the compile stall).  V1Instance warms the tier at
        construction, the same contract as the sharded engine's
        daemon-startup warmup."""
        self._run_wave(empty_batch(self.n * self.B), now_ms)
        self.fold(self.swap_accum())

    def check_columns(self, batch: RequestBatch, khash: np.ndarray,
                      now_ms: int) -> tuple:
        """Serve pinned GLOBAL requests, HOME-shard routed: numpy
        RequestBatch columns in, (status, remaining, reset_time, limit,
        row_lost) columns out.  The home replica sees every hit for its
        keys, so decisions are exact — bit-identical to the
        owner-sharded path on the same traffic."""
        n_req = len(khash)
        status = np.zeros(n_req, np.int64)
        rem = np.zeros(n_req, np.int64)
        rst = np.zeros(n_req, np.int64)
        lim = np.zeros(n_req, np.int64)
        lost = np.zeros(n_req, bool)
        home = shard_of(np.asarray(khash, np.uint64), self.n)
        by_time = np.argsort(np.asarray(batch.now), kind="stable")
        pending = by_time.tolist()
        inj = int(np.maximum(
            np.asarray(batch.hits)[np.asarray(batch.valid)], 0).sum())
        while pending:
            fill = [0] * self.n
            wave, rest, positions = [], [], []
            for i in pending:
                h = int(home[i])
                if fill[h] < self.B:
                    positions.append(h * self.B + fill[h])
                    fill[h] += 1
                    wave.append(i)
                else:
                    rest.append(i)
            idx = np.asarray(wave, np.int64)
            pos = np.asarray(positions, np.int64)
            glob = empty_batch(self.n * self.B)
            for f in range(len(glob)):
                np.asarray(glob[f])[pos] = np.asarray(batch[f])[idx]
            o_st, o_rem, o_rst, o_lim, o_err = self._run_wave(glob,
                                                              now_ms)
            status[idx] = o_st[pos]
            rem[idx] = o_rem[pos]
            rst[idx] = o_rst[pos]
            lim[idx] = o_lim[pos]
            lost[idx] = o_err[pos]
            pending = rest
        with self._state_mu:
            self.injected_hits += inj
            if inj and self._first_unfolded_t is None:
                self._first_unfolded_t = time.monotonic()
        return status, rem, rst, lim, lost

    def check_batch(self, reqs: Sequence[RateLimitRequest],
                    key_hashes: Sequence[int], now_ms: int
                    ) -> List[RateLimitResponse]:
        """Object-lane wrapper over ``check_columns``."""
        khash = np.asarray(list(key_hashes), np.uint64)
        batch, _ = pack_requests(list(reqs), now_ms, size=len(reqs),
                                 key_hashes=khash)
        st, rem, rst, lim, lost = self.check_columns(batch, khash,
                                                     now_ms)
        return [RateLimitResponse(
            status=Status(int(st[i])), limit=int(lim[i]),
            remaining=int(rem[i]), reset_time=int(rst[i]),
            error="mesh-global row lost" if lost[i] else "")
            for i in range(len(reqs))]

    def _run_wave(self, glob: RequestBatch, now_ms: int):
        a64, a32 = pack_wave_host(glob)
        sh = NamedSharding(self.mesh, P(None, SHARD_AXIS))
        d64 = jax.device_put(a64, sh)
        d32 = jax.device_put(a32, sh)
        with self._state_mu:
            acc = self._acc[self._active]
            with XLA_EXEC_MU:
                self.state, self._acc[self._active], packed = \
                    self._step(self.state, acc, d64, d32,
                               jnp.asarray(now_ms, jnp.int64))
        out = np.asarray(packed)
        return out[0], out[1], out[2], out[3], out[4] != 0

    # ---- fused-engine hooks (ISSUE 8) ----------------------------------

    def run_fused(self, fn):
        """One fused serving launch under the tier's state lock: the
        fused engine (parallel/pallas_engine.py › FusedServingMixin)
        folds this tier's home-replica decide AND the accumulator
        scatter-add into ITS wave program, deleting the separate
        serving dispatch this class's ``check_columns`` costs.
        ``fn(state, active_acc)`` must return (new_state, new_acc,
        result); both store back atomically w.r.t. the fold/pins —
        the double-buffer discipline holds because the launch writes
        only the ACTIVE buffer (the fold reads retired ones)."""
        # contended by the fold tick and by handlers pinning keys
        wait = phase("lock.mesh_state").begin()
        with self._state_mu:
            wait.end()
            st, acc, result = fn(self.state, self._acc[self._active])
            self.state = st
            self._acc[self._active] = acc
            return result

    def note_injected(self, hits: int) -> None:
        """Conservation-ledger feed for fused waves: the fused step
        counts applied mesh hits on device (the exact amount its
        scatter added to the active accumulator), so the
        folded == injected oracle stays exact across both serving
        paths."""
        if hits <= 0:
            return
        with self._state_mu:
            self.injected_hits += hits
            if self._first_unfolded_t is None:
                self._first_unfolded_t = time.monotonic()

    # ---- the reconcile collective --------------------------------------

    def swap_accum(self) -> int:
        """Retire the active accumulator buffer (new hits land in the
        fresh one) and return its index for ``fold``.  The caller (the
        instance's reconcile tick) fires the ``global_accum_swap``
        faultpoint BEFORE calling this, so an injected error leaves the
        buffers untouched — nothing is ever mid-swap."""
        with self._state_mu:
            retired = self._active
            self._active ^= 1
        return retired

    def swap_back(self) -> None:
        """Undo ``swap_accum`` after a failed fold: the retired buffer
        (still holding its unfolded hits) becomes active again, so no
        accumulated hit is ever stranded.  Exact because the tick holds
        the reconcile path single-threaded (GlobalManager's loop)."""
        with self._state_mu:
            self._active ^= 1

    def fold(self, retired: int) -> None:
        """Launch the reconcile collective over the retired buffer —
        asynchronously: the host does not block on the psum (TokenWeave
        overlap); results drain on the next tick or stats read."""
        t0 = time.monotonic()
        with self._state_mu:
            with XLA_EXEC_MU:
                self.state, self._acc[retired], slot_tot = self._fold(
                    self.state, self._acc[retired])
            self.generation += 1
            stale = (t0 - self._first_unfolded_t
                     if self._first_unfolded_t is not None else 0.0)
            self.last_staleness_s = max(stale, 0.0)
            self._first_unfolded_t = None
            self._pending.append(slot_tot)

    def drain(self) -> None:
        """Materialize pending fold totals into ``folded_hits`` (blocks
        on any fold still in flight — call off the serving path)."""
        with self._state_mu:
            pending, self._pending = self._pending, []
            for slot_tot in pending:
                self.folded_hits += int(np.asarray(slot_tot).sum())

    def stats(self) -> dict:
        self.drain()
        with self._state_mu:
            return {
                "generation": self.generation,
                "pinned_keys": len(self.slots),
                "capacity": self.capacity,
                "n_shards": self.n,
                "injected_hits": self.injected_hits,
                "folded_hits": self.folded_hits,
                "last_staleness_s": round(self.last_staleness_s, 6),
            }
