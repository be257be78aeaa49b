"""Replicated hot-set engine: GLOBAL rate limits as one psum per tick.

This is the TPU-native replacement for the reference's entire GLOBAL
replication machinery (global.go › runAsyncHits + runBroadcasts +
UpdatePeerGlobals — reconstructed; SURVEY.md §2.3/§3.3): instead of
non-owners queueing hits over gRPC to an owner which broadcasts merged
state back, every chip holds a full replica of a small "hot set" table
and serves GLOBAL decisions locally; consumption deltas are folded
across the mesh with a single ``lax.psum`` on the sync tick.  Traffic
per tick is O(hot-set size), independent of request rate — the pod acts
as one coherent rate-limit region with read-local latency.

Scope (enforced by the host router): TOKEN_BUCKET or LEAKY_BUCKET keys
with stable (algorithm, limit, duration, burst) and no
RESET/DRAIN/Gregorian flags — the shape of real-world hot global
limits.  Everything else takes the owner-sharded path
(parallel/sharded.py), which is already coherent.

Merge semantics (per slot, between syncs; replicas start identical at
``base``):

TOKEN_BUCKET:

- a replica that saw ``now ≥ expire`` re-created the bucket fresh
  (detected as ``t_i != base.t``); ``any_refresh`` adopts the latest
  re-creation via pmax of timestamps,
- per-replica consumption ``d_i = (limit if refreshed_i else base.rem)
  - rem_i``  (≥ 0),
- merged ``rem = clamp((limit if any_refresh else base.rem) - Σ d_i,
  0, limit)``.

LEAKY_BUCKET (``remaining`` is token-duration fixed point, replenished
``limit`` per ``eff_ms`` up to ``burst × eff_ms`` — core/table.py): a
replica's timestamp moves on *every* touch, so refresh detection is
meaningless; instead consumption is measured against the base
replenished to the replica's own clock:

- ``rep(t) = min(base.rem + clamp(t - base.t) × limit, burst × eff)``,
- per-replica consumption ``d_i = max(rep(t_i) - rem_i, 0)``,
- merged at ``T = pmax(t_i)``: ``rem = clamp(rep(T) - Σ d_i, 0,
  burst × eff)``.

A replica whose row expired (idle > duration) re-creates it at
``burst × eff``; ``rep(t_i)`` saturates at the same ceiling by then, so
the merge needs no special refresh case.  (If ``burst > limit`` and the
bucket was deeply drained, a refresh can forgive un-replenished debt —
bounded by one bucket, inside GLOBAL's eventual-consistency contract.)

Within one sync window total admissions across the mesh can exceed the
limit by at most (n_chips - 1) × per-window consumption — the same
eventual-consistency window the reference's GLOBAL behavior documents;
tests assert convergence and post-sync conservation.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.batch import (RequestBatch, clamp_config,
                          empty_batch, pack_requests)
from ..core.step import REPLICA_PROBES, decide_batch_impl, divmod_nn
from ..core.table import (TableState, from_host, init_table, join64,
                          split64, to_host)
from ..types import EFF_MAX, RateLimitRequest, RateLimitResponse, Status
from .mesh import SHARD_AXIS


def _rep(mesh):
    return NamedSharding(mesh, P(SHARD_AXIS))


def _cfg_of(req: RateLimitRequest) -> tuple:
    """(alg, limit, duration, burst) exactly as pack_requests clamps them
    — the pinned row must agree with every packed request that hits it,
    else the device step would see a config change and reset the row."""
    return clamp_config(req.algorithm, req.limit, req.duration, req.burst,
                        req.behavior)


def make_hot_step(mesh):
    """Per-chip replica apply over the packed wire layout
    (sharded.py › PACK64/PACK32: 2 uploads + 1 download per wave):
    state has leading [n] device axis; each chip runs the full decision
    program on its own replica and its own sub-batch.  No collectives
    on the request path."""

    def _step(state, a64, a32, now):
        st = jax.tree.map(lambda x: x[0], state)
        bt = RequestBatch(
            key=lax.bitcast_convert_type(a64[0], jnp.uint64),
            hits=a64[1], limit=a64[2], duration=a64[3], eff_ms=a64[4],
            greg_end=a64[5], burst=a64[6], now=a64[7],
            behavior=a32[0], algorithm=a32[1], valid=a32[2] != 0)
        st, out = decide_batch_impl(st, bt, now, REPLICA_PROBES)
        st = jax.tree.map(lambda x: x[None], st)
        packed = jnp.stack([
            out.status.astype(jnp.int64), out.remaining, out.reset_time,
            out.limit, out.err.astype(jnp.int64)])
        return st, packed

    return jax.jit(shard_map(
        _step, mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(None, SHARD_AXIS),
                  P(None, SHARD_AXIS), P()),
        out_specs=(P(SHARD_AXIS), P(None, SHARD_AXIS))))


def make_hot_sync(mesh):
    """The psum fold: merge per-replica consumption into a new common
    base — the entire global.go subsystem as one collective."""
    S = SHARD_AXIS

    def _sync(state, base_rem, base_t):
        st = jax.tree.map(lambda x: x[0], state)
        brem, bt = base_rem[0], base_t[0]
        # a replica map is smaller than a wave: its rows as int64
        limit, t_ms, rem = (join64(st.limit), join64(st.t_ms),
                            join64(st.remaining))
        is_leaky = (st.meta & 1) == 1
        # --- token: refresh detection + consumption vs (refreshed) base
        refreshed = (~is_leaky) & (t_ms != bt)
        any_refresh = lax.pmax(refreshed.astype(jnp.int32), S) > 0
        start = jnp.where(refreshed, limit, brem)
        d_tok = jnp.maximum(start - rem, 0)
        # --- leaky: consumption vs base replenished to the replica's t.
        # elapsed is clamped so elapsed × limit cannot wrap int64: leaky
        # burst ≤ TD_BOUND // eff per the packer clamps, so cap_td ≤ 2^61
        # and the clamped product ≤ cap_td + limit < 2^62.  eff is masked
        # to 1 on token rows (stored token eff can reach DURATION_MAX =
        # 2^53; an unmasked product would wrap even though d_leaky is
        # discarded by the is_leaky select).
        eff = jnp.maximum(jnp.where(is_leaky, join64(st.eff_ms), 1), 1)
        cap_td = join64(st.burst) * eff
        el_max = divmod_nn(cap_td, jnp.maximum(limit, 1))[0] + 1

        def rep_at(t):
            el = jnp.clip(t - bt, 0, el_max)
            return jnp.minimum(brem + el * limit, cap_td)

        d_leaky = jnp.maximum(rep_at(t_ms) - rem, 0)
        d = jnp.where(is_leaky, d_leaky, d_tok)
        total = lax.psum(d, S)
        new_t = lax.pmax(t_ms, S)
        merged_base = jnp.where(any_refresh, limit, brem)
        new_rem_tok = jnp.clip(merged_base - total, 0, limit)
        new_rem_leaky = jnp.clip(rep_at(new_t) - total, 0, cap_td)
        new_rem = jnp.where(is_leaky, new_rem_leaky, new_rem_tok)
        new_exp = lax.pmax(join64(st.expire_at), S)
        st = st._replace(remaining=split64(new_rem), t_ms=split64(new_t),
                         expire_at=split64(new_exp))
        out_state = jax.tree.map(lambda x: x[None], st)
        return out_state, new_rem[None], new_t[None]

    return jax.jit(shard_map(
        _sync, mesh=mesh,
        in_specs=(P(S), P(S), P(S)),
        out_specs=(P(S), P(S), P(S))))


class HotSetEngine:
    """Host-managed replicated hot-set over a mesh.

    The host pins keys to fixed slots (deterministic across replicas —
    the property open addressing can't give divergent replicas), routes
    qualifying GLOBAL requests here round-robin across chips, and calls
    ``sync()`` on the GlobalSyncWait tick.
    """

    def __init__(self, mesh, capacity: int = 1024, batch_per_chip: int = 512):
        self.mesh = mesh
        self.n = mesh.shape[SHARD_AXIS]
        self.capacity = capacity
        self.B = batch_per_chip
        self.slots: Dict[int, int] = {}  # key_hash → slot
        #: key_hash → (alg, limit, duration, burst) — see _cfg_of
        self.pinned_cfg: Dict[int, tuple] = {}
        #: Demoted keys keep their slot reserved (and their device row in
        #: place): clearing the key column would let an in-flight hot
        #: request re-insert a phantom fresh bucket, and re-pinning at a
        #: different probe slot would be shadowed by the stale row.
        self._retired: Dict[int, int] = {}
        self._occupied: set = set()
        self._mu = threading.Lock()
        #: Serializes every state read-modify-write (request steps, the
        #: sync tick, pins): a sync computed from pre-step state would
        #: otherwise overwrite a concurrent step's consumption.
        self._state_mu = threading.Lock()
        # state with leading device axis [n, cap]: one replica per chip
        base = init_table(capacity)
        rep = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (self.n,) + x.shape), base)
        sh = _rep(mesh)
        self.state: TableState = jax.tree.map(
            lambda x: jax.device_put(x, sh), rep)
        self.base_rem = jax.device_put(
            jnp.zeros((self.n, capacity), jnp.int64), sh)
        self.base_t = jax.device_put(
            jnp.zeros((self.n, capacity), jnp.int64), sh)
        self._step = make_hot_step(mesh)
        self._sync = make_hot_sync(mesh)
        self._rr = 0  # round-robin cursor across chips
        self.sync_count = 0

    # ---- host slot management ------------------------------------------

    def _probe_slots_host(self, key_hash: int) -> List[int]:
        """The key's probe sequence — MUST match core/step.py ›
        _probe_slots, since the device kernel looks keys up by probing;
        a pinned key outside its probe window would be invisible."""
        k = np.uint64(key_hash)
        stride = int((k >> np.uint64(17)) | np.uint64(1))
        return [int((int(k) + p * stride) & (self.capacity - 1))
                for p in range(REPLICA_PROBES)]

    def pin(self, req: RateLimitRequest, key_hash: int, now_ms: int,
            seed: Optional[dict] = None) -> bool:
        """Assign an on-probe-path slot and initialize the bucket on
        every replica.  ``seed`` carries the key's current row state
        from the owner-sharded table (promotion must NOT forget hits
        already consumed); without it the bucket starts fresh.  Returns
        False when the key's probe window is fully occupied (hot sets
        are sized sparse, so this is rare)."""
        with self._mu:
            if key_hash in self.slots:
                return True
            if key_hash in self._retired:
                slot = self._retired.pop(key_hash)  # reuse: row is there
            else:
                probes = self._probe_slots_host(key_hash)
                slot = next((s for s in probes
                             if s not in self._occupied), None)
                if slot is None:
                    # reclaim a retired slot in the window: its old key
                    # was demoted (state already migrated out), so the
                    # stale row may be overwritten.  Without this,
                    # promote/demote churn would exhaust capacity.
                    retired_by_slot = {s: k for k, s in
                                       self._retired.items()}
                    slot = next((s for s in probes
                                 if s in retired_by_slot), None)
                    if slot is None:
                        return False
                    del self._retired[retired_by_slot[slot]]
                else:
                    self._occupied.add(slot)
            self.slots[key_hash] = slot
            self.pinned_cfg[key_hash] = _cfg_of(req)
        alg, limit, dur, burst = _cfg_of(req)
        # Effective denominator exactly as the packers compute it
        # (core/batch.py): floor at 1; leaky additionally clamps to
        # EFF_MAX (the td-bound contract).  Gregorian is _HOT_EXCLUDED,
        # so the non-calendar branch is the only one.  Seeding eff from
        # the raw duration would disagree with every packed request
        # (spurious per-step "eff change") and burst × dur could wrap
        # int64 at calendar-scale durations.
        eff = max(int(dur), 1)
        if alg:
            eff = min(eff, EFF_MAX)
        # fresh leaky buckets start at burst × eff token-duration fixed
        # point; token buckets at limit (core/step.py › rem_fresh)
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
        # one tiny device_put per column: pin is rare (promotion only)
        with self._state_mu:
            cols = to_host(self.state)
            for f, col in cols.items():
                col[:, slot] = host[f]
            self.state = jax.device_put(from_host(cols), _rep(self.mesh))
            br = np.asarray(self.base_rem).copy()
            br[:, slot] = host["remaining"]
            self.base_rem = jax.device_put(br, _rep(self.mesh))
            bt = np.asarray(self.base_t).copy()
            bt[:, slot] = host["t_ms"]
            self.base_t = jax.device_put(bt, _rep(self.mesh))
        return True

    def is_pinned(self, key_hash: int) -> bool:
        return key_hash in self.slots

    def matches_pinned(self, key_hash: int, req: RateLimitRequest) -> bool:
        return self.pinned_cfg.get(key_hash) == _cfg_of(req)

    def row_state(self, key_hash: int) -> Optional[dict]:
        """Merged row values for a pinned key (call ``sync()`` first —
        post-sync all replicas agree; replica 0 is read).  Used to
        migrate state back to the sharded table on demotion."""
        slot = self.slots.get(key_hash)
        if slot is None:
            return None
        with self._state_mu:
            return {f: col[0, slot]
                    for f, col in to_host(self.state).items() if f != "key"}

    def unpin(self, key_hash: int) -> None:
        """Stop hot-routing a key.  The slot stays reserved and the
        device row stays in place (see ``_retired``); hits from requests
        already in flight land on the retired row and are lost — a
        bounded, demotion-only window consistent with GLOBAL's
        eventual-consistency contract."""
        with self._mu:
            slot = self.slots.pop(key_hash, None)
            self.pinned_cfg.pop(key_hash, None)
            if slot is not None:
                self._retired[key_hash] = slot

    def unpin_all(self) -> None:
        with self._mu:
            self.slots.clear()
            self.pinned_cfg.clear()
            self._retired.clear()
            self._occupied.clear()

    # ---- request path ---------------------------------------------------

    def _run_hot_wave(self, glob: RequestBatch, now_ms: int):
        """One replica-step launch over the packed layout: 2 uploads +
        1 download.  ``glob`` holds [n·B] numpy columns in block order;
        returns (status, remaining, reset_time, limit, lost) arrays."""
        from .sharded import pack_wave_host

        a64, a32 = pack_wave_host(glob)
        sh = NamedSharding(self.mesh, P(None, SHARD_AXIS))
        d64 = jax.device_put(a64, sh)
        d32 = jax.device_put(a32, sh)
        with self._state_mu:
            self.state, packed = self._step(
                self.state, d64, d32, jnp.asarray(now_ms, jnp.int64))
        out = np.asarray(packed)
        return out[0], out[1], out[2], out[3], out[4] != 0

    def check_batch(self, reqs: Sequence[RateLimitRequest],
                    key_hashes: Sequence[int], now_ms: int
                    ) -> List[RateLimitResponse]:
        """Serve pinned GLOBAL requests: spread across chips round-robin
        (any replica answers), one device launch, no collectives."""
        n_req = len(reqs)
        responses: List[Optional[RateLimitResponse]] = [None] * n_req
        pending = list(range(n_req))
        while pending:
            wave, rest = pending[: self.n * self.B], pending[self.n * self.B:]
            # pack the whole wave once, then place with one fancy index
            packed, _ = pack_requests(
                [reqs[i] for i in wave], now_ms, size=len(wave),
                key_hashes=np.asarray([key_hashes[i] for i in wave],
                                      np.uint64))
            positions = np.empty(len(wave), np.int64)
            fill = [0] * self.n
            for j, i in enumerate(wave):
                c = self._rr % self.n
                self._rr += 1
                # find a chip with room (wave is bounded so one exists)
                for _ in range(self.n):
                    if fill[c] < self.B:
                        break
                    c = (c + 1) % self.n
                positions[j] = c * self.B + fill[c]
                fill[c] += 1
            glob = empty_batch(self.n * self.B)
            for f in range(len(glob)):
                np.asarray(glob[f])[positions] = packed[f][:len(wave)]
            slot_of = list(zip(wave, positions.tolist()))
            status, rem, rst, lim, err = self._run_hot_wave(glob, now_ms)
            for i, pos in slot_of:
                responses[i] = RateLimitResponse(
                    status=Status(int(status[pos])), limit=int(lim[pos]),
                    remaining=int(rem[pos]), reset_time=int(rst[pos]),
                    error="hot-set row lost" if err[pos] else "")
            pending = rest
        return responses  # type: ignore[return-value]

    def check_columns(self, batch: RequestBatch, khash: np.ndarray,
                      now_ms: int) -> tuple:
        """Columnar twin of ``check_batch`` (the wire lane's GLOBAL
        path): numpy RequestBatch columns in, response columns out —
        (status, remaining, reset_time, limit, row_lost) arrays.  Any
        replica answers; placement round-robins across chips."""
        n_req = len(khash)
        status = np.zeros(n_req, np.int64)
        rem = np.zeros(n_req, np.int64)
        rst = np.zeros(n_req, np.int64)
        lim = np.zeros(n_req, np.int64)
        lost = np.zeros(n_req, bool)
        W = self.n * self.B
        # earliest requests take the earliest waves (same rule as
        # check_packed): merged batches spanning instants keep per-key
        # time monotone across internal waves too
        by_time = np.argsort(np.asarray(batch.now), kind="stable")
        done = 0
        while done < n_req:
            m = min(W, n_req - done)
            idx = by_time[done:done + m]  # original indices, time order
            p = np.arange(m)
            chip = (self._rr + p) % self.n
            self._rr += m
            # fill order per chip → block positions [chip·B + row]
            order = np.argsort(chip, kind="stable")
            cs = chip[order]
            starts = np.searchsorted(cs, np.arange(self.n))
            rowin = np.empty(m, np.int64)
            rowin[order] = np.arange(m) - starts[cs]
            positions = chip * self.B + rowin
            glob = empty_batch(W)
            for f in range(len(glob)):
                np.asarray(glob[f])[positions] = np.asarray(batch[f])[idx]
            o_st, o_rem, o_rst, o_lim, o_err = self._run_hot_wave(
                glob, now_ms)
            status[idx] = o_st[positions]
            rem[idx] = o_rem[positions]
            rst[idx] = o_rst[positions]
            lim[idx] = o_lim[positions]
            lost[idx] = o_err[positions]
            done += m
        return status, rem, rst, lim, lost

    # ---- the tick -------------------------------------------------------

    def sync(self) -> None:
        """Fold all replicas' consumption: ONE psum replaces the
        reference's hit-queue flush + owner broadcast round-trip."""
        with self._state_mu:
            self.state, self.base_rem, self.base_t = self._sync(
                self.state, self.base_rem, self.base_t)
        self.sync_count += 1
