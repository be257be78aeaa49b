"""The batch decision step: one jit program per GetRateLimits batch.

TPU-native replacement for the reference hot path (gubernator.go ›
getLocalRateLimit → algorithms.go › tokenBucket/leakyBucket over an LRU
map — reconstructed): hash-probe the key column → row indices (inserting
misses), gather row state, apply both algorithms branchlessly, scatter
back, return per-request (status, remaining, reset_time).

Duplicate keys inside a batch must behave exactly as the reference's
sequential per-request processing (SURVEY.md §7.3 "parity under
batching").  Requests are sorted by row (stable, preserving request
order); each segment (same key) is applied serially-equivalently:

- position 0 of every segment runs the full per-request transition
  vectorized across segments;
- "simple" tails (uniform request fields, no RESET/DRAIN flags) have a
  closed form: with per-request cost c and remaining r after position 0,
  position j ≥ 1 is admitted iff j ≤ r // c;
- LEAKY tails with uniform config but MIXED arrival times take a
  speculative segmented associative scan (maps x → min(m, x+b) compose
  closedly); segments where the speculation fails (any deny) fall back
  to the loop below;
- everything else (mixed hits/configs/flags on one key, or mixed-time
  leaky segments that actually deny) runs a while_loop over in-segment
  positions, vectorized across segments — bounded by the longest such
  segment, zero iterations when absent.

All arithmetic is int64 (x64 enabled); semantics match oracle.py
bit-for-bit — the parity tests enforce this on random + Zipf streams.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ..types import FRAC_SAFE, TD_BOUND, Algorithm, Behavior
from .batch import RequestBatch
from .table import (TableState, Words, match_rows, put_rows, split64,
                    take_rows)

#: probe window of the column table, per lookup: a constant of the
#: table, not an option (a window shorter than the depth a snapshot's
#: rows were placed at would hide them).  Sized for the north-star
#: deployment: 10M keys in CAP 2^26, load 0.149.
#: First-free placement leaves a key without a slot in one key set in
#: N * load^P / (P + 1): at P = 8 that is 0.27 — about one 10M key set
#: in four loses a key, and a live key without a slot answers
#: table_full on every request — at P = 16 it is 4e-8.  The first 8
#: probes are the same sequence whatever P, so every row a shorter
#: window placed is still found and snapshots stay valid.
PROBES = 16
#: probe window of the 4,096-slot replica map (parallel/meshglobal.py
#: and the mesh lane of the fused program):
#: its slots are pinned from the host over this window, and it does
#: not grow with the table's
REPLICA_PROBES = 8
INSERT_ROUNDS = 4  # slot-claim rounds per batch


def _long_divmod(n, d) -> tuple[jax.Array, jax.Array]:
    """Shift-subtract long division of the operands' 64 bits as
    unsigned words, as a loop unrolled 8-fold (8 trips) — divmod_nn's
    TPU lowering."""
    i64, u64 = jnp.int64, jnp.uint64
    n, d, one = n.astype(u64), d.astype(u64), u64(1)

    def step(_, c):
        q, r, n = c
        r = (r << one) | (n >> u64(63))
        ge = r >= d
        return ((q << one) | ge.astype(u64), jnp.where(ge, r - d, r),
                n << one)

    # zeros that inherit BOTH operands' mesh variance: under shard_map
    # a loop carry must enter as varying as it leaves
    zero = (n | d) & u64(0)
    q, r, _ = lax.fori_loop(0, 64, step, (zero, zero, n | zero), unroll=8)
    return q.astype(i64), r.astype(i64)


def divmod_nn(n, d) -> tuple[jax.Array, jax.Array]:
    """``(n // d, n % d)`` for int64 ``n >= 0`` and ``d >= 1``,
    elementwise (operands broadcast).  Lanes outside that domain return
    garbage; every caller selects them away.

    TPUs have no 64-bit integer divider and XLA:TPU expands EACH int64
    divide into a fully unrolled 64-step long division: ~6 s of compile
    per divide, ~35 divides in the decision step — minutes per wave
    program, longer than the dispatcher's result timeout.  On TPU this
    lowers to the same long division as a short loop (_long_divmod),
    which compiles in a fraction of a second; other platforms divide
    natively."""
    n, d = jnp.broadcast_arrays(jnp.asarray(n, jnp.int64),
                                jnp.asarray(d, jnp.int64))
    return lax.platform_dependent(
        n, d, tpu=_long_divmod,
        default=lambda n, d: (lax.div(n, d), lax.rem(n, d)))


_RESET = int(Behavior.RESET_REMAINING)
_DRAIN = int(Behavior.DRAIN_OVER_LIMIT)
_GREG = int(Behavior.DURATION_IS_GREGORIAN)

_I64_MAX = jnp.iinfo(jnp.int64).max

#: test hook (tests/test_scatter_invariants.py): when True at TRACE
#: time, every step asserts EVERY index vector a scatter makes promises
#: about really satisfies them — wrow must be strictly ascending +
#: unique (unique_indices + indices_are_sorted), _insert's tkey claim
#: vector and body_fn's idxj must be all-distinct (unique_indices).
#: The promises are UB if lied about, and a CPU parity run would not
#: catch the lie.
_CHECK_SCATTER_INVARIANTS = False
_SCATTER_INVARIANT_VIOLATIONS: list = []


#: per-site fire counters: a hook that never ran would make the
#: invariant test pass vacuously
_SCATTER_INVARIANT_CHECKS = {"wrow": 0, "insert_tkey": 0,
                             "body_idxj": 0}


def _record_wrow(wrow_np):
    import numpy as np

    _SCATTER_INVARIANT_CHECKS["wrow"] += 1
    w = np.asarray(wrow_np)
    if not (np.diff(w.astype(np.int64)) > 0).all():
        _SCATTER_INVARIANT_VIOLATIONS.append(("wrow", w.copy()))


def _record_unique(label, idx_np):
    """unique_indices-only promise sites (no sortedness claimed)."""
    import numpy as np

    _SCATTER_INVARIANT_CHECKS[label] += 1
    w = np.asarray(idx_np)
    if np.unique(w).size != w.size:
        _SCATTER_INVARIANT_VIOLATIONS.append((label, w.copy()))


class StepOutput(NamedTuple):
    """Per-request results in original request order."""

    status: jax.Array  # int32[B], Status values
    remaining: jax.Array  # int64[B]
    reset_time: jax.Array  # int64[B]
    limit: jax.Array  # int64[B]
    err: jax.Array  # bool[B], True = table full / dropped
    over_count: jax.Array  # int64, OVER_LIMIT decisions this batch
    insert_count: jax.Array  # int64, new keys inserted


class _Item(NamedTuple):
    """Per-segment item state carried through in-segment positions."""

    alg: jax.Array  # int32
    status: jax.Array  # int32
    limit: jax.Array
    duration: jax.Array
    eff: jax.Array
    burst: jax.Array
    rem: jax.Array
    t: jax.Array
    exp: jax.Array


class _Req(NamedTuple):
    """One request's fields, vectorized across segments."""

    hits: jax.Array
    limit: jax.Array
    duration: jax.Array
    eff: jax.Array
    greg_end: jax.Array
    behavior: jax.Array
    alg: jax.Array
    burst: jax.Array
    now: jax.Array  # per-request arrival time (epoch ms)


def _probe_slots(key: Words, cap: int, probes: int = PROBES
                 ) -> jax.Array:
    """[B, probes] int32 probe sequence (double hashing, odd stride):
    ``(key + p * ((key >> 17) | 1)) & (cap - 1)``.  cap <= 2^31, so only
    the sum's low word counts, and it is computed on the key's words in
    32-bit arithmetic (which wraps as the 64-bit sum's low word does) —
    the host's uint64 formula (sharded.py › _place_rows) gives the same
    slots."""
    u32 = jnp.uint32
    stride = (key.lo >> u32(17)) | (key.hi << u32(15)) | u32(1)
    p = jnp.arange(probes, dtype=u32)
    slots = (key.lo[:, None] + p[None, :] * stride[:, None]) & u32(cap - 1)
    return slots.astype(jnp.int32)


def _first_hit(hit: jax.Array, slots: jax.Array):
    """(any hit bool[B], the slot of the first one int32[B])."""
    fp = jnp.argmax(hit, axis=1)
    return hit.any(axis=1), jnp.take_along_axis(slots, fp[:, None],
                                                axis=1)[:, 0]


def _lookup(tkey: Words, slots: jax.Array, key: Words) -> jax.Array:
    """row int32[B] or -1 — first probe slot holding key."""
    found, row = _first_hit(match_rows(tkey, slots, key)[0], slots)
    return jnp.where(found, row, -1)


def _insert(tkey: Words, slots: jax.Array, key: Words,
            valid: jax.Array, row: jax.Array):
    """Claim first-empty probe slots for missing keys, deterministically.

    Per round: resolve matches (covers same-key losers of earlier
    rounds), pick each active miss's first empty slot, dedupe claims by
    slot (stable sort → lowest request index wins), scatter winners.
    The analog of lrucache.go › Add, without locks: one batch is one
    program, so claim conflicts are resolved by sort order, not mutexes.
    """
    cap = tkey.lo.shape[0]
    B = key.lo.shape[0]
    n_claimed = jnp.asarray(0, jnp.int64)

    for _ in range(INSERT_ROUNDS):
        match, empty = match_rows(tkey, slots, key)
        found, frow = _first_hit(match, slots)
        row = jnp.where((row < 0) & valid & found, frow, row)

        active = valid & (row < 0)
        has_empty, cand = _first_hit(empty, slots)
        cand_eff = jnp.where(active & has_empty, cand, cap)
        order = jnp.argsort(cand_eff, stable=True)
        c_s = cand_eff[order]
        first = jnp.concatenate([jnp.ones(1, bool), c_s[1:] != c_s[:-1]])
        first = first & (c_s < cap)
        # order is a permutation and winning cands are slot-deduped, so
        # both scatters can promise uniqueness (losers get DISTINCT
        # out-of-bounds sentinels, dropped by mode="drop") — without the
        # promise the TPU backend must assume colliding writes and can
        # emit a serialized scatter loop
        winner = jnp.zeros(B, bool).at[order].set(first,
                                                  unique_indices=True)
        claim = jnp.where(winner, cand,
                          cap + jnp.arange(B, dtype=cand.dtype))
        if _CHECK_SCATTER_INVARIANTS:  # traced-ok: test-only scatter-invariant hook, off in production
            jax.debug.callback(_record_unique, "insert_tkey", claim)
        tkey = put_rows(tkey, claim, key)
        row = jnp.where(winner, cand, row)
        n_claimed = n_claimed + winner.sum(dtype=jnp.int64)

    # final resolve for same-key losers of the last round
    found, frow = _first_hit(match_rows(tkey, slots, key)[0], slots)
    row = jnp.where((row < 0) & valid & found, frow, row)
    return tkey, row, n_claimed


def _apply_position(item: _Item, req: _Req):
    """One request applied to its item — the full §2.4 transition,
    vectorized across segments, at the request's OWN arrival time
    (req.now).  Mirrors oracle.apply_token/apply_leaky exactly (same
    operation order, same integer arithmetic).

    Time is clamped per key to never run backward (max with the item's
    clock): a no-op on monotonic streams (where oracle parity is
    asserted), and a sane defined behavior when merged callers' clocks
    invert — without it a leaky replenish would see negative elapsed."""
    i64 = jnp.int64
    now = jnp.maximum(req.now, item.t)
    is_leaky = req.alg == int(Algorithm.LEAKY_BUCKET)
    is_greg = (req.behavior & _GREG) != 0
    reset = (req.behavior & _RESET) != 0
    drain = (req.behavior & _DRAIN) != 0

    # --- fresh determination (missing/expired/algorithm switch)
    fresh = (now >= item.exp) | (item.alg != req.alg)
    # token duration change → recompute expiry from created_at; expiring
    # now means start fresh
    tok_dur_change = (~is_leaky) & (~fresh) & (req.duration != item.duration)
    new_exp_tok = jnp.where(is_greg, req.greg_end, item.t + req.eff)
    exp1 = jnp.where(tok_dur_change, new_exp_tok, item.exp)
    fresh = fresh | (tok_dur_change & (exp1 <= now))

    # --- adopt fresh or existing state
    # Leaky td products multiply by eff only on leaky rows (operand
    # masked to 1/0 otherwise): token hits/limit go up to VALUE_MAX
    # (2^53), so an unmasked product would wrap int64 even though its
    # value is discarded by the jnp.where select.
    eff_l = jnp.where(is_leaky, req.eff, 1)
    tok_exp_fresh = jnp.where(is_greg, req.greg_end, now + req.eff)
    rem_fresh = jnp.where(is_leaky, req.burst, req.limit) * eff_l
    limit0 = jnp.where(fresh, req.limit, item.limit)
    eff0 = jnp.where(fresh, req.eff, item.eff)
    rem0 = jnp.where(fresh, rem_fresh, item.rem)
    t0 = jnp.where(fresh, now, item.t)
    exp0 = jnp.where(fresh, jnp.where(is_leaky, now + req.eff, tok_exp_fresh), exp1)
    status0 = jnp.where(fresh, 0, item.status)

    # --- leaky denominator change → rescale td fixed point.  Whole
    # tokens clamp to TD_BOUND // new_eff (they could not survive the
    # burst cap anyway); the sub-token fraction is kept only while
    # frac × eff fits int64 (both denominators ≤ FRAC_SAFE), else the
    # rescale floors to whole tokens — identical in oracle.apply_leaky.
    leaky_eff_change = is_leaky & (~fresh) & (req.eff != eff0)
    old_eff = jnp.maximum(eff0, 1)
    whole, frac = divmod_nn(rem0, old_eff)
    whole = jnp.minimum(
        whole, divmod_nn(TD_BOUND, jnp.maximum(req.eff, 1))[0])
    frac_ok = (eff0 <= FRAC_SAFE) & (req.eff <= FRAC_SAFE)
    frac_term = divmod_nn(jnp.where(frac_ok, frac, 0) * req.eff,
                          old_eff)[0]
    rem_rescaled = whole * req.eff + frac_term
    rem0 = jnp.where(leaky_eff_change, rem_rescaled, rem0)
    eff0 = jnp.where(is_leaky, req.eff, jnp.where(tok_dur_change, req.eff, eff0))

    # --- RESET_REMAINING (existing items only; fresh items already start
    # full — for leaky that means burst, not limit, as in the oracle)
    reset_live = reset & (~fresh)
    rem0 = jnp.where(reset_live, req.limit * eff_l, rem0)
    status0 = jnp.where(reset_live, 0, status0)
    limit_after_reset = jnp.where(reset_live & (~is_leaky), req.limit, limit0)

    # --- token limit change in place
    tok_lim_change = (~is_leaky) & (req.limit != limit_after_reset)
    rem_adj = jnp.clip(rem0 + req.limit - limit_after_reset, 0, req.limit)
    rem0 = jnp.where(tok_lim_change, rem_adj, rem0)
    limit1 = req.limit

    # --- leaky replenish (exact: elapsed × limit td, clamped to burst).
    # elapsed > TD_BOUND // limit means the true product already exceeds
    # the burst cap (cap_td ≤ TD_BOUND), so the bucket is simply full —
    # the guard is exact, not an approximation (oracle.apply_leaky
    # mirrors it).
    burst1 = jnp.where(is_leaky, req.burst, limit1)
    elapsed = now - t0
    cap_td = burst1 * jnp.where(is_leaky, eff0, 0)
    safe_el = divmod_nn(TD_BOUND, jnp.maximum(limit1, 1))[0]
    rem_rep = jnp.where(
        elapsed > safe_el, cap_td,
        jnp.minimum(rem0 + jnp.minimum(elapsed, safe_el) * limit1, cap_td))
    rem0 = jnp.where(is_leaky, rem_rep, rem0)
    t1 = jnp.where(is_leaky, now, t0)

    rate = jnp.where(limit1 > 0,
                     divmod_nn(eff0, jnp.maximum(limit1, 1))[0], eff0)
    exp_out = jnp.where(is_leaky, now + eff0, exp0)
    # leaky: answered from the request's OWN stamp, not the clamped
    # clock (the older request — oracle.py, "Leaky fixed point")
    reset_time = jnp.where(is_leaky, req.now + rate, exp_out)

    # --- hits
    cost = req.hits * jnp.where(is_leaky, eff0, 1)
    is_query = req.hits == 0
    ok = cost <= rem0
    rem2 = jnp.where((~is_query) & ok, rem0 - cost, rem0)
    rem2 = jnp.where((~is_query) & (~ok) & drain, i64(0), rem2)
    status1 = jnp.where(is_query, status0,
                        jnp.where(ok, 0, 1)).astype(jnp.int32)

    out_rem = jnp.where(
        is_leaky, divmod_nn(rem2, jnp.maximum(eff0, 1))[0], rem2)
    dur1 = req.duration
    new_item = _Item(alg=req.alg, status=status1, limit=limit1, duration=dur1,
                     eff=eff0, burst=burst1, rem=rem2, t=t1, exp=exp_out)
    out = (status1, out_rem, reset_time, limit1)
    return new_item, out


def _tree_where(mask, a, b):
    return jax.tree.map(lambda x, y: jnp.where(mask, x, y), a, b)


def decide_batch_impl(state: TableState, batch: RequestBatch, now_ms: jax.Array,
                      probes: int = PROBES
                      ) -> tuple[TableState, StepOutput]:
    """Apply one request batch to the table; returns (new state, outputs).

    Semantically equivalent to the reference's per-request loop in
    gubernator.go › GetRateLimits over a local cache, for any batch
    composition including duplicate keys.

    Unjitted building block: compose under jit/scan/shard_map.  Use
    ``decide_batch`` for direct host dispatch.  ``probes`` is the probe
    window of ``state`` (the replica maps pass ``REPLICA_PROBES``).
    """
    cap = state.capacity
    B = batch.key.shape[0]
    i32 = jnp.int32
    i64 = jnp.int64
    now = jnp.asarray(now_ms, i64)

    valid = batch.valid & (batch.key != 0)
    key = split64(batch.key)
    # per-request arrival time; 0 entries (padding / legacy callers
    # without the column) fall back to the scalar argument
    if batch.now is None:
        now_col = jnp.full((B,), now, i64)
    else:
        now_col = jnp.where(jnp.asarray(batch.now, i64) > 0,
                            jnp.asarray(batch.now, i64), now)

    # ---- probe / insert -------------------------------------------------
    slots = _probe_slots(key, cap, probes)
    tkey = state.key
    row = _lookup(tkey, slots, key)
    row = jnp.where(valid & (row >= 0), row, -1)
    miss = valid & (row < 0)

    tkey, row, insert_count = lax.cond(
        miss.any(),
        lambda ops: _insert(*ops),
        # zero derived from a varying operand so both branches have the
        # same varying-manual-axes type under shard_map
        lambda ops: (ops[0], ops[4], (ops[4].sum() * 0).astype(i64)),
        (tkey, slots, key, valid, row),
    )
    err = valid & (row < 0)  # probe window exhausted: table overfull
    row = jnp.where(valid & (row >= 0), row, cap)  # cap = dropped sentinel

    # ---- sort into segments ordered by (row, now, original index) ----
    # Two stable sorts = lexicographic: within a key's segment, requests
    # apply in arrival-time order (then original order) — sequential
    # parity even when the dispatcher merges batches from callers whose
    # clocks differ (the oracle, like the reference's sequential loop,
    # assumes per-key time-monotonic application; a time-inverted leaky
    # replenish would see negative elapsed).  Uniform-now batches — the
    # common case: any unmerged call — take the single-sort branch;
    # lax.cond executes only the taken side, so the extra sort costs
    # nothing unless instants actually mixed.
    def _sort_single(_):
        return jnp.argsort(row, stable=True)

    def _sort_by_time(_):
        p0 = jnp.argsort(now_col, stable=True)
        return p0[jnp.argsort(row[p0], stable=True)]

    perm = lax.cond(jnp.all(now_col == now_col[0]),
                    _sort_single, _sort_by_time, None)
    r_s = row[perm]
    head = jnp.concatenate([jnp.ones(1, bool), r_s[1:] != r_s[:-1]])
    seg_id = (jnp.cumsum(head) - 1).astype(i32)
    seg = partial(jax.ops.segment_min, segment_ids=seg_id, num_segments=B)
    seg_max = partial(jax.ops.segment_max, segment_ids=seg_id, num_segments=B)
    seg_start = seg(jnp.arange(B, dtype=i32))
    seg_len = jax.ops.segment_sum(jnp.ones(B, i32), seg_id, num_segments=B)
    seg_row = seg(r_s)
    exists = (seg_len > 0) & (seg_row < cap)

    sf = _Req(
        hits=batch.hits[perm], limit=batch.limit[perm],
        duration=batch.duration[perm], eff=batch.eff_ms[perm],
        greg_end=batch.greg_end[perm], behavior=batch.behavior[perm],
        alg=batch.algorithm[perm], burst=batch.burst[perm],
        now=now_col[perm],
    )

    # A segment is contiguous in the sorted order, so a field is
    # uniform over it iff no position but its head differs from the one
    # before: ONE 32-bit segment reduction for all the fields, where a
    # segment min AND max per int64 field are scatters the chip runs
    # serially (0.57 ms each at B = 8,192 on a v5e: PERF.md §6, PR 32).
    def breaks(*fields):
        differs = jnp.zeros(B - 1, bool)
        for x in fields:
            differs = differs | (x[1:] != x[:-1])
        broken = (~head) & jnp.concatenate([jnp.zeros(1, bool), differs])
        return seg_max(broken.astype(i32)) > 0

    uniform_cfg = ~breaks(sf.hits, sf.limit, sf.duration, sf.eff,
                          sf.behavior, sf.alg, sf.burst)
    uni_now = ~breaks(sf.now)
    any_flag = seg_max((sf.behavior & (_RESET | _DRAIN))) > 0
    # (simple/complex masks are finalized after the head apply: token
    # segments with mixed arrival times can still take the closed form
    # when no tail request crosses the head's window — see below)

    # ---- gather item state per segment ---------------------------------
    def grow(col, fill=0):
        return take_rows(col, seg_row, fill)

    item0 = _Item(
        alg=(grow(state.meta) & 1).astype(i32),
        status=((grow(state.meta) >> 1) & 1).astype(i32),
        limit=grow(state.limit), duration=grow(state.duration),
        eff=grow(state.eff_ms, 1), burst=grow(state.burst),
        rem=grow(state.remaining), t=grow(state.t_ms),
        exp=grow(state.expire_at),
    )

    idx0 = jnp.where(exists, seg_start, B).astype(i32)

    def greq(x):
        return x.at[idx0].get(mode="fill", fill_value=0)

    req0 = _Req(*[greq(f) for f in sf])

    item1, out0 = _apply_position(item0, req0)
    item1 = _tree_where(exists, item1, item0)

    # ---- simple tails: closed form, fully vectorized -------------------
    is_leaky0 = req0.alg == int(Algorithm.LEAKY_BUCKET)
    # Mixed arrival times usually force the per-position path (leaky
    # replenishes per request), but a TOKEN transition is time-invariant
    # except for the expiry check: with uniform config/flags and every
    # tail arrival inside the head's window (max now < item1.exp after
    # the head applied), the decrement-only closed form is exact.  This
    # keeps dispatcher-coalesced concurrent callers — distinct clocks,
    # shared hot keys — on the vectorized path instead of a while_loop
    # as long as the longest such segment (the serving common case).
    # (both sorts leave a segment in arrival order: its last position
    # holds its latest arrival)
    last = jnp.where(exists, seg_start + seg_len - 1, B).astype(i32)
    latest = sf.now.at[last].get(mode="fill", fill_value=0)
    time_safe = uni_now | ((~is_leaky0) & (latest < item1.exp))
    uniform = uniform_cfg & time_safe
    simple = exists & uniform & (~any_flag)
    complex_seg = exists & (seg_len > 1) & (~simple)
    cost0 = req0.hits * jnp.where(is_leaky0, item1.eff, 1)
    k_raw = jnp.where(
        cost0 > 0, divmod_nn(item1.rem, jnp.maximum(cost0, 1))[0],
        _I64_MAX)
    tail_n = jnp.maximum(seg_len - 1, 0).astype(i64)
    k = jnp.minimum(k_raw, tail_n)  # accepted tail requests
    # final per-segment state after the whole tail
    s_rem_final = item1.rem - k * jnp.maximum(cost0, 0)
    s_status_final = jnp.where(
        cost0 > 0, jnp.where(tail_n <= k_raw, 0, 1), item1.status
    ).astype(i32)
    simple_tail_seg = simple & (seg_len > 1)
    item_final = _Item(
        alg=item1.alg,
        status=jnp.where(simple_tail_seg, s_status_final, item1.status),
        limit=item1.limit, duration=item1.duration, eff=item1.eff,
        burst=item1.burst,
        rem=jnp.where(simple_tail_seg, s_rem_final, item1.rem),
        t=item1.t, exp=item1.exp,
    )

    # per-position outputs for simple tails
    pos = jnp.arange(B, dtype=i32) - seg_start.at[seg_id].get(mode="fill", fill_value=0)
    sid = seg_id
    jj = pos.astype(i64)
    tail_ok = jj <= k_raw[sid]
    t_status = jnp.where(cost0[sid] > 0,
                         jnp.where(tail_ok, 0, 1), item1.status[sid]).astype(i32)
    t_rem = item1.rem[sid] - jnp.minimum(jj, k[sid]) * jnp.maximum(cost0[sid], 0)
    t_rem_out = jnp.where(
        is_leaky0[sid],
        divmod_nn(t_rem, jnp.maximum(item1.eff[sid], 1))[0], t_rem)
    tail_mask = simple[sid] & (pos > 0)

    # assemble sorted-order outputs: heads then simple tails.  out0 is
    # per-SEGMENT-ID; a segment's head value lands on its head lane.
    # The historical `zeros.at[idx0].set(out0)` scatter (idx0 =
    # seg_start per segment id) is equivalent to a head-masked gather
    # by seg_id — a select + contiguous gather lowers cheaply on every
    # backend, where scatter is the op the TPU backend can serialize.
    head_w = head & exists[sid]
    o_status = jnp.where(head_w, out0[0][sid], 0).astype(i32)
    o_rem = jnp.where(head_w, out0[1][sid], 0)
    o_reset = jnp.where(head_w, out0[2][sid], 0)
    o_limit = jnp.where(head_w, out0[3][sid], 0)
    o_status = jnp.where(tail_mask, t_status, o_status)
    o_rem = jnp.where(tail_mask, t_rem_out, o_rem)
    o_reset = jnp.where(tail_mask, out0[2][sid], o_reset)
    o_limit = jnp.where(tail_mask, out0[3][sid], o_limit)

    # ---- leaky mixed-time tails: speculative associative scan ----------
    # The last per-position exposure (ROUND_NOTES r2 open #4).  A
    # uniform-config, no-flag LEAKY segment whose arrivals mix instants
    # has the exact per-position transition (on the clamped clock
    # e_j = max(now_j, e_{j-1}), d_j = e_j - e_{j-1}):
    #     u_j = min(cap_td, r_{j-1} + d_j*limit)        (replenish)
    #     r_j = u_j - c  if c <= u_j (allow)  else  u_j (deny)
    # Crossing the expiry inside such a segment is EXACTLY replenish
    # saturation for leaky (fresh rem = burst*eff = cap_td, same t/exp
    # writes), so expiry needs no special case.  SPECULATE that every
    # tail position is allowed: each position becomes x -> min(m, x+b)
    # with m = cap_td - c, b = d_j*limit - c, and such maps compose
    # closedly: (m1,b1) then (m2,b2) = (min(m2, m1+b2), b1+b2) — a
    # segmented associative scan yields every prefix in O(log B)
    # instead of a while_loop iteration per position.  Validation: the
    # speculation holds iff min_j r_j >= 0 (nothing was denied);
    # segments where it fails keep the while_loop.  Queries (hits == 0)
    # consume nothing, never fail, and propagate the item status —
    # flipping to 0 once any position crossed the expiry (the fresh
    # reset).  The deny branch itself is non-monotone (a denied caller
    # keeps more tokens than an allowed one), which is why the general
    # mixed allow/deny case has no bounded-state scan.
    lseg = (exists & uniform_cfg & (~any_flag) & is_leaky0
            & (~uni_now) & (seg_len > 1))

    def _leaky_mixed_scan(carry):
        (os_, or_, ot_, ol_), item_f, cplx = carry
        i64max = _I64_MAX
        INF = jnp.asarray(1 << 62, i64)
        LOWC = jnp.asarray(-(1 << 62), i64)
        now_s = sf.now
        T = item1.t[sid]  # head's post-apply clock, per position
        e = jnp.maximum(now_s, T)
        now_prev = jnp.concatenate([now_s[:1], now_s[:-1]])
        e_prev = jnp.where(pos > 0, jnp.maximum(now_prev, T), T)
        d = jnp.maximum(e - e_prev, 0)
        L = sf.limit
        effp = jnp.maximum(sf.eff, 1)
        c = sf.hits * jnp.where(lseg[sid], effp, 1)  # mask: token
        # hits*eff of a non-participating segment may wrap int64
        cap_td = sf.burst * jnp.where(lseg[sid], effp, 1)
        safe_el = divmod_nn(TD_BOUND, jnp.maximum(L, 1))[0]
        tail_sel = lseg[sid] & (pos > 0)
        m_el = jnp.where(tail_sel, cap_td - c, INF)
        # d >= eff crosses the expiry: the bucket goes FRESH (rem =
        # burst*eff = cap_td) — NOT mere replenishment, which would
        # under-fill whenever burst > limit and d*limit < cap_td.
        # d > safe_el is the int64 overflow guard (same arm: the true
        # product exceeds every cap).
        b_raw = jnp.where((d >= effp) | (d > safe_el), cap_td - c,
                          jnp.minimum(d, safe_el) * L - c)
        # low clamp preserves "speculation fails" (r0 <= cap_td < 2^61
        # so r0 + LOWC < 0 always) while keeping every later sum in
        # int64 range; identity positions contribute (INF, 0)
        b_el = jnp.where(tail_sel, jnp.maximum(b_raw, LOWC), 0)
        flag = pos == 1  # segment start, for the segmented combine

        def comb(lft, rgt):
            ml, bl, fl = lft
            mr, br, fr = rgt
            m = jnp.minimum(mr, ml + br)
            b = jnp.minimum(jnp.maximum(bl + br, LOWC), m)
            return (jnp.where(fr, mr, m), jnp.where(fr, br, b), fl | fr)

        M, Bc, _ = lax.associative_scan(comb, (m_el, b_el, flag))
        r0 = item1.rem[sid]
        r = jnp.minimum(M, r0 + Bc)
        min_r = jax.ops.segment_min(
            jnp.where(tail_sel, r, i64max), seg_id, num_segments=B)
        ok_seg = lseg & (min_r >= 0)

        # per-position outputs (only adopted where ok_seg & tail)
        is_query = c == 0
        fi = (tail_sel & (d >= effp)).astype(i32)
        cs = jnp.cumsum(fi)
        cs_head = cs.at[seg_start[sid]].get(mode="fill", fill_value=0)
        crossed = (cs - cs_head) > 0  # any expiry crossing at <= this pos
        st_pos = jnp.where(is_query,
                           jnp.where(crossed, 0, item1.status[sid]),
                           0).astype(i32)
        rate = jnp.where(L > 0,
                         divmod_nn(effp, jnp.maximum(L, 1))[0], effp)
        ap = ok_seg[sid] & tail_sel
        os_ = jnp.where(ap, st_pos, os_)
        or_ = jnp.where(ap, divmod_nn(r, effp)[0], or_)
        # own stamp, not the clamped clock e (the older request)
        ot_ = jnp.where(ap, now_s + rate, ot_)
        ol_ = jnp.where(ap, L, ol_)

        # per-segment final item from the last tail position
        idxL = jnp.where(ok_seg, seg_start + seg_len - 1, B).astype(i32)

        def glast(x, fill=0):
            return x.at[idxL].get(mode="fill", fill_value=fill)

        item_scan = item1._replace(
            status=glast(st_pos), rem=glast(r), t=glast(e),
            exp=glast(e) + item1.eff)
        item_f = _tree_where(ok_seg, item_scan, item_f)
        return (os_, or_, ot_, ol_), item_f, cplx & (~ok_seg)

    (o_status, o_rem, o_reset, o_limit), item_final, complex_seg = lax.cond(
        lseg.any(), _leaky_mixed_scan, lambda carry: carry,
        ((o_status, o_rem, o_reset, o_limit), item_final, complex_seg))

    # ---- complex tails: while_loop over in-segment positions -----------
    max_complex = jnp.max(jnp.where(complex_seg, seg_len, 0))

    def cond_fn(c):
        return c[0] < max_complex

    def body_fn(c):
        j, item, (os_, or_, ot_, ol_) = c
        m = complex_seg & (j < seg_len)
        # active indices seg_start+j are distinct across segments and
        # inactive lanes get DISTINCT OOB sentinels (dropped), so the
        # unique promise holds — same backend-vectorization rationale
        # as the table writeback below
        idxj = jnp.where(m, seg_start + j,
                         B + jnp.arange(B, dtype=i32)).astype(i32)
        if _CHECK_SCATTER_INVARIANTS:  # traced-ok: test-only scatter-invariant hook, off in production
            jax.debug.callback(_record_unique, "body_idxj", idxj)
        reqj = _Req(*[x.at[idxj].get(mode="fill", fill_value=0) for x in sf])
        item2, outj = _apply_position(item, reqj)
        item = _tree_where(m, item2, item)
        os_ = os_.at[idxj].set(outj[0], mode="drop", unique_indices=True)
        or_ = or_.at[idxj].set(outj[1], mode="drop", unique_indices=True)
        ot_ = ot_.at[idxj].set(outj[2], mode="drop", unique_indices=True)
        ol_ = ol_.at[idxj].set(outj[3], mode="drop", unique_indices=True)
        return j + 1, item, (os_, or_, ot_, ol_)

    _, item_final, (o_status, o_rem, o_reset, o_limit) = lax.while_loop(
        cond_fn, body_fn,
        (jnp.asarray(1, i32), item_final, (o_status, o_rem, o_reset, o_limit)),
    )

    # ---- write back per-segment final state ----------------------------
    # wrow is per SEGMENT ID — one writer per segment already (sorted
    # by row, so live segments have distinct rows).  The non-existent
    # segments get DISTINCT out-of-bounds sentinels (dropped by
    # mode="drop") so the unique_indices promise below is honest: it
    # lets the TPU backend vectorize the scatters instead of assuming
    # colliding writes.  The vector is also globally ASCENDING — both sort
    # paths end with a stable argsort by row, so seg_row rises across
    # live segment ids (err/invalid rows are remapped to cap and sort
    # LAST into a non-exists segment), and the cap+i sentinels occupy
    # ids >= n_segments with values > any live row — hence
    # indices_are_sorted too (verified on real wrow vectors by
    # tests/test_scatter_invariants.py)
    wrow = jnp.where(exists, seg_row, cap + jnp.arange(B, dtype=i32))
    if _CHECK_SCATTER_INVARIANTS:  # traced-ok: test-only scatter-invariant hook, no cost when off
        jax.debug.callback(_record_wrow, wrow)
    meta_new = (item_final.alg & 1) | ((item_final.status & 1) << 1)

    # Hot/cold column split: the four
    # hot columns (meta, remaining, t_ms, expire_at) change on ~every
    # step; the cold config columns (limit, duration, eff_ms, burst —
    # and key, via the insert cond above) change only on insert or
    # config change.  Gate the cold scatters behind a cond so clean
    # steps return those buffers untouched: under donation
    # (decide_batch_donated) the pass-through aliases in place and
    # steady-state HBM traffic drops from 9 streamed columns to 4.
    cold_dirty = miss.any() | (exists & (
        (item_final.limit != item0.limit)
        | (item_final.duration != item0.duration)
        | (item_final.eff != item0.eff)
        | (item_final.burst != item0.burst))).any()

    def put(col, vals):
        return put_rows(col, wrow, vals, sorted_idx=True)

    def _cold_scatter(cols):
        limit_c, duration_c, eff_c, burst_c = cols
        return (put(limit_c, item_final.limit),
                put(duration_c, item_final.duration),
                put(eff_c, item_final.eff),
                put(burst_c, item_final.burst))

    limit_n, duration_n, eff_n, burst_n = lax.cond(
        cold_dirty, _cold_scatter, lambda cols: cols,
        (state.limit, state.duration, state.eff_ms, state.burst))

    new_state = TableState(
        key=tkey,
        meta=put(state.meta, meta_new.astype(i32)),
        limit=limit_n,
        duration=duration_n,
        eff_ms=eff_n,
        burst=burst_n,
        remaining=put(state.remaining, item_final.rem),
        t_ms=put(state.t_ms, item_final.t),
        expire_at=put(state.expire_at, item_final.exp),
    )

    # ---- back to request order -----------------------------------------
    inv = jnp.zeros(B, i32).at[perm].set(jnp.arange(B, dtype=i32),
                                         unique_indices=True)
    status = jnp.where(valid & (~err), o_status[inv], 0)
    remaining = jnp.where(valid & (~err), o_rem[inv], 0)
    reset_time = jnp.where(valid & (~err), o_reset[inv], 0)
    limit_out = jnp.where(valid & (~err), o_limit[inv], 0)
    over_count = (valid & (~err) & (status == 1)).sum(dtype=i64)

    return new_state, StepOutput(
        status=status, remaining=remaining, reset_time=reset_time,
        limit=limit_out, err=err, over_count=over_count,
        insert_count=insert_count,
    )


#: Host-dispatch entry point WITHOUT buffer donation — test/debug use:
#: callers that cannot thread state linearly (tests asserting on both
#: old and new tables, lowerings without aliasing support).  Serving
#: uses the donated variant below.
decide_batch = jax.jit(decide_batch_impl)

#: Donated variant: the table aliases in/out, so the cond-gated cold
#: columns (limit/duration/eff/burst; key when no insert) pass through
#: with ZERO copies on clean steps and the hot scatters update in
#: place, making per-step HBM traffic ~B-sized instead of CAP-sized
#: (what the step costs on the chip: PERF.md §5, cell 6).  Inside
#: lax.scan the loop-carried state gets the same in-place treatment
#: automatically.  Callers MUST thread state linearly: the old state
#: dies at the call.
decide_batch_donated = jax.jit(decide_batch_impl, donate_argnums=0)
