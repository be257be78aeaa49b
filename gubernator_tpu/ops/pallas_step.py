"""Pallas TPU kernel: the decision step (probe → gather → update →
scatter) as ONE hand-scheduled Mosaic program — the TPU's default
serving kernel (parallel/pallas_engine.py).

Why this exists (SURVEY §2.2 north star): the XLA decision step's
throughput depends on how the compiler of the day lowers its scatters
and gathers.  This kernel owns its memory traffic explicitly.
bench.py also enters it in the per-run mode duel alongside copy/donate
(`extra.step_mode` can report "pallas").

Design (TPU-first, not a translation):

- **Bucket = one DMA window, one lane per slot.**  Instead of the XLA
  path's SoA columns + double-hash probing (9 scattered per-row
  touches), the Pallas table is ``[n_buckets, 16, 128] int32``: a
  bucket holds 128 slots, word ``w`` of slot ``s`` at ``[w, s]``.  That
  is two native (8, 128) int32 tiles — HBM holds exactly 64 B per slot
  with no lane padding (a ``[CAP, 32]`` row table is padded 4× by the
  TPU's (8, 128) tiling and cannot be sliced by a DMA) — and ONE
  aligned 8 KiB DMA moves a key's entire probe window *with* its data.
  In VMEM each state word of the bucket is a (1, 128) lane vector, so
  the decision math runs once for all 128 candidate slots and the
  matched slot is lane-selected at writeback: no vector→scalar
  extraction anywhere.  Layout is a mode-level choice — decisions are
  layout-independent, and the parity tests assert exactly that.
- **Sequential grid + in-tile serial loop.**  TPU Pallas grids run
  sequentially, which gives cross-tile duplicate ordering for free;
  within a tile a `fori_loop` applies requests strictly in order
  against the live VMEM bucket copies (deduplicated via a host-computed
  first-occurrence map), reproducing the reference's sequential
  per-request semantics by construction — duplicates, config changes,
  RESET/DRAIN flags and all.
- **int64 as 2×i32 lanes** (as ops/pallas_sweep.py already does):
  Mosaic has no 64-bit vector lanes.  Times (now/t/expire/duration,
  ~2^41 ms) use paired-word add/compare; counter values (hits, limit,
  burst, remaining) are host-qualified to < 2^30 and use plain i32
  arithmetic.

Domain (host-checked by ``pallas_qualifies``): TOKEN_BUCKET and
LEAKY_BUCKET.  All behaviors are supported: RESET_REMAINING,
DRAIN_OVER_LIMIT, DURATION_IS_GREGORIAN (greg_end / eff_ms are
precomputed columns), hits==0 queries, mixed per-request `now`.

LEAKY's td fixed point (oracle.apply_leaky: remaining stored as
``remaining × eff`` in int64 "token-duration" units) runs in paired-i32
arithmetic:

- every REQUEST-only td product (``hits×eff``, ``burst×eff``,
  ``limit×eff``, ``eff//limit``, ``TD_BOUND//limit``) is precomputed as
  an int64 column by the XLA wrapper — real 64-bit hardware, masked to
  eff=1 on token rows exactly like core/step.py's ``eff_l`` operand
  masking;
- the two STATE-dependent ops run in-kernel: ``elapsed × limit`` via an
  unsigned 32×32→64 multiply built from 16-bit halves (``_umul32x32``),
  and ``td // eff`` (+ the rescale divmods) via a 32-step restoring
  division (``_udiv64_32``) whose quotient provably fits one word: the
  domain bounds counters < 2^30 and leaky eff < 2^31 (``EFF_BOUND``),
  so td < 2^30 × eff and every quotient < 2^31.

The divisions live only in the ``pl.when`` leaky branch — token tiles
pay nothing for them.

Use ``interpret=True`` (or the CPU backend) for the reference
interpreter used by the parity tests.
"""
from __future__ import annotations

import os
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.batch import RequestBatch
from ..core.step import StepOutput, divmod_nn
from ..types import TD_BOUND, Behavior

SLOTS = 128  # slots per bucket = lanes: the probe window is one bucket
WORDS = 16  # i32 words per slot = sublanes of a bucket's two tiles
TILE = 128  # requests per grid step (default; see pallas_tile())


def pallas_tile() -> int:
    """Requests per Mosaic grid step — the kernel's block-shape knob
    (GUBER_PALLAS_TILE).  A multiple of 8 (the response block's sublane
    rule) in [8, 512]: the in-tile dedup map is O(tile²) host work and
    the VMEM scratch is tile×8 KiB, so an unbounded value would trade
    one launch for an unschedulable tile.  Malformed/out-of-range
    values keep the default (a perf knob must never turn into a crash
    knob).  Resolved at engine/program BUILD time — a live env flip
    does not retrace compiled programs."""
    raw = os.environ.get("GUBER_PALLAS_TILE", "")
    if raw:
        try:
            t = int(raw)
            if 8 <= t <= 512 and t % 8 == 0:
                return t
        except ValueError:
            pass
    return TILE

#: value bound for i32 counter arithmetic (limit-change adjustment adds
#: two limits before clipping, so 2^30 keeps every intermediate in i32)
VALUE_BOUND = 1 << 30

#: leaky eff_ms bound (~24.8 days): keeps the division divisor in one
#: i32 word and, with VALUE_BOUND, every td quotient < 2^31.  Also puts
#: both denominators under oracle FRAC_SAFE (2^31), so the kernel's
#: rescale ALWAYS keeps the sub-token fraction — no floor branch —
#: and under TD_BOUND//eff ≥ 2^30 ≥ any whole-token count, so the
#: oracle's whole-token clamp is a domain no-op.  Longer windows are
#: DURATION_IS_GREGORIAN's job (fixed-rate eff) or the XLA modes'.
EFF_BOUND = 1 << 31

_RESET = int(Behavior.RESET_REMAINING)
_DRAIN = int(Behavior.DRAIN_OVER_LIMIT)
_GREG = int(Behavior.DURATION_IS_GREGORIAN)

# ---- slot word layout (word w of a bucket = sublane w) ------------------
W_KLO, W_KHI = 0, 1
W_REM, W_STATUS, W_LIMIT = 2, 3, 4
W_TLO, W_THI = 5, 6
W_XLO, W_XHI = 7, 8  # expire_at
W_ELO, W_EHI = 9, 10  # eff_ms
W_DLO, W_DHI = 11, 12  # duration
W_ALG = 13  # 0 token / 1 leaky (empty slot = 0: insert is fresh anyway)
W_TDLO, W_TDHI = 14, 15  # leaky remaining, td units (= remaining × eff)
# (item.burst is NOT stored: oracle.apply_leaky overwrites it from the
# request before every read, so the replenish cap is the request-only
# burst×eff column)

#: python int, not a jnp constant: a module-level traced array would be
#: captured by the kernel closure, which pallas_call rejects
_FLIP = -2147483648


def _ult(a, b):
    """unsigned-i32 a < b on reinterpreted int32 words."""
    return (a ^ _FLIP) < (b ^ _FLIP)


def _uge(a, b):
    return ~_ult(a, b)


def _add64(ah, al, bh, bl):
    lo = al + bl
    carry = _ult(lo, al).astype(jnp.int32)
    return ah + bh + carry, lo


def _ge64(ah, al, bh, bl):
    """signed 64-bit (ah:al) >= (bh:bl)."""
    return (ah > bh) | ((ah == bh) & _uge(al, bl))


def _neq64(ah, al, bh, bl):
    return (ah != bh) | (al != bl)


def _sel(c, a, b):
    return jnp.where(c, a, b)


def _sel64(c, ah, al, bh, bl):
    return jnp.where(c, ah, bh), jnp.where(c, al, bl)


def _sub64(ah, al, bh, bl):
    """(ah:al) - (bh:bl), callers guarantee a >= b."""
    borrow = _ult(al, bl).astype(jnp.int32)
    return ah - bh - borrow, al - bl


def _umul32x32(a, b):
    """Unsigned 32×32→64 multiply from 16-bit halves: ``a`` is any u32
    word, ``b`` must be < 2^31 (true of every multiplier here: limit
    < VALUE_BOUND, eff < EFF_BOUND).  Mosaic's i32 multiply yields the
    low 32 product bits, which for 16-bit partials IS the exact
    unsigned value."""
    i32 = jnp.int32
    mask = i32(0xFFFF)
    ah, al = (a >> 16) & mask, a & mask
    bh, bl = (b >> 16) & mask, b & mask  # bh < 2^15 given b < 2^31
    t = al * bl           # < 2^32 (exact bits in the word)
    u = ah * bl           # < 2^32
    v = al * bh           # < 2^31
    w = ah * bh           # < 2^31
    lo1 = t + (u << 16)
    c1 = _ult(lo1, t).astype(i32)
    lo2 = lo1 + (v << 16)
    c2 = _ult(lo2, lo1).astype(i32)
    hi = w + ((u >> 16) & mask) + ((v >> 16) & mask) + c1 + c2
    return hi, lo2


def _umul64x32(ah, al, m):
    """(ah:al) × m for results the caller guarantees < 2^63 (here:
    elapsed ≤ TD_BOUND//limit, so elapsed×limit ≤ TD_BOUND < 2^62) —
    the ah×m high bits then provably vanish and the wrapping i32
    multiply is exact."""
    hi, lo = _umul32x32(al, m)
    return hi + ah * m, lo


def _udiv64_32(nh, nl, d):
    """(nh:nl) ÷ d → (quotient, remainder), both one u32 word.

    32-step restoring division (shift/compare/subtract only — Mosaic
    lowers no 64-bit divide, and i32 divide lowerings are float-backed).
    Exact under the precondition nh < d (⟺ quotient < 2^32), which the
    leaky domain guarantees: every dividend < 2^31 × divisor
    (td < 2^30×eff, frac×eff < eff×2^31).  Outside the precondition
    (e.g. a discarded token-lane divisor) the result is garbage but the
    loop is still well-defined — callers select it away."""
    i32 = jnp.int32

    def step(_, c):
        R, Q, L = c
        msb = (L >> 31) & i32(1)
        L = L << 1
        R = (R << 1) | msb
        geq = _uge(R, d)
        R = jnp.where(geq, R - d, R)
        Q = (Q << 1) | geq.astype(i32)
        return R, Q, L

    R, Q, _ = lax.fori_loop(0, 32, step, (nh, jnp.zeros_like(nh), nl))
    return Q, R


def _split64(x):
    u = x.astype(jnp.uint64)
    hi = (u >> jnp.uint64(32)).astype(jnp.uint32).astype(jnp.int32)
    lo = u.astype(jnp.uint32).astype(jnp.int32)
    return hi, lo


def _join64(hi, lo, dtype):
    u = (hi.astype(jnp.uint32).astype(jnp.uint64) << jnp.uint64(32)) | \
        lo.astype(jnp.uint32).astype(jnp.uint64)
    return u.astype(dtype)


class PallasTable(NamedTuple):
    """Bucket table: ``buckets[n_buckets, WORDS, SLOTS]`` int32,
    n_buckets a power of two; word w of bucket b's slot s is
    ``buckets[b, w, s]``.  Empty slot: all words 0."""

    buckets: jax.Array


def init_pallas_table(capacity: int) -> PallasTable:
    """``capacity`` counts slots (the unit every engine sizes in)."""
    if capacity < SLOTS or capacity & (capacity - 1):
        raise ValueError(f"capacity must be a power of two >= {SLOTS}")
    return PallasTable(buckets=jnp.zeros(
        (capacity // SLOTS, WORDS, SLOTS), jnp.int32))


def buckets_to_rows(buckets):
    """[nb, WORDS, SLOTS] device layout → [nb, SLOTS, WORDS] slot rows
    (the host-side view: row ``[b, s]`` is one slot's words).  Its own
    inverse; numpy or jax arrays."""
    return buckets.transpose(0, 2, 1)


def pallas_value_domain_mask(batch: RequestBatch):
    """(per-row value-domain mask, the ``algorithm == 1`` column it is
    built from — None where no row is leaky, and then the leaky half
    of the mask, three passes over ``eff_ms``, is skipped).  The mask
    (np bool[B]) is True where the row's algorithm/counters/eff fit
    the kernel's i32 arithmetic.  Row-level twin of the value checks
    in ``pallas_qualifies`` — the serving engine uses it to scope
    out-of-domain rows instead of failing a whole coalesced wave
    (ordering is not row-separable and stays a batch-level property)
    and counts its wave's leaky rows from the second column."""
    import numpy as np

    alg = np.asarray(batch.algorithm)
    leaky = alg == 1
    ok = (alg == 0) | leaky
    for col in (batch.hits, batch.limit, batch.burst):
        c = np.asarray(col)
        ok &= (c >= 0) & (c < VALUE_BOUND)
    if not leaky.any():
        return ok, None
    eff = np.asarray(batch.eff_ms)
    ok &= ~leaky | ((eff >= 1) & (eff < EFF_BOUND))
    return ok, leaky


def pallas_qualifies(batch: RequestBatch) -> bool:
    """Host-side domain check (np, cheap): every valid row TOKEN_BUCKET
    or LEAKY_BUCKET with counter values inside the i32-arithmetic
    bound, leaky eff_ms inside the one-word divisor bound, and per-key
    arrival times non-decreasing in batch order (the kernel applies
    requests strictly in batch order, where the XLA path re-sorts each
    key's segment by arrival time — a time-inverted duplicate pair
    would serialize differently)."""
    import numpy as np

    v = np.asarray(batch.valid)
    alg = np.asarray(batch.algorithm)
    if (v & (alg != 0) & (alg != 1)).any():
        return False
    for col in (batch.hits, batch.limit, batch.burst):
        c = np.asarray(col)
        if ((v) & ((c < 0) | (c >= VALUE_BOUND))).any():
            return False
    leaky = v & (alg == 1)
    if leaky.any():
        eff = np.asarray(batch.eff_ms)
        if (leaky & ((eff < 1) | (eff >= EFF_BOUND))).any():
            return False
    if batch.now is not None:
        now = np.asarray(batch.now)
        if now.size and not (now == now.flat[0]).all():
            # drop invalid rows FIRST: an invalid row sitting between
            # two valid same-key rows would break the adjacency check
            # (both pairs span an invalid member), letting a
            # time-inverted duplicate through.  Then a stable key sort
            # preserves batch order within a key, so per-key
            # monotonicity = non-decreasing now on same-key neighbors.
            keys = np.asarray(batch.key)[v]
            now_v = now[v]
            order = np.argsort(keys, kind="stable")
            k_s, n_s = keys[order], now_v[order]
            same = k_s[1:] == k_s[:-1]
            if (same & (n_s[1:] < n_s[:-1])).any():
                return False
    return True


#: request columns, one SMEM row each (row index within a tile's block)
(C_BUCKET, C_BREP, C_KLO, C_KHI, C_HITS, C_LIM, C_DLO, C_DHI, C_ELO,
 C_EHI, C_GLO, C_GHI, C_BEH, C_NLO, C_NHI, C_VALID, C_ALG, C_HTL, C_HTH,
 C_CPL, C_CPH, C_RSL, C_RSH, C_RATE, C_GDL, C_GDH) = range(26)
N_COLS = 32  # SMEM block rows: the 26 columns above, padded to 8k

#: output lanes of a request's (1, SLOTS) result row
O_STATUS, O_REM, O_RLO, O_RHI, O_LIMIT, O_FLAGS = range(6)


def _kernel(tile, c_ref, _table_in, table_ref, out_ref, scratch, sems):
    """One grid step = one ``tile`` of requests, strictly in order.

    scratch[j] holds request j's bucket copy iff j is its tile-first
    occurrence (brep[j] == j); later same-bucket requests read/write
    the first copy, so in-tile duplicates see each other's updates
    exactly as a sequential loop would.

    Every per-request value is a (1, SLOTS) lane vector — lane s is
    "this request applied to slot s" — and the matched (or claimed)
    slot's lane is selected at writeback.  Request scalars are splat
    from SMEM; the only cross-lane work is the found/first-empty
    reductions and the response-row reductions."""
    i32 = jnp.int32
    row = (1, SLOTS)
    lane = lax.broadcasted_iota(i32, row, 1)

    def first_live(j):
        return (c_ref[C_BREP, j] == j) & (c_ref[C_VALID, j] != 0)

    def bucket_dma(j, gather):
        hbm = table_ref.at[c_ref[C_BUCKET, j]]
        if gather:
            return pltpu.make_async_copy(hbm, scratch.at[j], sems.at[0])
        return pltpu.make_async_copy(scratch.at[j], hbm, sems.at[1])

    def for_each_first_live(fn):
        def step(j, c):
            @pl.when(first_live(j))
            def _():
                fn(j)
            return c

        lax.fori_loop(0, tile, step, 0)

    # 1) gather: one DMA per distinct live bucket in the tile
    for_each_first_live(lambda j: bucket_dma(j, True).start())
    for_each_first_live(lambda j: bucket_dma(j, True).wait())

    def lanes_of(x):
        """(1, 1) reduction result → all lanes."""
        return jnp.broadcast_to(x, row)

    # 2) apply requests in order against the live bucket copies
    def body(j, c):
        def splat(k):
            return jnp.full(row, c_ref[k, j], i32)

        def emit(vals):
            """Store request j's response row: ``vals`` maps output
            lane → (1, SLOTS) value (already lane-uniform)."""
            out = jnp.zeros(row, i32)
            for o, v in vals.items():
                out = jnp.where(lane == o, v, out)
            out_ref[pl.ds(j, 1), :] = out

        @pl.when(c_ref[C_VALID, j] != 0)
        def _process():
            b = c_ref[C_BREP, j]

            def ld(w):
                return scratch[b, pl.ds(w, 1), :]

            def pick(mask, v):
                """The masked slot's value on every lane (0 if none)."""
                return lanes_of(jnp.sum(jnp.where(mask, v, 0), axis=1,
                                        keepdims=True))

            klo, khi = splat(C_KLO), splat(C_KHI)
            s_klo, s_khi = ld(W_KLO), ld(W_KHI)
            match = (s_klo == klo) & (s_khi == khi)
            empty = (s_klo == 0) & (s_khi == 0)
            found = pick(match, 1) > 0
            # first empty slot: lowest lane among empties
            first_idx = lanes_of(jnp.min(
                jnp.where(empty, lane, SLOTS), axis=1, keepdims=True))
            has_empty = first_idx < SLOTS
            insert = (~found) & has_empty
            err = (~found) & (~has_empty)  # bucket full
            slot1h = (found & match) | (insert & (lane == first_idx))

            # item state per slot lane (an insert claims a zeroed empty
            # slot → fresh fires below, matching the XLA path's
            # post-insert read)
            it_rem, it_status = ld(W_REM), ld(W_STATUS)
            it_limit, it_alg = ld(W_LIMIT), ld(W_ALG)
            it_tlo, it_thi = ld(W_TLO), ld(W_THI)
            it_xlo, it_xhi = ld(W_XLO), ld(W_XHI)
            it_elo, it_ehi = ld(W_ELO), ld(W_EHI)
            it_dlo, it_dhi = ld(W_DLO), ld(W_DHI)
            it_tdlo, it_tdhi = ld(W_TDLO), ld(W_TDHI)

            # request fields
            r_hits, r_lim = splat(C_HITS), splat(C_LIM)
            r_dlo, r_dhi = splat(C_DLO), splat(C_DHI)
            r_elo, r_ehi = splat(C_ELO), splat(C_EHI)
            r_glo, r_ghi = splat(C_GLO), splat(C_GHI)
            r_alg_s = c_ref[C_ALG, j]
            r_alg = splat(C_ALG)
            beh = splat(C_BEH)
            is_greg = (beh & _GREG) != 0
            reset = (beh & _RESET) != 0
            drain = (beh & _DRAIN) != 0

            # now = max(req.now, item.t)  (per-key monotonic clock)
            nhi0, nlo0 = splat(C_NHI), splat(C_NLO)
            use_req = _ge64(nhi0, nlo0, it_thi, it_tlo)
            nhi1, nlo1 = _sel64(use_req, nhi0, nlo0, it_thi, it_tlo)

            # fresh: empty / expired / algorithm switch
            fresh0 = ((~found) | _ge64(nhi1, nlo1, it_xhi, it_xlo)
                      | (it_alg != r_alg))
            is_query = r_hits == 0
            flags = err.astype(i32) | (insert.astype(i32) << 1)
            lim_out = _sel(err, 0, r_lim)
            # a valid row with an out-of-domain algorithm (neither
            # pl.when below fires — callers must gate on
            # pallas_qualifies, but defense here is one store) returns
            # zeros, never uninitialized output memory
            emit({O_LIMIT: lim_out, O_FLAGS: flags})

            def writeback(words):
                """Store the selected slot's new words (a full bucket —
                err — selects no lane, so nothing changes)."""
                for w, v in words.items():
                    scratch[b, pl.ds(w, 1), :] = jnp.where(slot1h, v,
                                                           ld(w))

            @pl.when(r_alg_s == 0)
            def _token():
                fresh = fresh0
                # token duration change → recompute expiry from item.t
                dur_change = ((~fresh)
                              & _neq64(r_dhi, r_dlo, it_dhi, it_dlo))
                ne_hi, ne_lo = _add64(it_thi, it_tlo, r_ehi, r_elo)
                ne_hi, ne_lo = _sel64(is_greg, r_ghi, r_glo, ne_hi,
                                      ne_lo)
                x1hi, x1lo = _sel64(dur_change, ne_hi, ne_lo,
                                    it_xhi, it_xlo)
                # oracle's `exp1 <= now`
                fresh = fresh | (dur_change
                                 & _ge64(nhi1, nlo1, x1hi, x1lo))

                # adopt fresh or existing
                xf_hi, xf_lo = _add64(nhi1, nlo1, r_ehi, r_elo)
                xf_hi, xf_lo = _sel64(is_greg, r_ghi, r_glo,
                                      xf_hi, xf_lo)
                limit0 = _sel(fresh, r_lim, it_limit)
                rem0 = _sel(fresh, r_lim, it_rem)
                t_hi, t_lo = _sel64(fresh, nhi1, nlo1, it_thi, it_tlo)
                x_hi, x_lo = _sel64(fresh, xf_hi, xf_lo, x1hi, x1lo)
                status0 = _sel(fresh, 0, it_status)
                e_hi, e_lo = _sel64(fresh | dur_change, r_ehi, r_elo,
                                    it_ehi, it_elo)

                # RESET_REMAINING on existing items
                reset_live = reset & (~fresh)
                rem0 = _sel(reset_live, r_lim, rem0)
                status0 = _sel(reset_live, 0, status0)
                limit_ar = _sel(reset_live, r_lim, limit0)

                # token limit change in place
                lim_change = r_lim != limit_ar
                rem_adj = jnp.clip(rem0 + r_lim - limit_ar, 0, r_lim)
                rem0 = _sel(lim_change, rem_adj, rem0)

                # hits
                ok = r_hits <= rem0
                rem2 = _sel((~is_query) & ok, rem0 - r_hits, rem0)
                rem2 = _sel((~is_query) & (~ok) & drain, 0, rem2)
                status1 = _sel(is_query, status0, _sel(ok, 0, 1))

                zero = jnp.zeros(row, i32)
                writeback({
                    W_KLO: klo, W_KHI: khi, W_REM: rem2,
                    W_STATUS: status1, W_LIMIT: r_lim,
                    W_TLO: t_lo, W_THI: t_hi, W_XLO: x_lo, W_XHI: x_hi,
                    W_ELO: e_lo, W_EHI: e_hi, W_DLO: r_dlo,
                    W_DHI: r_dhi, W_ALG: zero, W_TDLO: zero,
                    W_TDHI: zero})
                # err rows select no lane → zeros, as the XLA step
                # masks them
                emit({O_STATUS: pick(slot1h, status1),
                      O_REM: pick(slot1h, rem2),
                      O_RLO: pick(slot1h, x_lo),
                      O_RHI: pick(slot1h, x_hi),
                      O_LIMIT: lim_out, O_FLAGS: flags})

            @pl.when(r_alg_s == 1)
            def _leaky():
                # request-only td columns (precomputed by the wrapper):
                # hits×eff, burst×eff (cap), limit×eff (reset value),
                # eff//limit (rate), TD_BOUND//limit (replenish guard)
                r_htl, r_hth = splat(C_HTL), splat(C_HTH)
                r_cpl, r_cph = splat(C_CPL), splat(C_CPH)
                r_rsl, r_rsh = splat(C_RSL), splat(C_RSH)
                r_rate = splat(C_RATE)
                r_gdl, r_gdh = splat(C_GDL), splat(C_GDH)
                zero = jnp.zeros(row, i32)

                # denominator change → rescale the td fixed point to
                # the new eff.  In the kernel domain both denominators
                # are < EFF_BOUND ≤ FRAC_SAFE, so the sub-token
                # fraction is ALWAYS kept, and whole < 2^30 ≤
                # TD_BOUND//eff makes the oracle's whole-token clamp a
                # no-op (see EFF_BOUND).  Divides run unconditionally
                # (lane-selected away on ~eff_change); a token-item or
                # empty-slot divisor feeds garbage that fresh0
                # discards — _udiv64_32 is total, never faulting.
                eff_change = ((~fresh0)
                              & _neq64(r_ehi, r_elo, it_ehi, it_elo))
                whole, fracr = _udiv64_32(it_tdhi, it_tdlo, it_elo)
                fth, ftl = _umul32x32(fracr, r_elo)
                frac_term, _ = _udiv64_32(fth, ftl, it_elo)
                wh, wl = _umul32x32(whole, r_elo)
                resc_h, resc_l = _add64(wh, wl, zero, frac_term)
                td0h, td0l = _sel64(eff_change, resc_h, resc_l,
                                    it_tdhi, it_tdlo)

                # fresh adoption: bucket starts full (burst × eff)
                td0h, td0l = _sel64(fresh0, r_cph, r_cpl, td0h, td0l)
                status0 = _sel(fresh0, 0, it_status)
                t0h, t0l = _sel64(fresh0, nhi1, nlo1, it_thi, it_tlo)

                # RESET_REMAINING on existing items: limit × eff
                reset_live = reset & (~fresh0)
                td0h, td0l = _sel64(reset_live, r_rsh, r_rsl,
                                    td0h, td0l)
                status0 = _sel(reset_live, 0, status0)

                # replenish: elapsed × limit td, clamped to cap.
                # elapsed > TD_BOUND//limit ⇒ the true product already
                # exceeds the cap — bucket simply full (exact, as in
                # oracle.apply_leaky).  Fresh lanes: t0 = now ⇒
                # elapsed = 0 ⇒ no-op, mirroring the XLA step.
                elh, ell = _sub64(nhi1, nlo1, t0h, t0l)
                over_g = ~_ge64(r_gdh, r_gdl, elh, ell)
                ech, ecl = _sel64(over_g, r_gdh, r_gdl, elh, ell)
                adh, adl = _umul64x32(ech, ecl, r_lim)
                sh, sl = _add64(td0h, td0l, adh, adl)
                full = over_g | _ge64(sh, sl, r_cph, r_cpl)
                rph, rpl = _sel64(full, r_cph, r_cpl, sh, sl)

                # hits (cost = hits × eff, precomputed)
                ok = _ge64(rph, rpl, r_hth, r_htl)
                d2h, d2l = _sub64(rph, rpl, r_hth, r_htl)
                apply_ok = (~is_query) & ok
                td2h, td2l = _sel64(apply_ok, d2h, d2l, rph, rpl)
                drain_hit = (~is_query) & (~ok) & drain
                td2h, td2l = _sel64(drain_hit, zero, zero, td2h, td2l)
                status1 = _sel(is_query, status0, _sel(ok, 0, 1))

                # response: remaining in whole tokens, reset_time =
                # the request's OWN stamp + eff//limit (NOT the stored
                # expire = now + eff, and not the clamped clock: the
                # older request — oracle.py, "Leaky fixed point")
                rem_out, _ = _udiv64_32(td2h, td2l, r_elo)
                x_hi, x_lo = _add64(nhi1, nlo1, r_ehi, r_elo)
                rsh_, rsl_ = _add64(nhi0, nlo0, zero, r_rate)

                writeback({
                    W_KLO: klo, W_KHI: khi, W_REM: zero,
                    W_STATUS: status1, W_LIMIT: r_lim,
                    W_TLO: nlo1, W_THI: nhi1, W_XLO: x_lo,
                    W_XHI: x_hi, W_ELO: r_elo, W_EHI: r_ehi,
                    W_DLO: r_dlo, W_DHI: r_dhi,
                    W_ALG: jnp.ones(row, i32), W_TDLO: td2l,
                    W_TDHI: td2h})
                emit({O_STATUS: pick(slot1h, status1),
                      O_REM: pick(slot1h, rem_out),
                      O_RLO: pick(slot1h, rsl_),
                      O_RHI: pick(slot1h, rsh_),
                      O_LIMIT: lim_out, O_FLAGS: flags})

        @pl.when(c_ref[C_VALID, j] == 0)
        def _invalid():
            emit({})

        return c

    lax.fori_loop(0, tile, body, 0)

    # 3) scatter: write distinct live buckets back, then fence the tile
    # (the wait orders these stores before the NEXT tile's gathers)
    for_each_first_live(lambda j: bucket_dma(j, False).start())
    for_each_first_live(lambda j: bucket_dma(j, False).wait())


def _call_kernel(buckets, cols, interpret: bool, tile: int = TILE):
    """cols: [G·N_COLS, tile] int32 — grid step g reads rows
    [g·N_COLS, (g+1)·N_COLS) as its SMEM block (C_* row order).
    Returns (buckets, [G·tile, SLOTS] int32 response rows, O_* lanes)."""
    G = cols.shape[0] // N_COLS
    table_spec = pl.BlockSpec(memory_space=pl.ANY)
    # x64 off while tracing the kernel: every operand is explicitly
    # int32, but under x64 the index_map literals and loop counters
    # trace as i64 scalars, which Mosaic cannot legalize
    with jax.enable_x64(False):
        return pl.pallas_call(
            partial(_kernel, tile),
            grid=(G,),
            in_specs=[pl.BlockSpec((N_COLS, tile), lambda i: (i, 0),
                                   memory_space=pltpu.SMEM),
                      table_spec],
            out_specs=[table_spec,
                       pl.BlockSpec((tile, SLOTS), lambda i: (i, 0),
                                    memory_space=pltpu.VMEM)],
            out_shape=[
                jax.ShapeDtypeStruct(buckets.shape, jnp.int32),
                jax.ShapeDtypeStruct((G * tile, SLOTS), jnp.int32)],
            input_output_aliases={1: 0},
            scratch_shapes=[
                pltpu.VMEM((tile, WORDS, SLOTS), jnp.int32),
                pltpu.SemaphoreType.DMA((2,)),  # gather, scatter
            ],
            interpret=interpret,
        )(cols, buckets)


def decide_batch_pallas_impl(table: PallasTable, batch: RequestBatch,
                             now_ms, *, interpret: bool = False,
                             tile: int = 0
                             ) -> tuple[PallasTable, StepOutput]:
    """Unjitted kernel step — for embedding in larger programs (the
    Pallas serving engine wraps it in shard_map; plain callers use the
    jitted/donated ``decide_batch_pallas`` below).

    Same contract as core/step.py › decide_batch for batches inside
    the kernel's domain (``pallas_qualifies``) — the parity tests
    assert identical decisions on shared request streams.  ``tile``
    (requests per grid step) 0 resolves the GUBER_PALLAS_TILE knob at
    trace time; engines resolve it once at build and pass it explicitly
    so a live env flip can't desync compiled programs.
    """
    i32, i64 = jnp.int32, jnp.int64
    TILE = tile if tile else pallas_tile()
    n_buckets = table.buckets.shape[0]
    B = batch.key.shape[0]
    G = -(-B // TILE)
    pad = G * TILE - B

    now = jnp.asarray(now_ms, i64)
    if batch.now is None:
        now_col = jnp.full((B,), now, i64)
    else:
        now_col = jnp.where(jnp.asarray(batch.now, i64) > 0,
                            jnp.asarray(batch.now, i64), now)

    key = batch.key.astype(jnp.uint64)
    valid = (batch.valid & (key != 0)).astype(i32)
    bucket = (key & jnp.uint64(n_buckets - 1)).astype(i32)

    def pad_to(x, fill=0):
        return jnp.pad(x, (0, pad), constant_values=fill) if pad else x

    khi, klo = _split64(key)
    dhi, dlo = _split64(batch.duration.astype(i64))
    ehi, elo = _split64(batch.eff_ms.astype(i64))
    ghi, glo = _split64(batch.greg_end.astype(i64))
    nhi, nlo = _split64(now_col)

    # Request-only leaky td products, in REAL int64 before the i32
    # split (eff masked to 1 on token rows so huge token hits/limits
    # can't wrap the unused product — same operand masking as
    # core/step.py's eff_l).
    alg = batch.algorithm.astype(i32)
    is_lk = alg == 1
    eff64 = batch.eff_ms.astype(i64)
    lim64 = batch.limit.astype(i64)
    eff_l = jnp.where(is_lk, eff64, 1)
    hth, htl = _split64(batch.hits.astype(i64) * eff_l)
    cph, cpl = _split64(batch.burst.astype(i64) * eff_l)
    rsh, rsl = _split64(lim64 * eff_l)
    lim_d = jnp.maximum(lim64, 1)
    rate = jnp.where(lim64 > 0, divmod_nn(eff_l, lim_d)[0],
                     eff_l).astype(i32)
    gdh, gdl = _split64(divmod_nn(TD_BOUND, lim_d)[0])

    # tile-relative first occurrence of each bucket (dedup map): the
    # kernel's serial loop routes same-bucket requests to one VMEM
    # copy.  Invalid rows get a UNIQUE sentinel so they can never
    # become a bucket's representative: first_live gates the DMA on
    # valid, so an invalid representative would starve a later valid
    # same-bucket request of its gather/writeback entirely.
    bt = pad_to(bucket).reshape(G, TILE)
    iota = jnp.arange(G * TILE, dtype=jnp.int64).reshape(G, TILE)
    vpad = pad_to(valid).reshape(G, TILE).astype(bool)
    rep_key = jnp.where(vpad, bt.astype(jnp.int64), -1 - iota)
    eq = rep_key[:, :, None] == rep_key[:, None, :]
    brep = jnp.argmax(eq, axis=-1).astype(i32)  # first True per row

    cols1d = [  # C_* order, after bucket/brep
        klo, khi,
        batch.hits.astype(i32), batch.limit.astype(i32),
        dlo, dhi, elo, ehi, glo, ghi,
        batch.behavior.astype(i32), nlo, nhi, valid,
        alg, htl, hth, cpl, cph, rsl, rsh, rate, gdl, gdh,
    ]
    cols = [bt, brep] + [pad_to(c).reshape(G, TILE) for c in cols1d]
    cols += [jnp.zeros((G, TILE), i32)] * (N_COLS - len(cols))
    # [G, N_COLS, TILE] → one SMEM block of N_COLS rows per grid step
    cols = jnp.stack(cols, axis=1).reshape(G * N_COLS, TILE)
    buckets2, res = _call_kernel(table.buckets, cols, interpret, TILE)

    res = res[:B]
    flg = res[:, O_FLAGS]
    err = (flg & 1) != 0
    vb = valid.astype(bool)
    live = vb & (~err)
    status = jnp.where(live, res[:, O_STATUS], 0)
    remaining = jnp.where(live, res[:, O_REM].astype(i64), 0)
    reset_time = jnp.where(
        live, _join64(res[:, O_RHI], res[:, O_RLO], i64), 0)
    limit_out = jnp.where(live, res[:, O_LIMIT].astype(i64), 0)
    over = (live & (status == 1)).sum(dtype=i64)
    inserts = ((flg >> 1) & 1).sum(dtype=i64)
    return PallasTable(buckets=buckets2), StepOutput(
        status=status.astype(i32), remaining=remaining,
        reset_time=reset_time, limit=limit_out,
        err=vb & err, over_count=over, insert_count=inserts)


def fused_tap_columns(batch: RequestBatch, out: StepOutput):
    """[4, B] int64 heavy-hitter tap emitted BY THE SAME device program
    as the decision step (ISSUE 8): rows are (khash bit-viewed i64,
    hits, over_limit, served).  The analytics worker drains this device
    array off the serving path (analytics.KeyAnalytics.tap_device) —
    the host-side column copies the dispatcher's tap_packed made per
    wave are deleted for fused engines.  ``served`` gates padding,
    invalid rows and table_full rows out of the sketch exactly as the
    host tap's job-scoped columns did."""
    i64 = jnp.int64
    served = batch.valid & (~out.err)
    return jnp.stack([
        lax.bitcast_convert_type(
            jnp.asarray(batch.key).astype(jnp.uint64), i64),
        jnp.asarray(batch.hits, i64),
        (out.status == 1).astype(i64),
        served.astype(i64)])


#: Jitted/donated entry point (the bench duel + battery callers):
#: table aliases in/out like decide_batch_donated.
decide_batch_pallas = jax.jit(decide_batch_pallas_impl,
                              static_argnames=("interpret", "tile"),
                              donate_argnums=(0,))
