"""The counter table: one struct-of-arrays resident in HBM.

TPU-native replacement for the reference's Cache interface + LRUCache
(cache.go › Cache{Add, GetItem, UpdateExpiration, Each, Remove},
lrucache.go › LRUCache — reconstructed): instead of millions of heap
items behind a map + intrusive list, all state lives in fixed-capacity
parallel arrays; key→row is an open-addressing (double-hash probe) table
over the ``key`` column.

Layout: nine logical columns, 68 B a row.  ``meta`` is one int32
column; each of the eight 64-bit columns is held as TWO uint32 word
columns of the table's shape (``Words``: low word, high word) — never
as one 64-bit array.  A TPU has no 64-bit lanes and XLA:TPU carries an
int64 as such a pair anyway: a program handed a ``[cap]`` int64 array
splits ALL of it at entry and recombines ALL of it at exit, whatever
it touches (PERF.md §6, PR 31/32: ~60 of a 69-ms step at 2^26 rows).
Held as words, a column is only ever gathered and scattered at the
rows a wave names; 64-bit values exist at wave size — ``take_rows``
joins the gathered halves, the decision arithmetic stays int64,
``put_rows`` splits and scatters the halves — and a pass over the whole
table (the sweep) compares on the words.  One representation on every
backend and for every table (the 2^26-row shard and the 4,096-slot
replica maps alike).  The HOST side keeps int64 / uint64 numpy columns
(snapshots, store.py, restore): ``to_host`` / ``from_host`` convert at
the boundary, by views and one copy a column.

Eviction model (documented deviation, SURVEY.md §7.1): the reference
evicts strict-LRU at capacity; here expired rows are reclaimed by
``sweep_expired`` and capacity pressure is handled by sizing CAPACITY for
the working set.  Decision parity is unaffected: an expired item and a
missing item produce identical responses (both take the fresh-item path).
"""
from __future__ import annotations

import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

jax.config.update("jax_enable_x64", True)

# meta column bit layout
META_ALG_MASK = 1  # bit0: Algorithm (0 token, 1 leaky)
META_STATUS_SHIFT = 1  # bit1: stored Status (for hits=0 queries)

#: K-split scatter fallback (GUBER_KSPLIT=<log2 window>, default off):
#: a TPU compiler that serializes the donated step's table scatters at
#: large CAP can be worked around by performing every table-row scatter
#: as CAP/2^K slice-local scatters — subtracting each window's base
#: preserves BOTH scatter promises (an ascending+unique index vector
#: stays ascending+unique; rows outside the window fall out of bounds
#: and drop), so no masking is needed.  Opt-in: on backends WITHOUT
#: the pathology it is pure overhead (measured 2x on XLA:CPU at CAP
#: 2^22 — the per-window concatenate streams the table).  Not measured
#: on the current stack.
KSPLIT_LOG2 = int(os.environ.get("GUBER_KSPLIT", "0"))


class Words(NamedTuple):
    """A 64-bit column as its two 32-bit word columns (same shape).
    The minor dimension is the table's: never ``[cap, 2]``, which the
    chip would pad to 128 lanes."""

    lo: jax.Array  # uint32[..., cap], bits 0–31
    hi: jax.Array  # uint32[..., cap], bits 32–63


class TableState(NamedTuple):
    """Parallel [capacity] columns; one row per tracked rate-limit key.

    ``key`` is the 64-bit identity hash (both words 0 = empty slot).
    ``remaining`` holds tokens for TOKEN_BUCKET rows and token-duration
    fixed-point for LEAKY_BUCKET rows (see oracle.py module docstring).
    ``t_ms`` is created_at for token rows, updated_at for leaky rows.
    The logical dtypes (the host's) are ``COLUMN_DTYPES``.
    """

    key: Words  # uint64, 0 = empty
    meta: jax.Array  # int32[cap], bit0 alg, bit1 stored status
    limit: Words  # int64
    duration: Words  # int64, as given (ms or Gregorian ordinal)
    eff_ms: Words  # int64, effective ms denominator
    burst: Words  # int64
    remaining: Words  # int64
    t_ms: Words  # int64
    expire_at: Words  # int64, 0 = never-written (always expired)

    @property
    def capacity(self) -> int:
        return self.meta.shape[0]


#: a column's dtype on the host (snapshots, store.py, the Loader files)
COLUMN_DTYPES = {f: np.int64 for f in TableState._fields}
COLUMN_DTYPES.update(key=np.uint64, meta=np.int32)


def init_table(capacity: int) -> TableState:
    """Empty table.  ``capacity`` must be a power of two (probe masking)."""
    if capacity & (capacity - 1) or capacity <= 0:
        raise ValueError(f"capacity must be a power of two, got {capacity}")
    if not jax.config.jax_enable_x64:
        # Guard against an embedding application resetting the flag after
        # our import-time enable: the wave-sized int64 arithmetic would
        # silently become int32 and overflow on epoch-ms.
        raise RuntimeError(
            "gubernator_tpu requires jax_enable_x64 (int64 epoch-ms "
            "arithmetic); it was disabled after import")

    def words(lo=0):
        return Words(lo=jnp.full((capacity,), lo, jnp.uint32),
                     hi=jnp.zeros((capacity,), jnp.uint32))

    return TableState(
        key=words(), meta=jnp.zeros((capacity,), jnp.int32),
        limit=words(), duration=words(), eff_ms=words(1), burst=words(),
        remaining=words(), t_ms=words(), expire_at=words())


# ---- 64-bit values ↔ words, at wave size ------------------------------


def split64(x) -> Words:
    """int64 / uint64 values → their words."""
    u = jnp.asarray(x).astype(jnp.uint64)
    return Words(lo=u.astype(jnp.uint32),
                 hi=(u >> jnp.uint64(32)).astype(jnp.uint32))


def join64(w: Words, dtype=jnp.int64) -> jax.Array:
    """Words → the 64-bit values they spell."""
    u = (w.hi.astype(jnp.uint64) << jnp.uint64(32)) | w.lo.astype(jnp.uint64)
    return u.astype(dtype)


def take_rows(col, idx, fill: int = 0) -> jax.Array:
    """``col`` at rows ``idx`` (``fill`` where out of bounds): a word
    column's halves gathered and joined to int64, ``meta`` as it is."""
    if not isinstance(col, Words):
        return col.at[idx].get(mode="fill", fill_value=fill)
    fill &= (1 << 64) - 1
    return join64(Words(
        lo=col.lo.at[idx].get(mode="fill", fill_value=fill & 0xFFFFFFFF),
        hi=col.hi.at[idx].get(mode="fill", fill_value=fill >> 32)))


def _put(col, idx, vals, sorted_idx: bool, unique: bool):
    cap = col.shape[0]
    if not unique:
        return col.at[idx].set(vals, mode="drop")
    if not KSPLIT_LOG2 or cap <= (1 << KSPLIT_LOG2):
        return col.at[idx].set(vals, mode="drop", unique_indices=True,
                               indices_are_sorted=sorted_idx)
    S = 1 << KSPLIT_LOG2
    # Out-of-window rows get DISTINCT >= S sentinels (dropped): a plain
    # idx - base would send below-window rows NEGATIVE, and negative
    # scatter indices WRAP (numpy semantics), corrupting the window's
    # tail.  The remap keeps uniqueness but not global order, so the
    # per-window scatters promise unique only — uniqueness is what
    # unlocks the parallel lowering; sortedness is a secondary hint the
    # split trades away.
    arange_b = jnp.arange(idx.shape[0], dtype=idx.dtype)
    parts = []
    for k in range(cap // S):
        base = k * S
        loc = jnp.where((idx >= base) & (idx < base + S),
                        idx - base, S + arange_b)
        sl = lax.slice_in_dim(col, base, base + S)
        parts.append(sl.at[loc].set(vals, mode="drop",
                                    unique_indices=True))
    return lax.concatenate(parts, 0)


def put_rows(col, idx, vals, *, sorted_idx: bool = False,
             unique: bool = True):
    """Table-row scatter: ``vals`` (for a word column: 64-bit values,
    split here, or their ``Words``) written at rows ``idx``; entries
    out of [0, cap) are drop sentinels.  ``unique`` / ``sorted_idx`` are the backend
    promises of the call site (unique_indices / indices_are_sorted —
    UB if lied about: tests/test_scatter_invariants.py); a promised
    scatter is K-split when enabled (see KSPLIT_LOG2)."""
    if not isinstance(col, Words):
        return _put(col, idx, vals, sorted_idx, unique)
    v = vals if isinstance(vals, Words) else split64(vals)
    return Words(lo=_put(col.lo, idx, v.lo, sorted_idx, unique),
                 hi=_put(col.hi, idx, v.hi, sorted_idx, unique))


def is_empty(key: Words) -> jax.Array:
    """The empty mark: BOTH words 0 (a live key may have either 0)."""
    return (key.lo == 0) & (key.hi == 0)


def match_rows(tkey: Words, slots, key: Words):
    """(match, empty) bool[B, P]: which of each request's probe
    ``slots`` holds its key, and which is free — on the words, no
    64-bit value is made."""
    at = Words(lo=tkey.lo[slots], hi=tkey.hi[slots])
    match = (at.lo == key.lo[:, None]) & (at.hi == key.hi[:, None])
    return match, is_empty(at)


def le64(a: Words, b: Words) -> jax.Array:
    """Signed ``a <= b`` on words: signed high word, unsigned low."""
    ahi = lax.bitcast_convert_type(a.hi, jnp.int32)
    bhi = lax.bitcast_convert_type(b.hi, jnp.int32)
    return (ahi < bhi) | ((ahi == bhi) & (a.lo <= b.lo))


def occupancy(state: TableState) -> jax.Array:
    """Number of live rows (cache-size gauge analog, lrucache.go)."""
    return (~is_empty(state.key)).sum(dtype=jnp.int32)


@jax.jit
def sweep_expired(state: TableState, now_ms: jax.Array) -> TableState:
    """Reclaim rows whose expiry has passed.

    Parity-safe: an expired row and an empty row behave identically on
    next access (fresh-item path), so clearing keys changes no decisions.
    Replaces the reference's LRU eviction + UpdateExpiration bookkeeping.
    """
    dead = le64(state.expire_at, split64(jnp.asarray(now_ms, jnp.int64)))

    def clear(w: Words) -> Words:
        return Words(lo=jnp.where(dead, jnp.uint32(0), w.lo),
                     hi=jnp.where(dead, jnp.uint32(0), w.hi))

    return state._replace(
        key=clear(state.key),
        # Also zero expire_at so a later occupant of the slot is
        # unconditionally fresh even if its first access carries an
        # earlier now_ms (caller clock skew) than the dead row's expiry.
        expire_at=clear(state.expire_at),
    )


# ---- the host boundary --------------------------------------------------
#
# Snapshots, store.py and the Loader files keep the parent's format:
# one int64 / uint64 / int32 numpy column a field.  Little-endian
# hosts: a 64-bit value's low word comes first in memory.


def column_to_host(col, dtype) -> np.ndarray:
    """One column (device or numpy leaves) → a writable numpy column in
    its logical ``dtype``: a word column's halves interleaved in ONE
    copy."""
    if not isinstance(col, Words):
        return np.array(col)
    lo = np.asarray(col.lo)
    both = np.empty(lo.shape + (2,), np.uint32)
    both[..., 0] = lo
    both[..., 1] = np.asarray(col.hi)
    return both.view(dtype).reshape(lo.shape)


def to_host(state: TableState) -> dict:
    """Device table → {field: numpy column}, store.py's format."""
    return {f: column_to_host(col, COLUMN_DTYPES[f])
            for f, col in zip(state._fields, state)}


def from_host(arrays: dict) -> TableState:
    """{field: numpy column} → a TableState of numpy leaves, the word
    columns as strided VIEWS of the 64-bit ones (no copy); the caller
    places it (``jax.device_put(tree, sharding)``) or reads rows of
    it."""
    cols = {}
    for f in TableState._fields:
        a = np.ascontiguousarray(arrays[f], dtype=COLUMN_DTYPES[f])
        if a.dtype.itemsize == 8:
            v = a.view(np.uint32).reshape(a.shape + (2,))
            cols[f] = Words(lo=v[..., 0], hi=v[..., 1])
        else:
            cols[f] = a
    return TableState(**cols)
