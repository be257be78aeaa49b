"""Pallas TPU kernel: fused expired-row sweep + live-row count.

The decision step itself is deliberately plain XLA (ARCHITECTURE.md §2:
scattered 72-byte row updates don't map onto TPU DMA, while XLA's dense
fusion already exceeds the perf target 11×).  The sweep is the opposite
case — a pure dense streaming pass over the table — which is exactly
the memory-bound shape Pallas is for, and fusing the occupancy count
into the same pass halves its HBM traffic vs. sweep-then-count.

TPU Mosaic has no 64-bit vector lanes, and the table has no 64-bit
columns (core/table.py › Words): the kernel takes the key's and
expire_at's word columns as they are and the expiry comparison is done
on the words (signed hi, unsigned lo).  Set ``interpret=True`` (or run
on CPU) for the reference-interpreter path used by tests.

Usage: ``sweep_expired_pallas(state, now_ms)`` — a drop-in equivalent
of core/table.py › sweep_expired that also returns the live-row count.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.table import TableState, Words, split64

LANES = 128
BLK = 8  # sublanes per block → (8, 128) 32-bit tiles


def _sweep_kernel(now_ref, khi_ref, klo_ref, ehi_ref, elo_ref,
                  khi_out, klo_out, ehi_out, elo_out, live_ref):
    """One (BLK, LANES) tile: zero dead rows, accumulate live count."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        # pinned dtype: a bare 0 is weakly-typed and becomes an i64
        # constant under x64, which Mosaic refuses to store/return
        live_ref[0] = jnp.int32(0)

    now_hi, now_lo = now_ref[0], now_ref[1]
    ehi_w, elo_w = ehi_ref[:], elo_ref[:]
    # expire_at <= now on the words: signed hi compare, unsigned lo.
    # The uint32 words are reinterpreted in registers (a bitcast in the
    # wrapper would be a pass over the column of its own); flipping the
    # low words' sign bit makes int32 compare order match the unsigned
    # order.
    ehi = lax.bitcast_convert_type(ehi_w, jnp.int32)
    elo = lax.bitcast_convert_type(elo_w, jnp.int32)
    flip = jnp.int32(-2147483648)
    expired = (ehi < now_hi) | ((ehi == now_hi) &
                                (elo ^ flip <= now_lo ^ flip))
    khi, klo = khi_ref[:], klo_ref[:]
    zero = jnp.zeros_like(khi)
    empty = (khi == zero) & (klo == zero)
    # zero exactly what sweep_expired zeroes (expired rows only — an
    # empty row's stale expire_at is never read, and bit-equality with
    # the XLA sweep is what the parity tests assert)
    khi_out[:] = jnp.where(expired, zero, khi)
    klo_out[:] = jnp.where(expired, zero, klo)
    ehi_out[:] = jnp.where(expired, zero, ehi_w)
    elo_out[:] = jnp.where(expired, zero, elo_w)
    # count in float32: with x64 enabled, jnp.sum on int32 routes through
    # an int64 accumulator (numpy promotion) even when dtype=int32 is
    # passed, and Mosaic cannot lower 64-bit; f32 is promotion-stable and
    # exact here (a tile holds BLK×LANES = 1024 ≪ 2^24 elements)
    live = ~(expired | empty)
    live_ref[0] += jnp.sum(live.astype(jnp.float32)).astype(jnp.int32)


def _sweep_2d(khi, klo, ehi, elo, now_hi_lo, *, interpret: bool):
    rows = khi.shape[0]
    grid = (rows // BLK,)
    tile = pl.BlockSpec((BLK, LANES), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)
    out_shape = jax.ShapeDtypeStruct((rows, LANES), jnp.uint32)
    # x64 off while tracing the kernel: every operand is already int32,
    # but under x64 the BlockSpec index_map's literals trace as i64
    # scalars and Mosaic fails to legalize the index function's return
    with jax.enable_x64(False):
        return pl.pallas_call(
            _sweep_kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),  # now (2,) scalar
                tile, tile, tile, tile,
            ],
            out_specs=[tile, tile, tile, tile,
                       pl.BlockSpec(memory_space=pltpu.SMEM)],
            out_shape=[out_shape, out_shape, out_shape, out_shape,
                       jax.ShapeDtypeStruct((1,), jnp.int32)],
            interpret=interpret,
        )(now_hi_lo, khi, klo, ehi, elo)


@partial(jax.jit, static_argnames=("interpret",))
def sweep_expired_pallas(state: TableState, now_ms, *,
                         interpret: bool = False
                         ) -> tuple[TableState, jax.Array]:
    """Fused sweep + occupancy: (new state, live-row count).

    Semantically identical to core/table.py › sweep_expired (dead rows
    get key=0 AND expire_at=0 so later occupants are unconditionally
    fresh), plus the live count from the same pass.
    """
    cap = state.capacity
    if cap % (BLK * LANES):
        raise ValueError(f"capacity {cap} not a multiple of {BLK * LANES}")

    def tiles(x):  # a word column as the kernel's [rows, LANES]
        return x.reshape(cap // LANES, LANES)

    now = split64(jnp.asarray(now_ms, jnp.int64))
    now_hi_lo = lax.bitcast_convert_type(jnp.stack([now.hi, now.lo]),
                                         jnp.int32)
    key, exp = state.key, state.expire_at
    khi, klo, ehi, elo, live = _sweep_2d(
        tiles(key.hi), tiles(key.lo), tiles(exp.hi), tiles(exp.lo),
        now_hi_lo, interpret=interpret)
    return state._replace(
        key=Words(lo=klo.reshape(-1), hi=khi.reshape(-1)),
        expire_at=Words(lo=elo.reshape(-1), hi=ehi.reshape(-1))), live[0]
