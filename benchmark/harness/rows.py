"""Resident rows made from the seed, as the snapshot's columns
(``store.py › _COLUMNS``), and the key hash they are filed under.

Row i of a population is a TOKEN_BUCKET row of the population's own
limit and duration, part-used (``remaining`` in 1..limit) and stamped so
that it expires inside the first ``duration`` of the measured window:
the window's first requests are answered FROM the restored state, and
the correctness check holds them to it.  Stamps are virtual (a day ahead
of the wall clock, like the requests' ``created_at``), so the daemon's
wall-clock sweep sees every resident row live for the whole run.
"""
from __future__ import annotations

import numpy as np

from . import traffic as tr
from . import wire

_FNV_OFFSET = np.uint64(0xCBF29CE484222325)
_FNV_PRIME = np.uint64(0x100000001B3)


def key_hash(name: str, kids: np.ndarray) -> np.ndarray:
    """64-bit identity of ``name + "_" + <10 hex digits>`` as the
    program files it: FNV-1a 64, a splitmix64 finaliser, 0 → 1.
    Vectorised over the keys (the bytes of a key are walked in a loop
    over the fixed key LENGTH, not over the keys)."""
    prefix = (name + "_").encode()
    with np.errstate(over="ignore"):
        h0 = _FNV_OFFSET
        for b in prefix:
            h0 = (h0 ^ np.uint64(b)) * _FNV_PRIME
        h = np.full(len(kids), h0, np.uint64)
        for col in wire.key_digits(kids).T:
            h = (h ^ col.astype(np.uint64)) * _FNV_PRIME
        h ^= h >> np.uint64(30)
        h *= np.uint64(0xBF58476D1CE4E5B9)
        h ^= h >> np.uint64(27)
        h *= np.uint64(0x94D049BB133111EB)
        h ^= h >> np.uint64(31)
    return np.where(h == 0, np.uint64(1), h)


def remaining0(index: np.ndarray, pop: dict, seed: int) -> np.ndarray:
    """Restored ``remaining`` of key index i: 1..limit."""
    i = np.asarray(index, np.int64)
    return 1 + (i * 7919 + seed % 1000003) % pop["limit"]


def expire0(index: np.ndarray, pop: dict, seed: int, v0: int) -> np.ndarray:
    """Restored ``expire_at`` of key index i: inside (v0, v0+duration)."""
    i = np.asarray(index, np.int64)
    return v0 + 1 + (i * 104729 + seed % 1000003) % (pop["duration_ms"] - 1)


def snapshot_columns(pop: dict, seed: int, v0: int) -> dict:
    """All rows of one population, ready for ``engine.restore``."""
    n = pop["keys"]
    i = np.arange(n, dtype=np.int64)
    exp = expire0(i, pop, seed, v0)
    dur = np.full(n, pop["duration_ms"], np.int64)
    lim = np.full(n, pop["limit"], np.int64)
    return {
        "key": key_hash(pop["name"], tr.key_id(i, seed)),
        "meta": np.zeros(n, np.int32),  # TOKEN_BUCKET, UNDER_LIMIT
        "limit": lim, "duration": dur, "eff_ms": dur.copy(),
        "burst": lim.copy(),
        "remaining": remaining0(i, pop, seed),
        "t_ms": exp - dur, "expire_at": exp,
    }
