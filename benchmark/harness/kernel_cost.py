"""What the decision kernel has to move, computed from shapes.

The table is ``[buckets, 16, 128] int32``: a key's whole probe window is
one 8 KiB bucket (128 slots × 64 B).  A grid step of the kernel takes a
tile of 128 requests and, for each DISTINCT bucket among them, reads the
bucket once from HBM and writes it back once (``ops/pallas_step.py``);
requests that share a bucket inside a tile share the copy in VMEM.  The
request columns and the response rows (a few hundred bytes a request)
are left out, so the count is a floor of the traffic, and the roofline
share computed from it a floor of the share.
"""
from __future__ import annotations

import numpy as np

BUCKET_BYTES = 16 * 128 * 4
TILE = 128


def decide_bytes_per_row(key_index: np.ndarray, n_per_call: np.ndarray
                         ) -> float:
    """Mean HBM bytes a served row needs: 2 × 8 KiB for every distinct
    key of each 128-row tile of each call, over the rows.  Distinct keys
    stand for distinct buckets (two keys of one tile in one of 2^19
    buckets is a once-in-thousands event, and counts the traffic a
    little too HIGH only there); calls that the dispatcher merges into
    one wave can share more, never less."""
    rows = int(n_per_call.sum())
    if rows == 0:
        return 0.0
    call = np.repeat(np.arange(len(n_per_call)), n_per_call)
    pos = np.arange(rows) - np.repeat(np.cumsum(n_per_call) - n_per_call,
                                      n_per_call)
    tile = call * (int(n_per_call.max()) // TILE + 1) + pos // TILE
    distinct = len(np.unique(np.stack([tile, key_index]), axis=1).T)
    return 2.0 * BUCKET_BYTES * distinct / rows
