"""A request history and the snapshot the PARENT of the two-word table
wrote after serving it (``tests/data/snapshot_parent_format.npz``: int64
/ uint64 numpy columns, written by commit 2849552 — PR 31 — through
``ShardedEngine.snapshot`` + ``store.save_arrays``).

``tests/test_restore_place.py`` restores that file into today's table
and holds the answers that follow to an oracle that saw the same
history.  To write the file again, run this module with a checkout of
that commit first on the path:

    PYTHONPATH=<checkout> JAX_PLATFORMS=cpu python tests/snapshot_history.py
"""
import os

import numpy as np

from gubernator_tpu import Algorithm, RateLimitRequest
from gubernator_tpu.core.batch import pack_requests
from gubernator_tpu.hashing import hash_request_keys

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "snapshot_parent_format.npz")
NOW = 1_790_000_000_000
DAY = 86_400_000
#: identities filed under a chosen hash: either word may be 0
EDGE_HASH = {"lo0": 0xDEADBEEF << 32, "hi0": 0xDEADBEEF, "one": 1,
             "top": (1 << 63) | (1 << 31), "ones": (1 << 64) - 1}


def requests(hits=1):
    reqs = [RateLimitRequest(name="snap", unique_key=f"k{i}", hits=hits,
                             limit=5 + i, duration=60_000 + 1000 * i)
            for i in range(24)]
    reqs += [RateLimitRequest(name="snap", unique_key=f"l{i}", hits=hits,
                              limit=40 + i, duration=30_000, burst=50 + i,
                              algorithm=Algorithm.LEAKY_BUCKET)
             for i in range(8)]
    reqs += [RateLimitRequest(name="snap", unique_key="big", hits=hits,
                              limit=(1 << 40) + 7, duration=30 * DAY),
             RateLimitRequest(name="snap", unique_key="wide",
                              hits=(1 << 33) * hits, limit=1 << 35,
                              duration=365 * DAY)]
    reqs += [RateLimitRequest(name="snap", unique_key=u, hits=hits, limit=9,
                              duration=120_000) for u in EDGE_HASH]
    return reqs


def hashes(reqs) -> np.ndarray:
    kh = hash_request_keys([r.name for r in reqs],
                           [r.unique_key for r in reqs])
    for i, r in enumerate(reqs):
        if r.unique_key in EDGE_HASH:
            kh[i] = EDGE_HASH[r.unique_key]
    return kh


#: served before the snapshot, and after the restore
BEFORE = [(1, NOW), (2, NOW + 1_000), (1, NOW + 29_000)]
AFTER = [(1, NOW + 30_000), (3, NOW + 61_000), (1, NOW + 200_000)]


def serve(engine, batches):
    """[(status, limit, remaining, reset_time, table_full) columns]."""
    out = []
    for hits, now in batches:
        reqs = requests(hits)
        kh = hashes(reqs)
        batch, errs = pack_requests(reqs, now, size=len(reqs),
                                    key_hashes=kh)
        assert not any(errs)
        out.append(engine.check_packed(batch, kh, now))
    return out


if __name__ == "__main__":
    from gubernator_tpu.parallel import ShardedEngine, make_mesh
    from gubernator_tpu.store import save_arrays

    eng = ShardedEngine(make_mesh(n=1), capacity_per_shard=1 << 10,
                        batch_per_shard=64)
    serve(eng, BEFORE)
    snap = eng.snapshot()
    save_arrays(PATH, snap)
    print({f: (v.dtype, len(v)) for f, v in snap.items()})
