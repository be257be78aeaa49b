"""Persistence hooks and checkpoint/resume.

reference: store.go › Store{OnChange, Get, Remove} (synchronous
write-through per mutation) and Loader{Load, Save} (startup/shutdown
snapshot), plus MockStore/MockLoader used by the test suite —
reconstructed, mount empty.

The TPU design checkpoints the device table as plain arrays: TableState
is a NamedTuple of [capacity] columns, so Save/Load is a device→host
`np.savez` round-trip (SURVEY.md §5.4) — no per-item heap walk.  The
item-granular Store/Loader protocols are kept for API parity and for
user-supplied databases; the array fast path is `save_table`/`load_table`.
"""
from __future__ import annotations

import io
import os
import tempfile
from dataclasses import dataclass, field
from typing import Iterable, Iterator, List, Optional, Protocol

import numpy as np

from .types import Algorithm, RateLimitRequest


@dataclass
class CacheItem:
    """One persisted rate-limit counter.

    reference: cache.go › CacheItem (Algorithm/Key/Value/ExpireAt); the
    value fields are flattened here instead of an interface{} payload.
    """

    key: str = ""
    key_hash: int = 0  # 64-bit identity; 0 = unknown (rehash from key)
    algorithm: int = int(Algorithm.TOKEN_BUCKET)
    limit: int = 0
    duration: int = 0
    eff_ms: int = 1
    burst: int = 0
    remaining: int = 0  # token: tokens; leaky: td fixed point
    t_ms: int = 0
    expire_at: int = 0
    status: int = 0


class Store(Protocol):
    """Write-through persistence, invoked synchronously around cache
    mutations.  reference: store.go › Store."""

    def on_change(self, req: RateLimitRequest, item: CacheItem) -> None: ...

    def get(self, req: RateLimitRequest) -> Optional[CacheItem]: ...

    def remove(self, key: str) -> None: ...


class Loader(Protocol):
    """Snapshot persistence at daemon startup/shutdown.
    reference: store.go › Loader."""

    def load(self) -> Iterable[CacheItem]: ...

    def save(self, items: Iterator[CacheItem]) -> None: ...


@dataclass
class MockStore:
    """In-memory Store recording calls (reference: store.go › MockStore)."""

    called: dict = field(default_factory=lambda: {
        "on_change": 0, "get": 0, "remove": 0})
    items: dict = field(default_factory=dict)

    def on_change(self, req: RateLimitRequest, item: CacheItem) -> None:
        self.called["on_change"] += 1
        self.items[item.key or req.key] = item

    def get(self, req: RateLimitRequest) -> Optional[CacheItem]:
        self.called["get"] += 1
        return self.items.get(req.key)

    def remove(self, key: str) -> None:
        self.called["remove"] += 1
        self.items.pop(key, None)


@dataclass
class MockLoader:
    """In-memory Loader recording calls (reference: store.go › MockLoader)."""

    called: dict = field(default_factory=lambda: {"load": 0, "save": 0})
    contents: List[CacheItem] = field(default_factory=list)

    def load(self) -> Iterable[CacheItem]:
        self.called["load"] += 1
        return list(self.contents)

    def save(self, items: Iterator[CacheItem]) -> None:
        self.called["save"] += 1
        self.contents = list(items)


class FileLoader:
    """Loader persisting to an .npz snapshot file (the array fast path)."""

    def __init__(self, path: str):
        self.path = path

    def load(self) -> Iterable[CacheItem]:
        if not os.path.exists(self.path):
            return []
        return items_from_arrays(dict(np.load(self.path, allow_pickle=False)))

    def save(self, items: Iterator[CacheItem]) -> None:
        arrays = arrays_from_items(list(items))
        save_arrays(self.path, arrays)


_COLUMNS = ("key", "meta", "limit", "duration", "eff_ms", "burst",
            "remaining", "t_ms", "expire_at")


def save_arrays(path: str, arrays: dict) -> None:
    """Atomic .npz write (tmp + rename) — a crash mid-save keeps the old
    snapshot, matching the reference's expectation that Save is all-or-
    nothing at daemon shutdown."""
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".npz.tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            np.savez_compressed(f, **arrays)
        os.replace(tmp, path)
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise


def table_to_arrays(state) -> dict:
    """Device TableState → host column dict (drops empty rows)."""
    from .core.table import to_host

    cols = to_host(state)
    live = cols["key"] != 0
    return {name: col[live] for name, col in cols.items()}


def items_from_arrays(arrays: dict) -> List[CacheItem]:
    n = len(arrays["key"])
    out = []
    for i in range(n):
        meta = int(arrays["meta"][i])
        out.append(CacheItem(
            key="", key_hash=int(arrays["key"][i]),
            algorithm=meta & 1, status=(meta >> 1) & 1,
            limit=int(arrays["limit"][i]),
            duration=int(arrays["duration"][i]),
            eff_ms=int(arrays["eff_ms"][i]),
            burst=int(arrays["burst"][i]),
            remaining=int(arrays["remaining"][i]),
            t_ms=int(arrays["t_ms"][i]),
            expire_at=int(arrays["expire_at"][i]),
        ))
    return out


def arrays_from_items(items: List[CacheItem]) -> dict:
    from .hashing import hash_key

    n = len(items)
    arrays = {
        "key": np.zeros(n, np.uint64),
        "meta": np.zeros(n, np.int32),
        "limit": np.zeros(n, np.int64),
        "duration": np.zeros(n, np.int64),
        "eff_ms": np.ones(n, np.int64),
        "burst": np.zeros(n, np.int64),
        "remaining": np.zeros(n, np.int64),
        "t_ms": np.zeros(n, np.int64),
        "expire_at": np.zeros(n, np.int64),
    }
    for i, it in enumerate(items):
        kh = it.key_hash
        if kh == 0 and it.key:
            name, _, uniq = it.key.partition("_")
            kh = hash_key(name, uniq)
        arrays["key"][i] = np.uint64(kh)
        arrays["meta"][i] = (it.algorithm & 1) | ((it.status & 1) << 1)
        arrays["limit"][i] = it.limit
        arrays["duration"][i] = it.duration
        arrays["eff_ms"][i] = max(it.eff_ms, 1)
        arrays["burst"][i] = it.burst
        arrays["remaining"][i] = it.remaining
        arrays["t_ms"][i] = it.t_ms
        arrays["expire_at"][i] = it.expire_at
    return arrays
