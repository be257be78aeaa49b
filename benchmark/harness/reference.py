"""The plain reference: upstream's token bucket (``algorithms.go ›
tokenBucket``) for one fixed limit and duration, in exact integers, one
request after another.  It imports nothing of the program.

``precision="float32"`` is the CONTROL, not a reference: the same walk
with its time arithmetic in float32 (the nearest step below the int64
epoch-millisecond arithmetic the configuration states).  An epoch-ms
stamp needs 41 bits and float32 keeps 24, so its reset times land on a
131-second grid: the check has to call that not correct.
"""
from __future__ import annotations

import numpy as np

UNDER, OVER = 0, 1


class TokenBucket:
    def __init__(self, limit: int, duration_ms: int,
                 precision: str = "int64"):
        if precision not in ("int64", "float32"):
            raise ValueError(precision)
        self.limit, self.duration = limit, duration_ms
        self.low = precision == "float32"
        self.rows: dict = {}  # key -> [remaining, expire_at]

    def seed_row(self, key: int, remaining: int, expire_at: int) -> None:
        self.rows[key] = [int(remaining), int(expire_at)]

    def _expire(self, now: int) -> int:
        if self.low:
            return int(np.float32(now) + np.float32(self.duration))
        return now + self.duration

    def hit(self, key: int, now: int) -> tuple[int, int, int, int]:
        """One request of hits=1 → (status, limit, remaining, reset)."""
        row = self.rows.get(key)
        expired = row is None or (
            np.float32(now) >= np.float32(row[1]) if self.low
            else now >= row[1])
        if expired:
            row = self.rows[key] = [self.limit, self._expire(now)]
        if row[0] >= 1:
            row[0] -= 1
            return UNDER, self.limit, row[0], row[1]
        return OVER, self.limit, row[0], row[1]

    def call(self, keys, now: int) -> dict:
        out = np.array([self.hit(int(k), now) for k in keys], np.int64)
        out = out.reshape(-1, 4)
        return {"status": out[:, 0], "limit": out[:, 1],
                "remaining": out[:, 2], "reset_time": out[:, 3]}
