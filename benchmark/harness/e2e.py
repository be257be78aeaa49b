"""The end-to-end metrics, taken by the benchmark itself on the client's
clock: ``METRICS[name](window, traffic) -> value``.

A rate is all the answers received inside the window over all of its
seconds; a latency statistic is over every call of the window that was
answered (a failed call has no latency and makes the run not correct).
"""
from __future__ import annotations

import numpy as np


def _answered_in_window(w: dict) -> np.ndarray:
    rec = w["rec"]
    return (rec["ok"] & (rec["answered"] == rec["n"])
            & (rec["done"] >= w["start_at"]) & (rec["done"] <= w["end"]))


def decisions_per_s(w: dict, traffic: dict) -> float:
    return float(w["rec"]["n"][_answered_in_window(w)].sum()) / w["seconds"]


def latencies_ms(w: dict, traffic: dict) -> np.ndarray:
    """Closed loop: send → answer, the calls answered inside the window.
    Open loop: DUE time → answer, every call that was due in the window
    (they all are), so a stall's wait is counted on the calls behind it."""
    rec = w["rec"]
    if traffic["loop"] == "open":
        sel = rec["ok"] & (rec["answered"] == rec["n"])
    else:
        sel = _answered_in_window(w)
    return 1000.0 * (rec["done"][sel] - rec["due"][sel])


def _stat(fn):
    def metric(w: dict, traffic: dict) -> float:
        lat = latencies_ms(w, traffic)
        return float(fn(lat)) if len(lat) else float("nan")
    return metric


METRICS = {
    "decisions_per_s": decisions_per_s,
    "call_p50_ms": _stat(np.median),
    "call_mean_ms": _stat(np.mean),
    "call_p90_ms": _stat(lambda x: np.percentile(x, 90)),
}
