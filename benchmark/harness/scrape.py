"""Helpers over two scrapes of the daemon's ``/metrics`` page."""
from __future__ import annotations


def delta(m0: dict, m1: dict, prefix: str, must_contain: str = "") -> float:
    return sum(v - m0.get(k, 0.0) for k, v in m1.items()
               if k.startswith(prefix) and must_contain in k)


def hist_mean(m0: dict, m1: dict, name: str):
    """Mean of a histogram's observations between the scrapes, or None."""
    n = delta(m0, m1, name + "_count")
    return delta(m0, m1, name + "_sum") / n if n > 0 else None
