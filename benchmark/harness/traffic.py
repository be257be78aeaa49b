"""The one general traffic generator: everything a mix needs is in its
data file ``benchmark/traffic/<name>.json``.

Keys of a traffic file
  loop               "closed" (each caller sends its next call when the
                     last one answered) or "open" (calls are sent on a
                     schedule whatever the system does)
  callers            closed: concurrent callers; open: connections
  generators         generator processes the callers are spread over
  requests_per_call  requests in one GetRateLimits call
  population         which key population of the configuration is hit
  keys               {"dist": "zipf", "a": 1.1}: numpy's unbounded
                     Zipf(a) taken modulo the population (as
                     ``chip_smoke.py``), so draws above it wrap round
  rate_calls_per_s   open loop only: offered calls per second, all
                     connections together
  arrivals           open loop only: "poisson" or "grid"

Every seed gives the same amount of work: an open mix has exactly
``rate × seconds`` calls whose gaps are ONE fixed set (drawn once, from
a constant) that the seed only reorders, so two seeds differ in order
and phase, never in load.
"""
from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_GAPS_CONSTANT = 20260927  # the fixed set of Poisson gaps


def load(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        t = json.load(f)
    if t["loop"] not in ("closed", "open"):
        raise ValueError(f"traffic {name}: loop {t['loop']!r}")
    if t["loop"] == "open" and t["arrivals"] not in ("poisson", "grid"):
        raise ValueError(f"traffic {name}: arrivals {t['arrivals']!r}")
    if t["callers"] % t["generators"]:
        raise ValueError(f"traffic {name}: callers not divisible by "
                         "generators")
    return t


def key_id(index, seed: int) -> np.ndarray:
    """Distinct 40-bit key id of key index i (an odd multiplier mod 2^40
    is a bijection), salted by the seed."""
    salt = np.uint64((seed * 0x9E3779B97F4A7C15) % (1 << 40))
    return ((np.asarray(index, np.uint64) * np.uint64(0x5851F42D4C957F2D)
             + salt) & np.uint64((1 << 40) - 1))


def caller_rng(seed: int, caller: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, caller]))


def sample_indices(rng: np.random.Generator, keys: dict, n: int,
                   population: int) -> np.ndarray:
    """n key indices in [0, population)."""
    if keys["dist"] == "zipf":
        return (rng.zipf(keys["a"], n) % population).astype(np.int64)
    raise ValueError(f"key distribution {keys['dist']!r}")


def open_schedule(traffic: dict, seconds: float, seed: int
                  ) -> tuple[np.ndarray, np.ndarray]:
    """(due offsets in seconds, ascending; connection of each call) for
    the whole mix — each generator takes the calls of its connections."""
    conns = traffic["callers"]
    n = int(round(traffic["rate_calls_per_s"] * seconds))
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0xA221]))
    if traffic["arrivals"] == "poisson":
        gaps = np.random.default_rng(_GAPS_CONSTANT).exponential(1.0, n + 1)
        gaps = rng.permutation(gaps)
        due = np.cumsum(gaps)[:n] * (seconds / gaps.sum())
        conn = rng.integers(0, conns, n)
    else:
        # each connection on its own period, phases staggered evenly,
        # the whole grid shifted by one seeded offset per run
        step = 1.0 / traffic["rate_calls_per_s"]
        due = (np.arange(n) + rng.random()) * step
        due = due[due < seconds]
        conn = np.arange(len(due)) % conns
    return due, conn
