"""``"arrivals": "burst"``: a Poisson stream whose rate is ``factor``
times its base rate for ``ms`` of every ``every_ms`` (the mix's
``"burst": {"factor": 3, "ms": 200, "every_ms": 1000}``) and whose MEAN
is the mix's ``rate_calls_per_s`` — at 30 calls/s: 64.3 calls/s for 200
ms of every second, 21.4 calls/s for the other 800 ms.

Exactly ``rate × seconds`` calls whatever the seed: as ``poisson.py``,
ONE fixed set of exponential gaps (drawn once, from a constant) that the
seed only reorders; their running sum, stretched over the window, is
then read through the inverse of the cumulative rate (a time change), so
the same gaps fall closer together inside a burst.  The seed moves the
bursts' phase inside ``every_ms``, the order of the gaps and the
connections: never the load."""
import numpy as np

_GAPS_CONSTANT = 20260930  # the fixed set of gaps


def schedule(traffic: dict, seconds: float, seed: int
             ) -> tuple[np.ndarray, np.ndarray]:
    """(due offsets in seconds, ascending; connection of each call)."""
    b = traffic["burst"]
    every, width, factor = b["every_ms"] / 1000.0, b["ms"] / 1000.0, \
        float(b["factor"])
    n = int(round(traffic["rate_calls_per_s"] * seconds))
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0xB0057]))
    gaps = np.random.default_rng(_GAPS_CONSTANT).exponential(1.0, n + 1)
    gaps = rng.permutation(gaps)
    # where the rate changes: a burst opens at phase + k × every
    phase = rng.random() * every
    opens = phase + every * np.arange(-1, int(seconds / every) + 2)
    t = np.unique(np.clip(np.r_[0.0, opens, opens + width, seconds],
                          0.0, seconds))
    mid = (t[1:] + t[:-1]) / 2
    rate = np.where((mid - phase) % every < width, factor, 1.0)
    work = np.r_[0.0, np.cumsum(rate * np.diff(t))]  # cumulative rate
    due = np.interp(np.cumsum(gaps)[:n] * (work[-1] / gaps.sum()), work, t)
    conn = rng.integers(0, traffic["callers"], n)
    return due, conn
