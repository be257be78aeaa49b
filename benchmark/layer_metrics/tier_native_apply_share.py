"""Share of the window's cold-tier requests that ONE C++ pass applied
(``ops/_native.cpp › cold_apply_batch``, from ``tiering.py ›
TierController.resolve`` over the native store), in %:
Δ``gubernator_tier_cold_native_serves_total`` ÷
Δ``gubernator_tier_cold_serves_total`` between the window's scrapes.
The rest went through the Python loop of ``_host_apply`` calls — the
dict store's lane (``GUBER_TIER_NATIVE=0``), and what a daemon falls to
in silence on a ``_native*.so`` built before the pass existed.  Both
counters are incremented side by side, one ``inc(n)`` a wave's cold
lane.  A program without the counter, or a window in which the cold tier
served nothing, reads nothing."""
from benchmark.harness.scrape import delta

NATIVE = "gubernator_tier_cold_native_serves_total"
SERVES = "gubernator_tier_cold_serves_total"


def read(ctx):
    m0, m1 = ctx["m0"], ctx["m1"]
    if NATIVE not in m1:
        return None
    served = delta(m0, m1, SERVES)
    if served <= 0:
        return None
    return 100.0 * delta(m0, m1, NATIVE) / served
