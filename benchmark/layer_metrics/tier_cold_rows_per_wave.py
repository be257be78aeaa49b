"""Mean rows of a dispatcher wave that the HOST cold tier answered over
the window — cold-resident keys (pre-masked out of the device wave) and
first-seen keys whose device bucket was full:
``gubernator_tier_cold_serves`` (one ``inc(n)`` a wave's cold lane,
``tiering.py › TierController.resolve``) ÷ the waves
``gubernator_dispatcher_wave_size`` counted.  Beside ``rows_per_wave``
it is the share of a wave that leaves the device lane.  A program
without the counter reads nothing."""
from benchmark.harness.scrape import delta

NAME = "gubernator_tier_cold_serves_total"


def read(ctx):
    m0, m1 = ctx["m0"], ctx["m1"]
    if not any(k.startswith(NAME) for k in m1):
        return None
    waves = delta(m0, m1, "gubernator_dispatcher_wave_size_count")
    return delta(m0, m1, NAME) / waves if waves > 0 else None
