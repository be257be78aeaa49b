"""Mean keys a dispatcher wave CREATED in the host cold tier over the
window — a served row whose key neither tier held (a first-seen key
whose device bucket is full): ``gubernator_tier_cold_creates`` (counted
beside ``gubernator_tier_cold_serves``, ``tiering.py › TierController ›
_serve``) ÷ the waves ``gubernator_dispatcher_wave_size`` counted.  A
program without the counter reads nothing."""
from benchmark.harness.scrape import delta

NAME = "gubernator_tier_cold_creates_total"


def read(ctx):
    m0, m1 = ctx["m0"], ctx["m1"]
    if not any(k.startswith(NAME) for k in m1):
        return None
    waves = delta(m0, m1, "gubernator_dispatcher_wave_size_count")
    return delta(m0, m1, NAME) / waves if waves > 0 else None
