"""Mean number of GetRateLimits handlers in flight at a handler's
entry, itself included: `gubernator_door_inflight` over the window
(the open-loop cell)."""
from benchmark.harness.scrape import hist_mean


def read(ctx):
    return hist_mean(ctx["m0"], ctx["m1"], "gubernator_door_inflight")
