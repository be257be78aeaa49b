"""Mean number of GetRateLimits handlers in flight at a handler's
entry, itself included: `gubernator_door_inflight` over the window
(closed-loop cells; the door's pool has 32 worker threads)."""
from benchmark.harness.scrape import hist_mean


def read(ctx):
    return hist_mean(ctx["m0"], ctx["m1"], "gubernator_door_inflight")
