"""Mean wave duration, launch to resolve, as the dispatcher times it:
``gubernator_dispatcher_wave_duration`` over the window."""
from benchmark.harness.scrape import hist_mean


def read(ctx):
    v = hist_mean(ctx["m0"], ctx["m1"],
                  "gubernator_dispatcher_wave_duration")
    return None if v is None else 1000.0 * v
