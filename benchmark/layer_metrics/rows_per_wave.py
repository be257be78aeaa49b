"""Mean requests per coalesced device wave over the window:
``gubernator_dispatcher_wave_size``."""
from benchmark.harness.scrape import hist_mean


def read(ctx):
    return hist_mean(ctx["m0"], ctx["m1"], "gubernator_dispatcher_wave_size")
