"""Share of the window's device waves whose rows reached the upload
buffers by the identity route — the calls' blocks joined straight into
the lease, no shard sort, no scatter — in %:
``gubernator_wave_route_total{route="identity"}`` ÷ both routes (the
engine counts one a device wave, in ``launch_packed``).  A program
without the counter reads nothing."""
from benchmark.harness.scrape import delta

NAME = "gubernator_wave_route_total"


def read(ctx):
    m0, m1 = ctx["m0"], ctx["m1"]
    waves = delta(m0, m1, NAME)
    if waves <= 0:
        return None
    return 100.0 * delta(m0, m1, NAME, 'route="identity"') / waves
