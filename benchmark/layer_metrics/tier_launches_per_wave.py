"""Mean device launches of a dispatcher wave over the window:
Δ``gubernator_wave_route_total`` summed over every ``route`` (one
``inc`` a device wave, ``ShardedEngine._count_route``: the wave itself
and every re-dispatch of its unanswered rows at sync) ÷ the waves
``gubernator_dispatcher_wave_size`` counted.  A wave all of whose rows
the device answers reads 1; with a cold tier bound the sync side
re-dispatches the rows that erred and the rows that rode invalid, and
each launch of those is a blocking round trip under the engine lock.
A program without the counter reads nothing."""
from benchmark.harness.scrape import delta

NAME = "gubernator_wave_route_total"


def read(ctx):
    m0, m1 = ctx["m0"], ctx["m1"]
    if not any(k.startswith(NAME) for k in m1):
        return None
    waves = delta(m0, m1, "gubernator_dispatcher_wave_size_count")
    return delta(m0, m1, NAME) / waves if waves > 0 else None
