"""Share of the window's sorted-route device waves — routed by shard
and scattered into a pair ``shards × bucket`` wide — that the program's
C++ extension planned and filled, one pass each that keeps the GIL
(``ops/_native.cpp › route_plan``, ``route_fill``), in %:
Δ``gubernator_wave_native_route_total`` ÷
Δ``gubernator_wave_route_total{route="sorted"}`` between the window's
scrapes; the rest took the numpy route on the dispatch worker.  Both
are incremented once a device wave at ``ShardedEngine._count_route``.
A program without the counter, or a window without a sorted wave, reads
nothing."""
from benchmark.harness.scrape import delta

NATIVE = "gubernator_wave_native_route_total"
ROUTE = "gubernator_wave_route_total"


def read(ctx):
    m0, m1 = ctx["m0"], ctx["m1"]
    if NATIVE not in m1:
        return None
    waves = delta(m0, m1, ROUTE, 'route="sorted"')
    if waves <= 0:
        return None
    return 100.0 * delta(m0, m1, NATIVE) / waves
