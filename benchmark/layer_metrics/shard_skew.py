"""How uneven the shards of the window's device waves were: the rows of
each wave's densest shard, summed, × the configuration's chips ÷ the
rows the waves carried — 1.0 is an even wave, the chip count one shard
holding everything.  Δ``gubernator_wave_densest_shard_rows_total`` and
Δ``gubernator_wave_routed_rows_total`` between the window's scrapes
(``ShardedEngine._count_route``, once a device wave; the densest shard
is what ``_build_waves`` chose the wave's bucket by).  A program
without the counters reads nothing."""
from benchmark.harness import shard_cost
from benchmark.harness.scrape import delta


def read(ctx):
    m0, m1 = ctx["m0"], ctx["m1"]
    rows = delta(m0, m1, shard_cost.ROUTED_ROWS)
    densest = delta(m0, m1, shard_cost.DENSEST_ROWS)
    if rows <= 0 or densest <= 0:
        return None
    return densest * ctx["config"]["chips"] / rows
