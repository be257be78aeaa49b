"""Share of the slots the window's device waves uploaded, launched the
step program over and downloaded that held no row, in %: 100 × (1 −
Δ``gubernator_wave_routed_rows_total`` ÷ Δ``gubernator_wave_slots_total``)
between the window's scrapes.  On a mesh a wave is ``shards × the
bucket of its densest shard`` slots wide
(``ShardedEngine._build_waves``); both counters are incremented once a
device wave at ``ShardedEngine._count_route``.  A program without the
counters reads nothing."""
from benchmark.harness import shard_cost
from benchmark.harness.scrape import delta


def read(ctx):
    m0, m1 = ctx["m0"], ctx["m1"]
    slots = delta(m0, m1, shard_cost.SLOTS)
    if slots <= 0:
        return None
    return 100.0 * (1.0 - delta(m0, m1, shard_cost.ROUTED_ROWS) / slots)
