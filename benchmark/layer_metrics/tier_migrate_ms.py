"""The tier's migration work, ms a dispatcher wave: program phase
`tier.migrate` (``tiering.py › TierController``: the rows a wave's
admission moves between the tiers — the promotees' device buckets
fetched, victims picked and taken out, the rows written back, the cold
store's side of both), its seconds between the window's scrapes ÷ the
window's waves.  It lies INSIDE `tier.resolve` (``tier_resolve_ms``), on
the dispatch worker's sync side, under the engine lock: what a waking
tenant costs every caller of the wave.  A program whose `/metrics` has no
`tier.migrate` series (none was ever sampled: no tier, or a cell whose
keys never reach the admission rank) reads nothing."""
from benchmark.harness import progspans, scrape

SERIES = progspans.DURATION + "_count"
LABEL = 'phase="tier.migrate"'


def read(ctx):
    m0, m1 = ctx["m0"], ctx["m1"]
    if not any(k.startswith(SERIES) and LABEL in k for k in m1):
        return None
    waves = scrape.delta(m0, m1, progspans.WAVES)
    if waves <= 0:
        return None
    return 1000.0 * progspans.seconds(ctx, "tier.migrate") / waves
