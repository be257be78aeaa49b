"""Rows that changed tier per sample of program phase `tier.migrate`
over the window: (Δ ``gubernator_tier_promotions`` + Δ
``gubernator_tier_demotions``) ÷ Δ samples of `tier.migrate`
(``tiering.py › TierController``).  1–2 where keys move one at a time
(one sample an admission tried: a promotion and, where the bucket was
full, a demotion), hundreds where ONE pass moves all the keys a wave
admitted.  A program without the counters or without a `tier.migrate`
sample in the window reads nothing."""
from benchmark.harness import progspans
from benchmark.harness.scrape import delta

NAMES = ("gubernator_tier_promotions_total",
         "gubernator_tier_demotions_total")


def read(ctx):
    m0, m1 = ctx["m0"], ctx["m1"]
    if not all(any(k.startswith(n) for k in m1) for n in NAMES):
        return None
    passes = progspans.samples(ctx, "tier.migrate")
    if passes <= 0:
        return None
    return sum(delta(m0, m1, n) for n in NAMES) / passes
