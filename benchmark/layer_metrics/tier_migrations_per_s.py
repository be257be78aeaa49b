"""Rows that changed tier a second of the window: Δ
``gubernator_tier_promotions`` + Δ ``gubernator_tier_demotions``
(``tiering.py › promote`` / ``demote``) ÷ the window's seconds.  Under a
static Zipf no cold key reaches the admission rank, so this reads ~0;
it is here so that a change which moves it is seen.  A program without
the counters reads nothing."""
from benchmark.harness.scrape import delta

NAMES = ("gubernator_tier_promotions_total",
         "gubernator_tier_demotions_total")


def read(ctx):
    m0, m1 = ctx["m0"], ctx["m1"]
    if not all(any(k.startswith(n) for k in m1) for n in NAMES):
        return None
    return sum(delta(m0, m1, n) for n in NAMES) / ctx["seconds"]
