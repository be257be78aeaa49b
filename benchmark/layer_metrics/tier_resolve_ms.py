"""The cold lane of a wave, ms a dispatcher wave: program phase
`tier.resolve` (``tiering.py › TierController.resolve`` — every cold
row applied to the host store one after another, then the admission of
the keys served), its seconds between the window's scrapes ÷ the
window's waves.  It lies INSIDE `wave.scatter` (``wave_resolve_ms``), on
the dispatch worker's sync side, under the engine lock.  A program
without the phase, or a window without a cold row, reads nothing."""
from benchmark.harness import progspans


def read(ctx):
    return progspans.ms_per_wave(ctx, "tier.resolve")
