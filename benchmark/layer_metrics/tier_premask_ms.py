"""The cold tier's membership read, ms a dispatcher wave: program phase
`tier.premask` (``parallel/sharded.py › _ride_invalid``: which of the
wave's keys the host tier holds, so that they ride the device wave
invalid), its seconds between the window's scrapes ÷ the window's
waves — once on the launch side and once for each re-dispatch of the
wave's erred and cold rows on the sync side.  It lies INSIDE
`wave.route` (``wave_route_ms``).  A program without the phase reads
nothing."""
from benchmark.harness import progspans


def read(ctx):
    return progspans.ms_per_wave(ctx, "tier.premask")
