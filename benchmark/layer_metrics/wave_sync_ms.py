"""Time of `wave.sync` a wave: `_finish_wave` blocked on the device and
the download of the packed results and counters.  Program phase,
`gubernator_phase_duration{phase="wave.sync"}` ÷ waves."""
from benchmark.harness import progspans


def read(ctx):
    return progspans.ms_per_wave(ctx, "wave.sync")
