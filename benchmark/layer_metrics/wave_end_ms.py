"""Host time of `wave.end` a wave: `Dispatcher._wave_end` (histograms,
the `wave_completed` event) + the analytics tap.  Program phase,
`gubernator_phase_duration{phase="wave.end"}` ÷ waves."""
from benchmark.harness import progspans


def read(ctx):
    return progspans.ms_per_wave(ctx, "wave.end")
