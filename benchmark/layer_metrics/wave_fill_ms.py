"""Host time of `wave.fill` a wave: `_fill_packed` scattering the
requests into the leased upload buffers.  Program phase,
`gubernator_phase_duration{phase="wave.fill"}` ÷ waves."""
from benchmark.harness import progspans


def read(ctx):
    return progspans.ms_per_wave(ctx, "wave.fill")
