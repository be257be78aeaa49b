"""Host time of `wave.concat` a wave: the dispatcher concatenating the
merged jobs' columns (+ mslot column, max now).  Program phase,
`gubernator_phase_duration{phase="wave.concat"}` ÷ waves."""
from benchmark.harness import progspans


def read(ctx):
    return progspans.ms_per_wave(ctx, "wave.concat")
