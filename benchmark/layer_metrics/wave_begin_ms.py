"""Host time of `wave.begin` a wave: the dispatcher registering a wave
(`Dispatcher._wave_begin`: telemetry lock, one wait observation per merged
job, the `wave_launched` event, the tenant hint).  Program phase,
`gubernator_phase_duration{phase="wave.begin"}` over the window ÷ its
waves."""
from benchmark.harness import progspans


def read(ctx):
    return progspans.ms_per_wave(ctx, "wave.begin")
