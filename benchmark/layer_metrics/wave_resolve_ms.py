"""Host time a wave spends turning downloaded results into answers:
`wave.scatter` (the engine's scatter into request order) + `wave.resolve`
(the dispatcher's `future.set_result` loop).  Program phases,
`gubernator_phase_duration` sums ÷ waves."""
from benchmark.harness import progspans


def read(ctx):
    return progspans.ms_per_wave(ctx, "wave.scatter", "wave.resolve")
