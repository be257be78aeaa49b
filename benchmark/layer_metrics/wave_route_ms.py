"""Host time of `wave.route` a wave: the engine's out-of-domain and tier
masks, arrival order and `_build_waves` (`ShardedEngine.launch_packed`).
Program phase, `gubernator_phase_duration{phase="wave.route"}` ÷ waves."""
from benchmark.harness import progspans


def read(ctx):
    return progspans.ms_per_wave(ctx, "wave.route")
