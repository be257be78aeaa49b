"""Host time of `wave.dispatch` a wave: `_launch_arrays` after
XLA_EXEC_MU is held — the device_puts and the jit call, until it returns.
Program phase, `gubernator_phase_duration{phase="wave.dispatch"}` ÷ waves."""
from benchmark.harness import progspans


def read(ctx):
    return progspans.ms_per_wave(ctx, "wave.dispatch")
