"""Time a wave waits for locks on its launch side: every `lock.*`
phase of the program — `lock.engine` (the engine lock, worker and inline
callers), `lock.xla_exec` (XLA_EXEC_MU in `_launch_arrays`) and, where the
mesh-GLOBAL tier is bound, `lock.mesh_state` — summed over the window ÷
its waves (`gubernator_phase_duration{phase="lock.…"}`)."""
from benchmark.harness import progspans


def read(ctx):
    return progspans.ms_per_wave(ctx, progspans.LOCK_PREFIX)
