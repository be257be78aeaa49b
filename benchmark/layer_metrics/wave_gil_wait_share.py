"""Part of a wave's host-side stretches in which the thread that ran them
did not run: 1 − `gubernator_phase_cpu_seconds_total` ÷
`gubernator_phase_cpu_wall_seconds_total` over the coarse phases `pack`
(wave begin → launch returned) and `resolve` (results on the host → wave
end), each one stretch of one thread with CPU and wall clock read at the
same boundaries, in the 1 wave in 16 the dispatcher samples for it.  It is
host work with next to nothing to block on (`lock_wait_ms` is in it), so
what is missing is the wait for the GIL, which the dispatch worker shares
with the door's 32 handler threads.  `device` (in flight) is left out."""
from benchmark.harness import progspans


def read(ctx):
    return progspans.wait_share(ctx, "pack", "resolve")
