"""Part of GLOBAL routing's wall time its handler thread did not run:
1 − `gubernator_phase_cpu_seconds_total` ÷
`gubernator_phase_cpu_wall_seconds_total` over the `route.*` phases (thread CPU and wall clock read at the same
boundaries).  32 handler threads route on one GIL."""
from benchmark.harness import progspans


def read(ctx):
    return progspans.wait_share(ctx, "route.")
