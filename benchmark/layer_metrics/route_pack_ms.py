"""Mean of `route.pack` a call: GLOBAL routing's `mix64_np` +
`pack_columns` + masks (`instance.py › _wire_mesh_runner`).  Program
phase, `gubernator_phase_duration{phase="route.pack"}`."""
from benchmark.harness import progspans


def read(ctx):
    return progspans.ms_per_sample(ctx, "route.pack")
