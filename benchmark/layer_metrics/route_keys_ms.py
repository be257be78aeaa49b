"""Mean of `route.keys` a call: GLOBAL routing's per-distinct-key config
loop (`np.unique`, per-key column compares, `is_pinned`).  Program phase,
`gubernator_phase_duration{phase="route.keys"}`."""
from benchmark.harness import progspans


def read(ctx):
    return progspans.ms_per_sample(ctx, "route.keys")
