"""Mean of `route.slots` a call: GLOBAL routing's slot-map copy under
the tier's lock + the per-key mslot column loop.  Program phase,
`gubernator_phase_duration{phase="route.slots"}`."""
from benchmark.harness import progspans


def read(ctx):
    return progspans.ms_per_sample(ctx, "route.slots")
