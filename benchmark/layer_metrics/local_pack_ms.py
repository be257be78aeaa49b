"""Mean of `local.pack` a call: what a LOCAL call on a daemon over
several shards does in its own handler thread before it is queued —
`mix64_np`, `pack_columns`, `lay_out` (`instance.py ›
_wire_check_columns`, lane `wire_local`; the wait for its wave is
`call.wait`, not this).  Program phase,
`gubernator_phase_duration{phase="local.pack"}`; a program without the
phase reads nothing."""
from benchmark.harness import progspans


def read(ctx):
    return progspans.ms_per_sample(ctx, "local.pack")
