"""Time a wave the dispatch worker spends BETWEEN two of its phases:
`gubernator_phase_duration_sum{phase="worker.gap"}` over the window ÷ its
waves.  The worker's phases and this gap partition its wall time
(`tracing.partition_thread`); no annotation covers the gap, so in the
profile it is the idle time no phase claims (`idle_unattributed_share`).
It is glue, the phases' own bookkeeping and, on a loaded daemon, nearly
all waiting to get the GIL back."""
from benchmark.harness import progspans


def read(ctx):
    return progspans.ms_per_wave(ctx, "worker.gap")
