"""Share of the device's idle time that no group of phases took
(`idle_unattributed_share`) AND that lies under the program's
annotation `worker.gap` — the dispatch worker between two of its
phases, on the profile's clock.  Where the two shares differ by more
than 5 points the reader says so on standard error: that much of the
idle device lies under no phase and no gap.  A profile without the
annotation reads nothing."""
from benchmark.harness import threadcost


def read(ctx):
    return threadcost.idle_worker_gap_share(ctx)
