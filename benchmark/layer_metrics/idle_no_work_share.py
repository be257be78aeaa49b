"""Share of the device's idle time in which the dispatch worker waited
for work or coalesced (`worker.wait`, `worker.coalesce`) and no wave
phase was open anywhere: profile annotations against idle intervals."""
from benchmark.harness import progspans


def read(ctx):
    return progspans.idle_share(ctx, "no_work")
