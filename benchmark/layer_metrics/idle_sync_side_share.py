"""Share of the device's idle time with a sync-side phase open and no
launch-side one (`wave.sync`, `wave.scatter`, `wave.resolve`,
`wave.end`): profile annotations against the device's idle intervals."""
from benchmark.harness import progspans


def read(ctx):
    return progspans.idle_share(ctx, "sync_side")
