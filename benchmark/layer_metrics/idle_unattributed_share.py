"""Share of the device's idle time with NO phase of the program open:
what the program's tracing still cannot see."""
from benchmark.harness import progspans


def read(ctx):
    return progspans.idle_share(ctx, "unattributed")
