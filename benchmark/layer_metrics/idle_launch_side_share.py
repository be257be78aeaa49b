"""Share of the device's idle time with a launch-side phase of the
program open in any thread (`wave.begin`, `wave.concat`, `lock.*`,
`wave.route`, `wave.fill`, `wave.dispatch`): profile annotations against
the device's idle intervals, each idle nanosecond to exactly one group."""
from benchmark.harness import progspans


def read(ctx):
    return progspans.idle_share(ctx, "launch_side")
