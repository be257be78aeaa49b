"""Share of the CPU the daemon's Python threads used over the window
that role `worker` used — the dispatch worker (`device-dispatcher`): every wave's concat, launch, sync and resolve: its
Δ`gubernator_thread_cpu_seconds_total` ÷ Σ the Python roles', in %
(`gil_demand_cores` is that sum in cores).  A program without the
thread ledger reads nothing."""
from benchmark.harness import threadcost


def read(ctx):
    return threadcost.python_cpu_share(ctx, "worker")
