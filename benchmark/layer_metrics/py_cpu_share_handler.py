"""Share of the CPU the daemon's Python threads used over the window
that role `handler` used — the gRPC pools' threads: grpcio's per-call Python, the servicer, a call's ingest / pack / build: its
Δ`gubernator_thread_cpu_seconds_total` ÷ Σ the Python roles', in %
(`gil_demand_cores` is that sum in cores).  A program without the
thread ledger reads nothing."""
from benchmark.harness import threadcost


def read(ctx):
    return threadcost.python_cpu_share(ctx, "handler")
