"""Cores the daemon's NATIVE threads used over the window — gRPC core,
the XLA / PJRT / TPU runtime's pools, anything Python did not start: Σ
over the `native-*` roles of Δ`gubernator_thread_cpu_seconds_total` ÷ Δ
`gubernator_thread_ledger_clock_seconds`, between the window's first
scrape and the profiler's start (`threadcost.scrapes`).  They share the daemon's
cores with the Python threads without asking for the GIL.  A program
without the thread ledger reads nothing."""
from benchmark.harness import threadcost


def read(ctx):
    return threadcost.cores(ctx, native=True)
