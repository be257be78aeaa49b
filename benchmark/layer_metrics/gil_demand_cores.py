"""Cores the daemon's PYTHON threads used over the window: Σ over the
Python roles (every role but `native-*`) of
Δ`gubernator_thread_cpu_seconds_total{role}` ÷ Δ
`gubernator_thread_ledger_clock_seconds`, between the window's first
scrape and the profiler's start (`threadcost.scrapes`).
Only one of those threads holds the GIL at a time, so this is an UPPER
bound of the GIL's load (CPU in C sections that released the GIL counts
too): near 1 the GIL is full and the `py_cpu_share_*` say of what; well
under 1 while phases still wait for it, the time goes in the hand-offs.
A program without the thread ledger reads nothing."""
from benchmark.harness import threadcost


def read(ctx):
    return threadcost.cores(ctx, native=False)
