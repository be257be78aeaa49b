"""Thread CPU of the program's own part of a call, phase `handler`
(`instance.py › get_rate_limits_wire`, whole): Δ
`gubernator_phase_cpu_seconds_total{phase="handler"}` ÷ Δ
`gubernator_phase_duration_count{phase="handler"}` between the window's
scrapes, in ms — every sample of the phase records CPU.  What
`handler_cpu_ms_per_call` holds beyond it is grpcio's.  A program whose
`handler` records no CPU reads nothing."""
from benchmark.harness import threadcost


def read(ctx):
    return threadcost.phase_cpu_ms_per_sample(ctx, "handler")
