"""Thread CPU of `local.pack` a call — the numpy wire lane's hash, pack
and lay-out in the call's own handler thread (`instance.py ›
_wire_check_columns`): Δ
`gubernator_phase_cpu_seconds_total{phase="local.pack"}` ÷ Δ
`gubernator_phase_duration_count{phase="local.pack"}` between the
window's scrapes, in ms.  × calls a second it is the GIL time a fused
ingest would free; `local_pack_ms` is the same samples' wall time.  A
program without the phase reads nothing."""
from benchmark.harness import threadcost


def read(ctx):
    return threadcost.phase_cpu_ms_per_sample(ctx, "local.pack")
