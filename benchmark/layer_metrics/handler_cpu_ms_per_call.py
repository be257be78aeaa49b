"""CPU the gRPC pools' threads used a call: role `handler`'s
Δ`gubernator_thread_cpu_seconds_total` between the ledger's two scrapes
(`threadcost.scrapes`) ÷ the calls answered in that part of the window
(client's records), in ms.  It
holds everything a call does on its thread — grpcio's Python round the
servicer as well as the program's own (`handler_own_cpu_ms_per_call`).
A program without the thread ledger reads nothing."""
from benchmark.harness import threadcost


def read(ctx):
    m0, m1 = threadcost.scrapes(ctx)
    cpu = threadcost.by_role(m0, m1, threadcost.CPU)
    if cpu is None or "handler" not in cpu:
        return None
    calls = threadcost.calls_answered(ctx, threadcost.elapsed(m0, m1))
    if calls <= 0:
        return None
    return 1000.0 * cpu["handler"] / calls
