"""Thread CPU the `key-analytics` thread spends learning which tenant
each call's keys belong to, a call: Δ
`gubernator_phase_cpu_seconds_total{phase="analytics.learn"}` (one
sample a drain window of the analytics worker, wall and CPU read at the
same boundaries) ÷ the calls answered inside the window (client's
records), in ms.  It is CPU a thread other than the serving ones takes
on the daemon's one GIL.  A program without the phase reads nothing."""
import numpy as np

from benchmark.harness import progspans
from benchmark.harness.scrape import delta

PHASE = 'phase="analytics.learn"'


def read(ctx):
    m0, m1 = ctx["m0"], ctx["m1"]
    if not any(k.startswith(progspans.CPU_SECONDS) and PHASE in k
               for k in m1):
        return None
    rec = ctx["rec"]
    calls = int(np.count_nonzero(
        rec["ok"] & (rec["done"] >= ctx["start_at"])
        & (rec["done"] <= ctx["end"])))
    if calls <= 0:
        return None
    return 1000.0 * delta(m0, m1, progspans.CPU_SECONDS, PHASE) / calls
