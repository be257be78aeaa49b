"""Share of its time the dispatch worker was blocked on an empty queue:
`gubernator_phase_duration_sum{phase="worker.wait"}` over the window ÷
the sum of all the worker's phases (`worker.*`, `wave.*`, `lock.*`), which
partition its wall time — the window, as the worker's own clock saw it."""
from benchmark.harness import progspans


def read(ctx):
    return progspans.share_of_worker(ctx, "worker.wait")
