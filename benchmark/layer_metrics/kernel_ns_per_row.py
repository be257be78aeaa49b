"""Device time of the decision kernel (the serving program's Mosaic
custom call) per served row: the trace's kernel time ÷ the rows of the
waves whose kernel calls the trace holds (``tracered.kernel_rows``)."""
from benchmark.harness import tracered


def read(ctx):
    tr = ctx["trace"]
    rows = tracered.kernel_rows(tr, ctx["tm0"], ctx["tm1"])
    if not rows:
        return None
    return 1e9 * tr["kernel_s"] / rows
