"""The decision kernel's share of its HBM roofline: the bytes that the
rows of the traced kernel calls need (``kernel_cost.decide_bytes_per_row``,
computed from the window's own calls, × ``tracered.kernel_rows``) ÷ peak
bytes/s ÷ the kernel's device time in the trace.  Bound by memory: the
kernel does a few hundred integer ops per 16 KiB moved."""
from benchmark.harness import kernel_cost, peaks, tracered


def read(ctx):
    tr = ctx["trace"]
    rows = tracered.kernel_rows(tr, ctx["tm0"], ctx["tm1"])
    if not rows:
        return None
    rec = ctx["rec"]
    per_row = kernel_cost.decide_bytes_per_row(rec["key_index"], rec["n"])
    least_s = per_row * rows / peaks.of(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / tr["kernel_s"]
