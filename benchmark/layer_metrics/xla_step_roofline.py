"""The XLA step program's share of its HBM roofline: the bytes the rows
of the profiled waves cannot avoid (``xla_cost.step_bytes_per_row``,
from the window's own calls) ÷ peak bytes/s ÷ the device time of the
step's modules in the profile.  Bound by memory (a row is a few hundred
integer operations); the count is a floor, so is the share."""
from benchmark.harness import peaks, xla_cost


def read(ctx):
    got = xla_cost.step_modules(ctx)
    per_wave = xla_cost.rows_per_wave(ctx)
    if not got or not per_wave:
        return None
    seconds, calls = got
    rec = ctx["rec"]
    per_row = xla_cost.step_bytes_per_row(rec["key_index"], rec["n"],
                                          per_wave)
    least_s = (per_row * calls * per_wave
               / peaks.of(ctx["device_kind"])["hbm_bytes_per_s"])
    return 100.0 * least_s / seconds if per_row else None
