"""Device time of the XLA engine's step program per served row: the
time of its modules in the profile (``xla_cost.step_modules``, by the
jit's name on the "XLA Modules" line) ÷ (their count × the mean rows of
a wave between the profile's two scrapes).  A program whose step is not
that module reads nothing."""
from benchmark.harness import xla_cost


def read(ctx):
    got = xla_cost.step_modules(ctx)
    per_wave = xla_cost.rows_per_wave(ctx)
    if not got or not per_wave:
        return None
    seconds, calls = got
    return 1e9 * seconds / (calls * per_wave)
