"""How long serving waits for one whole-table expiry sweep: the mean
HOST wall time of the program's phase `sweep` (round `engine.sweep`,
between waves under the engine lock: the 30-s tick, or a table_full
row's request) between the window's scrapes.  A host span, not a
device time: the sweep's program queues behind the waves in flight.  A
program without the phase, or a window without a sweep, reads
nothing."""
from benchmark.harness import xla_cost


def read(ctx):
    return xla_cost.sweep_ms(ctx)
