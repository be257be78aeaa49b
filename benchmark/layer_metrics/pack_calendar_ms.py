"""Mean of `pack.calendar` a call: the period ends of a call's
DURATION_IS_GREGORIAN rows inside ``core/batch.py › pack_columns`` — one
``gregorian_expiration`` a distinct (ordinal, stamp) pair, broadcast to
its rows — read apart from the numpy lane it lies in (`local.pack`).
Program phase, ``gubernator_phase_duration{phase="pack.calendar"}``; a
program without the phase, or a window without a calendar row, reads
nothing."""
from benchmark.harness import progspans


def read(ctx):
    return progspans.ms_per_sample(ctx, "pack.calendar")
