"""Mean DURATION_IS_GREGORIAN rows per device wave over the window:
``gubernator_wave_gregorian_rows`` (the rows with that Behavior bit that
entered a wave's device program, counted by the engine at `wave.route`
from the counts each call's handler took while it packed) ÷ the waves
``gubernator_dispatcher_wave_size`` counted; where every request is a
calendar request it equals ``rows_per_wave``.  A program without the
counter reads nothing."""
from benchmark.harness.scrape import delta

NAME = "gubernator_wave_gregorian_rows_total"


def read(ctx):
    m0, m1 = ctx["m0"], ctx["m1"]
    if not any(k.startswith(NAME) for k in m1):
        return None
    waves = delta(m0, m1, "gubernator_dispatcher_wave_size_count")
    return delta(m0, m1, NAME) / waves if waves > 0 else None
