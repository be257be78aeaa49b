"""Mean rows a device wave OPENED in the table over the window — keys it
found no row for: ``gubernator_wave_created_rows`` (the ``insert_count``
every step program returns with a wave's counters, added where they
reach the host: ``ShardedEngine._download_wave``) ÷ the waves
``gubernator_dispatcher_wave_size`` counted.  A program without the
counter reads nothing."""
from benchmark.harness.scrape import delta

NAME = "gubernator_wave_created_rows_total"


def read(ctx):
    m0, m1 = ctx["m0"], ctx["m1"]
    if not any(k.startswith(NAME) for k in m1):
        return None
    waves = delta(m0, m1, "gubernator_dispatcher_wave_size_count")
    return delta(m0, m1, NAME) / waves if waves > 0 else None
