"""Keys the host cold tier holds at the window's end, in millions: the
gauge ``gubernator_tier_cold_keys`` (``tiering.py › _gauge``, set after
every membership change) at the window's last scrape.  A program without
the gauge reads nothing."""

NAME = "gubernator_tier_cold_keys"


def read(ctx):
    v = ctx["m1"].get(NAME)
    return None if v is None else v / 1e6
