"""Share of the calls the fused C++ wire ingest REFUSED in the window
that it refused for a DURATION_IS_GREGORIAN row, in %:
Δ``gubernator_wire_fused_declined_total{reason="gregorian"}`` ÷ Δ the
counter over every reason, between the window's scrapes (``instance.py ›
_count_fused_declined``: counted once a refused call, right after the
classic parse that follows the refusal).  Such a call is parsed, hashed,
packed and laid out in numpy by its own handler thread (`local.pack`);
``fused_ingest_share`` says what share of the ROWS the lane took.  A
program without the counter, or a window in which the lane refused
nothing, reads nothing."""
from benchmark.harness.scrape import delta

NAME = "gubernator_wire_fused_declined_total"


def read(ctx):
    m0, m1 = ctx["m0"], ctx["m1"]
    total = delta(m0, m1, NAME)
    if total <= 0:
        return None
    return 100.0 * delta(m0, m1, NAME, 'reason="gregorian"') / total
