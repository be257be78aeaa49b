"""Share of the window's requests that the fused C++ wire ingest
parsed, clamped, hashed and laid out in ONE pass that keeps the GIL
(``parallel/sharded.py › prepack_wire`` → ``ops/_native.cpp ›
pack_wire_wave``), in %: Δ``gubernator_wire_fused_requests_total`` ÷
Δ Σ ``gubernator_wire_lane_requests_total`` over every lane, between
the window's scrapes.  The rest were parsed and packed in numpy by
their handler thread (``_wire_check_columns``, the GLOBAL runners) or
took the pb2 path; the lane label says nothing of this — both ways in
wear ``wire_local``.  The counter is incremented where a prepacked call
is accepted (``instance.py › _wire_client_fused`` /
``_wire_peer_fused``), in rows, as the lane counter counts them.  A
program without the counter, or a window without a request, reads
nothing."""
from benchmark.harness.scrape import delta

FUSED = "gubernator_wire_fused_requests_total"
LANES = "gubernator_wire_lane_requests_total"


def read(ctx):
    m0, m1 = ctx["m0"], ctx["m1"]
    if FUSED not in m1:
        return None
    total = delta(m0, m1, LANES)
    if total <= 0:
        return None
    return 100.0 * delta(m0, m1, FUSED) / total
