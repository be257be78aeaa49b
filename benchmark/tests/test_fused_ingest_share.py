"""``layer_metrics/fused_ingest_share.py`` against hand-made pairs of
scrapes: it reads nothing from a program without the counter (the
parent commit) or in a window without a request, 0 where every call was
declined (GLOBAL rows: the bypass), 100 where every row came in by the
one C++ pass, and counts ROWS over every lane in between."""
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_FUSED = "gubernator_wire_fused_requests_total"
_LOCAL = 'gubernator_wire_lane_requests_total{lane="wire_local"}'
_HOT = 'gubernator_wire_lane_requests_total{lane="wire_hotset"}'
_PEER = 'gubernator_wire_lane_requests_total{lane="peer_wire"}'
_PB2 = 'gubernator_wire_lane_requests_total{lane="pb2_fallback"}'

#: case → (first scrape, second scrape, what the reader gives)
FUSED_SHARE = {
    "no_scrape_at_all": ({}, {}, None),
    # the parent commit: requests on the lane, no such counter
    "requests_without_the_counter": (
        {_LOCAL: 1000.0}, {_LOCAL: 51000.0}, None),
    "no_request_inside_the_window": (
        {_FUSED: 7000.0, _LOCAL: 7000.0}, {_FUSED: 7000.0, _LOCAL: 7000.0},
        None),
    # cell 4: every call is GLOBAL rows, declined at the first header
    "every_call_declined": (
        {_FUSED: 0.0, _HOT: 2000.0}, {_FUSED: 0.0, _HOT: 52000.0}, 0.0),
    # cells 1-3, 5-7 on the change: what was there before the window
    # (the warm-up's calls) counts for nothing
    "every_row_in_one_pass": (
        {_FUSED: 3000.0, _LOCAL: 9000.0},
        {_FUSED: 53000.0, _LOCAL: 59000.0}, 100.0),
    # rows, over every lane: 30,000 fused of 30,000 local + 10,000 on
    # the peer wire's numpy lane + 10,000 GLOBAL + 10,000 through pb2
    "rows_over_every_lane": (
        {_FUSED: 0.0, _LOCAL: 0.0, _PEER: 0.0, _HOT: 0.0, _PB2: 0.0},
        {_FUSED: 30000.0, _LOCAL: 30000.0, _PEER: 10000.0, _HOT: 10000.0,
         _PB2: 10000.0}, 50.0),
}


@pytest.mark.parametrize("case", FUSED_SHARE)
def test_fused_ingest_share_on_a_hand_made_pair_of_scrapes(case):
    from benchmark.harness import plugins

    m0, m1, want = FUSED_SHARE[case]
    got = plugins.load("layer_metrics", "fused_ingest_share").read(
        {"m0": m0, "m1": m1})
    assert got is None if want is None else got == pytest.approx(want)


def test_fused_ingest_share_is_declared_for_every_cell():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = manifest["per_layer"][-1]
    assert entry == {
        "name": "fused_ingest_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "front door",
        "moves": "decisions_per_s",
        "workloads": [w["name"] for w in manifest["workloads"]]}
