"""The benchmark's yardstick under the tier-1 command, which collects
``tests/`` only: every case of ``benchmark/tests/test_plugins.py`` (pure
numpy, no daemon) — the LEAKY_BUCKET window rules sound on the plain
reference in random serial orders and tight on each control and on one
altered answer a rule, the leaky reference on hand-worked cases, the key
draws, every plug-in file against its seam, and the golden digests of
the existing cells' request bytes.  The cell ``r1-leaky-b1000-sat`` is
judged by these rules; nothing else guards them in CI."""
import pytest

pytest.register_assert_rewrite("benchmark.tests.test_plugins")

from benchmark.tests.test_plugins import *  # noqa: E402,F401,F403


# ---- ISSUE 31: the XLA-engine deployment and its readers ----------------

import json  # noqa: E402
import os  # noqa: E402

import numpy as np  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
XLA_CELL = "r1x-zipf-b1000-sat"
XLA_METRICS = ("xla_step_ns_per_row", "xla_step_roofline", "sweep_ms")


def _manifest() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_xla_deployment_and_its_cell_are_found_by_name():
    from benchmark import run

    cell = run.load_cell(XLA_CELL, rehearsal=False)
    cfg = cell["config"]
    assert (cfg["name"], cfg["engine"]) == ("region1-10m-xla", "xla-classic")
    assert cfg["env"] == {"GUBER_ENGINE": "xla"} and cfg["reduced"] == []
    rows, width = cfg["sizes"]["table_rows"], cfg["sizes"]["bytes_per_row"]
    assert (rows, width) == (1 << 26, 68)
    assert cfg["sizes"]["table_bytes_in_hbm"] == rows * width
    north = run.load_cell("r1-zipf-b1000-sat", rehearsal=False)
    assert cell["traffic"] == north["traffic"]
    for part in ("populations", "guarantees", "daemon"):
        assert cfg[part] == north["config"][part], part
    small = run.load_cell(XLA_CELL, rehearsal=True)["config"]
    assert small["env"] == {"GUBER_ENGINE": "xla",
                            "GUBER_WAVE_BUCKETS": "128"}
    # a sweep a second, so that the rehearsal's 4-s window holds one
    assert small["daemon"] == {"cache_size": 16384,
                               "sweep_interval_ms": 1000}
    # no share of a roofline from a host span: the sweep's device time
    # is in the profile one run in ten, and `sweep_ms` is mostly queue
    assert not any(m["name"] == "sweep_roofline"
                   for m in _manifest()["per_layer"])
    assert next(m["source"] for m in cell["per_layer"]
                if m["name"] == "sweep_ms") == "program_span"
    got = {m["name"] for m in cell["per_layer"]}
    assert set(XLA_METRICS) <= got
    assert not {"kernel_ns_per_row", "decide_kernel_roofline"} & got
    assert {m["name"] for m in cell["end_to_end"]} == {
        "decisions_per_s", "call_p50_ms", "setup_s"}


@pytest.mark.parametrize("name", XLA_METRICS)
def test_an_xla_reader_is_found_and_reads_nothing_where_nothing_is(name):
    """A program without the phase, the counter or the module — the
    parent commit — gives the reader nothing to read: it returns None
    and does not raise."""
    from benchmark.harness import plugins

    entry = next(m for m in _manifest()["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [XLA_CELL]
    read = plugins.load("layer_metrics", name).read
    ctx = {"m0": {}, "m1": {}, "tm0": {}, "tm1": {}, "trace": {"devices": 0},
           "_xla_step_modules": (0.0, 0), "device_kind": "TPU v5 lite",
           "rec": {"key_index": np.zeros(0, np.int64),
                   "n": np.zeros(0, np.int64)}}
    assert read(ctx) is None


def test_xla_cost_counts_a_hand_made_wave_and_a_hand_made_profile(tmp_path):
    from benchmark.harness import plugins, xla_cost

    assert (xla_cost.ROW_BYTES, xla_cost.DIRTY_BYTES) == (68, 28)
    assert (xla_cost.UPLOAD_BYTES, xla_cost.DOWNLOAD_BYTES) == (76, 40)
    # two waves of two 4-row calls: keys {1,2,3} and {7,8,9,1}
    keys = np.array([1, 1, 2, 3, 3, 3, 2, 1,
                     7, 8, 9, 9, 1, 1, 1, 1], np.int64)
    n = np.array([4, 4, 4, 4], np.int64)
    per_row = xla_cost.step_bytes_per_row(keys, n, wave_rows=8.0)
    assert per_row == 76 + 40 + (8 + 68 + 28) * 7 / 16
    # one call a wave: a key counts once a call
    assert xla_cost.step_bytes_per_row(keys, n, wave_rows=4.0) \
        == 116 + 104 * 10 / 16
    assert xla_cost.step_bytes_per_row(keys[:3], n, 8.0) == 0.0
    # the profile: two executions of the step's module on the first
    # device plane, another module, and the second plane's copies
    dev = "/device:TPU:0"
    rows = [[dev, "XLA Modules", "jit_xla_step_packed(1)", 0.0, 3e6],
            [dev, "XLA Modules", "jit_xla_step_packed(1)", 9e6, 5e6],
            [dev, "XLA Modules", "jit__one(2)", 20e6, 7e6],
            [dev, "XLA Ops", "%fusion = fusion(", 0.0, 1e6],
            ["/device:TPU:1", "XLA Modules", "jit_xla_step_packed(1)",
             0.0, 4e6]]
    wave = "gubernator_dispatcher_wave_size"
    ctx = {"trace_dir": str(tmp_path), "device_kind": "TPU v5 lite",
           "tm0": {wave + "_sum": 0.0, wave + "_count": 0.0},
           "tm1": {wave + "_sum": 80.0, wave + "_count": 10.0},
           "rec": {"key_index": keys, "n": n},
           "trace": {"devices": 2},
           "m0": {}, "m1": {
               'gubernator_phase_duration_sum{phase="sweep"}': 0.05,
               'gubernator_phase_duration_count{phase="sweep"}': 2.0}}
    from benchmark.harness import tracered

    old, tracered.load_xplane = tracered.load_xplane, lambda d: rows
    try:
        assert xla_cost.step_modules(ctx) == (8e-3, 2)
    finally:
        tracered.load_xplane = old
    read = lambda name: plugins.load("layer_metrics", name).read(ctx)  # noqa: E731
    assert read("xla_step_ns_per_row") == pytest.approx(8e6 / 16)
    least_s = per_row * 16 / 819e9
    assert read("xla_step_roofline") == pytest.approx(100 * least_s / 8e-3)
    assert read("sweep_ms") == pytest.approx(25.0)
