"""The benchmark's yardstick under the tier-1 command, which collects
``tests/`` only: every case of ``benchmark/tests/test_plugins.py`` (pure
numpy, no daemon) — the LEAKY_BUCKET window rules sound on the plain
reference in random serial orders and tight on each control and on one
altered answer a rule, the leaky reference on hand-worked cases, the key
draws, every plug-in file against its seam, and the golden digests of
the existing cells' request bytes.  The cell ``r1-leaky-b1000-sat`` is
judged by these rules; nothing else guards them in CI."""
import pytest

pytest.register_assert_rewrite("benchmark.tests.test_plugins",
                              "benchmark.tests.test_fused_ingest_share",
                              "benchmark.tests.test_calendar_plugins")

from benchmark.tests.test_plugins import *  # noqa: E402,F401,F403
# ISSUE 38: the reader of the fused ingest's share, on canned scrapes
from benchmark.tests.test_fused_ingest_share import *  # noqa: E402,F401,F403
# ISSUE 39: the calendar token bucket's rules (the cell
# ``r1-greg-zipf-b1000-sat`` is judged by them), the key draw over a
# space larger than its population, the burst schedule
from benchmark.tests.test_calendar_plugins import *  # noqa: E402,F401,F403


# ---- ISSUE 39: three cases of benchmark/tests, restated -----------------
# ``benchmark/tests/test_plugins.py`` and ``test_fused_ingest_share.py`` are
# files the benchmark has, which ISSUE 39 may not edit; three of their cases
# hold an invariant that ISSUE 39's own files and entries end: one plug-in a wire algorithm number (the calendar token
# bucket is the wire's algorithm 0 with Behavior bit 4), and no word of a
# plug-in's name in ``harness/`` (``arrivals/burst.py``, the name
# ``benchmark/README.md`` gives it, against ``harness/wire.py``'s ``burst``
# field), and ``fused_ingest_share`` the LAST entry of ``per_layer``.  They
# are restated here under their own names, so that they are run as
# restated; by hand the originals fail on exactly these three points until
# a ``benchmark`` PR edits them (PERF.md §7).

def test_an_algorithm_is_found_by_the_name_a_population_has_to_state():  # noqa: F811
    from benchmark.harness import plugins

    leaky = plugins.load("algorithms", "leaky_bucket")
    assert plugins.algorithm({"name": "l", "algorithm": "LEAKY_BUCKET"}) \
        is leaky
    with pytest.raises(ValueError):
        plugins.algorithm({"name": "p", "limit": 1})
    mods = [plugins.load("algorithms", n)
            for n in plugins.names("algorithms")]
    # the wire knows two algorithms; two plug-ins of one number differ in
    # the Behavior bit their populations carry
    assert {m.WIRE_ALGORITHM for m in mods} == {0, 1}
    kinds = [(m.WIRE_ALGORITHM, getattr(m, "BEHAVIOR", 0)) for m in mods]
    assert len(set(kinds)) == len(kinds), kinds
    with pytest.raises(ValueError):
        plugins.load("keys", "no-such-draw")


def test_fused_ingest_share_is_declared_for_every_cell():  # noqa: F811
    """``test_fused_ingest_share.py`` holds the entry to be the LAST of
    ``per_layer``; a manifest only grows at its end, so ISSUE 39's four
    readers stand behind it.  Restated: found by name."""
    manifest = _manifest()
    entry = next(m for m in manifest["per_layer"]
                 if m["name"] == "fused_ingest_share")
    assert entry == {
        "name": "fused_ingest_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "front door",
        "moves": "decisions_per_s",
        "workloads": [w["name"] for w in manifest["workloads"]]}


def test_nothing_in_run_or_harness_names_a_plugin():  # noqa: F811
    """An algorithm, a key draw and an arrival process are found by the
    names in the data files: adding one is adding a file."""
    import re

    from benchmark.harness import plugins

    bench = os.path.join(REPO, "benchmark")
    names = {n for kind in ("algorithms", "keys", "arrivals")
             for n in plugins.names(kind)}
    assert {"leaky_bucket", "token_bucket", "token_bucket_gregorian",
            "uniform", "zipf", "zipf_space", "zipf_drift", "poisson",
            "grid", "burst"} <= names
    words = "|".join(sorted(
        {re.escape(w) for n in names for w in (n, n.replace("_", " "),
                                               n.split("_")[0])}
        # "a grid step of the kernel" is no arrival process, and the
        # wire's ``burst`` field none either
        - {"grid", "token", "burst"}))
    pat = re.compile(rf"{words}|[\"']grid[\"']|[\"']burst[\"']\s*[:\]]"
                     rf"|token.?bucket|gregorian", re.I)
    files = [os.path.join(bench, "run.py")] + [
        os.path.join(bench, "harness", f)
        for f in sorted(os.listdir(os.path.join(bench, "harness")))
        if f.endswith(".py")]
    hits = [f"{os.path.relpath(f, bench)}:{n}: {line.strip()}"
            for f in files for n, line in enumerate(open(f), 1)
            if pat.search(line)]
    assert not hits, hits


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
    # (the sweep's phase is the Pallas engine's too: cell 10, whose
    # tiered waves ask for a sweep of their own, reads it since PR 41)
    assert entry["workloads"] == [XLA_CELL] + (
        ["r1-churn-100m", "r1-drift-100m"] if name == "sweep_ms" else [])
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


# ---- ISSUE 33: the four-chip LOCAL deployment and its readers -----------

R4_CELL, G4_CELL = "r4-zipf-b1000-sat", "r4-global-b1000-sat"
#: the one-chip cell whose calls the fused ingest declines (ISSUE 39):
#: `local.pack` reads again there
GREG_CELL = "r1-greg-zipf-b1000-sat"
SHARD_METRICS = {"shard_pad_share": [R4_CELL, G4_CELL],
                 "shard_skew": [R4_CELL, G4_CELL],
                 "local_pack_ms": [R4_CELL, GREG_CELL],
                 "shard_kernel_ns_per_slot": [R4_CELL],
                 "shard_kernel_roofline": [R4_CELL]}


def test_the_sharded_deployment_and_its_cell_are_found_by_name():
    from benchmark import run

    man = _manifest()
    entry = next(c for c in man["configs"] if c["name"] == "region4-10m")
    assert entry["reduced"] == [] and len(entry["source"]) <= 200
    assert entry["file"] == "benchmark/configs/region4-10m.json"
    cell = run.load_cell(R4_CELL, rehearsal=False)
    cfg = cell["config"]
    assert (cfg["name"], cfg["engine"], cfg["chips"], cell["chips"]) == (
        "region4-10m", "pallas-fused", 4, 4)
    assert cfg["source"] == entry["source"]
    assert cfg["env"] == {} and cfg["reduced"] == []
    sizes = cfg["sizes"]
    assert sizes["table_rows"] == 1 << 26 == 4 * sizes["table_rows_per_chip"]
    assert sizes["table_bytes_in_hbm"] == (1 << 26) * sizes["bytes_per_row"] \
        == 4 * sizes["table_bytes_in_hbm_per_chip"] == 1 << 32
    # cell 4's daemon, cell 1's traffic, population and guarantees
    north = run.load_cell("r1-zipf-b1000-sat", rehearsal=False)
    glob = run.load_cell(G4_CELL, rehearsal=False)["config"]
    assert cell["traffic"] == north["traffic"]
    assert cfg["daemon"] == glob["daemon"] == {
        "cache_size": 1 << 26, "global_mode": "mesh"}
    assert cfg["populations"] == north["config"]["populations"]
    assert cfg["populations"]["resident"] == glob["populations"]["resident"]
    assert cfg["guarantees"] == north["config"]["guarantees"]
    assert not any(k.startswith("global") for k in cfg["guarantees"])
    small = run.load_cell(R4_CELL, rehearsal=True)["config"]
    assert small["daemon"] == {"cache_size": 16384, "global_mode": "mesh"}
    assert small["populations"]["resident"]["keys"] == 3000
    got = {m["name"] for m in cell["per_layer"]}
    assert set(SHARD_METRICS) <= got
    assert {"frontdoor_ms", "handler_ms", "queue_wait_ms", "door_inflight",
            "wave_identity_route_share"} <= got
    # first plane, even shards; the mesh runner's; the fold's
    assert not {"kernel_ns_per_row", "decide_kernel_roofline", "fold_ms",
                "route_pack_ms", "route_gil_wait_share"} & got
    assert {m["name"] for m in cell["end_to_end"]} == {
        "decisions_per_s", "call_p50_ms", "setup_s"}
    assert sum(w["chips"] == 4 for w in man["workloads"]) == 2
    assert len(man["workloads"]) >= 7


def _nothing() -> dict:
    return {"m0": {}, "m1": {}, "tm0": {}, "tm1": {},
            "trace": {"devices": 0}, "_shard_kernel": (0.0, 0),
            "device_kind": "TPU v5 lite", "config": {"chips": 4},
            "rec": {"key_index": np.zeros(0, np.int64),
                    "n": np.zeros(0, np.int64)}}


@pytest.mark.parametrize("name", SHARD_METRICS)
def test_a_shard_reader_is_found_and_reads_nothing_where_nothing_is(name):
    """A program without the counters or the phase — the parent commit —
    gives the reader nothing to read, with a profile or without: it
    returns None and does not raise."""
    from benchmark.harness import plugins

    entry = next(m for m in _manifest()["per_layer"] if m["name"] == name)
    assert entry["workloads"] == SHARD_METRICS[name]
    read = plugins.load("layer_metrics", name).read
    assert read(_nothing()) is None
    # the parent's scrapes and a profile that HOLDS kernel calls
    waves = {'gubernator_wave_route_total{route="sorted"}': 50.0,
             "gubernator_dispatcher_wave_size_sum": 4e5,
             "gubernator_dispatcher_wave_size_count": 50.0}
    ctx = dict(_nothing(), m1=waves, tm1=waves, _shard_kernel=(0.2, 200))
    assert read(ctx) is None


def test_shard_cost_and_its_readers_on_a_hand_made_wave_and_profile(
        tmp_path):
    from benchmark.harness import plugins, shard_cost, tracered

    assert shard_cost.BUCKET_BYTES == 8192
    # two waves of two 4-row calls: keys {1,2,3} and {7,8,9,1}
    keys = np.array([1, 1, 2, 3, 3, 3, 2, 1,
                     7, 8, 9, 9, 1, 1, 1, 1], np.int64)
    n = np.array([4, 4, 4, 4], np.int64)
    assert shard_cost.distinct_keys_per_row(keys, n, 8.0) == 7 / 16
    assert shard_cost.wave_bytes_per_row(keys, n, 8.0) == 2 * 8192 * 7 / 16
    # one call a wave: a key counts once a call
    assert shard_cost.wave_bytes_per_row(keys, n, 4.0) == 2 * 8192 * 10 / 16
    assert shard_cost.wave_bytes_per_row(keys[:3], n, 8.0) == 0.0
    assert shard_cost.wave_bytes_per_row(keys, n, None) == 0.0
    # the profile: two device waves on four planes with UNEVEN kernel
    # times (the densest shard's chip works longest), another op, a
    # module line and a host plane
    kern = '%_step.1 = custom-call(), custom_call_target="tpu_custom_call"'
    times = {0: (9e6, 8e6), 1: (3e6, 2e6), 2: (2e6, 3e6), 3: (1e6, 4e6)}
    rows = [[f"/device:TPU:{d}", "XLA Ops", kern, 1e7 * i, t]
            for d, ts in times.items() for i, t in enumerate(ts)]
    rows += [["/device:TPU:0", "XLA Ops", "%fusion = fusion(", 5e7, 1e6],
             ["/device:TPU:1", "XLA Modules", "jit__step(1)", 0.0, 4e6],
             ["/host:CPU", "python3", kern, 0.0, 7e6]]
    old, tracered.load_xplane = tracered.load_xplane, lambda d: rows
    try:
        ctx = {"trace_dir": str(tmp_path)}
        assert shard_cost.kernel_planes(ctx) == (32e-3, 8)
    finally:
        tracered.load_xplane = old
    # the scrapes: 10 device waves between the profile's, each 4 × 8
    # slots for 8 rows, 5 on the densest shard; 20 over the window
    route = 'gubernator_wave_route_total{route="sorted"}'
    size = "gubernator_dispatcher_wave_size"
    pack = 'gubernator_phase_duration_%s{phase="local.pack"}'
    zero = {route: 0.0, shard_cost.SLOTS: 0.0, shard_cost.ROUTED_ROWS: 0.0,
            shard_cost.DENSEST_ROWS: 0.0, size + "_sum": 0.0,
            size + "_count": 0.0, pack % "sum": 0.0, pack % "count": 0.0}
    at = lambda k: {route: k, shard_cost.SLOTS: 32.0 * k,  # noqa: E731
                    shard_cost.ROUTED_ROWS: 8.0 * k,
                    shard_cost.DENSEST_ROWS: 5.0 * k, size + "_sum": 8.0 * k,
                    size + "_count": k, pack % "sum": 0.003 * k,
                    pack % "count": 2.0 * k}
    ctx.update(m0=zero, m1=at(20.0), tm0=at(5.0), tm1=at(15.0),
               device_kind="TPU v5 lite", config={"chips": 4},
               rec={"key_index": keys, "n": n})
    read = lambda name: plugins.load("layer_metrics", name).read(ctx)  # noqa: E731
    assert read("shard_pad_share") == pytest.approx(75.0)
    assert read("shard_skew") == pytest.approx(5 * 4 / 8)
    assert read("local_pack_ms") == pytest.approx(1.5)
    # 32 ms of kernel over 8 calls of 8 slots a shard
    assert read("shard_kernel_ns_per_slot") == pytest.approx(32e6 / (8 * 8))
    # 8 calls ÷ 4 chips = 2 device waves of 8 rows, 7 distinct keys in 16
    least_s = 2 * 8192 * (7 / 16) * 8 * 2 / 819e9
    assert read("shard_kernel_roofline") == pytest.approx(
        100 * least_s / 32e-3)
    assert read("shard_kernel_roofline") < 100


# ---- ISSUE 36: the share of sorted waves the C++ extension routed -------

_SORTED = 'gubernator_wave_route_total{route="sorted"}'
_IDENT = 'gubernator_wave_route_total{route="identity"}'
_NATIVE = "gubernator_wave_native_route_total"

#: case → (first scrape, second scrape, what the reader gives)
NATIVE_SHARE = {
    "no_scrape_at_all": ({}, {}, None),
    # the parent commit: sorted waves, no such counter
    "sorted_waves_without_the_counter": (
        {_SORTED: 10.0}, {_SORTED: 60.0}, None),
    # a one-shard cell: the counter is there, no wave was sorted
    "the_counter_without_a_sorted_wave": (
        {_NATIVE: 0.0, _SORTED: 0.0, _IDENT: 5.0},
        {_NATIVE: 0.0, _SORTED: 0.0, _IDENT: 55.0}, None),
    "no_sorted_wave_inside_the_window": (
        {_NATIVE: 7.0, _SORTED: 7.0}, {_NATIVE: 7.0, _SORTED: 7.0}, None),
    "every_sorted_wave": (
        {_NATIVE: 3.0, _SORTED: 3.0, _IDENT: 9.0},
        {_NATIVE: 53.0, _SORTED: 53.0, _IDENT: 9.0}, 100.0),
    # 50 sorted waves in the window, 40 of them the extension's; the
    # identity waves beside them count for nothing
    "four_sorted_waves_in_five": (
        {_NATIVE: 10.0, _SORTED: 10.0, _IDENT: 0.0},
        {_NATIVE: 50.0, _SORTED: 60.0, _IDENT: 50.0}, 80.0),
    # a checkout without the extension serves the numpy route
    "the_numpy_route": (
        {_NATIVE: 0.0, _SORTED: 0.0}, {_NATIVE: 0.0, _SORTED: 25.0}, 0.0),
}


@pytest.mark.parametrize("case", NATIVE_SHARE)
def test_wave_native_route_share_on_a_hand_made_pair_of_scrapes(case):
    from benchmark.harness import plugins

    entry = next(m for m in _manifest()["per_layer"]
                 if m["name"] == "wave_native_route_share")
    assert entry == {
        "name": "wave_native_route_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "engine",
        "moves": "decisions_per_s",
        # cell 10: a tier bound, so every one-shard wave is a sorted wave
        "workloads": [R4_CELL, G4_CELL, "r1-churn-100m", "r1-drift-100m"]}
    m0, m1, want = NATIVE_SHARE[case]
    got = plugins.load("layer_metrics", "wave_native_route_share").read(
        dict(_nothing(), m0=m0, m1=m1))
    assert got is None if want is None else got == pytest.approx(want)
