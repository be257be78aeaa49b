"""The trace reduction, on a small trace recorded on the chip (the first
four waves of a saturated run, ``data/trace_v5e_sat_4waves.json``)."""
import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness import tracered  # noqa: E402
from benchmark.harness.spans import SITES  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "trace_v5e_sat_4waves.json")


def rows():
    return json.load(open(DATA))["events"]


def test_interval_arithmetic():
    u = tracered.union(np.array([[5., 7.], [0., 2.], [1., 3.], [7., 8.]]))
    assert u.tolist() == [[0., 3.], [5., 8.]]  # touching ones merge
    assert tracered.measure(u) == 6.0
    gaps = tracered.complement(u, -1.0, 10.0)
    assert gaps.tolist() == [[-1., 0.], [3., 5.], [8., 10.]]
    both = tracered.intersect(gaps, np.array([[2., 4.], [9., 12.]]))
    assert both.tolist() == [[3., 4.], [9., 10.]]


def test_recorded_trace_reduces_to_its_hand_counts():
    ev = rows()
    red = tracered.reduce(ev, span_names=list(SITES))
    ops = [r for r in ev if r[1] == tracered.OPS_LINE]
    kernels = [r for r in ops if tracered.KERNEL_MARK in r[2]]
    assert red["devices"] == 1
    assert red["kernel_calls"] == len(kernels) == 4  # one per wave
    assert np.isclose(red["kernel_s"], sum(r[4] for r in kernels) / 1e9)
    # the kernel is nearly all of the device's busy time, and the
    # device is idle for most of a saturated wave
    assert 0.9 * red["busy_s"] < red["kernel_s"] <= red["busy_s"]
    assert 0.0 < red["busy_s"] < 0.15 * red["window_s"]
    assert red["device_ops"][0][0] == "_step.1 custom-call"
    assert red["fold_calls"] == 0
    # idle time with one of our spans open cannot exceed the idle time
    idle = red["window_s"] - red["busy_s"]
    gaps = dict(red["idle_gaps"])
    assert all(0 < v <= idle * 1.0001 for v in gaps.values())
    assert "dispatcher.launch" in gaps and "engine.launch_packed" in gaps
    assert gaps["engine.launch_packed"] <= gaps["dispatcher.launch"]


def test_kernel_rows_come_from_the_traced_kernel_calls():
    """Rows and kernel time are of one interval: four traced kernel
    calls serve four waves' rows, however many more waves the scrapes
    round them saw."""
    red = tracered.reduce(rows(), span_names=list(SITES))
    name = "gubernator_dispatcher_wave_size"
    m0 = {name + "_sum": 1000.0, name + "_count": 10.0}
    # six waves of 7,960 rows between the scrapes, four kernel calls
    m1 = {name + "_sum": 1000.0 + 6 * 7960.0, name + "_count": 16.0}
    got = tracered.kernel_rows(red, m0, m1)
    assert got == 4 * 7960.0
    ctx = {"trace": red, "tm0": m0, "tm1": m1}
    from benchmark.run import layer_reader

    ns = layer_reader("kernel_ns_per_row")(ctx)
    assert np.isclose(ns, 1e9 * red["kernel_s"] / (4 * 7960.0))
    # the recorded calls take ~3.39 ms each: ~426 ns a row at 7,960 rows
    assert 400.0 < ns < 450.0
    assert tracered.kernel_rows(red, m0, m0) is None  # no wave observed
    assert tracered.kernel_rows({"devices": 1, "kernel_calls": 0}, m0,
                                m1) is None


def test_short_names():
    assert tracered.short_name(
        '%_step.1 = (s32[8,128]{1,0:T(8,128)}, s32[8]{0}) custom-call('
        's32[2048,128]{1,0} %b), custom_call_target="tpu_custom_call"'
    ) == "_step.1 custom-call"
    assert tracered.short_name(
        "%fusion.7 = u32[8192]{0:T(1024)S(1)} fusion(u32[8192]{0} %p)"
    ) == "fusion.7 fusion"
