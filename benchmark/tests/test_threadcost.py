"""The readers over the program's thread ledger and the front door's
phases (``harness/threadcost.py`` and the eleven ``layer_metrics``
files PR 37 added), each against two hand-made scrapes — and ``None``
on the scrapes of a program without the series (the parent commit)."""
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness import plugins, threadcost, tracered  # noqa: E402

NEW = ("gil_demand_cores", "py_cpu_share_handler", "py_cpu_share_worker",
       "py_cpu_share_grpc_serve", "native_cpu_cores",
       "handler_cpu_ms_per_call",
       "handler_own_cpu_ms_per_call", "local_pack_cpu_ms_per_call",
       "door_wait_ms", "door_recv_ms", "idle_worker_gap_share")

DEV = tracered.DEVICE_PLANE + "0"
OPS = tracered.OPS_LINE
HOST = "/host:CPU"


def read(name, ctx):
    return plugins.load("layer_metrics", name).read(ctx)


def scrapes():
    """Ten seconds of the ledger's clock.  Python roles: handler 6.0 s
    of CPU, worker 2.0, grpc-serve 1.0, analytics 0.5, tick 0.1,
    py-other 0.4 = 10.0 (one core); native: grpc 3.0, xla 1.5, other
    0.5 = 5.0.  1,000 calls were answered, 125 of them sampled: `handler` 0.5 s of CPU, `local.pack` 0.25 s,
    `door.wait` 0.25 s and `door.recv` 1.0 s of wall."""
    cpu = {"worker": 2.0, "handler": 6.0, "grpc-serve": 1.0,
           "analytics": 0.5, "tick": 0.1, "py-other": 0.4,
           "native-grpc": 3.0, "native-xla": 1.5, "native-other": 0.5}
    m0, m1 = {}, {}
    for role, seconds in cpu.items():
        key = f'{threadcost.CPU}{{role="{role}"}}'
        m0[key], m1[key] = 100.0, 100.0 + seconds
    m0[threadcost.CLOCK], m1[threadcost.CLOCK] = 1000.0, 1010.0
    m0["gubernator_dispatcher_wave_duration_count"] = 10.0
    m1["gubernator_dispatcher_wave_duration_count"] = 210.0
    for name, wall, cpu_s in (("handler", 9.0, 0.5),
                              ("local.pack", 1.5, 0.25),
                              ("door.wait", 0.25, None),
                              ("door.recv", 1.0, None)):
        lab = f'{{phase="{name}"}}'
        m0["gubernator_phase_duration_count" + lab] = 5.0
        m1["gubernator_phase_duration_count" + lab] = 130.0
        m0["gubernator_phase_duration_sum" + lab] = 1.0
        m1["gubernator_phase_duration_sum" + lab] = 1.0 + wall
        if cpu_s is not None:
            m0["gubernator_phase_cpu_seconds_total" + lab] = 2.0
            m1["gubernator_phase_cpu_seconds_total" + lab] = 2.0 + cpu_s
    return m0, m1


def context(m0, m1):
    ok = np.ones(1200, bool)
    ok[:50] = False
    done = np.r_[np.full(150, 99.0), np.linspace(100.0, 110.0, 1000),
                 np.full(50, 111.0)]  # 100 of the ok ones fall outside
    return {"m0": m0, "m1": m1, "start_at": 100.0, "end": 110.0,
            "seconds": 10.0, "rec": {"ok": ok, "done": done}}


def test_a_traced_run_reads_the_ledger_up_to_the_profilers_start():
    """The profiler's export runs on the benchmark's main thread inside
    the window: with a scrape taken as the profiler starts (`tm0`), the
    ledger's readers stop there, and count the calls of that part."""
    m0, m1 = scrapes()
    ctx = context(m0, m1)
    late = dict(m1)  # 40 s later: the export burnt 30 s in `py-other`
    late[threadcost.CLOCK] += 40.0
    late[f'{threadcost.CPU}{{role="py-other"}}'] += 30.0
    ctx.update(tm0=m1, m1=late, end=150.0)
    assert threadcost.scrapes(ctx) == (m0, m1)
    assert read("gil_demand_cores", ctx) == pytest.approx(1.0)
    assert read("py_cpu_share_handler", ctx) == pytest.approx(60.0)
    assert read("handler_cpu_ms_per_call", ctx) == pytest.approx(6.0)
    del ctx["tm0"]
    assert read("gil_demand_cores", ctx) == pytest.approx(40.0 / 50.0)


def test_each_reader_against_two_hand_made_scrapes():
    ctx = context(*scrapes())
    assert read("gil_demand_cores", ctx) == pytest.approx(1.0)
    assert read("native_cpu_cores", ctx) == pytest.approx(0.5)
    assert read("py_cpu_share_handler", ctx) == pytest.approx(60.0)
    assert read("py_cpu_share_worker", ctx) == pytest.approx(20.0)
    assert read("py_cpu_share_grpc_serve", ctx) == pytest.approx(10.0)
    # 6.0 s of the handler threads over the 1,000 calls of the window
    assert read("handler_cpu_ms_per_call", ctx) == pytest.approx(6.0)
    assert read("handler_own_cpu_ms_per_call", ctx) == pytest.approx(4.0)
    assert read("local_pack_cpu_ms_per_call", ctx) == pytest.approx(2.0)
    assert read("door_wait_ms", ctx) == pytest.approx(2.0)
    assert read("door_recv_ms", ctx) == pytest.approx(8.0)
    assert read("handler_own_cpu_ms_per_call", ctx) \
        <= read("handler_cpu_ms_per_call", ctx)
    shares = sum(read(f"py_cpu_share_{r}", ctx)
                 for r in ("handler", "worker", "grpc_serve"))
    assert shares <= 100.0


def test_a_program_without_the_series_reads_nothing():
    """The parent commit under the new benchmark files: no thread
    ledger, no `door.*`, a `handler` without CPU."""
    m0, m1 = scrapes()
    drop = ("gubernator_thread_", 'phase="door.',
            "gubernator_phase_cpu_seconds_total")
    old0 = {k: v for k, v in m0.items() if not any(d in k for d in drop)}
    old1 = {k: v for k, v in m1.items() if not any(d in k for d in drop)}
    ctx = context(old0, old1)
    for name in NEW:
        if name != "idle_worker_gap_share":  # the profile's: below
            assert read(name, ctx) is None, name


def test_two_scrapes_of_one_read_are_no_interval():
    """Both scrapes inside the ledger's 0.5-s cache: the same clock,
    the same totals — nothing to divide by."""
    m0, _ = scrapes()
    ctx = context(m0, dict(m0))
    for name in ("gil_demand_cores", "native_cpu_cores",
                 "py_cpu_share_handler", "handler_cpu_ms_per_call"):
        assert read(name, ctx) is None, name


def table(gaps=True):
    """One device, window [0, 1000]: ops at 0–100 and 900–1000, idle
    800.  The worker: wave.dispatch 100–300, a gap 300–400, wave.sync
    400–600, a gap 600–650, worker.wait 650–700, nothing at all
    700–900 (a hole no annotation covers).  A gap annotation that
    overlaps a phase of another thread (wave.sync 400–600 against
    worker.gap 380–420 in thread c) counts only where no group is."""
    rows = [
        [DEV, OPS, "%fusion.1 = f32[] fusion()", 0.0, 100.0],
        [DEV, OPS, "%fusion.1 = f32[] fusion()", 900.0, 100.0],
        [HOST, "w", "wave.dispatch", 100.0, 200.0],
        [HOST, "w", "wave.sync", 400.0, 200.0],
        [HOST, "w", "worker.wait", 650.0, 50.0],
    ]
    if gaps:
        rows += [[HOST, "w", "worker.gap", 300.0, 100.0],
                 [HOST, "w", "worker.gap", 600.0, 50.0],
                 [HOST, "c", "worker.gap", 380.0, 40.0]]
    return rows


def test_idle_under_the_gap_annotation():
    got = threadcost.idle_worker_gap(table())
    assert got["idle"] * 1e9 == 800.0
    # no group took 300–400, 600–650 and 700–900
    assert got["unattributed"] * 1e9 == 100.0 + 50.0 + 200.0
    # the annotation covers the first two, not the hole
    assert got["gap"] * 1e9 == 100.0 + 50.0
    assert threadcost.idle_worker_gap(table(gaps=False)) is None
    assert threadcost.idle_worker_gap(
        [r for r in table() if r[0] != DEV]) is None


def test_the_reader_says_so_where_the_gap_is_not_the_unattributed_time(
        monkeypatch, capsys):
    monkeypatch.setattr(tracered, "load_xplane", lambda d: table())
    share = read("idle_worker_gap_share", {"trace_dir": "unused"})
    assert share == pytest.approx(100.0 * 150.0 / 800.0)
    # 43.75 % lie under no group, 18.75 % under the annotation
    assert "25.0 points" in capsys.readouterr().err
    full = table() + [[HOST, "w", "worker.gap", 700.0, 200.0]]
    monkeypatch.setattr(tracered, "load_xplane", lambda d: full)
    assert read("idle_worker_gap_share", {"trace_dir": "unused"}) \
        == pytest.approx(43.75)
    assert capsys.readouterr().err == ""
    monkeypatch.setattr(tracered, "load_xplane",
                        lambda d: table(gaps=False))
    assert read("idle_worker_gap_share", {"trace_dir": "unused"}) is None


def test_the_parents_gap_annotations_are_not_the_gaps_intervals(
        monkeypatch):
    """The parent annotated the hand-over of the gap's SUM, microseconds
    a wave: annotations that cover next to nothing of what the phase
    summed while the profile recorded read nothing."""
    key = 'gubernator_phase_duration_sum{phase="worker.gap"}'
    monkeypatch.setattr(tracered, "load_xplane", lambda d: table())
    ctx = {"trace_dir": "unused", "tm0": {key: 5.0},
           "tm1": {key: 5.0 + 180e-9}}  # the annotations hold 170 ns
    assert read("idle_worker_gap_share", ctx) == pytest.approx(18.75)
    handover = table(gaps=False) + [[HOST, "w", "worker.gap", 310.0, 1.0],
                                    [HOST, "w", "worker.gap", 610.0, 1.0]]
    monkeypatch.setattr(tracered, "load_xplane", lambda d: handover)
    assert read("idle_worker_gap_share", ctx) is None


def test_every_new_reader_is_in_the_manifest_with_a_list_of_cells():
    import json

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"] for w in manifest["workloads"]}
    closed = {m["name"]: set(m.get("workloads", cells))
              for m in manifest["end_to_end"]}
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert [m["name"] for m in manifest["per_layer"]][-len(NEW):] == list(NEW)
    for name in NEW:
        m = by_name[name]
        assert set(m["workloads"]) <= closed[m["moves"]], name
        assert plugins.exists("layer_metrics", name)
