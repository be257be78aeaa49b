"""The one timing primitive and the phases it records (ISSUE 24):
``tracing.phase`` → TraceAnnotation + gubernator_phase_duration{phase} /
PhaseLedger + a span with real timestamps.

Every wave runs on the dispatch worker, through the launch/sync
pipeline wherever the engine has ``launch_packed`` — the path the chip
runs:

- the dispatch worker's phases partition its wall time;
- pack + device + resolve == gubernator_dispatcher_wave_duration, WITH
  `pack` for a fused engine;
- every recorded child of a `wave` span lies inside it on real
  timestamps, a wave that waited in the pending ring included;
- every phase emitted is catalogued, and the catalog matches the code's
  literals and OBSERVABILITY.md (guberlint);
- route.* wall >= CPU >= 0;
- a /debug/profile-style capture holds the annotations by exact name.
"""
import glob
import os
import re
import threading
import time

import numpy as np
import pytest

from gubernator_tpu import tracing
from gubernator_tpu.analytics import KeyAnalytics
from gubernator_tpu.config import BehaviorConfig, Config, DaemonConfig
from gubernator_tpu.core.batch import pack_columns
from gubernator_tpu.dispatcher import Dispatcher
from gubernator_tpu.instance import V1Instance
from gubernator_tpu.metrics import Metrics
from gubernator_tpu.parallel import ShardedEngine, make_mesh
from gubernator_tpu.proto import gubernator_pb2 as pb
from gubernator_tpu.tracing import phase, request_context
from gubernator_tpu.types import Behavior, RateLimitRequest

NOW = 1_790_000_000_000

#: the dispatch worker's phases (tracing.PHASE_CATALOG, second block)
WORKER = {n for n in tracing.PHASE_CATALOG
          if n.startswith(("worker.", "wave.", "lock."))}
COARSE = ("pack", "device", "resolve")


@pytest.fixture()
def pipelined(monkeypatch):
    monkeypatch.delenv("GUBER_ENGINE", raising=False)
    monkeypatch.delenv("GUBER_STEP_IMPL", raising=False)
    return monkeypatch


@pytest.fixture(scope="module")
def engine():
    eng = ShardedEngine(make_mesh(n=1), capacity_per_shard=1 << 16)
    eng.warmup()
    return eng


def packed(seed, n=300, keys=2000):
    rng = np.random.default_rng(seed)
    kh = rng.integers(1, keys, n).astype(np.uint64) * np.uint64(2654435761)
    batch, _ = pack_columns(
        kh, np.ones(n, np.int64), np.full(n, 1_000_000, np.int64),
        np.full(n, 600_000, np.int64), np.zeros(n, np.int32),
        np.zeros(n, np.int32), np.zeros(n, np.int64), NOW)
    return batch, kh


def ser(n, key="k", name="ph", behavior=0, keys=7):
    m = pb.GetRateLimitsReq()
    for i in range(n):
        q = m.requests.add()
        q.name, q.unique_key = name, f"{key}{i % keys}"
        q.hits, q.limit, q.duration = 1, 1_000_000, 600_000
        q.behavior = int(behavior)
    return m.SerializeToString()


def hist(text, family, phase_name, field):
    m = re.search(r'%s_%s\{phase="%s"\} (\S+)'
                  % (family, field, re.escape(phase_name)), text)
    return float(m.group(1)) if m else None


def hammer(fn, threads=6, calls=12):
    ths = [threading.Thread(target=lambda s=s: [fn(s * 1000 + i)
                                                for i in range(calls)])
           for s in range(threads)]
    for t in ths:
        t.start()
    for t in ths:
        t.join(timeout=120)


# ---- the dispatch worker's wall time is partitioned --------------------


def test_worker_phases_partition_the_workers_wall_time(pipelined, engine):
    """worker.wait + worker.coalesce + wave.* + lock.* of a run sum to
    the dispatch worker's elapsed time (it waits for work or works on a
    wave, nothing else) — which is what lets an idle device's time go
    to exactly one of them."""
    ka = KeyAnalytics(metrics=None)
    t0 = time.perf_counter()
    d = Dispatcher(engine, analytics=ka)
    try:
        assert d._pipelined  # the engine has launch_packed
        hammer(lambda s: d.check_packed(*packed(s), NOW))
    finally:
        d.close()  # joins the worker
        elapsed = time.perf_counter() - t0
        ka.close()
    snap = ka.phases.snapshot()
    assert {"worker.wait", "worker.coalesce", "wave.begin", "wave.concat",
            "lock.engine", "wave.route", "wave.fill", "lock.xla_exec",
            "wave.dispatch", "wave.sync", "wave.scatter", "wave.resolve",
            "wave.end"} <= set(snap)
    total = sum(v["total_ms"] for k, v in snap.items() if k in WORKER) / 1e3
    # thread start-up and the exit after the last phase are all that
    # lies outside
    assert total == pytest.approx(elapsed, rel=0.02), (total, elapsed)
    assert total <= elapsed
    # one wave.begin / concat / resolve / end per wave, and the waves'
    # handler side: every queued call waited once
    waves = snap["wave.begin"]["count"]
    assert waves == snap["wave.end"]["count"] == snap["pack"]["count"]
    # ... once; `call.wait` times 1 call in CALL_SAMPLE
    assert snap["queue_wait"]["count"] == 72
    assert snap["call.wait"]["count"] == 72 // Dispatcher.CALL_SAMPLE
    assert (snap["call.wait"]["total_ms"] / snap["call.wait"]["count"]
            >= 0.5 * snap["queue_wait"]["total_ms"] / 72)


# ---- the coarse partition, now WITH pack for a fused engine ------------


def test_coarse_phases_partition_wave_duration_on_a_fused_engine(pipelined):
    inst = V1Instance(Config(cache_size=1 << 12, sweep_interval_ms=0,
                             engine="pallas"), mesh=make_mesh(n=1))
    try:
        assert inst.dispatcher._pipelined and inst.engine.fused_serving
        hammer(lambda s: inst.get_rate_limits_wire(
            ser(40, key=f"c{s % 5}_"), now_ms=NOW + s), threads=4, calls=6)
        text = inst.metrics.render().decode()
        sums = {p: hist(text, "gubernator_phase_duration", p, "sum")
                for p in COARSE}
        assert all(v is not None and v > 0 for v in sums.values()), sums
        wave_sum = float(re.search(
            r"gubernator_dispatcher_wave_duration_sum (\S+)", text).group(1))
        assert sum(sums.values()) == pytest.approx(wave_sum, rel=1e-6)
        counts = {hist(text, "gubernator_phase_duration", p, "count")
                  for p in COARSE}
        assert counts == {float(re.search(
            r"gubernator_dispatcher_wave_duration_count (\S+)",
            text).group(1))}
        for ev in inst.recorder.events(kind="wave_completed"):
            assert set(ev["phases"]) == set(COARSE), ev
            assert sum(ev["phases"].values()) == pytest.approx(
                ev["duration_ms"], abs=0.01)
    finally:
        inst.close()


# ---- wave spans: real children, pending ring included ------------------


def _waves_with_children(spans):
    out = []
    for t in tracing.assemble(spans):
        stack = list(t["roots"])
        while stack:
            n = stack.pop()
            stack.extend(n.get("children", ()))
            if n["name"] == "wave":
                out.append(n)
    return out


def test_wave_children_lie_inside_a_wave_that_waited_in_the_ring(pipelined):
    """Every child span of a `wave` carries the start and end it was
    read at and lies inside its parent — also when the wave's launch
    and its sync were two visits of the worker with another wave's
    launch between them (depth-2 pipeline)."""
    inst = V1Instance(Config(cache_size=1 << 12, sweep_interval_ms=0),
                      mesh=make_mesh(n=1))
    try:
        inst.span_recorder.sample = 1.0
        assert inst.dispatcher._pipelined
        before = time.time()

        def call(s):
            with request_context(None, recorder=inst.span_recorder):
                inst.get_rate_limits_wire(ser(30, key=f"r{s}_"),
                                          now_ms=NOW + s)

        hammer(call, threads=6, calls=8)
        after = time.time()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            waves = _waves_with_children(inst.span_recorder.spans())
            if any(w["attrs"].get("slot") for w in waves):
                break
            hammer(call, threads=6, calls=4)
            after = time.time()
        ringed = [w for w in waves if w["attrs"].get("slot")]
        assert ringed, "no wave was launched behind another (slot > 0)"
        for w in waves:
            kids = w["children"]
            names = [k["name"] for k in kids]
            assert {"wave.begin", "wave.sync", "wave.end"} <= set(names), w
            assert before <= w["start"] <= w["end"] <= after
            for k in kids:
                assert k["name"] in WORKER, k
                assert w["start"] <= k["start"] <= k["end"] <= w["end"], \
                    (w["start"], k, w["end"])
                assert k["attrs"]["wave"] == w["attrs"]["wave"]
            for a, b in zip(kids, kids[1:]):
                assert a["end"] <= b["start"], (a, b)
        # a ringed wave's children leave a hole where the worker
        # launched the next wave: timestamps are what happened, not a
        # layout of durations end to end
        w = ringed[0]
        busy = sum(k["end"] - k["start"] for k in w["children"])
        assert busy < w["end"] - w["start"]
    finally:
        inst.close()


def test_phase_span_timestamps_are_clock_readings():
    rec = tracing.SpanRecorder(sample=1.0)
    with request_context(None, recorder=rec):
        tid = tracing.current_trace_id()
        a = time.time()
        with phase("handler"):
            with phase("ingest"):
                time.sleep(0.002)
            b = time.time()
            time.sleep(0.002)
        c = time.time()
    spans = {s["name"]: s for s in rec.spans(trace_id=tid)}
    h, i = spans["handler"], spans["ingest"]
    assert i["parent_id"] == h["span_id"] and h["parent_id"] is None
    assert a <= h["start"] <= i["start"] <= i["end"] <= b <= h["end"] <= c
    assert i["end"] - i["start"] >= 0.002


# ---- catalog ↔ emitted ↔ code literals ↔ OBSERVABILITY.md --------------


#: what a wave on the worker records whatever the engine
WORKER_SIDE = {"wave.begin", "lock.engine", "wave.resolve", "wave.end",
               "worker.wait", "worker.coalesce", "queue_wait", *COARSE}


@pytest.mark.parametrize("engine_kind", ["launch_packed", "serial"])
def test_every_emitted_phase_is_catalogued(monkeypatch, engine_kind):
    """Drive the wire lanes (local keys and GLOBAL on the mesh tier)
    and the object lane through the daemon-less instance: whatever
    lands in the ledger is a catalogued name, and both worker branches — the pipeline of an
    engine with ``launch_packed``, the serial branch of one without
    (OracleEngine) — use the same names for the same work."""
    monkeypatch.delenv("GUBER_ENGINE", raising=False)
    monkeypatch.delenv("GUBER_STEP_IMPL", raising=False)
    monkeypatch.setenv("GUBER_MESH_GLOBAL_CAP", "256")
    if engine_kind == "serial":
        from gubernator_tpu.oracle import OracleEngine

        inst = V1Instance(Config(cache_size=1 << 12, sweep_interval_ms=0),
                          engine=OracleEngine())
    else:
        inst = V1Instance(Config(
            cache_size=1 << 12, sweep_interval_ms=0, engine="pallas",
            global_mode="mesh", batch_rows=64,
            behaviors=BehaviorConfig(global_sync_wait_ms=100)),
            mesh=make_mesh(n=8))
    try:
        assert inst.dispatcher._pipelined == (engine_kind != "serial")
        for i in range(3):
            if engine_kind != "serial":  # OracleEngine: object lane only
                inst.get_rate_limits_wire(ser(20), now_ms=NOW + i)
                inst.get_rate_limits_wire(
                    ser(20, name="g", behavior=Behavior.GLOBAL),
                    now_ms=NOW + i)
            inst.get_rate_limits([RateLimitRequest(
                name="o", unique_key=f"o{i}", hits=1, limit=10,
                duration=60_000)], now_ms=NOW + i)
        if engine_kind != "serial":
            inst._mesh_reconcile_tick()
        snap = inst.dispatcher.analytics.phases.snapshot()
    finally:
        inst.close()
    assert set(snap) <= set(tracing.PHASE_CATALOG), \
        set(snap) - set(tracing.PHASE_CATALOG)
    assert WORKER_SIDE <= set(snap), sorted(snap)
    if engine_kind != "serial":
        assert {"handler", "call.wait", "ingest", "build", "route.pack", "route.keys",
                "route.pin", "route.slots", "global_fold", "wave.route",
                "wave.fill", "lock.xla_exec", "lock.mesh_state",
                "wave.dispatch", "wave.sync", "wave.scatter",
                "wave.concat"} <= set(snap), sorted(snap)


def test_phase_catalog_matches_code_and_docs():
    from tools.guberlint import docs

    assert docs.phase_catalog_doc_problems() == []
    # and the lint is sharp: a name nobody documents is a finding
    tracing.PHASE_CATALOG["wave.bogus"] = "not a phase"
    try:
        found = docs.phase_catalog_doc_problems()
    finally:
        del tracing.PHASE_CATALOG["wave.bogus"]
    assert len(found) == 2 and all("wave.bogus" in p for p in found), found


# ---- route.*: wall and CPU at the same boundaries ----------------------


def test_route_phases_record_wall_and_cpu(pipelined):
    pipelined.setenv("GUBER_MESH_GLOBAL_CAP", "256")
    inst = V1Instance(Config(
        cache_size=1 << 12, sweep_interval_ms=0, engine="pallas",
        global_mode="mesh", batch_rows=64,
        behaviors=BehaviorConfig(global_sync_wait_ms=100)),
        mesh=make_mesh(n=8))
    try:
        hammer(lambda s: inst.get_rate_limits_wire(
            ser(200, name="g", behavior=Behavior.GLOBAL, keys=50),
            now_ms=NOW + s), threads=4, calls=5)
        text = inst.metrics.render().decode()
    finally:
        inst.close()
    for name in ("route.pack", "route.keys", "route.slots", "handler"):
        wall = hist(text, "gubernator_phase_duration", name, "sum")
        n = hist(text, "gubernator_phase_duration", name, "count")
        cpu = hist(text, "gubernator_phase_cpu_seconds", name, "total")
        cpu_wall = hist(text, "gubernator_phase_cpu_wall_seconds", name,
                        "total")
        assert n == 20, (name, n)
        # every call records both: the CPU samples' wall is the wall
        assert cpu_wall == pytest.approx(wall, rel=1e-9)
        # two clocks: allow the thread-CPU clock a millisecond in all
        assert 0 <= cpu <= wall + 1e-3, (name, cpu, wall)
    # phases that do not ask for CPU time have no series
    assert hist(text, "gubernator_phase_cpu_seconds", "build",
                "total") is None
    # the parts lie inside the whole
    parts = sum(hist(text, "gubernator_phase_duration", p, "sum")
                for p in ("route.pack", "route.keys", "route.slots",
                          "call.wait", "build"))
    assert parts <= hist(text, "gubernator_phase_duration", "handler",
                         "sum")


def test_sampled_waves_record_cpu_against_their_own_wall(pipelined, engine,
                                                         monkeypatch):
    """thread_time() is a system call, so only 1 wave in CPU_SAMPLE
    records CPU time in its phases — with the wall seconds of exactly
    those samples beside it, so that CPU ÷ wall is of the same waves."""
    m = Metrics()
    d = Dispatcher(engine, metrics=m)
    try:
        hammer(lambda s: d.check_packed(*packed(s), NOW), threads=2,
               calls=40)
    finally:
        d.close()
    text = m.render().decode()
    waves = hist(text, "gubernator_phase_duration", "pack", "count")
    assert waves >= 2 * Dispatcher.CPU_SAMPLE
    for name in ("pack", "resolve", "wave.route", "wave.fill",
                 "wave.sync", "wave.end"):
        wall = hist(text, "gubernator_phase_duration", name, "sum")
        cpu = hist(text, "gubernator_phase_cpu_seconds", name, "total")
        cpu_wall = hist(text, "gubernator_phase_cpu_wall_seconds", name,
                        "total")
        assert 0 <= cpu <= cpu_wall + 1e-3, (name, cpu, cpu_wall)
        assert 0 < cpu_wall < wall, (name, cpu_wall, wall)  # a sample
    # the worker's own phases outside a wave never record it
    assert hist(text, "gubernator_phase_cpu_seconds", "worker.wait",
                "total") is None
    monkeypatch.setattr(Dispatcher, "CPU_SAMPLE", 1)
    m1 = Metrics()
    d1 = Dispatcher(engine, metrics=m1)
    try:
        hammer(lambda s: d1.check_packed(*packed(s), NOW), threads=2,
               calls=5)
    finally:
        d1.close()
    t1 = m1.render().decode()
    assert hist(t1, "gubernator_phase_cpu_wall_seconds", "pack",
                "total") == pytest.approx(
        hist(t1, "gubernator_phase_duration", "pack", "sum"), rel=1e-9)


# ---- a profile of the process holds the phases by exact name -----------


def test_profile_capture_holds_phase_annotations_by_name(pipelined,
                                                         tmp_path):
    from jax.profiler import ProfileData

    inst = V1Instance(Config(cache_size=1 << 12, sweep_interval_ms=0),
                      mesh=make_mesh(n=1))
    try:
        inst.get_rate_limits_wire(ser(30), now_ms=NOW)
        prof = tracing.DeviceProfiler(str(tmp_path))  # /debug/profile's
        hammer(lambda s: inst.get_rate_limits_wire(
            ser(30, key=f"p{s}_"), now_ms=NOW + s), threads=3, calls=6)
        prof.stop()
    finally:
        inst.close()
    path = sorted(glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    by_line = {}
    for plane in ProfileData.from_file(path).planes:
        for li, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name in tracing.PHASE_CATALOG:
                    by_line.setdefault((plane.name, li), []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         ev.name))
    seen = {n for evs in by_line.values() for _, _, n in evs}
    assert {"ingest", "handler", "call.wait", "build", "queue_wait",
            "worker.wait", "worker.coalesce", "wave.begin", "wave.route",
            "wave.fill", "lock.xla_exec", "wave.dispatch", "wave.sync",
            "wave.scatter", "wave.resolve", "wave.end", "worker.gap",
            *COARSE} <= seen, sorted(seen)
    # the worker's line: its phases and the gaps between them (each an
    # annotation of its own while a profile records), no two open at
    # once
    worker = [evs for evs in by_line.values()
              if any(n == "worker.wait" for _, _, n in evs)]
    assert len(worker) == 1
    evs = sorted(e for e in worker[0] if e[2] in WORKER)
    assert len(evs) > 30
    for a, b in zip(evs, evs[1:]):
        assert a[1] <= b[0], (a, b)
    # and gaps lie between phases, never side by side
    names = [e[2] for e in evs]
    assert names.count("worker.gap") > 10
    assert all(not (a == b == "worker.gap")
               for a, b in zip(names, names[1:]))
    # no other thread has one
    assert sum(any(n == "worker.gap" for _, _, n in evs)
               for evs in by_line.values()) == 1


# ---- the front door counts who is inside -------------------------------


def test_door_inflight_counts_handlers_in_flight():
    import grpc

    from gubernator_tpu.daemon import spawn_daemon
    from gubernator_tpu.netutil import free_port

    addr = f"127.0.0.1:{free_port()}"
    d = spawn_daemon(DaemonConfig(
        grpc_listen_address=addr,
        http_listen_address=f"127.0.0.1:{free_port()}",
        cache_size=1 << 10), mesh=make_mesh(n=1))
    try:
        ch = grpc.insecure_channel(addr)
        call = ch.unary_unary("/pb.gubernator.V1/GetRateLimits")
        hammer(lambda s: call(ser(3, key=f"d{s}_"), timeout=30),
               threads=4, calls=8)
        ch.close()
        text = d.instance.metrics.render().decode()
        n = float(re.search(r"gubernator_door_inflight_count (\S+)",
                            text).group(1))
        total = float(re.search(r"gubernator_door_inflight_sum (\S+)",
                                text).group(1))
        assert n == 32 // 8  # 1 call in 8 is observed: the mean's enough
        assert n <= total <= 4 * n  # itself included; 4 callers at most
    finally:
        d.close()


# ---- the handler's CPU, wherever it is timed (ISSUE 37) ----------------


def test_handler_records_cpu_on_the_calls_it_samples(pipelined):
    """Off mesh-GLOBAL mode `handler` times 1 call in 8 — wall AND
    thread CPU (two ``thread_time()`` a sampled call), so that the
    program's share of a handler thread's CPU can be read."""
    inst = V1Instance(Config(cache_size=1 << 12, sweep_interval_ms=0),
                      mesh=make_mesh(n=1))
    try:
        assert inst.dispatcher.call_sample == 8
        tracing.phase._uses.pop("handler", None)
        for i in range(16):
            inst.get_rate_limits_wire(ser(20, key=f"h{i}_"), now_ms=NOW + i)
        text = inst.metrics.render().decode()
    finally:
        inst.close()
    assert hist(text, "gubernator_phase_duration", "handler", "count") == 2
    wall = hist(text, "gubernator_phase_duration", "handler", "sum")
    cpu = hist(text, "gubernator_phase_cpu_seconds", "handler", "total")
    cpu_wall = hist(text, "gubernator_phase_cpu_wall_seconds", "handler",
                    "total")
    assert cpu is not None and 0.0 <= cpu <= wall + 0.02
    assert cpu_wall == pytest.approx(wall)
