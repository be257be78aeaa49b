"""End-to-end distributed tracing (ISSUE 12): SpanRecorder semantics
(deterministic head sampling, bounded ring/pending, forced-sample
outcomes, tombstone routing for late adds), wave spans whose phase
children lie inside the wave on real timestamps, cross-daemon stitching
over the raw TLV lanes on a 3-daemon cluster, ``/debug/traces`` +
``?trace=`` event filtering, slo_breach exemplars, and a 16-thread
soak asserting the recorder never builds backpressure."""
import json
import random
import threading
import time
import urllib.request

import grpc
import pytest

from gubernator_tpu import tracing
from gubernator_tpu.config import BehaviorConfig, Config, DaemonConfig
from gubernator_tpu.instance import V1Instance
from gubernator_tpu.oracle import OracleEngine
from gubernator_tpu.proto import gubernator_pb2 as pb
from gubernator_tpu.slo import SLO, SLOEngine
from gubernator_tpu.telemetry import FlightRecorder
from gubernator_tpu.tracing import (SpanRecorder, assemble, force_sample,
                                    hop_traceparent, render_waterfall,
                                    request_context, span)
from gubernator_tpu.types import RateLimitRequest

NOW = 1_791_000_000_000
TID = "ab" * 16


def req(key, name="traceco/api", hits=1, **kw):
    d = dict(limit=100_000, duration=600_000)
    d.update(kw)
    return RateLimitRequest(name=name, unique_key=key, hits=hits, **d)


def _tids(seed, n):
    rng = random.Random(seed)
    return [f"{rng.getrandbits(128):032x}" for _ in range(n)]


def _span(tid, sid, parent=None, name="s", start=0.0, end=1.0):
    return {"trace_id": tid, "span_id": sid, "parent_id": parent,
            "name": name, "start": start, "end": end, "attrs": {}}


# ---- SpanRecorder unit semantics ---------------------------------------


class TestHeadSampling:
    def test_seeded_decisions_are_deterministic(self):
        """Same trace id → same verdict on every recorder (the cluster
        property: assembly never sees half a trace)."""
        tids = _tids(7, 2000)
        a = SpanRecorder(sample=0.1)
        b = SpanRecorder(capacity=4, sample=0.1)
        da = [a.head_sampled(t) for t in tids]
        assert da == [b.head_sampled(t) for t in tids]
        assert da == [a.head_sampled(t) for t in tids]  # stable, not RNG
        frac = sum(da) / len(da)
        assert 0.05 < frac < 0.2, frac  # the rate is honored, roughly

    def test_rate_edges(self):
        r = SpanRecorder(sample=0.0)
        assert not r.head_sampled(TID)
        r.sample = 1.0
        assert r.head_sampled(TID)
        r.sample = 0.5
        assert not r.head_sampled("zz")  # malformed id → drop, not raise


def test_ring_bound_eviction():
    r = SpanRecorder(capacity=8, sample=1.0)
    for i in range(20):
        tid = f"{i:032x}"
        r.add(_span(tid, f"{i:016x}"))
        assert r.commit(tid)
    assert len(r) == 8
    kept = [s["trace_id"] for s in r.spans()]
    assert kept == [f"{i:032x}" for i in range(12, 20)]  # newest survive
    st = r.stats()
    assert st["spans"] == 8 and st["capacity"] == 8 and st["pending"] == 0


def test_pending_bounds_never_grow_unbounded():
    r = SpanRecorder(capacity=512, sample=1.0)
    for i in range(3 * SpanRecorder.PENDING_SPANS):
        r.add(_span(TID, f"{i:016x}"))
    assert r.stats()["pending"] == 1
    assert r.commit(TID)
    assert len(r) == SpanRecorder.PENDING_SPANS  # per-trace span cap
    assert r.stats()["dropped"] >= 2 * SpanRecorder.PENDING_SPANS
    for i in range(2 * SpanRecorder.PENDING_TRACES):
        r.add(_span(f"{i:032x}", "aa" * 8))
    assert r.stats()["pending"] <= SpanRecorder.PENDING_TRACES


def test_forced_sample_outcomes_survive_at_sample_zero():
    r = SpanRecorder(sample=0.0)
    for reason in ("shed", "degraded"):
        with request_context(None, recorder=r):
            with span(f"forced.{reason}"):
                force_sample(reason)
    with pytest.raises(RuntimeError):
        with request_context(None, recorder=r):
            with span("forced.error"):
                raise RuntimeError("boom")
    names = {s["name"] for s in r.spans()}
    assert names == {"forced.shed", "forced.degraded", "forced.error"}
    # control: the same flow without forcing drops at sample=0
    with request_context(None, recorder=r):
        with span("unforced"):
            pass
    assert "unforced" not in {s["name"] for s in r.spans()}


def test_late_adds_route_via_tombstones():
    """A pipelined wave worker can add() after the request committed;
    the remembered decision routes the span (ring vs drop)."""
    r = SpanRecorder(sample=1.0)
    assert r.commit(TID)
    r.add(_span(TID, "aa" * 8))
    assert [s["span_id"] for s in r.spans(trace_id=TID)] == ["aa" * 8]
    r.sample = 0.0
    tid2 = "cd" * 16
    assert not r.commit(tid2)
    before = r.stats()["dropped"]
    r.add(_span(tid2, "bb" * 8))
    assert r.spans(trace_id=tid2) == []
    assert r.stats()["dropped"] == before + 1


def test_exemplar_tracks_last_sampled_trace():
    r = SpanRecorder(sample=1.0)
    assert r.exemplar() is None
    r.commit(TID)
    assert r.exemplar() == {"trace_id": TID}
    r.sample = 0.0
    r.commit("cd" * 16)  # unsampled: must not steal the exemplar
    assert r.exemplar() == {"trace_id": TID}


def test_hop_span_id_is_the_minted_traceparent_parent():
    """The caller-side ``peer.forward`` hop span's id IS the span id
    sent in the outbound traceparent — the owner's request span parents
    under it, which is the whole cross-daemon stitch."""
    r = SpanRecorder(sample=1.0)
    with request_context(None, recorder=r):
        with span("grpc.GetRateLimits"):
            tp = hop_traceparent("peer.forward", attrs={"items": 3})
    hop = [s for s in r.spans() if s["name"] == "peer.forward"]
    assert len(hop) == 1
    assert hop[0]["span_id"] == tp.split("-")[2]
    assert hop[0]["attrs"]["items"] == 3
    root = [s for s in r.spans() if s["name"] == "grpc.GetRateLimits"]
    assert hop[0]["parent_id"] == root[0]["span_id"]


def test_assemble_nests_dedups_and_orphans_to_roots():
    spans = [
        _span(TID, "r" * 16, name="root", start=0.0, end=3.0),
        _span(TID, "c" * 16, parent="r" * 16, name="child",
              start=1.0, end=2.0),
        _span(TID, "c" * 16, parent="r" * 16, name="child",
              start=1.0, end=2.0),  # duplicate slice fetch: dedup
        _span(TID, "o" * 16, parent="f" * 16, name="orphan",
              start=0.5, end=0.6),  # parent unknown: surfaces as root
        _span("99" * 16, "d" * 16, name="other"),
    ]
    traces = assemble(spans, trace_id=TID)
    assert len(traces) == 1 and traces[0]["spans"] == 3
    roots = {r["name"] for r in traces[0]["roots"]}
    assert roots == {"root", "orphan"}
    root = next(r for r in traces[0]["roots"] if r["name"] == "root")
    assert [c["name"] for c in root["children"]] == ["child"]
    text = render_waterfall(traces[0])
    for name in ("root", "child", "orphan"):
        assert name in text
    assert assemble(spans)[0]["trace_id"] in (TID, "99" * 16)


def test_slo_breach_event_carries_exemplar_trace():
    rec = FlightRecorder()
    eng = SLOEngine(recorder=rec, fast_s=10.0, slow_s=20.0,
                    clock=lambda: 0.0, exemplar=lambda: TID)
    state = {"bad": 0.0, "total": 0.0}

    def source():
        state["bad"] += 10.0
        state["total"] += 10.0  # 100% bad: burns past any threshold
        return state["bad"], state["total"]

    eng.register(SLO("error_ratio", "ratio", 0.99, source))
    for t in range(8):
        eng.tick(now=float(t))
    evs = rec.events(kind="slo_breach")
    assert evs and evs[-1]["exemplar_trace"] == TID
    # a failing exemplar callable must not kill the tick
    eng2 = SLOEngine(recorder=FlightRecorder(),
                     exemplar=lambda: 1 / 0)
    eng2.register(SLO("error_ratio", "ratio", 0.99, source))
    for t in range(8):
        eng2.tick(now=float(t))


# ---- instance-level: wave spans + their real children ------------------


def _wave_tree(recorder, tid, deadline_s=10.0):
    """Poll until the trace assembles with a wave that has phase
    children (the dispatcher thread lands them asynchronously)."""
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        traces = assemble(recorder.spans(), trace_id=tid)
        if traces:
            flat = []

            def walk(n):
                flat.append(n)
                for c in n.get("children", []):
                    walk(c)

            for r in traces[0]["roots"]:
                walk(r)
            waves = [n for n in flat
                     if n["name"] == "wave" and n.get("children")]
            if waves and len(traces[0]["roots"]) == 1:
                return traces[0], flat, waves
        time.sleep(0.05)
    raise AssertionError("wave span with children never assembled")


def _descendants(node):
    for c in node.get("children", ()):
        yield c
        yield from _descendants(c)


def _assert_children_inside(wave):
    """The wave's children are the wave.* / lock.* phases that ran for
    it, each with the start and end it was read at (ISSUE 24): real
    timestamps, so every child lies inside its parent, start <= end,
    and — one thread ran them — none overlaps the next."""
    kids = wave["children"]
    assert kids, "wave has no phase children"
    for k in kids:
        assert k["name"] in tracing.PHASE_CATALOG, k["name"]
        assert k["name"].startswith(("wave.", "lock.")), k["name"]
        assert k["attrs"]["wave"] == wave["attrs"]["wave"]
        assert wave["start"] <= k["start"] <= k["end"] <= wave["end"], \
            (wave["start"], k, wave["end"])
    for a, b in zip(kids, kids[1:]):  # assemble() sorts by start
        assert a["end"] <= b["start"], (a, b)
    assert {"wave.begin", "wave.end"} <= {k["name"] for k in kids}
    # nothing is laid out from a duration any more: the children's
    # total is at most the wave's own extent
    total = sum(k["end"] - k["start"] for k in kids)
    assert 0 < total <= wave["end"] - wave["start"]


def test_wave_phase_children_lie_inside_the_wave():
    inst = V1Instance(Config(cache_size=1 << 10, sweep_interval_ms=0),
                      engine=OracleEngine())
    try:
        inst.span_recorder.sample = 1.0
        with request_context(None, recorder=inst.span_recorder):
            tid = tracing.current_trace_id()
            with span("grpc.GetRateLimits"):
                inst.get_rate_limits([req(f"pk{i}") for i in range(8)],
                                     now_ms=NOW)
        trace, flat, waves = _wave_tree(inst.span_recorder, tid)
        root = trace["roots"][0]
        assert root["name"] == "grpc.GetRateLimits"
        for wave in waves:
            _assert_children_inside(wave)
        # the wave hangs under the request span (submit-time parent)
        names = {n["name"] for n in flat}
        assert "wave" in names
        wave_parents = {n["parent_id"] for n in waves}
        assert root["span_id"] in wave_parents
        assert all(w in list(_descendants(root)) for w in waves)
        # wave events carry the span id (join key event ↔ trace)
        evs = [e for e in inst.recorder.events(kind="wave_completed")
               if e.get("trace") == tid]
        assert evs and evs[-1].get("span_id") in {
            n["span_id"] for n in waves}
    finally:
        inst.close()


def test_shed_outcome_forces_sampling():
    from gubernator_tpu.dispatcher import ResourceExhausted

    inst = V1Instance(Config(cache_size=1 << 10, sweep_interval_ms=0),
                      engine=OracleEngine())
    try:
        inst.span_recorder.sample = 0.0
        inst.get_rate_limits([req("warm")], now_ms=NOW)
        inst.dispatcher.drain()
        with request_context(None, recorder=inst.span_recorder):
            tid = tracing.current_trace_id()
            with pytest.raises(ResourceExhausted):
                with span("grpc.GetRateLimits"):
                    inst.get_rate_limits([req("shed_k")], now_ms=NOW)
        # at sample=0 the trace survived only because the shed forced it
        spans = inst.span_recorder.spans(trace_id=tid)
        assert {s["name"] for s in spans} >= {"grpc.GetRateLimits"}
        evs = [e for e in inst.recorder.events(kind="admission_shed")
               if e.get("trace") == tid]
        assert evs and evs[-1].get("span_id")
    finally:
        inst.close()


# ---- 3-daemon cluster: cross-lane stitching ----------------------------


def test_three_daemon_cross_lane_stitch():
    """The acceptance shape: client → daemon 0 (traceparent metadata)
    → raw-TLV forward lanes → owner daemons.  Stitching the three
    ``/debug/traces`` slices yields ONE tree: the owner-side request
    span parents under daemon 0's ``peer.forward`` hop, its wave hangs
    below, and the wave's phase children lie inside it."""
    from gubernator_tpu import cluster as cluster_mod

    c = cluster_mod.start(3)
    try:
        for i in range(3):
            c.instance_at(i).span_recorder.sample = 1.0
        msg = pb.GetRateLimitsReq()
        for i in range(40):
            q = msg.requests.add()
            q.name, q.unique_key = "stitch", f"sk{i}"
            q.hits, q.limit, q.duration = 1, 100_000, 600_000
        ch = grpc.insecure_channel(c.grpc_address(0))
        call = ch.unary_unary(
            "/pb.gubernator.V1/GetRateLimits",
            request_serializer=pb.GetRateLimitsReq.SerializeToString,
            response_deserializer=pb.GetRateLimitsResp.FromString)
        resp = call(msg, timeout=60,
                    metadata=[("traceparent",
                               f"00-{TID}-00f067aa0ba902b7-01")])
        assert len(resp.responses) == 40

        deadline = time.monotonic() + 15.0
        stitched = None
        while time.monotonic() < deadline and stitched is None:
            spans = []
            for i in range(3):
                spans.extend(c.instance_at(i).span_recorder.spans(
                    trace_id=TID))
            traces = assemble(spans, trace_id=TID)
            if len(traces) == 1 and len(traces[0]["roots"]) == 1:
                root = traces[0]["roots"][0]
                # the hops hang under the request span's phases
                # (handler → ...), the waves under the owner
                # request's: descendants, not only children
                hops = {n["span_id"]: n for n in _descendants(root)
                        if n["name"] == "peer.forward"}
                owner_reqs = [
                    n for h in hops.values() for n in h["children"]
                    if n["name"] == "grpc.GetPeerRateLimits"]
                owner_waves = [
                    w for o in owner_reqs for w in _descendants(o)
                    if w["name"] == "wave" and w.get("children")]
                if hops and owner_reqs and owner_waves:
                    stitched = (root, hops, owner_reqs, owner_waves)
                    break
            time.sleep(0.1)
        assert stitched is not None, "cross-daemon trace never stitched"
        root, hops, owner_reqs, owner_waves = stitched
        assert root["name"] == "grpc.GetRateLimits"
        # the owner-side wave is a child of the owner request span,
        # which is a child of the caller's hop span — i.e. the wave is
        # a DESCENDANT of the caller's request span, cross-daemon
        for wave in owner_waves:
            _assert_children_inside(wave)
        ch.close()
    finally:
        c.stop()


# ---- daemon HTTP surface: /debug/traces + ?trace= ----------------------


@pytest.fixture(scope="module")
def tdaemon():
    from gubernator_tpu.daemon import spawn_daemon
    from gubernator_tpu.netutil import free_port

    d = spawn_daemon(DaemonConfig(
        grpc_listen_address=f"127.0.0.1:{free_port()}",
        http_listen_address=f"127.0.0.1:{free_port()}",
        cache_size=1 << 10), engine=OracleEngine())
    d.instance.span_recorder.sample = 1.0
    yield d
    d.close()


def _get(daemon, path, timeout=10):
    url = f"http://127.0.0.1:{daemon.http_port}{path}"
    with urllib.request.urlopen(url, timeout=timeout) as f:
        return json.loads(f.read())


def _post_check(daemon, key, timeout=60):
    body = json.dumps({"requests": [{
        "name": "traceco", "unique_key": key, "hits": 1,
        "limit": 100, "duration": 60_000}]}).encode()
    r = urllib.request.Request(
        f"http://127.0.0.1:{daemon.http_port}/v1/GetRateLimits",
        data=body, headers={"Content-Type": "application/json",
                            "traceparent": f"00-{TID}-{'cd' * 8}-01"})
    with urllib.request.urlopen(r, timeout=timeout) as f:
        return json.loads(f.read())


def test_debug_traces_endpoint(tdaemon):
    out = _post_check(tdaemon, "k_traces")
    assert out["responses"][0]["error"] == ""
    deadline = time.monotonic() + 10.0
    names = set()
    while time.monotonic() < deadline and "wave" not in names:
        body = _get(tdaemon, f"/debug/traces?trace_id={TID}")
        names = {s["name"] for s in body["spans"]}
        time.sleep(0.05)
    assert {"http.GetRateLimits", "wave"} <= names, names
    for k in ("sample", "capacity", "dropped"):
        assert k in body
    assert all(s["trace_id"] == TID for s in body["spans"])
    # limit keeps the newest N
    full = _get(tdaemon, "/debug/traces")["spans"]
    lim = _get(tdaemon, "/debug/traces?limit=2")["spans"]
    assert len(lim) == min(2, len(full)) and lim == full[-len(lim):]


def test_debug_events_trace_filter(tdaemon):
    _post_check(tdaemon, "k_evfilter")
    evs = _get(tdaemon, f"/debug/events?trace={TID}")["events"]
    assert evs and all(e.get("trace") == TID for e in evs)
    wave_evs = [e for e in evs if e["kind"].startswith("wave_")]
    assert wave_evs and all(e.get("span_id") for e in wave_evs)
    assert _get(tdaemon, "/debug/events?trace=none")["events"] == []


def test_trace_dump_written_on_close(tmp_path, monkeypatch):
    import glob
    import os

    monkeypatch.setenv("GUBER_DEBUG_DUMP_DIR", str(tmp_path))
    inst = V1Instance(Config(cache_size=1 << 10, sweep_interval_ms=0),
                      engine=OracleEngine())
    inst.span_recorder.sample = 1.0
    with request_context(None, recorder=inst.span_recorder):
        with span("grpc.GetRateLimits"):
            inst.get_rate_limits([req("dump_k")], now_ms=NOW)
    inst.close()
    files = glob.glob(os.path.join(str(tmp_path), "guber_traces_*.jsonl"))
    assert len(files) == 1
    with open(files[0], encoding="utf-8") as f:
        lines = [json.loads(ln) for ln in f if ln.strip()]
    assert lines[0]["kind"] == "trace_header"
    assert lines[0]["spans"] == len(lines) - 1 >= 1
    assert all("span_id" in ln for ln in lines[1:])
    # tools/trace_assemble.py stitches the spill into a waterfall
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, os.path.join("tools", "trace_assemble.py"),
         files[0]],
        capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr
    assert "grpc.GetRateLimits" in out.stdout


def test_cli_debug_traces_subcommand(tdaemon):
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    _post_check(tdaemon, "k_cli_traces")
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        if any(s["name"] == "wave" for s in
               tdaemon.instance.span_recorder.spans(trace_id=TID)):
            break
        time.sleep(0.05)
    url = f"http://127.0.0.1:{tdaemon.http_port}"
    r = subprocess.run(
        [sys.executable, "-m", "gubernator_tpu.cmd.cli", "debug",
         "traces", "--url", url, "--trace-id", TID, "--json"],
        cwd=repo, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    body = json.loads(r.stdout)
    assert body["daemons"] and {s["name"] for s in body["spans"]} >= {
        "http.GetRateLimits", "wave"}
    # waterfall render: one tree, the request span on top
    r2 = subprocess.run(
        [sys.executable, "-m", "gubernator_tpu.cmd.cli", "debug",
         "traces", "--url", url, "--trace-id", TID, "--waterfall"],
        cwd=repo, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert r2.returncode == 0, r2.stderr
    assert f"trace {TID}" in r2.stdout
    assert "http.GetRateLimits" in r2.stdout and "#" in r2.stdout
    # events --trace: server-side filter through the CLI
    r3 = subprocess.run(
        [sys.executable, "-m", "gubernator_tpu.cmd.cli", "debug",
         "events", "--url", url, "--trace", TID, "--json"],
        cwd=repo, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert r3.returncode == 0, r3.stderr
    evs = json.loads(r3.stdout)["events"]
    assert evs and all(e["trace"] == TID for e in evs)


# ---- 16-thread soak: zero recorder backpressure ------------------------


@pytest.mark.slow
def test_sixteen_thread_soak_no_recorder_backpressure():
    """Armed-but-unsampled is the production default: 16 threads of
    traced traffic must leave the recorder EMPTY — no pending buildup
    (every trace commits), nothing sampled into the ring, no errors."""
    inst = V1Instance(Config(cache_size=1 << 12, sweep_interval_ms=0),
                      engine=OracleEngine())
    errors = []
    try:
        inst.span_recorder.sample = 0.0
        N, T = 20, 16

        def worker(t):
            try:
                for i in range(N):
                    with request_context(None,
                                         recorder=inst.span_recorder):
                        with span("grpc.GetRateLimits"):
                            out = inst.get_rate_limits(
                                [req(f"soak{t}_{i}")], now_ms=NOW)
                    assert out[0].error == ""
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(T)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert not errors, errors
        # late wave adds drain through tombstones within moments
        deadline = time.monotonic() + 5.0
        st = inst.span_recorder.stats()
        while time.monotonic() < deadline and st["pending"]:
            time.sleep(0.05)
            st = inst.span_recorder.stats()
        assert st["pending"] == 0, st
        assert st["spans"] == 0, st  # nothing head-sampled at rate 0
    finally:
        inst.close()
