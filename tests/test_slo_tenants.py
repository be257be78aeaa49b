"""Tenant-aware SLO plane (ISSUE 11): per-tenant attribution, the
burn-rate SLO engine, the collective cost model, and crash forensics.

Covers the tentpole invariants — bounded tenant cardinality with EXACT
``__other__`` folding (per-tenant sums == totals, conservation), the
multi-window breach→recover lifecycle (fake clock, deterministic), the
chaos staleness story on a real 8-device mesh (fold failures breach
``global_staleness``, a clean fold recovers it), the α-β cost-model fit
on held-out samples — plus the satellites: the ``/debug/tenants`` /
``/debug/slo`` / ``/debug/costmodel`` endpoints, the ``?tenant=`` event
filter, the drain debug dump, and ``healthcheck --fail-on-burn``."""
import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

from gubernator_tpu.analytics import CostModel, TenantLedger
from gubernator_tpu.config import BehaviorConfig, Config, DaemonConfig
from gubernator_tpu.instance import V1Instance
from gubernator_tpu.oracle import OracleEngine
from gubernator_tpu.proto import gubernator_pb2 as pb
from gubernator_tpu.slo import SLO, SLO_CATALOG, SLOEngine
from gubernator_tpu.telemetry import FlightRecorder
from gubernator_tpu.types import RateLimitRequest

NOW = 1_791_000_000_000


def req(name, key, hits=1, **kw):
    d = dict(limit=100_000, duration=600_000)
    d.update(kw)
    return RateLimitRequest(name=name, unique_key=key, hits=hits, **d)


def ser(reqs):
    m = pb.GetRateLimitsReq()
    for r in reqs:
        q = m.requests.add()
        q.name, q.unique_key = r.name, r.unique_key
        q.hits, q.limit, q.duration = r.hits, r.limit, r.duration
        q.behavior = int(r.behavior)
        q.algorithm = int(r.algorithm)
    return m.SerializeToString()


def drain_analytics(ana):
    """Fold every queued tap (learn items included) into the ledgers."""
    ana.flush(timeout=5.0)
    ana.flush(timeout=5.0)  # second pass: learns land before re-counts


# ---- TenantLedger: bounded cardinality + exact conservation ------------


def test_tenant_ledger_bounded_cardinality(monkeypatch):
    """10× max distinct prefixes stay bounded at max+1 buckets and the
    overflow folds into ``__other__`` EXACTLY (conservation)."""
    monkeypatch.setenv("GUBER_TENANT_MAX", "8")
    tl = TenantLedger()
    n = 80  # 10× the max
    for i in range(n):
        idx = tl.index_of(f"t{i:03d}/api")
        tl.add(idx, "requests", 3)
    snap = tl.snapshot()
    assert snap["tenant_count"] <= 8 + 1  # + __other__
    assert snap["overflowed"] is True
    # conservation: every request landed somewhere
    per_tenant = sum(c["requests"] for c in snap["tenants"].values())
    assert per_tenant == snap["totals"]["requests"] == n * 3
    assert snap["tenants"][TenantLedger.OTHER]["requests"] == \
        (n - 8) * 3


def test_tenant_ledger_fold_conservation(monkeypatch):
    """Vectorized fold: hits/over counts distribute by bucket index
    with nothing lost, including rows folded to ``__other__``."""
    monkeypatch.setenv("GUBER_TENANT_MAX", "4")
    tl = TenantLedger()
    idxs = np.array([tl.index_of(f"p{i}/k") for i in range(12)])
    hits = np.arange(12, dtype=np.int64) + 1
    over = np.arange(12) % 3 == 0
    tl.fold(idxs, hits, over)
    tot = tl.totals()
    assert tot["requests"] == 12
    assert tot["hits"] == int(hits.sum())
    assert tot["over_limit"] == int(over.sum())
    snap = tl.snapshot()
    assert sum(c["hits"] for c in snap["tenants"].values()) == \
        int(hits.sum())


def test_tenant_ledger_chaos_soak_16_threads(monkeypatch):
    """16 threads hammer assignment, folds, flags, and snapshots
    concurrently; totals conserve exactly afterwards."""
    monkeypatch.setenv("GUBER_TENANT_MAX", "16")
    tl = TenantLedger()
    N_THREADS, PER = 16, 200
    errs = []

    def worker(w):
        try:
            rng = np.random.default_rng(w)
            for i in range(PER):
                idx = tl.index_of(f"ten{int(rng.integers(0, 40))}/x")
                tl.add(idx, "requests", 1)
                if i % 7 == 0:
                    idxs = np.array([idx, tl.index_of("soak/y")])
                    tl.fold(idxs, np.array([2, 1], np.int64),
                            np.array([False, True]))
                if i % 13 == 0:
                    tl.snapshot()
                    tl.red("shed")
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    ts = [threading.Thread(target=worker, args=(w,))
          for w in range(N_THREADS)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=60)
    assert not errs
    snap = tl.snapshot()
    folds = sum(2 for w in range(N_THREADS)
                for i in range(PER) if i % 7 == 0)
    expect = N_THREADS * PER + folds
    assert snap["totals"]["requests"] == expect
    assert sum(c["requests"] for c in snap["tenants"].values()) == expect
    assert snap["tenant_count"] <= 16 + 1


# ---- instance-level attribution (both lanes) ---------------------------


def test_instance_tenant_attribution_conservation():
    """Object + wire lanes attribute every request to its key-prefix
    tenant; per-tenant sums equal the ledger totals equal the traffic
    actually sent (nothing dropped, nothing double-counted).  Default
    (sharded jax) engine: the wire lane needs check_packed."""
    inst = V1Instance(Config(cache_size=1 << 10, sweep_interval_ms=0,
                             batch_rows=64))
    try:
        sent = 0
        for w in range(3):
            reqs = [req(f"acme{i % 3}/api", f"u{w}_{i}")
                    for i in range(24)]
            inst.get_rate_limits(reqs, now_ms=NOW + w)
            sent += len(reqs)
            out = inst.get_rate_limits_wire(ser(reqs), now_ms=NOW + w)
            assert out
            sent += len(reqs)
        ana = inst.dispatcher.analytics
        drain_analytics(ana)
        snap = ana.tenants_snapshot()
        assert snap["enabled"]
        names = set(snap["tenants"])
        assert {"acme0", "acme1", "acme2"} <= names
        per_tenant = sum(c["requests"] for c in snap["tenants"].values())
        assert per_tenant == snap["totals"]["requests"] == sent
        # the three named tenants got equal shares; nothing leaked to
        # __other__ (cardinality 3 « the default max)
        for t in ("acme0", "acme1", "acme2"):
            assert snap["tenants"][t]["requests"] == sent // 3
    finally:
        inst.close()


def test_shed_attributed_to_tenant():
    """A drained dispatcher sheds with the triggering tenant on both
    the admission_shed event and the tenant ledger."""
    from gubernator_tpu.dispatcher import ResourceExhausted

    inst = V1Instance(Config(cache_size=1 << 10, sweep_interval_ms=0),
                      engine=OracleEngine())
    try:
        inst.get_rate_limits([req("shedco/api", "warm")], now_ms=NOW)
        ana = inst.dispatcher.analytics
        drain_analytics(ana)
        inst.dispatcher.drain()
        with pytest.raises(ResourceExhausted):
            inst.get_rate_limits([req("shedco/api", "k1")],
                                 now_ms=NOW + 1)
        evs = inst.recorder.events(kind="admission_shed")
        assert evs and evs[-1]["tenant"] == "shedco"
        drain_analytics(ana)
        assert ana.tenant_totals()["shed"] == 1
        assert ana.tenants_snapshot()["tenants"]["shedco"]["shed"] == 1
    finally:
        inst.close()


def test_wave_events_carry_tenant():
    inst = V1Instance(Config(cache_size=1 << 10, sweep_interval_ms=0),
                      engine=OracleEngine())
    try:
        inst.get_rate_limits([req("waveco/api", "k")], now_ms=NOW)
        evs = inst.recorder.events(kind="wave_completed")
        assert evs and evs[-1].get("tenant") == "waveco"
        # server-side tenant filter round trip
        assert inst.recorder.events(tenant="waveco")
        assert not inst.recorder.events(tenant="nobody")
    finally:
        inst.close()


# ---- SLO engine: deterministic breach → recover ------------------------


def test_slo_breach_recover_lifecycle():
    """Multi-window burn: a sustained bad period breaches (fast AND
    slow over threshold), a good period recovers (fast back under);
    events latch exactly once each."""
    rec = FlightRecorder()
    state = {"bad": 0.0, "total": 0.0}
    eng = SLOEngine(metrics=None, recorder=rec, fast_s=10.0,
                    slow_s=30.0, burn_threshold=2.0)
    eng.register(SLO("err", "ratio", 0.99,
                     lambda: (state["bad"], state["total"])))
    t = 1000.0
    for _ in range(35):  # healthy baseline fills both windows
        state["total"] += 100
        eng.tick(now=t)
        t += 1.0
    assert not rec.events(kind="slo_breach")
    for _ in range(35):  # 50% bad → burn 50 ≫ 2 in both windows
        state["total"] += 100
        state["bad"] += 50
        eng.tick(now=t)
        t += 1.0
    breaches = rec.events(kind="slo_breach")
    assert len(breaches) == 1 and breaches[0]["slo"] == "err"
    assert breaches[0]["fast_burn"] > 2.0
    for _ in range(40):  # clean again → fast window drains → recover
        state["total"] += 100
        eng.tick(now=t)
        t += 1.0
    recs = rec.events(kind="slo_recovered")
    assert len(recs) == 1 and recs[0]["slo"] == "err"
    assert len(rec.events(kind="slo_breach")) == 1  # latched once
    # verdicts() reports the latched state without re-evaluating
    v = {r["slo"]: r["breached"] for r in eng.verdicts()}
    assert v == {"err": False}


def test_slo_tenant_group_breach_is_attributed():
    rec = FlightRecorder()
    state = {"t-bad": (0.0, 0.0)}
    eng = SLOEngine(recorder=rec, fast_s=10.0, slow_s=20.0,
                    burn_threshold=2.0)
    eng.register_group("tenant_err", 0.99,
                       lambda: {"t-bad": state["t-bad"],
                                "t-good": (0.0, state["t-bad"][1])})
    t = 0.0
    for i in range(40):
        state["t-bad"] = (i * 60.0, i * 100.0)  # 60% bad
        eng.tick(now=t)
        t += 1.0
    evs = rec.events(kind="slo_breach")
    assert evs and evs[0]["slo"] == "tenant_err"
    assert evs[0]["tenant"] == "t-bad"
    assert not any(e.get("tenant") == "t-good" for e in evs)


def test_slo_threshold_kind_counts_out_of_bounds_ticks():
    eng = SLOEngine(fast_s=10.0, slow_s=20.0, burn_threshold=2.0)
    val = {"v": 0.0}
    eng.register(SLO("stale", "threshold", 0.95,
                     lambda: (val["v"], 1.0)))
    t = 0.0
    for _ in range(30):
        eng.tick(now=t)
        t += 1.0
    rows = eng.tick(now=t)
    assert rows[0]["fast_burn"] == 0.0
    val["v"] = 5.0  # out of bounds from here on
    for _ in range(15):
        rows = eng.tick(now=t)
        t += 1.0
    assert rows[0]["breached"]
    assert rows[0]["value"] == 5.0 and rows[0]["target"] == 1.0


# ---- chaos staleness on a real mesh ------------------------------------


def test_mesh_staleness_slo_breach_and_recover(monkeypatch):
    """The acceptance chaos story: fold failures stop the coherence
    clock, ``global_staleness`` breaches past 2× the reconcile
    interval, and a clean fold recovers it — pinned via the recorder
    events and the /debug/slo snapshot shape."""
    from gubernator_tpu.parallel import make_mesh

    monkeypatch.setenv("GUBER_MESH_GLOBAL_CAP", "256")
    monkeypatch.setenv("GUBER_SLO_FAST", "1s")
    monkeypatch.setenv("GUBER_SLO_SLOW", "2s")
    # the test ticks the engine on a clock of its own: a tick of the
    # instance's loop (wall clock, every second by default) in between
    # would put a sample of another time base into the same windows
    monkeypatch.setenv("GUBER_SLO_TICK", "1h")
    inst = V1Instance(
        Config(cache_size=1 << 12, sweep_interval_ms=0,
               global_mode="mesh", batch_rows=64,
               behaviors=BehaviorConfig(global_sync_wait_ms=100)),
        mesh=make_mesh(n=8))
    try:
        from gubernator_tpu.types import Behavior

        reqs = [req("mesh-t/api", f"k{i}", behavior=Behavior.GLOBAL)
                for i in range(8)]
        inst.get_rate_limits(reqs, now_ms=NOW)
        inst._mesh_reconcile_tick()  # clean fold: staleness clock set
        assert inst._mesh_last_fold_ok is not None
        eng = inst.slo
        t = 0.0
        for _ in range(12):  # healthy baseline
            eng.tick(now=t)
            t += 0.1
        assert not inst.recorder.events(kind="slo_breach")
        # chaos: every fold fails → the last-good-fold age grows past
        # the 2×interval target (0.2 s) in real time
        inst.faults.arm("global_psum:error", seed=5)
        inst._mesh_reconcile_tick()
        time.sleep(0.25)
        for _ in range(12):  # every tick now sees staleness > target
            eng.tick(now=t)
            t += 0.1
        breaches = inst.recorder.events(kind="slo_breach")
        assert any(e["slo"] == "global_staleness" for e in breaches), \
            breaches
        # recovery: clear the fault, one clean fold resets the clock
        inst.faults.clear()
        inst._mesh_reconcile_tick()
        for _ in range(25):
            eng.tick(now=t)
            t += 0.1
        recovered = inst.recorder.events(kind="slo_recovered")
        assert any(e["slo"] == "global_staleness" for e in recovered), \
            recovered
        snap = eng.snapshot()
        row = next(r for r in snap["slos"]
                   if r["slo"] == "global_staleness")
        assert not row["breached"] and row["value"] < row["target"]
        # the fold also fed the cost model
        cm = inst.dispatcher.analytics.costmodel_snapshot()
        assert any(b["phase"] == "global_fold" and b["ndev"] == 8
                   for b in cm["buckets"])
    finally:
        inst.close()


# ---- cost model: fit + held-out prediction -----------------------------


def test_cost_model_recovers_alpha_beta_held_out():
    """Noisy synthetic α-β samples: the closed-form fit predicts
    held-out durations within 10% relative error."""
    rng = np.random.default_rng(7)
    cm = CostModel()
    alpha, beta = 200e-6, 0.8e-9  # 200 µs + 0.8 ns/byte
    sizes = rng.integers(10_000, 5_000_000, size=60)
    for s in sizes:
        noise = 1.0 + float(rng.normal(0, 0.01))
        cm.add("fold", int(s), 8, (alpha + beta * int(s)) * noise)
    fit = cm.fit("fold", 8)
    assert fit is not None
    for s in (25_000, 400_000, 4_000_000):  # held out
        pred = cm.predict("fold", 8, s)
        truth = alpha + beta * s
        assert abs(pred - truth) / truth < 0.10, (s, pred, truth)
    assert abs(fit["alpha_s"] - alpha) / alpha < 0.25
    assert abs(fit["beta_s_per_byte"] - beta) / beta < 0.10
    snap = cm.snapshot()
    assert snap["model"].startswith("T = alpha")
    assert snap["buckets"][0]["samples"] == 60


# ---- crash forensics: the drain dump -----------------------------------


def test_debug_dump_on_close(tmp_path, monkeypatch):
    monkeypatch.setenv("GUBER_DEBUG_DUMP_DIR", str(tmp_path))
    monkeypatch.setenv("GUBER_INSTANCE_ID", "dump-test")
    inst = V1Instance(Config(cache_size=1 << 10, sweep_interval_ms=0),
                      engine=OracleEngine())
    inst.get_rate_limits([req("dumpco/api", "k")], now_ms=NOW)
    inst.close()
    files = sorted(tmp_path.glob("guber_dump_dump-test_*.jsonl"))
    assert len(files) == 1
    lines = files[0].read_text().splitlines()
    header = json.loads(lines[0])
    assert header["kind"] == "dump_header"
    assert header["instance"] == "dump-test"
    assert isinstance(header["slo_verdicts"], list)
    assert {v["slo"] for v in header["slo_verdicts"]} >= \
        {"decision_p99", "error_ratio", "shed_ratio"}
    events = [json.loads(ln) for ln in lines[1:]]
    assert len(events) == header["events"] >= 1
    assert any(e["kind"] == "wave_completed" for e in events)
    # the write itself left a breadcrumb in the (post-dump) ring
    assert inst.recorder.events(kind="debug_dump_written")


def test_debug_dump_disabled_by_default(tmp_path, monkeypatch):
    monkeypatch.delenv("GUBER_DEBUG_DUMP_DIR", raising=False)
    inst = V1Instance(Config(cache_size=1 << 10, sweep_interval_ms=0),
                      engine=OracleEngine())
    inst.get_rate_limits([req("a/b", "k")], now_ms=NOW)
    inst.close()
    assert not inst.recorder.events(kind="debug_dump_written")


# ---- daemon endpoints + CLI + healthcheck ------------------------------


@pytest.fixture(scope="module")
def daemon():
    from gubernator_tpu.daemon import spawn_daemon
    from gubernator_tpu.netutil import free_port

    # a lax p99 target + quick ticks: the SLO plane must not flap the
    # endpoint tests on a loaded CI box
    os.environ["GUBER_SLO_P99_MS"] = "60000"
    os.environ["GUBER_SLO_TICK"] = "100ms"
    try:
        d = spawn_daemon(DaemonConfig(
            grpc_listen_address=f"127.0.0.1:{free_port()}",
            http_listen_address=f"127.0.0.1:{free_port()}",
            cache_size=1 << 10), engine=OracleEngine())
    finally:
        del os.environ["GUBER_SLO_P99_MS"]
        del os.environ["GUBER_SLO_TICK"]
    yield d
    d.close()


def _get(daemon, path, timeout=10):
    url = f"http://127.0.0.1:{daemon.http_port}{path}"
    with urllib.request.urlopen(url, timeout=timeout) as f:
        return json.loads(f.read())


def _post_check(daemon, name, key):
    body = json.dumps({"requests": [{
        "name": name, "unique_key": key, "hits": 1, "limit": 100,
        "duration": 60_000}]}).encode()
    r = urllib.request.Request(
        f"http://127.0.0.1:{daemon.http_port}/v1/GetRateLimits",
        data=body, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(r, timeout=30) as f:
        return json.loads(f.read())


def test_debug_tenants_endpoint(daemon):
    for i in range(6):
        _post_check(daemon, f"team{i % 2}/svc", f"k{i}")
    body = _get(daemon, "/debug/tenants")
    assert body["enabled"]
    assert {"team0", "team1"} <= set(body["tenants"])
    assert sum(c["requests"] for c in body["tenants"].values()) == \
        body["totals"]["requests"]


def test_debug_slo_endpoint(daemon):
    body = _get(daemon, "/debug/slo")
    assert body["burn_threshold"] > 0
    names = {r["slo"] for r in body["slos"]}
    # instance-wide SLOs always present; tenant groups appear once
    # attributed traffic exists (the test above sent some)
    assert {"decision_p99", "global_staleness", "error_ratio",
            "shed_ratio"} <= names
    for r in body["slos"]:
        assert r["slo"] in SLO_CATALOG
        assert "fast_burn" in r and "breached" in r


def test_debug_costmodel_endpoint(daemon):
    body = _get(daemon, "/debug/costmodel")
    assert body["model"] == "T = alpha + beta * bytes"
    assert isinstance(body["buckets"], list)


def test_healthz_deep_has_slo_block(daemon):
    body = _get(daemon, "/healthz?deep=1")
    assert "slo" in body
    assert set(body["slo"]) >= {"breached", "burning", "max_fast_burn",
                                "burn_threshold"}


def test_debug_events_tenant_filter_endpoint(daemon):
    _post_check(daemon, "filterco/svc", "fk")
    evs = _get(daemon, "/debug/events?tenant=filterco")["events"]
    assert evs and all(e["tenant"] == "filterco" for e in evs)
    assert not _get(daemon, "/debug/events?tenant=ghost")["events"]


def test_cli_debug_tenants_and_slo(daemon, capsys):
    from gubernator_tpu.cmd.cli import main

    url = f"http://127.0.0.1:{daemon.http_port}"
    assert main(["debug", "tenants", "--url", url]) == 0
    out = capsys.readouterr().out
    assert "team0" in out and "TOTAL" in out
    assert main(["debug", "slo", "--url", url, "--json"]) == 0
    body = json.loads(capsys.readouterr().out)
    assert {r["slo"] for r in body["slos"]} >= {"decision_p99"}


def test_healthcheck_fail_on_burn(daemon, capsys):
    from gubernator_tpu.cmd.healthcheck import main

    url = f"http://127.0.0.1:{daemon.http_port}/healthz"
    # nothing breached (lax targets) → ready
    assert main(["--url", url, "--fail-on-burn"]) == 0
    capsys.readouterr()


def test_healthcheck_fail_on_burn_exits_1_on_breach(capsys):
    """Flag logic against a canned /healthz: a breached SLO flips the
    exit code; without the flag the same body stays healthy."""
    from http.server import BaseHTTPRequestHandler, HTTPServer

    from gubernator_tpu.cmd.healthcheck import main

    body = json.dumps({
        "status": "healthy", "message": "", "peer_count": 0,
        "slo": {"breached": ["error_ratio"], "burning": ["error_ratio"],
                "max_fast_burn": 9.5, "burn_threshold": 2.0}}).encode()

    class H(BaseHTTPRequestHandler):
        def do_GET(self):
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):
            pass

    srv = HTTPServer(("127.0.0.1", 0), H)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        url = f"http://127.0.0.1:{srv.server_address[1]}/healthz"
        assert main(["--url", url, "--fail-on-burn"]) == 1
        assert "SLO breached: error_ratio" in capsys.readouterr().err
        assert main(["--url", url]) == 0  # plain probe ignores burn
    finally:
        srv.shutdown()
        srv.server_close()
