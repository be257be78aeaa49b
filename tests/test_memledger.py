"""Device-memory ledger (ISSUE 13): the exactness audit.

The ledger's claim is strong — accounted bytes equal the live jax-array
``nbytes`` at any instant, on every engine configuration — so the audit
independently walks the instance's device-resident state (engine table
leaves, mesh-GLOBAL replica + both hit accumulators) and compares
against ``memledger.snapshot()`` totals.
Covered configs: classic sharded, fused XLA serving, mesh-GLOBAL bound,
and the tiered store (whose cold tier must land on the HOST ledger, not
the device one).  Enrollment is leak-free across engine stand-down, and
the two-tier snapshot/restore round trip keeps the audit exact because
probes re-read the live rebinding state.  Plus the ledger unit surface:
pressure edge-triggering, suspend/resume, republish label hygiene, and
the advisor's floor/budget invariants on synthetic demand."""
import jax
import pytest

from gubernator_tpu.config import Config
from gubernator_tpu.instance import V1Instance
from gubernator_tpu.memledger import MemoryLedger, _pow2_ceil
from gubernator_tpu.parallel import make_mesh
from gubernator_tpu.store import MockLoader
from gubernator_tpu.types import Behavior, RateLimitRequest

NOW = 1_793_000_000_000
DAY = 86_400_000


def _reqs(prefix, n, behavior=Behavior.BATCHING, duration=DAY):
    return [RateLimitRequest(name="led", unique_key=f"{prefix}{i}",
                             hits=1, limit=10 ** 6, duration=duration,
                             behavior=behavior)
            for i in range(n)]


def _expected_device_bytes(inst) -> int:
    """The audit's independent walk of every device-resident array the
    instance owns — deliberately NOT via the ledger's probes."""
    total = sum(int(a.nbytes)
                for a in jax.tree.leaves(inst.engine.state))
    mge = inst._meshglobal
    if mge is not None:
        with mge._state_mu:
            total += sum(int(a.nbytes)
                         for a in jax.tree.leaves(mge.state))
            total += sum(int(a.nbytes) for a in mge._acc)
    return total


def _audit(inst):
    snap = inst.memledger.snapshot()
    assert snap["enabled"] is True
    for name, rec in snap["consumers"].items():
        assert "error" not in rec, (name, rec)
    assert snap["device_bytes"] == _expected_device_bytes(inst), \
        snap["consumers"]
    assert 0.0 <= snap["pressure"] <= 1.0
    return snap


def test_exact_classic():
    inst = V1Instance(Config(cache_size=2048, sweep_interval_ms=0),
                      mesh=make_mesh(n=1))
    try:
        inst.get_rate_limits(_reqs("c", 200), now_ms=NOW)
        snap = _audit(inst)
        hot = snap["consumers"]["hot_table"]
        assert hot["capacity_rows"] >= 2048
        assert hot["occupied_rows"] >= 200
        assert hot["advisable"] is True and hot["host"] is False
    finally:
        inst.close()


def test_exact_fused_xla(monkeypatch):
    monkeypatch.setenv("GUBER_ENGINE", "pallas")  # → fused XLA off-TPU
    inst = V1Instance(Config(cache_size=2048, sweep_interval_ms=0),
                      mesh=make_mesh(n=1))
    try:
        assert type(inst.engine).__name__ == "XlaFusedEngine"
        inst.get_rate_limits(_reqs("f", 200), now_ms=NOW)
        _audit(inst)
    finally:
        inst.close()


def test_exact_mesh_global_bound():
    inst = V1Instance(Config(cache_size=2048, sweep_interval_ms=0,
                             global_mode="mesh"), mesh=make_mesh(n=1))
    try:
        # GLOBAL traffic builds the mesh tier lazily; its replica and
        # BOTH accumulator buffers must land on the device ledger
        inst.get_rate_limits(_reqs("g", 32, behavior=Behavior.GLOBAL),
                             now_ms=NOW)
        snap = _audit(inst)
        mg = snap["consumers"]["mesh_global"]
        assert mg["bytes"] > 0 and mg["occupied_rows"] >= 32
        assert mg["advisable"] is True
    finally:
        inst.close()


def test_exact_tiered_and_snapshot_restore_roundtrip():
    """Cap 1024 vs a 3000-key domain: overflow rows live in the HOST
    cold store; the audit stays exact through spill and through the
    two-tier snapshot/restore round trip (probes re-read the live
    rebinding state, so a restored instance audits exactly too)."""
    loader = MockLoader()

    def _cfg():
        return Config(cache_size=1024, cache_autogrow_max=1024,
                      tier_cold=True, tier_promote_threshold=2,
                      sweep_interval_ms=0,
                      loader=loader)

    inst = V1Instance(_cfg(), mesh=make_mesh(n=1))
    try:
        for base in range(0, 3000, 500):
            inst.get_rate_limits(_reqs(f"t{base}_", 500),
                                 now_ms=NOW + base)
        snap = _audit(inst)
        cold = snap["consumers"]["cold_store"]
        assert cold["host"] is True and cold["bytes"] > 0
        assert cold["occupied_rows"] > 0
        assert inst._tier.mem_bytes() == cold["bytes"]
        assert snap["host_bytes"] >= cold["bytes"]
    finally:
        inst.close()  # saves BOTH tiers through the loader
    assert loader.called["save"] == 1
    inst2 = V1Instance(_cfg(), mesh=make_mesh(n=1))
    try:
        snap2 = _audit(inst2)
        assert snap2["consumers"]["cold_store"]["occupied_rows"] > 0, \
            "restore overflow rows did not land cold"
    finally:
        inst2.close()


def test_enroll_release_leak_free_across_stand_down():
    inst = V1Instance(Config(cache_size=1024, sweep_interval_ms=0),
                      mesh=make_mesh(n=1))
    led = inst.memledger
    assert "hot_table" in led.consumers()
    inst.close()
    assert led.consumers() == [], "close() must drain every enrollment"
    assert led.release("hot_table") is False
    # a released ledger still snapshots (empty plane, no stale probes)
    snap = led.snapshot()
    assert snap["device_bytes"] == 0 and snap["consumers"] == {}


def test_disabled_by_env(monkeypatch):
    monkeypatch.setenv("GUBER_MEM_LEDGER", "0")
    inst = V1Instance(Config(cache_size=1024, sweep_interval_ms=0),
                      mesh=make_mesh(n=1))
    try:
        assert inst.memledger is None
    finally:
        inst.close()


# ---- ledger unit surface (no instance) ------------------------------------


class _Recorder:
    def __init__(self):
        self.events = []

    def record(self, kind, **fields):
        self.events.append(dict(fields, kind=kind))


def test_pressure_edge_triggering():
    rec = _Recorder()
    led = MemoryLedger(recorder=rec)
    occ = {"n": 0}
    led.enroll("tbl", lambda: {"bytes": 1 << 20, "capacity_rows": 100,
                               "occupied_rows": occ["n"]},
               advisable=True)
    assert led.pressure_sample() == (0.0, led.pressure_target)
    occ["n"] = 95  # above the 0.85 default target
    p, _t = led.pressure_sample()
    assert p == pytest.approx(0.95)
    led.pressure_sample()  # still hot: must NOT re-record
    kinds = [e["kind"] for e in rec.events]
    assert kinds == ["memory_pressure"], rec.events
    assert rec.events[0]["occupancy"] == {"tbl": 0.95}
    occ["n"] = 10  # excursion ends → the edge re-arms
    led.pressure_sample()
    occ["n"] = 95
    led.pressure_sample()
    assert [e["kind"] for e in rec.events] == ["memory_pressure"] * 2


def test_suspend_resume_and_probe_error_containment():
    led = MemoryLedger()
    led.enroll("ok", lambda: {"bytes": 64})
    led.enroll("boom", lambda: (_ for _ in ()).throw(RuntimeError("x")))
    snap = led.snapshot()
    assert snap["device_bytes"] == 64
    assert "error" in snap["consumers"]["boom"]
    led.suspend()
    assert led.enabled is False
    empty = led.snapshot()
    assert empty["device_bytes"] == 0 and empty["consumers"] == {}
    led.resume()
    assert led.snapshot()["device_bytes"] == 64
    assert sorted(led.consumers()) == ["boom", "ok"]


def test_advise_floor_and_budget_invariants():
    led = MemoryLedger()
    led.enroll("hot", lambda: {
        "bytes": 1 << 20, "capacity_rows": 1024, "occupied_rows": 1024,
        "demand": {"ranks": [1000 - i for i in range(512)]}},
        advisable=True)
    led.enroll("idle", lambda: {
        "bytes": 1 << 20, "capacity_rows": 1024, "occupied_rows": 8,
        "demand": {"fold_rate": 2.0}}, advisable=True)
    led.enroll("host_thing", lambda: {"bytes": 123}, host=True)
    adv = led.advise(total_rows=2048)
    assert set(adv["advised"]) == {"hot", "idle"}, \
        "host consumers must never enter the advised split"
    assert sum(adv["advised"].values()) == 2048, adv
    assert all(v >= adv["floor_rows"] for v in adv["advised"].values())
    # demand concentrates on `hot`: the idle tier keeps its floor only
    assert adv["advised"]["idle"] == adv["floor_rows"]
    assert adv["advised"]["hot"] == 2048 - adv["floor_rows"]
    assert adv["advised_pow2"]["hot"] == _pow2_ceil(
        adv["advised"]["hot"])
    assert adv["demand"]["hot"]["ranks"][0] == 1000


def test_republish_removes_departed_labels():
    from gubernator_tpu.metrics import Metrics

    m = Metrics()
    led = MemoryLedger()
    led.enroll("a", lambda: {"bytes": 10, "capacity_rows": 4,
                             "occupied_rows": 2})
    led.republish(m)
    text = m.render().decode()
    assert 'gubernator_memledger_bytes{consumer="a"} 10.0' in text
    assert ('gubernator_memledger_rows{consumer="a",state="capacity"} '
            '4.0') in text
    led.release("a")
    led.enroll("b", lambda: {"bytes": 7})
    led.republish(m)
    text = m.render().decode()
    assert 'consumer="a"' not in text, "departed label set must go"
    assert 'gubernator_memledger_bytes{consumer="b"} 7.0' in text


def test_memledger_cli_and_debug_endpoint(capsys):
    """`GET /debug/memory?advise=1` and `guber-cli debug memory` over a
    live daemon: the fourth debug plane round-trips, and deep health
    carries the memory block."""
    import json
    import urllib.request

    from gubernator_tpu.cmd.cli import main
    from gubernator_tpu.config import DaemonConfig
    from gubernator_tpu.daemon import spawn_daemon
    from gubernator_tpu.netutil import free_port

    d = spawn_daemon(DaemonConfig(
        grpc_listen_address=f"127.0.0.1:{free_port()}",
        http_listen_address=f"127.0.0.1:{free_port()}",
        cache_size=1 << 10), mesh=make_mesh(n=1))
    try:
        base = f"http://127.0.0.1:{d.http_port}"
        with urllib.request.urlopen(f"{base}/debug/memory?advise=1",
                                    timeout=10) as r:
            body = json.loads(r.read())
        assert body["enabled"] is True
        assert "hot_table" in body["consumers"]
        assert body["device_bytes"] > 0
        assert "advise" in body and "advised" in body["advise"]
        with urllib.request.urlopen(f"{base}/healthz?deep=1",
                                    timeout=10) as r:
            deep = json.loads(r.read())
        assert deep["memory"]["device_bytes"] == body["device_bytes"]
        assert main(["debug", "memory", "--url", base,
                     "--advise"]) == 0
        out = capsys.readouterr().out
        assert "hot_table" in out and "advised" in out
    finally:
        d.close()
