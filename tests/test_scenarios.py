"""Scenario lab (ISSUE 16): spec round-trips, schedule determinism,
per-stack smoke runs, oracle firing, and the clock-skew regression pin.

The determinism contract is the headline: the same spec + seed must
replay a byte-identical decision stream across two runs, and every
committed spec must serialize/round-trip losslessly.  The clock-skew
pin proves the PR-6 ``created_at`` first-hop-wins discipline END TO END
under the DSL: clients skewed ±5 s produce the same decision stream as
an unskewed twin — and flipping ``GUBER_CREATED_AT_FWD=0`` (the
pre-fix behavior) must break that equality, or the test pins nothing.
"""
import copy
import json
import os

import pytest

from gubernator_tpu import scenarios as scn
from gubernator_tpu.scenarios import (
    DecisionDigest,
    JudgeTap,
    ScenarioRunner,
    ScenarioSpec,
    compile_schedule,
    jain_index,
)
from gubernator_tpu.types import RateLimitRequest, RateLimitResponse

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIB = os.path.join(REPO, "scenarios")


def _small(name="t", stack="object", **kw):
    kw.setdefault("seed", 9)
    kw.setdefault("ticks", 3)
    kw.setdefault("tick_ms", 250)
    kw.setdefault("clients", 2)
    kw.setdefault("sources", [
        {"kind": "zipf_drift", "name": "sm", "rows": 12, "n_keys": 10,
         "a0": 1.3, "a1": 1.8, "limit": 5000, "duration": 3_600_000}])
    kw.setdefault("oracles", ["parity", "conservation"])
    return ScenarioSpec(name=name, stack=stack, **kw)


# ---------------------------------------------------------------------------
# DSL: serialization, validation, schedule determinism


def test_spec_roundtrip_lossless():
    spec = _small(skew_ms=[-5, 5], expect={"jain_min": 0.2},
                  faults=[{"at_tick": 1, "arm": "device_step:error",
                           "seed": 3}],
                  fast={"ticks": 2, "rows_scale": 0.5})
    d = spec.to_dict()
    again = ScenarioSpec.from_dict(copy.deepcopy(d))
    assert again == spec
    assert again.to_dict() == d
    # JSON round trip too (what save_spec/load_spec do)
    assert ScenarioSpec.from_dict(
        json.loads(json.dumps(d))).to_dict() == d


def test_library_specs_load_validate_and_roundtrip():
    """Every committed spec parses, validates, compiles, and
    round-trips byte-losslessly — the spec library is the payload."""
    names = set()
    files = [f for f in sorted(os.listdir(LIB)) if f.endswith(".json")]
    assert len(files) >= 7, files
    stacks = set()
    for fn in files:
        with open(os.path.join(LIB, fn)) as f:
            raw = json.load(f)
        spec = ScenarioSpec.from_dict(raw)
        assert spec.to_dict() == raw, f"{fn} does not round-trip"
        names.add(spec.name)
        stacks.add(spec.stack)
        fast = spec.with_fast()
        sched = compile_schedule(fast)
        assert len(sched) == fast.ticks
        assert any(any(c for c in tick) for tick in sched), \
            f"{fn} compiles to an empty schedule"
    assert len(names) == len(files), "duplicate scenario names"
    assert stacks == set(scn.STACKS), \
        f"library must cover every stack class, got {stacks}"


def test_spec_validation_rejects_unknowns():
    with pytest.raises(ValueError, match="unknown scenario keys"):
        ScenarioSpec.from_dict({"name": "x", "bogus": 1})
    with pytest.raises(ValueError, match="unknown stack"):
        _small(stack="warp").validate()
    with pytest.raises(ValueError, match="unknown source kind"):
        ScenarioSpec(name="x", sources=[{"kind": "nope"}]).validate()
    with pytest.raises(ValueError, match="unknown oracle"):
        ScenarioSpec(name="x", oracles=["vibes"]).validate()
    with pytest.raises(ValueError, match="one offset per client"):
        ScenarioSpec(name="x", clients=3, skew_ms=[1]).validate()
    with pytest.raises(ValueError, match="schema"):
        ScenarioSpec.from_dict({"schema": 99, "name": "x"})


def test_schedule_is_deterministic_and_seed_sensitive():
    spec = _small()
    a = compile_schedule(spec)
    b = compile_schedule(spec)
    assert a == b  # RateLimitRequest is a frozen-enough dataclass: ==
    c = compile_schedule(ScenarioSpec.from_dict(
        {**spec.to_dict(), "seed": spec.seed + 1}))
    assert a != c, "seed must steer the schedule"


def test_sources_shape_traffic():
    """Each primitive leaves its fingerprint on the compiled rows."""
    # flash crowd: celebrity rows only inside the window
    spec = _small(sources=[{"kind": "flash_crowd", "name": "f",
                            "rows": 4, "n_keys": 50,
                            "celebrity": "star", "start_tick": 1,
                            "stop_tick": 2, "crowd_rows": 9}],
                  ticks=3)
    sched = compile_schedule(spec)
    per_tick = [sum(1 for c in tick for r in c
                    if r.unique_key == "star") for tick in sched]
    assert per_tick[0] == 0 and per_tick[1] == 9 and per_tick[2] == 0
    # tenant mix: ~90/10 split lands on tenant-prefixed names
    spec = _small(sources=[{"kind": "tenant_mix", "name": "api",
                            "rows": 200, "tenants": [
                                {"tenant": "hog", "weight": 90,
                                 "n_keys": 3},
                                {"tenant": "tiny", "weight": 10,
                                 "n_keys": 3}]}], ticks=1)
    rows = [r for c in compile_schedule(spec)[0] for r in c]
    hog = sum(1 for r in rows if r.name.startswith("hog/"))
    assert 150 < hog < 200 and len(rows) == 200
    # diurnal: volume varies across the period
    spec = _small(sources=[{"kind": "diurnal", "rows": 20,
                            "period_ticks": 4, "amplitude": 0.9,
                            "n_keys": 5}], ticks=4)
    vols = [sum(len(c) for c in tick)
            for tick in compile_schedule(spec)]
    assert max(vols) > min(vols)


def test_jain_index_bounds():
    assert jain_index([5, 5, 5, 5]) == pytest.approx(1.0)
    assert jain_index([100, 0, 0, 0]) == pytest.approx(1.0)  # 1 active
    assert jain_index([97, 1, 1, 1]) < 0.3
    assert jain_index([]) == 1.0


def test_judge_tap_retains_then_attributes():
    j = JudgeTap(delim="/")
    reqs = [RateLimitRequest(name="a/x", unique_key="k", hits=2,
                             limit=10, duration=1000),
            RateLimitRequest(name="b/x", unique_key="k", hits=1,
                             limit=10, duration=1000)]
    resps = [RateLimitResponse(status=0, limit=10, remaining=8,
                               reset_time=1),
             RateLimitResponse(status=1, limit=10, remaining=0,
                               reset_time=1)]
    j.observe(reqs, resps, 0)
    assert j.total == 0  # service-path half only retains
    j.finalize()
    assert j.total == 2 and j.over_limit == 1
    assert j.admitted == {"a/x_k": 2}
    assert j.tenants["a"]["admitted_hits"] == 2
    assert j.tenants["b"]["over_limit"] == 1
    d = DecisionDigest()
    d.update(0, 8, "")
    d.update(1, 0, "")
    assert j.digest.hex() == d.hex()
    j.finalize()  # idempotent
    assert j.total == 2


# ---------------------------------------------------------------------------
# runner: determinism + one small scenario per stack class


def test_run_replays_byte_identical_decision_stream():
    """Acceptance: same spec + seed -> byte-identical decision stream
    across two full runs (fresh stack each time)."""
    spec = _small(name="det")
    rows = [ScenarioRunner(spec).run() for _ in range(2)]
    assert rows[0]["decision_digest"] == rows[1]["decision_digest"]
    assert rows[0]["ok"] and rows[1]["ok"]
    assert rows[0]["requests"] == rows[1]["requests"] > 0


def test_smoke_object_stack_parity_and_conservation():
    row = ScenarioRunner(_small(name="sm_obj")).run()
    assert row["ok"], row
    assert row["oracles"]["parity"]["ok"]
    assert row["oracles"]["conservation"]["ok"]
    assert row["requests"] > 0 and row["error_rows"] == 0


def test_smoke_wire_stack():
    pytest.importorskip("gubernator_tpu.ops._native",
                        reason="wire lane needs the C++ codec")
    row = ScenarioRunner(_small(name="sm_wire", stack="wire")).run()
    assert row["ok"], row


def test_smoke_tiered_stack():
    row = ScenarioRunner(
        _small(name="sm_tier", stack="tiered",
               sources=[{"kind": "uniform", "name": "sm", "rows": 24,
                         "n_keys": 300, "limit": 5000,
                         "duration": 3_600_000}])).run()
    assert row["ok"], row


def test_smoke_mesh_stack():
    row = ScenarioRunner(
        _small(name="sm_mesh", stack="mesh",
               sources=[
                   {"kind": "uniform", "name": "g", "rows": 8,
                    "n_keys": 4, "behavior": "global",
                    "limit": 50_000, "duration": 3_600_000},
                   {"kind": "uniform", "name": "p", "rows": 8,
                    "n_keys": 6, "limit": 50_000,
                    "duration": 3_600_000}],
               oracles=["conservation"])).run()
    assert row["ok"], row


def test_smoke_clustered_stack_with_fairness():
    """Clustered smoke + the 90/10 fairness oracle firing for real:
    Jain's index lands in the unfair band and the run stays exact."""
    spec = _small(
        name="sm_clu", stack="clustered", clients=2, ticks=3,
        sources=[{"kind": "tenant_mix", "name": "api", "rows": 30,
                  "limit": 100_000, "duration": 3_600_000,
                  "tenants": [
                      {"tenant": "hog", "weight": 90, "n_keys": 3},
                      {"tenant": "t1", "weight": 5, "n_keys": 2},
                      {"tenant": "t2", "weight": 5, "n_keys": 2}]}],
        oracles=["conservation", "fairness"],
        expect={"jain_min": 0.05, "jain_max": 0.75})
    row = ScenarioRunner(spec).run(fast=True)
    assert row["ok"], row
    assert 0.0 < row["jain_index"] < 0.9
    assert row["oracles"]["conservation"]["ok"]


def test_fairness_oracle_exact_ledger_conservation():
    """Solo stack: the analytics plane's per-tenant (requests, hits)
    must equal the judge's own counts exactly."""
    spec = _small(
        name="fair", stack="object", ticks=4,
        sources=[{"kind": "tenant_mix", "name": "api", "rows": 40,
                  "limit": 100_000, "duration": 3_600_000,
                  "tenants": [
                      {"tenant": "abuser", "weight": 9, "n_keys": 4},
                      {"tenant": "meek", "weight": 1, "n_keys": 4}]}],
        oracles=["fairness"], expect={"jain_min": 0.1,
                                      "jain_max": 0.9})
    row = ScenarioRunner(spec).run()
    fair = row["oracles"]["fairness"]
    assert fair["ok"], fair
    assert fair["ledger_conserved"] is True
    assert fair["ledger_mismatches"] == []
    assert row["ok"], row


def test_partition_scenario_conserves_after_reconcile():
    """The committed partition spec (fast mode): hits admitted during
    the partition debit exactly once after the heal — the conservation
    oracle converges to zero mismatches."""
    spec = scn.load_spec(
        os.path.join(LIB, "partition_reconcile.json"))
    row = ScenarioRunner(spec, fast=True).run(fast=True)
    assert row["ok"], row
    cons = row["oracles"]["conservation"]
    assert cons["ok"] and cons["mismatches"] == []
    assert cons["keys"] > 0


def test_replay_capture_assembles_end_to_end():
    """The committed trace capture replays through a fresh cluster and
    the new run's spans assemble into stitched multi-span traces."""
    spec = scn.load_spec(os.path.join(LIB, "replay_trace.json"))
    row = ScenarioRunner(spec, fast=True).run(fast=True)
    assert row["ok"], row
    tr = row["oracles"]["trace_assembly"]
    assert tr["assembled"] >= 1 and tr["spans"] > 0


def test_scenario_events_and_metric_recorded():
    spec = _small(name="ev", oracles=[])
    runner = ScenarioRunner(spec)
    handle = runner._build()
    handle.close()
    row = runner.run()
    assert row["ok"]
    # the runner's own instance is closed; assert via a fresh run's
    # recorder by driving the pieces directly
    h = ScenarioRunner(_small(name="ev2", oracles=[]))._build()
    try:
        inst = h.instances[0]
        r = ScenarioRunner(_small(name="ev2", oracles=[]))
        judge = JudgeTap()
        r._drive(h, judge)
        inst.recorder.record("scenario_started", name="ev2")
        inst.recorder.record("scenario_finished", name="ev2", ok=True)
        kinds = {e["kind"] for e in inst.recorder.events()}
        assert {"scenario_started", "scenario_finished"} <= kinds
        inst.metrics.scenario_runs.labels(verdict="ok").inc()
    finally:
        h.close()


# ---------------------------------------------------------------------------
# clock-skew regression pin (satellite): created_at first-hop-wins


def _skew_spec(skew):
    return ScenarioSpec(
        name="skewpin", stack="clustered", seed=77, ticks=3,
        tick_ms=1000, clients=3, daemons=3, skew_ms=skew,
        sources=[{"kind": "zipf_drift", "name": "skw", "rows": 10,
                  "n_keys": 12, "a0": 1.4, "a1": 1.4, "limit": 5000,
                  "duration": 86_400_000}],
        oracles=[])


def test_clock_skew_decisions_byte_identical_to_unskewed():
    """±5 s client skew must not change a single decision: created_at
    rides the first hop, owners apply rows at the caller's time base,
    and token-bucket windows dwarf the skew."""
    skewed = ScenarioRunner(_skew_spec([-5000, 0, 5000])).run()
    unskewed = ScenarioRunner(_skew_spec([])).run()
    assert skewed["requests"] == unskewed["requests"] > 0
    assert skewed["error_rows"] == unskewed["error_rows"] == 0
    assert skewed["decision_digest"] == unskewed["decision_digest"]


def test_clock_skew_pin_is_sharp(monkeypatch):
    """GUBER_CREATED_AT_FWD=0 (the pre-PR-6 escape: owners stamp their
    own wall clock on forwarded rows) must BREAK the byte-identity —
    the owner's real clock sits years past the virtual NOW0, so every
    forwarded bucket expires on arrival and the decision stream
    visibly diverges.  If this stops failing, the pin above proves
    nothing.

    WHICH rows diverge depends on the ring, and the ring on the ports
    this run's daemons were given: a key breaks only when its first
    touch is its owner's own client (the lab's clock) and a later one
    is forwarded (the wall clock).  About one ring in four has no such
    key among the twelve, so the pin is given four rings to show one
    break; the unskewed digest is the same for every ring."""
    unskewed_digest = ScenarioRunner(_skew_spec([])).run()["decision_digest"]
    monkeypatch.setenv("GUBER_CREATED_AT_FWD", "0")
    digests = []
    for _ in range(4):
        skewed = ScenarioRunner(_skew_spec([-5000, 0, 5000])).run()
        digests.append(skewed["decision_digest"])
        if digests[-1] != unskewed_digest:
            break
    assert digests[-1] != unskewed_digest, digests
