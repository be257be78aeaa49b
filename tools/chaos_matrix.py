"""Chaos matrix (ISSUE 5): every faultpoint × {error, delay} against a
live in-proc cluster, with a JSON verdict table.

For each cell the matrix arms ONE faultpoint on daemon 0 of a 2-daemon
loopback cluster (snapshot/restore run against a solo MockLoader
instance), drives the code path that owns the point, and classifies the
outcome:

- ``served``            the operation completed with clean rows
- ``served_degraded``   completed, rows carry the degraded flag
- ``error_rows``        completed, rows carry error text (visible, loud)
- ``raised``            the operation raised ``FaultInjected`` (loud)
- ``aborted_tick``      an async tick saw the fault and aborted safely
- ``not_reached``       the armed point never fired on this host
                        (e.g. ``dispatch_sync`` without a pipelined
                        engine) — recorded, not counted as failure
- ``hung``              the operation exceeded its wall bound — FAILURE

A cell passes (``ok``) when it did not hang and a clean probe call
succeeds after the fault is cleared (recovery).  The point of the
matrix is the invariant the resilience layer promises: an injected
fault may degrade or fail loudly, but may never wedge the daemon or
leave it broken after the fault clears.

Usage::

    python tools/chaos_matrix.py [--json out.json] [--verbose]
    make chaos

The full matrix additionally runs the SLO breach→recover cells
(ISSUE 11): a sustained ``global_psum`` delay must latch a
``global_staleness`` breach and clear it after repair, and sustained
``peer_send`` faults must do the same for ``error_ratio`` — the chaos
proof that the burn-rate plane sees what the fault plane injects.

Exit 0 when every exercised cell is ok; 1 otherwise.  Tier-1-safe:
in-proc daemons, loopback only, a few seconds of wall time
(tests/test_resilience.py runs a smoke of the same harness).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

DAY = 24 * 3_600_000
NOW0 = 1_780_000_000_000
WALL_S = 30.0  # per-cell bound: anything slower than this is a hang


def _serialize(reqs):
    from gubernator_tpu.proto import gubernator_pb2 as pb

    msg = pb.GetRateLimitsReq()
    for r in reqs:
        m = msg.requests.add()
        for f in ("name", "unique_key", "hits", "limit", "duration",
                  "burst"):
            setattr(m, f, getattr(r, f))
        m.algorithm = int(r.algorithm)
        m.behavior = int(r.behavior)
    return msg.SerializeToString()


def _one(key, hits=1, behavior=0):
    from gubernator_tpu.types import RateLimitRequest

    return _serialize([RateLimitRequest(
        name="chaos", unique_key=key, hits=hits, limit=10 ** 6,
        duration=DAY, behavior=behavior)])


class _Ctx:
    """The live fixture the drivers run against."""

    def __init__(self):
        from gubernator_tpu import cluster as cluster_mod
        from gubernator_tpu.config import BehaviorConfig

        self.c = cluster_mod.start(2, behaviors=BehaviorConfig(
            batch_timeout_ms=300, batch_wait_ms=50,
            peer_retry_limit=1, peer_retry_backoff_ms=5,
            peer_circuit_threshold=2, peer_circuit_cooldown_ms=200,
            global_sync_wait_ms=50))
        self.i0 = self.c.instance_at(0)
        self.addr1 = self.c.peer_at(1).grpc_address
        # a key owned by daemon 1 (remote from daemon 0's view) and one
        # owned by daemon 0
        self.remote_key = self.local_key = None
        for i in range(200):
            k = f"ck{i}"
            owner = self.c.owner_daemon_of("chaos_" + k)
            if owner is self.c.daemon_at(1) and self.remote_key is None:
                self.remote_key = k
            if owner is self.c.daemon_at(0) and self.local_key is None:
                self.local_key = k
            if self.remote_key and self.local_key:
                break
        assert self.remote_key and self.local_key
        # solo instance with a MockLoader for snapshot/restore points
        from gubernator_tpu.config import Config
        from gubernator_tpu.instance import V1Instance
        from gubernator_tpu.store import MockLoader

        cfg = Config(behaviors=BehaviorConfig())
        cfg.loader = MockLoader()
        self.solo = V1Instance(cfg)
        # solo mesh-mode instance (ISSUE 7): the collective reconcile
        # faultpoints (global_psum / global_accum_swap) live on its
        # GlobalManager tick
        self.mesh = V1Instance(Config(
            global_mode="mesh",
            behaviors=BehaviorConfig(global_sync_wait_ms=50)))
        # solo tiered instance (ISSUE 10): a device table capped far
        # below the keyspace so the cold tier and its migration
        # faultpoints (tier_promote / tier_demote) see real traffic
        # (1024 rows is the engine's per-shard floor — hence n=1)
        from gubernator_tpu.parallel import make_mesh

        self.tier = V1Instance(Config(
            cache_size=1024, cache_autogrow_max=1024, tier_cold=True,
            tier_promote_threshold=2, behaviors=BehaviorConfig()),
            mesh=make_mesh(n=1))
        self.tier_hits = {}  # unique_key → hits issued (conservation)
        self.tier_cell = 0  # fresh key namespace per driven cell

    def close(self):
        try:
            self.tier.close()
        finally:
            try:
                self.mesh.close()
            finally:
                try:
                    self.solo.close()
                finally:
                    self.c.stop()


def _classify_rows(data: bytes) -> str:
    from gubernator_tpu.proto import gubernator_pb2 as pb

    out = pb.GetRateLimitsResp.FromString(data)
    if any(r.error for r in out.responses):
        return "error_rows"
    if any(r.metadata.get("degraded") == "true" for r in out.responses):
        return "served_degraded"
    return "served"


# ---- drivers: one per faultpoint -------------------------------------------
# each returns an outcome string; FaultInjected escaping is normalized
# to "raised" by the harness


def _drive_forward(ctx: _Ctx) -> str:
    """peer_send / peer_recv / peer_circuit: a client batch whose key
    the ring owns remotely — the forward path."""
    return _classify_rows(ctx.i0.get_rate_limits_wire(
        _one(ctx.remote_key), now_ms=NOW0))


def _drive_ingest(ctx: _Ctx) -> str:
    return _classify_rows(ctx.i0.get_rate_limits_wire(
        _one(ctx.local_key), now_ms=NOW0))


def _drive_dispatch(ctx: _Ctx) -> str:
    """dispatch_enqueue / dispatch_launch / dispatch_sync /
    device_step: a local batch through the dispatcher's queue and
    worker, on a thread of its own so that a hang is a verdict."""
    box = {}

    def call():
        try:
            box["out"] = _classify_rows(ctx.i0.get_rate_limits_wire(
                _one(ctx.local_key), now_ms=NOW0))
        except BaseException as e:  # noqa: BLE001 - classified by harness
            box["err"] = e

    th = threading.Thread(target=call)
    th.start()
    th.join(WALL_S)
    if th.is_alive():
        return "hung"
    if "err" in box:
        raise box["err"]
    return box["out"]


def _drive_global(loop_attr: str):
    def drive(ctx: _Ctx) -> str:
        from gubernator_tpu.types import Behavior

        # queue GLOBAL work on daemon 0 (owner side for local_key,
        # non-owner for remote_key), then force the tick
        ctx.i0.get_rate_limits_wire(
            _one(ctx.local_key, behavior=int(Behavior.GLOBAL)),
            now_ms=NOW0)
        ctx.i0.get_rate_limits_wire(
            _one(ctx.remote_key, behavior=int(Behavior.GLOBAL)),
            now_ms=NOW0)
        gm = ctx.i0.global_manager
        before = ctx.i0.faults.describe()
        fired0 = sum(p["fired"] for p in before["points"])
        getattr(gm, loop_attr).poke()
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            now = sum(p["fired"]
                      for p in ctx.i0.faults.describe()["points"])
            if now > fired0:
                return "aborted_tick"
            time.sleep(0.02)
        return "served"  # tick ran without reaching the point

    return drive


def _drive_mesh(ctx: _Ctx) -> str:
    """global_psum / global_accum_swap (ISSUE 7): GLOBAL traffic on the
    solo mesh-mode instance, then force the reconcile tick.  An error
    at either point aborts the tick with the accumulators intact
    (swap-back); ``_mesh_probe`` re-verifies exact conservation after
    the harness clears the fault."""
    from gubernator_tpu.types import Behavior

    inst = ctx.mesh
    inst.get_rate_limits_wire(
        _one("meshkey", behavior=int(Behavior.GLOBAL)), now_ms=NOW0)
    fired0 = sum(p["fired"] for p in inst.faults.describe()["points"])
    inst.global_manager.poke()
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        if sum(p["fired"]
               for p in inst.faults.describe()["points"]) > fired0:
            return "aborted_tick"
        time.sleep(0.02)
    return "served"  # tick ran without reaching the point


def _mesh_probe(ctx: _Ctx) -> bool:
    """Post-clear recovery for the mesh cells: one clean reconcile
    tick must fold EVERY accumulated hit — folded == injected is the
    conservation oracle the collective path promises even after an
    injected swap/psum failure (nothing stranded, nothing doubled)."""
    inst = ctx.mesh
    try:
        inst._mesh_reconcile_tick()
        mge = inst._meshglobal
        if mge is None:
            return False
        mge.drain()
        return mge.folded_hits == mge.injected_hits
    except Exception:  # noqa: BLE001 - a raising probe is a failure
        return False


def _drive_mr(ctx: _Ctx) -> str:
    """mr_sync (ISSUE 7 satellite): multiregion reconciliation had
    zero fault coverage.  Queue MR hits, force the tick; an ERROR
    fault aborts BEFORE the queues pop, so the aggregate must survive
    intact (the conservation assertion) — a DELAY fault lets the tick
    proceed and consume the queue normally."""
    from gubernator_tpu.types import Behavior, RateLimitRequest

    inst = ctx.i0
    mr = inst._ensure_mr_manager()
    mr.queue_hits(RateLimitRequest(
        name="chaos", unique_key="mrkey", hits=7, limit=10 ** 6,
        duration=DAY, behavior=Behavior.MULTI_REGION))
    fired0 = sum(p["fired"] for p in inst.faults.describe()["points"])
    mr.poke()
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        if sum(p["fired"]
               for p in inst.faults.describe()["points"]) > fired0:
            time.sleep(0.1)  # let the tick finish either way
            with mr._mu:
                kept = {k: acc for k, (_r, acc, _s) in mr._hits.items()}
            if not kept:
                return "served"  # delay mode: flushed normally
            if kept.get("chaos_mrkey") != 7:
                # popped-but-partial would be a conservation loss
                return f"unexpected:queue_lost {kept}"
            return "aborted_tick"
        time.sleep(0.02)
    return "served"


def _drive_snapshot(ctx: _Ctx) -> str:
    ctx.solo.get_rate_limits_wire(_one("snapkey"), now_ms=NOW0)
    ctx.solo._save_to_loader()
    return "served"


def _drive_restore(ctx: _Ctx) -> str:
    ctx.solo._load_from_loader()
    return "served"


def _drive_tier(ctx: _Ctx) -> str:
    """tier_promote / tier_demote (ISSUE 10): overflow the 1024-row
    device table with a cell-fresh keyspace so keys land in the cold
    tier, then hammer a band of cold keys past the admission
    threshold — every promotion (and the demotion it triggers on the
    full table) crosses the armed faultpoint.  An ERROR fault must
    abort the migration cleanly: the row stays in its source tier and
    serving continues without error rows."""
    from gubernator_tpu.hashing import hash_key
    from gubernator_tpu.types import RateLimitRequest

    ctx.tier_cell += 1
    ns = f"t{ctx.tier_cell}k"

    def hit(key, hits=1):
        ctx.tier_hits[key] = ctx.tier_hits.get(key, 0) + hits
        return RateLimitRequest(name="chaos", unique_key=key, hits=hits,
                                limit=10 ** 6, duration=DAY)

    inst = ctx.tier
    for base in range(0, 2048, 512):
        out = inst.get_rate_limits(
            [hit(f"{ns}{i}") for i in range(base, base + 512)],
            now_ms=NOW0)
        if any(r.error for r in out):
            return "error_rows"
    cold = [i for i in range(2048) if inst._tier.peek_row(
        hash_key("chaos", f"{ns}{i}")) is not None][:8]
    if not cold:
        return "unexpected:no_cold_rows"
    for _ in range(6):  # past the threshold → promote (+ demote)
        out = inst.get_rate_limits([hit(f"{ns}{i}") for i in cold],
                                   now_ms=NOW0)
        if any(r.error for r in out):
            return "error_rows"
        time.sleep(0.1)  # let the async rank feed fold the wave
    return "served"


def _tier_probe(ctx: _Ctx) -> bool:
    """Post-fault oracle for the tier cells: EXACT conservation across
    every key ever driven, wherever its row now lives (device or cold,
    including rows whose migration the fault aborted mid-flight)."""
    from gubernator_tpu.types import RateLimitRequest

    for k, n in ctx.tier_hits.items():
        r = ctx.tier.get_rate_limits([RateLimitRequest(
            name="chaos", unique_key=k, hits=0, limit=10 ** 6,
            duration=DAY)], now_ms=NOW0)[0]
        if r.error or r.remaining != 10 ** 6 - n:
            return False
    return True


def _probe(ctx: _Ctx) -> bool:
    """Clean-path probe after clearing a fault: both a local and a
    forwarded row must serve without error rows."""
    try:
        a = _classify_rows(ctx.i0.get_rate_limits_wire(
            _one(ctx.local_key, hits=0), now_ms=NOW0 + 5_000))
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            b = _classify_rows(ctx.i0.get_rate_limits_wire(
                _one(ctx.remote_key, hits=0), now_ms=NOW0 + 5_000))
            if a == "served" and b == "served":
                return True
            time.sleep(0.1)  # circuit cooldown / readmit settling
        return False
    except Exception:  # noqa: BLE001 - a raising probe is a failure
        return False


#: point → (driver, where to arm: "cluster" daemon-0 instance or "solo")
MATRIX = {
    "peer_send": (_drive_forward, "cluster"),
    "peer_recv": (_drive_forward, "cluster"),
    "peer_circuit": (_drive_forward, "cluster"),
    "dispatch_enqueue": (_drive_dispatch, "cluster"),
    "dispatch_launch": (_drive_dispatch, "cluster"),
    "dispatch_sync": (_drive_dispatch, "cluster"),
    # the racer's preemption points (ISSUE 6): exercised by the same
    # dispatch driver — error fails the wave's callers, delay widens
    # the merge/carry/splice windows (tools/racer.py leans on these)
    "dispatch_merge": (_drive_dispatch, "cluster"),
    "dispatch_carry": (_drive_dispatch, "cluster"),
    "dispatch_splice": (_drive_dispatch, "cluster"),
    "device_step": (_drive_dispatch, "cluster"),
    "wire_ingest": (_drive_ingest, "cluster"),
    "global_broadcast": (_drive_global("_bcast_loop"), "cluster"),
    "global_hits": (_drive_global("_hits_loop"), "cluster"),
    # mesh-GLOBAL collective reconcile (ISSUE 7): armed on the solo
    # mesh-mode instance; each cell re-verifies exact conservation
    # after the fault clears
    "global_psum": (_drive_mesh, "mesh"),
    "global_accum_swap": (_drive_mesh, "mesh"),
    # multiregion reconciliation (ISSUE 7 satellite: ROADMAP flagged
    # zero fault coverage) — abort-before-pop keeps the queue intact
    "mr_sync": (_drive_mr, "cluster"),
    "snapshot": (_drive_snapshot, "solo"),
    "restore": (_drive_restore, "solo"),
    # tiered key store (ISSUE 10): armed on the capped solo instance;
    # the probe re-verifies exact conservation over every key driven
    "tier_promote": (_drive_tier, "tier"),
    "tier_demote": (_drive_tier, "tier"),
}

MODES = ("error", "delay")


# ---- SLO breach→recover cells (ISSUE 11) -----------------------------------
# The point×mode matrix proves a fault can't wedge the daemon; these
# cells prove the SLO plane SEES a sustained fault and un-sees its
# repair: the burn-rate engine must latch a breach while the fault
# holds and emit the matching recovery once it clears.  Run on the
# full matrix only (`make chaos`) — they cost real wall time (burn
# windows are wall-clock even at the 1s/2s chaos settings).

#: wall-clock window overrides for the SLO cells: tight enough that a
#: breach latches within a couple of folds and recovery within ~2 s
_SLO_ENV = {"GUBER_SLO_FAST": "1s", "GUBER_SLO_SLOW": "2s",
            "GUBER_SLO_TICK": "100ms", "GUBER_SLO_P99_MS": "60000"}


def _slo_events(inst, kind: str, slo: str) -> bool:
    return any(e.get("kind") == kind and e.get("slo") == slo
               for e in inst.recorder.events())


def _slo_staleness_cell() -> dict:
    """global_psum:delay → mesh-GLOBAL folds run late → measured
    coherence staleness exceeds 2× the reconcile interval →
    ``global_staleness`` breaches; clearing the fault and folding
    cleanly must emit ``slo_recovered``."""
    from gubernator_tpu.config import BehaviorConfig, Config
    from gubernator_tpu.instance import V1Instance
    from gubernator_tpu.types import Behavior

    spec = "global_psum:delay:400ms"
    cell = {"cell": "slo_staleness", "slo": "global_staleness",
            "spec": spec}
    t0 = time.perf_counter()
    inst = V1Instance(Config(
        global_mode="mesh",
        behaviors=BehaviorConfig(global_sync_wait_ms=100)))
    try:
        def drive():
            inst.get_rate_limits_wire(_one(
                "slokey", behavior=int(Behavior.GLOBAL)), now_ms=NOW0)
            inst._mesh_reconcile_tick()
            inst.slo.tick()

        drive()  # clean fold: the healthy baseline sample
        inst.faults.arm(spec, seed=7)
        deadline = time.monotonic() + 15.0
        breached = False
        while time.monotonic() < deadline and not breached:
            drive()  # each fold lands ≥400ms stale (target: 200ms)
            breached = _slo_events(inst, "slo_breach",
                                   "global_staleness")
        inst.faults.clear()
        recovered = False
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline and breached and not recovered:
            drive()  # clean folds: staleness back under target
            recovered = _slo_events(inst, "slo_recovered",
                                    "global_staleness")
            time.sleep(0.1)  # let the bad ticks age out of the window
    finally:
        inst.close()
    cell.update({"breached": breached, "recovered": recovered,
                 "elapsed_ms": round((time.perf_counter() - t0) * 1000,
                                     1),
                 "ok": breached and recovered})
    return cell


def _slo_error_ratio_cell() -> dict:
    """peer_send:error → every forwarded row degrades (or errors) →
    ``error_ratio`` burns past threshold and breaches; clearing the
    fault and serving clean traffic must emit ``slo_recovered``.
    The driven requests run under a trace context, so the degraded
    outcomes force-sample and the breach event must carry an
    ``exemplar_trace`` (ISSUE 12: page → waterfall in one hop)."""
    from gubernator_tpu import cluster as cluster_mod
    from gubernator_tpu.config import BehaviorConfig
    from gubernator_tpu.tracing import request_context

    spec = "peer_send:error"
    cell = {"cell": "slo_error_ratio", "slo": "error_ratio",
            "spec": spec}
    t0 = time.perf_counter()
    c = cluster_mod.start(2, behaviors=BehaviorConfig(
        batch_timeout_ms=300, batch_wait_ms=50,
        peer_retry_limit=1, peer_retry_backoff_ms=5,
        peer_circuit_threshold=2, peer_circuit_cooldown_ms=200))
    try:
        i0 = c.instance_at(0)
        remote = local = None
        for i in range(200):
            k = f"sk{i}"
            owner = c.owner_daemon_of("chaos_" + k)
            if owner is c.daemon_at(1) and remote is None:
                remote = k
            if owner is c.daemon_at(0) and local is None:
                local = k
            if remote and local:
                break
        ana = i0.dispatcher.analytics

        def drive(key):
            with request_context(None, recorder=i0.span_recorder):
                i0.get_rate_limits_wire(_one(key), now_ms=NOW0)
            if ana is not None:
                ana.flush(timeout=2.0)  # land the RED taps
            i0.slo.tick()

        drive(local)  # clean baseline sample
        i0.faults.arm(spec, seed=7)
        deadline = time.monotonic() + 15.0
        breached = False
        while time.monotonic() < deadline and not breached:
            drive(remote)  # forwarded row degrades/errors
            breached = _slo_events(i0, "slo_breach", "error_ratio")
        i0.faults.clear()
        recovered = False
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline and breached and not recovered:
            drive(local)  # clean rows dilute + age out the window
            recovered = _slo_events(i0, "slo_recovered", "error_ratio")
            time.sleep(0.1)
        exemplar = any(
            e.get("kind") == "slo_breach"
            and e.get("slo") == "error_ratio"
            and e.get("exemplar_trace")
            for e in i0.recorder.events())
    finally:
        c.stop()
    cell.update({"breached": breached, "recovered": recovered,
                 "exemplar": exemplar,
                 "elapsed_ms": round((time.perf_counter() - t0) * 1000,
                                     1),
                 "ok": breached and recovered and exemplar})
    return cell


def _memory_pressure_cell() -> dict:
    """tier churn against a capped hot table → byte-weighted occupancy
    climbs past GUBER_MEM_PRESSURE → ``hbm_pressure`` breaches while
    the rows are live, with the breach carrying an ``exemplar_trace``
    (the driven churn runs sampled, ISSUE 12 wiring); sweeping the
    expired churn keys drains occupancy and the engine must emit the
    matching ``slo_recovered`` (ISSUE 13)."""
    from gubernator_tpu.config import Config
    from gubernator_tpu.instance import V1Instance
    from gubernator_tpu.tracing import request_context
    from gubernator_tpu.types import RateLimitRequest

    cell = {"cell": "memory_pressure", "slo": "hbm_pressure",
            "spec": "tier_churn_vs_4k_cap"}
    t0 = time.perf_counter()
    # a target the churn phase clears decisively even where probe
    # exhaustion tops the open-addressed table out below 100% load
    prev = os.environ.get("GUBER_MEM_PRESSURE")
    os.environ["GUBER_MEM_PRESSURE"] = "0.6"
    try:
        inst = V1Instance(Config(
            cache_size=4096, cache_autogrow_max=4096,
            tier_cold=True, tier_promote_threshold=2,
            sweep_interval_ms=0))
    finally:
        if prev is None:
            os.environ.pop("GUBER_MEM_PRESSURE", None)
        else:
            os.environ["GUBER_MEM_PRESSURE"] = prev
    try:
        inst.span_recorder.sample = 1.0  # every churn batch commits a
        # sampled trace, so the breach tick has an exemplar to link
        now = NOW0
        nkey = 0

        def churn(n=500):
            nonlocal now, nkey
            reqs = [RateLimitRequest(
                name="chaos", unique_key=f"mp{nkey + i}", hits=1,
                limit=10 ** 6, duration=30_000)
                for i in range(n)]
            nkey += n
            now += 1
            with request_context(None, recorder=inst.span_recorder):
                inst.get_rate_limits(reqs, now_ms=now)
            inst.slo.tick()

        churn(64)  # healthy baseline sample: occupancy well under target
        deadline = time.monotonic() + 15.0
        breached = False
        while time.monotonic() < deadline and not breached:
            churn()  # distinct 30s-lived keys: occupancy only climbs
            breached = _slo_events(inst, "slo_breach", "hbm_pressure")
        # relieve: everything driven above has expired; one sweep
        # reclaims the rows and occupancy collapses to ~zero
        now += 60_000
        recovered = False
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline and breached and not recovered:
            with inst._engine_mu:
                inst.engine.sweep(now)
            inst.slo.tick()
            recovered = _slo_events(inst, "slo_recovered",
                                    "hbm_pressure")
            time.sleep(0.1)  # let the bad ticks age out of the window
        exemplar = any(
            e.get("kind") == "slo_breach"
            and e.get("slo") == "hbm_pressure"
            and e.get("exemplar_trace")
            for e in inst.recorder.events())
        pressure, target = inst.memledger.pressure_sample()
    finally:
        inst.close()
    cell.update({"breached": breached, "recovered": recovered,
                 "exemplar": exemplar,
                 "final_pressure": round(pressure, 4), "target": target,
                 "elapsed_ms": round((time.perf_counter() - t0) * 1000,
                                     1),
                 "ok": breached and recovered and exemplar})
    return cell


def _trace_plane_cell() -> dict:
    """peer_send:error → the forwarded request serves degraded, its
    trace force-samples, and the CALLER-side slice still assembles
    end-to-end (request span → ``peer.forward`` hop → local degraded
    wave); after clearing the fault, a healthy forwarded request
    stitches ACROSS daemons — the owner's handler + wave spans hang
    under the caller's request span (ISSUE 12 acceptance shape)."""
    from gubernator_tpu import cluster as cluster_mod
    from gubernator_tpu.config import BehaviorConfig
    from gubernator_tpu.tracing import (assemble, current_trace_id,
                                        request_context, span)

    spec = "peer_send:error"
    cell = {"cell": "trace_plane", "spec": spec}
    t0 = time.perf_counter()
    c = cluster_mod.start(2, behaviors=BehaviorConfig(
        batch_timeout_ms=300, batch_wait_ms=50,
        peer_retry_limit=1, peer_retry_backoff_ms=5,
        peer_circuit_threshold=2, peer_circuit_cooldown_ms=200))
    try:
        i0, i1 = c.instance_at(0), c.instance_at(1)
        remote = None
        for i in range(200):
            k = f"tk{i}"
            if c.owner_daemon_of("chaos_" + k) is c.daemon_at(1):
                remote = k
                break
        assert remote
        r0, r1 = i0.span_recorder, i1.span_recorder
        old_sample = (r0.sample, r1.sample)
        r0.sample = r1.sample = 1.0

        def names(node, acc):
            acc.add(node["name"])
            for ch in node.get("children", []):
                names(ch, acc)
            return acc

        def drive():
            with request_context(None, recorder=r0):
                with span("grpc.GetRateLimits"):
                    tid = current_trace_id()
                    data = i0.get_rate_limits_wire(_one(remote),
                                                   now_ms=NOW0)
            return tid, _classify_rows(data)

        def assembled(tid, spans, want):
            traces = assemble(spans, trace_id=tid)
            if len(traces) != 1 or len(traces[0]["roots"]) != 1:
                return False  # still waiting on late wave spans
            root = traces[0]["roots"][0]
            return (root["name"] == "grpc.GetRateLimits"
                    and want <= names(root, set()))

        degraded_assembled = stitched = False
        try:
            i0.faults.arm(spec, seed=7)
            deadline = time.monotonic() + 15.0
            while (time.monotonic() < deadline
                   and not degraded_assembled):
                tid, outcome = drive()
                if outcome != "served_degraded":
                    continue
                # the degraded wave lands from the dispatcher thread;
                # poll until the caller slice holds the whole chain
                sub = time.monotonic() + 2.0
                while (time.monotonic() < sub
                       and not degraded_assembled):
                    degraded_assembled = assembled(
                        tid, r0.spans(),
                        {"peer.forward", "wave"})
                    if not degraded_assembled:
                        time.sleep(0.05)
            i0.faults.clear()
            deadline = time.monotonic() + 15.0
            while time.monotonic() < deadline and not stitched:
                time.sleep(0.25)  # let the peer circuit half-open
                tid, outcome = drive()
                if outcome != "served":
                    continue
                sub = time.monotonic() + 2.0
                while time.monotonic() < sub and not stitched:
                    stitched = assembled(
                        tid, r0.spans() + r1.spans(),
                        {"peer.forward", "grpc.GetPeerRateLimits",
                         "wave"})
                    if not stitched:
                        time.sleep(0.05)
        finally:
            r0.sample, r1.sample = old_sample
    finally:
        c.stop()
    cell.update({"degraded_assembled": degraded_assembled,
                 "stitched": stitched,
                 "elapsed_ms": round((time.perf_counter() - t0) * 1000,
                                     1),
                 "ok": degraded_assembled and stitched})
    return cell


def _fleet_conservation_cell() -> dict:
    """peer_send:error partition → GLOBAL flushes to the owner fail
    and requeue → the daemons' OWN audit vectors (instance.audit_doc,
    the same document GET /debug/audit serves — no test-harness
    walking) show nonzero fleet drift and the ``fleet_conservation``
    SLO breaches once the backlog outlives its flush-window bound;
    healing the partition must drain the drift to EXACTLY zero and
    emit ``slo_recovered`` (ISSUE 19 acceptance)."""
    from gubernator_tpu import cluster as cluster_mod
    from gubernator_tpu import fleet
    from gubernator_tpu.config import BehaviorConfig
    from gubernator_tpu.types import Behavior

    spec = "peer_send:error"
    cell = {"cell": "fleet_conservation", "slo": "fleet_conservation",
            "spec": spec}
    t0 = time.perf_counter()
    c = cluster_mod.start(3, behaviors=BehaviorConfig(
        batch_timeout_ms=300, batch_wait_ms=50,
        peer_retry_limit=1, peer_retry_backoff_ms=5,
        peer_circuit_threshold=2, peer_circuit_cooldown_ms=200,
        global_sync_wait_ms=100))
    try:
        i0 = c.instance_at(0)
        remote = None
        for i in range(200):
            k = f"fc{i}"
            if c.owner_daemon_of("chaos_" + k) is not c.daemon_at(0):
                remote = k
                break
        assert remote

        def fold():
            return fleet.fold_audits(
                [c.instance_at(i).audit_doc() for i in range(3)])

        def drive():
            i0.get_rate_limits_wire(_one(
                remote, behavior=int(Behavior.GLOBAL)), now_ms=NOW0)
            gm = i0.global_manager
            if gm is not None:
                gm.poke()
            i0.slo.tick()

        drive()  # clean baseline: flush lands, drift drains
        i0.faults.arm(spec, seed=7)
        deadline = time.monotonic() + 15.0
        drift_seen = breached = False
        while time.monotonic() < deadline \
                and not (drift_seen and breached):
            drive()  # flush fails → requeue → backlog holds nonzero
            drift_seen = drift_seen or fold()["drift"] > 0
            breached = _slo_events(i0, "slo_breach",
                                   "fleet_conservation")
            time.sleep(0.05)
        i0.faults.clear()
        recovered = drained = False
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline and breached \
                and not (recovered and drained):
            drive()  # circuit half-opens, flush lands, backlog drains
            f = fold()
            drained = f["conserved"] and f["totals"]["injected"] > 0
            recovered = _slo_events(i0, "slo_recovered",
                                    "fleet_conservation")
            time.sleep(0.1)
        final = fold()
    finally:
        c.stop()
    cell.update({"drift_seen": drift_seen, "breached": breached,
                 "recovered": recovered, "drained": drained,
                 "final_drift": final["drift"],
                 "elapsed_ms": round((time.perf_counter() - t0) * 1000,
                                     1),
                 "ok": (drift_seen and breached and recovered
                        and drained and final["drift"] == 0)})
    return cell


def _fleet_ring_divergence_cell() -> dict:
    """sustained peer_send:error holds a peer's circuit open past
    ``peer_eject_after_ms`` → the routing gate ejects it → the audit
    docs' ring views disagree (routing != membership) and the fleet
    watch emits ``fleet_ring_divergence``; clearing the fault lets the
    peer recover and readmit, and the watch must emit the matching
    ``fleet_ring_converged`` (ISSUE 19 satellite)."""
    from gubernator_tpu import cluster as cluster_mod
    from gubernator_tpu import fleet
    from gubernator_tpu.config import BehaviorConfig

    spec = "peer_send:error"
    cell = {"cell": "fleet_ring_divergence", "spec": spec}
    t0 = time.perf_counter()
    c = cluster_mod.start(2, behaviors=BehaviorConfig(
        batch_timeout_ms=300, batch_wait_ms=50,
        peer_retry_limit=1, peer_retry_backoff_ms=5,
        peer_circuit_threshold=2, peer_circuit_cooldown_ms=250,
        peer_eject_after_ms=300, peer_readmit_after_ms=250))
    try:
        i0 = c.instance_at(0)
        remote = None
        for i in range(200):
            k = f"rd{i}"
            if c.owner_daemon_of("chaos_" + k) is c.daemon_at(1):
                remote = k
                break
        assert remote
        watch = fleet.RingWatch()

        def check():
            # the fleet tick: fold the daemons' own ring views; the
            # watch records divergence/convergence edges into daemon
            # 0's flight recorder
            return watch.check(
                [c.instance_at(i).audit_doc() for i in range(2)],
                recorder=i0.recorder)

        def fired(kind):
            return any(e.get("kind") == kind
                       for e in i0.recorder.events())

        assert check()["consistent"]
        i0.faults.arm(spec, seed=7)
        deadline = time.monotonic() + 15.0
        diverged = False
        while time.monotonic() < deadline and not diverged:
            # forwarded traffic trips the circuit; routing lookups
            # derive the gated picker, ejecting the dead peer
            i0.get_rate_limits_wire(_one(remote), now_ms=NOW0)
            diverged = not check()["consistent"] \
                and fired("fleet_ring_divergence")
            time.sleep(0.05)
        i0.faults.clear()
        converged = False
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline and diverged and not converged:
            # light traffic half-opens the circuit; once recovered
            # past readmit the gate clears and the views re-agree
            i0.get_rate_limits_wire(_one(remote), now_ms=NOW0)
            converged = check()["consistent"] \
                and fired("fleet_ring_converged")
            time.sleep(0.1)
    finally:
        c.stop()
    cell.update({"diverged": diverged, "converged": converged,
                 "elapsed_ms": round((time.perf_counter() - t0) * 1000,
                                     1),
                 "ok": diverged and converged})
    return cell


def run_slo_cells(verbose=False) -> list:
    old = {k: os.environ.get(k) for k in _SLO_ENV}
    os.environ.update(_SLO_ENV)
    cells = []
    try:
        for fn in (_slo_staleness_cell, _slo_error_ratio_cell,
                   _memory_pressure_cell, _trace_plane_cell,
                   _fleet_conservation_cell,
                   _fleet_ring_divergence_cell):
            cell = fn()
            cells.append(cell)
            if verbose:
                print(json.dumps(cell), file=sys.stderr)
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return cells


def run_scenario_cells(verbose=False) -> list:
    """Generated cells from the scenario lab (ISSUE 16): every spec in
    the committed library runs in fast mode and contributes one cell —
    its oracle verdicts ARE the cell verdict.  Adding a scenario file
    grows the chaos matrix with no code here."""
    from gubernator_tpu import scenarios as scn

    cells = []
    for spec in scn.load_library():
        try:
            row = scn.ScenarioRunner(spec, fast=True).run(fast=True)
            cell = {"cell": f"scenario:{spec.name}",
                    "stack": row["stack"], "ok": row["ok"],
                    "requests": row["requests"],
                    "error_rows": row["error_rows"],
                    "oracles": {k: v["ok"]
                                for k, v in row["oracles"].items()}}
        except Exception as e:  # noqa: BLE001 - recorded verdict
            cell = {"cell": f"scenario:{spec.name}", "ok": False,
                    "error": (str(e) or repr(e))[:200]}
        cells.append(cell)
        if verbose:
            print(json.dumps(cell), file=sys.stderr)
    return cells


def run_matrix(points=None, verbose=False) -> dict:
    from gubernator_tpu.faults import FAULT_POINTS, FaultInjected

    missing = set(FAULT_POINTS) - set(MATRIX)
    assert not missing, f"faultpoints without a matrix driver: {missing}"
    ctx = _Ctx()
    cells = []
    try:
        for point, (driver, where) in MATRIX.items():
            if points and point not in points:
                continue
            inst = {"solo": ctx.solo, "mesh": ctx.mesh,
                    "tier": ctx.tier}.get(where, ctx.i0)
            for mode in MODES:
                spec = (f"{point}:delay:5ms" if mode == "delay"
                        else f"{point}:error")
                inst.faults.arm(spec, seed=7)
                t0 = time.perf_counter()
                try:
                    outcome = driver(ctx)
                except FaultInjected:
                    outcome = "raised"
                except Exception as e:  # noqa: BLE001 - recorded verdict
                    outcome = f"unexpected:{type(e).__name__}"
                elapsed = time.perf_counter() - t0
                fired = sum(p["fired"]
                            for p in inst.faults.describe()["points"])
                inst.faults.clear()
                if fired == 0:
                    outcome = "not_reached"
                if where == "cluster":
                    recovered = _probe(ctx)
                elif where == "mesh":
                    recovered = _mesh_probe(ctx)
                elif where == "tier":
                    recovered = _tier_probe(ctx)
                else:
                    recovered = True
                ok = (outcome != "hung"
                      and not outcome.startswith("unexpected")
                      and recovered)
                cell = {"point": point, "mode": mode, "spec": spec,
                        "outcome": outcome, "fired": fired,
                        "elapsed_ms": round(elapsed * 1000, 1),
                        "recovered": recovered, "ok": ok}
                cells.append(cell)
                if verbose:
                    print(json.dumps(cell), file=sys.stderr)
    finally:
        ctx.close()
    # SLO breach→recover cells and generated scenario cells ride the
    # FULL matrix only (`make chaos`): a --point / smoke subset stays
    # fast
    slo_cells = run_slo_cells(verbose=verbose) if not points else []
    scenario_cells = (run_scenario_cells(verbose=verbose)
                      if not points else [])
    exercised = [c for c in cells if c["outcome"] != "not_reached"]
    return {
        "cells": cells,
        "slo_cells": slo_cells,
        "scenario_cells": scenario_cells,
        "exercised": len(exercised),
        "not_reached": [f"{c['point']}:{c['mode']}" for c in cells
                        if c["outcome"] == "not_reached"],
        "failed": ([f"{c['point']}:{c['mode']}" for c in cells
                    if not c["ok"]]
                   + [c["cell"] for c in slo_cells if not c["ok"]]
                   + [c["cell"] for c in scenario_cells
                      if not c["ok"]]),
        "ok": (all(c["ok"] for c in cells)
               and all(c["ok"] for c in slo_cells)
               and all(c["ok"] for c in scenario_cells)),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="run the faultpoint × mode chaos matrix")
    ap.add_argument("--json", default=None,
                    help="also write the verdict table to this path")
    ap.add_argument("--point", action="append", default=None,
                    help="restrict to these faultpoints (repeatable)")
    ap.add_argument("--verbose", action="store_true",
                    help="stream per-cell verdicts to stderr")
    args = ap.parse_args(argv)
    verdict = run_matrix(points=args.point, verbose=args.verbose)
    doc = json.dumps(verdict, indent=2)
    print(doc)
    if args.json:
        with open(args.json, "w", encoding="utf-8") as f:
            f.write(doc + "\n")
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
