"""The core instance: request routing over the device engine.

reference: gubernator.go › V1Instance{GetRateLimits, GetPeerRateLimits,
UpdatePeerGlobals, HealthCheck, SetPeers} — reconstructed, mount empty.

The hot path inverts the reference design (SURVEY.md §7.1): instead of a
per-request loop over a mutex-guarded LRU, all locally-owned requests in
a client batch execute as ONE device program (probe → gather →
branchless update → scatter) on the sharded HBM table.  Peer routing
(consistent hash over daemon processes) wraps around that device core
exactly like the reference wraps around its cache.
"""
from __future__ import annotations

import logging
import os
import threading
import time
from concurrent.futures import Future
from dataclasses import replace
from typing import Dict, List, Optional, Sequence

import numpy as np

from .config import Config
from .global_manager import GlobalManager
from .gregorian import gregorian_rate_duration_ms
from .hashing import hash_key
from .metrics import Metrics
from .multiregion import MultiRegionManager
from .peer_client import ErrClosing, PeerClient
from .peers import RegionPeerPicker, ReplicatedConsistentHash
from .telemetry import FlightRecorder, exc_text
from .tracing import phase
from .proto import gubernator_pb2 as pb
from .proto import peers_pb2 as peers_pb
from .store import CacheItem
from .types import (
    Algorithm,
    Behavior,
    HealthCheckResponse,
    MAX_BATCH_SIZE,
    PeerInfo,
    RateLimitRequest,
    RateLimitResponse,
    Status,
)

log = logging.getLogger("gubernator_tpu.instance")

#: empty boolean mask — the "no rows match" result when a behavior_or
#: gate proves a column scan unnecessary (.any() is False)
_NO_ROWS = np.zeros(0, bool)

try:  # C++ wire-ingest lane (ops/_native.cpp); optional
    from .ops import native as _wire_native
except ImportError:  # pragma: no cover - unbuilt extension
    _wire_native = None



def _created_at_fwd_enabled() -> bool:
    """GUBER_CREATED_AT_FWD=0 disables caller-clock forwarding (the
    created_at stamp on forwarded TLVs and deferred hit queues) —
    restoring the pre-fix behavior where every hop applies requests at
    its own wall clock.  Exists so tools/racer.py and the conservation
    regression tests can demonstrate the cold-key loss the stamp fixes;
    never disable it in production."""
    return os.environ.get("GUBER_CREATED_AT_FWD", "1") != "0"

def clock_ms() -> int:
    return time.time_ns() // 1_000_000  # clock-ok: the clock source itself


def _group_key_configs(kh: np.ndarray, idx: np.ndarray, batch,
                       pinned_cfg: dict):
    """Rows ``idx`` of a packed batch grouped by key in ONE pass over
    the call's rows, whatever the number of distinct keys
    (``_wire_mesh_runner``'s routing pass).

    Returns ``(keys, unpinned)`` — the key of each row as a Python int,
    in row order, and the distinct keys ``pinned_cfg`` does not hold,
    SORTED (pin order decides slot placement when probe windows
    collide) — or None where the object path must serve the batch per
    request: a key whose rows carry more than one config, or a pinned
    key whose config changed.

    A dict pass over ``tolist()`` columns, not ``np.unique`` and
    whole-column compares: 32 handlers route on one GIL, and a numpy
    call that gives it up (``np.unique``, reductions, casts, scatters
    — gathers and ``tolist()`` do not) has to win it back.  The array
    version took 10.1 ms a call on the chip's host where this takes
    2.6 (PERF.md §6, PR 25).  The batch's columns are
    compared as they are: ``pack_columns`` clamps them exactly as
    ``clamp_config`` clamped the tier's ``pinned_cfg`` tuples
    (core/batch.py: "must stay in lockstep";
    tests/test_mesh_global.py holds the two together)."""
    keys = kh[idx].tolist()
    cfgs = zip(*(np.asarray(col)[idx].tolist() for col in (
        batch.algorithm, batch.limit, batch.duration, batch.burst)))
    cfg_of: dict = {}
    for k, cfg in zip(keys, cfgs):
        if cfg_of.setdefault(k, cfg) != cfg:
            return None  # mid-batch config change
    unpinned = []
    for k, cfg in cfg_of.items():
        have = pinned_cfg.get(k)
        if have is None:
            unpinned.append(k)
        elif have != cfg:
            return None  # config changed → demote path
    unpinned.sort()
    return keys, unpinned


def _forward_fail_reason(e: Optional[BaseException]) -> str:
    """Stable low-cardinality reason label for
    gubernator_forward_failed (ISSUE 5 satellite)."""
    from .peer_client import ErrCircuitOpen

    if isinstance(e, ErrCircuitOpen):
        return "circuit_open"
    if isinstance(e, ErrClosing):
        return "closing"
    if isinstance(e, (TimeoutError,)) or \
            type(e).__name__ == "TimeoutError":
        return "timeout"
    if isinstance(e, RuntimeError) and "short" in (str(e) or ""):
        return "short_response"
    return "rpc_error"


class V1Instance:
    """One daemon's rate-limit brain: device engine + peer router."""

    def __init__(self, config: Config, mesh=None, engine=None,
                 peer_tls_creds=None):
        self.config = config
        self.metrics = Metrics()
        #: bounded structured-event ring (telemetry.py): wave launches/
        #: stalls/timeouts, handover passes, GLOBAL broadcasts, errors —
        #: served as JSON at the daemon's GET /debug/events
        self.recorder = FlightRecorder()
        # Trace plane (ISSUE 12, tracing.py): bounded ring of completed
        # spans, armed per request by the daemon's handlers; head
        # sampling at GUBER_TRACE_SAMPLE (default 0 — forced-sample
        # outcomes still record), capacity GUBER_TRACE_SPANS.  Served
        # at GET /debug/traces; spilled as JSONL on close.
        from .tracing import SpanRecorder

        try:
            _sample = float(os.environ.get("GUBER_TRACE_SAMPLE") or 0.0)
        except ValueError:
            _sample = 0.0
        try:
            _tcap = int(os.environ.get("GUBER_TRACE_SPANS") or 2048)
        except ValueError:
            _tcap = 2048
        self.span_recorder = SpanRecorder(capacity=max(_tcap, 1),
                                          sample=_sample)
        # Fault injection (ISSUE 5, faults.py): per-instance named
        # faultpoints, armed from GUBER_FAULT / POST /debug/faults.
        # One attribute read per instrumented site while disarmed.
        from .faults import FaultSet

        self.faults = FaultSet.from_env()
        self.faults.metrics = self.metrics
        self.faults.recorder = self.recorder
        # Device-memory ledger (ISSUE 13, memledger.py): every device-
        # resident allocation enrolls with a probe closure; serves the
        # memledger gauges, GET /debug/memory (+?advise=1), and the
        # hbm_pressure SLO.  GUBER_MEM_LEDGER=0 disables the plane.
        self.memledger = None
        self._memledger_live = 0  # last occupancy_nowait sample
        if os.environ.get("GUBER_MEM_LEDGER", "1") != "0":
            from .memledger import MemoryLedger

            self.memledger = MemoryLedger(recorder=self.recorder)
        # Compile ledger (ISSUE 14, compileledger.py): per-fn XLA
        # compile counts + the steady-state recompile verdict — the
        # runtime twin of guberlint's retrace pass.  Process-wide
        # singleton (compiles are process-wide events); each instance
        # mirrors counts into its own registry.
        from .compileledger import LEDGER as _compile_ledger
        from .compileledger import install_if_enabled

        if install_if_enabled():
            _compile_ledger.attach_metrics(self.metrics)
        self.compile_ledger = _compile_ledger
        kind = type(engine).__name__  # an injected engine names itself
        if engine is None:
            # lazy: an injected engine (tests, alternative backends)
            # must not drag the sharded/jax stack in
            from .parallel import make_mesh

            m = mesh if mesh is not None else make_mesh()
            n = m.shape["shard"]
            cap_local = max(config.cache_size // n, 1024)
            cap_local = 1 << (cap_local - 1).bit_length()
            step_impl = (os.environ.get("GUBER_STEP_IMPL")
                         or config.step_impl or "xla")
            if step_impl not in ("xla", "pallas"):
                # a typo must not silently serve the wrong mode — the
                # pallas choice carries domain restrictions the
                # operator believes are live
                raise ValueError(
                    f"unknown step_impl {step_impl!r} (want 'xla' or "
                    "'pallas')")
            import jax as _jax

            from .parallel.pallas_engine import resolve_engine_kind

            # GUBER_ENGINE (ISSUE 8): auto → fused pallas on TPU,
            # classic xla elsewhere; explicit pallas → fused serving
            # everywhere (compiled XLA flavor off-TPU); unknown raises
            # inside resolve_engine_kind.
            kind = resolve_engine_kind(
                os.environ.get("GUBER_ENGINE") or config.engine or "",
                step_impl, _jax.default_backend())
            engine = self._build_engine(kind, m, n, cap_local, config)
        self.engine = engine
        #: what is serving — the daemon's start-up log line and /healthz
        #: name it, so a daemon on the wrong backend or engine is seen
        #: at a glance rather than inferred from its speed
        devs = (list(engine.mesh.devices.flat)
                if getattr(engine, "mesh", None) is not None else [])
        self.serving_info = {
            "engine": kind,
            "platform": devs[0].platform if devs else "",
            "device_kind": devs[0].device_kind if devs else "",
            "device_count": len(devs),
            # ops/_native*.so is a build output: a checkout that never
            # built it serves through the slow numpy/pb2 lane
            "native_wire_lane": _wire_native is not None}
        self._engine_mu = threading.Lock()
        from .dispatcher import Dispatcher

        # Key-level analytics (ISSUE 4, analytics.py): heavy-hitter
        # ledger + per-phase latency attribution, fed off the hot path
        # from resolved waves' columns.  GUBER_ANALYTICS=0 disables the
        # whole subsystem (GUBER_TOPK / GUBER_SKETCH_WIDTH tune it).
        analytics = None
        if os.environ.get("GUBER_ANALYTICS", "1") != "0":
            from .analytics import KeyAnalytics

            analytics = KeyAnalytics(metrics=self.metrics)
        # Cross-request coalescing: concurrent handler threads share
        # device launches instead of serializing on the engine lock
        # (the worker-pool analog, see dispatcher.py).  Wave telemetry
        # lands on this instance's registry + recorder.
        # A wave takes what ONE launch of the engine holds
        # (``wave_capacity``: its ladder's top rung) and never less
        # than the default cap: a daemon whose ladder is set small
        # coalesces as it always did, its waves splitting into several
        # launches.
        self.dispatcher = Dispatcher(engine,
                                     max_wave=max(
                                         Dispatcher.MAX_WAVE, getattr(
                                             engine, "wave_capacity", 0)),
                                     lock=self._engine_mu,
                                     metrics=self.metrics,
                                     recorder=self.recorder,
                                     analytics=analytics,
                                     faults=self.faults)
        # waves emit fan-in spans + exact phase children (ISSUE 12)
        self.dispatcher.span_recorder = self.span_recorder
        # Fused-engine wiring (ISSUE 8): the fused serving program
        # emits the heavy-hitter tap columns ON DEVICE — hand the
        # analytics sink + metrics registry to the engine BEFORE any
        # serving starts (single assignment, read-only afterwards).
        if getattr(engine, "fused_tap", False) and analytics is not None:
            engine.tap_sink = analytics.tap_device
        if hasattr(engine, "metrics_ref"):
            engine.metrics_ref = self.metrics
        # wave-buffer pool counters (hit/miss/leak) land on this
        # instance's registry; the pool lives engine-side (lease scope
        # is the engine's fill→launch window)
        pool = getattr(engine, "wave_pool", None)
        if pool is not None:
            pool.metrics = self.metrics
        # Tiered key store (ISSUE 10, tiering.py): host cold tier
        # behind the device table with sketch-rank admission.  The
        # controller binds as engine.tier; check_packed pre-masks and
        # cold-serves through it.  Victim picks skip mesh-pinned
        # keys: their device row is a replica-coherence home copy,
        # and demoting it would fork state.
        self._tier = None
        tier_cold = os.environ.get("GUBER_TIER_COLD")
        if (tier_cold == "1" if tier_cold is not None
                else config.tier_cold):
            from .tiering import TierController

            thr = int(os.environ.get("GUBER_TIER_PROMOTE")
                      or config.tier_promote_threshold)
            # the admission and victim rank: the hits a key is KNOWN
            # to have drawn (count - err), not its raw sketch count —
            # under load every tracked key's count reads in the
            # thousands (ARCHITECTURE.md §2.2)
            rank_fn = ((lambda kh: int(analytics.sketch_known([kh])[0]))
                       if analytics is not None else None)
            tap = None
            if getattr(engine, "fused_tap", False) \
                    and analytics is not None:
                # fused engines tap on device and the device tap gates
                # out invalid rows — cold rows ride the wave invalid,
                # so the tier feeds their counts to the sketch itself
                tap = analytics.tap_packed
            self._tier = TierController(
                engine, rank_fn=rank_fn, promote_threshold=thr,
                metrics=self.metrics, recorder=self.recorder,
                fault=self._fault_point,
                skip_victim=self._tier_victim_pinned, tap=tap,
                rank_batch=(analytics.sketch_known
                            if analytics is not None else None))
        # every eagerly-built consumer enrolls now; the lazy mesh-GLOBAL
        # tier enrolls inside its _ensure_meshglobal builder
        self._enroll_memledger()
        self._peer_tls = peer_tls_creds
        # Datacenter-aware deployments route through a region picker
        # (region_picker.go); single-region uses the flat ring.
        if config.data_center:
            self._picker = RegionPeerPicker(config.data_center)  # guarded-by: self._peer_mu
        else:
            self._picker = ReplicatedConsistentHash()  # guarded-by: self._peer_mu
        self._peer_mu = threading.Lock()
        self._self_addr = config.advertise_address
        # Health-gated routing ring (ISSUE 5): peers whose circuit has
        # been open past peer_eject_after_ms are EJECTED from a derived
        # routing picker (their keys deterministically rehome to the
        # next ring point) and readmitted only after staying recovered
        # for peer_readmit_after_ms.  All under _peer_mu.
        #: lock-free reads are fine (immutable frozenset swap); all
        #: WRITES and read-modify-write derivations hold _peer_mu
        self._gate_bad: frozenset = frozenset()
        self._gate_picker = None  # guarded-by: self._peer_mu
        self._ring_gen = 0  # guarded-by: self._peer_mu
        #: IntervalLoop probing EJECTED peers (rehomed keys carry no
        #: organic traffic, so nothing else would half-open their
        #: circuit); started lazily on first ejection
        self._probe_loop = None
        self.global_manager: Optional[GlobalManager] = None
        self.mr_manager: Optional[MultiRegionManager] = None
        self._gm_mu = threading.Lock()
        # GLOBAL reconcile backend (ISSUE 7): "grpc" keeps the
        # reference's hit-queue/broadcast machinery; "mesh" serves
        # pod-local GLOBAL keys from the mesh-resident replica tier
        # (parallel/meshglobal.py) and reconciles with ONE collective
        # fold per GlobalSyncWait tick — zero gRPC peer fan-out.  The
        # gRPC path stays for cross-pod owners and as the degraded
        # fallback when the fold is unhealthy.
        global_mode = (os.environ.get("GUBER_GLOBAL_MODE")
                       or config.global_mode or "grpc")
        if global_mode not in ("grpc", "mesh"):
            # a typo must not silently serve the wrong coherence model
            raise ValueError(
                f"unknown global_mode {global_mode!r} (want 'grpc' or "
                "'mesh')")
        self._global_mode = global_mode
        if global_mode == "mesh":
            # GLOBAL rows route on the handler threads: time every
            # call's handler and call.wait (see get_rate_limits_wire)
            self.dispatcher.call_sample = 1
        self._meshglobal = None
        #: single-writer state (the GlobalManager hits-loop thread owns
        #: the reconcile tick); request threads only read — a stale
        #: read routes one batch the conservative (sharded) way
        self._mesh_fail_streak = 0  # lock-free: tick-thread only
        self._mesh_degraded = False  # lock-free: single racy bool
        self._mesh_down_until = 0.0  # lock-free: single racy float
        # stateful-handover serialization: one pass at a time, and a
        # generation counter so a newer membership change supersedes an
        # in-flight pass (it re-snapshots whatever is left)
        self._handover_mu = threading.Lock()
        self._handover_gen = 0  # guarded-by: self._handover_gen_mu
        self._handover_gen_mu = threading.Lock()
        self._closed = False
        self._last_sweep = clock_ms()  # clock-ok: sweep cadence bookkeeping, never a bucket stamp
        self._last_asked_sweep = 0  # the last sweep a table_full row asked for
        self.store = config.store
        self.loader = config.loader
        if self.loader is not None:
            self._load_from_loader()
        if self._mesh_mode():
            # the reconcile tick rides the GlobalManager's hits loop
            # (its mesh backend) — start it now so folds run even
            # before any gRPC-lane work would have built the manager
            self._ensure_global_manager()
            # pre-compile the mesh tier's step + fold NOW: a lazy
            # first-touch compile would land inside a caller's GLOBAL
            # request, long enough (CPU: seconds) to idle-expire
            # short-duration buckets before their second request
            self._ensure_meshglobal().warmup()
            # fused engines: also pre-compile the fused mesh program
            # (decide + scatter in one launch) per wave bucket
            if hasattr(self.engine, "warmup_mesh_fused"):
                self.engine.warmup_mesh_fused()
        # Always-on conservation auditor (ISSUE 19, fleet.py): folds
        # the GLOBAL lanes' audit vectors into a per-daemon drift doc
        # served at GET /debug/audit and sampled by the
        # fleet_conservation SLO below.
        from .fleet import ConservationAuditor
        self.auditor = ConservationAuditor(self)
        # Tenant-aware SLO plane (ISSUE 11, slo.py): multi-window
        # burn-rate verdicts over the signals the layers above emit
        # (phase ledger p99, mesh staleness, tenant RED ledger).
        self.slo = None
        self._slo_loop = None
        #: monotonic stamp of the last SUCCESSFUL mesh fold; the
        #: staleness SLO ages against it so a wedged/failing fold
        #: breaches even though last_staleness_s stops updating
        self._mesh_last_fold_ok: Optional[float] = None  # lock-free: tick-thread writes, SLO tick reads
        if os.environ.get("GUBER_SLO", "1") != "0":
            self._build_slo()

    def _build_engine(self, kind: str, m, n: int, cap_local: int,
                      config: Config):
        """Construct the resolved engine kind (ISSUE 8).  An engine
        that was selected and cannot be built stops the daemon: serving
        from a different engine than the one the operator (or the
        platform default) chose would hide the device."""
        from .parallel.sharded import (ShardedEngine,
                                       autogrow_limit_per_shard)

        if kind == "xla-fused":
            from .parallel.pallas_engine import XlaFusedEngine

            return XlaFusedEngine(
                m, capacity_per_shard=cap_local,
                batch_per_shard=config.batch_rows,
                auto_grow_limit=autogrow_limit_per_shard(
                    config.cache_autogrow_max, n, cap_local))
        if kind in ("pallas-kernel", "pallas-fused"):
            from .parallel.pallas_engine import PallasServingEngine

            if config.cache_autogrow_max:
                # silently different capacity semantics would be a
                # trap: the xla engine grows to this bound, pallas
                # mode never grows (VERDICT r4 weak #4)
                log.warning(
                    "pallas serving engine ignores "
                    "cache_autogrow_max=%d: this mode has no "
                    "on-device grow — size cache_size for peak "
                    "keys up front (full 128-slot buckets err as "
                    "table_full; watch "
                    "gubernator_pallas_bucket_saturation)",
                    config.cache_autogrow_max)
            return PallasServingEngine(
                m, capacity_per_shard=cap_local,
                batch_per_shard=config.batch_rows)
        return ShardedEngine(
            m, capacity_per_shard=cap_local,
            batch_per_shard=config.batch_rows,
            auto_grow_limit=autogrow_limit_per_shard(
                config.cache_autogrow_max, n, cap_local))

    # ---- persistence wiring (store.go › Loader/Store) ------------------

    def _load_from_loader(self) -> None:
        from .store import arrays_from_items

        self._fault_point("restore")
        # restore is a serving-blackout window — attribute it (ISSUE 5
        # satellite; closes the PR-4 ROADMAP item with broadcast/
        # snapshot)
        with phase("restore", self.dispatcher):
            items = list(self.loader.load())
            if items:
                arrays = arrays_from_items(items)
                placed = self.engine.restore(arrays)
                log.info("loader: restored %d/%d items", placed,
                         len(items))

    def _save_to_loader(self) -> None:
        from .store import items_from_arrays

        if self.loader is None:
            return
        self._fault_point("snapshot")
        with phase("snapshot", self.dispatcher):
            # mesh-tier rows live outside the sharded table; fold
            # them back in so the snapshot is complete
            self._mesh_demote_all()
            arrays = self.engine.snapshot()
            if self._tier is not None:
                # cold-tier rows are first-class state: a snapshot
                # covers BOTH tiers (restore re-adopts whatever the
                # device table cannot hold — engine.restore's unplaced
                # → tier path)
                cold = self._tier.snapshot_arrays()
                if cold is not None:
                    arrays = {f: np.concatenate([arrays[f], cold[f]])
                              for f in arrays}
            self.loader.save(iter(items_from_arrays(arrays)))

    def _fault_point(self, point: str, tag: Optional[str] = None) -> None:
        """Instance-level faultpoint check (one attribute read while
        disarmed — the acceptance A/B bound)."""
        f = self.faults
        if f.armed:
            f.fire(point, tag)

    # ---- peer management (gubernator.go › SetPeers) --------------------

    def set_peers(self, infos: Sequence[PeerInfo]) -> None:
        """Rebuild the picker atomically; drain clients for departed
        peers.  Keys silently re-home on ring change; moved keys reset
        (documented reference behavior, SURVEY.md §5.3)."""
        with self._peer_mu:
            old_picker = self._picker  # immutable; handover routes by it
            old = {p.info.grpc_address: p for p in self._picker.peers()}
            picker = self._picker.new()
            for info in infos:
                existing = old.pop(info.grpc_address, None)
                if existing is not None:
                    picker.add(existing)
                else:
                    picker.add(PeerClient(info, self.config.behaviors,
                                          tls_creds=self._peer_tls,
                                          metrics=self.metrics,
                                          analytics=self.analytics,
                                          faults=self.faults))
            self._picker = picker
            # membership change invalidates the health-gated view —
            # the next routing lookup re-derives it from live health
            self._gate_bad = frozenset()
            self._gate_picker = None
            self._ring_gen += 1
            self.metrics.ring_generation.set(self._ring_gen)
            self.metrics.ring_ejected_peers.set(0)
        for departed in old.values():
            threading.Thread(target=departed.shutdown, daemon=True,
                             name="peer-shutdown").start()
        # The mesh-GLOBAL tier is pod-local: once any non-self peer
        # exists (mesh routing turns off), its keys must go back to
        # daemon-level ownership with their consumption intact.
        have_others = any(info.grpc_address != self._self_addr
                          for info in infos)
        if have_others:
            self._mesh_demote_all()
        # Stateful re-sharding (beyond-reference, opt-in): the
        # reference resets re-homed keys (SURVEY.md §5.3); with the
        # flag on, rows whose ring owner moved are handed to the new
        # owner over the peer wire instead.
        if self.config.handover_on_reshard and have_others:
            with self._handover_gen_mu:
                self._handover_gen += 1
                gen = self._handover_gen
            threading.Thread(target=self._handover_moved_rows,
                             args=(old_picker, gen),
                             daemon=True, name="handover").start()

    @staticmethod
    def _uses_default_hash(picker) -> bool:
        """Hash-level routing is only valid on the default pipeline
        (table key hashes ARE mixed fnv1a64 of the identity string)."""
        from .hashing import mixed_fnv1a64

        pickers = (list(picker.regions.values())
                   if isinstance(picker, RegionPeerPicker) else [picker])
        return all(getattr(pk, "_hash", None) is mixed_fnv1a64
                   for pk in pickers)

    def _handover_moved_rows(self, old_picker, gen: int) -> None:
        """Send every live row that this daemon OWNED under the old
        ring and no longer owns to its new owner (UpdatePeerGlobals
        with the key_hash + eff_ms extension fields), then drop it
        locally.  Rows held only as GLOBAL/MULTI_REGION replicas (owned
        by another peer under the old ring too) stay put — handing a
        replica over would overwrite the owner's authoritative state.

        Best effort: delivery failure leaves the row in place (the new
        owner serves a fresh bucket — the reference's reset-on-rehome
        behavior).  ``gen`` guards against a second membership change
        mid-flight: a newer set_peers bumps the generation, this pass
        aborts before its next chunk, and the newer pass re-snapshots
        whatever is left.  Interim hits on the new owner between the
        picker swap and the upsert are overwritten — the same bounded
        window GLOBAL broadcasts already have."""
        # route by the health-gated ring: a handover triggered by an
        # ejection/readmit must target where requests actually go
        picker = self._routing_picker()
        if not self._uses_default_hash(picker) or (
                old_picker.peers()
                and not self._uses_default_hash(old_picker)):
            log.warning("handover_on_reshard requires the default "
                        "picker hash; skipping handover")
            return
        with self._handover_mu:  # one in-flight pass at a time
            with self._handover_gen_mu:
                if self._handover_gen != gen:
                    return  # superseded before it started
            with self._engine_mu:
                snap = self.engine.snapshot()
            keys = snap.get("key")
            if keys is None or not len(keys):
                return
            had_old = bool(old_picker.peers())
            moved: Dict[str, list] = {}
            peers_by_addr: Dict[str, PeerClient] = {}
            for i, k in enumerate(keys):
                try:
                    # only rows we OWNED may move (solo ⇒ we owned all)
                    if had_old and not self.is_self(
                            old_picker.get_by_hash(int(k))):
                        continue
                    p = picker.get_by_hash(int(k))
                except RuntimeError:
                    return  # picker emptied concurrently
                addr = p.info.grpc_address
                if addr != self._self_addr:
                    moved.setdefault(addr, []).append(i)
                    peers_by_addr[addr] = p
            if not moved:
                return
            limit = self.config.behaviors.global_batch_limit
            sent = 0
            for addr, idxs in moved.items():
                peer = peers_by_addr[addr]
                for a in range(0, len(idxs), limit):
                    with self._handover_gen_mu:
                        if self._handover_gen != gen:
                            log.info("handover superseded after %d rows",
                                     sent)
                            return
                    chunk = idxs[a:a + limit]
                    batch = []
                    for i in chunk:
                        meta = int(snap["meta"][i])
                        alg = meta & 1
                        eff = max(int(snap["eff_ms"][i]), 1)
                        batch.append(peers_pb.UpdatePeerGlobal(
                            key_hash=int(keys[i]), eff_ms=eff,
                            algorithm=alg,
                            duration=int(snap["duration"][i]),
                            created_at=int(snap["t_ms"][i]),
                            burst=int(snap["burst"][i]),
                            update=pb.RateLimitResp(
                                status=(meta >> 1) & 1,
                                limit=int(snap["limit"][i]),
                                # RAW internal value — for leaky that is
                                # td fixed point; the receiver detects
                                # eff_ms>0 and skips the rescale, so the
                                # transfer is lossless
                                remaining=int(snap["remaining"][i]),
                                reset_time=int(snap["expire_at"][i]))))
                    delivered = False
                    for attempt in range(3):
                        try:
                            peer.update_peer_globals(batch)
                            delivered = True
                            break
                        except Exception as e:  # noqa: BLE001
                            # a first RPC to a just-joined peer can
                            # exceed its deadline while that daemon
                            # compiles its upsert program; the upsert is
                            # idempotent, so retrying is safe.
                            # exc_text: a deadline error str()s empty
                            log.warning("handover to %s failed "
                                        "(attempt %d/3): %s", addr,
                                        attempt + 1, exc_text(e))
                            self.recorder.record_error(
                                "handover_error", e, peer=addr,
                                attempt=attempt + 1)
                            time.sleep(0.5 * (attempt + 1))
                    if not delivered:
                        continue  # row stays: reset-on-rehome fallback
                    with self._engine_mu:
                        self.engine.remove_rows(
                            np.asarray([int(keys[i]) for i in chunk],
                                       np.uint64))
                    sent += len(chunk)
            log.info("handover: moved %d rows to %d peers", sent,
                     len(moved))
            self.recorder.record("handover", rows=sent,
                                 peers=len(moved))

    @property
    def analytics(self):
        """The key-analytics subsystem (None when disabled).  Lives on
        the dispatcher so bench A/B detaches ONE reference and every
        tap — dispatcher waves and fused instance lanes — goes dark."""
        return self.dispatcher.analytics

    def owner_addr_by_khash(self, khash: int) -> Optional[str]:
        """Owner peer address for a MIXED table key hash (the heavy-
        hitter ledger's key space) — /debug/topkeys' owner column.
        None when solo, on a custom picker hash (hash-level routing
        would be wrong there), or for an emptied ring."""
        with self._peer_mu:
            picker = self._picker
            if not picker.peers():
                return None
        if not self._uses_default_hash(picker):
            return None
        try:
            return picker.get_by_hash(int(khash)).info.grpc_address
        except RuntimeError:  # ring emptied concurrently
            return None

    def peers(self) -> List[PeerClient]:
        with self._peer_mu:
            return self._picker.peers()

    def owner_of(self, key: str) -> Optional[PeerClient]:
        with self._peer_mu:
            if not self._picker.peers():
                return None
            return self._picker.get(key)

    def default_hash_routing(self) -> bool:
        """True when the picker runs the default mixed_fnv1a64 pipeline,
        i.e. raw-khash owner lookups (owner_by_raw_khash) are valid."""
        with self._peer_mu:
            picker = self._picker
        return self._uses_default_hash(picker)

    def owner_by_raw_khash(self, khash_raw: int) -> Optional[PeerClient]:
        """Owner peer for a RAW (unmixed) FNV-1a64 key hash — the wire
        lanes' async-queue key space.  Callers gate on
        ``default_hash_routing()`` first."""
        with self._peer_mu:
            if not self._picker.peers():
                return None
            return self._picker.get_by_raw_hash(khash_raw)

    def is_self(self, peer: PeerClient) -> bool:
        return peer.info.grpc_address == self._self_addr

    # ---- health-gated routing ring (ISSUE 5) ---------------------------

    def _routing_picker(self):
        """The picker requests ROUTE by: the membership picker with
        long-unhealthy peers ejected (their keys deterministically
        rehome to the next ring point — exactly the picker that would
        exist without them) and readmitted after the hysteresis window.
        The membership picker itself stays authoritative for reconcile
        targets (owner_of / owner_by_raw_khash), so degraded hits always
        flush to the TRUE owner once it is reachable.

        Healthy cluster fast path: one lock + one health read per peer,
        returning the membership picker itself."""
        b = self.config.behaviors
        if not getattr(b, "peer_health_gate", True):
            with self._peer_mu:
                return self._picker
        eject_s = max(int(getattr(b, "peer_eject_after_ms", 3000)),
                      0) / 1e3
        readmit_s = max(int(getattr(b, "peer_readmit_after_ms", 3000)),
                        0) / 1e3
        with self._peer_mu:
            picker = self._picker
            peers = picker.peers()
            if not peers:
                return picker
            bad = frozenset(
                p.info.grpc_address for p in peers
                if not self.is_self(p) and hasattr(p, "route_healthy")
                and not p.route_healthy(eject_s, readmit_s))
            if len(bad) >= len(peers):
                # never empty the ring: with every peer unhealthy the
                # membership ring is the least-wrong answer
                bad = frozenset()
            if bad == self._gate_bad:
                return (self._gate_picker
                        if self._gate_picker is not None else picker)
            old_bad = self._gate_bad
            old_routing = (self._gate_picker
                           if self._gate_picker is not None else picker)
            gated = None
            if bad:
                gated = picker.new()
                for p in peers:
                    if p.info.grpc_address not in bad:
                        gated.add(p)
            self._gate_bad = bad
            self._gate_picker = gated
            self._ring_gen += 1
            gen = self._ring_gen
        # emission + probe/handover management OFF the lock
        self.metrics.ring_generation.set(gen)
        self.metrics.ring_ejected_peers.set(len(bad))
        for addr in sorted(bad - old_bad):
            log.warning("ring: peer %s EJECTED from routing (circuit "
                        "open > %.1fs); its keys rehome until readmit",
                        addr, eject_s)
            self.recorder.record("ring_ejected", peer=addr,
                                 generation=gen)
        for addr in sorted(old_bad - bad):
            log.info("ring: peer %s readmitted to routing "
                     "(recovered > %.1fs)", addr, readmit_s)
            self.recorder.record("ring_readmitted", peer=addr,
                                 generation=gen)
        if bad:
            self._ensure_probe_loop()
        if self.config.handover_on_reshard:
            # keys moved between live daemons: reuse the stateful
            # rehome machinery so consumption follows them (best
            # effort — an ejected target just keeps its rows)
            with self._handover_gen_mu:
                self._handover_gen += 1
                hgen = self._handover_gen
            threading.Thread(target=self._handover_moved_rows,
                             args=(old_routing, hgen),
                             daemon=True, name="handover-rehome").start()
        return gated if gated is not None else picker

    def _route_owner_of(self, key: str) -> Optional[PeerClient]:
        """owner_of through the health-gated ring (the forward path's
        view); reconcile/broadcast targets keep using owner_of."""
        picker = self._routing_picker()
        if not picker.peers():
            return None
        return picker.get(key)

    def _ensure_probe_loop(self) -> None:
        with self._gm_mu:
            if self._probe_loop is None and not self._closed:
                from .interval import IntervalLoop

                iv = max(int(getattr(self.config.behaviors,
                                     "peer_circuit_cooldown_ms", 2000)),
                         100)
                self._probe_loop = IntervalLoop(
                    iv, self._probe_ejected, name="ring-health-probe")

    def _probe_ejected(self) -> None:
        """Probe every EJECTED peer with one empty flush so a recovered
        peer's circuit can close (rehomed keys generate no organic
        traffic toward it).  Failures keep the circuit open — that is
        the point."""
        with self._peer_mu:
            bad = self._gate_bad
            peers = list(self._picker.peers())
        if not bad:
            return
        for p in peers:
            if p.info.grpc_address in bad and hasattr(p, "probe"):
                try:
                    p.probe()
                except Exception:  # noqa: BLE001 - probe is best-effort
                    pass

    def _ensure_global_manager(self) -> GlobalManager:
        with self._gm_mu:
            if self.global_manager is None:
                self.global_manager = GlobalManager(
                    self, self.config.behaviors, self.metrics)
            return self.global_manager

    def _ensure_mr_manager(self) -> MultiRegionManager:
        with self._gm_mu:
            if self.mr_manager is None:
                self.mr_manager = MultiRegionManager(
                    self, self.config.behaviors)
            return self.mr_manager

    def region_pickers(self) -> dict:
        """Per-datacenter pickers (region_picker.go); single-region
        deployments expose their one ring under their own name."""
        with self._peer_mu:
            if isinstance(self._picker, RegionPeerPicker):
                return dict(self._picker.regions)
            return {self.config.data_center: self._picker}

    # ---- the public API ------------------------------------------------

    def get_rate_limits(self, reqs: Sequence[RateLimitRequest],
                        now_ms: Optional[int] = None
                        ) -> List[RateLimitResponse]:
        """Batch entry point (gubernator.go › GetRateLimits): split by
        ownership, serve owned + GLOBAL keys in one device step, forward
        the rest to their owners (batched per peer)."""
        if len(reqs) > MAX_BATCH_SIZE:
            raise ValueError(
                f"Requests.RateLimits list too large; max size is "
                f"{MAX_BATCH_SIZE}")
        # overload admission (ISSUE 5): shed cheaply at ingest, before
        # any engine work (raises ResourceExhausted → RESOURCE_EXHAUSTED)
        self.dispatcher.admit(
            len(reqs), tenant_cb=lambda: self._tenant_of_reqs(reqs))
        now = clock_ms() if now_ms is None else now_ms  # clock-domain: caller
        self.metrics.getratelimit_counter.labels(calltype="api").inc(len(reqs))
        self.metrics.concurrent_checks.inc()
        try:
            with self.metrics.time_func("GetRateLimits"):
                return self._get_rate_limits(reqs, now)
        finally:
            self.metrics.concurrent_checks.dec()

    def get_rate_limits_wire(self, data: bytes,
                             now_ms: Optional[int] = None) -> bytes:
        """Wire-to-wire GetRateLimits: serialized GetRateLimitsReq in,
        serialized GetRateLimitsResp out.

        Takes the C++ columnar fast lane (ops/_native.cpp: wire bytes →
        packed arrays → one device step → wire bytes, zero per-request
        Python objects) when the batch qualifies: extension built, no
        Store hooks, no metadata, non-empty names/keys.  Solo (no peers
        beyond self): GLOBAL batches ride the mesh tier's columnar flow
        under ``global_mode=mesh`` (``_wire_mesh_runner``), and are
        plain owner rows of the sharded step otherwise (the broadcast
        has no one to go to).  Clustered: ALL batches ride
        the clustered columnar lane — non-GLOBAL rows are ring-split by
        owner (owned keys stepped locally, the rest forwarded as raw
        TLV slices over the peer wire and spliced back in order);
        GLOBAL rows are answered from the local replica with async
        reconcile queued as raw TLV prototypes (_wire_check_clustered).
        MULTI_REGION rows decided locally queue cross-region
        replication the same way (multiregion.queue_hits_raw, after
        the step).  Anything the lanes can't model falls back to the
        pb2 object path with identical semantics.  Raises ValueError
        on oversize batches (mirroring ``get_rate_limits``).
        """
        # the `handler` phase: the whole call, wall and thread CPU — on
        # 32 threads and one GIL the difference is waiting.  Where
        # GLOBAL rows route on the handler threads (mesh mode:
        # call_sample is 1) every call; elsewhere 1 call in 8: a
        # per-call phase costs single-request traffic its share of the
        # rate
        with phase("handler", self.dispatcher, cpu=True,
                   every=self.dispatcher.call_sample):
            return self._get_rate_limits_wire(data, now_ms)

    def _get_rate_limits_wire(self, data: bytes,
                              now_ms: Optional[int]) -> bytes:
        self._fault_point("wire_ingest")
        parsed = None
        is_global = False
        clustered = False
        declined = False
        if _wire_native is not None and self.store is None:
            peer_list = self.peers()
            if not peer_list or all(self.is_self(p) for p in peer_list):
                # solo fused lane: bytes → the call's block → wave → device
                # → bytes in one C++ ingest pass (no parse/pack numpy
                # columns at all); returns None for anything it can't
                # model (GLOBAL/MR rows, a calendar row of an invalid
                # ordinal, pb2 framing, busy-path gates) and the
                # classic lanes below take
                # over with identical semantics
                out = self._wire_client_fused(data, now_ms)
                if out is not None:
                    return out
                declined = True
            ing = phase("ingest", self.dispatcher).begin()
            parsed = _wire_native.parse_get_rate_limits(data)
            ing.end(keep=parsed is not None)
            if declined:
                self._count_fused_declined(parsed)
            if parsed is not None:
                is_global = bool(parsed["behavior_or"]
                                 & int(Behavior.GLOBAL))
                peer_list = self.peers()
                solo = not peer_list or all(
                    self.is_self(p) for p in peer_list)
                if not solo:
                    # clustered GLOBAL rides the same columnar lane:
                    # GLOBAL rows are answered from the local replica
                    # and their reconcile queues take raw TLV slices
                    # (global_manager.queue_*_raw), so no per-request
                    # objects are needed
                    clustered = True
                # solo GLOBAL rows are served where they live (the
                # mesh tier or the owner row, _wire_global_runner); the
                # object path's queue_update is a no-op with no peers
                # (nothing to broadcast to)
        if parsed is not None:
            n = parsed["n"]
            if n > MAX_BATCH_SIZE:
                raise ValueError(
                    f"Requests.RateLimits list too large; max size is "
                    f"{MAX_BATCH_SIZE}")
            now = clock_ms() if now_ms is None else now_ms  # clock-domain: caller
            # all gating happens before metrics or state are touched:
            # a None runner falls through to the object path untouched
            if clustered:
                lane = "wire_clustered"
                runner = lambda: self._wire_check_clustered(  # noqa: E731
                    parsed, data, now)
            else:
                # MULTI_REGION rows decided locally replicate
                # cross-region asynchronously; GLOBAL takes precedence
                # (the object path never MR-queues a GLOBAL row).
                # Solo: every row is local.  (The clustered lane
                # derives its own owned-rows mask.)  behavior_or gates
                # the column scans: MR-free traffic pays nothing.
                if parsed["behavior_or"] & int(Behavior.MULTI_REGION):
                    mr_mask = ((parsed["behavior"]
                                & int(Behavior.MULTI_REGION)) != 0) & \
                        ((parsed["behavior"]
                          & int(Behavior.GLOBAL)) == 0)
                else:
                    mr_mask = _NO_ROWS
                if is_global:
                    lane = "wire_global"
                    inner = self._wire_global_runner(parsed, now)
                else:
                    lane = "wire_local"
                    inner = lambda: self._wire_check_columns(  # noqa: E731
                        parsed, now)
                if inner is not None and mr_mask.any():
                    def runner(inner=inner):
                        out = inner()
                        # after the step: rows exist, replicate async
                        self._queue_mr_raw(parsed, data, mr_mask,
                                           stamp_ms=now)
                        return out
                else:
                    runner = inner
            if runner is not None:
                self.dispatcher.admit(
                    n, tenant_cb=lambda: self._tenant_of_wire(data))
                ana = self.dispatcher.analytics
                if ana is not None:
                    # tenant learn tap: the parse's views ride
                    # zero-copy; the worker reads a request TLV only
                    # for a rate-limit name it has never seen
                    ana.tap_wire_names(
                        data, parsed["khash_raw"], parsed["name_hash"],
                        parsed["tlv_off"], parsed["tlv_len"], raw=True)
                self.metrics.getratelimit_counter.labels(
                    calltype="api").inc(n)
                self.metrics.wire_lane_counter.labels(lane=lane).inc(n)
                self.metrics.concurrent_checks.inc()
                try:
                    with self.metrics.time_func("GetRateLimits"):
                        out_bytes = runner()
                        self._maybe_sweep(now)
                        return out_bytes
                finally:
                    self.metrics.concurrent_checks.dec()
        # pb2 object path: everything the columnar lanes can't model
        from google.protobuf.message import DecodeError

        from .wire import req_from_pb, resp_to_pb

        try:
            msg = pb.GetRateLimitsReq.FromString(data)
        except DecodeError as e:
            # surfaced as INVALID_ARGUMENT by the servicer, matching
            # what a grpc-layer deserializer failure produced before
            # the raw-bytes handler existed
            raise ValueError(f"invalid GetRateLimitsReq: {e}") from e
        reqs = [req_from_pb(m) for m in msg.requests]
        self.metrics.wire_lane_counter.labels(
            lane="pb2_fallback").inc(len(reqs))
        resps = self.get_rate_limits(reqs, now_ms=now_ms)
        out = pb.GetRateLimitsResp()
        out.responses.extend(resp_to_pb(r) for r in resps)
        return out.SerializeToString()

    # ---- fused wire lane (ops/_native.cpp › pack_wire_wave) ------------

    #: behaviors whose async side effects (mesh-tier routing, GLOBAL
    #: reconcile queues, cross-region replication) need the parsed
    #: columns — the fused lane hands them to the classic lanes, which
    #: keep those semantics in one place.  The policy lives HERE; the
    #: ingest's pre-pass is handed it (``prepack_wire``'s ``excluded``)
    #: and declines at the first row that carries one of these bits,
    #: before it packs anything: an all-GLOBAL call costs this lane one
    #: request's header.
    _FUSED_EXCLUDED = Behavior.GLOBAL | Behavior.MULTI_REGION

    def _count_fused_declined(self, parsed: Optional[dict]) -> None:
        """``gubernator_wire_fused_declined{reason}``: one call the
        fused ingest refused, by why — read off what the classic parse
        that follows a refusal has in hand anyway (``behavior_or``,
        ``n``), so the refusal itself stays one request's header.  A
        call with rows of several kinds counts under the first of
        global, multi_region that any row carries, else too_large;
        ``gregorian`` is what is left of a calendar call: the pass
        serves those, all but a row of an invalid ordinal (or a clock
        outside the calendar), whose error the classic lane builds."""
        if not hasattr(self.engine, "prepack_wire"):
            return  # no fused lane: nothing was refused
        reason = "other"  # framing the C++ lanes do not model, 0 rows
        if parsed is not None:
            b = parsed["behavior_or"]
            if b & int(Behavior.GLOBAL):
                reason = "global"
            elif b & int(Behavior.MULTI_REGION):
                reason = "multi_region"
            elif parsed["n"] > self.engine.wave_capacity:
                reason = "too_large"
            elif b & int(Behavior.DURATION_IS_GREGORIAN):
                reason = "gregorian"
        self.metrics.wire_fused_declined.labels(reason=reason).inc()

    def _wire_client_fused(self, data: bytes,
                           now_ms: Optional[int]) -> Optional[bytes]:
        """Solo client twin of ``_wire_peer_fused``: the GetRateLimits
        front door when this daemon owns every key.  Returns None when
        the fused lane can't serve the batch (caller falls back)."""
        prepack = getattr(self.engine, "prepack_wire", None)
        if prepack is None:
            return None
        now = clock_ms() if now_ms is None else now_ms  # clock-domain: caller
        ing = phase("ingest", self.dispatcher).begin()
        pre = prepack(data, now, int(self._FUSED_EXCLUDED))
        ing.end(keep=pre is not None)
        if pre is None:
            return None
        if pre.n > MAX_BATCH_SIZE:
            raise ValueError(
                f"Requests.RateLimits list too large; max size is "
                f"{MAX_BATCH_SIZE}")
        self.dispatcher.admit(
            pre.n, tenant_cb=lambda: self._tenant_of_wire(data))
        ana = self.dispatcher.analytics
        if ana is not None:
            ana.tap_wire_names(data, pre.khash, pre.name_hash,
                               pre.tlv_off, pre.tlv_len)
        self.metrics.getratelimit_counter.labels(calltype="api").inc(
            pre.n)
        self.metrics.wire_lane_counter.labels(lane="wire_local").inc(
            pre.n)
        self.metrics.wire_fused_counter.inc(pre.n)
        self.metrics.concurrent_checks.inc()
        try:
            with self.metrics.time_func("GetRateLimits"):
                out = self._run_fused(pre, now)
                self._maybe_sweep(now)
                return out
        finally:
            self.metrics.concurrent_checks.dec()

    def _wire_peer_fused(self, data: bytes,
                         now_ms: Optional[int]) -> Optional[bytes]:
        """Fused owner side of the forward hop: received TLV bytes go
        straight into the call's block in the upload layout (C++
        parse+clamp+hash+fill, zero numpy column passes), which the
        dispatch worker joins into its wave, and responses serialize from the
        wave's result columns — a forwarded batch costs the same as a
        local wire call, a forwarded calendar row (its period the one
        that holds its ``created_at`` stamp) included.  None → classic
        lane (GLOBAL/MR rows whose async queues need parsed columns, a
        calendar row of an invalid ordinal, pb2 framing)."""
        prepack = getattr(self.engine, "prepack_wire", None)
        if prepack is None:
            return None
        now = clock_ms() if now_ms is None else now_ms  # clock-domain: caller
        ing = phase("ingest", self.dispatcher).begin()
        pre = prepack(data, now, int(self._FUSED_EXCLUDED))
        ing.end(keep=pre is not None)
        if pre is None:
            return None
        if pre.n > self.config.behaviors.batch_limit:
            raise ValueError(
                "'PeerRequest.rate_limits' list too large; max size is "
                f"{self.config.behaviors.batch_limit}")
        ana = self.dispatcher.analytics
        if ana is not None:
            ana.tap_wire_names(data, pre.khash, pre.name_hash,
                               pre.tlv_off, pre.tlv_len)
        self.metrics.getratelimit_counter.labels(calltype="peer").inc(
            pre.n)
        self.metrics.wire_lane_counter.labels(lane="peer_wire").inc(
            pre.n)
        self.metrics.wire_fused_counter.inc(pre.n)
        return self._run_fused(pre, now)

    def _run_fused(self, pre, now: int) -> bytes:
        """Submit a prepacked call to the dispatcher — its rows are laid
        out already, as the block the worker joins into the wave — and
        serialize its responses."""
        disp = self.dispatcher
        n = pre.n
        ana = disp.analytics
        kh = pre.khash
        view = disp.check_packed_view(pre.rows.batch, kh, now)
        status = view.cols[0][view.lo:view.hi]
        full = view.cols[4][view.lo:view.hi]
        self.metrics.over_limit_counter.inc(int((status == 1).sum()))
        errors = None
        if full.any():
            errors = [None] * n
            for i in np.nonzero(full)[0]:
                errors[int(i)] = "rate limit table full"
                if ana is not None:
                    ana.tap_flag("errors", 1, khash=int(kh[int(i)]))
        with phase("build", disp):
            resp = _wire_native.build_responses_from_columns(
                view.cols, view.lo, view.hi, errors)
        return resp

    # ---- tenant attribution helpers (ISSUE 11) -------------------------

    def _tenant_of_reqs(self, reqs) -> Optional[str]:
        """Shed-attribution hint for the object lane.  Only invoked on
        the exceptional path (admission rejected the batch), so the
        per-call cost never touches admitted traffic."""
        ana = self.dispatcher.analytics
        if ana is None or not reqs:
            return None
        try:
            return ana.tenant_hint(name=reqs[0].name)
        except Exception:
            return None

    def _tenant_of_wire(self, data: bytes) -> Optional[str]:
        """Shed-attribution hint for the wire lanes: tolerant
        pure-Python TLV walk to the first request's name.  Like
        ``_tenant_of_reqs`` this only runs when a shed actually fires;
        admitted wire batches never pay for it."""
        ana = self.dispatcher.analytics
        if ana is None:
            return None
        try:
            from .analytics import iter_wire_names

            pairs = iter_wire_names(data)
            if not pairs:
                return None
            return ana.tenant_hint(name=pairs[0][0])
        except Exception:
            return None

    def get_peer_rate_limits_wire(self, data: bytes,
                                  now_ms: Optional[int] = None) -> bytes:
        """Wire-to-wire GetPeerRateLimits — the owner side of request
        forwarding (peers.proto uses the same RateLimitReq/RateLimitResp
        submessages on field 1, so the C++ codec applies verbatim).
        Forwarded batches always apply locally, so peer membership does
        not gate the fast lane.  GLOBAL rows mark their keys changed
        for the next broadcast tick (queue_update_raw — this is the
        owner applying reconciled hits) and MULTI_REGION rows queue
        cross-region replication (queue_hits_raw), both AFTER the step,
        aggregated per unique key with raw TLV prototypes — the
        columnar twins of the per-request queueing the object path
        does."""
        self._fault_point("wire_ingest")
        parsed = None
        # rehome-target duty (ISSUE 5): while OUR health gate has peers
        # ejected, a forwarded row whose membership owner is ejected is
        # a rehomed row another daemon routed here — it must serve
        # DEGRADED (flag + reconcile queue), which needs parsed columns;
        # healthy gate (the steady state) costs one attribute read
        gate_rehome = bool(self._gate_bad) and getattr(
            self.config.behaviors, "peer_degraded_fallback", True)
        if _wire_native is not None and self.store is None:
            if not gate_rehome:
                out = self._wire_peer_fused(data, now_ms)
                if out is not None:
                    return out
            ing = phase("ingest", self.dispatcher).begin()
            parsed = _wire_native.parse_get_rate_limits(data)
            ing.end(keep=parsed is not None)
            if not gate_rehome:
                self._count_fused_declined(parsed)
        if parsed is None:
            from google.protobuf.message import DecodeError

            from .wire import req_from_pb, resp_to_pb

            try:
                msg = peers_pb.GetPeerRateLimitsReq.FromString(data)
            except DecodeError as e:
                raise ValueError(
                    f"invalid GetPeerRateLimitsReq: {e}") from e
            reqs = [req_from_pb(m) for m in msg.requests]
            self.metrics.wire_lane_counter.labels(
                lane="peer_pb2_fallback").inc(len(reqs))
            resps = self.get_peer_rate_limits(reqs, now_ms=now_ms)
            out = peers_pb.GetPeerRateLimitsResp()
            out.rate_limits.extend(resp_to_pb(r) for r in resps)
            return out.SerializeToString()
        if parsed["n"] > self.config.behaviors.batch_limit:
            raise ValueError(
                "'PeerRequest.rate_limits' list too large; max size is "
                f"{self.config.behaviors.batch_limit}")
        now = clock_ms() if now_ms is None else now_ms  # clock-domain: owner
        self.metrics.getratelimit_counter.labels(calltype="peer").inc(
            parsed["n"])
        self.metrics.wire_lane_counter.labels(lane="peer_wire").inc(
            parsed["n"])
        out = self._wire_check_columns(parsed, now)
        # behavior_or gates the column scans: plain forwarded traffic
        # pays nothing here
        if parsed["behavior_or"] & int(Behavior.GLOBAL):
            glob = (parsed["behavior"] & int(Behavior.GLOBAL)) != 0
            self._queue_global_updates_raw(parsed, data, glob)
        # NO GLOBAL precedence here: the object path's peer handler
        # queues BOTH for a GLOBAL|MULTI_REGION row (two independent
        # per-request ifs), unlike the client path
        if parsed["behavior_or"] & int(Behavior.MULTI_REGION):
            mr = (parsed["behavior"]
                  & int(Behavior.MULTI_REGION)) != 0
            # clock-ok: first-hop-wins — stamp_ms only fills rows missing a created_at TLV; stamped rows keep the caller's time base
            self._queue_mr_raw(parsed, data, mr, stamp_ms=now)
        if gate_rehome:
            # clock-ok: first-hop-wins fallback, same as _queue_mr_raw above
            out = self._peer_degraded_rewrite(parsed, data, out,
                                              stamp_ms=now)
        return out

    def _peer_degraded_rewrite(self, parsed: dict, data: bytes,
                               out: bytes,
                               stamp_ms: Optional[int] = None) -> bytes:
        """Rehome-target side of degraded mode (ISSUE 5): a forwarded
        row whose MEMBERSHIP owner is ejected from our health gate was
        routed here by another daemon's gated ring.  Its local apply
        (already done by the caller) is a DEGRADED serve: flag the
        response row and queue the hits for reconcile to the true
        owner, exactly like a rehomed row on the client path — without
        this, hits forwarded to a rehome target would be silently
        absorbed into its shard and conservation would break.  Only
        runs while our gate has ejected peers (``gate_rehome``)."""
        bad = self._gate_bad
        with self._peer_mu:
            mpick = self._picker
        if not bad or not mpick.peers() \
                or not self._uses_default_hash(mpick):
            return out
        peers_l = mpick.owner_peers()
        bad_pi = [pi for pi, p in enumerate(peers_l)
                  if p.info.grpc_address in bad]
        if not bad_pi:
            return out
        from .hashing import mix64_np

        raw = mix64_np(parsed["khash_raw"])
        owners = mpick.owner_indices(raw)
        # GLOBAL rows excluded alongside the state-mutating behaviors:
        # as acting owner we queue their broadcast state already —
        # degrading them too would double-queue the hits
        mask = (np.isin(owners, bad_pi)
                & ((parsed["behavior"]
                    & int(self._DEGRADED_EXCLUDED
                          | Behavior.GLOBAL)) == 0))
        if not mask.any():
            return out
        gm = self._ensure_global_manager()
        for k, tlv, a, _i in self._raw_queue_groups(parsed, data, mask,
                                                    stamp_ms=stamp_ms):
            gm.queue_hits_raw(k, tlv, a, degraded=True)
        # flag the masked rows: re-serialize just those items with the
        # degraded metadata (pb2 — metadata has no C++ lane; this path
        # only runs mid-outage)
        ro, rl, _rs = _wire_native.split_resp_items(out)
        items: List[bytes] = []
        by_addr: Dict[str, int] = {}
        for j in range(parsed["n"]):
            tlv = out[int(ro[j]):int(ro[j] + rl[j])]
            if mask[j]:
                m = pb.GetRateLimitsResp.FromString(tlv)
                r = m.responses[0]
                if not r.error:
                    addr = peers_l[int(owners[j])].info.grpc_address
                    r.metadata["degraded"] = "true"
                    r.metadata["degraded_peer"] = addr
                    by_addr[addr] = by_addr.get(addr, 0) + 1
                    tlv = m.SerializeToString()
            items.append(tlv)
        for addr, cnt in by_addr.items():
            self.metrics.degraded_served.labels(peer_addr=addr).inc(cnt)
        if by_addr:
            rows = sum(by_addr.values())
            ana = self.dispatcher.analytics
            tenant = None
            if ana is not None:
                kh0 = int(raw[mask][0])
                tenant = ana.tenant_hint(khash=kh0)
                ana.tap_flag("degraded", rows, khash=kh0)
            from .tracing import current_span_id, force_sample

            force_sample("degraded")
            ev = {"peer": min(by_addr), "rows": rows, "rehomed": True}
            if tenant is not None:
                ev["tenant"] = tenant
            sid = current_span_id()
            if sid is not None:
                ev["span_id"] = sid
            self.recorder.record("degraded", **ev)
        return b"".join(items)

    @staticmethod
    def _raw_queue_groups(parsed: dict, data: bytes, mask: np.ndarray,
                          stamp_ms: Optional[int] = None):
        """(khash, last-occurrence TLV, summed hits, last row index)
        per unique masked key — the shared aggregation for the raw
        async queues (LAST occurrence: a mid-batch config change must
        win, matching the object-path producers).

        ``stamp_ms`` stamps ``created_at`` (field 10) onto yielded TLVs
        that don't already carry one: hit-queue prototypes apply at the
        owner LATER (flush/reconcile cadence), and applying them at the
        owner's then-clock on a row living on the request's time base
        reads as expired → bucket reset → the reconciled hits silently
        vanish (the cold-key conservation loss, reconcile edition)."""
        idx = np.nonzero(mask)[0]
        if not idx.size:
            return
        from .wire import tlv_with_created

        toff, tlen = parsed["tlv_off"], parsed["tlv_len"]
        created = parsed["created_at"]
        w = np.maximum(parsed["hits"][idx], 0)
        uniq, inv = np.unique(parsed["khash_raw"][idx],
                              return_inverse=True)
        # exact int64 accumulation (bincount's float64 weights would
        # round sums past 2^53 — the object-path producers are exact
        # Python ints, and conservation must match across lanes)
        acc = np.zeros(uniq.size, np.int64)
        np.add.at(acc, inv, w)
        last = np.zeros(uniq.size, np.int64)
        last[inv] = np.arange(inv.size)
        stamping = _created_at_fwd_enabled()
        for k, f, a in zip(uniq, last, acc):
            i = int(idx[int(f)])
            tlv = bytes(data[int(toff[i]):int(toff[i] + tlen[i])])
            if stamping and stamp_ms is not None \
                    and not int(created[i]):
                tlv = tlv_with_created(tlv, stamp_ms)
            yield (int(k), tlv, int(a), i)

    def _queue_mr_raw(self, parsed: dict, data: bytes,
                      mask: np.ndarray,
                      stamp_ms: Optional[int] = None) -> None:
        """Queue cross-region replication for locally-decided
        MULTI_REGION rows, zero per-request objects (the wire-lane twin
        of the object path's mr.queue_hits calls)."""
        mr = self._ensure_mr_manager()
        for k, tlv, a, _i in self._raw_queue_groups(parsed, data, mask,
                                                    stamp_ms=stamp_ms):
            mr.queue_hits_raw(k, tlv, a)

    def _queue_global_updates_raw(self, parsed: dict, data: bytes,
                                  mask: np.ndarray) -> None:
        """Owner side of forwarded GLOBAL rows: mark each unique key
        changed for the next broadcast tick (queue_update_raw), as
        get_peer_rate_limits does per request on the object path."""
        gm = self._ensure_global_manager()
        # clock-ok: broadcast marking only — queue_update_raw records WHICH keys changed, applies no hits, needs no created_at stamp
        for k, tlv, _a, _i in self._raw_queue_groups(parsed, data, mask):
            gm.queue_update_raw(k, tlv)

    def _wire_global_runner(self, parsed: dict, now: int):
        """Where a solo daemon's GLOBAL call is served: the mesh tier
        under ``global_mode=mesh`` while it is routable, the owner rows
        of the sharded step otherwise (the object path's queue_update
        broadcasts to no one).  Returns a zero-argument executor, or
        None when the mesh runner hands a per-request case to the
        object path (a pinned key whose config changed — it demotes).

        All gating runs here, before any state mutation, so a None
        return leaves the instance untouched for the fallback.
        """
        if self._mesh_routable():
            # qualifying rows ride the mesh tier (ISSUE 7); degraded or
            # stood down, the owner rows below serve — always correct,
            # reconciled by the gRPC queues
            return self._wire_mesh_runner(parsed, now)
        return lambda: self._wire_check_columns(parsed, now)

    def _wire_mesh_runner(self, parsed: dict, now: int):
        """Columnar mesh-GLOBAL flow (ISSUE 7; the wire-lane twin of
        ``_mesh_route``): qualifying GLOBAL rows serve on the
        mesh-resident replica tier — pinned on first touch, in ONE
        batched upload — everything else rides the sharded step.
        Returns a zero-argument executor, or None when a pinned key's
        config changed (the object path demotes it with state
        intact)."""
        from .core.batch import pack_columns
        from .hashing import mix64_np

        # route.* (ISSUE 24): per call, wall AND thread CPU — 32
        # handler threads route on one GIL, and wall − CPU is the wait
        disp = self.dispatcher
        n = parsed["n"]
        with phase("route.pack", disp, cpu=True):
            kh = mix64_np(parsed["khash_raw"])
            kh = np.where(kh == 0, np.uint64(1), kh)
            batch, errs = pack_columns(
                kh, parsed["hits"], parsed["limit"], parsed["duration"],
                parsed["algorithm"], parsed["behavior"], parsed["burst"],
                now, created_at=parsed.get("created_at"),
                sink=self.dispatcher)
            beh = np.asarray(batch.behavior)
            glob_mask = (beh & int(Behavior.GLOBAL)) != 0
            excluded = (beh & int(self._REPLICA_EXCLUDED)) != 0
            mesh_mask = glob_mask & ~excluded & np.asarray(batch.valid)
            mge = self._ensure_meshglobal()
        pins: List[tuple] = []
        if mesh_mask.any():
            with phase("route.keys", disp, cpu=True):
                # one config per key per batch (pinned OR to-pin): a
                # mid-batch config change, or a pinned key's changed
                # config, takes the object path, which demotes/serves
                # it per request with exact semantics
                idx = np.nonzero(mesh_mask)[0]
                groups = _group_key_configs(kh, idx, batch,
                                            mge.pinned_cfg)
                if groups is None:
                    return None
                keys, unpinned = groups
                # first touches only (never in a warm tier): the pin
                # adopts the config of the key's first row
                for k in unpinned:
                    i = int(idx[keys.index(k)])
                    pins.append((RateLimitRequest(
                        name="", unique_key="",
                        hits=int(batch.hits[i]),
                        limit=int(batch.limit[i]),
                        duration=int(batch.duration[i]),
                        algorithm=int(batch.algorithm[i]),
                        behavior=int(beh[i]),
                        burst=int(batch.burst[i])),
                        k, self._seed_row(k)))
        refused = set()
        if pins:
            with phase("route.pin", disp, cpu=True):
                ok = mge.pin_many(pins, now)
                for (proto, ik, _s), good in zip(pins, ok):
                    if good:
                        self._seed_commit(ik)
                    elif not self._mesh_admit(proto, ik, now):
                        # window full, nothing colder → sharded path
                        refused.add(ik)
                if refused:
                    mesh_mask[idx] = [k not in refused for k in keys]

        # Fused single-launch path (ISSUE 8): a fused engine serves the
        # WHOLE batch — mesh rows on the home replica + accumulator,
        # sharded rows on the serving kernel — in ONE device program,
        # deleting the second (meshglobal.check_columns) dispatch this
        # runner otherwise pays per batch.  The mslot column carries
        # each mesh row's pinned replica slot; -1 = sharded lane.
        mslot_col = None
        if getattr(self.engine, "mesh_bound", False) and mesh_mask.any():
            with phase("route.slots", disp, cpu=True):
                with mge._mu:
                    smap = dict(mge.slots)
                for k in refused:  # sharded even if pinned since
                    smap.pop(k, None)
                # -1: unpinned underneath us — sharded is correct
                slot_rows = np.fromiter((smap.get(k, -1) for k in keys),
                                        np.int32, len(keys))
                mesh_mask[idx] = slot_rows >= 0
                if mesh_mask.any():
                    mslot_col = np.full(n, -1, np.int32)
                    mslot_col[idx] = slot_rows

        def run_fused() -> bytes:
            st, lim_o, rem, rst, full = self.dispatcher.check_packed(
                batch, kh, now, mslot=mslot_col)
            errors: Optional[list] = None
            if full.any():
                errors = [None] * n
                for j in np.nonzero(full)[0]:
                    errors[int(j)] = ("mesh-global row lost"
                                      if mslot_col[int(j)] >= 0
                                      else "rate limit table full")
            if errs:
                errors = errors or [None] * n
                for i, emsg in errs.items():
                    errors[i] = emsg
            self.metrics.over_limit_counter.inc(int((st == 1).sum()))
            with phase("build", disp):
                return _wire_native.build_rate_limit_resps(
                    np.asarray(st, np.int64), lim_o, rem, rst, errors)

        if mslot_col is not None:
            return run_fused

        def run() -> bytes:
            status = np.zeros(n, np.int64)
            rem = np.zeros(n, np.int64)
            rst = np.zeros(n, np.int64)
            lim_o = np.zeros(n, np.int64)
            errors: Optional[list] = None
            shard_mask = ~mesh_mask
            if shard_mask.any():
                idx = np.nonzero(shard_mask)[0]
                sub = type(batch)(*[np.asarray(c)[idx] for c in batch])
                s_st, s_lim, s_rem, s_rst, s_full = \
                    self.dispatcher.check_packed(sub, kh[idx], now)
                status[idx] = s_st
                lim_o[idx] = s_lim
                rem[idx] = s_rem
                rst[idx] = s_rst
                if s_full.any():
                    errors = [None] * n
                    for j in np.nonzero(s_full)[0]:
                        errors[int(idx[j])] = "rate limit table full"
            if mesh_mask.any():
                idx = np.nonzero(mesh_mask)[0]
                sub = type(batch)(*[np.asarray(c)[idx] for c in batch])
                m_st, m_rem, m_rst, m_lim, m_lost = mge.check_columns(
                    sub, kh[idx], now)
                status[idx] = m_st
                rem[idx] = m_rem
                rst[idx] = m_rst
                lim_o[idx] = m_lim
                if m_lost.any():
                    errors = errors or [None] * n
                    for j in np.nonzero(m_lost)[0]:
                        errors[int(idx[j])] = "mesh-global row lost"
            if errs:
                errors = errors or [None] * n
                for i, emsg in errs.items():
                    errors[i] = emsg
            self.metrics.over_limit_counter.inc(int((status == 1).sum()))
            return _wire_native.build_rate_limit_resps(
                status, lim_o, rem, rst, errors)

        return run

    def _packed_check_to_bytes(self, kh: np.ndarray, hits, limit, duration,
                               algorithm, behavior, burst, now: int,
                               created=None) -> bytes:
        """Columns → pack → device step → response wire bytes: the
        shared fast-lane body (solo client wire, peer wire, and the
        clustered lane's local sub-batch all end here)."""
        from .core.batch import pack_columns

        batch, errs = pack_columns(kh, hits, limit, duration, algorithm,
                                   behavior, burst, now,
                                   created_at=created)
        return self._packed_batch_to_bytes(batch, errs, kh, now)

    def _packed_batch_to_bytes(self, batch, errs: dict, kh: np.ndarray,
                               now: int) -> bytes:
        """A packed call → device step → response wire bytes.  Resolves
        from the dispatcher's ResultView — row bounds into the wave's
        shared downloaded result columns — and serializes straight from
        them in THIS caller's thread (ops/_native.cpp ›
        build_responses_from_columns), so response build never runs on
        the dispatch worker and materializes no per-job column
        tuples."""
        view = self.dispatcher.check_packed_view(batch, kh, now)
        status = view.cols[0][view.lo:view.hi]
        full = view.cols[4][view.lo:view.hi]
        self.metrics.over_limit_counter.inc(int((status == 1).sum()))
        errors = None
        if errs or full.any():
            # errored rows already come back zeroed from the device
            # (invalid/overfull rows are masked out)
            errors = [None] * len(kh)
            for i, emsg in errs.items():
                errors[i] = emsg
            for i in np.nonzero(full)[0]:
                if errors[int(i)] is None:
                    errors[int(i)] = "rate limit table full"
        with phase("build", self.dispatcher):
            resp = _wire_native.build_responses_from_columns(
                view.cols, view.lo, view.hi, errors)
        return resp

    def _wire_check_columns(self, parsed: dict, now: int) -> bytes:
        """Parsed wire columns → device step → serialized responses
        (identical for the client and peer wire)."""
        from .core.batch import pack_columns
        from .hashing import mix64_np

        # local.pack (ISSUE 33): what a call on this lane does in its
        # own thread before it is queued — hash, pack, lay out — wall
        # AND thread CPU, as route.* (on 32 handler threads and one GIL
        # the difference is waiting), sampled as `handler` is.  The
        # fused lane (any shard count) does all of it in one C++ pass
        # inside `ingest` and never comes here: this is the lane of
        # what that pass declines — Gregorian rows, a MULTI_REGION row,
        # a call over the largest bucket, the gated peer wire, a
        # checkout without the extension's ingest.
        disp = self.dispatcher
        with phase("local.pack", disp, cpu=True, every=disp.call_sample):
            kh = mix64_np(parsed["khash_raw"])
            kh = np.where(kh == 0, np.uint64(1), kh)
            batch, errs = pack_columns(
                kh, parsed["hits"], parsed["limit"], parsed["duration"],
                parsed["algorithm"], parsed["behavior"], parsed["burst"],
                now, created_at=parsed.get("created_at"), sink=disp)
            disp.lay_out(batch, kh, None)  # check_packed_view finds it done
        return self._packed_batch_to_bytes(batch, errs, kh, now)

    def _wire_check_clustered(self, parsed: dict, data: bytes, now: int
                              ) -> bytes:
        """Clustered wire fast lane (the cluster twin of
        ``_wire_check_columns``): C++ parse → batch hash → vectorized
        ring split by owner → forward each remote owner's sub-batch as
        verbatim request-TLV slices over the peer wire (framing is
        byte-compatible: GetRateLimitsReq.requests and
        GetPeerRateLimitsReq.requests are both field 1) → device step
        for owned keys, overlapped with the forward RPCs → splice
        response TLVs back together in request order.

        Zero per-request Python objects end to end; the owner side rides
        get_peer_rate_limits_wire's columnar lane.  A failed forward
        degrades to per-request error responses for that sub-batch only,
        mirroring the object path's per-request forward errors.

        GLOBAL rows (global.go semantics, SURVEY §3.3): answered from
        the LOCAL replica — never forwarded — with hits queued for async
        reconcile to the owner (raw TLV prototypes, aggregated per
        unique key; global_manager.queue_hits_raw/queue_update_raw)."""
        from .hashing import mix64_np

        n = parsed["n"]
        raw = mix64_np(parsed["khash_raw"])
        with self._peer_mu:
            membership = self._picker
        # route by the health-gated ring (ISSUE 5): long-dead owners
        # are ejected and their keys rehome; healthy clusters get the
        # membership picker itself back (pickers are immutable, so the
        # lookups below run lock-free)
        picker = self._routing_picker()
        peer_list = picker.owner_peers()
        # pre-zero-remap, matching picker.get(key)'s hash pipeline
        owners = picker.owner_indices(raw)
        kh = np.where(raw == 0, np.uint64(1), raw)
        toff, tlen = parsed["tlv_off"], parsed["tlv_len"]

        self_pi = [pi for pi, p in enumerate(peer_list) if self.is_self(p)]
        local_mask = np.isin(owners, self_pi)
        # rows rehomed to us by an ejection are DEGRADED serves: answer
        # locally, flag the response, and queue the hits for reconcile
        # to the membership owner — never silently authoritative
        deg_mask = _NO_ROWS
        m_owners = m_peers = None
        if (picker is not membership and membership.peers()
                and getattr(self.config.behaviors,
                            "peer_degraded_fallback", True)):
            m_peers = membership.owner_peers()
            m_owners = membership.owner_indices(raw)
            m_self = [pi for pi, p in enumerate(m_peers)
                      if self.is_self(p)]
            deg_mask = (local_mask & ~np.isin(m_owners, m_self)
                        & ((parsed["behavior"]
                            & int(self._DEGRADED_EXCLUDED)) == 0))
        # behavior_or gates the column scan: GLOBAL-free batches (the
        # common clustered shape) pay nothing here
        if parsed["behavior_or"] & int(Behavior.GLOBAL):
            glob_mask = (parsed["behavior"] & int(Behavior.GLOBAL)) != 0
        else:
            glob_mask = _NO_ROWS
        glob_queue: List[tuple] = []
        if glob_mask.any():
            # every GLOBAL row is served locally; collect the reconcile
            # work per UNIQUE key (hot keys repeat, so this loop is
            # short even for big batches).  The owner-side queue_update
            # entries are ENQUEUED ONLY AFTER the local step below: a
            # broadcast tick firing in between would gather a row that
            # doesn't exist yet and silently drop the update (observed
            # as a cold-compile-window flake).
            # shared aggregation (_raw_queue_groups): unmixed-khash
            # queue keys — the same key space as the peer-wire
            # producers — with last-occurrence TLV prototypes
            for k, tlv, a, i in self._raw_queue_groups(
                    parsed, data, glob_mask, stamp_ms=now):
                glob_queue.append(
                    (k, tlv, a, int(owners[i]) in self_pi))
            local_mask = local_mask | glob_mask
            if deg_mask.size:
                # GLOBAL rows already answer from the replica with
                # their own reconcile queues — degrading them too would
                # double-queue the hits
                deg_mask = deg_mask & ~glob_mask
        item_tlvs: List[Optional[bytes]] = [None] * n

        # fire remote forwards first so the local device step overlaps:
        # each owner's sub-batch enters its peer's pooled send buffer
        # (peer_client.py › forward_raw) — concurrent callers
        # forwarding to the same owner share flush RPCs, with depth-K
        # in flight; a dead peer fails fast via ErrCircuitOpen instead
        # of queuing every caller behind its timeouts.  The TLV slices
        # join through ONE memoryview (no per-slice bytes copies).
        created = parsed["created_at"]
        groups = []
        for pi in np.unique(owners[~local_mask]):
            # ~local_mask also excludes GLOBAL rows that share an owner
            # with forwarded rows: they were answered locally above and
            # reconcile asynchronously — forwarding them too would
            # double-debit the owner
            idxs = np.nonzero((owners == pi) & ~local_mask)[0]
            # stamp OUR accepted-at clock (field 10) onto each slice
            # that doesn't already carry one: the owner applies the
            # rows at this caller's time base instead of its own wall
            # clock — mixing bases resets cold bucket rows and loses
            # their debits (types.RateLimitRequest.created_at)
            if _created_at_fwd_enabled():
                sub = _wire_native.stamp_req_tlvs(
                    data, toff[idxs], tlen[idxs], created[idxs], now)
            else:  # pre-fix behavior (racer/regression demos only)
                sub = b"".join(
                    bytes(data[int(toff[i]):int(toff[i] + tlen[i])])
                    for i in idxs)
            fut = send_err = None
            try:
                fut = peer_list[int(pi)].forward_raw(sub, int(idxs.size))
            except Exception as e:  # noqa: BLE001 - incl. ErrClosing /
                # ErrCircuitOpen (fail-fast: degraded local answers —
                # or per-request error rows — for this sub-batch only)
                send_err = e
            groups.append((idxs, fut, send_err,
                           peer_list[int(pi)].info.grpc_address))

        over = 0  # remote OVER_LIMITs (the local step counts its own)
        # rehomed rows serve DEGRADED (flag + reconcile queue), apart
        # from the normal local step
        if deg_mask.size and deg_mask.any():
            for pi in np.unique(m_owners[deg_mask]):
                didx = np.nonzero(deg_mask & (m_owners == pi))[0]
                addr = m_peers[int(pi)].info.grpc_address
                try:
                    tlvs = self._serve_degraded_wire(
                        parsed, data, didx, kh, now, addr)
                    for j, i in enumerate(didx):
                        item_tlvs[int(i)] = tlvs[j]
                except Exception as e:  # noqa: BLE001 - degraded serve
                    # must never take the whole batch down
                    log.warning("degraded serve for %d rehomed rows "
                                "(owner %s) failed: %s", didx.size,
                                addr, exc_text(e))
            local_mask = local_mask & ~deg_mask
        local_idx = np.nonzero(local_mask)[0]
        if local_idx.size:
            lbytes = self._packed_check_to_bytes(
                kh[local_idx], parsed["hits"][local_idx],
                parsed["limit"][local_idx], parsed["duration"][local_idx],
                parsed["algorithm"][local_idx],
                parsed["behavior"][local_idx],
                parsed["burst"][local_idx], now,
                created=created[local_idx])
            lo, ll, _ = _wire_native.split_resp_items(lbytes)
            for j, i in enumerate(local_idx):
                item_tlvs[int(i)] = lbytes[int(lo[j]):int(lo[j] + ll[j])]
        if glob_queue:
            # rows exist now (the step above wrote them): safe to queue
            # owner-side updates for the next broadcast tick
            gm = self._ensure_global_manager()
            for k, tlv, a, own in glob_queue:
                if own:
                    gm.queue_update_raw(k, tlv)
                else:
                    gm.queue_hits_raw(k, tlv, a)
        # locally-OWNED MULTI_REGION rows replicate cross-region async
        # (forwarded MR rows are queued by their owner; GLOBAL rows
        # never MR-queue — object-path precedence).  behavior_or-gated
        # like the GLOBAL scan above.
        if parsed["behavior_or"] & int(Behavior.MULTI_REGION):
            not_glob = ~glob_mask if glob_mask.size else True
            mr_mask = (np.isin(owners, self_pi) & not_glob
                       & ((parsed["behavior"]
                           & int(Behavior.MULTI_REGION)) != 0))
            if mr_mask.any():
                self._queue_mr_raw(parsed, data, mr_mask,
                                   stamp_ms=now)

        # lane futures always resolve (RPC deadline + bounded retries +
        # explicit failure paths); the wait bound below is that worst
        # case plus slack, a belt against a lane bug parking a caller
        b = self.config.behaviors
        fwd_wait = ((b.peer_retry_limit + 1)
                    * (b.batch_timeout_ms / 1000.0 + 60.0)
                    + b.peer_retry_limit * b.peer_retry_backoff_ms
                    / 1000.0 + 5.0)
        for idxs, fut, send_err, addr in groups:
            rbytes, err, sp = None, send_err, None
            if fut is not None:
                try:
                    rbytes = fut.result(timeout=fwd_wait)
                except Exception as e:  # noqa: BLE001
                    err = e
            if rbytes is not None:
                sp = _wire_native.split_resp_items(rbytes)
                if sp is None or sp[0].size != idxs.size:
                    err = RuntimeError(
                        "malformed or short peer response batch")
                    sp = None
            if sp is None:
                self.metrics.check_error_counter.labels(
                    error="peer_forward").inc(int(idxs.size))
                self.metrics.forward_failed.labels(
                    peer_addr=addr,
                    reason=_forward_fail_reason(err)).inc(int(idxs.size))
                served = self._degrade_failed_forward(
                    parsed, data, idxs, kh, now, addr, item_tlvs)
                rest = idxs[~served]
                if rest.size:
                    z32 = np.zeros(rest.size, np.int32)
                    z64 = np.zeros(rest.size, np.int64)
                    # exc_text: a grpc deadline/TimeoutError str()s
                    # empty — the row must stay diagnosable; the peer
                    # address attributes WHICH owner failed
                    ebytes = _wire_native.build_rate_limit_resps(
                        z32, z64, z64, z64,
                        [f"while fetching rate limit from peer {addr}: "
                         f"{exc_text(err)}"] * int(rest.size))
                    eo, el, _ = _wire_native.split_resp_items(ebytes)
                    for j, i in enumerate(rest):
                        item_tlvs[int(i)] = \
                            ebytes[int(eo[j]):int(eo[j] + el[j])]
                continue
            ro, rl, rs = sp
            over += int((rs == 1).sum())
            for j, i in enumerate(idxs):
                item_tlvs[int(i)] = rbytes[int(ro[j]):int(ro[j] + rl[j])]

        self.metrics.over_limit_counter.inc(over)
        if any(t is None for t in item_tlvs):
            # belt: a failed degraded serve must still answer its rows
            miss = [i for i, t in enumerate(item_tlvs) if t is None]
            z32 = np.zeros(len(miss), np.int32)
            z64 = np.zeros(len(miss), np.int64)
            ebytes = _wire_native.build_rate_limit_resps(
                z32, z64, z64, z64,
                ["degraded-mode serve failed"] * len(miss))
            eo, el, _ = _wire_native.split_resp_items(ebytes)
            for j, i in enumerate(miss):
                item_tlvs[i] = ebytes[int(eo[j]):int(eo[j] + el[j])]
        return b"".join(item_tlvs)

    # ---- degraded-mode owner fallback (ISSUE 5) ------------------------

    #: behaviors that must NOT be served from a non-authoritative row:
    #: RESET/DRAIN mutate state the reconcile queue cannot carry, and
    #: MULTI_REGION replication must originate from the region owner
    _DEGRADED_EXCLUDED = (Behavior.RESET_REMAINING
                          | Behavior.DRAIN_OVER_LIMIT
                          | Behavior.MULTI_REGION)

    def _serve_degraded_wire(self, parsed: dict, data: bytes,
                             idxs: np.ndarray, kh: np.ndarray, now: int,
                             peer_addr: str) -> List[bytes]:
        """Answer ``idxs`` from the LOCAL shard in degraded mode: one
        device step over the sub-batch, responses flagged with
        ``metadata.degraded`` (pb2-built — the C++ response builder has
        no metadata lane, and degraded serving is off the happy path by
        definition), and the hits queued per unique key into the GLOBAL
        hit-flush queues for reconcile to the owner — bounded staleness
        instead of unavailability.  Returns one response TLV per row of
        ``idxs``."""
        from .core.batch import pack_columns
        from .wire import _varint

        m = int(idxs.size)
        batch, errs = pack_columns(
            kh[idxs], parsed["hits"][idxs], parsed["limit"][idxs],
            parsed["duration"][idxs], parsed["algorithm"][idxs],
            parsed["behavior"][idxs], parsed["burst"][idxs], now,
            created_at=parsed["created_at"][idxs])
        view = self.dispatcher.check_packed_view(batch, kh[idxs], now)
        st, lim, rem, rst, full = view.sliced()
        self.metrics.over_limit_counter.inc(int((st == 1).sum()))
        out: List[bytes] = []
        for j in range(m):
            msg = pb.RateLimitResp(
                status=int(st[j]), limit=int(lim[j]),
                remaining=int(rem[j]), reset_time=int(rst[j]))
            if errs and j in errs:
                msg.error = errs[j]
            elif bool(full[j]):
                msg.error = "rate limit table full"
            else:
                msg.metadata["degraded"] = "true"
                msg.metadata["degraded_peer"] = peer_addr
            payload = msg.SerializeToString()
            out.append(b"\x0a" + _varint(len(payload)) + payload)
        # reconcile-on-recovery: aggregate this sub-batch's hits per
        # unique key into the raw hit queue (the owner applies them
        # once reachable; failed flushes requeue — global_manager.py)
        mask = np.zeros(parsed["n"], bool)
        mask[idxs] = True
        gm = self._ensure_global_manager()
        for k, tlv, a, _i in self._raw_queue_groups(parsed, data, mask,
                                                    stamp_ms=now):
            gm.queue_hits_raw(k, tlv, a, degraded=True)
        self.metrics.degraded_served.labels(peer_addr=peer_addr).inc(m)
        ana = self.dispatcher.analytics
        tenant = None
        if ana is not None and idxs.size:
            kh0 = int(kh[idxs][0])
            tenant = ana.tenant_hint(khash=kh0)
            ana.tap_flag("degraded", m, khash=kh0)
        from .tracing import current_span_id, force_sample

        force_sample("degraded")
        ev = {"peer": peer_addr, "rows": m}
        if tenant is not None:
            ev["tenant"] = tenant
        sid = current_span_id()
        if sid is not None:
            ev["span_id"] = sid
        self.recorder.record("degraded", **ev)
        return out

    def _degrade_failed_forward(self, parsed: dict, data: bytes,
                                idxs: np.ndarray, kh: np.ndarray,
                                now: int, addr: str,
                                item_tlvs: List[Optional[bytes]]
                                ) -> np.ndarray:
        """Failed-forward fallback: serve the eligible rows of a failed
        sub-batch degraded (writes into ``item_tlvs``); returns the
        boolean mask (aligned with ``idxs``) of rows served.  Rows with
        excluded behaviors — or everything, when the fallback is
        disabled — stay unserved for the caller's error rows."""
        served = np.zeros(int(idxs.size), bool)
        if not getattr(self.config.behaviors,
                       "peer_degraded_fallback", True):
            return served
        elig = (parsed["behavior"][idxs]
                & int(self._DEGRADED_EXCLUDED)) == 0
        if not elig.any():
            return served
        sub = idxs[elig]
        try:
            tlvs = self._serve_degraded_wire(parsed, data, sub, kh,
                                             now, addr)
        except Exception as e:  # noqa: BLE001 - fall back to error rows
            log.warning("degraded serve for %d rows (owner %s) "
                        "failed: %s", sub.size, addr, exc_text(e))
            return served
        for j, i in enumerate(sub):
            item_tlvs[int(i)] = tlvs[j]
        served[elig] = True
        return served

    @staticmethod
    def _req_stamped(req: RateLimitRequest, now: int) -> RateLimitRequest:
        """The request with ``created_at`` defaulted to its serving
        time base — REQUIRED before queueing it for deferred hit
        application (GLOBAL reconcile, cross-region replication): the
        flush applies at the owner later, and without the stamp the
        owner's then-clock reads a row living on the request's base as
        expired → bucket reset → the deferred hits silently vanish."""
        if req.created_at or not _created_at_fwd_enabled():
            return req
        return replace(req, created_at=now)

    def _get_rate_limits(self, reqs, now) -> List[RateLimitResponse]:
        n = len(reqs)
        responses: List[Optional[RateLimitResponse]] = [None] * n
        local_idx: List[int] = []
        meshl: List[tuple[int, int]] = []  # mesh-GLOBAL (idx, key hash)
        fwd: List[tuple[int, PeerClient, RateLimitRequest]] = []

        have_peers = bool(self.peers())
        glob_q: List[tuple] = []  # (req, we_are_owner), queued post-step
        # routing picker hoisted out of the hot loop (health-gated
        # ring, ISSUE 5); membership picker alongside so rehomed rows
        # are recognized as DEGRADED serves, not silently authoritative
        rpick = self._routing_picker() if have_peers else None
        with self._peer_mu:
            mpick = self._picker
        gate_active = have_peers and rpick is not mpick
        deg_local: List[tuple] = []  # (idx, membership owner addr)
        # hot loop: plain-int flag tests (IntFlag.__and__ costs ~µs each
        # and this loop runs per request)
        GLOBAL = int(Behavior.GLOBAL)
        MULTI_REGION = int(Behavior.MULTI_REGION)
        NO_BATCHING = int(Behavior.NO_BATCHING)
        DEGRADED_EXCL = int(self._DEGRADED_EXCLUDED)
        for i, req in enumerate(reqs):
            if not req.unique_key:
                responses[i] = RateLimitResponse(
                    error="field 'unique_key' cannot be empty")
                continue
            if not req.name:
                responses[i] = RateLimitResponse(
                    error="field 'name' cannot be empty")
                continue
            behavior = int(req.behavior)
            if behavior & GLOBAL:
                # Pod-local (no peers other than ourselves) under
                # global_mode=mesh: ALL qualifying GLOBAL keys ride
                # the mesh-resident replica tier (ISSUE 7,
                # parallel/meshglobal.py) — no queues at all.  A False
                # return (excluded flags, window full, degraded
                # stand-down) takes the owner-sharded path below.
                if self._mesh_routable() and \
                        self._mesh_route(req, meshl, i, now):
                    continue
                # Otherwise: answer from the local replica now, reconcile
                # hits to the owner asynchronously (global.go semantics).
                # Owner-side queue_update is deferred until AFTER the
                # local step below — a broadcast tick firing first would
                # gather a not-yet-written row and drop the update.
                local_idx.append(i)
                owner = self.owner_of(req.key) if have_peers else None
                glob_q.append(
                    (req, owner is None or self.is_self(owner)))
                continue
            if not have_peers:
                local_idx.append(i)
                if behavior & MULTI_REGION:
                    self._ensure_mr_manager().queue_hits(
                        self._req_stamped(req, now))
                continue
            try:
                owner = rpick.get(req.key) if rpick.peers() else None
            except RuntimeError:
                owner = None
            if owner is None or self.is_self(owner):
                local_idx.append(i)
                if gate_active and not (behavior & DEGRADED_EXCL):
                    # rehomed to us by an ejection? serve DEGRADED:
                    # flag the response and reconcile the hits to the
                    # membership owner once it is back
                    try:
                        mowner = (mpick.get(req.key)
                                  if mpick.peers() else None)
                    except RuntimeError:
                        mowner = None
                    if mowner is not None and not self.is_self(mowner):
                        deg_local.append((i, mowner.info.grpc_address))
                # local-region owner replicates cross-DC asynchronously
                if behavior & MULTI_REGION:
                    self._ensure_mr_manager().queue_hits(
                        self._req_stamped(req, now))
            else:
                fwd.append((i, owner, req))

        # forwards first (async futures), so the device step overlaps RPCs
        futures: List[tuple] = []
        for i, peer, req in fwd:
            if not req.created_at and _created_at_fwd_enabled():
                # stamp OUR accepted-at clock so the owner applies the
                # request at this caller's time base (first hop wins;
                # rides the TLV as field 10 — wire.req_to_tlv)
                req = replace(req, created_at=now)
            if int(req.behavior) & NO_BATCHING:
                f: Future = Future()

                def _go(peer=peer, req=req, f=f):
                    try:
                        f.set_result(peer.get_peer_rate_limit(req))
                    except Exception as e:  # noqa: BLE001
                        f.set_exception(e)

                threading.Thread(target=_go, daemon=True,
                                 name="peer-forward-nobatch").start()
            else:
                try:
                    f = peer.enqueue(req)
                except Exception as e:  # noqa: BLE001 - incl. ErrClosing
                    f = Future()
                    f.set_exception(e)
            futures.append((i, f, peer.info.grpc_address, req))

        if meshl:
            m_reqs = [reqs[i] for i, _ in meshl]
            m_resps = self._meshglobal.check_batch(
                m_reqs, [h for _, h in meshl], now)
            for (i, _), resp in zip(meshl, m_resps):
                responses[i] = resp
                if resp.status == Status.OVER_LIMIT:
                    self.metrics.over_limit_counter.inc()
            # Store write-through covers mesh keys too (home-replica
            # values are exact; the fold converges the other replicas)
            self._after_local(m_reqs, m_resps)

        if local_idx:
            local_reqs = [reqs[i] for i in local_idx]
            self._read_through(local_reqs)
            local_resps = self.dispatcher.check_batch(local_reqs, now)
            for i, resp in zip(local_idx, local_resps):
                responses[i] = resp
                if resp.status == Status.OVER_LIMIT:
                    self.metrics.over_limit_counter.inc()
            self._after_local(
                [reqs[i] for i in local_idx],
                [responses[i] for i in local_idx])
        if deg_local:
            gm = self._ensure_global_manager()
            for i, addr in deg_local:
                resp = responses[i]
                if resp is None or resp.error:
                    continue
                resp.metadata["degraded"] = "true"
                resp.metadata["degraded_peer"] = addr
                gm.queue_hits(self._req_stamped(reqs[i], now),
                              degraded=True)
                self.metrics.degraded_served.labels(
                    peer_addr=addr).inc()
        if glob_q:
            gm = self._ensure_global_manager()
            for req, own in glob_q:
                if own:
                    gm.queue_update(req)  # row written by the step above
                else:
                    gm.queue_hits(self._req_stamped(req, now))

        timeout = (self.config.behaviors.batch_timeout_ms
                   + self.config.behaviors.batch_wait_ms) / 1000.0 + 30.0
        deg_ok = getattr(self.config.behaviors,
                         "peer_degraded_fallback", True)
        deg_failed: List[tuple] = []  # (idx, req, owner addr)
        for i, f, addr, req in futures:
            try:
                responses[i] = f.result(timeout=timeout)
                if responses[i].status == Status.OVER_LIMIT:
                    self.metrics.over_limit_counter.inc()
            except Exception as e:  # noqa: BLE001
                self.metrics.check_error_counter.labels(
                    error="peer_forward").inc()
                self.metrics.forward_failed.labels(
                    peer_addr=addr,
                    reason=_forward_fail_reason(e)).inc()
                if deg_ok and not (int(req.behavior) & DEGRADED_EXCL):
                    deg_failed.append((i, req, addr))
                else:
                    responses[i] = RateLimitResponse(
                        error=f"while fetching rate limit from peer "
                              f"{addr}: {exc_text(e)}")
        if deg_failed:
            # degraded-mode owner fallback (ISSUE 5): answer the failed
            # forwards from the local shard, flag them, and reconcile
            # the hits through the GLOBAL hit-flush queues
            try:
                dresps = self.dispatcher.check_batch(
                    [req for _, req, _ in deg_failed], now)
                gm = self._ensure_global_manager()
                for (i, req, addr), resp in zip(deg_failed, dresps):
                    if not resp.error:
                        resp.metadata["degraded"] = "true"
                        resp.metadata["degraded_peer"] = addr
                        gm.queue_hits(
                            self._req_stamped(req, now), degraded=True)
                        self.metrics.degraded_served.labels(
                            peer_addr=addr).inc()
                        if resp.status == Status.OVER_LIMIT:
                            self.metrics.over_limit_counter.inc()
                    responses[i] = resp
                ana = self.dispatcher.analytics
                tenant = None
                if ana is not None:
                    tenant = ana.tenant_hint(
                        name=deg_failed[0][1].name)
                    ana.tap_flag("degraded", len(deg_failed),
                                 tenant=tenant)
                from .tracing import current_span_id, force_sample

                force_sample("degraded")
                ev = {"peer": deg_failed[0][2],
                      "rows": len(deg_failed)}
                if tenant is not None:
                    ev["tenant"] = tenant
                sid = current_span_id()
                if sid is not None:
                    ev["span_id"] = sid
                self.recorder.record("degraded", **ev)
            except Exception as e:  # noqa: BLE001 - degraded serve must
                # never take the batch down; fall back to error rows
                for i, req, addr in deg_failed:
                    if responses[i] is None:
                        responses[i] = RateLimitResponse(
                            error=f"while fetching rate limit from "
                                  f"peer {addr}: {exc_text(e)}")
        self._maybe_sweep(now)
        return responses  # type: ignore[return-value]

    # ---- mesh-resident GLOBAL (ISSUE 7, parallel/meshglobal.py) --------

    #: per-request flags that mutate config/state: such a row is never
    #: served from a replica tier, and demotes a pinned key
    _REPLICA_EXCLUDED = (Behavior.RESET_REMAINING | Behavior.DRAIN_OVER_LIMIT
                         | Behavior.DURATION_IS_GREGORIAN
                         | Behavior.MULTI_REGION)

    def _mesh_mode(self) -> bool:
        """True when the mesh reconcile backend is selected AND the
        engine exposes a mesh (injected test engines may not)."""
        return (self._global_mode == "mesh"
                and getattr(self.engine, "mesh", None) is not None)

    def _mesh_routable(self) -> bool:
        """Mesh routing is pod-local (no non-self peers) and stands
        down while the fold is degraded — then the
        owner-sharded path + gRPC queues serve, which is always
        correct, just slower to cohere."""
        if not self._mesh_mode() or self._mesh_degraded:
            return False
        peers = self.peers()
        return not peers or all(self.is_self(p) for p in peers)

    def _ensure_meshglobal(self):
        with self._gm_mu:
            if self._meshglobal is None:
                from .parallel.meshglobal import MeshGlobalEngine

                raw = os.environ.get("GUBER_MESH_GLOBAL_CAP", "")
                try:
                    cap = int(raw) if raw else 4096
                except ValueError:
                    cap = 4096
                cap = 1 << max((cap - 1).bit_length(), 4)
                self._meshglobal = MeshGlobalEngine(
                    self.engine.mesh, capacity=cap,
                    batch_per_chip=self.config.batch_rows)
                if self.memledger is not None:
                    self.memledger.enroll("mesh_global",
                                          self._probe_meshglobal,
                                          advisable=True)
                # fused engines (ISSUE 8) fold the tier's home-replica
                # decide + accumulator scatter into the serving wave's
                # program — one launch per wave even in mesh mode.
                # Routing still gates on _mesh_routable(): a degraded
                # tier simply stops attaching mslot columns.
                if hasattr(self.engine, "bind_mesh"):
                    self.engine.bind_mesh(self._meshglobal)
            return self._meshglobal

    @staticmethod
    def _mesh_fallback_after() -> int:
        raw = os.environ.get("GUBER_MESH_FALLBACK_AFTER", "")
        try:
            return max(int(raw), 1) if raw else 3
        except ValueError:
            return 3

    def _seed_row(self, kh: int) -> Optional[dict]:
        """The key's sharded-table row, for pin seeding (promotion into
        the mesh tier must not forget hits already consumed).  With the
        tiered store the key may live in the COLD tier instead: seed
        from that row too.  Callers that pin successfully MUST follow
        with ``_seed_commit(kh)`` — a lingering cold copy would shadow
        the demoted row after the pin retires."""
        with self._engine_mu:
            found, cols = self.engine.gather_rows(
                np.array([kh], np.uint64))
            if not found[0] and self._tier is not None:
                cold = self._tier.peek_row(kh)
                if cold is not None:
                    return {f: cold[f]
                            for f in ("remaining", "t_ms", "expire_at",
                                      "meta")}
        if not found[0]:
            return None
        return {f: int(cols[f][0])
                for f in ("remaining", "t_ms", "expire_at", "meta")}

    def _seed_commit(self, kh: int) -> None:
        """Post-pin half of ``_seed_row``: the replica tier took
        ownership of the key's state, so drop the cold-tier copy (a
        no-op when the key wasn't cold-resident)."""
        if self._tier is not None:
            self._tier.pop_row(kh)

    def _mesh_route(self, req: RateLimitRequest, mesh_list, i,
                    now: int) -> bool:
        """Route a qualifying GLOBAL request to the mesh tier: pin on
        first touch (seeded from the sharded row), demote on config
        change or excluded flags.  Returns True when routed; False
        sends the request down the standard (owner-sharded) path."""
        qualifies = not int(req.behavior) & int(self._REPLICA_EXCLUDED)
        kh = hash_key(req.name, req.unique_key)
        mge = self._ensure_meshglobal()
        if mge.is_pinned(kh):
            if not qualifies or not mge.matches_pinned(kh, req):
                self._mesh_demote(kh)
                return False
            mesh_list.append((i, kh))
            return True
        if not qualifies:
            return False
        if not self._mesh_admit(req, kh, now):
            return False  # window full, nothing colder: sharded path
        mesh_list.append((i, kh))
        return True

    def _mesh_admit(self, req: RateLimitRequest, kh: int,
                    now: int) -> bool:
        """Pin ``kh`` into the mesh tier under the overflow admission
        policy: when the key's probe window is full, the coldest pinned
        occupant (by sketch rank) is demoted — through the exact
        stand-down migration path, so no hit is lost — and the pin
        retried, provided the newcomer ranks strictly hotter.  Cap
        overflow becomes a migration, not a silent fallback."""
        mge = self._ensure_meshglobal()
        if mge.pin(req, kh, now, seed=self._seed_row(kh)):
            self._seed_commit(kh)
            return True
        victim = self._mesh_overflow_victim(kh)
        if victim is None:
            return False
        self._mesh_demote(victim)
        self.recorder.record("mesh_overflow_demote", khash=victim,
                             admitted=kh)
        if not mge.pin(req, kh, now, seed=self._seed_row(kh)):
            return False  # window changed underneath us: sharded path
        self._seed_commit(kh)
        return True

    def _mesh_overflow_victim(self, kh: int) -> Optional[int]:
        """The coldest pinned occupant of ``kh``'s probe window, or
        None when the newcomer does not STRICTLY outrank anyone there
        (overflow then declines and the sharded/tiered path — always
        exact — keeps serving the key)."""
        if self.analytics is None or self._meshglobal is None:
            return None
        rank = self.analytics.sketch_count
        best = None
        best_rank = rank(kh)
        for k in self._meshglobal.probe_occupants(kh):
            if k == kh:
                continue
            r = rank(k)
            if r < best_rank:
                best, best_rank = k, r
        return best

    def _mesh_demote(self, key_hash: int) -> None:
        """Migrate one mesh key's HOME-replica row back into the
        sharded table (exact without any collective — home routing
        means only the home copy ever moved), then retire its slot."""
        mge = self._meshglobal
        if mge is None:
            return
        row = mge.row_state(key_hash)
        if row is not None:
            cols = {f: np.array([row[f]]) for f in row}
            with self._engine_mu:
                placed = self.engine.upsert_rows(
                    np.array([key_hash], np.uint64), cols)
                if not placed and self._tier is not None:
                    # device table full: the row lands in the cold tier
                    # instead of being silently dropped
                    self._tier.put_row(key_hash,
                                       {f: int(row[f]) for f in row})
        mge.unpin(key_hash)

    def _mesh_demote_all(self) -> None:
        """Demote every mesh-tier key in one batched writeback (peer
        join / stand-down / snapshot).  Exact: home-row reads need no
        collective, so this works even when the fold is the thing
        that broke."""
        mge = self._meshglobal
        if mge is None:
            return
        khs = mge.pinned_keys()
        if not khs:
            return
        rows = [(kh, mge.row_state(kh)) for kh in khs]
        rows = [(kh, r) for kh, r in rows if r is not None]
        if rows:
            karr = np.array([kh for kh, _ in rows], np.uint64)
            cols = {f: np.array([r[f] for _, r in rows])
                    for f in rows[0][1]}
            with self._engine_mu:
                placed = self.engine.upsert_rows(karr, cols)
                if placed < len(rows) and self._tier is not None:
                    # some rows found no device slot: adopt them into
                    # the cold tier (exact — nothing silently dropped)
                    found, _ = self.engine.gather_rows(karr)
                    for j, (kh, r) in enumerate(rows):
                        if not found[j]:
                            self._tier.put_row(
                                kh, {f: int(r[f]) for f in r})
        for kh in khs:
            mge.unpin(kh)

    def _mesh_reconcile_tick(self) -> None:
        """The GlobalManager mesh backend's tick: swap the accumulator
        double buffer, launch the reconcile collective, account
        staleness/generation, and run the degraded fallback.  Never
        raises (the hits loop must survive every failure mode)."""
        if not self._mesh_mode():
            return
        mge = self._meshglobal
        if mge is None:
            return
        fold = phase("global_fold", self.dispatcher).begin()
        retired = None
        try:
            self._fault_point("global_accum_swap")
            retired = mge.swap_accum()
            self._fault_point("global_psum")
            mge.fold(retired)
        except Exception as e:  # noqa: BLE001 - incl. FaultInjected
            fold.end(keep=False)
            if retired is not None:
                mge.swap_back()  # unfolded hits stay accumulating
            self.metrics.mesh_global_fold_errors.inc()
            self._mesh_fail_streak += 1
            log.warning("mesh-GLOBAL fold failed (streak %d): %s",
                        self._mesh_fail_streak, exc_text(e))
            if (self._mesh_fail_streak >= self._mesh_fallback_after()
                    and not self._mesh_degraded):
                self._mesh_stand_down()
            return
        # the collective's time is its own phase (PhaseLedger)
        dt = fold.end()
        self._mesh_fail_streak = 0
        self.metrics.mesh_global_folds.inc()
        self.metrics.mesh_global_staleness.set(mge.last_staleness_s)
        self.metrics.mesh_global_keys.set(len(mge.slots))
        # stamp the coherence epoch onto subsequent waves
        self.dispatcher.reconcile_gen = mge.generation
        self._mesh_last_fold_ok = time.monotonic()
        ana = self.dispatcher.analytics
        if ana is not None:
            # cost-model sample (ISSUE 11): the fold moves the
            # replicated value columns + accumulator across mge.n
            # devices — one (bytes, ndev, duration) observation
            ana.tap_cost("global_fold", mge.fold_nbytes, mge.n, dt)
        if (self._mesh_degraded
                and time.monotonic() >= self._mesh_down_until):
            # cooldown elapsed AND a clean fold: re-arm the tier
            self._mesh_degraded = False
            self.metrics.mesh_global_degraded.set(0)
            self.recorder.record("mesh_recovered",
                                 generation=mge.generation)

    def _mesh_stand_down(self) -> None:
        """Degraded fallback: demote every pinned key back to the
        owner-sharded path (exact) and route GLOBAL traffic the grpc
        way until the fold has recovered past the cooldown —
        bounded-staleness degradation, never unavailability."""
        cooldown = max(
            self.config.behaviors.global_sync_wait_ms, 100) * 10 / 1000.0
        self._mesh_down_until = time.monotonic() + cooldown
        self._mesh_degraded = True
        self.metrics.mesh_global_degraded.set(1)
        self.recorder.record("mesh_degraded",
                             streak=self._mesh_fail_streak,
                             cooldown_s=round(cooldown, 3))
        try:
            self._mesh_demote_all()
        except Exception:  # noqa: BLE001 - demotion is best-effort here
            log.exception("mesh-GLOBAL stand-down demotion")

    def _read_through(self, reqs) -> None:
        """Seed table misses from the write-through Store before the
        device step (store.go › Store.Get on cache miss).  One extra
        row-gather per batch, only when a Store is configured.

        The whole gather→get→upsert sequence holds the engine lock: a
        concurrent request inserting the same key between our miss and
        our overwrite-upsert would otherwise have its hits erased by the
        stale store copy."""
        if self.store is None or not reqs:
            return
        from .hashing import hash_request_keys
        from .store import arrays_from_items

        khash = hash_request_keys([r.name for r in reqs],
                                  [r.unique_key for r in reqs])
        with self._engine_mu:
            found, _ = self.engine.gather_rows(khash)
            items = []
            for j, req in enumerate(reqs):
                if found[j]:
                    continue
                item = self.store.get(req)
                if item is not None:
                    if not item.key and not item.key_hash:
                        item.key = req.key
                    items.append(item)
            if items:
                arrays = arrays_from_items(items)
                self.engine.upsert_rows(arrays.pop("key"), arrays)

    def _after_local(self, reqs, resps) -> None:
        """Post-step hooks: Store write-through for mutated keys."""
        if self.store is None:
            return
        for req, resp in zip(reqs, resps):
            if resp.error:
                continue
            self.store.on_change(req, CacheItem(
                key=req.key, algorithm=int(req.algorithm),
                limit=resp.limit, duration=int(req.duration),
                remaining=resp.remaining, expire_at=resp.reset_time,
                status=int(resp.status)))

    def _maybe_sweep(self, now: int) -> None:
        """The whole-table expiry sweep, between waves and under the
        engine lock: when the interval has come round (cause "tick"),
        or ahead of it, once an interval, when a wave answered a row
        table_full ("table_full": a window clogged by expired rows
        takes inserts again after it; one full of live keys does not,
        which is why no wave sweeps for itself)."""
        iv = self.config.sweep_interval_ms
        if iv <= 0:
            return
        if now - self._last_sweep >= iv:
            cause = "tick"
            self._last_sweep = now
        elif (getattr(self.engine, "sweep_wanted", False)
              and now - self._last_asked_sweep >= iv):
            cause = "table_full"
            self._last_asked_sweep = now
        else:
            return
        with self._engine_mu, phase("sweep", self.dispatcher):
            self.engine.sweep_wanted = False
            self.engine.sweep(now)
        self.metrics.sweeps.labels(cause=cause).inc()

    # ---- peer service (owner side) -------------------------------------

    def get_peer_rate_limits(self, reqs: Sequence[RateLimitRequest],
                             now_ms: Optional[int] = None
                             ) -> List[RateLimitResponse]:
        """Apply a forwarded batch locally (gubernator.go ›
        GetPeerRateLimits).  GLOBAL keys get queued for broadcast."""
        if len(reqs) > self.config.behaviors.batch_limit:
            raise ValueError(
                "'PeerRequest.rate_limits' list too large; max size is "
                f"{self.config.behaviors.batch_limit}")
        now = clock_ms() if now_ms is None else now_ms  # clock-domain: owner
        self.metrics.getratelimit_counter.labels(calltype="peer").inc(len(reqs))
        reqs = list(reqs)
        self._read_through(reqs)
        resps = self.dispatcher.check_batch(reqs, now)
        gm = None
        for req in reqs:
            if req.behavior & Behavior.GLOBAL:
                gm = gm or self._ensure_global_manager()
                gm.queue_update(req)
            if req.behavior & Behavior.MULTI_REGION:
                # we are the local-region owner for this forwarded key
                self._ensure_mr_manager().queue_hits(
                    self._req_stamped(req, now))
        # rehome-target duty (ISSUE 5, object-path twin of
        # _peer_degraded_rewrite): rows whose membership owner is
        # ejected from OUR gate were rehomed here — flag + reconcile
        if self._gate_bad and getattr(self.config.behaviors,
                                      "peer_degraded_fallback", True):
            self._peer_degraded_objects(reqs, resps, now)
        self._after_local(reqs, resps)
        return resps

    def _peer_degraded_objects(self, reqs, resps, now: int) -> None:
        bad = self._gate_bad
        with self._peer_mu:
            mpick = self._picker
        if not bad or not mpick.peers():
            return
        gm = None
        excl = int(self._DEGRADED_EXCLUDED | Behavior.GLOBAL)
        for req, resp in zip(reqs, resps):
            if resp.error or (int(req.behavior) & excl):
                continue
            try:
                owner = mpick.get(req.key)
            except RuntimeError:
                return
            addr = owner.info.grpc_address
            if addr not in bad or self.is_self(owner):
                continue
            resp.metadata["degraded"] = "true"
            resp.metadata["degraded_peer"] = addr
            gm = gm or self._ensure_global_manager()
            gm.queue_hits(self._req_stamped(req, now),
                          degraded=True)
            self.metrics.degraded_served.labels(peer_addr=addr).inc()

    # ---- GLOBAL broadcast plumbing -------------------------------------

    def build_global_updates(self, reqs: Sequence[RateLimitRequest]
                             ) -> List[peers_pb.UpdatePeerGlobal]:
        """Owner side: read authoritative rows for changed GLOBAL keys
        and serialize them for UpdatePeerGlobals."""
        from .hashing import hash_request_keys

        khash = hash_request_keys([r.name for r in reqs],
                                  [r.unique_key for r in reqs])
        with self._engine_mu:
            found, cols = self.engine.gather_rows(khash)
        out: List[peers_pb.UpdatePeerGlobal] = []
        for j, req in enumerate(reqs):
            if not found[j]:
                continue
            meta = int(cols["meta"][j])
            alg = meta & 1
            eff = int(cols["eff_ms"][j])
            rem = int(cols["remaining"][j])
            if alg == int(Algorithm.LEAKY_BUCKET):
                rem_out = rem // max(eff, 1)
                reset = int(cols["t_ms"][j]) + (
                    eff // max(int(cols["limit"][j]), 1))
            else:
                rem_out = rem
                reset = int(cols["expire_at"][j])
            out.append(peers_pb.UpdatePeerGlobal(
                key=req.key,
                update=pb.RateLimitResp(
                    status=(meta >> 1) & 1, limit=int(cols["limit"][j]),
                    remaining=rem_out, reset_time=reset),
                algorithm=alg, duration=int(cols["duration"][j]),
                created_at=int(cols["t_ms"][j]),
                behavior=int(req.behavior), burst=int(cols["burst"][j])))
        return out

    def update_peer_globals(self, updates: Sequence[peers_pb.UpdatePeerGlobal]
                            ) -> None:
        """Replica side: overwrite local rows with the owner's
        authoritative state (gubernator.go › UpdatePeerGlobals)."""
        m = len(updates)
        if m == 0:
            return
        from .hashing import hash_keys

        # identity = hash(name + "_" + unique_key) and g.key IS that
        # joined string — one native batch hash instead of m scalar
        # ones.  Handover senders only hold the hash and send it in the
        # extension field (peers.proto › key_hash); it takes precedence.
        khash = hash_keys([g.key for g in updates])
        sent_kh = np.fromiter((g.key_hash for g in updates), np.uint64, m)
        khash = np.where(sent_kh != 0, sent_kh, khash)
        cols = {
            "meta": np.zeros(m, np.int32),
            "limit": np.zeros(m, np.int64),
            "duration": np.zeros(m, np.int64),
            "eff_ms": np.ones(m, np.int64),
            "burst": np.zeros(m, np.int64),
            "remaining": np.zeros(m, np.int64),
            "t_ms": np.zeros(m, np.int64),
            "expire_at": np.zeros(m, np.int64),
        }
        for j, g in enumerate(updates):
            alg = int(g.algorithm)
            if g.eff_ms > 0:
                # handover extension: the sender knows the exact
                # denominator (including Gregorian rows')
                eff = int(g.eff_ms)
            elif g.behavior & Behavior.DURATION_IS_GREGORIAN:
                try:
                    eff = gregorian_rate_duration_ms(int(g.duration))
                except (ValueError, KeyError):
                    eff = 1
            else:
                eff = max(int(g.duration), 1)
            burst = int(g.burst) if g.burst > 0 else int(g.update.limit)
            if alg == int(Algorithm.LEAKY_BUCKET):
                # broadcasts carry whole tokens (× eff to td); handover
                # messages (eff_ms set) carry the raw td fixed point —
                # lossless across the hop
                rem = (int(g.update.remaining) if g.eff_ms > 0
                       else int(g.update.remaining) * eff)
                expire = int(g.created_at) + eff
            else:
                rem = int(g.update.remaining)
                expire = int(g.update.reset_time)
            cols["meta"][j] = (alg & 1) | ((int(g.update.status) & 1) << 1)
            cols["limit"][j] = int(g.update.limit)
            cols["duration"][j] = int(g.duration)
            cols["eff_ms"][j] = eff
            cols["burst"][j] = burst
            cols["remaining"][j] = rem
            cols["t_ms"][j] = int(g.created_at)
            cols["expire_at"][j] = expire
        with self._engine_mu:
            self.engine.upsert_rows(khash, cols)

    # ---- health / lifecycle --------------------------------------------

    def health_status(self) -> str:
        """Cheap liveness answer ("healthy"/"unhealthy") from the async
        managers' last-error state alone — NO device work, no metrics
        side effects.  For callers that poll (health Watch streams):
        ``health_check`` additionally syncs a device occupancy count,
        which must not run at poll frequency."""
        if self.global_manager is not None and self.global_manager.last_error:
            return "unhealthy"
        if self.mr_manager is not None and self.mr_manager.last_error:
            return "unhealthy"
        return "healthy"

    # ---- SLO plane (ISSUE 11) ------------------------------------------

    def _build_slo(self) -> None:
        """Register the catalog (slo.py › SLO_CATALOG) against this
        instance's live signals and start the tick loop.  Sources are
        cheap reads of already-maintained state — the SLO plane adds
        no work to the serving path."""
        from .config import parse_duration_ms
        from .interval import IntervalLoop
        from .slo import (DEFAULT_BURN_THRESHOLD, DEFAULT_FAST_S,
                          DEFAULT_SLOW_S, SLO, SLO_CATALOG, SLOEngine)

        def _dur_s(v: str, default_s: float) -> float:
            if not v:
                return default_s
            try:
                return parse_duration_ms(v) / 1000.0
            except (ValueError, TypeError):
                return default_s

        def _flt(v: str, default: float) -> float:
            try:
                return float(v or default)
            except ValueError:
                return default

        fast = _dur_s(os.environ.get("GUBER_SLO_FAST", ""),
                      DEFAULT_FAST_S)
        slow = _dur_s(os.environ.get("GUBER_SLO_SLOW", ""),
                      DEFAULT_SLOW_S)
        tick_s = _dur_s(os.environ.get("GUBER_SLO_TICK", ""), 1.0)
        burn = _flt(os.environ.get("GUBER_SLO_BURN", ""),
                    DEFAULT_BURN_THRESHOLD)
        p99_s = _flt(os.environ.get("GUBER_SLO_P99_MS", ""),
                     250.0) / 1000.0
        def _breach_exemplar():
            # a burning SLO links to one concrete sampled trace
            # (ISSUE 12); None when nothing sampled recently
            ex = self.span_recorder.exemplar()
            return ex["trace_id"] if ex else None

        eng = SLOEngine(metrics=self.metrics, recorder=self.recorder,
                        fast_s=fast, slow_s=slow, burn_threshold=burn,
                        exemplar=_breach_exemplar)
        ana = self.dispatcher.analytics

        def decision_p99():
            p = (ana.phases.recent_p99("device")
                 if ana is not None else None)
            return (p or 0.0, p99_s)

        stale_target = 2.0 * max(
            self.config.behaviors.global_sync_wait_ms, 100) / 1000.0

        def global_staleness():
            mge = self._meshglobal
            if mge is None:
                return (0.0, stale_target)
            v = float(mge.last_staleness_s)
            ok = self._mesh_last_fold_ok
            if ok is not None:
                # a wedged/failing fold stops updating last_staleness_s
                # — age against the last SUCCESSFUL fold so the SLO
                # still sees the coherence gap widening
                v = max(v, time.monotonic() - ok)
            return (v, stale_target)

        def error_ratio():
            t = ana.tenant_totals() if ana is not None else {}
            return (t.get("errors", 0) + t.get("degraded", 0),
                    t.get("requests", 0))

        def shed_ratio():
            t = ana.tenant_totals() if ana is not None else {}
            return (t.get("shed", 0),
                    t.get("requests", 0) + t.get("shed", 0))

        eng.register(SLO("decision_p99", "threshold", 0.95,
                         decision_p99, SLO_CATALOG["decision_p99"]))
        eng.register(SLO("global_staleness", "threshold", 0.95,
                         global_staleness,
                         SLO_CATALOG["global_staleness"]))
        eng.register(SLO("error_ratio", "ratio", 0.999, error_ratio,
                         SLO_CATALOG["error_ratio"]))
        eng.register(SLO("shed_ratio", "ratio", 0.999, shed_ratio,
                         SLO_CATALOG["shed_ratio"]))
        led = self.memledger
        if led is not None:
            # the ledger's pressure sample IS the (value, target) pair;
            # it also edge-triggers the memory_pressure event, so the
            # early-warning fires on the same tick cadence as the SLO
            eng.register(SLO("hbm_pressure", "threshold", 0.95,
                             led.pressure_sample,
                             SLO_CATALOG["hbm_pressure"]))
        if ana is not None:
            eng.register_group(
                "tenant_error_ratio", 0.999,
                lambda: ana.tenant_red("errors"),
                SLO_CATALOG["tenant_error_ratio"])
            eng.register_group(
                "tenant_shed_ratio", 0.999,
                lambda: ana.tenant_red("shed"),
                SLO_CATALOG["tenant_shed_ratio"])
        if self.auditor.enabled:
            # value = seconds the audit drift has been nonzero, target
            # = the one-flush-window staleness bound; a partition (or a
            # real loss) holds drift nonzero past the bound and burns
            eng.register(SLO("fleet_conservation", "threshold", 0.95,
                             self.auditor.slo_sample,
                             SLO_CATALOG["fleet_conservation"]))
        self.slo = eng
        self._slo_loop = IntervalLoop(
            max(int(tick_s * 1000), 10), eng.tick, name="slo-engine")

    def audit_doc(self) -> dict:
        """The conservation audit vector served at GET /debug/audit
        (fleet.py › ConservationAuditor.doc): per-lane injected /
        applied / queued / in-flight / degraded-pending counters and
        the drift they prove, plus the ring view the fleet fold
        cross-checks.  Always available — the auditor rides the GLOBAL
        lanes' own accounting, no extra thread."""
        return self.auditor.doc()

    def health_check(self) -> HealthCheckResponse:
        """reference: gubernator.go › HealthCheck — healthy + peer count,
        surfacing the last async replication error if any."""
        msg = ""
        status = "healthy"
        if self.global_manager is not None and self.global_manager.last_error:
            status = "unhealthy"
            msg = self.global_manager.last_error
        elif self.mr_manager is not None and self.mr_manager.last_error:
            status = "unhealthy"
            msg = self.mr_manager.last_error
        # under _engine_mu: occupancy/saturation read self.engine.state,
        # which the donated step consumes and rebinds mid-wave — an
        # unlocked read can be handed a deleted buffer.  One device
        # call (pre-warmed at engine init) so serving waves queue
        # behind a sync, not a compile.
        with self._engine_mu:
            if hasattr(self.engine, "occupancy_and_saturation"):
                occ, full, total = self.engine.occupancy_and_saturation()
                self.metrics.bucket_saturation.set(full / max(total, 1))
            else:
                occ = self.engine_occupancy()
            self.metrics.cache_size.set(int(occ))
            self.metrics.dropped_rows.set(self.engine.dropped_rows)
        self.metrics.cache_capacity.set(self.engine.cap_local
                                        * self.engine.n)
        return HealthCheckResponse(status=status, message=msg,
                                   peer_count=len(self.peers()))

    def remove(self, name: str, unique_key: str) -> bool:
        """Delete one rate limit's state (library admin path; the
        reference exposes the same through its Cache.Remove + Store).
        Returns True when a row existed."""
        kh = hash_key(name, unique_key)
        if self._meshglobal is not None and self._meshglobal.is_pinned(kh):
            self._mesh_demote(kh)
        with self._engine_mu:
            n = self.engine.remove_rows(np.array([kh], np.uint64))
            if self._tier is not None \
                    and self._tier.pop_row(kh) is not None:
                n += 1  # cold-resident: the row lived in the cold tier
        if self.store is not None:
            self.store.remove(f"{name}_{unique_key}")
        return n > 0

    def _tier_victim_pinned(self, kh: int) -> bool:
        """Tier-eviction victim filter: a mesh-pinned key's device
        row is the HOME copy of the tier's coherence — demoting it
        to the cold tier while the pin serves would fork its state."""
        mge = self._meshglobal
        return mge is not None and mge.is_pinned(kh)

    def engine_occupancy(self) -> int:
        # the engine owns its table layout (SoA columns vs the pallas
        # engine's bucket rows) — layout-specific counting lives there
        return self.engine.occupancy()

    # ---- device-memory ledger probes (ISSUE 13) --------------------
    # Each probe re-reads the live attributes at snapshot time (state
    # arrays rebind on grow/sweep/donated steps) and takes the owning
    # lock itself — the ledger never holds its own lock across a probe.

    def _enroll_memledger(self) -> None:
        led = self.memledger
        if led is None:
            return
        if getattr(self.engine, "state", None) is not None \
                and hasattr(self.engine, "cap_local"):
            led.enroll("hot_table", self._probe_hot_table,
                       advisable=True)
        if getattr(self.engine, "wave_pool", None) is not None:
            led.enroll("wave_pool", self._probe_wave_pool, host=True)
        if self.analytics is not None:
            led.enroll("sketch", self._probe_sketch, host=True)
        if self._tier is not None:
            led.enroll("cold_store", self._probe_cold_store, host=True)

    @staticmethod
    def _leaves_nbytes(leaves) -> int:
        return sum(int(getattr(a, "nbytes", 0)) for a in leaves)

    def _probe_hot_table(self) -> dict:
        import jax

        eng = self.engine
        # under _engine_mu: the donated step consumes and rebinds
        # state mid-wave — an unlocked read can hold a deleted buffer
        with self._engine_mu:
            nbytes = self._leaves_nbytes(jax.tree.leaves(eng.state))
            cap = int(getattr(eng, "cap_local", 0)) \
                * int(getattr(eng, "n", 1))
            live = int(getattr(eng, "live_rows", -1))
            if live < 0:
                # tick-cadence sampler: must not WAIT on the device
                # gate while holding the engine lock (that convoys
                # serving waves in multi-engine processes) — reuse the
                # last sample when the gate is contended
                fresh = eng.occupancy_nowait() \
                    if hasattr(eng, "occupancy_nowait") else None
                if fresh is None:
                    live = self._memledger_live
                else:
                    live = self._memledger_live = int(fresh)
        demand: dict = {}
        ana = self.analytics
        if ana is not None:
            demand["ranks"] = ana.rank_distribution()
        tier = self._tier
        if tier is not None:
            st = tier.stats()
            demand["promote_rate"] = st.get("promotions", 0)
            demand["demote_rate"] = st.get("demotions", 0)
            demand["overflow"] = st.get("cold_served", 0)
        return {"bytes": nbytes, "capacity_rows": cap,
                "occupied_rows": max(live, 0), "demand": demand}

    def _probe_wave_pool(self) -> dict:
        pool = getattr(self.engine, "wave_pool", None)
        if pool is None:
            return {"bytes": 0}
        st = pool.mem_stats()
        return {"bytes": st["pooled_bytes"], "capacity_rows": 0,
                "occupied_rows": st["pooled"],
                "demand": {"rate": st["hits"]}}

    def _probe_sketch(self) -> dict:
        ana = self.analytics
        if ana is None:
            return {"bytes": 0}
        st = ana.mem_stats()
        return {"bytes": st["bytes"], "capacity_rows": st["width"],
                "occupied_rows": st["used"],
                "demand": {"rate": st["total_weight"]}}

    def _probe_cold_store(self) -> dict:
        tier = self._tier
        if tier is None:
            return {"bytes": 0}
        st = tier.stats()
        return {"bytes": tier.mem_bytes(), "capacity_rows": 0,
                "occupied_rows": st["cold_keys"],
                "demand": {"promote_rate": st["promotions"],
                           "demote_rate": st["demotions"],
                           "rate": st["cold_served"]}}

    def _probe_meshglobal(self) -> dict:
        import jax

        mge = self._meshglobal
        if mge is None:
            return {"bytes": 0}
        # state + BOTH accumulator buffers; never mge.stats() here —
        # it drains collectives, a probe must stay read-only
        with mge._state_mu:
            nbytes = self._leaves_nbytes(
                jax.tree.leaves(mge.state)
                + jax.tree.leaves(mge._acc))
            folded = float(mge.folded_hits + mge.injected_hits)
        with mge._mu:
            occ = len(mge._occupied)
        return {"bytes": nbytes, "capacity_rows": int(mge.capacity),
                "occupied_rows": occ,
                "demand": {"fold_rate": folded}}

    def close(self) -> None:
        """Flush async managers, snapshot via Loader, drop peers.
        reference: V1Instance.Close (SURVEY.md §3.5)."""
        if self._closed:
            return
        self._closed = True
        if self._slo_loop is not None:
            # first: the close runs one FINAL tick, so the verdicts the
            # debug dump captures below reflect end-of-life state
            self._slo_loop.close()
        if self.global_manager is not None:
            self.global_manager.close()
        if self.mr_manager is not None:
            self.mr_manager.close()
        if self._probe_loop is not None:
            self._probe_loop.close()
        self.dispatcher.close()
        if self.dispatcher.analytics is not None:
            self.dispatcher.analytics.close()
        self._write_debug_dump()
        self._save_to_loader()
        if self.memledger is not None:
            # stand the ledger down leak-free: every enrolled consumer
            # releases (tests assert consumers() drains to empty here)
            for consumer in self.memledger.consumers():
                self.memledger.release(consumer)
        for p in self.peers():
            p.shutdown()

    def _write_debug_dump(self) -> None:
        """Crash forensics (ISSUE 11): when ``GUBER_DEBUG_DUMP_DIR`` is
        set, drain dumps the whole event ring plus the final SLO
        verdicts as JSONL — a killed pod leaves its black box on disk.
        Best-effort: a dying process must never wedge on forensics."""
        dirpath = os.environ.get("GUBER_DEBUG_DUMP_DIR", "")
        if not dirpath:
            return
        try:
            from .telemetry import write_debug_dump

            verdicts = (self.slo.verdicts()
                        if self.slo is not None else None)
            iid = (os.environ.get("GUBER_INSTANCE_ID", "")
                   or self.config.advertise_address or "instance")
            path = write_debug_dump(
                dirpath, iid,
                self.recorder.events(), slo_verdicts=verdicts)
            self.recorder.record("debug_dump_written", path=path,
                                 events=len(self.recorder))
            spans = self.span_recorder.spans()
            if spans:
                # trace-plane sibling (ISSUE 12): sampled spans spill
                # next to the event dump, trace_assemble.py-readable
                from .telemetry import write_trace_dump

                write_trace_dump(dirpath, iid, spans)
        except Exception as e:  # noqa: BLE001 - forensics is best-effort
            log.warning("debug dump failed: %s", exc_text(e))
