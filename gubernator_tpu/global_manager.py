"""GLOBAL behavior: async hit reconciliation + owner broadcasts.

reference: global.go › globalManager{QueueHits, QueueUpdate,
runAsyncHits, runBroadcasts} — reconstructed, mount empty.

Any peer answers GLOBAL requests immediately from its local replica of
the counter; hits are queued here and asynchronously flushed to the
key's owner (aggregated per key); the owner applies them to its
authoritative copy and periodically broadcasts merged state to every
peer, which overwrites the replicas.  Short-window over-admission is the
documented consequence (SURVEY.md §2.4 GLOBAL).

On a TPU pod the intra-node analog of this manager is the psum delta
fold (SURVEY.md §3.3); this module is the inter-node (host gRPC) tier.
"""
from __future__ import annotations

import logging
import threading
import time
from contextlib import contextmanager
from typing import Dict, List, Tuple

from .config import BehaviorConfig
from .interval import IntervalLoop
from .telemetry import exc_text
from .tracing import phase
from .types import Behavior, RateLimitRequest

log = logging.getLogger("gubernator_tpu.global")


def _raw_lanes_available() -> bool:
    """The columnar flush paths need the native codec (peer_client's
    send lanes split responses with it)."""
    try:
        from .ops import native  # noqa: F401
        return True
    except ImportError:  # pragma: no cover - unbuilt extension
        return False


def _failed_future(e: BaseException):
    from concurrent.futures import Future

    f: Future = Future()
    f.set_exception(e)
    return f


class GlobalManager:
    def __init__(self, instance, behaviors: BehaviorConfig, metrics):
        self.instance = instance
        self.behaviors = behaviors
        self.metrics = metrics
        self._mu = threading.Lock()
        #: cross-lane arrival order (under _mu): when the SAME key is
        #: queued through both the object and wire lanes in one window,
        #: the prototype with the highest seq wins the flush-time merge
        #: — "latest config wins" must hold across lanes, not just
        #: within one
        self._seq = 0  # guarded-by: self._mu
        #: key → (request prototype, accumulated hits, seq) — non-owner.
        self._hits: Dict[str, Tuple[RateLimitRequest, int, int]] = {}  # guarded-by: self._mu
        #: key → (seq, request prototype) for changed GLOBAL keys —
        #: owner side.
        self._updates: Dict[str, Tuple[int, RateLimitRequest]] = {}  # guarded-by: self._mu
        #: key-hash → (request TLV bytes, accumulated hits, seq) — the
        #: wire lane's non-owner side.  The columnar request path queues
        #: the raw `requests` TLV slice instead of building per-request
        #: objects; entries materialize into prototypes at flush
        #: cadence (_req_from_tlv) and merge into _hits.
        self._hits_raw: Dict[int, Tuple[bytes, int, int]] = {}  # guarded-by: self._mu
        #: key-hash → (seq, request TLV bytes) — wire lane, owner side.
        self._updates_raw: Dict[int, Tuple[int, bytes]] = {}  # guarded-by: self._mu
        #: degraded share of the queued accumulators, keyed like the
        #: queues (ISSUE 19): parallel dicts instead of widening the
        #: queue tuples — external drivers (chaos) unpack 3-tuples
        self._deg: Dict[str, int] = {}  # guarded-by: self._mu
        self._deg_raw: Dict[int, int] = {}  # guarded-by: self._mu
        #: conservation audit tap (ISSUE 19, fleet.py): sender-side
        #: double-entry ledger behind GET /debug/audit.  Own leaf
        #: lock; every call sits OUTSIDE self._mu so the tap adds no
        #: lock-order edge.  None when GUBER_FLEET_AUDIT=0.
        from .fleet import AuditTap, audit_enabled

        self.audit = AuditTap() if audit_enabled() else None
        self._err_mu = threading.Lock()
        self._last_error = ""  # guarded-by: self._err_mu
        self._last_error_at = 0.0  # guarded-by: self._err_mu
        self._hits_loop = IntervalLoop(
            behaviors.global_sync_wait_ms, self._run_async_hits,
            name="global-async-hits")
        self._bcast_loop = IntervalLoop(
            behaviors.global_broadcast_interval_ms, self._run_broadcasts,
            name="global-broadcasts")

    # ---- producers (called from the request path) ----------------------

    def queue_hits(self, req: RateLimitRequest,
                   degraded: bool = False) -> None:
        """Accumulate hits for async reconcile to the owner.
        reference: global.go › QueueHits.  ``degraded`` marks hits
        queued by a degraded-mode serve (ISSUE 19 audit vector)."""
        inc = max(int(req.hits), 0)
        with self._mu:
            self._seq += 1
            _, acc, _ = self._hits.get(req.key, (req, 0, 0))
            self._hits[req.key] = (req, acc + inc, self._seq)
            if degraded and inc:
                self._deg[req.key] = self._deg.get(req.key, 0) + inc
            # both lanes share the flush: threshold and gauge must see
            # the raw queue too or mixed-lane traffic undercounts
            n = len(self._hits) + len(self._hits_raw)
        if self.audit is not None:
            self.audit.inject(inc, degraded)
        self.metrics.queue_length.set(n)
        if n >= self.behaviors.global_batch_limit:
            self._hits_loop.poke()

    def queue_update(self, req: RateLimitRequest) -> None:
        """Mark a GLOBAL key changed on the owner; broadcast on next tick.
        reference: global.go › QueueUpdate."""
        with self._mu:
            self._seq += 1
            self._updates[req.key] = (self._seq, req)
            n = len(self._updates) + len(self._updates_raw)
        if n >= self.behaviors.global_batch_limit:
            self._bcast_loop.poke()

    # ---- wire-lane producers (columnar request path) -------------------
    #
    # The clustered wire fast lane has no per-request Python objects —
    # only parsed columns and the raw `requests` TLV slices.  These
    # producers keep it that way: the request path hands over (key-hash,
    # TLV bytes, aggregated hits) per UNIQUE key; prototypes are built
    # lazily at flush cadence, off the request path, then flow through
    # the same flush/broadcast machinery as the object-path queues (so
    # a key served through both lanes merges correctly).

    def queue_hits_raw(self, khash: int, tlv: bytes, hits: int,
                       degraded: bool = False) -> None:
        """Wire-lane twin of ``queue_hits``: accumulate ``hits`` for the
        key identified by ``khash``, with ``tlv`` (the verbatim
        GetRateLimitsReq.requests TLV slice) as the deferred prototype.
        A hits=0 entry still refreshes the prototype, exactly as
        queue_hits stores the latest req unconditionally."""
        inc = max(int(hits), 0)
        with self._mu:
            self._seq += 1
            _, acc, _ = self._hits_raw.get(khash, (tlv, 0, 0))
            # keep the LATEST tlv as the prototype, exactly as
            # queue_hits keeps the latest req: a mid-window config
            # change must reconcile under the new limit/duration
            self._hits_raw[khash] = (tlv, acc + inc, self._seq)
            if degraded and inc:
                self._deg_raw[khash] = self._deg_raw.get(khash, 0) + inc
            n = len(self._hits_raw) + len(self._hits)
        if self.audit is not None:
            self.audit.inject(inc, degraded)
        self.metrics.queue_length.set(n)
        if n >= self.behaviors.global_batch_limit:
            self._hits_loop.poke()

    def queue_update_raw(self, khash: int, tlv: bytes) -> None:
        """Wire-lane twin of ``queue_update`` (owner side)."""
        with self._mu:
            self._seq += 1
            self._updates_raw[khash] = (self._seq, tlv)
            n = len(self._updates_raw) + len(self._updates)
        if n >= self.behaviors.global_batch_limit:
            self._bcast_loop.poke()

    @staticmethod
    def _req_from_tlv(tlv: bytes) -> RateLimitRequest:
        """Deferred prototype (wire.req_from_tlv).  Flush-cadence only."""
        from .wire import req_from_tlv

        return req_from_tlv(tlv)

    def queued_hits(self) -> Tuple[int, int]:
        """(total queued hit weight, degraded share) across both
        lanes — the audit vector's live-queue leg (ISSUE 19)."""
        with self._mu:
            q = (sum(a for _, a, _ in self._hits.values())
                 + sum(a for _, a, _ in self._hits_raw.values()))
            d = sum(self._deg.values()) + sum(self._deg_raw.values())
        return q, d

    def _requeue_hits(self, entries) -> None:
        """Put a FAILED flush's aggregates back into the queues
        (ISSUE 5): degraded-mode hits reconcile EXACTLY once the owner
        recovers, so an unreachable owner must requeue, not drop.
        ``entries``: (key-or-khash, proto (req object or raw TLV),
        accumulated hits, seq, degraded share); merges with anything
        queued since the flush popped them (latest-prototype-wins,
        sums preserved).  A requeue is NOT a re-inject — the audit
        tap saw these hits at queue-entry; they simply stay queued."""
        if not entries:
            return
        with self._mu:
            for k, proto, acc, seq, deg in entries:
                if isinstance(proto, bytes):
                    t0, a0, s0 = self._hits_raw.get(k, (proto, 0, 0))
                    self._hits_raw[k] = (proto if seq >= s0 else t0,
                                         a0 + acc, max(s0, seq))
                    if deg:
                        self._deg_raw[k] = self._deg_raw.get(k, 0) + deg
                else:
                    p0, a0, s0 = self._hits.get(k, (proto, 0, 0))
                    self._hits[k] = (proto if seq >= s0 else p0,
                                     a0 + acc, max(s0, seq))
                    if deg:
                        self._deg[k] = self._deg.get(k, 0) + deg
            n = len(self._hits) + len(self._hits_raw)
        self.metrics.queue_length.set(n)

    def _fault_tick(self, point: str, stage: str) -> bool:
        """Chaos hook for the async loops: True aborts this tick (the
        queues were not popped yet, so nothing is lost)."""
        f = getattr(self.instance, "faults", None)
        if f is None or not f.armed:
            return False
        try:
            f.fire(point)
        except Exception as e:  # noqa: BLE001 - incl. FaultInjected
            msg = f"{stage}: {exc_text(e)}"
            log.warning(msg)
            self._record([msg])
            return True
        return False

    # ---- async loops ---------------------------------------------------

    def _tick_context(self, name: str):
        """Traced context for one async tick (ISSUE 12): the tick gets
        its OWN trace (there is no caller request on this thread), a
        root span named after the aggregate, and hop spans from the
        lanes it sends on — so an owner-side UpdatePeerGlobals /
        GetPeerRateLimits handler stitches back to the flush that
        caused it.  No-op (null context) without a span recorder."""
        rec = getattr(self.instance, "span_recorder", None)
        if rec is None:
            import contextlib

            return contextlib.nullcontext()
        from .tracing import request_context, span

        @contextmanager
        def _cm():
            with request_context(None, recorder=rec), span(name):
                yield

        return _cm()

    def _run_async_hits(self) -> None:
        """Flush aggregated hits to each key's owner.
        reference: global.go › runAsyncHits.

        Columnar path (default-hash pickers + native codec): BOTH
        lanes' queues merge in raw-khash space, each key's aggregate
        becomes one TLV with the summed hits appended
        (wire.tlv_with_hits — zero request materialization), and the
        per-owner payloads ride the peers' pooled forward lanes
        (pipelined flushes, retry, circuit fail-fast), aggregated per
        peer per window.  Non-default pickers / no codec keep the
        legacy object flush."""
        with self._tick_context("global.hits_flush"):
            self._hits_tick()

    def _hits_tick(self) -> None:
        if self._fault_tick("global_hits", "global hits flush"):
            return
        # Mesh reconcile backend (ISSUE 7, GUBER_GLOBAL_MODE=mesh):
        # pod-local GLOBAL counters converge through the engine-side
        # collective fold instead of gRPC fan-out; the tick no-ops in
        # grpc mode.  The queued aggregates below (cross-pod owners,
        # degraded-mode reconcile) keep the gRPC lanes either way —
        # that path is also the mesh tier's degraded fallback.
        tick = getattr(self.instance, "_mesh_reconcile_tick", None)
        if tick is not None:
            tick()
        with self._mu:
            hits, self._hits = self._hits, {}
            hits_raw, self._hits_raw = self._hits_raw, {}
            deg, self._deg = self._deg, {}
            deg_raw, self._deg_raw = self._deg_raw, {}
        self.metrics.queue_length.set(0)
        inst = self.instance
        tap = self.audit
        if ((hits_raw or hits) and _raw_lanes_available()
                and inst.default_hash_routing()):
            self._flush_hits_raw(hits, hits_raw, deg, deg_raw)
            return
        for khash, (tlv, acc, seq) in hits_raw.items():
            d = deg_raw.get(khash, 0)
            try:
                req = self._req_from_tlv(tlv)
            except Exception:  # noqa: BLE001 - a corrupt queued TLV
                # can only come from a parser bug; drop it rather than
                # poison the whole flush
                log.warning("dropping unparseable queued TLV for key "
                            "hash %d", khash)
                if tap is not None:
                    # injected weight that will never apply: the audit
                    # vector's `lost` leg (permanent drift — ISSUE 19)
                    tap.lose(acc, d)
                continue
            proto, a0, s0 = hits.get(req.key, (req, 0, seq))
            hits[req.key] = (req if seq >= s0 else proto, a0 + acc,
                             max(s0, seq))
            if d:
                deg[req.key] = deg.get(req.key, 0) + d
        if not hits:
            return
        # group by owner peer; each entry keeps its requeue tuple so a
        # failed chunk goes BACK on the queue instead of vanishing
        by_owner: Dict[str, Tuple[object, List[RateLimitRequest],
                                  List[tuple]]] = {}
        absorbed = absorbed_deg = 0
        for key, (req, acc, seq) in hits.items():
            if acc <= 0:
                continue
            d = deg.get(key, 0)
            peer = self.instance.owner_of(key)
            if peer is None or self.instance.is_self(peer):
                # we are the owner: already applied locally — settle
                # the audit entry as absorbed
                absorbed += acc
                absorbed_deg += d
                continue
            merged = RateLimitRequest(
                name=req.name, unique_key=req.unique_key, hits=acc,
                limit=req.limit, duration=req.duration,
                algorithm=req.algorithm, behavior=req.behavior,
                burst=req.burst)
            addr = peer.info.grpc_address
            slot = by_owner.setdefault(addr, (peer, [], []))
            slot[1].append(merged)
            slot[2].append((key, req, acc, seq, d))
        if tap is not None:
            tap.apply(absorbed, absorbed_deg, absorbed=True)
        errors = []
        for addr, (peer, reqs, entries) in by_owner.items():
            limit = self.behaviors.global_batch_limit
            for i in range(0, len(reqs), limit):
                try:
                    peer.get_peer_rate_limits(
                        reqs[i:i + limit],
                        timeout_s=self.behaviors.global_timeout_ms
                        / 1000.0)
                except Exception as e:  # noqa: BLE001 - requeue, next
                    # tick retries (exact reconcile, ISSUE 5).
                    # exc_text: a peer deadline/TimeoutError str()s empty
                    self._requeue_hits(entries[i:])
                    errors.append(f"global hits sync to {addr}: "
                                  f"{exc_text(e)}")
                    self.metrics.check_error_counter.labels(
                        error="global_hits_sync").inc()
                    log.warning(errors[-1])
                    self._record_event("error", stage="global_hits_sync",
                                       error=errors[-1])
                    break
                if tap is not None:
                    # the owner acked this chunk: settle its entries
                    ent = entries[i:i + limit]
                    tap.apply(sum(e[2] for e in ent),
                              sum(e[4] for e in ent))
        self._record(errors)

    def _flush_hits_raw(self, hits, hits_raw, deg=None,
                        deg_raw=None) -> None:
        """Columnar hit flush: raw-khash merge → per-key TLV with the
        aggregate hits → per-owner payloads on the forward lanes."""
        from .hashing import fnv1a64
        from .wire import req_to_tlv, tlv_with_hits

        tap = self.audit
        merged: Dict[int, Tuple[object, int, int]] = dict(hits_raw)
        degm: Dict[int, int] = dict(deg_raw or {})
        for key, (req, acc, seq) in hits.items():
            kh = fnv1a64(key.encode("utf-8"))
            cur = merged.get(kh)
            if cur is None:
                merged[kh] = (req, acc, seq)
            else:
                proto, a0, s0 = cur
                merged[kh] = (req if seq >= s0 else proto, a0 + acc,
                              max(s0, seq))
            d = (deg or {}).get(key, 0)
            if d:
                degm[kh] = degm.get(kh, 0) + d
        inst = self.instance
        by_owner: Dict[str, Tuple[object, List[bytes], List[tuple]]] = {}
        absorbed = absorbed_deg = 0
        for kh, (proto, acc, seq) in merged.items():
            if acc <= 0:
                continue
            d = degm.get(kh, 0)
            peer = inst.owner_by_raw_khash(kh)
            if peer is None or inst.is_self(peer):
                # we are the owner: already applied locally — settle
                # the audit entry as absorbed
                absorbed += acc
                absorbed_deg += d
                continue
            tlv = (tlv_with_hits(proto, acc) if isinstance(proto, bytes)
                   else req_to_tlv(RateLimitRequest(
                       name=proto.name, unique_key=proto.unique_key,
                       hits=acc, limit=proto.limit,
                       duration=proto.duration,
                       algorithm=proto.algorithm, behavior=proto.behavior,
                       burst=proto.burst,
                       # _req_stamped's stamp (the raw lane's TLVs
                       # carry theirs): without it the owner applies
                       # the aggregate at its wall clock, and a row on
                       # an older base reads as expired — bucket reset
                       created_at=proto.created_at)))
            addr = peer.info.grpc_address
            slot = by_owner.setdefault(addr, (peer, [], []))
            slot[1].append(tlv)
            # requeue tuple keyed the way it was queued: raw-lane
            # protos under the raw khash, object-lane under the key
            if isinstance(proto, bytes):
                slot[2].append((kh, proto, acc, seq, d))
            else:
                slot[2].append((proto.key, proto, acc, seq, d))
        if tap is not None:
            tap.apply(absorbed, absorbed_deg, absorbed=True)
        futs = []
        limit = self.behaviors.global_batch_limit
        for addr, (peer, tlvs, entries) in by_owner.items():
            for i in range(0, len(tlvs), limit):
                chunk = tlvs[i:i + limit]
                ent = entries[i:i + limit]
                try:
                    # clock-ok: GLOBAL aggregate hit deltas — accumulated counts, not fresh requests; the owner's authoritative bucket is the time base by design
                    futs.append((addr, peer.forward_raw(
                        b"".join(chunk), len(chunk)), ent))
                except Exception as e:  # noqa: BLE001 - ErrCircuitOpen/
                    # ErrClosing fail fast; requeued below
                    futs.append((addr, _failed_future(e), ent))
        errors = []
        deadline = time.monotonic() + \
            self.behaviors.global_timeout_ms / 1000.0 + 30.0
        for addr, fut, ent in futs:
            try:
                fut.result(timeout=max(deadline - time.monotonic(), 0.1))
            except Exception as e:  # noqa: BLE001 - requeue so the
                # aggregates survive until the owner is reachable
                # (exact reconcile, ISSUE 5)
                self._requeue_hits(ent)
                errors.append(f"global hits sync to {addr}: "
                              f"{exc_text(e)}")
                self.metrics.check_error_counter.labels(
                    error="global_hits_sync").inc()
                log.warning(errors[-1])
                self._record_event("error", stage="global_hits_sync",
                                   error=errors[-1])
                continue
            if tap is not None:
                # the owner acked this chunk: settle its entries
                tap.apply(sum(e[2] for e in ent),
                          sum(e[4] for e in ent))
        self._record(errors)

    def _run_broadcasts(self) -> None:
        """Owner side: push merged authoritative state to all peers.
        reference: global.go › runBroadcasts → UpdatePeerGlobals."""
        with self._tick_context("global.broadcast"):
            self._broadcast_tick()

    def _broadcast_tick(self) -> None:
        if self._fault_tick("global_broadcast", "global broadcast"):
            return
        with self._mu:
            updates, self._updates = self._updates, {}
            updates_raw, self._updates_raw = self._updates_raw, {}
        for khash, (seq, tlv) in updates_raw.items():
            try:
                req = self._req_from_tlv(tlv)
            except Exception:  # noqa: BLE001
                log.warning("dropping unparseable queued TLV for key "
                            "hash %d", khash)
                continue
            cur = updates.get(req.key)
            if cur is None or seq > cur[0]:
                updates[req.key] = (seq, req)
        if not updates:
            return
        disp = getattr(self.instance, "dispatcher", None)
        # per-phase attribution (closes the PR-4 ROADMAP open item):
        # the broadcast path lands in the PhaseLedger / histogram next
        # to ingest/device/peer_flush
        bc = phase("broadcast", disp).begin()
        msgs = self.instance.build_global_updates(
            [r for _, r in updates.values()])
        if not msgs:
            bc.end(keep=False)
            return
        peers = [p for p in self.instance.peers() if not self.instance.is_self(p)]
        errors = []
        limit = self.behaviors.global_batch_limit
        if peers and _raw_lanes_available():
            # columnar broadcast: serialize each UpdatePeerGlobal ONCE
            # into its `globals` TLV (the typed stub re-serialized the
            # same messages per peer), then every peer's chunk rides
            # its pooled update lane — pipelined, retried, circuit-
            # gated, aggregated per peer per window
            from .wire import _varint

            tlvs = []
            for m in msgs:
                payload = m.SerializeToString()
                tlvs.append(b"\x0a" + _varint(len(payload)) + payload)
            chunks = [b"".join(tlvs[i:i + limit])
                      for i in range(0, len(tlvs), limit)]
            futs = []
            for peer in peers:
                for i, chunk in enumerate(chunks):
                    n = min(limit, len(tlvs) - i * limit)
                    try:
                        futs.append((peer.info.grpc_address,
                                     peer.send_globals_raw(chunk, n)))
                    except Exception as e:  # noqa: BLE001 - fail fast
                        futs.append((peer.info.grpc_address,
                                     _failed_future(e)))
            deadline = time.monotonic() + \
                self.behaviors.global_timeout_ms / 1000.0 + 30.0
            failed_addrs = set()
            for addr, fut in futs:
                try:
                    fut.result(timeout=max(deadline - time.monotonic(),
                                           0.1))
                except Exception as e:  # noqa: BLE001
                    if addr not in failed_addrs:
                        failed_addrs.add(addr)
                        errors.append(f"global broadcast to {addr}: "
                                      f"{exc_text(e)}")
                        self.metrics.check_error_counter.labels(
                            error="global_broadcast").inc()
                        log.warning(errors[-1])
        else:
            for peer in peers:
                try:
                    for i in range(0, len(msgs), limit):
                        peer.update_peer_globals(msgs[i:i + limit])
                except Exception as e:  # noqa: BLE001
                    errors.append(f"global broadcast to "
                                  f"{peer.info.grpc_address}: "
                                  f"{exc_text(e)}")
                    self.metrics.check_error_counter.labels(
                        error="global_broadcast").inc()
                    log.warning(errors[-1])
        self._record(errors)
        self.metrics.global_broadcast_counter.inc()
        dt = bc.end()
        self.metrics.broadcast_duration.observe(dt)
        if disp is not None:
            ana = getattr(disp, "analytics", None)
            if ana is not None and peers and not errors:
                # cost-model sample (ISSUE 11): one broadcast fans the
                # serialized update set out to every peer.  Errored
                # rounds are excluded — a timeout's duration measures
                # the deadline, not the transfer.
                nbytes = sum(m.ByteSize() for m in msgs) * len(peers)
                ana.tap_cost("broadcast", nbytes, len(peers) + 1, dt)
        self._record_event("broadcast", keys=len(msgs), peers=len(peers),
                           errors=len(errors),
                           error=("; ".join(errors) or None))

    # ---- error surfacing (health_check) --------------------------------

    #: An async-replication error older than this no longer marks the
    #: daemon unhealthy (the loops retry every tick; a stale error would
    #: otherwise fail readiness probes forever).
    ERROR_TTL_S = 60.0

    def _record_event(self, kind: str, **fields) -> None:
        """Best-effort flight-recorder hook (instance owns the ring)."""
        rec = getattr(self.instance, "recorder", None)
        if rec is not None:
            rec.record(kind, **fields)

    def _record(self, errors) -> None:
        """Per-tick error aggregation: success clears, failure stamps."""
        with self._err_mu:
            if errors:
                self._last_error = "; ".join(errors)
                self._last_error_at = time.monotonic()
            else:
                self._last_error = ""

    @property
    def last_error(self) -> str:
        with self._err_mu:
            if (self._last_error and
                    time.monotonic() - self._last_error_at > self.ERROR_TTL_S):
                return ""
            return self._last_error

    def poke(self) -> None:
        """Force both loops to run now (tests / shutdown flush)."""
        self._hits_loop.poke()
        self._bcast_loop.poke()

    def close(self) -> None:
        self._hits_loop.close()
        self._bcast_loop.close()
