"""Peer transport: columnar send lanes with pipelined flushes.

reference: peer_client.go › PeerClient — reconstructed, mount empty.

The forward hop is end-to-end columnar (ISSUE 3): callers enqueue raw
request TLV slices into a pooled per-peer send buffer (`_SendLane`);
a flusher thread drains it with the dispatcher's no-overshoot coalescer
rules (greedy backlog first, a tiny straggler window, never overshoot
the batch limit — the entry that would overflow leads the next flush)
and ships each flush as ONE raw-bytes RPC with up to depth-K in flight
(`BehaviorConfig.peer_inflight`).  RPC futures resolve off the flusher
thread (grpc callback threads), so the flusher packs flush N+1 while
N..N+K-1 ride the wire — the forward-hop analog of the dispatcher's
overlapped wave pipeline.  A failed flush retries with linear backoff;
after `peer_circuit_threshold` consecutive final failures the peer's
circuit OPENS and sends fail fast instead of queuing behind a dead
peer, until a cooldown elapses and one probe flush half-opens it.

Object-path forwards (`enqueue`) serialize to a TLV at enqueue time and
ride the same lane; GLOBAL hit flushes and owner broadcasts ride it too
(global_manager.py), aggregated per peer per window.  Without the C++
codec (`ops/_native`) the legacy object-batching flusher below serves
instead — same API, per-request pb2 objects.

Shutdown drains in-flight flushes before closing the channel.
"""
from __future__ import annotations

import logging
import queue
import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import List, Optional, Sequence

import grpc

from .config import BehaviorConfig
from .grpc_api import PeersV1Stub, dial_peer, raw_unary
from .proto import gubernator_pb2 as pb
from .proto import peers_pb2 as peers_pb
from .tracing import outbound_metadata, phase
from .types import Behavior, PeerInfo, RateLimitRequest, RateLimitResponse
from .wire import req_to_pb, resp_from_pb

try:  # raw response splitting for the columnar lanes; optional
    from .ops import native as _wire_native
except ImportError:  # pragma: no cover - unbuilt extension
    _wire_native = None

log = logging.getLogger("gubernator_tpu.peer")


class ErrClosing(Exception):
    """Raised for requests that arrive while the client drains.
    reference: peer_client.go › ErrClosing."""


class ErrCircuitOpen(Exception):
    """Raised (fail-fast) for sends while the peer's circuit is open —
    a dead peer must cost an immediate error response, not a queue of
    callers waiting out its timeouts."""


class _Entry:
    """One send-buffer entry: ``n_items`` request TLVs whose bytes sit
    in the lane's shared buffer; ``future`` resolves to this entry's
    contiguous slice of the response bytes."""

    __slots__ = ("nbytes", "n_items", "future", "trace", "t_enq")

    def __init__(self, nbytes: int, n_items: int, future: Future,
                 trace: Optional[str], t_enq: float):
        self.nbytes = nbytes
        self.n_items = n_items
        self.future = future
        self.trace = trace
        self.t_enq = t_enq


class _SendLane:
    """Pooled send buffer + depth-K pipelined raw RPCs to one peer
    method.  ``split`` lanes (GetPeerRateLimits) resolve each entry
    with its contiguous response-TLV byte slice; non-split lanes
    (UpdatePeerGlobals) resolve with the raw response bytes."""

    def __init__(self, client: "PeerClient", method: str,
                 max_items: int, rpc_timeout_s: float, split: bool):
        self.client = client
        self.method = method
        self.max_items = max(int(max_items), 1)
        self.rpc_timeout_s = rpc_timeout_s
        self.split = split
        b = client.behaviors
        self.window_s = max(int(getattr(b, "peer_coalesce_us", 200)),
                            0) / 1e6
        self.depth = max(int(getattr(b, "peer_inflight", 4)), 1)
        self.retries = max(int(getattr(b, "peer_retry_limit", 2)), 0)
        self.backoff_s = max(int(getattr(b, "peer_retry_backoff_ms", 25)),
                             0) / 1e3
        self._cond = threading.Condition()
        #: pooled: entries append, flush cuts
        self._buf = bytearray()  # guarded-by: self._cond
        self._entries: "deque[_Entry]" = deque()  # guarded-by: self._cond
        self._queued_items = 0  # guarded-by: self._cond
        self._inflight = 0  # guarded-by: self._cond
        self._thread: Optional[threading.Thread] = None  # guarded-by: self._cond
        self._closing = False  # guarded-by: self._cond

    # ---- producer side -------------------------------------------------

    def enqueue(self, data: bytes, n_items: int,
                traceparent: Optional[str] = None) -> Future:
        """Queue ``n_items`` request TLVs for the next flush.  Raises
        ErrClosing / ErrCircuitOpen (fail fast) instead of queuing."""
        if self.client._circuit_blocked():
            raise ErrCircuitOpen(
                f"peer {self.client.info.grpc_address} circuit open")
        fut: Future = Future()
        e = _Entry(len(data), int(n_items), fut, traceparent,
                   time.monotonic())
        with self._cond:
            if self._closing:
                raise ErrClosing("peer client is closing")
            self._buf += data
            self._entries.append(e)
            self._queued_items += e.n_items
            depth = self._queued_items
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._run, daemon=True,
                    name=f"peer-lane-{self.method}-"
                         f"{self.client.info.grpc_address}")
                self._thread.start()
            self._cond.notify_all()
        m = self.client._metrics
        if m is not None:
            m.peer_send_buffer_depth.labels(
                peer_addr=self.client.info.grpc_address).set(depth)
        return fut

    # ---- flusher -------------------------------------------------------

    # lock-free: caller holds self._cond (the flusher's take under its wait loop)
    def _take_locked(self) -> tuple:
        """Pop entries for one flush under _cond: greedy, never
        overshooting max_items — the entry that would overflow leads
        the NEXT flush (the dispatcher's no-overshoot rule)."""
        batch: List[_Entry] = []
        nbytes = items = 0
        while self._entries:
            e = self._entries[0]
            if batch and items + e.n_items > self.max_items:
                break
            self._entries.popleft()
            batch.append(e)
            items += e.n_items
            nbytes += e.nbytes
            if items >= self.max_items:
                break
        data = bytes(memoryview(self._buf)[:nbytes])
        del self._buf[:nbytes]
        self._queued_items -= items
        return batch, data, items

    def _run(self) -> None:
        while True:
            with self._cond:
                while not self._entries and not self._closing:
                    self._cond.wait(0.5)
                if not self._entries:
                    return  # closing and drained
                batch, data, items = self._take_locked()
            if (items < self.max_items and self.window_s > 0
                    # lock-free: racy bool read; a late close just skips the straggler wait
                    and not self._closing):
                # straggler window: only after the backlog was drained
                # (a full flush skips the wait entirely)
                deadline = time.monotonic() + self.window_s
                while items < self.max_items:
                    with self._cond:
                        remain = deadline - time.monotonic()
                        if remain <= 0:
                            break
                        if not self._entries:
                            self._cond.wait(remain)
                        if not self._entries:
                            break
                        e = self._entries[0]
                        if items + e.n_items > self.max_items:
                            break
                        self._entries.popleft()
                        batch.append(e)
                        items += e.n_items
                        extra = bytes(memoryview(self._buf)[:e.nbytes])
                        del self._buf[:e.nbytes]
                        self._queued_items -= e.n_items
                    data += extra
            with self._cond:
                while self._inflight >= self.depth and not self._closing:
                    self._cond.wait(0.2)
                depth_now = self._queued_items
            m = self.client._metrics
            if m is not None:
                m.peer_send_buffer_depth.labels(
                    peer_addr=self.client.info.grpc_address).set(
                        depth_now)
                m.peer_flush_size.observe(items)
                now = time.monotonic()
                for e in batch:
                    m.peer_flush_wait.observe(max(now - e.t_enq, 0.0))
            self._launch(batch, data, attempt=0)

    def _launch(self, entries: List[_Entry], data: bytes,
                attempt: int) -> None:
        client = self.client
        # lock-free: racy bool read; a retry racing close fails fast next hop
        if attempt and (self._closing or client._closing.is_set()):
            # a retry timer outliving shutdown must fail fast, never
            # re-dial a closed channel
            self._fail(entries, ErrClosing("peer client closed"))
            return
        if client._circuit_blocked():
            self._fail(entries, ErrCircuitOpen(
                f"peer {client.info.grpc_address} circuit open"))
            return
        # the forward hop's share of a request's wall time; ended by
        # whichever thread the RPC completes on
        flush = phase("peer_flush", client._analytics, span=False).begin()
        try:
            # faultpoint: a chaos run failing/delaying this peer's
            # sends lands here — same handling as a real dial failure
            client._fault("peer_send")
            call = client._raw_call(self.method)
            tp = next((e.trace for e in entries if e.trace), None)
            md = ([("traceparent", tp)] if tp else outbound_metadata())
            rpc = call.future(data, timeout=self.rpc_timeout_s,
                              metadata=md)
        except Exception as e:  # noqa: BLE001 - incl. closed channel
            self._on_done(None, entries, data, attempt, flush, err=e)
            return
        with self._cond:
            self._inflight += 1
        m = client._metrics
        if m is not None:
            m.peer_inflight_rpcs.labels(
                peer_addr=client.info.grpc_address).inc()
        rpc.add_done_callback(
            lambda f: self._rpc_done(f, entries, data, attempt, flush))

    def _rpc_done(self, f, entries, data, attempt, flush) -> None:
        """grpc callback thread: resolve futures OFF the flusher so it
        keeps packing the next flush while responses land."""
        with self._cond:
            self._inflight -= 1
            self._cond.notify_all()
        m = self.client._metrics
        if m is not None:
            m.peer_inflight_rpcs.labels(
                peer_addr=self.client.info.grpc_address).dec()
        try:
            rbytes = f.result()
            # faultpoint: lose/delay the response after the RPC
            # succeeded (tests the retry path's idempotence)
            self.client._fault("peer_recv")
        except Exception as e:  # noqa: BLE001 - RpcError et al.
            self._on_done(None, entries, data, attempt, flush, err=e)
            return
        self._on_done(rbytes, entries, data, attempt, flush)

    def _on_done(self, rbytes, entries, data, attempt, flush,
                 err: Optional[BaseException] = None) -> None:
        client = self.client
        m = client._metrics
        dt = flush.end()
        if m is not None:
            m.batch_send_duration.labels(
                peer_addr=client.info.grpc_address).observe(dt)
        if client._analytics is not None:
            if err is None:
                # cost-model sample (ISSUE 11): one point-to-point hop
                # of len(data) wire bytes (failed sends excluded — a
                # timeout measures the deadline, not the transfer)
                client._analytics.tap_cost("peer_flush", len(data),
                                           2, dt)
        if err is not None:
            # lock-free: racy bool read; a retry racing close fails fast next hop
            if (attempt < self.retries and not self._closing
                    and not client._circuit_blocked()):
                if m is not None:
                    m.peer_retry_counter.labels(
                        peer_addr=client.info.grpc_address).inc()
                from .telemetry import exc_text

                log.warning("peer flush to %s failed (attempt %d/%d), "
                            "retrying: %s", client.info.grpc_address,
                            attempt + 1, self.retries + 1,
                            exc_text(err))
                t = threading.Timer(
                    self.backoff_s * (attempt + 1),
                    self._launch, args=(entries, data, attempt + 1))
                t.daemon = True
                t.start()
                return
            client._record_failure()
            self._fail(entries, err)
            return
        client._record_success()
        self._resolve(entries, rbytes)

    def _resolve(self, entries: List[_Entry], rbytes: bytes) -> None:
        if not self.split:
            for e in entries:
                if not e.future.done():
                    e.future.set_result(rbytes)
            return
        sp = (_wire_native.split_resp_items(rbytes)
              if _wire_native is not None else None)
        total = sum(e.n_items for e in entries)
        if sp is None or sp[0].size != total:
            self._fail(entries, RuntimeError(
                "malformed or short peer response batch"))
            return
        off, ln, _st = sp
        i = 0
        for e in entries:
            if e.n_items == 0:
                payload = b""
            else:
                a = int(off[i])
                j = i + e.n_items - 1
                b = int(off[j]) + int(ln[j])
                payload = rbytes[a:b]
            i += e.n_items
            if not e.future.done():
                e.future.set_result(payload)

    def _fail(self, entries: List[_Entry],
              err: BaseException) -> None:
        from .telemetry import exc_text

        # exc_text: a flush deadline (grpc DEADLINE_EXCEEDED while the
        # owner compiles) must not log as an empty string
        log.warning("peer flush to %s failed (%d items): %s",
                    self.client.info.grpc_address,
                    sum(e.n_items for e in entries), exc_text(err))
        for e in entries:
            if not e.future.done():
                e.future.set_exception(err)

    # ---- lifecycle -----------------------------------------------------

    def stats(self) -> dict:
        with self._cond:
            return {"queued_items": self._queued_items,
                    "queued_entries": len(self._entries),
                    "inflight": self._inflight}

    def close(self, timeout_s: float) -> None:
        """Flush the remaining backlog, wait out in-flight RPCs, then
        fail anything still unresolved with ErrClosing."""
        with self._cond:
            self._closing = True
            self._cond.notify_all()
            t = self._thread
        if t is not None and t.is_alive():
            t.join(timeout=timeout_s)
        deadline = time.monotonic() + timeout_s
        with self._cond:
            while self._inflight > 0 and time.monotonic() < deadline:
                self._cond.wait(0.1)
            leftovers, self._entries = list(self._entries), deque()
            self._buf = bytearray()
            self._queued_items = 0
        for e in leftovers:
            if not e.future.done():
                e.future.set_exception(ErrClosing("peer client closed"))


class PeerClient:
    """One gRPC connection + columnar send lanes to a single peer."""

    def __init__(self, info: PeerInfo, behaviors: BehaviorConfig,
                 tls_creds: Optional[grpc.ChannelCredentials] = None,
                 metrics=None, analytics=None, faults=None):
        self.info = info
        self.behaviors = behaviors
        self._tls = tls_creds
        self._metrics = metrics
        #: optional KeyAnalytics: flush round-trips feed the
        #: "peer_flush" phase of the latency ledger (ISSUE 4)
        self._analytics = analytics
        #: optional FaultSet (faults.py): peer_send / peer_recv /
        #: peer_circuit faultpoints, tagged with this peer's address
        self._faults = faults
        self._channel: Optional[grpc.Channel] = None  # guarded-by: self._lock
        self._stub: Optional[PeersV1Stub] = None  # guarded-by: self._lock
        #: method → bytes-lane call handle
        self._raw_calls: dict = {}  # guarded-by: self._lock
        #: legacy object-batching queue (no-native fallback):
        #: (request, future, captured traceparent-or-None)
        self._queue: "queue.Queue[tuple]" = queue.Queue()
        self._closing = threading.Event()
        self._lock = threading.Lock()
        self._flusher: Optional[threading.Thread] = None
        # circuit breaker, shared by both lanes: consecutive final
        # flush failures open it; one success closes it
        self._circ_mu = threading.Lock()
        self._consec_failures = 0  # guarded-by: self._circ_mu
        self._open_until = 0.0  # guarded-by: self._circ_mu
        self._circuit_opens = 0  # guarded-by: self._circ_mu
        # routing-health hysteresis (ISSUE 5, health-gated ring):
        # _route_bad_since = start of the current circuit-open streak
        # (0 while healthy); _route_recovered_at = when the last streak
        # ended; _route_ejected = this peer is currently out of the
        # routing ring and held out until the readmit window passes
        self._route_bad_since = 0.0  # guarded-by: self._circ_mu
        self._route_recovered_at = 0.0  # guarded-by: self._circ_mu
        self._route_ejected = False  # guarded-by: self._circ_mu
        fwd_timeout = behaviors.batch_timeout_ms / 1000.0 + 60.0
        upd_timeout = behaviors.global_timeout_ms / 1000.0
        if _wire_native is not None:
            self._forward_lane: Optional[_SendLane] = _SendLane(
                self, "GetPeerRateLimits", behaviors.batch_limit,
                fwd_timeout, split=True)
            self._globals_lane: Optional[_SendLane] = _SendLane(
                self, "UpdatePeerGlobals", behaviors.global_batch_limit,
                upd_timeout, split=False)
        else:  # pragma: no cover - unbuilt extension
            self._forward_lane = self._globals_lane = None

    # ---- connection ----------------------------------------------------

    def _ensure_stub(self) -> PeersV1Stub:
        with self._lock:
            if self._stub is None:
                self._channel = dial_peer(self.info.grpc_address, self._tls)
                self._stub = PeersV1Stub(self._channel)
            return self._stub

    def _raw_call(self, method: str):
        """bytes-in/bytes-out call handle (identity serializers)."""
        self._ensure_stub()
        with self._lock:
            call = self._raw_calls.get(method)
            if call is None:
                call = self._raw_calls[method] = raw_unary(
                    self._channel, method)
            return call

    # ---- circuit breaker -----------------------------------------------

    def _fault(self, point: str) -> None:
        """Fire a faultpoint tagged with this peer's address (no-op
        while disarmed — one attribute read)."""
        f = self._faults
        if f is not None and f.armed:
            f.fire(point, self.info.grpc_address)

    def _circuit_blocked(self) -> bool:
        f = self._faults
        if (f is not None and f.armed
                and f.should("peer_circuit", self.info.grpc_address)):
            return True
        with self._circ_mu:
            return time.monotonic() < self._open_until

    def _record_failure(self) -> None:
        b = self.behaviors
        threshold = max(int(getattr(b, "peer_circuit_threshold", 3)), 1)
        cooldown = max(int(getattr(b, "peer_circuit_cooldown_ms",
                                   2000)), 0) / 1e3
        with self._circ_mu:
            self._consec_failures += 1
            if self._consec_failures < threshold:
                return
            now = time.monotonic()
            was_open = now < self._open_until
            self._open_until = now + cooldown
            self._circuit_opens += 1
            # routing health: the open streak starts at the FIRST open
            # and survives half-open probe failures (re-opens extend
            # it) — only a success ends it
            if self._route_bad_since == 0.0:
                self._route_bad_since = now
            self._route_recovered_at = 0.0
        if not was_open:
            log.warning("peer %s circuit OPEN after %d consecutive "
                        "flush failures; failing fast for %.1fs",
                        # lock-free: diagnostic snapshot just off the lock
                        self.info.grpc_address, self._consec_failures,
                        cooldown)
            if self._metrics is not None:
                self._metrics.peer_circuit_open_counter.labels(
                    peer_addr=self.info.grpc_address).inc()
                self._metrics.peer_circuit_state.labels(
                    peer_addr=self.info.grpc_address).set(1)

    def _record_success(self) -> None:
        with self._circ_mu:
            was_open = self._open_until > 0
            self._consec_failures = 0
            self._open_until = 0.0
            if self._route_bad_since:
                self._route_bad_since = 0.0
                self._route_recovered_at = time.monotonic()
        if was_open:
            log.info("peer %s circuit closed (probe flush succeeded)",
                     self.info.grpc_address)
            if self._metrics is not None:
                self._metrics.peer_circuit_state.labels(
                    peer_addr=self.info.grpc_address).set(0)

    def circuit_open(self) -> bool:
        """Operator-facing circuit state (deep healthz)."""
        return self._circuit_blocked()

    def route_healthy(self, eject_after_s: float,
                      readmit_after_s: float) -> bool:
        """Routing-ring health with hysteresis (ISSUE 5): False ejects
        this peer from the health-gated ring.

        Eject only after the circuit-open streak has lasted
        ``eject_after_s`` (a transient blip never moves keys); once
        ejected, readmit only after the peer has stayed recovered for
        ``readmit_after_s`` — a peer flapping open/closed inside the
        window stays out, so keys rehome exactly once per outage."""
        now = time.monotonic()
        with self._circ_mu:
            if self._route_bad_since:
                if now - self._route_bad_since >= eject_after_s:
                    self._route_ejected = True
                    return False
                return True
            if self._route_ejected:
                if (self._route_recovered_at
                        and now - self._route_recovered_at
                        >= readmit_after_s):
                    self._route_ejected = False
                    return True
                return False
            return True

    def probe(self):
        """One empty flush through the globals lane — the health
        prober's half-open probe for EJECTED peers (rehomed keys mean
        no organic traffic would ever close their circuit).  A 0-item
        UpdatePeerGlobals is a real RPC the peer answers trivially;
        success runs ``_record_success`` and starts the readmit clock.
        Returns the flush Future, or None when probing isn't possible
        (no native lanes / closing)."""
        if self._closing.is_set() or self._globals_lane is None:
            return None
        try:
            return self._globals_lane.enqueue(b"", 0)
        except (ErrClosing, ErrCircuitOpen):
            return None

    def lane_stats(self) -> dict:
        """Send-lane + circuit state for /healthz?deep=1."""
        with self._circ_mu:
            circ = {"open": time.monotonic() < self._open_until,
                    "consecutive_failures": self._consec_failures,
                    "opens": self._circuit_opens,
                    "route_ejected": self._route_ejected}
        out = {"circuit": circ}
        if self._forward_lane is not None:
            out["forward"] = self._forward_lane.stats()
        if self._globals_lane is not None:
            out["globals"] = self._globals_lane.stats()
        return out

    # ---- forwarded checks ----------------------------------------------

    def get_peer_rate_limit(self, req: RateLimitRequest,
                            timeout_s: Optional[float] = None
                            ) -> RateLimitResponse:
        """Forward one request to the owning peer.  Batched unless the
        request (or config) disables batching."""
        if self._closing.is_set():
            raise ErrClosing("peer client is closing")
        if req.behavior & Behavior.NO_BATCHING:
            return self.get_peer_rate_limits([req])[0]
        fut = self.enqueue(req)
        if timeout_s is None:
            timeout_s = (self.behaviors.batch_timeout_ms
                         + self.behaviors.batch_wait_ms) / 1000.0 + 30.0
        return fut.result(timeout=timeout_s)

    def enqueue(self, req: RateLimitRequest) -> Future:
        """Queue one request for the next batch flush; resolve later.

        With the C++ codec the request serializes to its TLV slice NOW
        and rides the columnar forward lane (pipelined flushes, retry,
        circuit); the response TLV parses back in the lane's callback
        thread.  The caller's trace context is captured here (the
        flusher thread has none).  Without the codec the legacy
        object-batching flusher below serves."""
        if self._closing.is_set():
            raise ErrClosing("peer client is closing")
        from .tracing import hop_traceparent

        if self._forward_lane is not None:
            from .wire import req_to_tlv

            inner = self._forward_lane.enqueue(
                req_to_tlv(req), 1,
                hop_traceparent("peer.forward",
                                attrs={"peer": self.info.grpc_address,
                                       "items": 1}))
            outer: Future = Future()

            def _convert(f: Future) -> None:
                try:
                    rbytes = f.result()
                    msg = pb.GetRateLimitsResp.FromString(rbytes)
                    outer.set_result(resp_from_pb(msg.responses[0]))
                except Exception as e:  # noqa: BLE001
                    outer.set_exception(e)

            inner.add_done_callback(_convert)
            return outer
        fut = Future()
        self._queue.put((req, fut,
                         hop_traceparent(
                             "peer.forward",
                             attrs={"peer": self.info.grpc_address,
                                    "items": 1})))
        self._start_flusher()
        return fut

    def forward_raw(self, data: bytes, n_items: int,
                    traceparent: Optional[str] = None) -> Future:
        """Columnar forward hop: ``data`` is ``n_items`` verbatim
        request TLV slices (GetRateLimitsReq.requests framing — byte-
        compatible with GetPeerRateLimitsReq.requests).  Returns a
        Future resolving to this call's contiguous slice of response
        TLV bytes (exactly ``n_items`` items, count-verified).  Rides
        the pooled send buffer: concurrent callers forwarding to the
        same peer share flush RPCs, with depth-K in flight.  Raises
        ErrClosing / ErrCircuitOpen for fail-fast paths."""
        if self._closing.is_set():
            raise ErrClosing("peer client is closing")
        if self._forward_lane is None:
            raise RuntimeError("columnar peer lane needs the native "
                               "extension (run `make native`)")
        if traceparent is None:
            # mint + RECORD the hop (ISSUE 12): the header's span id
            # becomes the owner-side request span's parent, stitching
            # the two daemons' halves into one assembled trace
            from .tracing import hop_traceparent

            traceparent = hop_traceparent(
                "peer.forward",
                attrs={"peer": self.info.grpc_address,
                       "items": int(n_items)})
        return self._forward_lane.enqueue(data, n_items, traceparent)

    def send_globals_raw(self, data: bytes, n_items: int,
                         traceparent: Optional[str] = None) -> Future:
        """Owner-broadcast twin of ``forward_raw``: ``data`` is
        ``n_items`` serialized UpdatePeerGlobalsReq.globals TLVs; the
        future resolves to the (empty) response bytes.  Serialized
        once, shared across every peer's lane — the per-peer pb2
        re-serialization the typed stub forced is gone.  Like
        forward_raw, a None ``traceparent`` captures (and records the
        hop for) the calling thread's trace — the global manager's
        tick wraps itself in a request context, so a broadcast is
        traceable end-to-end (ISSUE 12)."""
        if self._closing.is_set():
            raise ErrClosing("peer client is closing")
        if self._globals_lane is None:
            raise RuntimeError("columnar peer lane needs the native "
                               "extension (run `make native`)")
        if traceparent is None:
            from .tracing import hop_traceparent

            traceparent = hop_traceparent(
                "peer.forward",
                attrs={"peer": self.info.grpc_address,
                       "items": int(n_items), "lane": "globals"})
        return self._globals_lane.enqueue(data, n_items, traceparent)

    def get_peer_rate_limits(self, reqs: Sequence[RateLimitRequest],
                             timeout_s: Optional[float] = None,
                             traceparent: Optional[str] = None
                             ) -> List[RateLimitResponse]:
        """Synchronous batch call (peers.proto › GetPeerRateLimits).
        Default deadline is generous (forwarded checks must survive the
        owner's first-compile); the global manager passes its own
        global_timeout_ms.  ``traceparent`` lets a flusher carry a
        trace captured at enqueue time (its own thread has none)."""
        stub = self._ensure_stub()
        msg = peers_pb.GetPeerRateLimitsReq()
        msg.requests.extend(req_to_pb(r) for r in reqs)
        if timeout_s is None:
            timeout_s = self.behaviors.batch_timeout_ms / 1000.0 + 60.0
        md = ([("traceparent", traceparent)] if traceparent
              else outbound_metadata())
        resp = stub.GetPeerRateLimits(msg, timeout=timeout_s, metadata=md)
        return [resp_from_pb(m) for m in resp.rate_limits]

    def get_peer_rate_limits_raw_future(self, data: bytes,
                                        timeout_s: Optional[float] = None):
        """Forward already-serialized request TLVs and return a Future
        of raw response bytes.  Since ISSUE 3 this is a thin wrapper
        over the pooled forward lane (``forward_raw``) — kept for
        callers that hold pre-counted TLV bytes; ``timeout_s`` is
        subsumed by the lane's RPC deadline."""
        cnt = (_wire_native.count_req_items(data)
               if _wire_native is not None else None)
        if cnt is None:
            raise ValueError("unparseable request TLV bytes")
        # clock-ok: pass-through — callers stamp created_at into the raw TLVs (stamp_req_tlvs / _req_stamped) before handing bytes here
        return self.forward_raw(data, cnt)

    def update_peer_globals(self, updates: Sequence[peers_pb.UpdatePeerGlobal]
                            ) -> None:
        stub = self._ensure_stub()
        msg = peers_pb.UpdatePeerGlobalsReq()
        msg.globals.extend(updates)
        stub.UpdatePeerGlobals(
            msg, timeout=self.behaviors.global_timeout_ms / 1000.0,
            metadata=outbound_metadata())

    # ---- legacy batching loop (no-native fallback) ---------------------

    def _start_flusher(self) -> None:
        if self._flusher is None or not self._flusher.is_alive():
            with self._lock:
                if self._flusher is None or not self._flusher.is_alive():
                    self._flusher = threading.Thread(
                        target=self._run, daemon=True,
                        name=f"peer-flush-{self.info.grpc_address}")
                    self._flusher.start()

    def _run(self) -> None:
        """Collect until batch_limit or batch_timeout, then flush.
        reference: peer_client.go › run()."""
        timeout_s = max(self.behaviors.batch_timeout_ms, 1) / 1000.0
        while not self._closing.is_set() or not self._queue.empty():
            batch: List[tuple] = []
            deadline = time.monotonic() + timeout_s
            while len(batch) < self.behaviors.batch_limit:
                remain = deadline - time.monotonic()
                if remain <= 0:
                    break
                try:
                    batch.append(self._queue.get(timeout=remain))
                except queue.Empty:
                    break
            if batch:
                self._flush(batch)

    def _flush(self, batch: List[tuple]) -> None:
        flush = phase("peer_flush", self._analytics, span=False).begin()
        try:
            tp = next((t for _, _, t in batch if t), None)
            resps = self.get_peer_rate_limits([r for r, _, _ in batch],
                                              traceparent=tp)
            for (_, fut, _), resp in zip(batch, resps):
                fut.set_result(resp)
            missing = batch[len(resps):]
            for _, fut, _ in missing:
                fut.set_exception(
                    RuntimeError("peer returned short response batch"))
        except Exception as e:  # noqa: BLE001 - surfaced per-request
            from .telemetry import exc_text

            # exc_text: a flush deadline (grpc DEADLINE_EXCEEDED while
            # the owner compiles) must not log as an empty string
            log.warning("peer batch flush to %s failed (%d reqs): %s",
                        self.info.grpc_address, len(batch), exc_text(e))
            for _, fut, _ in batch:
                if not fut.done():
                    fut.set_exception(e)
        finally:
            dt = flush.end()
            if self._metrics is not None:
                self._metrics.batch_send_duration.labels(
                    peer_addr=self.info.grpc_address).observe(dt)

    # ---- lifecycle -----------------------------------------------------

    def shutdown(self) -> None:
        """Drain queued requests, then close (peer_client.go › shutdown)."""
        self._closing.set()
        if self._flusher is not None and self._flusher.is_alive():
            self._flusher.join(
                timeout=self.behaviors.batch_timeout_ms / 1000.0 + 5)
        # fail anything still queued on the legacy path
        while True:
            try:
                _, fut, _ = self._queue.get_nowait()
                fut.set_exception(ErrClosing("peer client closed"))
            except queue.Empty:
                break
        lane_timeout = self.behaviors.batch_timeout_ms / 1000.0 + 5
        for lane in (self._forward_lane, self._globals_lane):
            if lane is not None:
                lane.close(lane_timeout)
        with self._lock:
            if self._channel is not None:
                self._channel.close()
                self._channel = self._stub = None
                self._raw_calls = {}
