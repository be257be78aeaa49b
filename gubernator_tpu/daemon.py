"""Daemon: listeners + lifecycle around one V1Instance.

reference: daemon.go › Daemon / SpawnDaemon — reconstructed, mount
empty.  Serves:

- gRPC V1 + PeersV1 on ``grpc_listen_address`` (TLS optional),
- an HTTP/JSON gateway on ``http_listen_address`` mirroring the
  reference's grpc-gateway mux: POST /v1/GetRateLimits,
  GET /v1/HealthCheck, plus GET /metrics (prometheus), GET /healthz
  (``?deep=1`` adds dispatcher queue/wave/stall state), and
  GET /debug/events (the flight-recorder ring as JSON — see
  OBSERVABILITY.md),
- the configured discovery source wired to instance.set_peers.
"""
from __future__ import annotations

import json
import logging
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import List, Optional

import grpc

from .config import DaemonConfig
from .discovery import make_discovery
from .dispatcher import ResourceExhausted, request_deadline
from .grpc_api import (add_health_servicer, add_peers_servicer_raw,
                       add_v1_servicer_raw)
from .instance import V1Instance
from .netutil import resolve_host_ip, split_host_port
from .proto import gubernator_pb2 as pb
from .proto import peers_pb2 as peers_pb
from .store import FileLoader
from .telemetry import exc_text
from .tlsutil import setup_tls
from .tracing import (_tls, grpc_request_context, phase, request_context,
                      span)
from .types import Behavior, PeerInfo, RateLimitRequest
from .wire import health_to_pb, req_from_pb, resp_to_pb

log = logging.getLogger("gubernator_tpu.daemon")


class DoorPool(ThreadPoolExecutor):
    """A gRPC server's handler pool, stamping when each task is handed
    over and when it starts.  grpcio submits a unary call when the CALL
    is announced, before its request message has arrived
    (``grpc/_server.py › _handle_unary_unary``); the pool thread then
    blocks in ``unary_request()`` until the ``_serve`` loop has handed
    the message over, and only then calls the servicer.  ``submit``
    runs on the ``_serve`` thread, the task on a pool thread: the two
    readings, left in the pool thread's ``tracing._tls`` (``door_at``,
    ``door_run_at``), are what the servicer makes the phases
    `door.wait` and `door.recv` of."""

    def submit(self, fn, /, *args, **kwargs):
        return super().submit(_door_task, time.perf_counter(), fn,
                              *args, **kwargs)


def _door_task(at, fn, *args, **kwargs):
    _tls.door_at = at
    _tls.door_run_at = time.perf_counter()
    return fn(*args, **kwargs)


class _V1Servicer:
    def __init__(self, instance: V1Instance):
        self.instance = instance
        #: one entry per handler in flight; append / pop / len are
        #: each atomic under the GIL, so the count needs no lock
        self._door: list = []
        self._door_calls = 0  # lock-free: sampling counter, 1 call in 8 is observed

    def GetRateLimits(self, request: pb.GetRateLimitsReq, context):
        with grpc_request_context(
                context, recorder=self.instance.span_recorder), \
                span("grpc.GetRateLimits", metrics=self.instance.metrics), \
                request_deadline(context.time_remaining()):
            try:
                reqs = [req_from_pb(m) for m in request.requests]
                resps = self.instance.get_rate_limits(reqs)
            except ValueError as e:
                context.abort(grpc.StatusCode.INVALID_ARGUMENT, exc_text(e))
            except ResourceExhausted as e:
                context.abort(grpc.StatusCode.RESOURCE_EXHAUSTED,
                              exc_text(e))
            out = pb.GetRateLimitsResp()
            out.responses.extend(resp_to_pb(r) for r in resps)
            return out

    def GetRateLimitsWire(self, request: bytes, context):
        """Raw-bytes twin of GetRateLimits (grpc_api.add_v1_servicer_raw):
        lets the instance's C++ wire lane run decode→decide→encode
        without pb2 when the batch qualifies.  The caller's remaining
        deadline scopes deadline-aware admission shedding (ISSUE 5).

        gubernator_door_inflight: handlers in flight at this one's
        entry, itself included (1 call in 8).  What a call waited for
        between gRPC and this line is measured beside it, from the
        DoorPool's two readings: `door.wait` (handed to the pool →
        started on a pool thread: the pool's queue, the wake-up, the
        GIL) and `door.recv` (started → here: grpcio waiting for the
        request message, which the ONE `_serve` loop hands over under
        the GIL).  1 call in 8; every call where the dispatcher times
        every call (``call_sample`` 1)."""
        door = self._door
        door.append(None)
        n = self._door_calls = self._door_calls + 1
        disp = self.instance.dispatcher
        if not n & 7 or disp.call_sample == 1:
            here = time.perf_counter()
            if not n & 7:  # the mean needs no more than 1 call in 8
                self.instance.metrics.door_inflight.observe(len(door))
            at, run_at = _tls.door_at, _tls.door_run_at
            if at is not None:  # served through a DoorPool
                phase("door.wait", disp, span=False).begin(at=at).end(
                    at=run_at)
                phase("door.recv", disp, span=False).begin(
                    at=run_at).end(at=here)
        try:
            with grpc_request_context(
                    context, recorder=self.instance.span_recorder), \
                    span("grpc.GetRateLimits",
                         metrics=self.instance.metrics), \
                    request_deadline(context.time_remaining()):
                try:
                    return self.instance.get_rate_limits_wire(request)
                except ValueError as e:
                    context.abort(grpc.StatusCode.INVALID_ARGUMENT,
                                  exc_text(e))
                except ResourceExhausted as e:
                    context.abort(grpc.StatusCode.RESOURCE_EXHAUSTED,
                                  exc_text(e))
        finally:
            door.pop()

    def HealthCheck(self, request: pb.HealthCheckReq, context):
        return health_to_pb(self.instance.health_check())


class _PeersServicer:
    def __init__(self, instance: V1Instance):
        self.instance = instance

    def GetPeerRateLimits(self, request: peers_pb.GetPeerRateLimitsReq,
                          context):
        with grpc_request_context(
                context, recorder=self.instance.span_recorder), \
                span("grpc.GetPeerRateLimits",
                     metrics=self.instance.metrics):
            try:
                reqs = [req_from_pb(m) for m in request.requests]
                resps = self.instance.get_peer_rate_limits(reqs)
            except ValueError as e:
                context.abort(grpc.StatusCode.INVALID_ARGUMENT, exc_text(e))
            out = peers_pb.GetPeerRateLimitsResp()
            out.rate_limits.extend(resp_to_pb(r) for r in resps)
            return out

    def GetPeerRateLimitsWire(self, request: bytes, context):
        """Raw-bytes twin of GetPeerRateLimits (C++ wire lane)."""
        with grpc_request_context(
                context, recorder=self.instance.span_recorder), \
                span("grpc.GetPeerRateLimits",
                     metrics=self.instance.metrics), \
                request_deadline(context.time_remaining()):
            try:
                return self.instance.get_peer_rate_limits_wire(request)
            except ValueError as e:
                context.abort(grpc.StatusCode.INVALID_ARGUMENT, exc_text(e))
            except ResourceExhausted as e:
                context.abort(grpc.StatusCode.RESOURCE_EXHAUSTED,
                              exc_text(e))

    def UpdatePeerGlobals(self, request: peers_pb.UpdatePeerGlobalsReq,
                          context):
        with grpc_request_context(
                context, recorder=self.instance.span_recorder), \
                span("grpc.UpdatePeerGlobals",
                     metrics=self.instance.metrics):
            self.instance.update_peer_globals(list(request.globals))
            return peers_pb.UpdatePeerGlobalsResp()


def _json_to_req(o: dict) -> RateLimitRequest:
    """Accept both snake_case and grpc-gateway camelCase field names."""

    def g(*names, default=None):
        for n in names:
            if n in o:
                return o[n]
        return default

    return RateLimitRequest(
        name=g("name", default=""),
        unique_key=g("unique_key", "uniqueKey", default=""),
        hits=int(g("hits", default=1)),
        limit=int(g("limit", default=0)),
        duration=int(g("duration", default=0)),
        algorithm=int(g("algorithm", default=0)),
        behavior=Behavior(int(g("behavior", default=0))),
        burst=int(g("burst", default=0)),
        metadata=g("metadata", default={}) or {},
    )


def _resp_to_json(r) -> dict:
    # grpc-gateway emits proto JSON names (camelCase); keep snake_case
    # too so existing simple clients keep working
    return {"status": int(r.status), "limit": r.limit,
            "remaining": r.remaining,
            "reset_time": r.reset_time, "resetTime": r.reset_time,
            "error": r.error, "metadata": r.metadata}


class Daemon:
    """reference: daemon.go › Daemon.  Use spawn_daemon() to construct."""

    def __init__(self, cfg: DaemonConfig, mesh=None, engine=None):
        from .tracing import DeviceProfiler

        self.cfg = cfg
        self.tls = setup_tls(cfg.tls)
        self._closed = False
        #: drain-aware shutdown (ISSUE 5): True from the moment close()
        #: starts; /healthz answers 503 "draining" for the grace window
        #: before the listeners stop
        self._draining = False
        self.profiler = DeviceProfiler.from_env()
        #: on-demand device profiling (GET /debug/profile?seconds=N):
        #: at most ONE capture at a time — jax.profiler is process-
        #: global, so a second start_trace would corrupt the first
        self._prof_mu = threading.Lock()
        self._runtime_prof: Optional[dict] = None
        self.instance: Optional[V1Instance] = None
        self.discovery = None
        self.http_server: Optional[ThreadingHTTPServer] = None
        self._http_thread: Optional[threading.Thread] = None
        self.client_server: Optional[grpc.Server] = None
        self.client_port: int = 0

        # --- gRPC listener FIRST: an ephemeral port (":0") must be
        # resolved to the real bound port before the advertise address
        # (and thus peer identity / discovery) is derived from it.
        self.grpc_server = grpc.server(
            DoorPool(max_workers=32, thread_name_prefix="grpc-handler"),
            options=[("grpc.so_reuseport", 0)])
        if self.tls is not None:
            bound = self.grpc_server.add_secure_port(
                cfg.grpc_listen_address, self.tls.grpc_server_credentials())
        else:
            bound = self.grpc_server.add_insecure_port(cfg.grpc_listen_address)
        if bound == 0:
            raise OSError(f"failed to bind {cfg.grpc_listen_address}")
        self.grpc_port = bound

        try:
            icfg = cfg.instance_config()
            host, _ = split_host_port(cfg.grpc_listen_address)
            adv = icfg.advertise_address or f"{host}:{bound}"
            adv_host, adv_port = split_host_port(adv)
            if adv_port == 0:
                adv = f"{adv_host}:{bound}"
            icfg.advertise_address = resolve_host_ip(adv)
            self.advertise_address = icfg.advertise_address
            if cfg.snapshot_path:
                icfg.loader = FileLoader(cfg.snapshot_path)
            peer_creds = (self.tls.grpc_client_credentials()
                          if self.tls is not None else None)
            self.instance = V1Instance(icfg, mesh=mesh, engine=engine,
                                       peer_tls_creds=peer_creds)
            log.info("serving from engine=%(engine)s on "
                     "platform=%(platform)s device_kind=%(device_kind)r "
                     "devices=%(device_count)d "
                     "native_wire_lane=%(native_wire_lane)s",
                     self.instance.serving_info)
            if not self.instance.serving_info["native_wire_lane"]:
                log.warning(
                    "ops/_native is not built: hashing and the wire "
                    "lane run in pure Python/pb2 (build it with "
                    "`python gubernator_tpu/ops/setup_native.py "
                    "build_ext --inplace`)")
            # Warm-up: compile the device step before serving (an RPC
            # must not eat a cold compile).
            import jax

            if (hasattr(self.instance.engine, "warmup")
                    and jax.default_backend() == "tpu"):
                # every wave bucket, called on the engine directly and
                # FIRST: cold TPU compiles can outlast the dispatcher's
                # result timeout the warm-up request below waits under,
                # and a first coalesced burst must never eat one inside
                # an RPC.  Off TPU a bucket compiles quickly on first
                # use, not worth taxing every (test) daemon startup.
                with self.instance._engine_mu:
                    self.instance.engine.warmup()
            elif getattr(self.instance.engine, "tier", None) is not None:
                # off TPU too, with a cold tier bound: the row programs
                # of a migration pass (a dozen tiny compiles) — a pass
                # runs inside a served wave, and a deployment that
                # guarantees no compile there is held to it at its
                # rehearsal size as well
                with self.instance._engine_mu:
                    self.instance.engine.warmup_tier()
            self.instance.get_rate_limits(
                [RateLimitRequest(name="_warmup", unique_key="w", hits=0,
                                  limit=1, duration=1000)])
            add_v1_servicer_raw(self.grpc_server,
                                _V1Servicer(self.instance))
            add_peers_servicer_raw(self.grpc_server,
                                   _PeersServicer(self.instance))
            add_health_servicer(self.grpc_server, self.instance)

            if cfg.client_listen_address:
                # Shared front door: V1 (+ health) on a SO_REUSEPORT
                # socket so sibling daemon processes on this host can
                # bind the same address and split inbound connections.
                # Peer traffic stays on the unique grpc_listen_address —
                # the ring needs per-process identities.  Bound BEFORE
                # the peer server starts: readiness probes watch the
                # peer port's health service, and SERVING there must
                # imply the front door is already accepting.
                self.client_server = grpc.server(
                    DoorPool(max_workers=32,
                             thread_name_prefix="grpc-client-handler"),
                    options=[("grpc.so_reuseport", 1)])
                add_v1_servicer_raw(self.client_server,
                                    _V1Servicer(self.instance))
                add_health_servicer(self.client_server, self.instance)
                if self.tls is not None:
                    cbound = self.client_server.add_secure_port(
                        cfg.client_listen_address,
                        self.tls.grpc_server_credentials())
                else:
                    cbound = self.client_server.add_insecure_port(
                        cfg.client_listen_address)
                if cbound == 0:
                    raise OSError(
                        f"failed to bind client address "
                        f"{cfg.client_listen_address} (SO_REUSEPORT)")
                self.client_port = cbound
                self.client_server.start()
            self.grpc_server.start()

            if cfg.http_listen_address:
                self._start_http(cfg.http_listen_address)

            self_info = PeerInfo(grpc_address=self.advertise_address,
                                 http_address=cfg.http_listen_address,
                                 datacenter=cfg.data_center)
            self.discovery = make_discovery(cfg, self_info,
                                            self.instance.set_peers)
        except BaseException:
            # Don't leak live listeners/threads from a half-built daemon.
            self._teardown()
            raise

    # ---- HTTP gateway ---------------------------------------------------

    def _start_http(self, addr: str) -> None:
        host, port = split_host_port(addr)
        daemon = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):  # quiet
                log.debug("http: " + fmt, *args)

            def _send(self, code: int, body: bytes,
                      ctype: str = "application/json"):
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                from urllib.parse import parse_qs, urlsplit

                parts = urlsplit(self.path)
                path, q = parts.path, parse_qs(parts.query)
                if path == "/metrics":
                    ana = daemon.instance.analytics
                    if ana is not None:
                        # scrape-time top-K gauge refresh: the label
                        # churn (≤ K removes + sets) costs the scraper,
                        # never the serving loop or analytics worker
                        ana.republish()
                    led = getattr(daemon.instance, "memledger", None)
                    if led is not None:
                        # same scrape-time discipline for the ledger
                        # gauges: probes run on the scraper's dime
                        led.republish(daemon.instance.metrics)
                    self._send(200, daemon.instance.metrics.render(),
                               "text/plain; version=0.0.4")
                elif path in ("/v1/HealthCheck", "/healthz"):
                    if daemon._draining:
                        # drain-aware probe (ISSUE 5): load balancers
                        # must stop routing BEFORE the listener dies
                        self._send(503, json.dumps(
                            {"status": "draining",
                             "message": "daemon is shutting down",
                             "peer_count": len(
                                 daemon.instance.peers())}).encode())
                        return
                    h = daemon.instance.health_check()
                    code = 200 if h.status == "healthy" else 503
                    body = {"status": h.status, "message": h.message,
                            "peer_count": h.peer_count,
                            "serving": daemon.instance.serving_info}
                    if q.get("deep", ["0"])[-1] not in ("", "0", "false"):
                        # deep mode: dispatcher queue depth, last-wave
                        # age, stalled state — the stall watchdog's
                        # view, for probes that want a diagnosis and
                        # not just liveness (cmd/healthcheck.py --deep)
                        body["dispatcher"] = \
                            daemon.instance.dispatcher.debug_stats()
                        # per-peer send-lane + circuit state (ISSUE 3):
                        # a backed-up buffer or an open circuit is the
                        # forward hop's stall signal
                        peers_blk = {}
                        for p in daemon.instance.peers():
                            if hasattr(p, "lane_stats"):
                                peers_blk[p.info.grpc_address] = \
                                    p.lane_stats()
                        body["peers"] = peers_blk
                        # SLO verdicts (ISSUE 11): breached / burning
                        # objectives — the --fail-on-burn readiness feed
                        if daemon.instance.slo is not None:
                            body["slo"] = daemon.instance.slo.health()
                        # device-memory ledger totals (ISSUE 13): the
                        # pressure fraction a capacity probe wants
                        led = getattr(daemon.instance, "memledger",
                                      None)
                        if led is not None:
                            snap = led.snapshot()
                            body["memory"] = {
                                "device_bytes": snap["device_bytes"],
                                "host_bytes": snap["host_bytes"],
                                "pressure": snap["pressure"],
                                "pressure_target":
                                    snap["pressure_target"]}
                    self._send(code, json.dumps(body).encode())
                elif path == "/debug/events":
                    # flight recorder ring (telemetry.py), newest-last;
                    # ?limit=N keeps only the newest N events; ?kind=K,
                    # ?since_seq=S, ?tenant=T and ?trace=ID filter
                    # SERVER-side so a polling CLI doesn't re-download
                    # the whole ring
                    try:
                        limit = int(q.get("limit", ["0"])[-1]) or None
                    except ValueError:
                        limit = None
                    kind = q.get("kind", [""])[-1] or None
                    try:
                        since = int(q.get("since_seq", ["0"])[-1]) or None
                    except ValueError:
                        since = None
                    tenant = q.get("tenant", [""])[-1] or None
                    trace = q.get("trace", [""])[-1] or None
                    self._send(200, json.dumps({
                        "events": daemon.instance.recorder.events(
                            limit=limit, kind=kind, since_seq=since,
                            tenant=tenant, trace=trace)}).encode())
                elif path == "/debug/traces":
                    # trace plane (ISSUE 12, tracing.py): the span
                    # recorder's committed ring as JSON — one daemon's
                    # SLICE of each trace; tools/trace_assemble.py (or
                    # guber-cli debug traces --waterfall) stitches N
                    # daemons' slices into the cluster-wide tree
                    rec = daemon.instance.span_recorder
                    if rec is None:
                        self._send(404, json.dumps(
                            {"error": "tracing disabled"}).encode())
                        return
                    try:
                        limit = int(q.get("limit", ["0"])[-1]) or None
                    except ValueError:
                        limit = None
                    tid = q.get("trace_id", [""])[-1] or None
                    st = rec.stats()
                    body = {"sample": st["sample"],
                            "capacity": st["capacity"],
                            "dropped": st["dropped"],
                            "spans": rec.spans(trace_id=tid,
                                               limit=limit)}
                    self._send(200, json.dumps(body).encode())
                elif path == "/debug/topkeys":
                    # heavy-hitter ledger (analytics.py): the current
                    # top-K keys with hits / over-limit / error bound /
                    # last-seen, plus each key's ring owner when
                    # hash-level routing is valid
                    ana = daemon.instance.analytics
                    if ana is None:
                        self._send(404, json.dumps(
                            {"error": "analytics disabled "
                                      "(GUBER_ANALYTICS=0)"}).encode())
                        return
                    try:
                        limit = int(q.get("limit", ["0"])[-1]) or None
                    except ValueError:
                        limit = None
                    ana.flush(timeout=2.0)  # fold queued taps first
                    snap = ana.topkeys_snapshot(limit)
                    for e in snap["keys"]:
                        e["owner"] = daemon.instance.owner_addr_by_khash(
                            int(e["khash"], 16))
                    self._send(200, json.dumps(snap).encode())
                elif path == "/debug/phases":
                    # per-phase latency attribution (analytics.py ›
                    # PhaseLedger) + the wave-duration reference the
                    # in-wave phases partition
                    ana = daemon.instance.analytics
                    if ana is None:
                        self._send(404, json.dumps(
                            {"error": "analytics disabled "
                                      "(GUBER_ANALYTICS=0)"}).encode())
                        return
                    body = ana.phases_snapshot()
                    tel = daemon.instance.dispatcher.telemetry_snapshot()
                    body["waves"] = {
                        k: tel.get(k) for k in
                        ("waves", "wave_duration_p50_ms",
                         "wave_duration_p99_ms", "queue_wait_p50_ms",
                         "queue_wait_p99_ms")}
                    self._send(200, json.dumps(body).encode())
                elif path == "/debug/tenants":
                    # per-tenant RED ledger (analytics.py ›
                    # TenantLedger): bounded-cardinality request /
                    # over-limit / error / degraded / shed attribution
                    ana = daemon.instance.analytics
                    if ana is None:
                        self._send(404, json.dumps(
                            {"error": "analytics disabled "
                                      "(GUBER_ANALYTICS=0)"}).encode())
                        return
                    ana.flush(timeout=2.0)  # fold queued taps first
                    self._send(200, json.dumps(
                        ana.tenants_snapshot()).encode())
                elif path == "/debug/audit":
                    # conservation audit vector (fleet.py): per-lane
                    # injected/applied/queued/in-flight counters, the
                    # drift they prove, and the ring view the fleet
                    # fold cross-checks.  Always served — the auditor
                    # rides the GLOBAL lanes' own accounting (a
                    # GUBER_FLEET_AUDIT=0 daemon reports enabled=false
                    # with zeroed lanes rather than 404ing, so a fleet
                    # fold over a mixed cluster still completes)
                    self._send(200, json.dumps(
                        daemon.instance.audit_doc()).encode())
                elif path == "/debug/slo":
                    # SLO registry + live burn rates (slo.py)
                    if daemon.instance.slo is None:
                        self._send(404, json.dumps(
                            {"error": "slo engine disabled "
                                      "(GUBER_SLO=0)"}).encode())
                        return
                    self._send(200, json.dumps(
                        daemon.instance.slo.snapshot()).encode())
                elif path == "/debug/memory":
                    # device-memory ledger (ISSUE 13, memledger.py):
                    # per-consumer bytes / capacity / occupancy /
                    # demand vector; ?advise=1 adds the water-filling
                    # split recommendation (advisory — nothing
                    # repartitions live)
                    led = getattr(daemon.instance, "memledger", None)
                    if led is None:
                        self._send(404, json.dumps(
                            {"error": "memory ledger disabled "
                                      "(GUBER_MEM_LEDGER=0)"}).encode())
                        return
                    body = led.snapshot()
                    if q.get("advise", ["0"])[-1] not in ("", "0",
                                                          "false"):
                        body["advise"] = led.advise()
                    self._send(200, json.dumps(body).encode())
                elif path == "/debug/costmodel":
                    # fitted collective cost model (analytics.py ›
                    # CostModel): per-(phase, ndev) alpha/beta
                    ana = daemon.instance.analytics
                    if ana is None:
                        self._send(404, json.dumps(
                            {"error": "analytics disabled "
                                      "(GUBER_ANALYTICS=0)"}).encode())
                        return
                    self._send(200, json.dumps(
                        ana.costmodel_snapshot()).encode())
                elif path == "/debug/profile":
                    code, body = daemon._handle_profile(q)
                    self._send(code, json.dumps(body).encode())
                elif path == "/debug/faults":
                    # fault-injection state (faults.py): armed spec,
                    # per-point check/fire counters, catalog
                    self._send(200, json.dumps(
                        daemon.instance.faults.describe()).encode())
                else:
                    self._send(404, b'{"error":"not found"}')

            def do_POST(self):
                if self.path == "/debug/faults":
                    # arm/clear faultpoints at runtime (chaos drills):
                    # {"spec": "peer_send:error:0.3", "seed": 7} or
                    # {"clear": true}
                    try:
                        length = int(self.headers.get("Content-Length", 0))
                        payload = json.loads(
                            self.rfile.read(length) or b"{}")
                        if payload.get("clear"):
                            out = daemon.instance.faults.clear()
                        else:
                            out = daemon.instance.faults.arm(
                                payload.get("spec", ""),
                                seed=payload.get("seed"))
                    except (ValueError, TypeError) as e:
                        self._send(400, json.dumps(
                            {"error": exc_text(e)}).encode())
                        return
                    self._send(200, json.dumps(out).encode())
                    return
                if self.path not in ("/v1/GetRateLimits",
                                     "/v1/V1/GetRateLimits"):
                    self._send(404, b'{"error":"not found"}')
                    return
                try:
                    length = int(self.headers.get("Content-Length", 0))
                    payload = json.loads(self.rfile.read(length) or b"{}")
                    reqs = [_json_to_req(o)
                            for o in payload.get("requests", [])]
                    with request_context(
                            self.headers.get("traceparent"),
                            recorder=daemon.instance.span_recorder), \
                            span("http.GetRateLimits",
                                 metrics=daemon.instance.metrics):
                        resps = daemon.instance.get_rate_limits(reqs)
                except ValueError as e:
                    self._send(400, json.dumps(
                        {"error": exc_text(e)}).encode())
                    return
                except ResourceExhausted as e:
                    # admission shed / drain: 429, the HTTP analog of
                    # grpc RESOURCE_EXHAUSTED
                    self._send(429, json.dumps(
                        {"error": exc_text(e)}).encode())
                    return
                self._send(200, json.dumps({
                    "responses": [_resp_to_json(r) for r in resps]}).encode())

        self.http_server = ThreadingHTTPServer((host, port), Handler)
        if self.tls is not None:
            self.http_server.socket = self.tls.http_ssl_context().wrap_socket(
                self.http_server.socket, server_side=True)
        self.http_port = self.http_server.server_address[1]
        self._http_thread = threading.Thread(
            target=self.http_server.serve_forever, daemon=True,
            name=f"http-{addr}")
        self._http_thread.start()

    # ---- on-demand device profiling (GET /debug/profile) ----------------

    #: hard cap on a runtime capture: profiling taxes the serving loop
    #: and the trace grows with time — an unbounded capture left running
    #: would eventually wedge the daemon's disk
    PROFILE_MAX_SECONDS = 300.0

    def _handle_profile(self, q: dict):
        """``?seconds=N`` starts a DeviceProfiler capture for N seconds
        into a fresh directory (409 while any capture — runtime or the
        GUBER_PROFILE_DIR startup one — is active); without ``seconds``
        it reports capture status.  Returns (http_code, json_body)."""
        from .tracing import DeviceProfiler

        raw = q.get("seconds", [""])[-1]
        with self._prof_mu:
            active = (self._runtime_prof is not None
                      and not self._runtime_prof["done"].is_set())
            if not raw:
                body = {"active": active}
                if self._runtime_prof is not None:
                    body.update({
                        "dir": self._runtime_prof["dir"],
                        "seconds": self._runtime_prof["seconds"]})
                elif self.profiler is not None:
                    body.update({"active": True,
                                 "dir": self.profiler.log_dir,
                                 "startup_env": True})
                return 200, body
            try:
                seconds = float(raw)
            except ValueError:
                return 400, {"error": f"invalid seconds={raw!r}"}
            if not (0 < seconds <= self.PROFILE_MAX_SECONDS):
                return 400, {"error": f"seconds must be in (0, "
                                      f"{self.PROFILE_MAX_SECONDS:.0f}]"}
            if active or self.profiler is not None:
                # one capture at a time: jax.profiler is process-global
                return 409, {"error": "a profile capture is already "
                                      "active"}
            import tempfile

            log_dir = tempfile.mkdtemp(prefix="guber_profile_")
            try:
                prof = DeviceProfiler(log_dir)
            except Exception as e:  # noqa: BLE001 - surfaced to caller
                return 500, {"error": f"profiler start failed: "
                                      f"{exc_text(e)}"}
            done = threading.Event()
            state = {"profiler": prof, "dir": log_dir,
                     "seconds": seconds, "done": done}
            self._runtime_prof = state
        self.instance.recorder.record("profile_start", dir=log_dir,
                                      seconds=seconds)

        def _stop_later():
            done.wait(seconds)  # close() can cut the capture short
            try:
                prof.stop()
            finally:
                done.set()
                self.instance.recorder.record("profile_stop",
                                              dir=log_dir)

        t = threading.Thread(target=_stop_later, daemon=True,
                             name="debug-profile-stop")
        state["thread"] = t
        t.start()
        return 200, {"profiling": True, "dir": log_dir,
                     "seconds": seconds}

    # ---- lifecycle ------------------------------------------------------

    def set_peers(self, infos: List[PeerInfo]) -> None:
        self.instance.set_peers(infos)

    def peer_info(self) -> PeerInfo:
        return PeerInfo(grpc_address=self.advertise_address,
                        http_address=self.cfg.http_listen_address,
                        datacenter=self.cfg.data_center)

    def close(self) -> None:
        """Graceful shutdown (daemon.go › Daemon.Close, SURVEY.md §3.5).

        Drain FIRST (ISSUE 5): /healthz flips to 503 "draining", the
        dispatcher sheds new ingress with RESOURCE_EXHAUSTED, and the
        listeners stay up for ``drain_grace_ms`` so load balancers stop
        routing before connections die.  Then listeners stop, so no
        request lands after the instance has flushed its async managers
        and written the Loader snapshot — mutations during the shutdown
        window would be lost on restart."""
        if self._closed:
            return
        self._closed = True
        import time as _time

        self._draining = True
        if self.instance is not None:
            self.instance.recorder.record(
                "drain_started", grace_ms=self.cfg.drain_grace_ms)
            self.instance.metrics.draining.set(1)
            # during the grace window requests still SERVE (the point
            # is to let load balancers notice the 503 probe first);
            # only after it does the dispatcher shed new ingress
            grace = max(int(getattr(self.cfg, "drain_grace_ms", 0)), 0)
            if grace > 0:
                _time.sleep(grace / 1000.0)
            self.instance.dispatcher.drain()
        self._teardown()
        if self.instance is not None:
            self.instance.recorder.record("drain_completed")

    def _teardown(self) -> None:
        if self.discovery is not None:
            self.discovery.close()
        if self.client_server is not None:
            self.client_server.stop(grace=2).wait(timeout=5)
        self.grpc_server.stop(grace=2).wait(timeout=5)
        if self.http_server is not None:
            self.http_server.shutdown()
            self.http_server.server_close()
        if self.instance is not None:
            self.instance.close()
        if self.profiler is not None:
            self.profiler.stop()
        with self._prof_mu:
            rp = self._runtime_prof
        if rp is not None and not rp["done"].is_set():
            # cut a running on-demand capture short; its stop thread
            # owns the actual profiler.stop() (single stop path)
            rp["done"].set()
            t = rp.get("thread")
            if t is not None:
                t.join(timeout=5)


def spawn_daemon(cfg: DaemonConfig, mesh=None, engine=None) -> Daemon:
    """reference: daemon.go › SpawnDaemon."""
    d = Daemon(cfg, mesh=mesh, engine=engine)
    log.info("gubernator-tpu daemon up: grpc=%s http=%s advertise=%s",
             cfg.grpc_listen_address, cfg.http_listen_address,
             d.advertise_address)
    return d
