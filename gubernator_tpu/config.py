"""Layered configuration: programmatic dataclasses + GUBER_* env vars +
key=value config file.

The analog of the reference's config surface (config.go › Config /
BehaviorConfig / DaemonConfig / SetupDaemonConfig / SetDefaults —
reconstructed, mount empty): same knob names, same layering (defaults <
config file < environment), Go-style duration strings ("500ms", "30s")
accepted everywhere a duration appears.
"""
from __future__ import annotations

import dataclasses
import logging
import os
import re
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

from .types import PeerInfo

log = logging.getLogger("gubernator_tpu")

#: The GUBER_* environment-variable registry: every env var the code
#: reads, with a one-line operator description.  guberlint's ``envreg``
#: pass enforces it both ways (a read without an entry and an entry
#: without a read are both violations), and tools/check_metrics.py
#: lints the prose docs against it — so the operator surface can never
#: drift from the code.  Keep entries alphabetized.
ENV_REGISTRY: Dict[str, str] = {
    "GUBER_ADMISSION_LIMIT": "dispatcher ingress bound in rows; 0 disables (default 65536)",
    "GUBER_ADVERTISE_ADDRESS": "address peers should dial for this daemon",
    "GUBER_ANALYTICS": "0 disables the key-analytics subsystem (sketch + phase ledger)",
    "GUBER_BATCH_LIMIT": "max requests per peer-forward batch",
    "GUBER_BATCH_ROWS": "device batch rows per shard (B)",
    "GUBER_BATCH_TIMEOUT": "peer-forward batch RPC timeout (duration)",
    "GUBER_BATCH_WAIT": "peer-forward batch coalescing wait (duration)",
    "GUBER_BENCH_B": "bench: device-batch size override",
    "GUBER_BENCH_CAP": "bench: table capacity override",
    "GUBER_BENCH_FAST": "bench: fast mode (fewer reps, smaller shapes)",
    "GUBER_BENCH_KEYS": "bench: key-cardinality override",
    "GUBER_BENCH_NO_PALLAS": "bench: skip Pallas sections",
    "GUBER_BENCH_PARTIAL": "bench: emit partial BENCH row on timeout salvage",
    "GUBER_BENCH_SCAN": "bench: occupancy-scan section toggle",
    "GUBER_BENCH_SECTION": "bench: run only this section",
    "GUBER_BENCH_SECTION_OUT": "bench: per-section checkpoint JSON path",
    "GUBER_BENCH_SKIP_GROUP": "bench: skip the group-spread check",
    "GUBER_BENCH_STEP_MODE": "bench: step-impl mode for the step sections",
    "GUBER_CACHE_AUTOGROW_MAX": "auto-grow ceiling in TOTAL table rows; 0 disables",
    "GUBER_CACHE_SIZE": "table capacity per shard",
    "GUBER_CLIENT_ADDRESS": "HTTP client-facing listen address",
    "GUBER_COALESCE_US": "dispatcher coalescing window in µs (0 disables the wait)",
    "GUBER_COMPILE_LEDGER": "0 disables the runtime jit-compile ledger (compileledger.py): per-fn XLA compile counts, gubernator_jit_compiles, the steady-state recompile verdict",
    "GUBER_CREATED_AT_FWD": "0 disables caller-clock forwarding (created_at stamp) — pre-fix cold-key-loss demo ONLY",
    "GUBER_DATA_CENTER": "data-center name for DC-aware picking",
    "GUBER_DEBUG_DUMP_DIR": "crash forensics: close() dumps the event ring + final SLO verdicts here as JSONL",
    "GUBER_DNS_FQDN": "DNS discovery: FQDN to resolve for peers",
    "GUBER_DNS_RESOLVE_INTERVAL": "DNS discovery: re-resolve interval (duration)",
    "GUBER_DRAIN_GRACE": "graceful-shutdown drain budget (duration); bounds every drain join",
    "GUBER_ENGINE": "serving engine: auto (default; fused pallas on TPU, classic xla elsewhere), pallas (fused everywhere — compiled XLA flavor off-TPU), xla/sharded (classic)",
    "GUBER_ETCD_ENDPOINTS": "etcd discovery: comma-separated endpoints",
    "GUBER_ETCD_PREFIX": "etcd discovery: key prefix for peer registration",
    "GUBER_FAULT": "fault-injection spec point[@tag]:mode[:arg[:prob]],... (faults.py)",
    "GUBER_FAULT_SEED": "fault-injection RNG seed for bit-for-bit chaos replay",
    "GUBER_FLEET_AUDIT": "conservation auditor on the GLOBAL lanes: 0 disables the audit taps + /debug/audit drift (default on)",
    "GUBER_FLEET_DRIFT_BOUND": "conservation drift staleness bound (duration) before the fleet_conservation SLO burns; default 2x GUBER_GLOBAL_SYNC_WAIT",
    "GUBER_GLOBAL_BATCH_LIMIT": "GLOBAL hit-flush batch limit",
    "GUBER_GLOBAL_BROADCAST_INTERVAL": "GLOBAL owner-broadcast tick interval (duration)",
    "GUBER_GLOBAL_MODE": "GLOBAL reconcile backend: grpc (default) or mesh (pod-local collective fold)",
    "GUBER_GLOBAL_SYNC_WAIT": "GLOBAL hit-flush coalescing wait (duration)",
    "GUBER_GLOBAL_TIMEOUT": "GLOBAL flush RPC timeout (duration)",
    "GUBER_GRPC_ADDRESS": "gRPC listen address",
    "GUBER_HANDOVER_ON_RESHARD": "stream moved rows to new owners on SetPeers",
    "GUBER_HTTP_ADDRESS": "HTTP (metrics/debug) listen address",
    "GUBER_INSTANCE_ID": "stable instance id (defaults to advertise address)",
    "GUBER_K8S_INSECURE": "k8s discovery: skip API-server cert verification",
    "GUBER_K8S_NAMESPACE": "k8s discovery: namespace to watch",
    "GUBER_K8S_POD_SELECTOR": "k8s discovery: pod label selector",
    "GUBER_K8S_SERVICE": "k8s discovery: service name whose endpoints are peers",
    "GUBER_KSPLIT": "device step: probe K-split override (core/table.py)",
    "GUBER_LOG_LEVEL": "root log level",
    "GUBER_MEMBERLIST_KNOWN_HOSTS": "memberlist discovery: seed hosts",
    "GUBER_MEM_ADVISE_FLOOR": "memory ledger: per-consumer minimum rows in the advised split (default 64)",
    "GUBER_MEM_LEDGER": "0 disables the device-memory ledger plane (default 1)",
    "GUBER_MEM_PRESSURE": "hbm_pressure SLO target: byte-weighted occupancy fraction (default 0.85)",
    "GUBER_MESH_FALLBACK_AFTER": "consecutive mesh-GLOBAL fold failures before the tier stands down to the gRPC path",
    "GUBER_MESH_GLOBAL_CAP": "mesh-GLOBAL replica table capacity (keys; power of two)",
    "GUBER_MULTI_REGION_BATCH_LIMIT": "cross-region replication batch limit",
    "GUBER_MULTI_REGION_SYNC_WAIT": "cross-region flush coalescing wait (duration)",
    "GUBER_MULTI_REGION_TIMEOUT": "cross-region flush RPC timeout (duration)",
    "GUBER_NATIVE_SAN": "setup_native.py: build _native under tsan/asan (make tsan / make asan)",
    "GUBER_PALLAS_TILE": "Mosaic kernel block shape: requests per grid step (a multiple of 8 in 8-512, default 128)",
    "GUBER_PALLAS_SWEEP": "1/0 force the fused Pallas sweep on/off (default: TPU only)",
    "GUBER_PEERS": "static peer list (host:port,... ) for static discovery",
    "GUBER_PEERS_FILE": "file-based discovery: path to the peer list",
    "GUBER_PEER_DEGRADED_FALLBACK": "0 restores legacy error rows instead of degraded serves",
    "GUBER_PEER_DISCOVERY_TYPE": "peer discovery backend (static/file/dns/etcd/k8s/memberlist)",
    "GUBER_PEER_EJECT_AFTER": "circuit-open streak before ring ejection (duration)",
    "GUBER_PEER_HEALTH_GATE": "0 disables the health-gated routing ring",
    "GUBER_PEER_READMIT_AFTER": "recovered time before an ejected peer readmits (duration)",
    "GUBER_PIPELINE_DEPTH": "in-flight launched waves in the pipeline (min 1)",
    "GUBER_PROFILE_DIR": "on-demand device-profiler capture directory",
    "GUBER_RESULT_TIMEOUT_S": "caller wave-result timeout seconds (finite, > 0)",
    "GUBER_SCENARIO_DIR": "scenario-lab spec library directory (default scenarios/)",
    "GUBER_SCENARIO_FAST": "1 forces fast mode in every scenario-lab entry point",
    "GUBER_SCENARIO_SEED": "overrides every scenario spec's seed (sweep knob)",
    "GUBER_SKETCH_WIDTH": "heavy-hitter sketch counter width (default 4×TOPK)",
    "GUBER_SLO": "0 disables the in-process SLO burn-rate engine",
    "GUBER_SLO_BURN": "burn-rate breach threshold (multiple of the error-budget spend rate, default 2.0)",
    "GUBER_SLO_FAST": "SLO fast burn window (duration, default 1m)",
    "GUBER_SLO_P99_MS": "decision_p99 SLO target: device-phase p99 ms (default 250)",
    "GUBER_SLO_SLOW": "SLO slow burn window (duration, default 5m)",
    "GUBER_SLO_TICK": "SLO engine evaluation interval (duration, default 1s)",
    "GUBER_SNAPSHOT_PATH": "Loader snapshot path (save on close, load on start)",
    "GUBER_STALL_THRESHOLD_S": "wave stall-watchdog threshold seconds; <=0 disables",
    "GUBER_STEP_DONATE": "0 disables donated (aliased) step buffers",
    "GUBER_STEP_IMPL": "device step implementation (xla/pallas)",
    "GUBER_TENANT_DELIM": "tenant id = key-name prefix up to this delimiter (default /)",
    "GUBER_TENANT_MAX": "max distinct tenant buckets; overflow folds into __other__ (default 64)",
    "GUBER_TIER_COLD": "1 enables the host cold tier behind the device table",
    "GUBER_TIER_NATIVE": "0 forces the pure-python cold-store fallback",
    "GUBER_TIER_PROMOTE": "sketch-rank admission threshold for cold->hot promotion",
    "GUBER_TLS_AUTO": "generate a self-signed TLS setup at startup",
    "GUBER_TLS_CA": "TLS CA bundle path",
    "GUBER_TLS_CERT": "TLS server certificate path",
    "GUBER_TLS_CLIENT_AUTH": "TLS client-auth mode",
    "GUBER_TLS_CLIENT_AUTH_CA_CERT": "TLS client-auth CA path",
    "GUBER_TLS_INSECURE_SKIP_VERIFY": "peer clients skip TLS verification",
    "GUBER_TLS_KEY": "TLS server key path",
    "GUBER_TOPK": "heavy-hitter sketch tracked-key count K",
    "GUBER_TRACE_SAMPLE": "head-sampling rate for the trace plane (0 disables)",
    "GUBER_TRACE_SPANS": "span-recorder ring capacity (completed spans kept)",
    "GUBER_WAVE_BUCKETS": "comma-separated wave-size buckets for check_packed (default B,8B; B,8B,16B on the one-chip Mosaic engine; B = batch_rows)",
}

_DUR_RE = re.compile(r"(\d+(?:\.\d+)?)(ns|us|µs|ms|s|m|h)")
_DUR_UNIT_MS = {"ns": 1e-6, "us": 1e-3, "µs": 1e-3, "ms": 1.0,
                "s": 1000.0, "m": 60_000.0, "h": 3_600_000.0}


def parse_duration_ms(s: str | int | float) -> int:
    """Go-style duration string → integer milliseconds.

    Accepts bare numbers (already ms) and compound strings ("1m30s").
    Mirrors the reference's use of time.ParseDuration in config loading.
    """
    if isinstance(s, (int, float)):
        return int(s)
    s = s.strip()
    if not s:
        return 0
    if re.fullmatch(r"-?\d+", s):
        return int(s)
    total = 0.0
    pos = 0
    neg = s.startswith("-")
    if neg:
        pos = 1
    for m in _DUR_RE.finditer(s, pos):
        if m.start() != pos:
            raise ValueError(f"invalid duration: {s!r}")
        total += float(m.group(1)) * _DUR_UNIT_MS[m.group(2)]
        pos = m.end()
    if pos != len(s):
        raise ValueError(f"invalid duration: {s!r}")
    return int(-total if neg else total)


@dataclass
class BehaviorConfig:
    """Batch/global/multi-region timing knobs.

    reference: config.go › BehaviorConfig (same field names, ms integers
    instead of time.Duration).
    """

    #: How long to wait for more requests before flushing a peer batch.
    batch_timeout_ms: int = 500
    #: Time the owner waits to accumulate forwarded batches.
    batch_wait_ms: int = 500
    #: Max requests in one forwarded peer batch (reference default 1000).
    batch_limit: int = 1000

    #: How long to accumulate GLOBAL hit deltas before syncing to owner.
    global_sync_wait_ms: int = 100
    #: Deadline for global sync RPCs.
    global_timeout_ms: int = 500
    #: Max global hits per sync batch.
    global_batch_limit: int = 1000
    #: Interval between owner broadcasts of updated GLOBAL state.
    global_broadcast_interval_ms: int = 100

    #: Multi-region analogs (SURVEY.md §2.1 mutliregion.go).
    multi_region_sync_wait_ms: int = 300
    multi_region_timeout_ms: int = 900
    multi_region_batch_limit: int = 1000

    #: Columnar peer send lanes (peer_client.py › _SendLane): depth-K
    #: in-flight RPCs per peer per method — the forward hop's analog of
    #: the dispatcher's overlapped wave pipeline.
    peer_inflight: int = 4
    #: Send-buffer coalescing window (µs): how long a flush waits for
    #: straggler entries after draining the backlog — mirrors the
    #: dispatcher's GUBER_COALESCE_US rule (greedy backlog first, never
    #: overshoot the batch limit, tiny straggler window).
    peer_coalesce_us: int = 200
    #: Re-send attempts for a failed flush RPC before its requests get
    #: error responses (each retry backs off linearly).
    peer_retry_limit: int = 2
    peer_retry_backoff_ms: int = 25
    #: Consecutive flush failures (after retries) that OPEN the peer's
    #: circuit: sends fail fast instead of queuing behind a dead peer
    #: until the cooldown elapses (then one probe flush half-opens it).
    peer_circuit_threshold: int = 3
    peer_circuit_cooldown_ms: int = 2000

    #: Failure-domain resilience (ISSUE 5).  When a forward fails (RPC
    #: error after retries, or a circuit-open fail-fast), answer the
    #: row from the LOCAL shard with a DEGRADED response flag and
    #: reconcile the hits to the owner through the GLOBAL hit-flush
    #: queues — bounded staleness instead of per-request error rows.
    #: Rows with state-mutating flags (RESET_REMAINING /
    #: DRAIN_OVER_LIMIT) are never served degraded.
    peer_degraded_fallback: bool = True
    #: Health-gated routing ring: a peer whose circuit has been open
    #: continuously for peer_eject_after_ms is EJECTED from the routing
    #: ring (its keys deterministically rehome to the next ring point);
    #: it returns only after staying recovered for
    #: peer_readmit_after_ms (hysteresis against flapping).  False
    #: keeps the membership ring authoritative for routing.
    peer_health_gate: bool = True
    peer_eject_after_ms: int = 3000
    peer_readmit_after_ms: int = 3000


@dataclass
class Config:
    """Core-instance configuration.

    reference: config.go › Config (fields the TPU design keeps; cache
    workers/locks are replaced by the device table, SURVEY.md §7.1).
    """

    #: Rows in the device counter table (power of two).  The analog of
    #: the reference's CacheSize (default 50 000 → rounded up to 2^16).
    cache_size: int = 1 << 16
    #: Device batch rows per shard per step.
    batch_rows: int = 1024
    #: Upper bound (total rows) for on-device capacity auto-grow when
    #: the table fills with LIVE keys (0 disables; the reference's LRU
    #: never fails an insert, so enabling this matches that contract up
    #: to the bound).  Rounded to a power of two per shard.
    cache_autogrow_max: int = 0
    #: Stateful re-sharding (beyond-reference, opt-in): on membership
    #: change, rows whose ring owner moved are handed to the new owner
    #: over the peer wire instead of resetting (the reference loses
    #: re-homed state — SURVEY.md §5.3).  Requires the default picker
    #: hash (mixed fnv1a64).
    handover_on_reshard: bool = False
    behaviors: BehaviorConfig = field(default_factory=BehaviorConfig)
    #: This node's datacenter name (multi-region routing).
    data_center: str = ""
    #: Optional persistence hooks (store.py); any object implementing
    #: the Loader / Store protocols.
    loader: Optional[object] = None
    store: Optional[object] = None
    #: Milliseconds between expired-row sweeps (0 disables).  Also how
    #: long an insert into a probe window clogged by expired rows can
    #: go on failing: no wave sweeps for itself, a table_full row asks
    #: for one sweep ahead of the tick, once an interval
    #: (instance._maybe_sweep).
    sweep_interval_ms: int = 30_000
    #: Decision-step implementation: "xla" (default — unbounded values,
    #: auto-grow) or "pallas" (the hand-scheduled Mosaic kernel as the
    #: serving mode: lowering-independent throughput floor, bucketized
    #: table; counters must be < 2^30 and leaky eff < 2^31, no
    #: auto-grow — parallel/pallas_engine.py).  GUBER_STEP_IMPL
    #: overrides.
    step_impl: str = ""
    #: Serving-engine selector (ISSUE 8; GUBER_ENGINE overrides):
    #: "auto" (default) = the fused Pallas engine on TPU, the classic
    #: XLA sharded engine elsewhere; "pallas" = fused serving
    #: everywhere (off-TPU: the compiled XLA fused flavor — one fused
    #: program per wave with on-device tap + mesh scatter, small-shape
    #: wave buckets); "xla"/"sharded" = the classic engine explicitly.
    #: A selected engine that cannot be built stops the daemon (no
    #: stand-in engine: that would hide the device).
    engine: str = ""
    #: GLOBAL reconcile backend (ISSUE 7): "" / "grpc" keeps the
    #: reference's hit-queue + broadcast machinery; "mesh" serves
    #: pod-local GLOBAL keys from the mesh-resident replica tier
    #: (parallel/meshglobal.py) and reconciles them with ONE collective
    #: fold per tick — no gRPC peer fan-out.  Cross-pod owners and the
    #: degraded fallback keep the gRPC lanes either way.
    #: GUBER_GLOBAL_MODE overrides.
    global_mode: str = ""
    #: Host cold tier behind the device table (ISSUE 10): a key that
    #: misses (or overflows) the HBM-resident table is served EXACTLY
    #: from host memory instead of erroring table_full, and migrates to
    #: HBM only once its sketch rank clears tier_promote_threshold —
    #: key cardinality scales far past the device cap while the hot
    #: tier stays wave-sized.  GUBER_TIER_COLD overrides.
    tier_cold: bool = False
    #: Sketch-rank admission threshold for cold→hot promotion (see
    #: tiering.py).  GUBER_TIER_PROMOTE overrides.
    tier_promote_threshold: int = 8
    #: Local peer identity (set by the daemon).
    advertise_address: str = ""

    def set_defaults(self) -> "Config":
        """Normalize invalid values, like config.go › SetDefaults."""
        if self.cache_size <= 0:
            self.cache_size = 1 << 16
        # round up to a power of two (device probe masking requires it)
        self.cache_size = 1 << (self.cache_size - 1).bit_length()
        if self.batch_rows <= 0:
            self.batch_rows = 1024
        return self


@dataclass
class TLSSettings:
    """reference: tls.go › TLSConfig (declarative part)."""

    ca_file: str = ""
    cert_file: str = ""
    key_file: str = ""
    #: Generate a self-signed server certificate in memory.
    auto_tls: bool = False
    #: "none" | "request" | "require-any" | "verify" (client certs).
    client_auth: str = "none"
    client_auth_ca_file: str = ""
    insecure_skip_verify: bool = False


@dataclass
class DaemonConfig:
    """Everything needed to spawn a daemon.

    reference: config.go › DaemonConfig + SetupDaemonConfig env names
    (GUBER_* — reconstructed).
    """

    grpc_listen_address: str = "localhost:1051"
    http_listen_address: str = "localhost:1050"
    #: Optional SHARED client-facing gRPC address bound with SO_REUSEPORT.
    #: Several daemon processes on one host can bind the same
    #: client_listen_address; the kernel load-balances inbound client
    #: connections across them while each process keeps its unique
    #: grpc_listen_address for peer traffic.  This is the front-door
    #: scaling story for a GIL-bound host: N ingest processes share the
    #: port, ring-split batches, and forward over the peer wire lane.
    #: "" (default) disables the extra listener.
    client_listen_address: str = ""
    advertise_address: str = ""
    cache_size: int = 1 << 16
    cache_autogrow_max: int = 0
    #: Milliseconds between expiry sweeps (Config.sweep_interval_ms).
    sweep_interval_ms: int = 30_000
    #: Device wave rows per shard (Config.batch_rows).
    batch_rows: int = 1024
    handover_on_reshard: bool = False
    data_center: str = ""
    instance_id: str = ""
    behaviors: BehaviorConfig = field(default_factory=BehaviorConfig)
    tls: Optional[TLSSettings] = None
    log_level: str = "info"

    #: "none" | "static" | "file" | "dns" | "etcd" | "k8s" | "member-list"
    peer_discovery_type: str = "none"
    #: static discovery: explicit peer list.
    static_peers: List[str] = field(default_factory=list)
    #: file discovery: path to a JSON/lines peers file, re-read on change.
    peers_file: str = ""
    #: dns discovery.
    dns_fqdn: str = ""
    dns_resolve_interval_ms: int = 30_000
    #: etcd / k8s / member-list endpoints (gated: stub unless client
    #: libraries are installed — SURVEY.md §2.1 discovery rows).
    etcd_endpoints: List[str] = field(default_factory=list)
    etcd_prefix: str = "/gubernator/peers/"
    k8s_namespace: str = ""
    k8s_pod_selector: str = ""
    k8s_service: str = ""
    #: Explicit opt-out of API-server cert verification (GUBER_K8S_INSECURE).
    k8s_insecure_skip_verify: bool = False
    memberlist_known_hosts: List[str] = field(default_factory=list)

    #: Graceful-shutdown drain window (ms): Daemon.close reports
    #: "draining" on /healthz (503) for this long before stopping the
    #: listeners, so load balancers stop routing first.  0 skips the
    #: wait (the drain events still fire).
    drain_grace_ms: int = 0
    #: Path for Loader snapshots ("" disables checkpoint/resume).
    snapshot_path: str = ""
    #: Decision-step implementation ("" → "xla"; "pallas" = the Mosaic
    #: kernel serving mode — Config.step_impl).
    step_impl: str = ""
    #: Serving-engine selector ("" → "auto" — Config.engine).
    engine: str = ""
    #: GLOBAL reconcile backend ("" → "grpc"; "mesh" = pod-local
    #: collective fold — Config.global_mode).
    global_mode: str = ""

    def instance_config(self) -> Config:
        return Config(
            cache_size=self.cache_size,
            cache_autogrow_max=self.cache_autogrow_max,
            sweep_interval_ms=self.sweep_interval_ms,
            batch_rows=self.batch_rows,
            step_impl=self.step_impl,
            engine=self.engine,
            global_mode=self.global_mode,
            handover_on_reshard=self.handover_on_reshard,
            behaviors=self.behaviors,
            data_center=self.data_center,
            advertise_address=self.advertise_address or self.grpc_listen_address,
        ).set_defaults()


_MISSING = object()


class _Src:
    """One layered config source: conf-file dict then environment."""

    def __init__(self, conf: Dict[str, str]):
        self.conf = conf

    def get(self, name: str, default=_MISSING, cast: Callable = str):
        v = os.environ.get(name, _MISSING)
        if v is _MISSING:
            v = self.conf.get(name, _MISSING)
        if v is _MISSING:
            if default is _MISSING:
                return None
            return default
        if cast is bool:
            return str(v).strip().lower() in ("1", "true", "yes", "on")
        return cast(v)


def load_conf_file(path: str) -> Dict[str, str]:
    """Parse a `KEY=value` config file (reference example.conf format):
    blank lines and #-comments ignored."""
    out: Dict[str, str] = {}
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            if "=" not in line:
                raise ValueError(f"invalid config line (want KEY=value): {line!r}")
            k, _, v = line.partition("=")
            out[k.strip()] = v.strip()
    return out


def setup_daemon_config(conf_file: str = "",
                        env: Optional[Dict[str, str]] = None) -> DaemonConfig:
    """Build a DaemonConfig from defaults < config file < environment.

    reference: config.go › SetupDaemonConfig.  ``env`` overrides
    os.environ for tests.
    """
    conf = load_conf_file(conf_file) if conf_file else {}
    if env is not None:
        conf = {**conf, **env}
        src = _Src(conf)
        # env-dict mode: don't consult os.environ (hermetic tests)
        src.get = lambda name, default=_MISSING, cast=str: (  # type: ignore
            (default if default is not _MISSING else None)
            if conf.get(name, _MISSING) is _MISSING
            else (str(conf[name]).strip().lower() in ("1", "true", "yes", "on")
                  if cast is bool else cast(conf[name])))
    else:
        src = _Src(conf)

    d = DaemonConfig()
    d.grpc_listen_address = src.get("GUBER_GRPC_ADDRESS", d.grpc_listen_address)
    d.http_listen_address = src.get("GUBER_HTTP_ADDRESS", d.http_listen_address)
    d.client_listen_address = src.get("GUBER_CLIENT_ADDRESS",
                                      d.client_listen_address)
    d.advertise_address = src.get("GUBER_ADVERTISE_ADDRESS", d.advertise_address)
    d.cache_size = src.get("GUBER_CACHE_SIZE", d.cache_size, int)
    d.batch_rows = src.get("GUBER_BATCH_ROWS", d.batch_rows, int)
    d.cache_autogrow_max = src.get("GUBER_CACHE_AUTOGROW_MAX",
                                   d.cache_autogrow_max, int)
    d.handover_on_reshard = src.get("GUBER_HANDOVER_ON_RESHARD",
                                    d.handover_on_reshard, bool)
    d.data_center = src.get("GUBER_DATA_CENTER", d.data_center)
    d.instance_id = src.get("GUBER_INSTANCE_ID", d.instance_id)
    d.log_level = src.get("GUBER_LOG_LEVEL", d.log_level)
    d.snapshot_path = src.get("GUBER_SNAPSHOT_PATH", d.snapshot_path)
    d.step_impl = src.get("GUBER_STEP_IMPL", d.step_impl)
    d.engine = src.get("GUBER_ENGINE", d.engine)
    d.global_mode = src.get("GUBER_GLOBAL_MODE", d.global_mode)

    b = d.behaviors
    b.batch_timeout_ms = src.get("GUBER_BATCH_TIMEOUT", b.batch_timeout_ms,
                                 parse_duration_ms)
    b.batch_wait_ms = src.get("GUBER_BATCH_WAIT", b.batch_wait_ms,
                              parse_duration_ms)
    b.batch_limit = src.get("GUBER_BATCH_LIMIT", b.batch_limit, int)
    b.global_sync_wait_ms = src.get("GUBER_GLOBAL_SYNC_WAIT",
                                    b.global_sync_wait_ms, parse_duration_ms)
    b.global_timeout_ms = src.get("GUBER_GLOBAL_TIMEOUT", b.global_timeout_ms,
                                  parse_duration_ms)
    b.global_batch_limit = src.get("GUBER_GLOBAL_BATCH_LIMIT",
                                   b.global_batch_limit, int)
    b.global_broadcast_interval_ms = src.get(
        "GUBER_GLOBAL_BROADCAST_INTERVAL", b.global_broadcast_interval_ms,
        parse_duration_ms)
    b.multi_region_sync_wait_ms = src.get(
        "GUBER_MULTI_REGION_SYNC_WAIT", b.multi_region_sync_wait_ms,
        parse_duration_ms)
    b.multi_region_timeout_ms = src.get(
        "GUBER_MULTI_REGION_TIMEOUT", b.multi_region_timeout_ms,
        parse_duration_ms)
    b.multi_region_batch_limit = src.get(
        "GUBER_MULTI_REGION_BATCH_LIMIT", b.multi_region_batch_limit, int)
    b.peer_degraded_fallback = src.get("GUBER_PEER_DEGRADED_FALLBACK",
                                       b.peer_degraded_fallback, bool)
    b.peer_health_gate = src.get("GUBER_PEER_HEALTH_GATE",
                                 b.peer_health_gate, bool)
    b.peer_eject_after_ms = src.get("GUBER_PEER_EJECT_AFTER",
                                    b.peer_eject_after_ms,
                                    parse_duration_ms)
    b.peer_readmit_after_ms = src.get("GUBER_PEER_READMIT_AFTER",
                                      b.peer_readmit_after_ms,
                                      parse_duration_ms)
    d.drain_grace_ms = src.get("GUBER_DRAIN_GRACE", d.drain_grace_ms,
                               parse_duration_ms)

    d.peer_discovery_type = src.get("GUBER_PEER_DISCOVERY_TYPE",
                                    d.peer_discovery_type)
    peers = src.get("GUBER_PEERS", "")
    if peers:
        d.static_peers = [p.strip() for p in peers.split(",") if p.strip()]
        if d.peer_discovery_type == "none":
            d.peer_discovery_type = "static"
    d.peers_file = src.get("GUBER_PEERS_FILE", d.peers_file)
    d.dns_fqdn = src.get("GUBER_DNS_FQDN", d.dns_fqdn)
    d.dns_resolve_interval_ms = src.get("GUBER_DNS_RESOLVE_INTERVAL",
                                        d.dns_resolve_interval_ms,
                                        parse_duration_ms)
    etcd = src.get("GUBER_ETCD_ENDPOINTS", "")
    if etcd:
        d.etcd_endpoints = [p.strip() for p in etcd.split(",") if p.strip()]
    d.etcd_prefix = src.get("GUBER_ETCD_PREFIX", d.etcd_prefix)
    d.k8s_namespace = src.get("GUBER_K8S_NAMESPACE", d.k8s_namespace)
    d.k8s_pod_selector = src.get("GUBER_K8S_POD_SELECTOR", d.k8s_pod_selector)
    d.k8s_service = src.get("GUBER_K8S_SERVICE", d.k8s_service)
    d.k8s_insecure_skip_verify = src.get("GUBER_K8S_INSECURE",
                                         d.k8s_insecure_skip_verify, bool)
    ml = src.get("GUBER_MEMBERLIST_KNOWN_HOSTS", "")
    if ml:
        d.memberlist_known_hosts = [p.strip() for p in ml.split(",") if p.strip()]

    if (src.get("GUBER_TLS_AUTO", False, bool)
            or src.get("GUBER_TLS_CERT", "") or src.get("GUBER_TLS_CA", "")):
        d.tls = TLSSettings(
            ca_file=src.get("GUBER_TLS_CA", ""),
            cert_file=src.get("GUBER_TLS_CERT", ""),
            key_file=src.get("GUBER_TLS_KEY", ""),
            auto_tls=src.get("GUBER_TLS_AUTO", False, bool),
            client_auth=src.get("GUBER_TLS_CLIENT_AUTH", "none"),
            client_auth_ca_file=src.get("GUBER_TLS_CLIENT_AUTH_CA_CERT", ""),
            insecure_skip_verify=src.get("GUBER_TLS_INSECURE_SKIP_VERIFY",
                                         False, bool),
        )
    return d


def parse_peer_list(specs: List[str], default_dc: str = "") -> List[PeerInfo]:
    """"host:grpc_port[;host:http_port][@dc]" strings → PeerInfo list."""
    out = []
    for s in specs:
        dc = default_dc
        if "@" in s:
            s, _, dc = s.partition("@")
        grpc_addr, _, http_addr = s.partition(";")
        out.append(PeerInfo(grpc_address=grpc_addr.strip(),
                            http_address=http_addr.strip(),
                            datacenter=dc.strip()))
    return out
