"""Prometheus metrics.

Mirrors the reference's metric surface (gubernator.go › Collector impl,
lrucache.go gauges, global.go queue/broadcast metrics — reconstructed)
with the same metric names where sensible, so existing dashboards can be
pointed at this service (SURVEY.md §5.5).  Each instance gets its own
CollectorRegistry (multiple daemons per process in the test cluster).

The full metric catalog lives in OBSERVABILITY.md; tools/check_metrics.py
(a tier-1 test) asserts every metric registered here is documented there
and that names are unique.
"""
from __future__ import annotations

import time
from contextlib import contextmanager

from prometheus_client import (
    CollectorRegistry,
    Counter,
    Gauge,
    Histogram,
    generate_latest,
)

from .tracing import ThreadLedger

_BUCKETS = (.0005, .001, .0025, .005, .01, .025, .05, .1, .25, .5, 1.0, 2.5)

#: wave durations reach minutes on a cold TPU compile — the histogram
#: must resolve that tail, not clip it at 2.5 s, or the one event the
#: watchdog exists for is invisible.
_WAVE_DURATION_BUCKETS = _BUCKETS + (10.0, 30.0, 60.0, 120.0, 300.0, 600.0)

#: requests per coalesced wave: 1 (a lone call) up to max_wave — what
#: one launch of the engine holds, 8,192 rows at the least — and beyond
#: for merged packed columns; the default ladders' rungs (1,024 / 8,192
#: / 16,384) and the next doubling are bounds, so the share of waves on
#: each can be read (tools/wave_rungs.py)
_WAVE_SIZE_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 1024, 4096,
                      8192, 16384, 32768, 65536)


class Metrics:
    def __init__(self) -> None:
        r = self.registry = CollectorRegistry()
        self.getratelimit_counter = Counter(
            "gubernator_getratelimit", "GetRateLimits calls",
            ["calltype"], registry=r)
        self.over_limit_counter = Counter(
            "gubernator_over_limit", "OVER_LIMIT decisions", registry=r)
        self.check_error_counter = Counter(
            "gubernator_check_error", "errors while checking rate limits",
            ["error"], registry=r)
        self.func_duration = Histogram(
            "gubernator_func_duration", "handler durations (s)",
            ["name"], buckets=_BUCKETS, registry=r)
        self.batch_send_duration = Histogram(
            "gubernator_batch_send_duration",
            "peer batch flush durations (s)", ["peer_addr"],
            buckets=_BUCKETS, registry=r)
        self.queue_length = Gauge(
            "gubernator_global_queue_length",
            "pending GLOBAL hit aggregations", registry=r)
        self.broadcast_duration = Histogram(
            "gubernator_broadcast_duration", "GLOBAL broadcast durations (s)",
            buckets=_BUCKETS, registry=r)
        self.global_broadcast_counter = Counter(
            "gubernator_broadcast", "GLOBAL broadcasts sent", registry=r)
        self.cache_size = Gauge(
            "gubernator_cache_size", "live rows in the counter table",
            registry=r)
        self.cache_access_count = Counter(
            "gubernator_cache_access_count", "table lookups",
            ["type"], registry=r)
        self.concurrent_checks = Gauge(
            "gubernator_concurrent_checks_counter",
            "in-flight GetRateLimits batches", registry=r)
        self.cache_capacity = Gauge(
            "gubernator_cache_capacity",
            "total counter-table rows (grows under auto-grow)", registry=r)
        self.dropped_rows = Gauge(
            "gubernator_cache_dropped_rows",
            "live rows lost to grow/restore re-placement (each is a "
            "counter reset, the LRU-eviction analog)", registry=r)
        # Lane observability (VERDICT r1 weak #5/#8): the wire fast
        # lanes are perf cliffs when they silently disengage — export
        # where requests actually went so operators can see it.
        self.wire_lane_counter = Counter(
            "gubernator_wire_lane_requests",
            "requests by serving lane (wire-columnar vs pb2 fallback)",
            ["lane"], registry=r)
        # both lanes below wear lane="wire_local" / "peer_wire": this
        # says how many of those rows the ONE C++ pass packed
        # (instance.py › _wire_client_fused / _wire_peer_fused); the
        # rest were parsed and packed in numpy by their handler
        self.wire_fused_counter = Counter(
            "gubernator_wire_fused_requests",
            "requests the fused C++ wire ingest (prepack_wire) parsed "
            "and laid out in one pass; a subset of "
            "gubernator_wire_lane_requests", registry=r)
        self.wire_fused_declined = Counter(
            "gubernator_wire_fused_declined",
            "calls the fused C++ wire ingest refused, by why: global / "
            "multi_region (the lane's policy, _FUSED_EXCLUDED) / "
            "— the first of the two that any row of the call carries —, "
            "too_large (more rows than the largest wave bucket), "
            "gregorian (a calendar row pack_wire_wave cannot model: an "
            "ordinal outside 0..5, a clock outside the calendar; every "
            "other calendar call is served), other (framing "
            "the C++ lanes do not model, an empty call); counted where "
            "the classic parse that follows a refusal has behavior_or "
            "and n in hand (instance.py › _count_fused_declined)",
            ["reason"], registry=r)
        # pallas-mode capacity safety (VERDICT r4 item 6): no on-device
        # grow, so full buckets — not total occupancy — are where new
        # keys start erring as table_full.  0 in xla mode.
        self.bucket_saturation = Gauge(
            "gubernator_pallas_bucket_saturation",
            "fraction of 128-slot buckets that are FULL (pallas serving "
            "mode; new keys hashing into a full bucket are unservable)",
            registry=r)
        # Fused serving engine (ISSUE 8): GUBER_ENGINE=pallas serves
        # each wave as ONE device program (decision kernel + on-device
        # heavy-hitter tap + mesh-GLOBAL accumulator scatter when that
        # tier is bound).  Zero for the classic engine.
        self.pallas_fused_waves = Counter(
            "gubernator_pallas_fused_waves",
            "waves served by the fused serving program (device tap "
            "emitted in-launch; no host-side tap copies)", registry=r)
        self.pallas_mesh_fused_hits = Counter(
            "gubernator_pallas_mesh_fused_hits",
            "mesh-GLOBAL hits scatter-added by the fused serving "
            "program (the injected side of the mesh conservation "
            "ledger for fused waves)", registry=r)
        self.jit_compiles = Counter(
            "gubernator_jit_compiles",
            "XLA compilations by jitted function (compile ledger, "
            "ISSUE 14); any growth after warmup is a retrace bug — "
            "a call site is recompiling the serving program",
            ["fn"], registry=r)
        self.scenario_runs = Counter(
            "gubernator_scenario_runs",
            "scenario-lab runs by verdict (scenarios.py, ISSUE 16)",
            ["verdict"], registry=r)
        # Dispatcher wave telemetry (ISSUE 1): the wave/queue/compile
        # layer is the hot path and was previously unobservable — a
        # minutes-long cold compile surfaced only as an empty
        # TimeoutError at the caller.  dispatcher.py observes these per
        # wave.
        self.wave_size = Histogram(
            "gubernator_dispatcher_wave_size",
            "requests per coalesced device wave",
            buckets=_WAVE_SIZE_BUCKETS, registry=r)
        self.wave_leaky_rows = Counter(
            "gubernator_wave_leaky_rows",
            "LEAKY_BUCKET rows that entered a wave's device program "
            "(valid, inside the step program's domain, not cold-tier "
            "served), counted once a wave at the engine's wave.route",
            registry=r)
        self.wave_gregorian_rows = Counter(
            "gubernator_wave_gregorian_rows",
            "DURATION_IS_GREGORIAN rows that entered a wave's device "
            "program (valid, not cold-tier served), counted beside "
            "gubernator_wave_leaky_rows from the counts each call's "
            "handler took while it packed (pack_columns; lay_out for "
            "loose columns)", registry=r)
        self.wave_created_rows = Counter(
            "gubernator_wave_created_rows",
            "rows a wave opened in the table (keys it found no row "
            "for): the insert_count every step program already returns, "
            "added where a wave's counters reach the host "
            "(ShardedEngine._download_wave)", registry=r)
        self.wave_route = Counter(
            "gubernator_wave_route",
            "device waves by how their rows reached the upload buffers: "
            "identity = the calls' blocks were joined straight into the "
            "lease (one shard, clocks in order, no cold tier, one "
            "bucket), sorted = routed by shard and scattered",
            ["route"], registry=r)
        # what the shard route costs (ISSUE 33), counted beside
        # gubernator_wave_route at ShardedEngine._count_route, once a
        # device wave
        self.wave_slots = Counter(
            "gubernator_wave_slots",
            "slots device waves uploaded, launched over and downloaded: "
            "the lease's width, shards x the bucket of the wave's "
            "densest shard on the sorted route, the bucket on the "
            "identity route; padding included", registry=r)
        self.wave_routed_rows = Counter(
            "gubernator_wave_routed_rows",
            "rows device waves carried (slots less padding)", registry=r)
        self.wave_densest_shard_rows = Counter(
            "gubernator_wave_densest_shard_rows",
            "rows of each device wave's densest shard, summed: what "
            "chose the wave's bucket; x shards / routed rows is the "
            "skew, 1.0 an even wave (one shard: the rows themselves)",
            registry=r)
        self.wave_native_route = Counter(
            "gubernator_wave_native_route",
            "sorted-route device waves the C++ extension planned and "
            "filled (ops/_native.cpp: route_plan, route_fill), one pass "
            "each that keeps the GIL; the rest of "
            'gubernator_wave_route{route="sorted"} took the numpy route',
            registry=r)
        self.sweeps = Counter(
            "gubernator_sweep",
            "whole-table expiry sweeps by cause (instance._maybe_sweep, "
            "between waves): tick = the sweep interval came round, "
            "table_full = a wave answered a row table_full and asked "
            "for one ahead of the tick (at most one an interval)",
            ["cause"], registry=r)
        self.table_full_rows = Counter(
            "gubernator_table_full_rows",
            "rows answered table_full (unservable): probe window or "
            "bucket full after the retry, or values outside the step "
            "program's domain; cold-tier served rows excluded",
            registry=r)
        self.restore_unplaced_rows = Gauge(
            "gubernator_restore_unplaced_rows",
            "rows of the last engine.restore that no tier took: no "
            "slot in their probe window (or, on the bucket table, "
            "values outside the kernel's domain) and no cold tier to "
            "adopt them",
            registry=r)
        self.wave_queue_wait = Histogram(
            "gubernator_dispatcher_queue_wait",
            "job wait from submit to its wave launching (s)",
            buckets=_BUCKETS, registry=r)
        self.wave_duration = Histogram(
            "gubernator_dispatcher_wave_duration",
            "device wave duration, launch to resolve (s); the tail "
            "buckets exist for cold compiles",
            buckets=_WAVE_DURATION_BUCKETS, registry=r)
        self.waves_in_flight = Gauge(
            "gubernator_dispatcher_waves_in_flight",
            "waves currently executing on the device (incl. pipelined "
            "launches awaiting sync)", registry=r)
        self.wave_timeout_counter = Counter(
            "gubernator_dispatcher_wave_timeouts",
            "caller waits that hit RESULT_TIMEOUT_S", registry=r)
        self.dispatcher_stalled = Gauge(
            "gubernator_dispatcher_stalled",
            "1 while any wave has been in flight longer than the stall "
            "threshold (a cold compile shows here minutes before "
            "callers time out)", registry=r)
        self.stall_event_counter = Counter(
            "gubernator_dispatcher_stall_events",
            "waves flagged stalled by the watchdog", registry=r)
        self.first_wave_duration = Gauge(
            "gubernator_dispatcher_first_wave_seconds",
            "duration of this dispatcher's FIRST wave (includes any "
            "cold compile the warmup did not cover)", registry=r)
        # Overlapped wave pipeline + wave-buffer pool (ISSUE 2): the
        # depth-K in-flight ring and the pooled packed-upload matrices
        # are new perf-critical moving parts — export their shape and
        # churn so a regression (pool thrash, a leaked lease, an
        # unexpected depth) is visible on /metrics.
        self.pipeline_depth = Gauge(
            "gubernator_dispatcher_pipeline_depth",
            "configured depth of the overlapped wave pipeline (0 = "
            "pipeline off: CPU default or capability-less engine)",
            registry=r)
        self.wave_buffer_pool_hit = Counter(
            "gubernator_wave_buffer_pool_hits",
            "wave upload-buffer leases served from the pool",
            registry=r)
        self.wave_buffer_pool_miss = Counter(
            "gubernator_wave_buffer_pool_misses",
            "wave upload-buffer leases that allocated fresh matrices",
            registry=r)
        self.wave_buffer_leaks = Counter(
            "gubernator_wave_buffer_leaks",
            "wave buffer leases dropped without release (reclaimed by "
            "the GC hook; must stay 0 — asserted by the soak tests)",
            registry=r)
        # Columnar peer send lanes (ISSUE 3): the pooled per-peer send
        # buffers, depth-K in-flight forward RPCs, and retry/circuit
        # machinery are the forward hop's moving parts — export their
        # shape so a backed-up or circuit-open peer is visible on
        # /metrics, not just as caller error strings.
        self.peer_send_buffer_depth = Gauge(
            "gubernator_peer_send_buffer_depth",
            "request TLVs queued in a peer's send buffer awaiting a "
            "flush", ["peer_addr"], registry=r)
        self.peer_flush_size = Histogram(
            "gubernator_peer_flush_size",
            "request TLVs per peer flush RPC",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 4096),
            registry=r)
        self.peer_flush_wait = Histogram(
            "gubernator_peer_flush_wait",
            "entry wait from send-buffer enqueue to its flush RPC "
            "launching (s)", buckets=_BUCKETS, registry=r)
        self.peer_inflight_rpcs = Gauge(
            "gubernator_peer_inflight_rpcs",
            "peer flush RPCs currently in flight (depth-K pipelined)",
            ["peer_addr"], registry=r)
        self.peer_retry_counter = Counter(
            "gubernator_peer_retries",
            "peer flush RPCs re-sent after a failure (backoff applies)",
            ["peer_addr"], registry=r)
        self.peer_circuit_open_counter = Counter(
            "gubernator_peer_circuit_opens",
            "times a peer's circuit opened (consecutive flush failures "
            "crossed peer_circuit_threshold)", ["peer_addr"],
            registry=r)
        self.peer_circuit_state = Gauge(
            "gubernator_peer_circuit_state",
            "1 while a peer's circuit is open (sends fail fast)",
            ["peer_addr"], registry=r)
        # Key-level analytics (ISSUE 4): per-phase latency attribution
        # + the bounded heavy-hitter ledger's export surface.  The
        # topkey gauge is label-bounded BY CONSTRUCTION: analytics.py ›
        # KeyAnalytics._publish removes departed keys' labels before
        # setting the current top-K, so cardinality never exceeds
        # GUBER_TOPK — per-key labels over the whole key space are
        # exactly what a million-key deployment must never export.
        self.phase_duration = Histogram(
            "gubernator_phase_duration",
            "wall seconds of each program phase (tracing.PHASE_CATALOG)"
            " — pack+device+resolve partition wave_duration",
            ["phase"], buckets=_BUCKETS, registry=r)
        self.phase_cpu = Counter(
            "gubernator_phase_cpu_seconds",
            "thread CPU seconds inside the phases that record them "
            "(route.*, handler, local.pack, a wave's pack and "
            "resolve), read at the same boundaries as phase_duration: "
            "wall - cpu is time the thread waited for the GIL or a lock",
            ["phase"], registry=r)
        self.phase_cpu_wall = Counter(
            "gubernator_phase_cpu_wall_seconds",
            "wall seconds of exactly those phase samples that also "
            "recorded CPU time (phase_cpu_seconds' denominator: waves "
            "are sampled 1 in 16 for it, calls are not)",
            ["phase"], registry=r)
        self._phase_children: dict = {}  # phase → its three children
        self.door_inflight = Histogram(
            "gubernator_door_inflight",
            "GetRateLimits handlers in flight at a handler's entry, "
            "itself included (the gRPC pool has 32 worker threads)",
            buckets=(1, 2, 4, 8, 12, 16, 20, 24, 28, 32, 48, 64, 128),
            registry=r)
        self.topkey_overlimit = Gauge(
            "gubernator_topkey_overlimit_total",
            "OVER_LIMIT decisions observed for each CURRENT top-K key "
            "while tracked (bounded labels: departed keys are removed)",
            ["key"], registry=r)
        self.analytics_waves = Counter(
            "gubernator_analytics_waves_tapped",
            "resolved waves folded into the heavy-hitter sketch",
            registry=r)
        self.analytics_dropped = Counter(
            "gubernator_analytics_tap_dropped",
            "wave taps dropped because the analytics queue was full "
            "(analytics never applies backpressure to serving)",
            registry=r)
        self.analytics_learn_rows = Counter(
            "gubernator_analytics_learn_rows",
            "rows of wire calls the tenant learn looked at, by what it "
            "found: known (the khash table held the key), learned (a "
            "new key, filed under its tenant's bucket), other (a new "
            "key filed under __other__: the tenant ledger is full)",
            ["outcome"], registry=r)
        # Failure-domain resilience (ISSUE 5): degraded-mode serving,
        # health-gated ring churn, admission shedding, and fault
        # injection all need first-class visibility — a cluster riding
        # out a dead owner must LOOK like one on /metrics.
        self.forward_failed = Counter(
            "gubernator_forward_failed",
            "forwarded sub-batches that failed, by peer and reason "
            "(circuit_open, closing, rpc_error, short_response, "
            "send_error) — counts requests, whether they degraded to "
            "local answers or became error rows",
            ["peer_addr", "reason"], registry=r)
        self.degraded_served = Counter(
            "gubernator_degraded_served",
            "requests answered locally in degraded mode while their "
            "owner was unreachable or their keys were rehomed "
            "(response carries metadata degraded=true; hits reconcile "
            "to the owner through the GLOBAL hit-flush queues)",
            ["peer_addr"], registry=r)
        self.ring_generation = Gauge(
            "gubernator_ring_generation",
            "monotonic generation of the health-gated routing ring; "
            "bumps when a peer is ejected or readmitted (flap detector: "
            "one outage should cost exactly two bumps)", registry=r)
        self.ring_ejected_peers = Gauge(
            "gubernator_ring_ejected_peers",
            "peers currently ejected from the routing ring by the "
            "health gate (their keys are rehomed until readmit)",
            registry=r)
        self.admission_shed = Counter(
            "gubernator_admission_shed",
            "requests shed at ingress with RESOURCE_EXHAUSTED, by "
            "reason (queue_full, deadline, draining)",
            ["reason"], registry=r)
        self.draining = Gauge(
            "gubernator_draining",
            "1 while the daemon is in its shutdown drain window "
            "(shallow /healthz returns 503 'draining')", registry=r)
        self.fault_injected = Counter(
            "gubernator_fault_injected",
            "times an armed faultpoint fired (faults.py; 0 in healthy "
            "operation — nonzero means a chaos run is active)",
            ["point"], registry=r)
        # Mesh-resident GLOBAL (ISSUE 7): the collective reconcile
        # tier's shape — fold cadence, measured coherence staleness,
        # and the degraded fallback to the gRPC path must all be
        # visible, or a silently stood-down tier looks healthy while
        # every GLOBAL key quietly rides the slow path.
        self.mesh_global_folds = Counter(
            "gubernator_mesh_global_folds",
            "mesh-GLOBAL reconcile collectives completed (one "
            "all-reduce fold per generation)", registry=r)
        self.mesh_global_fold_errors = Counter(
            "gubernator_mesh_global_fold_errors",
            "mesh-GLOBAL reconcile ticks that failed (accumulators "
            "swap back — no hit is lost; consecutive failures past "
            "GUBER_MESH_FALLBACK_AFTER stand the tier down)",
            registry=r)
        self.mesh_global_staleness = Gauge(
            "gubernator_mesh_global_staleness_seconds",
            "measured coherence staleness at the last mesh-GLOBAL "
            "fold: age of the oldest hit the collective folded "
            "(bounded by the reconcile interval when ticks are "
            "healthy)", registry=r)
        self.mesh_global_degraded = Gauge(
            "gubernator_mesh_global_degraded",
            "1 while the mesh-GLOBAL tier is stood down (keys demoted "
            "to the owner-sharded path; reconcile rides the gRPC "
            "queues until the fold recovers)", registry=r)
        self.mesh_global_keys = Gauge(
            "gubernator_mesh_global_keys",
            "keys currently pinned in the mesh-GLOBAL replica table",
            registry=r)
        # Tiered key store (ISSUE 10): the hot/cold split only works if
        # its migration traffic is visible — a thrashing admission
        # policy or a cold tier absorbing most serves is a perf cliff
        # that decision latency alone won't attribute.
        self.tier_cold_keys = Gauge(
            "gubernator_tier_cold_keys",
            "keys resident in the host cold tier (device-table misses "
            "served exactly from host memory)", registry=r)
        self.tier_cold_serves = Counter(
            "gubernator_tier_cold_serves",
            "requests served from the host cold tier (device miss or "
            "table overflow; byte-exact with the device step)",
            registry=r)
        self.tier_cold_native_serves = Counter(
            "gubernator_tier_cold_native_serves",
            "of gubernator_tier_cold_serves, the requests that ONE C++ "
            "pass applied (ops/_native.cpp cold_apply_batch: the native "
            "store of a build that has it); the rest went through the "
            "Python loop of _host_apply calls; one inc a wave's cold "
            "lane", registry=r)
        self.tier_cold_creates = Counter(
            "gubernator_tier_cold_creates",
            "keys CREATED in the host cold tier: a served request whose "
            "key neither tier held (a first-seen key whose device "
            "bucket or probe window is full); counted beside "
            "gubernator_tier_cold_serves, one inc a wave", registry=r)
        self.tier_promotions = Counter(
            "gubernator_tier_promotions",
            "cold rows migrated into the device table after their "
            "sketch rank cleared GUBER_TIER_PROMOTE", registry=r)
        self.tier_demotions = Counter(
            "gubernator_tier_demotions",
            "device rows evicted to the host cold tier (promotion "
            "victims and table-full writebacks; created_at-preserving, "
            "conservation-exact)", registry=r)
        self.tier_migrations_aborted = Counter(
            "gubernator_tier_migrations_aborted",
            "tier migrations abandoned at the tier_promote/tier_demote "
            "faultpoints (the row stays in its source tier — no state "
            "is lost)", registry=r)
        self.tier_admissions_deferred = Counter(
            "gubernator_tier_admissions_deferred",
            "cold keys whose rank cleared the admission threshold in a "
            "wave whose migration pass was already full "
            "(tiering.MIGRATE_MAX keys a pass): they stay cold, are "
            "answered exactly there, and are admitted when next "
            "served; one inc(n) a pass", registry=r)
        # Tenant-aware SLO plane (ISSUE 11): per-tenant RED ledger
        # gauges (bounded cardinality — GUBER_TENANT_MAX buckets plus
        # __other__; the analytics worker republishes on its paced
        # publish tick) and the burn-rate verdict gauge.
        self.tenant_requests = Gauge(
            "gubernator_tenant_requests",
            "rows attributed to this tenant (tenant = key-name prefix "
            "up to GUBER_TENANT_DELIM; overflow folds into __other__)",
            ["tenant"], registry=r)
        self.tenant_hits = Gauge(
            "gubernator_tenant_hits",
            "hit weight attributed to this tenant", ["tenant"],
            registry=r)
        self.tenant_over_limit = Gauge(
            "gubernator_tenant_over_limit",
            "OVER_LIMIT rows attributed to this tenant", ["tenant"],
            registry=r)
        self.tenant_errors = Gauge(
            "gubernator_tenant_errors",
            "error rows attributed to this tenant", ["tenant"],
            registry=r)
        self.tenant_degraded = Gauge(
            "gubernator_tenant_degraded",
            "degraded-mode serves attributed to this tenant",
            ["tenant"], registry=r)
        self.tenant_shed = Gauge(
            "gubernator_tenant_shed",
            "admission-shed rows attributed to the tenant that "
            "triggered the shed", ["tenant"], registry=r)
        self.slo_burn = Gauge(
            "gubernator_slo_burn",
            "fast-window burn rate per SLO (error-budget spend "
            "multiple; breach latches when fast AND slow exceed the "
            "threshold — see GET /debug/slo); tenant label empty for "
            "instance-level SLOs", ["slo", "tenant"], registry=r)
        self.fleet_conservation_drift = Gauge(
            "gubernator_fleet_conservation_drift",
            "conservation drift this daemon contributes to the fleet "
            "fold: GLOBAL hits injected minus applied across both "
            "backends (nonzero while flushes fail or are in flight; "
            "held nonzero past the flush-window bound it burns the "
            "fleet_conservation SLO — see GET /debug/audit)",
            registry=r)
        self.memledger_bytes = Gauge(
            "gubernator_memledger_bytes",
            "live bytes per memory-ledger consumer (host-side "
            "consumers report host bytes — see GET /debug/memory)",
            ["consumer"], registry=r)
        self.memledger_rows = Gauge(
            "gubernator_memledger_rows",
            "memory-ledger rows per consumer: state=capacity is the "
            "allocated row budget, state=occupied the live occupancy",
            ["consumer", "state"], registry=r)
        # The thread ledger (ISSUE 37): gubernator_thread_* by role,
        # read from /proc/self/task when this registry is rendered
        # (tracing.ThreadLedger) — who uses the daemon's cores, and how
        # much of one GIL the Python threads ask for.
        self.thread_ledger = ThreadLedger()
        r.register(self.thread_ledger)

    @contextmanager
    def time_func(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.func_duration.labels(name=name).observe(
                time.perf_counter() - t0)

    def observe_phase(self, name: str, seconds: float, cpu=None,
                      exemplar=None) -> None:
        """One ``tracing.phase`` sample → gubernator_phase_duration
        {phase} (+ the CPU pair when the sample recorded CPU time).
        The label children are resolved once a phase: ``.labels()`` per
        sample is a lock + dict walk on the serving path."""
        kids = self._phase_children.get(name)
        if kids is None:  # benign race: labels() is idempotent
            kids = self._phase_children[name] = [
                self.phase_duration.labels(phase=name), None, None]
        if exemplar:
            observe_with_exemplar(kids[0], seconds, exemplar)
        else:
            kids[0].observe(seconds)
        if cpu is not None:
            if kids[1] is None:  # a series only for phases that record it
                kids[1] = self.phase_cpu.labels(phase=name)
                kids[2] = self.phase_cpu_wall.labels(phase=name)
            kids[1].inc(max(cpu, 0.0))
            kids[2].inc(seconds)

    def render(self) -> bytes:
        """Text exposition for the /metrics endpoint."""
        return generate_latest(self.registry)


def observe_with_exemplar(hist, value: float, exemplar=None) -> None:
    """Histogram observe with a best-effort exemplar attach (ISSUE 12).

    ``exemplar`` is a small label dict (``{"trace_id": ...}`` from
    SpanRecorder.exemplar()) linking the observation's bucket to one
    concrete sampled trace — surfaced by the openmetrics exposition.
    Any client that rejects the exemplar (older prometheus_client, a
    >128-char label set) falls back to a plain observe: the exemplar
    is a debugging link, never worth failing the serving path."""
    if exemplar:
        try:
            hist.observe(value, exemplar)
            return
        except (TypeError, ValueError):
            pass
    hist.observe(value)
