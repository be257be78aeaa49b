"""Device dispatcher: cross-request batch coalescing.

The TPU-native replacement for the reference's worker-pool cache
sharding (workers.go › WorkerPool — reconstructed): where the reference
hashes requests to per-core goroutines to avoid lock contention, here
ALL concurrent client batches are merged into one device program launch.
A single dispatcher thread drains the queue, packs every waiting request
into the next device step, and resolves each caller's future with its
slice of the results.

Why it's faster than per-caller engine calls under a lock: the device
step costs roughly the same for 1 request as for 10 000 (it streams the
whole table either way — core/step.py › decide_batch), so merging N
concurrent callers into one launch divides the per-launch cost by N and
removes the serialization point entirely.  This is the service-side
analog of the batch coalescing the raw benchmark does by hand.
"""
from __future__ import annotations

import logging
import os
import queue
import threading
import time
from concurrent.futures import Future
from concurrent.futures import TimeoutError as FuturesTimeout
from typing import List, Optional, Sequence, Tuple

from contextlib import contextmanager
from contextvars import ContextVar

from .tracing import WaveScope, phase, take_gap
from .types import RateLimitRequest, RateLimitResponse

log = logging.getLogger("gubernator_tpu.dispatcher")


class ResourceExhausted(RuntimeError):
    """Raised at ingress when admission control sheds a request batch
    (bounded queue full, projected queue-wait past the caller deadline,
    or drain mode).  The daemon maps it to grpc RESOURCE_EXHAUSTED /
    HTTP 429 — shedding must be CHEAP and explicit, never a timeout."""


#: caller deadline for deadline-aware shedding, set by the serving
#: front door (grpc context.time_remaining / HTTP timeout header) and
#: read by Dispatcher.admit in the same thread/context
_REQUEST_DEADLINE: "ContextVar[Optional[float]]" = ContextVar(
    "guber_request_deadline", default=None)


@contextmanager
def request_deadline(seconds: Optional[float]):
    """Scope the caller's remaining deadline (seconds) for admission
    control; None means 'no deadline' (only queue-full/drain shed)."""
    tok = _REQUEST_DEADLINE.set(seconds)
    try:
        yield
    finally:
        _REQUEST_DEADLINE.reset(tok)


def _job_len(job) -> int:
    return (len(job.reqs) if isinstance(job, _Job) else len(job.khash))


def _release_wave(batch) -> None:
    """Return the lease a joined wave's batch is a view of, if it is
    (ShardedEngine.join_calls): the end of every path on which the
    engine did not take it into a token.  Idempotent."""
    lease = getattr(getattr(batch, "rows", None), "lease", None)
    if lease is not None:
        lease.release()


class ResultView:
    """Row-slice view [lo, hi) into a wave's SHARED downloaded result
    columns (status i32, limit i64, remaining i64, reset i64, full
    bool).

    The worker thread resolves each job's future with one of these —
    two ints and a tuple reference — instead of materializing per-job
    column tuples, so everything downstream of the device download
    (slicing, over-limit counting, wire-byte serialization) runs in the
    CALLER's thread, off the single dispatch loop.  Unpacking iterates
    the five sliced columns, so ``st, lim, rem, rst, full = view``
    keeps working at every legacy call site."""

    __slots__ = ("cols", "lo", "hi")

    def __init__(self, cols, lo: int, hi: int):
        self.cols = cols
        self.lo = lo
        self.hi = hi

    def sliced(self) -> tuple:
        lo, hi = self.lo, self.hi
        return tuple(c[lo:hi] for c in self.cols)

    def __iter__(self):
        return iter(self.sliced())

    def __len__(self) -> int:
        return 5


class _Job:
    __slots__ = ("reqs", "now_ms", "future", "t_enq", "qwait", "trace",
                 "span")

    def __init__(self, reqs, now_ms):
        self.reqs = reqs
        self.now_ms = now_ms
        self.future: Future = Future()
        #: stamped by _submit: queue-wait start (and the open
        #: `queue_wait` phase, ended by the wave that takes the job) +
        #: caller's trace id (+ the caller's open span id, the wave
        #: span's parent)
        self.t_enq: Optional[float] = None
        self.qwait: Optional[phase] = None
        self.trace: Optional[str] = None
        self.span: Optional[str] = None


class _PackedJob:
    """Columnar job (the wire lanes): the call's rows as ONE block in
    the upload layout (core/batch.py › Rows — laid out by the call's
    own thread, with what the engine derived of them) + key hashes,
    instead of RateLimitRequest objects.  ``mslot`` (ISSUE 8): optional
    per-request mesh-GLOBAL replica slot column (-1 = sharded row) —
    rides the job so a fused engine can serve both lanes in ONE
    launch."""

    __slots__ = ("rows", "khash", "now_ms", "future", "t_enq", "qwait",
                 "trace", "span", "mslot")

    def __init__(self, rows, khash, now_ms, mslot=None):
        self.rows = rows
        self.khash = khash
        self.now_ms = now_ms
        self.mslot = mslot
        self.future: Future = Future()
        self.t_enq: Optional[float] = None
        self.qwait: Optional[phase] = None
        self.trace: Optional[str] = None
        self.span: Optional[str] = None

    @property
    def batch(self):
        """The rows as the RequestBatch other readers see (views)."""
        return self.rows.batch


class Dispatcher:
    """Serializes engine access by merging, not locking."""

    #: Hard cap on how long a caller waits for its wave; protects the
    #: request handler from a wedged device (first compile is warmed by
    #: the daemon before serving, so steady-state waves are ms-scale).
    #: GUBER_RESULT_TIMEOUT_S overrides: cold TPU compiles of wave
    #: programs take tens of seconds each and add up to minutes, so any
    #: caller that can arrive before warmup (benches, probes) must
    #: budget past the compile.
    RESULT_TIMEOUT_S = 120.0

    #: Default stall threshold: a wave in flight this long is flagged by
    #: the watchdog (gauge + log + recorder event) — deliberately well
    #: below RESULT_TIMEOUT_S so a cold compile surfaces as a DIAGNOSED
    #: stall minutes before callers give up.  GUBER_STALL_THRESHOLD_S
    #: overrides; <= 0 disables the watchdog.
    STALL_THRESHOLD_S = 30.0

    #: default depth of the overlapped wave pipeline: how many launched
    #: waves may be in flight (unsynced) at once.  Depth 2 = pack wave
    #: N+1 while wave N runs; GUBER_PIPELINE_DEPTH overrides (min 1 —
    #: depth 1 degenerates to launch-then-sync, i.e. no overlap).
    PIPELINE_DEPTH = 2

    #: default wave cap in rows, and the least an instance sets: it
    #: raises the cap to what ONE launch of its engine holds
    #: (``engine.wave_capacity``), never lowers it
    MAX_WAVE = 8192

    #: default admission bound: rows queued (not yet launched) before
    #: ingress sheds with RESOURCE_EXHAUSTED.  GUBER_ADMISSION_LIMIT
    #: overrides; 0 disables the bound (deadline/drain shed remain).
    #: In ROWS, whatever ``max_wave`` is: what an overloaded daemon
    #: sheds does not move with the size of its engine's largest launch
    ADMISSION_LIMIT_ROWS = 65536

    #: fan-in link bound (ISSUE 12): a wave span records at most this
    #: many OTHER batched requests' (trace, span) pairs as attributes
    WAVE_LINKS = 8

    #: one wave in this many also records thread CPU time in its
    #: phases (gubernator_phase_cpu_seconds against ..._cpu_wall_
    #: seconds): time.thread_time() is a system call, and ~30 of them
    #: a wave cost single-request traffic 1.6 % of its rate on the chip
    CPU_SAMPLE = 16

    #: the per-call phases `handler` and `call.wait` time one call in
    #: this many (``call_sample`` = 1: every call, set by an instance
    #: whose GLOBAL rows route on the handler threads)
    CALL_SAMPLE = 8

    def __init__(self, engine, max_wave: int = MAX_WAVE,
                 max_delay_ms: float = 0.2,
                 lock: Optional[threading.Lock] = None,
                 metrics=None, recorder=None, clock=time.monotonic,
                 analytics=None, faults=None):
        self.engine = engine
        #: optional FaultSet (faults.py): dispatch_enqueue / _launch /
        #: _sync / device_step faultpoints
        self._faults = faults
        #: key-level analytics subsystem (analytics.py › KeyAnalytics,
        #: optional): resolved waves tap their khash/hits/status
        #: columns into its worker queue AFTER the wave ends — strictly
        #: off the caller's critical path — and per-phase durations
        #: feed its ledger.  None (bare dispatchers) costs nothing.
        self.analytics = analytics
        self._scope_seq = 0  # lock-free: sampling counter (see _new_scope)
        self.call_sample = self.CALL_SAMPLE
        self.max_wave = max_wave
        # coalescing window: how long the worker waits for more jobs
        # after the first before launching the wave.  GUBER_COALESCE_US
        # (microseconds) overrides the constructor default; malformed
        # or negative values keep it.  _drain_wave skips the wait
        # entirely when the queue already held >= MAX_WAVE rows.
        coalesce_env = os.environ.get("GUBER_COALESCE_US", "")
        if coalesce_env:
            try:
                max_delay_ms = max(float(coalesce_env), 0.0) / 1000.0
            except ValueError:
                pass  # malformed: keep the constructor default
        self.max_delay_s = max_delay_ms / 1000.0
        # overlapped-pipeline depth (in-flight launched waves)
        depth_env = os.environ.get("GUBER_PIPELINE_DEPTH", "")
        try:
            depth = int(depth_env) if depth_env else self.PIPELINE_DEPTH
        except ValueError:
            depth = self.PIPELINE_DEPTH
        self.pipeline_depth = max(depth, 1)
        #: per-instance Metrics registry (metrics.py) and FlightRecorder
        #: (telemetry.py); both optional — a bare Dispatcher (tests,
        #: library use) pays only the cheap internal counters.
        self.metrics = metrics
        self.recorder = recorder
        #: optional tracing.SpanRecorder (ISSUE 12): when attached (by
        #: the instance), every wave emits a fan-in span whose children
        #: are the wave.*/lock.* phases that ran for it; None (bare
        #: dispatchers, bench "off" arm) costs nothing.  Plain attr —
        #: swapped whole, racy reads are fine.
        self.span_recorder = None
        self._clock = clock
        #: mesh-GLOBAL reconcile generation (ISSUE 7): bumped by the
        #: instance after each collective fold; every wave is stamped
        #: with the generation it served under, so a decision window
        #: correlates with the coherence epoch it read.  Single racy
        #: int write/read by design (a wave straddling a fold may carry
        #: either stamp — both are true).
        self.reconcile_gen = 0
        # --- wave telemetry state (all under _tel_mu) ---
        self._tel_mu = threading.Lock()
        #: wave_id → {t0, kind, size, trace, stalled}
        self._inflight: dict = {}  # guarded-by: self._tel_mu
        self._wave_seq = 0  # guarded-by: self._tel_mu
        self._wave_count = 0  # guarded-by: self._tel_mu
        self._stall_count = 0  # guarded-by: self._tel_mu
        self._timeout_count = 0  # guarded-by: self._tel_mu
        self._first_wave_s: Optional[float] = None  # guarded-by: self._tel_mu
        self._last_wave_end: Optional[float] = None  # guarded-by: self._tel_mu
        from collections import deque as _deque

        #: bounded recent-wave samples for telemetry_snapshot percentiles
        #: (prometheus histograms can't answer percentile queries)
        self._recent_sizes: "_deque" = _deque(maxlen=4096)  # guarded-by: self._tel_mu
        self._recent_durs: "_deque" = _deque(maxlen=4096)  # guarded-by: self._tel_mu
        self._recent_waits: "_deque" = _deque(maxlen=4096)  # guarded-by: self._tel_mu
        #: Shared with the instance's row-level ops (gather/upsert/
        #: restore/sweep), which run on other threads and mutate the
        #: same engine state.
        self._engine_lock = lock if lock is not None else threading.Lock()
        self._queue: "queue.Queue[_Job]" = queue.Queue()
        #: worker-local holdover: the job that would have pushed the
        #: current wave past max_wave leads the next wave instead
        #: (only the dispatch thread touches it)
        self._carry = None
        self._closing = threading.Event()
        self._submit_mu = threading.Lock()  # serializes submit vs close
        # ---- overload admission control (ISSUE 5) ----
        # bounded ingress: _queued_rows tracks rows submitted but not
        # yet pulled into a wave; admit() sheds past the limit, when
        # the projected queue wait exceeds the caller's deadline, or in
        # drain mode.  All under _submit_mu (brief).
        adm_env = os.environ.get("GUBER_ADMISSION_LIMIT", "")
        try:
            self.admission_limit = (int(adm_env) if adm_env
                                    else self.ADMISSION_LIMIT_ROWS)
        except ValueError:
            self.admission_limit = self.ADMISSION_LIMIT_ROWS
        self._queued_rows = 0  # guarded-by: self._submit_mu
        #: drain flag: single racy bool write in drain(), lock-free reads
        self._draining = False
        self._shed_rows = 0  # guarded-by: self._submit_mu
        #: recorder rate limit (1/s/reason)
        self._last_shed_event = 0.0  # guarded-by: self._submit_mu
        #: who runs a wave: the worker, always.  Pipelined (launch_packed
        #: / sync_packed, depth pipeline_depth) when the engine has the
        #: capability; serial (check_packed / check_batch) for an engine
        #: without it (OracleEngine) and for list/merged waves.
        self._pipelined = hasattr(engine, "launch_packed")
        #: who lays out what (ISSUE 30): a packed call's rows are
        #: stacked and examined ONCE, in its own thread (``lay_out``,
        #: in check_packed_view); the worker only joins the calls'
        #: blocks (``_join``, in _concat_jobs).  An engine with a say
        #: in either (ShardedEngine: its domain mask; a pooled lease to
        #: join into) brings its own.
        from .core.batch import join_calls, stack_rows

        self.lay_out = getattr(
            engine, "lay_out", lambda batch, khash, mslot: stack_rows(batch))
        self._join = getattr(engine, "join_calls", join_calls)
        # fused-engine capability (ISSUE 8): the engine emits the
        # heavy-hitter tap columns on device at launch, so the
        # dispatcher's host-side column copies are skipped.
        self._fused_tap = getattr(engine, "fused_tap", False)
        if self.metrics is not None:
            self.metrics.pipeline_depth.set(
                self.pipeline_depth if self._pipelined else 0)
        env_timeout = os.environ.get("GUBER_RESULT_TIMEOUT_S", "")
        if env_timeout:
            import math

            try:
                parsed = float(env_timeout)
            except ValueError:
                parsed = 0.0  # malformed: keep the class default
            if math.isfinite(parsed) and parsed > 0:
                # rejects 0/negative/NaN (a 0 s wait would fail EVERY
                # queued wave instantly) AND 'inf' (which silently
                # disabled the wave-wait cap: a wedged wave would park
                # its caller forever with no timeout diagnosis)
                self.RESULT_TIMEOUT_S = parsed
        # Stall watchdog: default well below the result timeout (and
        # scaled down with it, so a tightened timeout keeps the "stall
        # first, timeout later" ordering).  An explicit env value is an
        # operator choice and is honored verbatim; <= 0 disables.
        stall_env = os.environ.get("GUBER_STALL_THRESHOLD_S", "")
        if stall_env:
            try:
                self._stall_threshold_s = float(stall_env)
            except ValueError:
                self._stall_threshold_s = min(
                    self.STALL_THRESHOLD_S, self.RESULT_TIMEOUT_S / 4.0)
            if self._stall_threshold_s != self._stall_threshold_s:  # NaN
                self._stall_threshold_s = 0.0
        else:
            self._stall_threshold_s = min(
                self.STALL_THRESHOLD_S, self.RESULT_TIMEOUT_S / 4.0)
        self._watchdog: Optional[threading.Thread] = None
        if self._stall_threshold_s > 0:
            #: poll well inside the threshold so a stall is flagged
            #: promptly after it crosses the line
            self._watch_interval_s = max(
                min(self._stall_threshold_s / 4.0, 1.0), 0.02)
            self._watchdog = threading.Thread(
                target=self._watchdog_run, daemon=True,
                name="dispatcher-watchdog")
            self._watchdog.start()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="device-dispatcher")
        self._thread.start()

    def check_batch(self, reqs: Sequence[RateLimitRequest], now_ms: int
                    ) -> List[RateLimitResponse]:
        """Submit and wait; concurrent callers share device launches."""
        return self._submit_and_wait(_Job(list(reqs), now_ms))

    def _submit_and_wait(self, job):
        """Queue ``job`` and block on its wave's future: `call.wait` is
        queue wait + wave, from the caller's side."""
        with phase("call.wait", self, every=self.call_sample):
            self._submit(job)
            try:
                return job.future.result(timeout=self.RESULT_TIMEOUT_S)
            except FuturesTimeout as e:
                raise self._result_timeout(e) from e

    def check_packed(self, batch, khash, now_ms: int,
                     mslot=None) -> tuple:
        """Columnar submit (see engine.check_packed); coalesces with
        other packed callers by column concatenation.
        Returns the classic 5-tuple of per-request columns; the
        slicing out of the wave's shared result columns happens HERE,
        in the caller's thread (see ResultView).  ``mslot`` (ISSUE 8):
        per-request mesh-GLOBAL slot column for fused engines."""
        return self.check_packed_view(batch, khash, now_ms,
                                      mslot=mslot).sliced()

    def check_packed_view(self, batch, khash, now_ms: int,
                          mslot=None) -> ResultView:
        """``check_packed`` returning the zero-copy ResultView: row
        bounds into the wave's shared downloaded result columns.  The
        wire lanes serialize straight from the view (ops/_native.cpp ›
        build_responses_from_columns) without materializing per-job
        column tuples."""
        return self._submit_and_wait(_PackedJob(
            self.lay_out(batch, khash, mslot), khash, now_ms, mslot=mslot))

    def _fault(self, point: str) -> None:
        f = self._faults
        if f is not None and f.armed:
            f.fire(point)

    # ---- overload admission control (ISSUE 5) ---------------------------

    def _shed(self, reason: str, nrows: int,
              tenant_cb=None) -> None:
        from .tracing import current_span_id, force_sample

        # a shed outcome must survive head sampling (ISSUE 12): the
        # rejected caller's trace is exactly the one worth keeping
        force_sample("shed")
        if self.metrics is not None:
            self.metrics.admission_shed.labels(reason=reason).inc(nrows)
        # tenant attribution (ISSUE 11): resolved LAZILY — only sheds
        # pay the callback (a prefix split or a dict probe), the admit
        # fast path never does
        tenant = None
        if tenant_cb is not None:
            try:
                tenant = tenant_cb()
            except Exception:  # pragma: no cover - attribution only
                tenant = None
        ana = self.analytics
        if ana is not None:
            ana.tap_flag("shed", nrows, tenant=tenant)
        with self._submit_mu:
            self._shed_rows += nrows
            now = self._clock()
            throttled = now - self._last_shed_event < 1.0
            if not throttled:
                self._last_shed_event = now
        if self.recorder is not None and not throttled:
            # rate-limited: under sustained overload one event per
            # second, not one per rejected call
            ev = {"reason": reason, "rows": nrows,
                  "queued_rows": self._queued_rows}  # lock-free: diagnostic snapshot
            if tenant is not None:
                ev["tenant"] = tenant
            sid = current_span_id()
            if sid is not None:
                ev["span_id"] = sid
            self.recorder.record("admission_shed", **ev)
        raise ResourceExhausted(
            f"admission control shed {nrows} requests ({reason}: "
            f"queued_rows={self._queued_rows}, "  # lock-free: diagnostic snapshot
            f"limit={self.admission_limit})")

    def projected_queue_wait_s(self, extra_rows: int = 0) -> float:
        """Projected QUEUE WAIT for work entering now: how long the
        rows already ahead (+ ``extra_rows``) take to drain, from
        observed service rates.  The per-wave service time prefers the
        analytics PhaseLedger's per-phase means (pack+device+resolve,
        ISSUE 4), falling back to the recent-wave deques; an empty
        queue projects 0 — your wave launches immediately."""
        with self._tel_mu:
            # lock-free: projection input; a racy row read costs one wave of estimate error
            queued = self._queued_rows + extra_rows
            sizes = list(self._recent_sizes)
            durs = list(self._recent_durs)
        if queued <= 0:
            return 0.0
        wave_s = None
        ana = self.analytics
        if ana is not None:
            means = [ana.phases.mean(p)
                     for p in ("pack", "device", "resolve")]
            if any(m is not None for m in means):
                wave_s = sum(m for m in means if m is not None)
        if wave_s is None:
            if not durs:
                return 0.0
            wave_s = sum(durs) / len(durs)
        # queued rows coalesce into waves of up to max_wave rows each,
        # but never better than the sizes actually observed
        avg_rows = max(sum(sizes) / max(len(sizes), 1), 1.0)
        rows_per_wave = min(max(avg_rows, queued), self.max_wave)
        import math

        return math.ceil(queued / rows_per_wave) * wave_s

    def admit(self, nrows: int, deadline_s: Optional[float] = None,
              tenant_cb=None) -> None:
        """Deadline-aware ingress gate: raise ResourceExhausted instead
        of queueing work that cannot finish.  Cheap — a couple of
        reads; no device work, no allocation on the admit path.
        Deadline shedding only engages when a backlog EXISTS: an idle
        dispatcher serves any deadline (the wave launches at once).
        ``tenant_cb`` (ISSUE 11) resolves the triggering tenant — only
        invoked when a shed actually happens."""
        if self._draining:
            self._shed("draining", nrows, tenant_cb)
        lim = self.admission_limit
        if lim and self._queued_rows + nrows > lim:  # lock-free: GIL-atomic int read; admit is approximate by design
            self._shed("queue_full", nrows, tenant_cb)
        dl = deadline_s if deadline_s is not None \
            else _REQUEST_DEADLINE.get()
        if dl is not None and dl > 0 and self._queued_rows > 0:  # lock-free: GIL-atomic int read; admit is approximate by design
            # wait = draining what's AHEAD of this batch; its own
            # service time is not queue wait
            if self.projected_queue_wait_s(0) > dl:
                self._shed("deadline", nrows, tenant_cb)

    def drain(self) -> None:
        """Enter drain mode: queued/in-flight waves complete, new
        ingress sheds with RESOURCE_EXHAUSTED('draining').  Part of the
        daemon's graceful-shutdown sequence."""
        self._draining = True

    def _submit(self, job) -> None:
        from .tracing import current_span_id, current_trace_id

        self._fault("dispatch_enqueue")
        n = _job_len(job)
        self.admit(n)
        job.t_enq = self._clock()
        job.qwait = phase("queue_wait", self, span=False).begin(
            at=job.t_enq)
        job.trace = current_trace_id()
        job.span = current_span_id()
        with self._submit_mu:
            # checked under the same lock close() takes, so a job can
            # never slip into the queue after the final drain
            if self._closing.is_set():
                raise RuntimeError("dispatcher is closed")
            self._queue.put(job)
            self._queued_rows += n

    # ---- wave telemetry -------------------------------------------------
    #
    # Every engine execution — list, packed, merged, pipelined
    # launch/sync — is ONE wave: _wave_begin observes size + per-job
    # queue waits and registers the wave in _inflight (the watchdog's
    # scan set); _wave_end observes duration and resolves stall state.
    # All metric/recorder emission is None-guarded: a bare Dispatcher
    # costs two dict ops and a few deque appends per wave.

    def _wave_begin(self, scope: WaveScope, kind: str, jobs,
                    slot: Optional[int] = None) -> int:
        """Register a wave (see above) inside its already entered
        ``scope``: binds the wave's ids into it, ends the jobs'
        `queue_wait` phases, and opens the coarse `pack` phase at the
        wave's own t0.  The bookkeeping itself is the `wave.begin`
        phase."""
        t0 = self._clock()
        ph = phase("wave.begin", self).begin()
        waits = []
        trace = parent = tenant = None
        links = []
        nreq = sum(_job_len(j) for j in jobs)
        for j in jobs:
            if j.qwait is not None:
                waits.append(j.qwait.end(at=t0))
            if trace is None:
                trace = j.trace
                parent = getattr(j, "span", None)
            elif self.span_recorder is not None and j.trace \
                    and len(links) < self.WAVE_LINKS:
                # fan-in: every OTHER request batched into this
                # wave, linked by (trace, span) pairs (bounded)
                links.append(f"{j.trace}:{getattr(j, 'span', '') or ''}")
        wspan = None
        sr = self.span_recorder
        if sr is not None and trace is not None:
            from .tracing import new_span_id

            wspan = new_span_id()
        if self.recorder is not None:
            # event-field hint only (one dict probe / prefix split,
            # first job names the wave) — ledger attribution happens
            # in the analytics worker, not here
            tenant = self._job_tenant(jobs[0])
        gen = self.reconcile_gen
        with self._tel_mu:
            self._wave_seq += 1
            wid = self._wave_seq
            self._inflight[wid] = {"t0": t0, "kind": kind, "size": nreq,
                                   "trace": trace, "stalled": False,
                                   "slot": slot, "gen": gen,
                                   "tenant": tenant, "span": wspan,
                                   "links": links, "scope": scope,
                                   "coarse": phase(
                                       "pack", self, cpu=scope.cpu,
                                       span=False).begin(at=t0),
                                   "phases": {}}
            self._recent_sizes.append(nreq)
            self._recent_waits.extend(waits)
        if wspan is not None:
            scope.bind(sr, trace, wspan, parent, wid)
        if self.metrics is not None:
            self.metrics.wave_size.observe(nreq)
            for w in waits:
                self.metrics.wave_queue_wait.observe(w)
            self.metrics.waves_in_flight.inc()
        if self.recorder is not None:
            ev = {"trace": trace, "wave": wid, "wave_kind": kind,
                  "size": nreq, "jobs": len(jobs)}
            if wspan is not None:
                ev["span_id"] = wspan
            if gen:
                # mesh-GLOBAL coherence epoch this wave served under
                ev["gen"] = gen
            if slot is not None:
                # pipeline slot this launch occupies (0 = the oldest
                # in-flight wave) — correlates stalls with ring depth
                ev["slot"] = slot
            if tenant is not None:
                ev["tenant"] = tenant
            self.recorder.record("wave_launched", **ev)
        ph.end()
        return wid

    # ---- tenant event hints (ISSUE 11) ----------------------------------
    #
    # Wave/shed/degraded events carry a best-effort ``tenant`` field so
    # one tenant's incident filters server-side (/debug/events?tenant=).
    # Hints are the RAW key prefix (or the learned bucket for khash-only
    # lanes) — bounded-cardinality folding only applies to metric
    # labels, which go through the TenantLedger instead.

    def _hint_reqs(self, reqs) -> Optional[str]:
        if self.recorder is None or not reqs:
            return None
        ana = self.analytics
        if ana is None:
            return None
        return ana.tenant_hint(name=reqs[0].name)

    def _hint_khash(self, khash) -> Optional[str]:
        if self.recorder is None or len(khash) == 0:
            return None
        ana = self.analytics
        if ana is None:
            return None
        return ana.tenant_hint(khash=int(khash[0]))

    def _job_tenant(self, job) -> Optional[str]:
        reqs = getattr(job, "reqs", None)
        if reqs:
            return self._hint_reqs(reqs)
        kh = getattr(job, "khash", None)
        if kh is not None:
            return self._hint_khash(kh)
        return None

    # ---- per-phase attribution (ISSUE 4, ISSUE 24) ---------------------
    #
    # Each wave's duration partitions into three coarse phases, opened
    # and closed on the wave's own clock with SHARED boundary readings:
    # `pack` (wave begin → the launch returned; for unpipelined waves →
    # the engine call entered, engine lock held), `device` (→ results
    # on the host: IN-FLIGHT time, of which the device is busy a small
    # part) and `resolve` (→ wave end).  `pack` and `resolve` are each
    # one stretch of one thread, so they also record its CPU time: on
    # the chip the worker RUNS for a fifth of `pack`, the rest it
    # waits for the GIL.  So pack + device + resolve ==
    # wave_duration up to float rounding, on every engine — asserted
    # in tests/test_telemetry.py.  The fine phases (wave.*, lock.*,
    # tracing.PHASE_CATALOG) time what happens inside them.

    def _wave_mark(self, wid: int, nxt: phase) -> None:
        """A coarse boundary: close the wave's open coarse phase and
        open ``nxt`` on the same clock reading."""
        t = self._clock()
        with self._tel_mu:
            info = self._inflight.get(wid)
        if info is None:
            return
        cur = info["coarse"]
        info["phases"][cur.name] = cur.end(at=t,
                                           exemplar=self._exemplar())
        info["coarse"] = nxt.begin(at=t)

    def _new_scope(self) -> WaveScope:
        """The scope of the next wave; 1 in CPU_SAMPLE has its phases
        record thread CPU time too (a racy count: any 1 in ~16 will
        do)."""
        n = self._scope_seq = self._scope_seq + 1  # lock-free: sampling counter, a lost update skews nothing
        return WaveScope(self, cpu=n % self.CPU_SAMPLE == 0)

    def _exemplar(self):
        sr = self.span_recorder
        return sr.exemplar() if sr is not None else None

    @contextmanager
    def _engine_step(self, wid: int, scope: WaveScope):
        """One blocking engine call of wave ``wid`` under the engine
        lock: `lock.engine` times the wait for it, the coarse partition
        turns to `device` once it is held and to `resolve` when the
        call has returned."""
        wait = phase("lock.engine", self).begin()
        with self._engine_lock:
            wait.end()
            self._wave_mark(wid, phase("device", self, span=False))
            self._fault("device_step")
            yield
        self._wave_mark(wid, phase("resolve", self, cpu=scope.cpu,
                                   span=False))

    def _engine_check_packed(self, batch, khash, now_ms: int, mslot):
        """engine.check_packed with the mesh-slot column only when one
        exists: non-fused engines (oracle, store-backed) keep their
        3-arg signature."""
        if mslot is None:
            return self.engine.check_packed(batch, khash, now_ms)
        return self.engine.check_packed(batch, khash, now_ms,
                                        mslot=mslot)

    def observe_phase(self, name: str, seconds: float,
                      cpu: Optional[float] = None, exemplar=None) -> None:
        """The sink of ``tracing.phase`` on this dispatcher: one sample
        → histogram (+ the analytics ledger when attached;
        KeyAnalytics.observe_phase already feeds the same histogram, so
        don't double-observe).  ``exemplar`` links the bucket to a
        recent sampled trace (ISSUE 12)."""
        ana = self.analytics
        if ana is not None:
            ana.observe_phase(name, seconds, cpu, exemplar)
        elif self.metrics is not None:
            self.metrics.observe_phase(name, max(seconds, 0.0), cpu,
                                       exemplar)

    def _tap_packed(self, khash, hits, status) -> None:
        """Post-wave columnar tap (None-guarded, never raises into the
        serving path).  Fused engines already emitted the tap columns
        ON DEVICE inside the wave's program — the host-side copies
        here are exactly what the fusion deleted, so skip them."""
        if self._fused_tap:
            return
        ana = self.analytics
        if ana is not None:
            try:
                ana.tap_packed(khash, hits, status)
            except Exception:  # pragma: no cover - analytics only
                log.exception("analytics tap")

    def _tap_reqs(self, reqs, resps) -> None:
        ana = self.analytics
        if ana is not None:
            try:
                ana.tap_reqs(reqs, resps)
            except Exception:  # pragma: no cover - analytics only
                log.exception("analytics tap")

    def _wave_end(self, wid: int, error: Optional[BaseException] = None
                  ) -> None:
        t1 = self._clock()
        with self._tel_mu:
            info = self._inflight.pop(wid, None)
            if info is None:  # already ended (defensive)
                return
            dur = max(t1 - info["t0"], 0.0)
            self._wave_count += 1
            first = self._wave_count == 1
            if first:
                self._first_wave_s = dur
            self._recent_durs.append(dur)
            self._last_wave_end = t1
            was_stalled = info["stalled"]
            any_stalled = any(i["stalled"]
                              for i in self._inflight.values())
        # close the open coarse phase on the wave's own end reading —
        # off the _tel_mu lock, still before any caller resumes from
        # this wave
        ex = self._exemplar()
        phases = info["phases"]
        cur = info["coarse"]
        phases[cur.name] = (phases.get(cur.name, 0.0)
                            + cur.end(at=t1, exemplar=ex))
        if info["span"]:
            info["scope"].finish(self._wave_span_attrs(wid, info, error))
        if self.metrics is not None:
            from .metrics import observe_with_exemplar

            observe_with_exemplar(self.metrics.wave_duration, dur, ex)
            self.metrics.waves_in_flight.dec()
            if first:
                self.metrics.first_wave_duration.set(dur)
            if was_stalled and not any_stalled:
                self.metrics.dispatcher_stalled.set(0)
        if was_stalled:
            log.warning("dispatcher stall resolved: wave %d (%s, %d "
                        "reqs) completed after %.1fs%s", wid,
                        info["kind"], info["size"], dur,
                        " with error" if error is not None else "")
        if self.recorder is not None:
            from .telemetry import exc_text

            ev = {"trace": info["trace"], "wave": wid,
                  "wave_kind": info["kind"], "size": info["size"],
                  "duration_ms": round(dur * 1000, 3)}
            if info.get("span"):
                ev["span_id"] = info["span"]
            if info.get("gen"):
                ev["gen"] = info["gen"]
            if info.get("slot") is not None:
                ev["slot"] = info["slot"]
            if info.get("tenant") is not None:
                ev["tenant"] = info["tenant"]
            # per-phase breakdown in ms; sums to duration_ms
            ev["phases"] = {k: round(v * 1000, 3)
                            for k, v in phases.items()}
            if error is not None:
                self.recorder.record("wave_error", error=exc_text(error),
                                     **ev)
            else:
                self.recorder.record("wave_completed", **ev)
            if first:
                # the compile event: the first wave pays any compile
                # the warmup didn't cover (minutes on a cold TPU)
                self.recorder.record("first_wave", trace=info["trace"],
                                     duration_ms=round(dur * 1000, 3))

    def _wave_span_attrs(self, wid: int, info: dict, error) -> dict:
        """The wave's fan-in span attributes (ISSUE 12); the span
        itself is recorded by its WaveScope when the wave's last
        section on this thread ends, so its children — the wave.* /
        lock.* phases, each with the start and end it was read at — lie
        inside it."""
        attrs = {"wave": wid, "kind": info["kind"], "size": info["size"]}
        if info.get("gen"):
            attrs["gen"] = info["gen"]
        if info.get("slot") is not None:
            attrs["slot"] = info["slot"]
        if info.get("tenant") is not None:
            attrs["tenant"] = info["tenant"]
        if info.get("links"):
            attrs["links"] = ",".join(info["links"])
        if error is not None:
            from .telemetry import exc_text

            attrs["error"] = exc_text(error)
        return attrs

    def _watchdog_run(self) -> None:
        while not self._closing.wait(self._watch_interval_s):
            try:
                self._watchdog_poll()
            except Exception:  # pragma: no cover - must never die
                log.exception("dispatcher watchdog poll")

    def _watchdog_poll(self) -> bool:
        """One watchdog scan: flag waves in flight past the threshold.
        Separated from the thread loop so tests drive it with a fake
        clock (no real sleeps).  Returns True when a NEW stall was
        flagged this scan."""
        now = self._clock()
        newly = []
        with self._tel_mu:
            for wid, info in self._inflight.items():
                if (not info["stalled"]
                        and now - info["t0"] >= self._stall_threshold_s):
                    info["stalled"] = True
                    newly.append((wid, dict(info)))
            self._stall_count += len(newly)
            any_stalled = any(i["stalled"]
                              for i in self._inflight.values())
        if self.metrics is not None:
            self.metrics.dispatcher_stalled.set(1 if any_stalled else 0)
        for wid, info in newly:
            age = now - info["t0"]
            msg = (f"wave {wid} ({info['kind']}, {info['size']} reqs) in "
                   f"flight {age:.1f}s > stall threshold "
                   f"{self._stall_threshold_s:.1f}s — likely a cold "
                   f"device compile; callers time out at "
                   f"{self.RESULT_TIMEOUT_S:.0f}s "
                   f"(GUBER_RESULT_TIMEOUT_S)")
            log.warning("dispatcher stall: %s", msg)
            if self.metrics is not None:
                self.metrics.stall_event_counter.inc()
            if self.recorder is not None:
                self.recorder.record("wave_stalled", error=msg,
                                     trace=info["trace"], wave=wid,
                                     wave_kind=info["kind"],
                                     size=info["size"],
                                     age_s=round(age, 3))
        return bool(newly)

    def _result_timeout(self, e: BaseException) -> BaseException:
        """Build the caller-facing timeout with a wave diagnosis baked
        into the message — str() of a bare TimeoutError is EMPTY, which
        made the round-5 rows undiagnosable.  Same exception type, so
        existing handlers keep matching."""
        stats = self.debug_stats()
        msg = (f"dispatcher wave result timed out after "
               f"{self.RESULT_TIMEOUT_S:.0f}s (queue_depth="
               f"{stats['queue_depth']}, in_flight={stats['in_flight']}, "
               f"oldest_wave_age_s={stats['oldest_wave_age_s']}, "
               f"stalled={stats['stalled']}; cold TPU compiles add up "
               f"to minutes — raise GUBER_RESULT_TIMEOUT_S when callers "
               f"can arrive before warmup)")
        with self._tel_mu:
            self._timeout_count += 1
        if self.metrics is not None:
            self.metrics.wave_timeout_counter.inc()
        if self.recorder is not None:
            self.recorder.record("wave_timeout", error=msg)
        return type(e)(msg)

    def debug_stats(self) -> dict:
        """Cheap dispatcher state for /healthz?deep=1 and timeout
        diagnoses — no device work."""
        now = self._clock()
        with self._tel_mu:
            inflight = [dict(i) for i in self._inflight.values()]
            last_end = self._last_wave_end
            waves, stalls = self._wave_count, self._stall_count
            timeouts, first = self._timeout_count, self._first_wave_s
        oldest = max((now - i["t0"] for i in inflight), default=None)
        return {
            "queue_depth": self._queue.qsize(),
            "in_flight": len(inflight),
            "oldest_wave_age_s": (round(oldest, 3)
                                  if oldest is not None else None),
            "last_wave_age_s": (round(now - last_end, 3)
                                if last_end is not None else None),
            "stalled": any(i["stalled"] for i in inflight),
            "waves": waves,
            "stall_events": stalls,
            "timeouts": timeouts,
            "first_wave_s": (round(first, 3)
                             if first is not None else None),
            "stall_threshold_s": self._stall_threshold_s,
            "result_timeout_s": self.RESULT_TIMEOUT_S,
            # overlapped-pipeline shape: 0 when the pipeline is off
            # (CPU default / capability-less engine), else the depth-K
            # in-flight bound (GUBER_PIPELINE_DEPTH)
            "pipeline_depth": (self.pipeline_depth if self._pipelined
                               else 0),
            # overload admission control (ISSUE 5): ingress bound,
            # rows currently inside it, rows shed, drain state
            "admission": {"limit_rows": self.admission_limit,
                          # lock-free: healthz snapshot, staleness ok
                          "queued_rows": self._queued_rows,
                          "shed_rows": self._shed_rows,
                          "draining": self._draining,
                          "projected_wait_s": round(
                              self.projected_queue_wait_s(), 4)},
            "buffer_pool": (self.engine.wave_pool.stats()
                            if hasattr(self.engine, "wave_pool")
                            else None),
            # heavy-hitter tap shape (ISSUE 4): queue depth + drop
            # count — a saturated analytics worker sheds waves, it
            # never backs the serving path up
            "analytics": (self.analytics.stats()
                          if self.analytics is not None else None),
        }

    def telemetry_snapshot(self) -> dict:
        """debug_stats + recent-wave percentiles (bench.py folds this
        into each section's BENCH JSON row so perf rounds are
        self-diagnosing)."""
        import numpy as np

        with self._tel_mu:
            sizes = list(self._recent_sizes)
            durs = list(self._recent_durs)
            waits = list(self._recent_waits)

        def pct(xs, p, scale=1.0, nd=3):
            if not xs:
                return None
            return round(float(np.percentile(xs, p)) * scale, nd)

        snap = self.debug_stats()
        snap.update({
            "wave_size_p50": pct(sizes, 50),
            "wave_size_p99": pct(sizes, 99),
            "wave_duration_p50_ms": pct(durs, 50, 1e3),
            "wave_duration_p99_ms": pct(durs, 99, 1e3),
            "queue_wait_p50_ms": pct(waits, 50, 1e3),
            "queue_wait_p99_ms": pct(waits, 99, 1e3),
        })
        return snap

    # ---- the merge loop -------------------------------------------------

    def _dequeued(self, job) -> None:
        """Admission accounting: the job left the ingress queue (its
        rows now belong to a wave/carry, not the admission bound)."""
        with self._submit_mu:
            self._queued_rows -= _job_len(job)
            if self._queued_rows < 0:  # defensive
                self._queued_rows = 0

    def _drain_wave(self, block_s: float = 0.1) -> List[_Job]:
        """Block for one job (up to ``block_s``), then collect more for
        up to the coalescing window (GUBER_COALESCE_US, bounded by
        max_wave total requests) so bursty concurrent callers share the
        next device launch.  Jobs already queued are taken greedily
        FIRST: when the backlog alone fills the default cap (MAX_WAVE
        rows; max_wave itself where that is smaller), the wave launches
        with NO coalescing wait at all — the window exists to catch
        stragglers, not to tax a saturated queue.

        `worker.wait` is the block for the first job, `worker.coalesce`
        everything from there to the return: with the wave.* phases
        and `worker.gap` (what passed between the worker's phases since
        the last wave: glue and, mostly, waiting to get the GIL back)
        they partition the dispatch worker's wall time."""
        gap = take_gap()
        if gap:
            # a sum handed over, not a section timed here: its
            # intervals are annotated where they happen (phase.end)
            self.observe_phase("worker.gap", gap)
        if self._carry is not None:
            first, self._carry = self._carry, None
            co = phase("worker.coalesce", self, span=False).begin()
        else:
            wt = phase("worker.wait", self, span=False).begin()
            try:
                first = (self._queue.get(timeout=block_s) if block_s > 0
                         else self._queue.get_nowait())
            except queue.Empty:
                wt.end()
                return []
            wt.end()
            co = phase("worker.coalesce", self, span=False).begin(
                at=wt.t1)
            self._dequeued(first)
        try:
            return self._coalesce(first)
        finally:
            co.end()

    def _coalesce(self, first) -> List[_Job]:
        wave = [first]
        total = _job_len(first)
        deadline = None  # armed only after the backlog is drained
        while total < self.max_wave:
            try:
                job = self._queue.get_nowait()
                self._dequeued(job)
            except queue.Empty:
                if self.max_delay_s <= 0 or total >= self.MAX_WAVE:
                    # the window catches a SMALL wave's stragglers; a
                    # wave that fills the default cap had a backlog and
                    # launches at once, as it did when that cap cut it:
                    # the timed get gives the GIL up, and winning it
                    # back from ~30 handlers cost a saturated worker
                    # ~1 ms a wave (PERF.md §6, PR 49)
                    break
                if deadline is None:
                    deadline = time.monotonic() + self.max_delay_s
                remain = deadline - time.monotonic()
                if remain <= 0:
                    break
                try:
                    job = self._queue.get(timeout=remain)
                    self._dequeued(job)
                except queue.Empty:
                    break
            if total + _job_len(job) > self.max_wave:
                # never overshoot max_wave: an oversized wave splits
                # into one dense launch + a sparse tail launch at the
                # small bucket — the tail's fixed per-launch cost is
                # pure waste.  The job that would overflow leads the
                # NEXT wave instead.
                self._carry = job
                try:
                    # racer preemption point: delay parks the carried
                    # job across the wave boundary; error drops it
                    # (future failed, never launched)
                    self._fault("dispatch_carry")
                except Exception as e:  # noqa: BLE001 - injected only
                    self._carry = None
                    if not job.future.done():
                        job.future.set_exception(e)
                break
            wave.append(job)
            total += _job_len(job)
        if wave:
            try:
                # racer preemption point: a delay here widens the window
                # between collecting this wave and launching it, so
                # concurrent lanes land in the NEXT wave/engine call
                self._fault("dispatch_merge")
            except Exception as e:  # noqa: BLE001 - injected only
                for j in wave:
                    if not j.future.done():
                        j.future.set_exception(e)
                return []
        return wave

    def _run(self) -> None:
        # Overlapped wave pipeline (depth K = pipeline_depth,
        # GUBER_PIPELINE_DEPTH) for pure-packed waves: while up to K
        # launched waves are in flight on the device, the worker drains
        # and JOINS the next wave into a pooled upload buffer
        # (core/batch.py › WaveBufferPool via engine.join_calls) —
        # steady-state throughput becomes max(host, device) instead of
        # host + device.  Launches are ordered by the state threading
        # device-side, so correctness does not depend on when results
        # are read; completion resolves strictly oldest-first (the
        # in-flight ring is FIFO), preserving per-job splice order.
        # Mixed/list waves flush the pipeline first (bounded caller
        # latency).
        from collections import deque

        from .tracing import partition_thread

        # this thread waits for work or works on a wave, nothing else:
        # its phases (worker.*, wave.*, lock.*) partition its wall time
        partition_thread()
        pipelined = self._pipelined
        depth = self.pipeline_depth
        pending: deque = deque()  # [(jobs, token)] launched, unsynced

        def flush_pending() -> None:
            while pending:
                self._sync_and_resolve(*pending.popleft())

        while not (self._closing.is_set() and self._queue.empty()
                   and self._carry is None):
            wave = self._drain_wave(block_s=0.0 if pending else 0.1)
            if not wave:
                flush_pending()
                continue
            if pipelined and all(isinstance(j, _PackedJob) for j in wave):
                launched = self._launch_packed_jobs(wave,
                                                    slot=len(pending))
                if launched is not None:
                    pending.append(launched)
                    while len(pending) >= depth:
                        self._sync_and_resolve(*pending.popleft())
                continue
            flush_pending()
            # Packed jobs carry per-request arrival times in their `now`
            # column, so they ALL merge into one launch regardless of
            # wall-clock skew between callers — the device honors each
            # request's own time.  List jobs still group by timestamp
            # (pack_requests bakes one now per job, incl. Gregorian
            # period ends).  Execution units run in ascending-now order
            # so a list job never applies BEHIND a packed launch that
            # already advanced a shared key's clock (the step clamps
            # per-key time as the final defense).
            packed = [j for j in wave if isinstance(j, _PackedJob)]
            by_now: dict = {}
            for j in wave:
                if isinstance(j, _Job):
                    by_now.setdefault(j.now_ms, []).append(j)
            units = [(now, "list", jobs) for now, jobs in by_now.items()]
            if packed:
                units.append((min(j.now_ms for j in packed), "packed",
                              packed))
            if (len(units) > 1 and by_now
                    and hasattr(self.engine, "check_packed")):
                # several instants in one wave: pack each list job at
                # its own now and merge EVERYTHING into the packed
                # launch — per-request time makes quantization
                # unnecessary (single-unit waves keep the object lane's
                # zero-repack path)
                try:
                    self._run_merged_wave(wave)
                    continue
                except Exception as e:  # noqa: BLE001
                    for j in wave:
                        if not j.future.done():
                            j.future.set_exception(e)
                    continue
            for now, kind, jobs in sorted(units, key=lambda u: u[0]):
                if kind == "list":
                    self._run_list_jobs(jobs, now)
                else:
                    self._run_packed_jobs(jobs)
        # closing: resolve anything still in flight
        while pending:
            self._sync_and_resolve(*pending.popleft())

    def _launch_packed_jobs(self, jobs, slot: Optional[int] = None):
        """Concat + LAUNCH a pure-packed wave; returns (jobs, token,
        wave_id, batch, khash, scope) for the sync phase, or None when
        dispatch failed (futures already resolved with the error).  The
        wave stays "in flight" (watchdog-visible) from launch until its
        sync resolves; ``slot`` is its position in the in-flight ring
        at launch."""
        with self._new_scope() as scope:
            wid = self._wave_begin(scope, "packed_pipelined", jobs,
                                   slot=slot)
            batch = None
            try:
                self._fault("dispatch_launch")
                batch, khash, mslot, now = self._concat_jobs(jobs)
                wait = phase("lock.engine", self).begin()
                with self._engine_lock:
                    wait.end()
                    self._fault("device_step")
                    token = (self.engine.launch_packed(batch, khash, now)
                             if mslot is None
                             else self.engine.launch_packed(
                                 batch, khash, now, mslot=mslot))
                # the launch's host-side routing/fill IS pack work; the
                # wave is in flight from here until sync_packed returns
                self._wave_mark(wid, phase("device", self, span=False))
                return (jobs, token, wid, batch, khash, scope)
            except Exception as e:  # noqa: BLE001 - surfaced per-caller
                _release_wave(batch)  # no token took the wave's lease
                self._wave_end(wid, error=e)
                for j in jobs:
                    if not j.future.done():
                        j.future.set_exception(e)
                return None

    def _concat_jobs(self, jobs) -> tuple:
        """(batch, khash, mslot, now) of a pure-packed wave's jobs:
        their blocks joined into one wave — O(jobs) work, no pass per
        column; where the engine can, straight into the upload pair
        (the batch is then views of a lease: ``_release_wave``).
        ``jobs`` is put IN PLACE into the order the wave holds them in.
        The scalar now only backstops sweeps/padding; requests use
        their own now column.  max() keeps sweep time monotonic."""
        from .core.batch import clock_order

        with phase("wave.concat", self):
            # the jobs in the order of their clocks, where whole jobs
            # can be: a wave's rows apply in arrival-time order whatever
            # the queue's (callers' stamps cross on their way in), and
            # blocks already in that order need no sort of their rows
            order = clock_order([j.rows for j in jobs])
            if order is not None:
                jobs[:] = [jobs[i] for i in order]
            wave, khash, mslot = self._join(
                [j.rows for j in jobs], [j.khash for j in jobs],
                [j.mslot for j in jobs])
            return (wave.batch, khash, mslot,
                    max(j.now_ms for j in jobs))

    def _resolve_views(self, jobs, cols) -> None:
        """Resolve each packed job's future with its row bounds into
        the wave's shared result columns — a view, NOT materialized
        slices: response build runs in each caller's own thread
        (ResultView)."""
        with phase("wave.resolve", self):
            a = 0
            for j in jobs:
                b = a + len(j.khash)
                j.future.set_result(ResultView(cols, a, b))
                a = b

    def _sync_and_resolve(self, jobs, token, wid, batch, khash,
                          scope) -> None:
        with scope:
            try:
                self._fault("dispatch_sync")
                cols = self.engine.sync_packed(
                    token, engine_lock=self._engine_lock)
                self._wave_mark(wid, phase("resolve", self,
                                           cpu=scope.cpu, span=False))
                # racer preemption point: hold the result splice while
                # later waves launch (callers still waiting on their
                # views)
                self._fault("dispatch_splice")
                self._resolve_views(jobs, cols)
                with phase("wave.end", self):
                    self._wave_end(wid)
                    self._tap_packed(khash, batch.hits, cols[0])
            except Exception as e:  # noqa: BLE001 - surfaced per-caller
                self._wave_end(wid, error=e)
                for j in jobs:
                    if not j.future.done():
                        j.future.set_exception(e)
            finally:
                # the token is dead: its pooled upload buffers — which
                # ``batch`` may be views of — go back only now, after
                # the sync's re-dispatches and the tap read them
                self.engine.drop_packed(token)

    def _run_merged_wave(self, wave) -> None:
        """Cross-time merge of a mixed wave: every list job is packed at
        its own now (Gregorian period ends are per-instant), its rows
        join the packed jobs' blocks, and ONE launch serves all — the
        device applies each key's requests in arrival-time order."""
        from .parallel.sharded import responses_from_columns

        with self._new_scope() as scope:
            wid = self._wave_begin(scope, "merged", wave)
            batch = None
            try:
                with phase("wave.concat", self):
                    parts, batch, khash, mslot = self._merge_parts(wave)
                now = max(j.now_ms for j in wave)
                with self._engine_step(wid, scope):
                    st, lim, rem, rst, full = self._engine_check_packed(
                        batch, khash, now, mslot)
                self._fault("dispatch_splice")
                cols = (st, lim, rem, rst, full)
                with phase("wave.resolve", self):
                    a = 0
                    for j, kh, errs in parts:
                        b_ = a + len(kh)
                        if isinstance(j, _PackedJob):
                            j.future.set_result(ResultView(cols, a, b_))
                        else:
                            j.future.set_result(responses_from_columns(
                                (st[a:b_], lim[a:b_], rem[a:b_], rst[a:b_],
                                 full[a:b_]), errs))
                        a = b_
                with phase("wave.end", self):
                    self._wave_end(wid)
                    self._tap_packed(khash, batch.hits, st)
            except Exception as e:  # noqa: BLE001 - caller fails the futures
                self._wave_end(wid, error=e)
                raise
            finally:
                _release_wave(batch)

    def _merge_parts(self, wave) -> tuple:
        """([(job, khash, errs or None)], batch, khash, mslot) of a
        mixed wave: object jobs are packed and laid out here, on the
        worker (they carry no block), and joined with the packed jobs'
        as ``_concat_jobs`` joins those."""
        from .core.batch import pack_requests
        from .hashing import hash_request_keys

        parts, calls = [], []
        for j in wave:
            if isinstance(j, _PackedJob):
                parts.append((j, j.khash, None))
                calls.append(j.rows)
            else:
                kh = hash_request_keys([r.name for r in j.reqs],
                                       [r.unique_key for r in j.reqs])
                b, errs = pack_requests(j.reqs, j.now_ms,
                                        size=len(j.reqs), key_hashes=kh)
                parts.append((j, kh, errs))
                calls.append(self.lay_out(b, kh, None))
        rows, khash, mslot = self._join(
            calls, [p[1] for p in parts],
            [getattr(j, "mslot", None) for j in wave])
        return parts, rows.batch, khash, mslot

    def _run_list_jobs(self, jobs, now) -> None:
        if not jobs:
            return
        merged: List[RateLimitRequest] = []
        slices: List[Tuple[_Job, int, int]] = []
        for j in jobs:
            start = len(merged)
            merged.extend(j.reqs)
            slices.append((j, start, len(merged)))
        with self._new_scope() as scope:
            wid = self._wave_begin(scope, "list", jobs)
            try:
                self._fault("dispatch_launch")
                with self._engine_step(wid, scope):
                    resps = self.engine.check_batch(merged, now)
                self._fault("dispatch_splice")
                with phase("wave.resolve", self):
                    for j, a, b in slices:
                        j.future.set_result(resps[a:b])
                with phase("wave.end", self):
                    self._wave_end(wid)
                    self._tap_reqs(merged, resps)
            except Exception as e:  # noqa: BLE001 - surfaced per-caller
                self._wave_end(wid, error=e)
                for j, _, _ in slices:
                    if not j.future.done():
                        j.future.set_exception(e)

    def _run_packed_jobs(self, jobs) -> None:
        if not jobs:
            return
        with self._new_scope() as scope:
            wid = self._wave_begin(scope, "packed", jobs)
            batch = None
            try:
                batch, khash, mslot, now = self._concat_jobs(jobs)
                self._fault("dispatch_launch")
                with self._engine_step(wid, scope):
                    cols = self._engine_check_packed(batch, khash, now,
                                                     mslot)
                self._fault("dispatch_splice")
                self._resolve_views(jobs, cols)
                with phase("wave.end", self):
                    self._wave_end(wid)
                    self._tap_packed(khash, batch.hits, cols[0])
            except Exception as e:  # noqa: BLE001 - surfaced per-caller
                self._wave_end(wid, error=e)
                for j in jobs:
                    if not j.future.done():
                        j.future.set_exception(e)
            finally:
                _release_wave(batch)

    def close(self) -> None:
        with self._submit_mu:
            self._closing.set()
        self._thread.join(timeout=10)
        if self._watchdog is not None:
            self._watchdog.join(timeout=5)
        while True:
            try:
                job = self._queue.get_nowait()
                job.future.set_exception(RuntimeError("dispatcher closed"))
            except queue.Empty:
                break
