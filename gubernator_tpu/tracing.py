"""Tracing/profiling hooks (SURVEY.md §5.1).

The reference grew OpenTelemetry spans around handlers (otelgrpc
interceptors in daemon.go, span-per-request in gubernator.go —
version-dependent).  Here:

- ``phase(name)`` is the ONE way to time a section of the program
  (ISSUE 24): a ``jax.profiler.TraceAnnotation`` for its duration (so
  the section lands in any profile of the process, on the device
  trace's clock), a sample in ``gubernator_phase_duration{phase}`` and
  the PhaseLedger (``/debug/phases``), and — inside a recorded trace —
  a span with the real start and end.  ``span(name)`` is the same
  primitive for the handler entries (``gubernator_func_duration``).
- ``SpanRecorder`` (ISSUE 12) keeps the structure: when a request
  context is armed with a recorder, every ``phase()``/``span()`` — and
  the dispatcher's wave spans — lands in a bounded per-daemon ring,
  head-sampled at ``GUBER_TRACE_SAMPLE`` with forced sampling on
  error/degraded/shed outcomes.  ``GET /debug/traces`` exports the
  ring; ``assemble()``/``render_waterfall()`` stitch per-daemon
  slices into a cluster-wide tree (tools/trace_assemble.py).
- ``device_profile(...)`` captures a jax.profiler trace of the device
  step (the TPU-side profiling story: view in TensorBoard/XProf).

Enable device profiling with GUBER_PROFILE_DIR=/path (daemon reads it).
"""
from __future__ import annotations

import contextlib
import logging
import os
import time
from collections import OrderedDict, deque
from fnmatch import fnmatchcase
from typing import Dict, Iterator, List, Optional

log = logging.getLogger("gubernator_tpu.tracing")

# --- W3C trace-context propagation (traceparent in/out) ---------------
#
# The reference wires otelgrpc server/client interceptors (daemon.go),
# which propagate the W3C `traceparent` header across hops.  The OTEL
# SDK isn't required for that contract: the header format is a spec
# ("00-<32hex trace-id>-<16hex parent-span-id>-<2hex flags>"), so we
# parse/generate it natively and carry the active trace in a
# thread-local — servicers adopt the inbound header, and every peer
# call (pb2 or raw wire) re-emits it with a fresh span id.

import secrets
import threading



class _ThreadState(threading.local):
    """Per-thread tracing state.  The defaults live on the class, so a
    read on a thread that never set one is a plain attribute hit (a
    miss on a bare ``threading.local`` costs an exception: ~0.5 µs, and
    ``phase`` reads five)."""

    trace = None    # (trace_id, flags) of the active request
    span = None     # _SpanState of the active request
    wave = None     # enclosing WaveScope
    cursor = None   # partition_thread(): end of the last phase
    gap = 0.0       # partition_thread(): seconds between phases
    gap_ann = None  # partition_thread(): open "worker.gap" annotation
    door_at = None      # DoorPool: submit() of the task this thread runs
    door_run_at = None  # DoorPool: when that task started on this thread


_tls = _ThreadState()

#: Test/diagnostic hook: called with the RAW inbound traceparent header
#: (or None) each time a request context is adopted.
inbound_hook = None


def parse_traceparent(header: Optional[str]):
    """(trace_id_hex32, flags_hex2) or None for absent/malformed input
    (malformed → start a new trace, per the W3C spec's restart rule)."""
    if not header:
        return None
    parts = header.strip().split("-")
    if len(parts) != 4 or parts[0] != "00":
        return None
    tid, sid, flags = parts[1].lower(), parts[2].lower(), parts[3]
    if len(tid) != 32 or len(sid) != 16 or len(flags) != 2 \
            or tid == "0" * 32 or sid == "0" * 16:
        return None
    try:
        int(tid, 16), int(sid, 16), int(flags, 16)
    except ValueError:
        return None
    return tid, flags


def parent_span_id(header: Optional[str]) -> Optional[str]:
    """The 16-hex parent-span-id of a valid traceparent, or None.
    ``parse_traceparent`` deliberately discards it (the trace context
    is (trace_id, flags)); the span plane needs it back so an inbound
    request's first span parents under the caller's hop span."""
    if parse_traceparent(header) is None:
        return None
    return header.strip().split("-")[2].lower()


def current_trace_id() -> Optional[str]:
    """The active request's 32-hex trace id, or None outside any
    request context.  Cheap enough for hot-path capture (the flight
    recorder and dispatcher jobs stamp it at submit time — worker
    threads have no request context of their own)."""
    tp = _tls.trace
    return tp[0] if tp is not None else None


def current_traceparent() -> Optional[str]:
    """Outbound header for the active request's trace (fresh span id
    per hop), or None outside any request context."""
    tp = _tls.trace
    if tp is None:
        return None
    tid, flags = tp
    return f"00-{tid}-{secrets.token_hex(8)}-{flags}"


# --- span plane (ISSUE 12) --------------------------------------------
#
# ``span()`` historically measured durations into histograms and threw
# the structure away.  The SpanRecorder keeps it: completed spans
# (trace_id/span_id/parent_id, name, start/end, attrs) buffer per-trace
# while the request runs, then commit as a unit — head-sampled by a
# DETERMINISTIC function of the trace id so every daemon in a cluster
# keeps (or drops) the same traces and cross-daemon assembly always
# sees whole traces, with forced sampling on error/degraded/shed
# outcomes so the interesting requests survive even at sample=0.

#: span-name catalog (linted against OBSERVABILITY.md by
#: tools/check_metrics.py, like slo.SLO_CATALOG).  A phase() recorded
#: inside a trace is a span under its PHASE_CATALOG name; the names
#: here are the spans that are not phases.
SPAN_CATALOG: Dict[str, str] = {
    "grpc.GetRateLimits": "public V1 handler (pb2 and raw-wire twins)",
    "grpc.GetPeerRateLimits": "owner-side peer handler (pb2 and wire)",
    "grpc.UpdatePeerGlobals": "owner→replica GLOBAL broadcast handler",
    "http.GetRateLimits": "HTTP/JSON gateway handler",
    "peer.forward": "caller-side hop: batched forward lane send",
    "global.hits_flush": "async GLOBAL hit-flush tick (owner-bound)",
    "global.broadcast": "async GLOBAL broadcast tick (replica-bound)",
    "wave": "one dispatcher wave (fan-in over the batched jobs); its "
            "children are the wave.* / lock.* phases that ran for it",
}

#: phase-name catalog: every name the program hands to ``phase()``
#: (linted both ways against the code's literals and OBSERVABILITY.md's
#: phase table by tools/guberlint/docs.py).  name → site.
PHASE_CATALOG: Dict[str, str] = {
    # coarse in-wave partition of gubernator_dispatcher_wave_duration
    "pack": "dispatcher: wave begin → launch returned (engine call "
            "entered, for unpipelined waves)",
    "device": "dispatcher: launch returned → results on the host; "
              "IN-FLIGHT time, not device-busy time",
    "resolve": "dispatcher: results on the host → wave end",
    "queue_wait": "dispatcher: a job's wait from submit to its wave",
    # the dispatch worker's wall time, partitioned (it runs every
    # wave; serial waves record the same wave.*/lock.* names)
    "worker.wait": "_drain_wave: blocked on an empty queue",
    "worker.coalesce": "_drain_wave: first job → wave returned",
    "worker.gap": "the dispatch worker BETWEEN two of its phases: "
                  "glue, and waiting to get the GIL back",
    "wave.begin": "_wave_begin: telemetry, per-job waits, event",
    "wave.concat": "the jobs' blocks put in clock order and joined into "
                   "the wave (into its upload lease where the route is "
                   "the identity)",
    "lock.engine": "waiting to acquire the engine lock",
    "wave.route": "engine: tier mask, leaky rows counted; where the "
                  "wave is not in its lease yet: arrival order and the "
                  "plan of its device waves by shard (one C++ pass, "
                  "route_plan; without the extension _build_waves)",
    "wave.fill": "engine: a device wave's rows into the leased upload "
                 "buffers (one C++ pass that writes every cell, "
                 "route_fill; without the extension _fill scatters "
                 "them; identity route: marks invalid rows)",
    "lock.xla_exec": "engine: waiting to acquire XLA_EXEC_MU",
    "lock.mesh_state": "mesh-GLOBAL tier: a fused launch waiting for "
                       "the tier's state lock (fold tick, pins)",
    "wave.dispatch": "engine: device_puts + the jit call, until it "
                     "returns",
    "wave.sync": "engine: _finish_wave, blocked on the device and the "
                 "download",
    "wave.scatter": "engine: result scatter into request order "
                    "(+ retry / cold rows / out-of-domain merge)",
    "tier.premask": "engine, inside wave.route: the cold tier's "
                    "membership read of the wave's keys (resident_mask, "
                    "one C++ pass under the tier's lock), so that "
                    "cold-resident rows ride the wave invalid; only "
                    "with GUBER_TIER_COLD=1",
    "tier.resolve": "tiering.resolve, inside wave.scatter: the cold "
                    "lane of a wave that has cold rows — each applied "
                    "to the host store (one C++ pass over the native "
                    "store), then the admission of the keys served "
                    "(tier.migrate inside)",
    "tier.migrate": "tiering.migrate, inside tier.resolve: ONE "
                    "migration pass — all the keys a wave's admission "
                    "handed over (at most MIGRATE_MAX): their cold rows "
                    "read, their device buckets fetched (tier.fetch), "
                    "promotees placed and victims picked and taken out "
                    "on that host image, the victims put cold, the "
                    "image written back (tier.write), the promotees "
                    "dropped from the cold store; one sample a pass",
    "tier.fetch": "pallas_engine._BucketImage, inside tier.migrate: "
                  "the pass's distinct buckets gathered device → host "
                  "at a padded length, ONE blocking round trip that "
                  "queues behind whatever wave is already launched",
    "tier.write": "pallas_engine._BucketImage.commit, inside "
                  "tier.migrate: the changed image scattered host → "
                  "device, once (the dispatch returns; the next launch "
                  "orders after it)",
    "wave.resolve": "dispatcher: the future.set_result loop",
    "wave.end": "_wave_end + the analytics tap",
    # handler threads, per call
    "door.wait": "front door: grpcio's submit of a call to the handler "
                 "pool (on the _serve thread) → its task starts on a "
                 "pool thread: the pool's queue, the thread's wake-up, "
                 "winning the GIL (DoorPool; 1 call in 8, every call in "
                 "mesh-GLOBAL mode)",
    "door.recv": "front door: the task's start → the servicer's first "
                 "line: grpcio waiting for the request message (the "
                 "_serve loop reaching that event under the GIL) and "
                 "its prelude; shares door.wait's end reading",
    "handler": "instance.get_rate_limits_wire, whole, wall and CPU "
               "(1 call in 8; every call in mesh-GLOBAL mode)",
    "ingest": "wire parse / fused prepack (bytes → columns)",
    "build": "response wire-byte serialization",
    "call.wait": "handler blocked on its wave's future (queue wait + "
                 "wave, from the caller's side)",
    "local.pack": "_wire_check_columns: a call the fused C++ ingest "
                  "declined (MULTI_REGION rows, a calendar row of an "
                  "invalid ordinal, more rows "
                  "than the largest bucket, GLOBAL rows on the peer "
                  "wire, no extension; which, and how often: "
                  "gubernator_wire_fused_declined{reason}, counted at "
                  "instance.py › _count_fused_declined) hashed, packed "
                  "and laid out in "
                  "numpy in its own thread (mix64 + pack_columns + "
                  "lay_out), before it is queued; wall and CPU.  A "
                  "LOCAL call, plain or calendar, never enters it, on "
                  "any mesh",
    "pack.calendar": "pack_columns: the period ends of a call's "
                     "DURATION_IS_GREGORIAN rows, one a distinct "
                     "(ordinal, clock) pair, each on the clock its row "
                     "is applied at (gregorian.py); inside local.pack "
                     "or route.pack, wall and CPU, sampled as they are; "
                     "a call without such rows never enters it, nor one "
                     "the fused C++ ingest serves (its pass does the "
                     "calendar itself: _native.cpp › Period)",
    "route.pack": "_wire_mesh_runner: mix64 + pack_columns + masks",
    "route.keys": "_wire_mesh_runner: the call's mesh rows grouped by "
                  "key in one dict pass: one config per key, pinned "
                  "configs matched (_group_key_configs)",
    "route.pin": "_wire_mesh_runner: pin_many + seed commit / admit",
    "route.slots": "_wire_mesh_runner: slot-map copy + one lookup a "
                   "row into the mslot column",
    # background
    "analytics.learn": "key-analytics thread: one drain window's tenant "
                       "learn items merged into the khash → bucket "
                       "table (wall and CPU)",
    "peer_flush": "peer send lanes: forward-hop flush round trip",
    "broadcast": "GLOBAL owner tick: one broadcast pass",
    "snapshot": "Loader save blackout",
    "restore": "Loader load blackout",
    "restore.place": "engine.restore: the snapshot's rows placed into "
                     "the host copy of the table (ShardedEngine: numpy "
                     "rounds over the column table; PallasServingEngine: "
                     "the table's download and a bucket's free slots to "
                     "its rows in snapshot order), before the upload",
    "restore.adopt": "tiering.adopt_rows: the rows a restore found no "
                     "device slot for, into the host cold tier in one "
                     "batch put",
    "sweep": "engine.sweep, whole: the expiry pass over the table, "
             "between waves under the engine lock (_maybe_sweep: the "
             "tick, or a table_full row's request); host wall time, "
             "queue behind the waves in flight included",
    "global_fold": "mesh-GLOBAL reconcile tick (swap + fold launch)",
}


class SpanRecorder:
    """Bounded, lock-aware ring of completed spans (ISSUE 12).

    Spans ``add()``ed while a request runs buffer per-trace; the
    request context's exit ``commit()``s the whole trace — into the
    ring when head-sampled or forced, dropped otherwise.  A bounded
    tombstone map remembers recent commit decisions so late adds from
    pipelined wave workers (future resolved before ``_wave_end`` ran)
    still route correctly.  All state is O(bounded); the lock is a
    leaf (never held while calling out)."""

    PENDING_TRACES = 128   # distinct in-flight traces buffered
    PENDING_SPANS = 64     # spans buffered per trace
    TOMBSTONES = 256       # remembered commit decisions

    def __init__(self, capacity: int = 2048, sample: float = 0.0):
        if capacity < 1:
            raise ValueError("span recorder capacity must be >= 1")
        self.capacity = capacity
        #: head-sampling rate in [0,1]; plain attr, racy reads are fine
        self.sample = float(sample)
        self._mu = threading.Lock()
        self._ring: deque = deque(maxlen=capacity)  # guarded-by: self._mu
        self._pending: OrderedDict = OrderedDict()  # guarded-by: self._mu
        self._done: OrderedDict = OrderedDict()  # guarded-by: self._mu
        self._last_sampled: Optional[str] = None  # guarded-by: self._mu
        self._dropped = 0  # guarded-by: self._mu

    def head_sampled(self, trace_id: str) -> bool:
        """Deterministic head-sampling decision: a pure function of the
        trace id, so every daemon in the cluster keeps the same traces
        (cluster-wide assembly never sees half a trace)."""
        rate = self.sample
        if rate <= 0.0:
            return False
        if rate >= 1.0:
            return True
        try:
            return int(trace_id[:8], 16) / 4294967296.0 < rate
        except (ValueError, TypeError):
            return False

    def add(self, span_dict: dict) -> None:
        """Buffer one completed span under its trace (bounded).  After
        the trace committed, route by the remembered decision."""
        tid = span_dict.get("trace_id")
        if not tid:
            return
        with self._mu:
            if tid in self._done:
                if self._done[tid]:
                    self._ring.append(span_dict)
                else:
                    self._dropped += 1
                return
            buf = self._pending.get(tid)
            if buf is None:
                while len(self._pending) >= self.PENDING_TRACES:
                    self._pending.popitem(last=False)
                    self._dropped += 1
                buf = self._pending[tid] = []
            if len(buf) < self.PENDING_SPANS:
                buf.append(span_dict)
            else:
                self._dropped += 1

    def commit(self, trace_id: str, forced=None) -> bool:
        """Resolve a trace's buffered spans: keep when forced or
        head-sampled, drop otherwise.  Returns the decision."""
        sampled = bool(forced) or self.head_sampled(trace_id)
        with self._mu:
            buf = self._pending.pop(trace_id, None)
            self._done[trace_id] = sampled
            while len(self._done) > self.TOMBSTONES:
                self._done.popitem(last=False)
            if sampled:
                if buf:
                    self._ring.extend(buf)
                self._last_sampled = trace_id
            elif buf:
                self._dropped += len(buf)
        return sampled

    def discard(self, trace_id: str) -> None:
        """Drop a trace's buffered spans without a tombstone."""
        with self._mu:
            self._pending.pop(trace_id, None)

    def exemplar(self) -> Optional[dict]:
        """The most recently committed SAMPLED trace, as a prometheus
        exemplar label dict — the histogram/SLO link from a burning
        signal to one concrete trace."""
        with self._mu:
            tid = self._last_sampled
        return {"trace_id": tid} if tid else None

    def spans(self, trace_id: Optional[str] = None,
              limit: Optional[int] = None) -> List[dict]:
        """Chronological snapshot of committed spans (oldest first);
        ``trace_id`` filters server-side, ``limit`` keeps the newest N."""
        with self._mu:
            out = list(self._ring)
        if trace_id:
            out = [s for s in out if s.get("trace_id") == trace_id]
        if limit is not None and limit >= 0:
            out = out[len(out) - min(limit, len(out)):]
        return out

    def stats(self) -> dict:
        with self._mu:
            return {"spans": len(self._ring), "capacity": self.capacity,
                    "sample": self.sample, "pending": len(self._pending),
                    "dropped": self._dropped}

    def __len__(self) -> int:
        with self._mu:
            return len(self._ring)


class _SpanState:
    """Per-request span bookkeeping (thread-local): the recorder, the
    open-span stack, the inbound parent id, the head-sampling decision
    (a pure function of the trace id) and the forced-sample verdict."""

    __slots__ = ("recorder", "trace_id", "parent", "stack", "sampled",
                 "forced")

    def __init__(self, recorder, trace_id, parent):
        self.recorder = recorder
        self.trace_id = trace_id
        self.parent = parent
        self.stack: List[str] = []
        self.sampled = recorder.head_sampled(trace_id)
        self.forced: Optional[str] = None


def new_span_id() -> str:
    return secrets.token_hex(8)


def current_span_id() -> Optional[str]:
    """The innermost open recorded span's id (the wave's parent when
    launched from a request thread), or None when the span plane is
    not armed here."""
    st = _tls.span
    if st is None:
        return None
    return st.stack[-1] if st.stack else st.parent


def force_sample(reason: str) -> None:
    """Flag the active trace for forced sampling (error / degraded /
    shed outcomes must survive even at sample=0).  First reason wins."""
    st = _tls.span
    if st is not None and st.forced is None:
        st.forced = reason


def hop_traceparent(name: str, attrs: Optional[dict] = None
                    ) -> Optional[str]:
    """Mint an outbound traceparent AND record the caller-side hop as
    an instant span whose span id IS the minted parent id — the
    receiving daemon's request span then parents under it, stitching
    owner-side work back to this request (ISSUE 12)."""
    tp = _tls.trace
    if tp is None:
        return None
    tid, flags = tp
    sid = secrets.token_hex(8)
    st = _tls.span
    if st is not None and st.trace_id == tid:
        now = time.time()  # clock-ok: telemetry wall clock (span timestamps)
        st.recorder.add({
            "trace_id": tid, "span_id": sid,
            "parent_id": st.stack[-1] if st.stack else st.parent,
            "name": name, "start": now, "end": now,
            "attrs": dict(attrs) if attrs else {}})
    return f"00-{tid}-{sid}-{flags}"


@contextlib.contextmanager
def request_context(traceparent: Optional[str],
                    recorder: Optional[SpanRecorder] = None
                    ) -> Iterator[None]:
    """Adopt an inbound traceparent — or start a new trace — for the
    handler's duration; peer calls made inside propagate the same
    trace id (otelgrpc server-interceptor parity).  With ``recorder``
    the span plane arms: ``span()`` records, and exit commits the
    trace (head-sampled / forced)."""
    if inbound_hook is not None:
        inbound_hook(traceparent)
    parsed = parse_traceparent(traceparent)
    prev = _tls.trace
    _tls.trace = parsed or (secrets.token_hex(16), "01")
    st = prev_st = None
    if recorder is not None:
        prev_st = _tls.span
        st = _SpanState(recorder, _tls.trace[0],
                        parent_span_id(traceparent))
        _tls.span = st
    try:
        yield
    finally:
        _tls.trace = prev
        if st is not None:
            _tls.span = prev_st
            st.recorder.commit(st.trace_id, forced=st.forced)


def grpc_request_context(context, recorder: Optional[SpanRecorder] = None):
    """request_context from a grpc servicer context's metadata."""
    header = None
    try:
        for k, v in context.invocation_metadata():
            if k.lower() == "traceparent":
                header = v
                break
    except Exception:  # noqa: BLE001 - metadata is best-effort
        pass
    return request_context(header, recorder=recorder)


def outbound_metadata(extra=()):
    """grpc call metadata carrying the active trace (otelgrpc
    client-interceptor parity); None when there is neither a trace nor
    extra metadata."""
    tp = current_traceparent()
    md = list(extra)
    if tp is not None:
        md.append(("traceparent", tp))
    return md or None


# --- the one timing primitive (ISSUE 24) --------------------------------

_annotation = None


def _trace_annotation():
    """jax.profiler.TraceAnnotation, imported on first use: client.py
    and peer_client.py import this module and must not pull JAX in."""
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    return _annotation


def partition_thread() -> None:
    """Declare that ALL of the calling thread's time from here on
    belongs to phases (the dispatch worker: it waits for work or works
    on a wave, nothing else).  What passes BETWEEN two of its phases —
    glue, the phases' own bookkeeping, and above all waiting to get
    the GIL back from 32 handler threads — is then summed up as the
    thread's gap (``take_gap``), so that phases + gap partition the
    thread's wall time.  Phases given an explicit ``at=`` neither read
    nor move the cursor.  While a profile records, each gap is also a
    ``TraceAnnotation("worker.gap")`` — opened by ``phase.end``, closed
    by the thread's next ``phase.begin`` (one given ``at=`` closes it
    only where that IS the cursor: the boundary it shares with the
    phase before) — so the profile shows on its own clock what
    ``take_gap`` sums after the fact."""
    _tls.cursor = time.perf_counter()
    _tls.gap = 0.0


def take_gap() -> float:
    """Seconds the calling partition thread has spent between phases
    since the last call (0.0 on any other thread)."""
    gap = _tls.gap
    if gap:
        _tls.gap = 0.0
    return gap


class WaveScope:
    """One dispatcher wave as the enclosing scope of the ``phase()``s
    that run for it, in whichever thread runs them (the dispatch
    worker has no request context; a pipelined wave is entered twice,
    for its launch and for its sync).  Carries the phase sink for
    engine code, which knows neither the dispatcher nor the wave, and
    — when the wave's trace is recorded — the ids its children parent
    under (``children``: the trace is head-sampled, so the wave's
    phases are recorded as its child spans).  ``finish()`` hands over
    the wave span's attributes; the span is recorded when the scope
    exits, so every child lies inside it on the same clock.  ``cpu``:
    this wave is one of the few whose phases also record thread CPU
    time (the dispatcher samples 1 in ``Dispatcher.CPU_SAMPLE``)."""

    __slots__ = ("sink", "cpu", "recorder", "trace_id", "span_id",
                 "parent_id", "wave_id", "children", "start_ns", "attrs",
                 "_prev")

    def __init__(self, sink, cpu: bool = False):
        self.sink = sink
        self.cpu = cpu
        self.recorder = self.trace_id = self.span_id = None
        self.parent_id = self.wave_id = self.attrs = None
        self.children = False
        self.start_ns = time.time_ns()  # clock-ok: telemetry wall clock (span start)

    def bind(self, recorder, trace_id, span_id, parent_id, wave_id) -> None:
        self.recorder, self.trace_id = recorder, trace_id
        self.span_id, self.parent_id = span_id, parent_id
        self.wave_id = wave_id
        self.children = recorder.head_sampled(trace_id)

    def finish(self, attrs: dict) -> None:
        self.attrs = attrs

    def __enter__(self) -> "WaveScope":
        self._prev = _tls.wave
        _tls.wave = self
        return self

    def __exit__(self, *exc) -> None:
        _tls.wave = self._prev
        attrs, self.attrs = self.attrs, None
        if attrs is not None and self.span_id is not None:
            self.recorder.add({
                "trace_id": self.trace_id, "span_id": self.span_id,
                "parent_id": self.parent_id, "name": "wave",
                "start": self.start_ns / 1e9,
                "end": time.time_ns() / 1e9,  # clock-ok: telemetry wall clock (span end)
                "attrs": attrs})


class phase:
    """Time one section of the program (catalog: ``PHASE_CATALOG``).

    ``with phase(name, sink):`` — or ``p = phase(...).begin()`` …
    ``p.end()`` where a ``with`` does not fit the control flow.  Every
    use (a) holds a ``jax.profiler.TraceAnnotation(name)`` open, so the
    section shows in any profile of the process on the device trace's
    clock; (b) hands its wall seconds — and, with ``cpu=True``, the
    thread's CPU seconds over the same boundaries — to
    ``sink.observe_phase`` (``gubernator_phase_duration{phase}`` and
    the PhaseLedger); (c) inside a wave or request whose trace the
    SpanRecorder keeps (head-sampled, or already forced), adds a span
    with the real start and end, parented under that wave or request
    span.  ``sink`` defaults to the enclosing WaveScope's.

    ``begin(at=)`` / ``end(at=)`` take a tick the caller already read
    (two phases that share a boundary share the reading, so they
    partition exactly; a phase INSIDE another phase of its thread,
    `tier.premask` in `wave.route`, reads its own tick at both ends, so
    on a partition thread it neither moves the cursor nor counts the
    enclosing phase's time as gap); ``end(keep=False)`` closes the
    section without a sample (nothing was done in it).  ``span=False``
    keeps a phase that overlaps its siblings out of the span tree;
    ``always=True`` records the span whatever the sampling decision,
    for the commit to decide (``span()``: the handler entries).  ``cpu=True`` costs two
    ``time.thread_time()`` calls — a real system call, ~6 µs each on
    the chip's host and dearer under load — so it is for per-call
    phases whose wall − CPU split decides something, and for the
    phases of the waves the dispatcher samples (``WaveScope.cpu``).
    The annotation is made only while a profile records; on a
    partition thread (``partition_thread``) the time to the thread's
    next phase is then annotated ``worker.gap``.

    ``every=n`` times 1 use in n of this name and lets the others
    through untouched (a per-call phase on a path that serves hundreds
    of one-request calls a second: each timed use costs ~3 µs of a
    GIL those calls are bound by).  Means stay true; SUMS do not — so
    only for phases nobody adds up."""

    __slots__ = ("name", "sink", "_cpu", "_span", "_always", "_attrs",
                 "_ann", "_t0", "t1", "_c0", "_ns0", "_st", "_sid",
                 "_parent", "_skip")

    #: name → uses so far, for ``every=`` (racy on purpose: any 1 in
    #: ~n will do)
    _uses: Dict[str, int] = {}

    def __init__(self, name: str, sink=None, *, cpu: bool = False,
                 span: bool = True, always: bool = False,
                 attrs: Optional[dict] = None, every: int = 1):
        self.name = name
        self.sink = sink
        self._cpu = cpu
        self._span = span
        self._always = always
        self._attrs = attrs
        self._skip = False
        if every > 1:
            n = phase._uses[name] = phase._uses.get(name, 0) + 1
            self._skip = n % every != 0

    def begin(self, at: Optional[float] = None) -> "phase":
        if self._skip:
            return self
        cls = _annotation or _trace_annotation()
        gap_ann = _tls.gap_ann
        if gap_ann is not None and (at is None or at == _tls.cursor):
            # partition thread, profile recording: the gap ends where
            # its next phase begins (a begin AT the cursor shares the
            # boundary with the phase before: no gap at all)
            _tls.gap_ann = None
            gap_ann.__exit__(None, None, None)
        if cls.is_enabled():  # a profile is recording
            ann = self._ann = cls(self.name)
            ann.__enter__()
        else:
            self._ann = None
        self._st = None
        self._ns0 = 0
        if self._span:
            scope = _tls.wave
            if scope is not None:
                if scope.cpu:
                    self._cpu = True
                if scope.children or scope.span_id is None:
                    self._ns0 = time.time_ns()  # clock-ok: telemetry wall clock (span start)
            else:
                st = _tls.span
                if st is not None and (self._always or st.sampled
                                       or st.forced is not None):
                    # request thread: nest like any span
                    self._st = st
                    self._sid = secrets.token_hex(8)
                    self._parent = (st.stack[-1] if st.stack
                                    else st.parent)
                    st.stack.append(self._sid)
                    self._ns0 = time.time_ns()  # clock-ok: telemetry wall clock (span start)
        if self._cpu:
            self._c0 = time.thread_time()
        if at is None:
            at = time.perf_counter()
            cur = _tls.cursor
            if cur is not None:
                _tls.gap += at - cur
        self._t0 = at
        return self

    def end(self, at: Optional[float] = None, keep: bool = True,
            exemplar=None) -> float:
        """Close the section; returns its wall seconds (0.0 for a use
        that ``every=`` let through untimed)."""
        if self._skip:
            return 0.0
        gap_from_here = False
        if at is None:
            at = time.perf_counter()
            if _tls.cursor is not None:
                _tls.cursor = at
                gap_from_here = True
        self.t1 = at
        cpu = (time.thread_time() - self._c0) if self._cpu else None
        ns0 = self._ns0
        ns1 = time.time_ns() if ns0 else 0  # clock-ok: telemetry wall clock (span end)
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        if (gap_from_here and _tls.gap_ann is None
                and _annotation.is_enabled()):
            # a partition thread while a profile records: its gap on
            # the profile's clock, from here to its next begin()
            gap_ann = _tls.gap_ann = _annotation("worker.gap")
            gap_ann.__enter__()
        dt = at - self._t0
        if dt < 0.0:
            dt = 0.0
        st = self._st
        if st is not None:
            if st.stack and st.stack[-1] == self._sid:
                st.stack.pop()
        if not keep:
            return dt
        sink = self.sink
        if sink is None:
            scope = _tls.wave
            if scope is not None:
                sink = scope.sink
        if sink is not None:
            sink.observe_phase(self.name, dt, cpu, exemplar)
        if ns0:
            if st is not None:
                st.recorder.add({
                    "trace_id": st.trace_id, "span_id": self._sid,
                    "parent_id": self._parent, "name": self.name,
                    "start": ns0 / 1e9, "end": ns1 / 1e9,
                    "attrs": dict(self._attrs) if self._attrs else {}})
            else:
                scope = _tls.wave
                if scope is not None and scope.children:
                    scope.recorder.add({
                        "trace_id": scope.trace_id,
                        "span_id": secrets.token_hex(8),
                        "parent_id": scope.span_id, "name": self.name,
                        "start": ns0 / 1e9, "end": ns1 / 1e9,
                        "attrs": {"wave": scope.wave_id}})
        return dt

    __enter__ = begin

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is not None:
            st = _tls.span
            if st is not None and st.forced is None:
                # an exception in the body force-samples the whole trace
                st.forced = "error"
        self.end()


class _FuncDuration:
    """``span()``'s sink: gubernator_func_duration{name}."""

    __slots__ = ("metrics",)

    def __init__(self, metrics):
        self.metrics = metrics

    def observe_phase(self, name, seconds, cpu=None, exemplar=None):
        self.metrics.func_duration.labels(name=name).observe(seconds)


def span(name: str, metrics=None, attrs: Optional[dict] = None) -> phase:
    """A handler entry's span (catalog: ``SPAN_CATALOG``): ``phase()``
    with ``gubernator_func_duration{name}`` as its histogram — always a
    duration metric, including on the error path.  When the request
    context armed a SpanRecorder, the span is RECORDED: fresh span id,
    parented under the innermost open span (or the inbound hop), and
    an exception in the body force-samples the whole trace."""
    return phase(name, _FuncDuration(metrics) if metrics is not None
                 else None, always=True, attrs=attrs)


# --- the thread ledger (ISSUE 37) ----------------------------------------
#
# Every phase above times a SECTION; none counts a THREAD.  The kernel
# does: /proc/self/task/<tid>/schedstat holds, in nanoseconds, what each
# thread of the process has run on a CPU and what it has waited on a
# run queue for one.  The ledger adds those up by ROLE when /metrics is
# rendered, so "is this daemon GIL-bound, and by whom" is a sum over the
# Python roles' CPU between two scrapes (at most one core's worth can
# hold the GIL) beside what the native roles use of the same cores.

#: role → (kind, patterns, which threads).  kind "py": a thread Python
#: started (``threading.enumerate()``), matched on ``Thread.name``; kind
#: "comm": every other thread of the process — gRPC core's, the XLA /
#: PJRT / TPU runtime's pools — matched on /proc/self/task/<tid>/comm
#: (the kernel keeps 15 characters).  ``fnmatch`` patterns; within a
#: kind the first role that matches wins, so each kind ends in its
#: ``*-other``: an unknown thread is counted there, never dropped.
#: Linted both ways against OBSERVABILITY.md's "Thread roles" table
#: (tools/guberlint/docs.py › thread_roles_doc_problems).
THREAD_ROLES: Dict[str, tuple] = {
    "worker": ("py", ("device-dispatcher",),
               "the dispatch worker: every wave's launch and sync"),
    "handler": ("py", ("grpc-handler_*", "grpc-client-handler_*"),
                "the gRPC pools' threads (daemon.py › DoorPool): "
                "grpcio's per-call Python, the servicer, the call's "
                "ingest / pack / build, its wait for the wave"),
    "grpc-serve": ("py", ("*(_serve)",),
                   "grpcio's _serve loop, ONE Python thread a server: "
                   "every call's arrival, request message and response "
                   "pass through it"),
    "analytics": ("py", ("key-analytics",),
                  "the analytics worker: tenant learn, sketch fold, "
                  "publish"),
    "tick": ("py", ("tick:*",),
             "every IntervalLoop thread: the mesh-GLOBAL fold and the "
             "GLOBAL manager's ticks, SLO engine, discovery polls"),
    "py-other": ("py", ("*",),
                 "any other Python thread: main, the HTTP listener and "
                 "its request threads, the watchdog, peer lanes, an "
                 "embedding program's own (the benchmark's)"),
    "native-grpc": ("comm", ("grpc*", "default-executo*", "resolver-exe*",
                             "event_engine*", "timer_manager*",
                             "lifeguard"),
                    "gRPC core: pollers, the event engine, timers"),
    "native-xla": ("comm", ("tf_*", "tpu*", "TPU*", "xla*", "XLA*",
                            "pjrt*", "PjRt*", "pjit*", "py_xla_*",
                            "tfrt-*", "StreamExec*", "tsl*", "profiler*",
                            "llvm-worker*",
                            # libtpu's own pools, as a chip run showed
                            # them (41 + 38/4 a chip; PERF.md §6, PR 37)
                            "futex-default-*", "EventFDAsyncWor*",
                            "DefaultEventMan*", "PendingEventLog*",
                            "SlowOperationAl*", "BreakpointDebug*",
                            "thread_manager_*", "thread_threadpo*",
                            "learning_*", "timedcall"),
                   "the XLA / PJRT / TSL / TPU runtime's thread pools "
                   "(launches, transfers, host callbacks, the profiler)"),
    "native-other": ("comm", ("*",),
                     "any other native thread (BLAS pools, threads a "
                     "library never named: comm is the interpreter's)"),
}


#: nanoseconds a clock tick of /proc/<pid>/task/<tid>/stat (off POSIX:
#: unused, the ledger finds no /proc)
_NS_PER_TICK = 1_000_000_000 // (
    os.sysconf("SC_CLK_TCK") if hasattr(os, "sysconf") else 100)

try:  # the walk of /proc/self/task: one call, the GIL given up ONCE
    from .ops.native import thread_files as _thread_files
except ImportError:  # pragma: no cover - unbuilt extension: no ledger
    _thread_files = None


def thread_role(kind: str, name: str) -> str:
    """The role of a thread of ``kind`` ("py" / "comm") named ``name``."""
    for role, (k, patterns, _doc) in THREAD_ROLES.items():
        if k == kind and any(fnmatchcase(name, p) for p in patterns):
            return role
    raise AssertionError(f"THREAD_ROLES has no catch-all of kind {kind}")


class ThreadLedger:
    """CPU, run-queue wait and wake-ups of the process's threads by
    role (``THREAD_ROLES``), read from /proc/self/task WHEN /metrics IS
    RENDERED and at no other time: a collector on ``Metrics.registry``,
    nothing on the serving path, no option.

    Per thread one file.  ``schedstat`` where the kernel has it: ns on
    a CPU, ns runnable but waiting for a core, times scheduled in —
    nanosecond counters, where ``time.thread_time()`` ticks in 10-ms
    steps on some hosts.  Where it has not (a sandboxed kernel: the
    chip tool's machines), ``stat``: utime + stime in clock ticks — CPU
    only, sound as a sum over seconds, and the run-queue and wake-up
    series are then NOT exported (``source`` says which file it is).
    For the role ``worker`` also ``status``: voluntary / involuntary
    context switches (a voluntary one is the thread blocking: on the
    GIL, a lock, the device), exported where the kernel counts them.
    ``comm`` is read once a thread id, by a second walk in the reads
    that meet a native thread they do not know.

    Every walk is ONE call into the C++ extension (``ops/_native.cpp ›
    thread_files``) that gives the GIL up once.  A Python loop over the
    files gives it up at every open, read and close, and on a loaded
    daemon waits a switch interval to get it back each time: 3–5 ms a
    thread, seconds a scrape, one more contender for what the ledger
    measures (PERF.md §6, PR 37).  So there is none: a daemon without
    the extension has no ledger, as one off Linux has none.

    Role totals only grow: the ledger keeps each thread id's last
    reading and adds DELTAS to the thread's role, so a thread that
    exits takes nothing away.  A thread that lives and dies between
    two reads is missed, and so is what a thread ran after its last
    read.  A re-read inside ``MIN_INTERVAL_S`` returns the totals of
    the read before.  The totals are the PROCESS's: two daemons in one
    process (the test cluster) each report all of its threads.

    Off Linux (no ``/proc/self/task``) and without the extension the
    collector yields nothing."""

    MIN_INTERVAL_S = 0.5

    def __init__(self, task_dir: str = "/proc/self/task"):
        self._task_dir = task_dir
        self._mu = threading.Lock()
        #: the per-thread file the counters come from: "schedstat",
        #: "stat", or None until a read has found one
        self.source: Optional[str] = None
        self._last: Dict[int, tuple] = {}  # guarded-by: self._mu
        self._roles: Dict[int, tuple] = {}  # guarded-by: self._mu
        self._snap: Optional[dict] = None  # guarded-by: self._mu
        #: role → [cpu ns, run-queue wait ns, times scheduled in]
        self._totals = {r: [0, 0, 0] for r in THREAD_ROLES}  # guarded-by: self._mu
        #: the worker threads' [voluntary, involuntary] switches; None
        #: where the kernel's status files do not count them (asked of
        #: the first thread of the first walk)
        self._switches: Optional[list] = None  # guarded-by: self._mu

    def _walk(self, name: str) -> Optional[list]:
        """``[(tid, text of <task_dir>/<tid>/<name>)]`` for every thread
        that has the file; ``None`` where the directory cannot be
        listed, or the extension is not built."""
        if _thread_files is None:
            return None
        got = _thread_files(self._task_dir, name)
        return got and [(tid, raw.decode("ascii", "replace"))
                        for tid, raw in got]

    def _files(self) -> Optional[list]:
        """One walk of the file this kernel has — ``schedstat``, else
        ``stat``, settled by the first walk that finds either — or
        ``None`` where it has neither."""
        for source in (self.source,) if self.source else ("schedstat",
                                                          "stat"):
            files = self._walk(source)
            if files:
                self.source = source
                return files
        return None

    def _counters(self, text: str) -> tuple:
        """(cpu ns, run-queue wait ns, times scheduled in) from one
        thread's ``schedstat`` or ``stat``."""
        if self.source == "schedstat":
            cpu, wait, slices = text.split()[:3]
            return int(cpu), int(wait), int(slices)
        # the fields after "(comm)": utime and stime are the 12th and
        # 13th of them (the 14th and 15th of the line)
        fields = text.rsplit(")", 1)[1].split()
        return (int(fields[11]) + int(fields[12])) * _NS_PER_TICK, 0, 0

    def _worker_switches(self, tid: int) -> Optional[tuple]:
        """(voluntary, involuntary) context switches of one thread, or
        None where its status does not hold them."""
        try:
            with open(f"{self._task_dir}/{tid}/status") as f:
                st = f.read()
            return tuple(int(st.split(key, 1)[1].split(None, 1)[0])
                         for key in ("\nvoluntary_ctxt_switches:",
                                     "\nnonvoluntary_ctxt_switches:"))
        except (OSError, ValueError, IndexError):
            return None

    def read(self) -> Optional[dict]:
        """Walk the threads (at most once in ``MIN_INTERVAL_S``) and
        return ``{"clock", "source", "roles": {role: (cpu s, run-queue
        wait s, wake-ups, threads now)}, "switches": (voluntary,
        involuntary) or None}`` — ``None`` where the kernel offers no
        per-thread counters."""
        with self._mu:
            now = time.perf_counter()
            snap = self._snap
            if snap is not None and now - snap["clock"] < self.MIN_INTERVAL_S:
                return snap
            files = self._files()
            if files is None:
                return None  # no /proc, or no per-thread counters in it
            # a foreign thread that once ran Python shows as a
            # _DummyThread: it is what its comm says, not "py-other"
            py_names = {t.native_id: t.name for t in threading.enumerate()
                        if t.native_id is not None
                        and not isinstance(t, threading._DummyThread)}
            last, totals = self._last, self._totals
            if snap is None and self._worker_switches(files[0][0]):
                self._switches = [0, 0]
            seen = {}
            counts = dict.fromkeys(totals, 0)
            roles, comms = self._roles, None
            for tid, text in files:
                try:
                    cur = self._counters(text)
                except (ValueError, IndexError):
                    continue  # exited under the walk
                # the role, cached under what it was matched on: the
                # Python name (which a thread may change), else comm
                name = py_names.get(tid)
                got = roles.get(tid)
                if got is None or got[0] != name:
                    if name is not None:
                        role = thread_role("py", name)
                    else:
                        if comms is None:  # a native thread not met yet
                            comms = dict(self._walk("comm") or ())
                        if tid not in comms:
                            continue  # exited between the two walks
                        role = thread_role("comm", comms[tid].strip())
                    roles[tid] = got = (name, role)
                role = got[1]
                old = last.get(tid)
                if old is None or cur[0] < old[0]:
                    old = (0,) * 5  # new thread (or a reused id)
                tot = totals[role]
                tot[0] += cur[0] - old[0]
                tot[1] += cur[1] - old[1]
                tot[2] += cur[2] - old[2]
                if role == "worker" and self._switches is not None:
                    sw = self._worker_switches(tid)
                    if sw is None:
                        # its status raced its exit: the CPU above
                        # counts, the switches keep their last reading
                        cur += old[3:]
                    else:
                        if len(old) == 5:
                            self._switches[0] += sw[0] - old[3]
                            self._switches[1] += sw[1] - old[4]
                        cur += sw
                seen[tid] = cur
                counts[role] += 1
            self._last = seen
            for tid in [t for t in self._roles if t not in seen]:
                del self._roles[tid]
            self._snap = snap = {
                "clock": now, "source": self.source,
                "roles": {r: (t[0] / 1e9, t[1] / 1e9, t[2], counts[r])
                          for r, t in totals.items()},
                "switches": self._switches and tuple(self._switches)}
            return snap

    def collect(self):
        """prometheus_client's custom-collector hook: the families of
        one ``read()``."""
        snap = self.read()
        if snap is None:
            return
        from prometheus_client.core import (CounterMetricFamily,
                                            GaugeMetricFamily)

        cpu = CounterMetricFamily(
            "gubernator_thread_cpu_seconds",
            "seconds the process's threads have run on a CPU, by thread "
            "role (tracing.THREAD_ROLES; /proc/self/task/*/schedstat, "
            "read when /metrics is rendered): the Python roles' sum "
            "over an interval is an upper bound of the GIL's load",
            labels=["role"])
        runq = CounterMetricFamily(
            "gubernator_thread_runq_wait_seconds",
            "seconds the threads were runnable but waited for a core, "
            "by role: starved of CORES, not of the GIL", labels=["role"])
        wake = CounterMetricFamily(
            "gubernator_thread_wakeups",
            "times the threads were scheduled onto a CPU, by role",
            labels=["role"])
        alive = GaugeMetricFamily(
            "gubernator_threads", "threads alive at this read, by role",
            labels=["role"])
        for role, (c, w, n, k) in snap["roles"].items():
            cpu.add_metric([role], c)
            runq.add_metric([role], w)
            wake.add_metric([role], n)
            alive.add_metric([role], k)
        families = [cpu, alive]
        if snap["source"] == "schedstat":  # `stat` has neither
            families += [runq, wake]
        if snap["switches"] is not None:
            sw = CounterMetricFamily(
                "gubernator_thread_switches",
                "context switches of the dispatch worker: voluntary = "
                "it blocked (the GIL, a lock, the device), involuntary "
                "= it was preempted", labels=["role", "kind"])
            sw.add_metric(["worker", "voluntary"], snap["switches"][0])
            sw.add_metric(["worker", "involuntary"], snap["switches"][1])
            families.append(sw)
        clock = GaugeMetricFamily(
            "gubernator_thread_ledger_clock_seconds",
            "time.perf_counter() of the read the gubernator_thread_* "
            "totals come from: divide their deltas by THIS one's delta "
            "(a scrape inside 0.5 s of the last repeats its totals)")
        clock.add_metric([], snap["clock"])
        yield from (*families, clock)


# --- cross-daemon assembly (ISSUE 12) ---------------------------------


def assemble(spans: List[dict], trace_id: Optional[str] = None
             ) -> List[dict]:
    """Stitch span slices (possibly from N daemons' /debug/traces)
    into per-trace trees.  Returns one dict per trace — ``trace_id``,
    ``spans`` (count), ``roots`` (nested via ``children``) — ordered
    by earliest span start.  Duplicate span ids (the same daemon's
    slice fetched twice) dedup; orphans (parent not in the slice
    set) surface as extra roots rather than vanishing."""
    by_trace: Dict[str, dict] = {}
    for s in spans:
        tid = s.get("trace_id")
        if not tid or (trace_id and tid != trace_id):
            continue
        by_trace.setdefault(tid, {}).setdefault(s.get("span_id"), s)
    out = []
    for tid, seen in by_trace.items():
        nodes = {sid: dict(s, children=[]) for sid, s in seen.items()}
        roots = []
        for n in nodes.values():
            p = n.get("parent_id")
            if p and p in nodes and p != n.get("span_id"):
                nodes[p]["children"].append(n)
            else:
                roots.append(n)
        for n in nodes.values():
            n["children"].sort(key=lambda c: c.get("start") or 0.0)
        roots.sort(key=lambda c: c.get("start") or 0.0)
        out.append({"trace_id": tid, "spans": len(nodes),
                    "roots": roots})
    out.sort(key=lambda t: min((r.get("start") or 0.0
                                for r in t["roots"]), default=0.0))
    return out


def render_waterfall(trace: dict, width: int = 40) -> str:
    """Text waterfall for one assembled trace (a dict from
    ``assemble()``): indent = depth, one bar per span scaled to the
    trace's [min start, max end] window."""
    flat: List[tuple] = []

    def _walk(n, depth):
        flat.append((depth, n))
        for c in n.get("children", ()):
            _walk(c, depth + 1)

    for r in trace.get("roots", ()):
        _walk(r, 0)
    if not flat:
        return f"trace {trace.get('trace_id')}: no spans"
    t0 = min(n.get("start") or 0.0 for _, n in flat)
    t1 = max(n.get("end") or 0.0 for _, n in flat)
    window = max(t1 - t0, 1e-9)
    lines = [f"trace {trace.get('trace_id')}  "
             f"({trace.get('spans')} spans, {window * 1e3:.2f}ms)"]
    for depth, n in flat:
        s = (n.get("start") or 0.0) - t0
        e = (n.get("end") or 0.0) - t0
        lo = int(s / window * width)
        hi = max(int(e / window * width), lo + 1)
        bar = " " * lo + "#" * (hi - lo) + " " * (width - hi)
        dur_ms = max(e - s, 0.0) * 1e3
        lines.append(f"  [{bar}] {'  ' * depth}{n.get('name')} "
                     f"+{s * 1e3:.2f}ms {dur_ms:.2f}ms")
    return "\n".join(lines)


class DeviceProfiler:
    """jax.profiler session around the serving loop.

    Usage: ``prof = DeviceProfiler.from_env(); ...; prof.stop()`` —
    writes an XProf trace for TensorBoard under the given directory.
    """

    def __init__(self, log_dir: str):
        import jax

        self.log_dir = log_dir
        jax.profiler.start_trace(log_dir)
        self._active = True
        log.info("device profiling → %s", log_dir)

    @classmethod
    def from_env(cls) -> Optional["DeviceProfiler"]:
        d = os.environ.get("GUBER_PROFILE_DIR", "")
        return cls(d) if d else None

    def stop(self) -> None:
        if self._active:
            self._active = False
            import jax

            jax.profiler.stop_trace()
