"""Deterministic fault injection: named faultpoints, armed on demand.

The failure-domain resilience layer (ISSUE 5) needs failures it can
cause on purpose: a chaos run that kills an owner, delays the device
step, or drops a broadcast must be REPEATABLE, or a flake found once is
lost forever.  This module provides named faultpoints compiled to
near-zero-cost checks — each instrumented site costs one attribute read
(``fs.armed``) while disarmed — armed from the ``GUBER_FAULT`` env var,
``POST /debug/faults``, or ``guber-cli debug faults --set``.

Spec grammar (comma-separated)::

    point[@tag]:mode[:arg[:prob]]

    peer_send:error:0.3           30% of peer flush RPCs fail
    device_step:delay:50ms        every device step sleeps 50ms
    peer_send@10.0.0.2:5001:error forwards to that peer always fail
    global_broadcast:error:1.0:   (prob defaults to 1.0)

Modes:

- ``error`` — raise :class:`FaultInjected` at the faultpoint; ``arg``
  is the probability (default 1.0).
- ``delay`` — sleep; ``arg`` is a Go-style duration (``50ms``, ``1s``),
  optional 4th field is the probability.

``tag`` scopes a point to one call-site identity (peer points pass the
peer's gRPC address); a point without a tag matches every site.

Determinism: every point draws from its own ``random.Random`` seeded
from ``(seed, point, tag, mode)`` (``GUBER_FAULT_SEED``, default 0), so
a chaos run replays bit-for-bit regardless of how other points
interleave.  Each :class:`FaultSet` is per-instance (the daemon's
``POST /debug/faults`` arms only that daemon), so in-process cluster
tests can fail one daemon's view of the world without touching its
siblings.

The faultpoint catalog lives in :data:`FAULT_POINTS` (documented in
RESILIENCE.md); arming an unknown point is a loud error — a typo'd
chaos run must not silently test nothing.
"""
from __future__ import annotations

import logging
import os
import random
import threading
import time
from typing import Dict, List, Optional

log = logging.getLogger("gubernator_tpu.faults")


class FaultInjected(Exception):
    """Raised by an armed ``error``-mode faultpoint."""


#: faultpoint catalog: name → where the check lives (RESILIENCE.md
#: carries the operator-facing version of this table)
FAULT_POINTS = {
    "peer_send": "peer_client._SendLane._launch — before the flush RPC "
                 "leaves (tag: peer address)",
    "peer_recv": "peer_client._SendLane._rpc_done — after a flush RPC "
                 "succeeded, before entries resolve (tag: peer address)",
    "peer_circuit": "PeerClient._circuit_blocked — forces the peer's "
                    "circuit to read as OPEN (tag: peer address)",
    "dispatch_enqueue": "Dispatcher._submit — job admission into the "
                        "wave queue",
    "dispatch_launch": "Dispatcher wave launch — before the engine call "
                       "of a queued wave",
    "dispatch_sync": "Dispatcher._sync_and_resolve — before a pipelined "
                     "wave's sync",
    "dispatch_merge": "Dispatcher._drain_wave — after a wave's jobs are "
                      "collected, before the merge/launch (delay mode "
                      "widens the window for more callers to land in "
                      "the NEXT wave — the racer's preemption point)",
    "dispatch_carry": "Dispatcher._drain_wave — when an overflow job is "
                      "held as the next wave's carry (delay mode parks "
                      "the carried job across the wave boundary)",
    "dispatch_splice": "Dispatcher result splicing — after the engine "
                       "call, before per-job futures resolve from the "
                       "shared result columns (delay mode holds "
                       "responses while later waves launch)",
    "device_step": "the engine call itself (every wave, on the worker)",
    "wire_ingest": "instance wire entry — before the C++ parse",
    "global_broadcast": "GlobalManager._run_broadcasts — before the "
                        "owner broadcast tick",
    "global_hits": "GlobalManager._run_async_hits — before the hit "
                   "flush tick (failed aggregates requeue)",
    "global_accum_swap": "V1Instance._mesh_reconcile_tick — before the "
                         "mesh-GLOBAL accumulator double-buffer swap "
                         "(error aborts the tick; buffers untouched)",
    "global_psum": "V1Instance._mesh_reconcile_tick — before the "
                   "mesh-GLOBAL reconcile collective launches (error "
                   "swaps the retired buffer back; no hit stranded)",
    "mr_sync": "MultiRegionManager._run_async_reqs — before the "
               "cross-region flush tick (queues not yet popped, so an "
               "aborted tick loses nothing)",
    "snapshot": "instance._save_to_loader — before the Loader snapshot",
    "restore": "instance._load_from_loader — before the Loader restore",
    "tier_promote": "TierController.migrate — once a promotee of a "
                    "migration pass, after the admissibility gate, "
                    "before the pass touches the device (error drops "
                    "that ONE row from the pass: it stays cold, "
                    "tier_migrations_aborted increments; the rest of "
                    "the pass goes on)",
    "tier_demote": "TierController.migrate › _pick_victims (and demote) "
                   "— once a victim picked, before its row is taken "
                   "off the device image (error aborts that ONE "
                   "eviction: the row stays hot and the promotion that "
                   "asked for it is abandoned)",
}


class _Point:
    __slots__ = ("name", "tag", "mode", "prob", "delay_s", "rng",
                 "checked", "fired")

    def __init__(self, name: str, tag: Optional[str], mode: str,
                 prob: float, delay_s: float, seed: int):
        self.name = name
        self.tag = tag
        self.mode = mode
        self.prob = prob
        self.delay_s = delay_s
        # per-point stream: replay does not depend on how OTHER points
        # interleave their draws
        self.rng = random.Random(f"{seed}|{name}|{tag}|{mode}")
        self.checked = 0
        self.fired = 0

    def describe(self) -> dict:
        return {"point": self.name, "tag": self.tag, "mode": self.mode,
                "prob": self.prob,
                "delay_ms": round(self.delay_s * 1000, 3),
                "checked": self.checked, "fired": self.fired}


def _parse_spec(spec: str, seed: int) -> List[_Point]:
    from .config import parse_duration_ms

    points: List[_Point] = []
    for entry in spec.split(","):
        entry = entry.strip()
        if not entry:
            continue
        parts = entry.split(":")
        head = parts[0]
        tag: Optional[str] = None
        if "@" in head:
            head, _, tag = head.partition("@")
            # peer tags are host:port — the ":" split above cut the
            # port off; a purely-numeric next field can only be that
            # port (modes are words, probabilities carry a dot)
            if len(parts) > 1 and parts[1].isdigit():
                tag = f"{tag}:{parts[1]}"
                parts.pop(1)
        name = head.strip()
        if name not in FAULT_POINTS:
            raise ValueError(
                f"unknown faultpoint {name!r} (catalog: "
                f"{', '.join(sorted(FAULT_POINTS))})")
        mode = parts[1].strip() if len(parts) > 1 else "error"
        prob, delay_s = 1.0, 0.0
        if mode == "error":
            if len(parts) > 2 and parts[2].strip():
                prob = float(parts[2])
        elif mode == "delay":
            if len(parts) < 3 or not parts[2].strip():
                raise ValueError(
                    f"faultpoint {name!r}: delay mode needs a duration "
                    f"(e.g. {name}:delay:50ms)")
            delay_s = parse_duration_ms(parts[2].strip()) / 1000.0
            if len(parts) > 3 and parts[3].strip():
                prob = float(parts[3])
        else:
            raise ValueError(
                f"faultpoint {name!r}: unknown mode {mode!r} "
                "(want 'error' or 'delay')")
        if not (0.0 <= prob <= 1.0):
            raise ValueError(
                f"faultpoint {name!r}: probability {prob} outside [0,1]")
        points.append(_Point(name, tag or None, mode, prob, delay_s, seed))
    return points


class FaultSet:
    """One instance's armed faultpoints.

    ``armed`` is the hot-path gate: every instrumented site reads it
    first (``if fs is not None and fs.armed: fs.fire(...)``) so the
    disarmed cost is one attribute read — the acceptance A/B on
    ``6_service_path`` holds it under 1%.
    """

    def __init__(self, seed: int = 0):
        self.armed = False
        self.seed = seed
        self._mu = threading.Lock()
        self._points: Dict[str, List[_Point]] = {}
        self._spec = ""
        #: optional hooks wired by the owning instance
        self.metrics = None
        self.recorder = None

    @classmethod
    def from_env(cls, env=None) -> "FaultSet":
        env = os.environ if env is None else env
        seed = 0
        raw_seed = env.get("GUBER_FAULT_SEED", "")
        if raw_seed:
            try:
                seed = int(raw_seed)
            except ValueError:
                log.warning("malformed GUBER_FAULT_SEED=%r ignored",
                            raw_seed)
        fs = cls(seed=seed)
        spec = env.get("GUBER_FAULT", "")
        if spec:
            fs.arm(spec)
        return fs

    # ---- arming ---------------------------------------------------------

    def arm(self, spec: str, seed: Optional[int] = None) -> dict:
        """Replace the armed set with ``spec`` (empty spec disarms).
        Raises ValueError on malformed specs — nothing changes then."""
        if seed is not None:
            self.seed = seed
        points = _parse_spec(spec, self.seed)
        by_name: Dict[str, List[_Point]] = {}
        for p in points:
            by_name.setdefault(p.name, []).append(p)
        with self._mu:
            self._points = by_name
            self._spec = spec if points else ""
            self.armed = bool(points)
        if points:
            log.warning("faults ARMED (seed=%d): %s", self.seed, spec)
        if self.recorder is not None:
            if points:
                self.recorder.record("fault_armed", spec=spec,
                                     seed=self.seed)
            else:
                self.recorder.record("fault_cleared")
        return self.describe()

    def clear(self) -> dict:
        return self.arm("")

    def describe(self) -> dict:
        with self._mu:
            pts = [p.describe() for ps in self._points.values()
                   for p in ps]
        return {"armed": self.armed, "seed": self.seed,
                "spec": self._spec, "points": pts,
                "catalog": sorted(FAULT_POINTS)}

    # ---- the hot-path checks -------------------------------------------

    def _match(self, name: str, tag: Optional[str]) -> List[_Point]:
        pts = self._points.get(name)
        if not pts:
            return ()
        return [p for p in pts if p.tag is None or p.tag == tag]

    def fire(self, name: str, tag: Optional[str] = None) -> None:
        """Run the faultpoint: sleep for matched ``delay`` points, raise
        :class:`FaultInjected` for a matched ``error`` point.  Callers
        gate on ``.armed`` first; this re-checks so racing a disarm is
        harmless."""
        if not self.armed:
            return
        boom = False
        delay = 0.0
        fired = 0
        with self._mu:
            for p in self._match(name, tag):
                p.checked += 1
                if p.prob < 1.0 and p.rng.random() >= p.prob:
                    continue
                p.fired += 1
                fired += 1
                if p.mode == "delay":
                    delay += p.delay_s
                else:
                    boom = True
        if fired and self.metrics is not None:
            self.metrics.fault_injected.labels(point=name).inc(fired)
        if delay > 0:
            time.sleep(delay)
        if boom:
            raise FaultInjected(
                f"fault injected: {name}" + (f"@{tag}" if tag else ""))

    def should(self, name: str, tag: Optional[str] = None) -> bool:
        """Boolean twin of ``fire`` for points that gate a condition
        instead of raising (``peer_circuit``: forces circuit-open)."""
        if not self.armed:
            return False
        try:
            self.fire(name, tag)
        except FaultInjected:
            return True
        return False
