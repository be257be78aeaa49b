"""Host spans recorded from the benchmark's side, around the calls into
each layer of the program.  Only a ``--trace 1`` run installs them.

Each wrapped call is a ``jax.profiler.TraceAnnotation`` (so it lands in
the profiler's trace, on the device trace's clock, and idle gaps of the
device can be attributed to it) and is timed on the host clock into a
per-name list that the per-layer readers take their numbers from.
"""
from __future__ import annotations

import threading
import time

#: span name → (object path from the daemon, attribute wrapped)
SITES = {
    "instance.get_rate_limits_wire": ("instance", "get_rate_limits_wire"),
    "dispatcher.drain_wave": ("instance.dispatcher", "_drain_wave"),
    "dispatcher.launch": ("instance.dispatcher", "_launch_packed_jobs"),
    "dispatcher.sync_resolve": ("instance.dispatcher", "_sync_and_resolve"),
    "dispatcher.inline_wave": ("instance.dispatcher", "run_inline_wave"),
    "engine.launch_packed": ("instance.engine", "launch_packed"),
    "engine.sync_packed": ("instance.engine", "sync_packed"),
    "engine.check_prepacked": ("instance.engine", "check_prepacked"),
    "engine.sweep": ("instance.engine", "sweep"),
}


class Spans:
    def __init__(self):
        self._mu = threading.Lock()
        #: name → list of (start, end) on time.monotonic()
        self.times: dict = {name: [] for name in SITES}
        self._undo: list = []

    def install(self, daemon) -> None:
        import jax

        for name, (path, attr) in SITES.items():
            obj = daemon
            for part in path.split("."):
                obj = getattr(obj, part)
            inner = getattr(obj, attr, None)
            if inner is None:
                continue
            obj.__dict__[attr] = self._wrap(
                name, inner, jax.profiler.TraceAnnotation)
            self._undo.append((obj, attr))

    def _wrap(self, name, inner, annotation):
        rows = self.times[name]
        mu = self._mu

        def wrapped(*a, **kw):
            t0 = time.monotonic()
            with annotation(name):
                try:
                    return inner(*a, **kw)
                finally:
                    t1 = time.monotonic()
                    with mu:
                        rows.append((t0, t1))

        return wrapped

    def remove(self) -> None:
        for obj, attr in self._undo:
            obj.__dict__.pop(attr, None)
        self._undo.clear()

    def within(self, name: str, lo: float, hi: float) -> list:
        """Durations (s) of the spans of ``name`` that ended in [lo, hi]."""
        with self._mu:
            return [b - a for a, b in self.times.get(name, ())
                    if lo <= b <= hi]
