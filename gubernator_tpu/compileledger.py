"""Runtime jit-compile ledger (ISSUE 14) — the dynamic half of the
``retrace`` lint pass.

The static pass proves jit call SITES are retrace-stable; this module
proves the RUNTIME agrees: it counts every XLA compile per function
name, snapshots the counts once the service path is warm
(:meth:`CompileLedger.mark_steady`), and renders a verdict — a warmed
daemon must show **zero** compiles after the mark.  A nonzero
steady-state count is the retrace bug class at runtime: a weak-typed
scalar or drifting dtype at some call site is silently recompiling the
serving program per wave, turning a ~µs dispatch into a ~100 ms
compile stall.

Hook mechanism: jax (0.4.x) logs one ``"Compiling <fn> ..."`` record
on the ``jax._src.interpreters.pxla`` logger per actual XLA
compilation — including every recompile of an already-jitted function
— at DEBUG level, independent of the ``jax_log_compiles`` config.  The
ledger installs a :class:`logging.Handler` there and sets the logger
to DEBUG with ``propagate = False`` (else the raised level would spray
compile logs to stderr through the root handler); uninstall restores
the previous level/propagate.  No jax internals are imported — a
missing/renamed logger degrades to an empty ledger, never an error.

Exposed surfaces:

- ``gubernator_jit_compiles_total{fn}`` on every attached per-instance
  metrics registry (OBSERVABILITY.md);
- the ``compile_ledger`` block on bench row ``6_service_path``
  (``verdict()``: total compiles, steady flag, per-fn recompile map);
- tier-1: tests/test_compileledger.py asserts zero steady-state
  recompiles on the service path and that a deliberate dtype-drift
  escape makes the detector fire.

``GUBER_COMPILE_LEDGER=0`` disables installation (the handler, while
cheap — one regex per compile, and compiles are rare by definition —
sits on a global logger, so operators get an off switch).
"""
from __future__ import annotations

import logging
import os
import re
import threading
import weakref
from typing import Dict, List, Optional

#: the logger jax's pxla lowering emits per-compile records on; pinned
#: by tests/test_compileledger.py so a jax upgrade that moves it fails
#: loudly instead of silently recording nothing
_JAX_COMPILE_LOGGER = "jax._src.interpreters.pxla"

#: "Compiling jit(<fn>) with global shapes and types ..." — fn is the
#: jitted callable's __name__ (``<lambda>`` for lambdas)
_COMPILE_RX = re.compile(r"^Compiling jit\(([^\s]+)\)")


def enabled() -> bool:
    return os.environ.get("GUBER_COMPILE_LEDGER", "1") != "0"


class _LedgerHandler(logging.Handler):
    """Parses compile records into the owning ledger.  Never raises —
    a logging handler that throws poisons every subsequent log call."""

    def __init__(self, ledger: "CompileLedger"):
        super().__init__(level=logging.DEBUG)
        self._ledger = ledger

    def emit(self, record: logging.LogRecord) -> None:
        try:
            m = _COMPILE_RX.match(record.getMessage())
            if m:
                self._ledger._record_compile(m.group(1))
        except Exception:  # noqa: BLE001 - see class docstring
            pass


class CompileLedger:
    """Per-process compile counts + steady-state verdict.

    install()/uninstall() are idempotent; counts survive uninstall (a
    bench run uninstalls nothing, tests uninstall in teardown).
    """

    def __init__(self) -> None:
        self._mu = threading.Lock()
        self._counts: Dict[str, int] = {}  # guarded-by: self._mu
        self._steady_base: Optional[Dict[str, int]] = None  # guarded-by: self._mu
        self._handler: Optional[_LedgerHandler] = None  # guarded-by: self._mu
        self._prev_level: Optional[int] = None  # guarded-by: self._mu
        self._prev_propagate: Optional[bool] = None  # guarded-by: self._mu
        #: weakrefs to attached Metrics objects (per-instance
        #: registries; a 3-daemon test cluster attaches three)
        self._metrics: List[weakref.ref] = []  # guarded-by: self._mu

    # -- install / uninstall --------------------------------------------

    def install(self) -> bool:
        """Attach the handler to the jax compile logger.  Returns True
        when installed (or already was), False when jax never created
        the logger in this process (nothing to observe yet is fine —
        logging.getLogger creates it eagerly, so this is always True
        in practice)."""
        with self._mu:
            if self._handler is not None:
                return True
            lg = logging.getLogger(_JAX_COMPILE_LOGGER)
            self._handler = _LedgerHandler(self)
            self._prev_level = lg.level
            self._prev_propagate = lg.propagate
            lg.addHandler(self._handler)
            # DEBUG so the per-compile records reach the handler;
            # propagate off so the raised level doesn't leak compile
            # spam to stderr via the root handler while we listen
            lg.setLevel(logging.DEBUG)
            lg.propagate = False
            return True

    def uninstall(self) -> None:
        with self._mu:
            if self._handler is None:
                return
            lg = logging.getLogger(_JAX_COMPILE_LOGGER)
            lg.removeHandler(self._handler)
            if self._prev_level is not None:
                lg.setLevel(self._prev_level)
            if self._prev_propagate is not None:
                lg.propagate = self._prev_propagate
            self._handler = None
            self._prev_level = None
            self._prev_propagate = None

    @property
    def installed(self) -> bool:
        with self._mu:
            return self._handler is not None

    # -- recording ------------------------------------------------------

    def _record_compile(self, fn: str) -> None:
        with self._mu:
            self._counts[fn] = self._counts.get(fn, 0) + 1
            sinks = [m() for m in self._metrics]
            self._metrics = [r for r, m in zip(self._metrics, sinks)
                             if m is not None]
        for m in sinks:  # metric bump outside _mu: leaf lock stays leaf
            if m is not None:
                try:
                    m.jit_compiles.labels(fn=fn).inc()
                except Exception:  # noqa: BLE001 - a torn-down registry
                    # must not break compile accounting
                    pass

    def attach_metrics(self, metrics) -> None:
        """Mirror per-fn compile counts into ``metrics.jit_compiles``
        (held weakly: a closed instance's registry just drops off)."""
        with self._mu:
            if any(r() is metrics for r in self._metrics):
                return
            self._metrics.append(weakref.ref(metrics))

    # -- reading --------------------------------------------------------

    def counts(self) -> Dict[str, int]:
        with self._mu:
            return dict(self._counts)

    def total(self) -> int:
        with self._mu:
            return sum(self._counts.values())

    def reset(self) -> None:
        """Test hook: forget everything (counts AND steady mark)."""
        with self._mu:
            self._counts = {}
            self._steady_base = None

    # -- steady-state verdict -------------------------------------------

    def mark_steady(self) -> None:
        """Declare warmup over: compiles past this point are verdict
        failures.  Re-marking moves the baseline forward."""
        with self._mu:
            self._steady_base = dict(self._counts)

    def steady_compiles(self) -> Dict[str, int]:
        """Per-fn compiles since :meth:`mark_steady` (empty before the
        mark, and empty is the healthy answer after it)."""
        with self._mu:
            if self._steady_base is None:
                return {}
            out = {}
            for fn, n in self._counts.items():
                d = n - self._steady_base.get(fn, 0)
                if d > 0:
                    out[fn] = d
            return out

    def verdict(self) -> Dict[str, object]:
        """The bench/tier-1 provenance block: did the steady-state
        service path recompile?"""
        with self._mu:
            marked = self._steady_base is not None
            total = sum(self._counts.values())
            recompiles: Dict[str, int] = {}
            if marked:
                for fn, n in self._counts.items():
                    d = n - self._steady_base.get(fn, 0)
                    if d > 0:
                        recompiles[fn] = d
        return {
            "enabled": enabled(),
            "installed": self.installed,
            "marked_steady": marked,
            "total_compiles": total,
            "steady_recompiles": recompiles,
            "steady": marked and not recompiles,
        }


#: process-wide singleton: XLA compiles are process-wide events, so a
#: per-instance ledger would double-count a shared logger anyway
LEDGER = CompileLedger()


def install_if_enabled() -> bool:
    """Instance-construction hook: install the singleton unless
    GUBER_COMPILE_LEDGER=0.  Returns whether the ledger is live."""
    if not enabled():
        return False
    return LEDGER.install()
