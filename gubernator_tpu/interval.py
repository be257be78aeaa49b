"""Interval ticker driving the async managers.

reference: interval.go › Interval (holster clock-based ticker used by
global.go's runAsyncHits/runBroadcasts — reconstructed).  `wait()` blocks
until the next period boundary or `stop()`; background managers loop on
it.  A test clock can be injected for deterministic tests.
"""
from __future__ import annotations

import threading
import time
from typing import Callable


class Interval:
    """Periodic wakeup with early-fire support.

    ``wait()`` returns True on a tick, False once stopped.  ``fire()``
    wakes the waiter immediately (used to flush queues on demand or at
    shutdown, like the reference's batch-full early flush).
    """

    def __init__(self, period_ms: int,
                 now_fn: Callable[[], float] = time.monotonic):
        self.period_s = max(period_ms, 1) / 1000.0
        self._now = now_fn
        self._ev = threading.Event()
        self._stopped = False

    def wait(self) -> bool:
        if self._stopped:
            return False
        fired = self._ev.wait(self.period_s)
        if self._stopped:
            return False
        if fired:
            self._ev.clear()
        return True

    def fire(self) -> None:
        self._ev.set()

    def stop(self) -> None:
        self._stopped = True
        self._ev.set()


class IntervalLoop:
    """A daemon thread running ``fn()`` on every tick of an Interval.

    The analog of the reference's `go manager.run()` goroutines; `close()`
    runs one final ``fn()`` so pending queues flush at shutdown
    (global.go drains before exit).
    """

    def __init__(self, period_ms: int, fn: Callable[[], None], name: str):
        self.interval = Interval(period_ms)
        self._fn = fn
        # "tick:": the thread ledger's role of every IntervalLoop
        # thread (tracing.THREAD_ROLES)
        self._thread = threading.Thread(target=self._run,
                                        name=f"tick:{name}", daemon=True)
        self._thread.start()

    def _run(self) -> None:
        while self.interval.wait():
            try:
                self._fn()
            except Exception:  # pragma: no cover - logged, loop survives
                import logging

                logging.getLogger("gubernator_tpu").exception(
                    "interval loop %s", self._thread.name)

    def poke(self) -> None:
        self.interval.fire()

    @staticmethod
    def _drain_timeout_s() -> float:
        """Per-loop drain bound: GUBER_DRAIN_GRACE when set (the
        operator's whole-daemon drain budget — one wedged loop must
        not eat more than it), else 5 s."""
        import os

        raw = os.environ.get("GUBER_DRAIN_GRACE", "")
        if raw:
            try:
                from .config import parse_duration_ms

                ms = parse_duration_ms(raw)
                if ms > 0:
                    return ms / 1000.0
            except ValueError:
                pass
        return 5.0

    def close(self, timeout_s: float | None = None) -> None:
        self.interval.stop()
        self._thread.join(timeout=self._drain_timeout_s()
                          if timeout_s is None else timeout_s)
        if self._thread.is_alive():
            # a wedged fn() (dead-peer RPC with no deadline, device
            # stall) must not hang shutdown — and running the final
            # flush CONCURRENTLY with the wedged tick would race the
            # very queues it flushes, so skip it and say so
            import logging

            logging.getLogger("gubernator_tpu").warning(
                "interval loop %s did not drain within its bound; "
                "skipping the final flush", self._thread.name)
            return
        try:
            self._fn()  # final flush
        except Exception:  # pragma: no cover
            pass
