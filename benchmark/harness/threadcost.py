"""Readers over the PROGRAM's thread ledger (``gubernator_tpu/tracing.py ›
ThreadLedger``; roles in OBSERVABILITY.md, "Thread roles"): what the
daemon's threads used of the host between two scrapes, by role.

The ledger's series are totals read from ``/proc/self/task`` at the
scrape itself (which two scrapes: ``scrapes``):
``gubernator_thread_cpu_seconds_total{role}`` (on a CPU) and
``gubernator_thread_ledger_clock_seconds``, the ledger's own clock —
deltas are divided by ITS delta, not by the scrapes' (a scrape inside
0.5 s of the last repeats its totals).  The ledger's run-queue wait,
wake-ups and the worker's context switches have NO reader here: the
kernel of the machines the benchmark runs on (gVisor) has no
``schedstat`` and counts no switches, so the program exports CPU alone
there (from ``stat``: utime + stime, 10-ms ticks — sums over seconds).

The Python roles share one GIL, so their CPU over an interval is at
most ~one core's worth of GIL time plus what ran with the GIL released;
the ``native-*`` roles are the threads Python does not know (gRPC core,
the XLA / TPU runtime).  A program without the ledger has none of this:
every reader here then returns ``None``.

Also here, because ``progspans.py`` may not be edited: the part of the
device's idle time that the annotation ``worker.gap`` covers and no
group of ``progspans.idle_by_group`` took.
"""
from __future__ import annotations

import re
import sys

import numpy as np

from benchmark.harness import progspans, scrape, tracered

CPU = "gubernator_thread_cpu_seconds_total"
CLOCK = "gubernator_thread_ledger_clock_seconds"
NATIVE = "native-"
GAP = "worker.gap"

_ROLE = re.compile(r'role="([^"]+)"')


def scrapes(ctx) -> tuple:
    """The two scrapes the ledger's readers take: the window's first
    and — in a traced run, the only kind that reports per-layer metrics
    — the one taken as the profiler starts, 0.3 of the window in; the
    window's last where there is no profile.  Not the whole window: the
    profiler's export runs for seconds on the benchmark's main thread,
    which shares the daemon's process and would be counted as the
    daemon's `py-other`; nor is the daemon being profiled then."""
    return ctx["m0"], ctx.get("tm0", ctx["m1"])


def elapsed(m0: dict, m1: dict):
    """Seconds between the two reads of the ledger the scrapes hold, or
    ``None`` (no ledger, or both scrapes hold the same read)."""
    if CLOCK not in m0 or CLOCK not in m1:
        return None
    dt = m1[CLOCK] - m0[CLOCK]
    return dt if dt > 0 else None


def by_role(m0: dict, m1: dict, family: str):
    """role → Δ of ``family`` between the scrapes, or ``None``."""
    if elapsed(m0, m1) is None:
        return None
    out = {}
    for key, val in m1.items():
        if key.startswith(family + "{"):
            role = _ROLE.search(key).group(1)
            out[role] = out.get(role, 0.0) + val - m0.get(key, 0.0)
    return out or None


def side(deltas: dict, native: bool) -> float:
    """Σ over the Python roles, or over the ``native-*`` ones."""
    return sum(v for r, v in deltas.items()
               if r.startswith(NATIVE) == native)


def cores(ctx, native: bool):
    """CPU the Python (or the native) roles used between the scrapes ÷
    the ledger's elapsed time: cores."""
    m0, m1 = scrapes(ctx)
    cpu = by_role(m0, m1, CPU)
    if cpu is None:
        return None
    return side(cpu, native) / elapsed(m0, m1)


def python_cpu_share(ctx, role: str):
    """``role``'s share of the CPU the Python roles used, in %."""
    cpu = by_role(*scrapes(ctx), CPU)
    if cpu is None or role not in cpu:
        return None
    whole = side(cpu, native=False)
    return 100.0 * cpu[role] / whole if whole > 0 else None


def phase_cpu_ms_per_sample(ctx, name: str):
    """Thread CPU of phase ``name`` a sample, in ms — for a phase EVERY
    sample of which records CPU (``handler``, ``local.pack``)."""
    label = f'phase="{name}"'
    m0, m1 = ctx["m0"], ctx["m1"]
    if not any(k.startswith(progspans.CPU_SECONDS) and label in k
               for k in m1):
        return None
    n = progspans.samples(ctx, name)
    if n <= 0:
        return None
    return 1000.0 * scrape.delta(m0, m1, progspans.CPU_SECONDS, label) / n


def calls_answered(ctx, seconds: float) -> int:
    """Calls answered in the window's first ``seconds`` (the client's
    records; the window's first scrape is taken at its start)."""
    rec = ctx["rec"]
    return int(np.count_nonzero(
        rec["ok"] & (rec["done"] >= ctx["start_at"])
        & (rec["done"] <= min(ctx["start_at"] + seconds, ctx["end"]))))


# ---- the device's idle time under the annotation worker.gap ------------

def idle_worker_gap(rows: list):
    """Event rows (``tracered.load_xplane``) → ``{"idle", "gap",
    "unattributed", "annotated"}`` in seconds: the device's idle time
    (first device plane), the part of it that NO group of
    ``progspans.idle_by_group`` took and that lies under an annotation
    ``worker.gap`` (any thread), all that no group took, and all the
    annotation covers, idle device or not.  ``None`` when the trace
    holds no device op or no ``worker.gap`` annotation."""
    planes: dict = {}
    for r in rows:
        if r[0].startswith(tracered.DEVICE_PLANE) \
                and r[1] == tracered.OPS_LINE:
            planes.setdefault(r[0], []).append(r)
    gap = progspans._named(rows, (GAP,))
    if not planes or not len(gap):
        return None
    lo = min(r[3] for rs in planes.values() for r in rs)
    hi = max(r[3] + r[4] for rs in planes.values() for r in rs)
    first = planes[sorted(planes)[0]]
    idle = tracered.complement(tracered.union(np.array(
        [[r[3], r[3] + r[4]] for r in first]).reshape(-1, 2)), lo, hi)
    taken = progspans._named(
        rows, progspans.LAUNCH_SIDE + progspans.SYNC_SIDE
        + progspans.NO_WORK, progspans.LOCK_PREFIX)
    rest = tracered.intersect(idle, tracered.complement(taken, lo, hi))
    return {"idle": tracered.measure(idle) / 1e9,
            "gap": tracered.measure(tracered.intersect(rest, gap)) / 1e9,
            "unattributed": tracered.measure(rest) / 1e9,
            "annotated": tracered.measure(gap) / 1e9}


def idle_worker_gap_share(ctx):
    """The idle time no group took and ``worker.gap`` covers ÷ the idle
    time, in %; says so on standard error where it is more than 5
    points from what no group took (``idle_unattributed_share``).
    ``None`` also where the annotations are not the gap's INTERVALS: a
    program before PR 37 annotated only the hand-over of the phase's
    sum, microseconds a wave, so they cover next to nothing of what
    phase ``worker.gap`` summed between the two scrapes taken while the
    profile recorded."""
    got = idle_worker_gap(tracered.load_xplane(ctx["trace_dir"]))
    if not got or got["idle"] <= 0:
        return None
    tm0, tm1 = ctx.get("tm0"), ctx.get("tm1")
    if tm0 is not None and tm1 is not None and got["annotated"] < 0.5 \
            * scrape.delta(tm0, tm1, progspans.DURATION + "_sum",
                           progspans._label(GAP)):
        return None
    share = 100.0 * got["gap"] / got["idle"]
    rest = 100.0 * got["unattributed"] / got["idle"]
    if abs(share - rest) > 5.0:
        print(f"idle_worker_gap_share {share:.1f} % is not the "
              f"unattributed idle time {rest:.1f} %: {rest - share:.1f} "
              "points of the idle device lie under no phase and no "
              "worker.gap annotation", file=sys.stderr, flush=True)
    return share
