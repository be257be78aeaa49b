"""Readers over the PROGRAM's own phases (``gubernator_tpu/tracing.py ›
phase``; catalog in OBSERVABILITY.md), as against ``spans.py``, which
times whole methods from outside.

A phase is two things a benchmark can read: a sample in
``gubernator_phase_duration{phase}`` (always on; read between the
window's two scrapes) and a ``TraceAnnotation`` under its exact name in
the host planes of the profile (``--trace 1``; on the device trace's
clock).  A program without these phases has neither: every reader here
then returns ``None``.
"""
from __future__ import annotations

import numpy as np

from benchmark.harness import scrape, tracered

DURATION = "gubernator_phase_duration"
CPU_SECONDS = "gubernator_phase_cpu_seconds_total"
#: wall seconds of exactly the samples that also recorded CPU time
CPU_WALL_SECONDS = "gubernator_phase_cpu_wall_seconds_total"
WAVES = "gubernator_dispatcher_wave_duration_count"

#: the dispatch worker's phases, by the side of a wave they lie on
LAUNCH_SIDE = ("wave.begin", "wave.concat", "wave.route", "wave.fill",
               "wave.dispatch")
LOCK_PREFIX = "lock."
SYNC_SIDE = ("wave.sync", "wave.scatter", "wave.resolve", "wave.end")
NO_WORK = ("worker.wait", "worker.coalesce")


def _label(name: str) -> str:
    """The label text that selects phase ``name`` (a name that ends in
    a dot selects the whole family: ``lock.``, ``route.``)."""
    return f'phase="{name}' + ("" if name.endswith(".") else '"')


def seconds(ctx, *names) -> float:
    return sum(scrape.delta(ctx["m0"], ctx["m1"], DURATION + "_sum",
                            _label(n)) for n in names)


def samples(ctx, *names) -> float:
    return sum(scrape.delta(ctx["m0"], ctx["m1"], DURATION + "_count",
                            _label(n)) for n in names)


def ms_per_wave(ctx, *names):
    """Seconds the window spent in the named phases ÷ the window's
    dispatcher waves, in ms.  Per WAVE whatever the phases' own sample
    counts are (a wave over the largest bucket is several device waves,
    each with a fill, a dispatch and a sync), so that the parts add up
    to what ``spans.py`` times round the wave's launch and sync."""
    waves = scrape.delta(ctx["m0"], ctx["m1"], WAVES)
    if waves <= 0 or samples(ctx, *names) <= 0:
        return None
    return 1000.0 * seconds(ctx, *names) / waves


def ms_per_sample(ctx, name: str):
    """Mean of one phase's own samples (a per-call phase), in ms."""
    n = samples(ctx, name)
    return 1000.0 * seconds(ctx, name) / n if n > 0 else None


def share_of_worker(ctx, *names):
    """The named phases' share of the dispatch worker's time between
    the scrapes, in %.  The worker's phases partition its wall time
    (``tracing.partition_thread``), so their sum IS the interval the
    scrapes enclose, whenever each was answered."""
    whole = seconds(ctx, "worker.", "wave.", LOCK_PREFIX)
    if whole <= 0 or samples(ctx, *names) <= 0:
        return None
    return 100.0 * seconds(ctx, *names) / whole


def wait_share(ctx, *names):
    """1 − thread CPU seconds ÷ wall seconds over the samples of the
    named phases that recorded both (every call's ``route.*``; the
    phases of the 1 wave in 16 the dispatcher samples): the part of
    their wall time the thread did not run — waiting for the GIL or a
    lock — in %."""
    both = lambda family: sum(  # noqa: E731
        scrape.delta(ctx["m0"], ctx["m1"], family, _label(n))
        for n in names)
    wall = both(CPU_WALL_SECONDS)
    return 100.0 * (1.0 - both(CPU_SECONDS) / wall) if wall > 0 else None


# ---- the device's idle time, by the program phase that was open --------

def _named(rows, names=(), prefix=None) -> np.ndarray:
    keep = [r for r in rows
            if not r[0].startswith(tracered.DEVICE_PLANE)
            and (r[2] in names
                 or (prefix is not None and r[2].startswith(prefix)))]
    return tracered.union(np.array(
        [[r[3], r[3] + r[4]] for r in keep]).reshape(-1, 2))


def idle_by_group(rows: list):
    """Event rows (``tracered.load_xplane``) → seconds of the device's
    idle time (first device plane, as ``tracered.reduce`` takes it) by
    the ONE group each nanosecond goes to: ``launch_side`` if a
    launch-side or lock phase is open in any thread, else ``sync_side``
    if a sync-side one is, else ``no_work`` if the worker waits or
    coalesces, else ``unattributed``.  ``None`` when the trace holds no
    device op or none of the program's phases."""
    planes: dict = {}
    for r in rows:
        if r[0].startswith(tracered.DEVICE_PLANE) \
                and r[1] == tracered.OPS_LINE:
            planes.setdefault(r[0], []).append(r)
    if not planes:
        return None
    lo = min(r[3] for rs in planes.values() for r in rs)
    hi = max(r[3] + r[4] for rs in planes.values() for r in rs)
    first = planes[sorted(planes)[0]]
    idle = tracered.complement(tracered.union(np.array(
        [[r[3], r[3] + r[4]] for r in first]).reshape(-1, 2)), lo, hi)
    groups = (("launch_side", _named(rows, LAUNCH_SIDE, LOCK_PREFIX)),
              ("sync_side", _named(rows, SYNC_SIDE)),
              ("no_work", _named(rows, NO_WORK)))
    if not any(len(iv) for _, iv in groups):
        return None
    out = {"idle": tracered.measure(idle) / 1e9}
    rest = idle
    for name, iv in groups:
        out[name] = tracered.measure(tracered.intersect(rest, iv)) / 1e9
        rest = tracered.intersect(rest, tracered.complement(iv, lo, hi))
    out["unattributed"] = tracered.measure(rest) / 1e9
    return out


def idle_share(ctx, group: str):
    """``group``'s share of the device's idle time in the traced part
    of the window, in %; the profile is loaded once a run."""
    if "_idle_by_group" not in ctx:
        ctx["_idle_by_group"] = idle_by_group(
            tracered.load_xplane(ctx["trace_dir"]))
    got = ctx["_idle_by_group"]
    if not got or got["idle"] <= 0:
        return None
    return 100.0 * got[group] / got["idle"]
