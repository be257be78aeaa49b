"""Reduction of a profiler trace to the numbers the per-layer metrics
read.  Works on a plain table of events — ``[plane, line, name,
start_ns, duration_ns]`` rows — so that it can be checked on a small
recorded trace (``benchmark/tests/data``) without a chip.
"""
from __future__ import annotations

import glob
import os
import re

import numpy as np

DEVICE_PLANE = "/device:TPU:"
#: the line of a device plane that holds one event per executed op
OPS_LINE = "XLA Ops"
#: the Mosaic decision kernel is the serving program's custom call to
#: this target (XLA's own small custom calls have other targets)
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
_OP = re.compile(r"^%?(\S+) = .*?\s([a-z][\w\-.]*)\(")
#: the GLOBAL tier's fold program, by the name jit gives it
FOLD_MODULE_MARK = "_fold"
MODULES_LINE = "XLA Modules"


def load_xplane(trace_dir: str) -> list:
    """The newest ``*.xplane.pb`` under ``trace_dir`` → event rows."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no xplane file under {trace_dir}")
    rows = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        for line in plane.lines:
            for ev in line.events:
                rows.append([plane.name, line.name, ev.name,
                             float(ev.start_ns), float(ev.duration_ns)])
    return rows


def short_name(name: str) -> str:
    """An op's HLO text → ``<result name> <opcode>``."""
    m = _OP.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:60]


def union(iv: np.ndarray) -> np.ndarray:
    """[n, 2] intervals → sorted disjoint [m, 2]."""
    if len(iv) == 0:
        return np.zeros((0, 2))
    iv = iv[np.argsort(iv[:, 0])]
    end = np.maximum.accumulate(iv[:, 1])
    first = np.r_[True, iv[1:, 0] > end[:-1]]
    starts = iv[first, 0]
    ends = end[np.r_[np.flatnonzero(first)[1:] - 1, len(iv) - 1]]
    return np.stack([starts, ends], axis=1)


def measure(iv: np.ndarray) -> float:
    return float((iv[:, 1] - iv[:, 0]).sum()) if len(iv) else 0.0


def intersect(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Two sorted disjoint interval sets → their intersection."""
    out = []
    i = j = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i, 0], b[j, 0]), min(a[i, 1], b[j, 1])
        if lo < hi:
            out.append((lo, hi))
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return np.array(out).reshape(-1, 2)


def complement(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """Gaps of a sorted disjoint set inside [lo, hi]."""
    edges = np.r_[lo, np.clip(iv, lo, hi).reshape(-1), hi]
    gaps = edges.reshape(-1, 2)
    return gaps[gaps[:, 1] > gaps[:, 0]]


def _intervals(rows) -> np.ndarray:
    return np.array([[r[3], r[3] + r[4]] for r in rows]).reshape(-1, 2)


def reduce(rows: list, span_names=()) -> dict:
    """Event rows → busy/idle, per-op seconds, kernel and fold time and
    the device's idle time by the host span that was open.  The window
    is the span from the first to the last device op (what lies outside
    is the profiler starting and stopping)."""
    planes: dict = {}
    for r in rows:
        if r[0].startswith(DEVICE_PLANE) and r[1] == OPS_LINE:
            planes.setdefault(r[0], []).append(r)
    if not planes:
        return {"devices": 0}
    lo = min(r[3] for rs in planes.values() for r in rs)
    hi = max(r[3] + r[4] for rs in planes.values() for r in rs)
    busy = [measure(union(_intervals(rs))) for rs in planes.values()]
    first = planes[sorted(planes)[0]]
    by_op: dict = {}
    for r in first:
        op = short_name(r[2])
        by_op[op] = by_op.get(op, 0.0) + r[4]
    kernel = [r for r in first if KERNEL_MARK in r[2]]
    fold = [r for r in rows if r[0] == sorted(planes)[0]
            and r[1] == MODULES_LINE and FOLD_MODULE_MARK in r[2]]
    idle = complement(union(_intervals(first)), lo, hi)
    by_span = {}
    covered = np.zeros((0, 2))
    for name in span_names:
        iv = union(_intervals([r for r in rows if r[2] == name
                               and not r[0].startswith(DEVICE_PLANE)]))
        if len(iv):
            by_span[name] = measure(intersect(idle, iv)) / 1e9
            covered = union(np.concatenate([covered, iv]))
    by_span["(no span open)"] = (measure(idle)
                                 - measure(intersect(idle, covered))) / 1e9
    top = lambda d: sorted(([k, v] for k, v in d.items() if v > 0),  # noqa: E731
                           key=lambda kv: -kv[1])[:10]
    return {
        "devices": len(planes),
        "window_s": (hi - lo) / 1e9,
        "busy_s": float(np.mean(busy)) / 1e9,
        "device_ops": top({k: v / 1e9 for k, v in by_op.items()}),
        "idle_gaps": top(by_span),
        "kernel_s": sum(r[4] for r in kernel) / 1e9,
        "kernel_calls": len(kernel),
        "fold_s": sum(r[4] for r in fold) / 1e9,
        "fold_calls": len(fold),
    }


def kernel_rows(trace: dict, m0: dict, m1: dict):
    """Rows the decision kernel served inside the trace, on the chip
    whose plane ``reduce`` read: its kernel calls × the mean rows of a
    wave between two scrapes taken while the profiler recorded (every
    wave, inline or queued, is one launch of the step program and one
    observation of ``gubernator_dispatcher_wave_size``).  Counting the
    waves themselves between scrapes would take rows and kernel time
    from two different intervals."""
    from benchmark.harness.scrape import hist_mean

    per_wave = hist_mean(m0, m1, "gubernator_dispatcher_wave_size")
    if not trace.get("kernel_calls") or not per_wave:
        return None
    return trace["kernel_calls"] * per_wave / trace["devices"]
