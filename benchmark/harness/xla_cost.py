"""What the XLA engine's step program has to move, and where a profile
and the program's phases say how long it and the sweep took — the
shared part of ``layer_metrics/xla_step_ns_per_row.py``,
``xla_step_roofline.py`` and ``sweep_ms.py``.

The table of ``GUBER_ENGINE=xla`` is nine ``[rows]`` columns under open
addressing (``gubernator_tpu/core/table.py › TableState``): ``key``
uint64, ``meta`` int32 and seven int64 columns, 68 B a row.  The step
(``core/step.py › decide_batch_impl``) looks a key up along its probe
sequence in the key column, gathers the row of every DISTINCT key of
the wave, decides, and scatters the four columns a decision dirties
(``meta``, ``remaining``, ``t_ms``, ``expire_at``) back.  It has no
Mosaic call: its time is the device time of its whole XLA MODULE, which
a profile's "XLA Modules" line holds under the jit's name.

Every count here is a FLOOR of the traffic — the USEFUL bytes, what the
served rows cannot avoid — so ``xla_step_roofline`` is a useful-bytes
share: it cannot pass 100 %, and it does not say how busy HBM is.  A
step that moves O(table) bytes it could avoid (XLA:TPU's 64-bit
split/combine of whole columns, PERF.md §5) reads LOW here while it
saturates HBM; what the module really moves is in its
``memory_analysis`` and in the profile's ops, not in this count.
"""
from __future__ import annotations

import numpy as np

from benchmark.harness import progspans, scrape, tracered

#: the classic step's jit, by the name the program gives it
#: (``parallel/sharded.py › make_sharded_step_packed``); a program whose
#: step has another name has no such module, and the readers read nothing
STEP_MODULE_MARK = "xla_step_packed"
#: the program's phase round ``engine.sweep``
SWEEP_PHASE = "sweep"

KEY_BYTES = 8
ROW_BYTES = 8 + 4 + 7 * 8
DIRTY_BYTES = 4 + 3 * 8
#: the two packed uploads ([8, B] int64 + [3, B] int32) and the one
#: download ([5, B] int64), a row
UPLOAD_BYTES = 8 * 8 + 3 * 4
DOWNLOAD_BYTES = 5 * 8


def step_modules(ctx):
    """(device seconds, executions) of the step program's modules in
    the profile, on the first device plane (as ``tracered.reduce``
    takes it), or None where the profile holds none.  The profile is
    read once a run."""
    if "_xla_step_modules" not in ctx:
        rows = [r for r in tracered.load_xplane(ctx["trace_dir"])
                if r[0].startswith(tracered.DEVICE_PLANE)
                and r[1] == tracered.MODULES_LINE
                and STEP_MODULE_MARK in r[2]]
        plane = min((r[0] for r in rows), default=None)
        mine = [r[4] for r in rows if r[0] == plane]
        ctx["_xla_step_modules"] = (sum(mine) / 1e9, len(mine))
    seconds, calls = ctx["_xla_step_modules"]
    return (seconds, calls) if calls and seconds > 0 else None


def rows_per_wave(ctx):
    """Mean rows of a wave between the two scrapes taken while the
    profiler recorded (one execution of the step program a wave)."""
    return scrape.hist_mean(ctx["tm0"], ctx["tm1"],
                            "gubernator_dispatcher_wave_size")


def step_bytes_per_row(key_index: np.ndarray, n_per_call: np.ndarray,
                       wave_rows: float) -> float:
    """Mean HBM bytes a served row cannot avoid.  A row: its share of
    the uploads and of the download.  A DISTINCT key of a wave: one
    8-byte read of the key column (its first probe; deeper probes only
    add), its row's 68 B read, its four dirty columns' 28 B written.
    Which calls shared a wave is not known to the client, and need not
    be: the mix draws every call's keys independently, so the calls in
    file order, ``round(wave_rows ÷ rows a call)`` at a time, hold as
    many distinct keys as the waves that served them."""
    rows = int(n_per_call.sum())
    if rows == 0 or len(key_index) != rows:
        return 0.0
    per_wave = max(1, int(round(wave_rows * len(n_per_call) / rows)))
    wave = np.repeat(np.arange(len(n_per_call)) // per_wave, n_per_call)
    distinct = len(np.unique(np.stack([wave, key_index]), axis=1).T)
    return (UPLOAD_BYTES + DOWNLOAD_BYTES
            + (KEY_BYTES + ROW_BYTES + DIRTY_BYTES) * distinct / rows)


def sweep_ms(ctx):
    """Mean HOST wall time of the program's phase round ``engine.sweep``
    between the window's scrapes, in ms (None: no sweep fell into the
    window, or the program has no such phase).  Not a device time: the
    sweep's program queues behind the waves in flight, and the phase
    ends when its live count is back on the host — how long serving
    waits, which moves with the step as much as with the sweep."""
    return progspans.ms_per_sample(ctx, SWEEP_PHASE)
