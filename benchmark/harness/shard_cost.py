"""What the shard route in front of the sharded table costs, and what
the Mosaic decision kernel has to move on a mesh — the shared part of
``layer_metrics/shard_pad_share.py``, ``shard_skew.py``,
``shard_kernel_ns_per_slot.py`` and ``shard_kernel_roofline.py``.

A daemon over several chips routes every wave by shard
(``gubernator_tpu/parallel/sharded.py › _build_waves``): the rows are
argsorted by the shard of their key and scattered into an upload pair
``shards × bucket`` slots wide, the bucket the smallest of the engine's
ladder that covers the DENSEST shard.  Every chip then runs the kernel
over ``bucket`` slots, rows or padding.  The program counts, once a
device wave at ``ShardedEngine._count_route``: the slots uploaded
(``gubernator_wave_slots_total``), the rows among them
(``gubernator_wave_routed_rows_total``), the rows of the densest shard
(``gubernator_wave_densest_shard_rows_total``), beside the device wave
itself (``gubernator_wave_route_total{route}``).  A program without
these counters — the parent of PR 33 — gives every reader here nothing.

The kernel's figures read EVERY device plane of the profile:
``harness/tracered.py › reduce`` reads the first plane and takes its
rows as an even share of a wave, which is what a skewed key draw
breaks.  The mark is ``tracered.KERNEL_MARK``; a whole-table sweep that
falls into the 3-s profile (one tick in 30 s) is a Mosaic call too and
would be counted with the kernel, as it is in ``kernel_ns_per_row``.
"""
from __future__ import annotations

import numpy as np

from benchmark.harness import scrape, tracered
from benchmark.harness.kernel_cost import BUCKET_BYTES

SLOTS = "gubernator_wave_slots_total"
ROUTED_ROWS = "gubernator_wave_routed_rows_total"
DENSEST_ROWS = "gubernator_wave_densest_shard_rows_total"
DEVICE_WAVES = "gubernator_wave_route_total"


def kernel_planes(ctx):
    """(device seconds, calls) of the decision kernel summed over every
    device plane of the profile — chip-seconds — or None where the
    profile holds none.  The profile is read once a run."""
    if "_shard_kernel" not in ctx:
        mine = [r[4] for r in tracered.load_xplane(ctx["trace_dir"])
                if r[0].startswith(tracered.DEVICE_PLANE)
                and r[1] == tracered.OPS_LINE
                and tracered.KERNEL_MARK in r[2]]
        ctx["_shard_kernel"] = (sum(mine) / 1e9, len(mine))
    seconds, calls = ctx["_shard_kernel"]
    return (seconds, calls) if calls and seconds > 0 else None


def per_device_wave(ctx, counter: str):
    """Mean of ``counter`` a DEVICE wave between the two scrapes taken
    while the profiler recorded (one launch of the step program, so one
    kernel call a chip, a device wave), or None."""
    waves = scrape.delta(ctx["tm0"], ctx["tm1"], DEVICE_WAVES)
    got = scrape.delta(ctx["tm0"], ctx["tm1"], counter)
    return got / waves if waves > 0 and got > 0 else None


def distinct_keys_per_row(key_index: np.ndarray, n_per_call: np.ndarray,
                          wave_rows: float) -> float:
    """DISTINCT keys of a wave ÷ its rows, over the window's calls.
    Which calls shared a wave is taken as ``xla_cost.step_bytes_per_row``
    takes it: the mix draws every call's keys independently, so the
    calls in file order, ``round(wave_rows ÷ rows a call)`` at a time,
    hold as many distinct keys as the waves that served them."""
    rows = int(n_per_call.sum())
    if rows == 0 or len(key_index) != rows or not wave_rows:
        return 0.0
    per_wave = max(1, int(round(wave_rows * len(n_per_call) / rows)))
    wave = np.repeat(np.arange(len(n_per_call)) // per_wave, n_per_call)
    return len(np.unique(np.stack([wave, key_index]), axis=1).T) / rows


def wave_bytes_per_row(key_index: np.ndarray, n_per_call: np.ndarray,
                       wave_rows: float) -> float:
    """Mean HBM bytes of TABLE traffic a served row cannot avoid on the
    sharded Pallas table: one 8-KiB bucket (a key's whole probe window,
    ``kernel_cost.BUCKET_BYTES``) read and written back once for every
    DISTINCT key of a wave — distinct over the WHOLE wave, whatever
    shard, bucket of the ladder or 128-row tile a row rode: the least
    any tiling by shard can move, so a floor, and the share made from
    it a USEFUL-bytes share as ``xla_step_roofline`` is.  The kernel
    moves more (a key once a TILE it appears in, and the tiles of
    padding cost time and no bytes), so padding shows as a LOW share.
    Two keys of a wave in one of 2^19 buckets count twice here and move
    once where they share a tile: a few parts in a thousand."""
    return 2.0 * BUCKET_BYTES * distinct_keys_per_row(
        key_index, n_per_call, wave_rows)
