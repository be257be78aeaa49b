"""The Mosaic decision kernel's share of its HBM roofline on a mesh:
the least chip-seconds the traced waves' table traffic needs
(``shard_cost.wave_bytes_per_row``: one 8-KiB bucket read and written a
DISTINCT key of a wave, from the window's own calls, × the rows of the
traced device waves — the kernel's calls over the planes ÷ chips ×
the mean rows a device wave between the profile's scrapes — ÷ 819 GB/s
a chip) ÷ the kernel's chip-seconds summed over every device plane.
Bound by memory; the count is a floor whatever bucket the wave rode,
so the share is of USEFUL bytes and padding shows as a LOW share.  A
program without the counters reads nothing."""
from benchmark.harness import peaks, scrape, shard_cost


def read(ctx):
    got = shard_cost.kernel_planes(ctx)
    rows = shard_cost.per_device_wave(ctx, shard_cost.ROUTED_ROWS)
    if not got or not rows:
        return None
    seconds, calls = got
    rec = ctx["rec"]
    per_row = shard_cost.wave_bytes_per_row(
        rec["key_index"], rec["n"], scrape.hist_mean(
            ctx["tm0"], ctx["tm1"], "gubernator_dispatcher_wave_size"))
    least_s = (per_row * rows * calls / ctx["config"]["chips"]
               / peaks.of(ctx["device_kind"])["hbm_bytes_per_s"])
    return 100.0 * least_s / seconds if per_row else None
