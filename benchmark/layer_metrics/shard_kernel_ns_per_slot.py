"""Device time of the Mosaic decision kernel per slot it was launched
over, on a mesh: the kernel's time summed over EVERY device plane of
the profile ÷ (its calls summed over the planes × the mean slots a
shard of a device wave between the profile's two scrapes —
Δ``gubernator_wave_slots_total`` ÷ the configuration's chips ÷
Δ``gubernator_wave_route_total``).  A slot is a row or padding: every
chip runs the kernel over the bucket of the wave's densest shard
(``shard_cost``).  A program without the counters reads nothing."""
from benchmark.harness import shard_cost


def read(ctx):
    got = shard_cost.kernel_planes(ctx)
    slots = shard_cost.per_device_wave(ctx, shard_cost.SLOTS)
    if not got or not slots:
        return None
    seconds, calls = got
    return 1e9 * seconds / (calls * slots / ctx["config"]["chips"])
