"""Mean wait of a job from submit to its wave launching:
gubernator_dispatcher_queue_wait over the window (closed loop)."""
from benchmark.harness.scrape import hist_mean


def read(ctx):
    if ctx["traffic"]["loop"] != "closed":
        return None
    v = hist_mean(ctx["m0"], ctx["m1"], "gubernator_dispatcher_queue_wait")
    return None if v is None else 1000.0 * v
