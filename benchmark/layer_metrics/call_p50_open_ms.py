"""50th percentile of an open-loop call's latency from its DUE time
(client clock, every answered call of the window)."""
import numpy as np

from benchmark.harness import e2e


def read(ctx):
    if ctx["traffic"]["loop"] != "open":
        return None
    lat = e2e.latencies_ms(ctx, ctx["traffic"])
    return float(np.percentile(lat, 50)) if len(lat) else None
