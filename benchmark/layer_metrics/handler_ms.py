"""Mean time inside the routing layer's entry,
instance.get_rate_limits_wire (benchmark span, closed loop)."""
import numpy as np


def read(ctx):
    if ctx["traffic"]["loop"] != "closed":
        return None
    spans = ctx["spans"].within("instance.get_rate_limits_wire",
                                ctx["start_at"], ctx["end"])
    return float(1000.0 * np.mean(spans)) if spans else None
