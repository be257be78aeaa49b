"""How late the generator sent: 99th percentile of send time − due time
(open loop only; a closed loop has no due time)."""
import numpy as np


def read(ctx):
    if ctx["traffic"]["loop"] != "open":
        return None
    rec = ctx["rec"]
    return float(np.percentile(1000.0 * (rec["send"] - rec["due"]), 99))
