"""What the front door adds (gRPC transport, worker-thread queue, wire
codec outside the handler): mean client round trip − mean handler span,
over the window of the traced run (closed loop)."""
import numpy as np


def read(ctx):
    if ctx["traffic"]["loop"] != "closed":
        return None
    rec = ctx["rec"]
    sel = (rec["ok"] & (rec["done"] >= ctx["start_at"])
           & (rec["done"] <= ctx["end"]))
    spans = ctx["spans"].within("instance.get_rate_limits_wire",
                                ctx["start_at"], ctx["end"])
    if not sel.any() or not spans:
        return None
    return float(1000.0 * (np.mean(rec["done"][sel] - rec["send"][sel])
                           - np.mean(spans)))
