"""Mean host time of the engine's launch of one wave (routing, fill of
the upload buffers, dispatch): benchmark span round ``launch_packed``
(pipelined waves) and ``check_prepacked`` (inline waves)."""
import numpy as np


def read(ctx):
    s = ctx["spans"]
    d = (s.within("engine.launch_packed", ctx["start_at"], ctx["end"])
         + s.within("engine.check_prepacked", ctx["start_at"], ctx["end"]))
    return float(1000.0 * np.mean(d)) if d else None
