"""Share of the window's requests served by the native columnar wire
lanes (not the pb2 object path): ``gubernator_wire_lane_requests``."""


def read(ctx):
    name = "gubernator_wire_lane_requests_total"
    m0, m1 = ctx["m0"], ctx["m1"]
    lanes = {k: v - m0.get(k, 0.0) for k, v in m1.items()
             if k.startswith(name)}
    total = sum(lanes.values())
    if total <= 0:
        return None
    return 100.0 * sum(v for k, v in lanes.items() if "pb2" not in k) / total
