"""Share of the traced window in which no operation ran on the device:
1 − union of device-op intervals ÷ window, averaged over the chips."""


def read(ctx):
    tr = ctx["trace"]
    if not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
