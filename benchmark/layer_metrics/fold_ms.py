"""Device time of one GLOBAL reconcile fold (the psum program), from the
trace's module line; only a mesh-GLOBAL cell has one."""


def read(ctx):
    tr = ctx["trace"]
    if not tr.get("fold_calls"):
        return None
    return 1000.0 * tr["fold_s"] / tr["fold_calls"]
