"""``{"dist": "zipf_drift", "a": 1.1, "space": 100000000, "hot": 2048,
"every_calls": 40, "start": 20000000, "stride": 2048}``: ``zipf_space``'s
draw (numpy's unbounded Zipf(a) modulo a key SPACE larger than the
resident population) whose HEAD moves — the ``hot`` hottest ranks are a
tenant that is awake, and every ``every_calls`` calls of a caller the
next tenant wakes on the next ``stride`` keys.

With ``z = rng.zipf(a, n)``: a draw with ``z > hot`` is ``z % space``,
``zipf_space``'s draw unchanged (the static tail); a draw with
``z <= hot`` is ``z`` itself during step 0 and
``(start + (step - 1) * stride + z) % space`` from step 1 on, where
``step`` = the number of draws THIS ``rng`` has been asked for so far
``// every_calls``.  The phase is counted in calls, per ``rng`` object,
inside this file, and never read from a clock: every seed offers the
same work a request whatever the server's pace, and two fresh
``caller_rng(seed, i)`` give the same sequence of draws.  The callers
step on their own counts, so a transition smears over the time their
call counts differ by, as tenants do.

At a = 1.1 the 2,048 hottest ranks take 55.9 % of draws (H(2048, 1.1) /
zeta(1.1) = 5.918 / 10.584); the other 44.1 % are the static tail."""
import numpy as np

#: draws asked of each rng so far: id(rng) -> [rng, calls].  The rng is
#: held beside its count (a Generator takes no weak reference), so an
#: id is never reused for another stream while its count is kept.
_CALLS: dict = {}


def sample(rng: np.random.Generator, params: dict, n: int,
           population: int) -> np.ndarray:
    """n key indices in [0, space)."""
    space = params["space"]
    if space < population:
        raise ValueError(f"key space {space} is smaller than the "
                         f"population's {population} resident keys")
    seen = _CALLS.setdefault(id(rng), [rng, 0])
    step = seen[1] // params["every_calls"]
    seen[1] += 1
    z = rng.zipf(params["a"], n)
    if step:
        base = params["start"] + (step - 1) * params["stride"]
        z = np.where(z <= params["hot"], base + z, z)
    return (z % space).astype(np.int64)
