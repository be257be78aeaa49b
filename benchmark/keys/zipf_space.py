"""``{"dist": "zipf_space", "a": 1.1, "space": 100000000}``: numpy's
unbounded Zipf(a) taken modulo a key SPACE that is larger than the
population the configuration holds resident — indices below the
population are its resident keys, those from it up to ``space`` keys
the daemon has not seen, whose rows the window creates.  ``space`` below
the population is an error.

At a = 1.1, space 100M, population 10M (numpy, 16M draws, seed 1): 17.0 %
of draws fall on keys that are not resident (14.7 % are draws ≥ 100M
that wrap round the space), ~173 of a 1000-request call and ~1,350 of
the ~4,300 distinct keys of an 8,000-row wave; key 1 takes 9.5 % of all
draws and the ten hottest 25 %."""
import numpy as np


def sample(rng: np.random.Generator, params: dict, n: int,
           population: int) -> np.ndarray:
    """n key indices in [0, space)."""
    space = params["space"]
    if space < population:
        raise ValueError(f"key space {space} is smaller than the "
                         f"population's {population} resident keys")
    return (rng.zipf(params["a"], n) % space).astype(np.int64)
