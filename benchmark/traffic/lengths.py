"""Lengths shared by the request generators: a clipped lognormal."""
import numpy as np


def lognormal_ints(rng, spec, n):
    """n whole numbers, lognormal with the spec's median and sigma,
    clipped to [min, max]."""
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)
