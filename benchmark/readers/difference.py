"""(statistic a - statistic b) x scale: what one layer adds on top of the
layer below it, each as `series.py` reads it."""
from benchmark.stats import stat


def read(obs, ctx, a, b, scale=1.0):
    va, vb = stat(obs, **a), stat(obs, **b)
    return None if va is None or vb is None else (va - vb) * scale
