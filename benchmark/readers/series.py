"""One statistic of one series, times `scale` (1000 for seconds -> ms)."""
from benchmark.stats import stat


def read(obs, ctx, source, series, how, q=None, scale=1.0):
    v = stat(obs, source, series, how, q)
    return None if v is None else v * scale
