"""(Sum of the window's counters in `over`) / (sum of those in `of`) x
`scale`: a part of a whole whose parts are counted apart. None where a
counter is missing or the whole counted nothing."""
from benchmark.stats import stat


def read(obs, ctx, source, over, of, scale=1.0):
    num = [stat(obs, source, s, "value") for s in over]
    den = [stat(obs, source, s, "value") for s in of]
    if any(v is None for v in num + den) or not sum(den):
        return None
    return scale * sum(num) / sum(den)
