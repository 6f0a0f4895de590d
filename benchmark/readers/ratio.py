"""Sum of the window's counters in `over` divided by the counter `by`."""
from benchmark.stats import stat


def read(obs, ctx, source, over, by):
    num = [stat(obs, source, s, "value") for s in over]
    den = stat(obs, source, by, "value")
    if den in (None, 0) or any(v is None for v in num):
        return None
    return sum(num) / den
