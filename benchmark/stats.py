"""The arithmetic every reader shares: one statistic of one series of
the run's observations. A series is `obs[source][name]`: a list of
samples (client or engine times, seconds) or one number (a counter's
difference over the window)."""
import math


def percentile(values, q):
    """Nearest rank: the smallest sample with at least q% of the samples
    at or below it."""
    xs = sorted(values)
    return xs[min(len(xs) - 1, max(0, math.ceil(q / 100.0 * len(xs)) - 1))]


def stat(obs, source, series, how, q=None):
    """None where the run has no such series or it is empty."""
    v = (obs.get(source) or {}).get(series)
    if v is None or (isinstance(v, list) and not v):
        return None
    if how == "value":
        return v
    if how == "per_window_second":
        return v / obs["window_s"]
    if how == "percentile":
        return percentile(v, q)
    if how == "median":
        xs = sorted(v)
        mid = len(xs) // 2
        return xs[mid] if len(xs) % 2 else 0.5 * (xs[mid - 1] + xs[mid])
    raise ValueError(f"unknown statistic {how!r}")
