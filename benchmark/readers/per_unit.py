"""(Sum of the window's counters in `over`) / counter `by` x `scale`: a
cost per unit of work, e.g. seconds the engine spent planning per unified
step (x 1000: ms a step); `ratio.py` with a unit. The counters are the
program's own, cumulative, taken as the difference of two `snapshot()`s
(kinds/serve_http.py's `EngineWindow` keeps every top-level number). None
where `by` counted nothing or a counter is missing, so a program that has
no such counter (the parent of the PR that added it) leaves the metric
out of the line."""
from benchmark.readers import ratio


def read(obs, ctx, source, over, by, scale=1.0):
    value = ratio.read(obs, ctx, source, over, by)
    return None if value is None else value * scale
