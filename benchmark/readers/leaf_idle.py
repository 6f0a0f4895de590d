"""`span_idle` for a leaf of the engine's thread that a short trace may
not catch at all, as `serving::wait` in a cell whose engine never runs
out of work: 0.0 where the trace holds the program's spans and none of
these, and the program counts the leaf (`counter` is among the engine's
counters of the window). None where there is nothing to read: no trace,
no operation on a chip, or a program without the leaf (the parent of
the PR that added it), so the metric is left out of that line.
"""
from benchmark.readers import span_idle
from benchmark.stats import stat


def read(obs, ctx, spans, source, counter, excluding=()):
    value = span_idle.read(obs, ctx, spans, excluding)
    if value is None and obs.get(span_idle.KEY) \
            and stat(obs, source, counter, "value") is not None:
        return 0.0
    return value
