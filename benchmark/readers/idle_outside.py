"""Share (%) of the traced window in which the first chip ran no
operation while no host thread was inside any of the program's spans
named in `spans`: the idle that none of them names.

Given every leaf of the engine's thread, whose spans tile it, this is
near 0 unless the trace leaves part of the window out (a device plane
that ends before the host's), and what it reads is that part. It takes
the parse `span_idle` keeps on `obs` (one a run), and subtracts the
union of the named spans from the chip's idle intervals.

None where `span_idle` finds nothing to read: no trace, no operation on
a chip, or no span of those names.
"""
from benchmark import trace
from benchmark.readers import span_idle


def outside(parsed, spans):
    """Idle time of the chip outside every span of `spans`, over the
    window, in %."""
    named = trace._merge(
        [iv for n in spans for iv in parsed["spans"].get(n, ())])
    lo, hi = parsed["window"]
    return 100.0 * sum(e - s for s, e in span_idle._minus(
        parsed["idle"], named)) / (hi - lo)


def read(obs, ctx, spans):
    # parses the run's trace onto obs[span_idle.KEY] where nothing has yet
    if span_idle.read(obs, ctx, spans) is None:
        return None
    return outside(obs[span_idle.KEY], spans)
