"""Share (%) of the traced window in which the first chip ran no
operation while a host thread was inside one of the program's spans named
in `spans` (and, with `excluding`, inside none of those: a span's self
time, e.g. `serving::admit` without the `serving::spill`s it holds).

`trace._gaps` names an idle gap by the innermost host event, which is
JAX's own (`np.asarray`, `PjitFunction`), never the program's enclosing
span, and one such name covers several causes. The program's spans
(`paddle_tpu.profiler.RecordEvent`, a `jax.profiler.TraceAnnotation`
while a profiler session runs) lie in the `/host:` planes of the same
`.xplane.pb` as the device's `XLA Ops`, on one clock. So this reader
takes its own pass over the file that `trace.Session` left under
`<root>/.bench_trace/<cell>/`: the union of the named spans' intervals,
intersected with the complement of the chip's merged `XLA Ops` intervals
(pauses under `trace.MIN_GAP_NS` are launch latency, not idling, as in
`trace._gaps`), over the window `trace.reduce_xspace` reports. The file
is parsed once a run and kept on `obs`.

None where no trace was taken, the trace holds no span of those names
(a program without them: the parent of the PR that added the spans), or
no operation ran on a chip.
"""
import glob
import os
import re
import time

from benchmark import trace

KEY = "span_idle.parsed"        # on obs: one parse serves every metric
# the program's span names, `layer::what` in lower case; the runtime's own
# host events (`PjRtCpuExecutable::Execute`) are not
PROGRAM_SPAN = re.compile(r"^[a-z_]+::[a-z_]+$")


def parse(pd):
    """-> {"window": (lo, hi) ns over every event, "idle": the first
    chip's idle intervals, "spans": {name: [(start, end), ...]} of the
    host planes' events named like the program's spans (PROGRAM_SPAN)}
    or None if no operation ran on a chip."""
    lo = hi = None
    busy, spans = None, {}
    for plane in pd.planes:
        is_dev = plane.name.startswith("/device:TPU:")
        is_host = plane.name.startswith("/host:")
        chip = []
        for line in plane.lines:
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                lo = s if lo is None else min(lo, s)
                hi = e if hi is None else max(hi, e)
                if is_dev and line.name == trace.OPS_LINE:
                    chip.append((s, e))
                elif is_host and PROGRAM_SPAN.match(ev.name):
                    spans.setdefault(ev.name, []).append((s, e))
        if chip and busy is None:
            busy = trace._merge(chip)
    if busy is None:
        return None
    edges = [lo] + [t for s, e in busy for t in (s, e)] + [hi]
    idle = [(g0, g1) for g0, g1 in zip(edges[0::2], edges[1::2])
            if g1 - g0 >= trace.MIN_GAP_NS]
    return {"window": (lo, hi), "idle": idle, "spans": spans}


def _overlap(a, b):
    """Total length of the intersection of two sorted lists of disjoint
    intervals."""
    total, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _minus(a, b):
    """The parts of the sorted disjoint intervals `a` outside `b`."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append((s, b[k][0]))
            s = max(s, b[k][1])
            k += 1
        if s < e:
            out.append((s, e))
    return out


def share(parsed, spans, excluding=()):
    """Idle time of the chip under `spans` (less `excluding`) over the
    window, in %; None if the trace holds no span of those names."""
    named = [iv for n in spans for iv in parsed["spans"].get(n, ())]
    if not named:
        return None
    inside = trace._merge(named)
    if excluding:
        inside = _minus(inside, trace._merge(
            [iv for n in excluding for iv in parsed["spans"].get(n, ())]))
    lo, hi = parsed["window"]
    return 100.0 * _overlap(parsed["idle"], inside) / (hi - lo)


def read(obs, ctx, spans, excluding=()):
    if not obs.get("trace"):
        return None
    if KEY not in obs:
        from jax.profiler import ProfileData
        files = glob.glob(os.path.join(ctx.root, ".bench_trace",
                                       ctx.cell_name, "**", "*.xplane.pb"),
                          recursive=True)
        t0 = time.perf_counter()
        obs[KEY] = parse(ProfileData.from_file(files[0])) \
            if len(files) == 1 else None
        found = {n: len(v) for n, v in
                 sorted((obs[KEY] or {}).get("spans", {}).items())}
        ctx.log(f"trace parsed for the program's spans in "
                f"{time.perf_counter() - t0:.1f}s: {found}")
    return share(obs[KEY], spans, excluding) if obs[KEY] else None
