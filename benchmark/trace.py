"""From a profiler trace to numbers: device busy and idle time, the
device operations that took most time, the longest idle gaps by what
the host was doing, and the share of busy time under named kernels.

`reduce_xspace` works on `jax.profiler.ProfileData` (the `.xplane.pb`
the JAX profiler writes) and on nothing else, so it can be checked on a
small synthetic trace (rehearsal/test_rehearsal.py). `Session` takes a
short trace inside a run's window.

Device planes are named `/device:TPU:<n>`; their line `XLA Ops` holds
one event per executed HLO operation. Busy time is the union of those
events' intervals. Host planes are named `/host:...`, one line a thread.
"""
import bisect
import glob
import os
import re
import shutil
import threading
import time

TRACE_SECONDS = 4.0         # long enough for some tens of steps
OPS_LINE = "XLA Ops"
MIN_GAP_NS = 20_000         # shorter pauses are launch latency, not idling


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def short_name(name):
    """An operation's name for the breakdown. The trace names an XLA
    operation by its whole HLO text; the 24 layers' copies of one
    operation differ only in their numbers. So: the instruction's name
    without its number, its opcode and its result's shape without
    layout, e.g. `call custom-call bf16[8,16,16,8,128]`."""
    m = re.match(r"%([\w\-]+?)(?:\.\d+)* = (.*?) ?([\w\-]+)\(",
                 re.sub(r"\{[^{}]*\}", "", name))
    return f"{m[1]} {m[3]} {m[2]}"[:100] if m else name[:100]


def _describe(ev):
    """Name plus every string the event carries (the HLO text and the
    JAX name stack that hold a Pallas kernel's function name)."""
    parts = [ev.name]
    for k, v in ev.stats:
        if isinstance(v, str):
            parts.append(f"{k}={v}")
    return " ".join(parts)


def reduce_xspace(pd):
    """-> {"window_s", "busy_s" (mean over the chips that ran anything),
    "chips", "ops": {name: seconds, summed over chips},
    "text": {name: description}, "gaps": {host activity: idle seconds on
    the first chip}} or None if no device operation ran."""
    lo, hi = None, None
    host, per_chip = [], []
    ops, text = {}, {}
    for plane in pd.planes:
        is_dev = plane.name.startswith("/device:TPU:")
        is_host = plane.name.startswith("/host:")
        chip = []
        for line in plane.lines:
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                lo = s if lo is None else min(lo, s)
                hi = e if hi is None else max(hi, e)
                if is_dev and line.name == OPS_LINE:
                    chip.append((s, e))
                    name = short_name(ev.name)
                    if name not in text:
                        text[name] = _describe(ev)
                    ops[name] = ops.get(name, 0.0) + (e - s) / 1e9
                elif is_host:
                    host.append((s, e, ev.name))
        if chip:
            per_chip.append(_merge(chip))
    if not per_chip:
        return None
    busy = [sum(e - s for s, e in m) / 1e9 for m in per_chip]
    return {"window_s": (hi - lo) / 1e9, "busy_s": sum(busy) / len(busy),
            "chips": len(per_chip), "ops": ops, "text": text,
            "gaps": _gaps(per_chip[0], host, lo, hi)}


def _gaps(merged, host, lo, hi):
    """Idle intervals of one chip, each named by the host event that
    overlaps it most (the shortest such, so the innermost span wins),
    summed by name."""
    host = sorted(h for h in host if h[1] - h[0] < (hi - lo) / 2)
    starts = [h[0] for h in host]
    edges = [lo] + [t for s, e in merged for t in (s, e)] + [hi]
    out = {}
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        if g1 - g0 < MIN_GAP_NS:
            continue
        best, name = (0, 0), "no host event"
        j = bisect.bisect_left(starts, g1)
        for s, e, n in host[max(0, j - 400):j]:
            ov = min(e, g1) - max(s, g0)
            if ov > 0 and (ov, s - e) > best:
                best, name = (ov, s - e), n
        out[name] = out.get(name, 0.0) + (g1 - g0) / 1e9
    return out


def share_of_busy(red, needles):
    """Share (%) of the device's busy time under operations whose name
    or description contains one of `needles`; None if none does."""
    hit = sum(sec for name, sec in red["ops"].items()
              if any(n in red["text"][name] for n in needles))
    total = red["busy_s"] * red["chips"]
    return 100.0 * hit / total if hit and total else None


def breakdown(red, top=10):
    def first(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": first(red["ops"]), "idle_gaps": first(red["gaps"])}


class Session:
    """One short profiler trace inside the window. The directory is a
    fixed path inside the checkout, emptied first."""

    def __init__(self, ctx):
        self.dir = os.path.join(ctx.root, ".bench_trace", ctx.cell_name)
        shutil.rmtree(self.dir, ignore_errors=True)
        self.log = ctx.log
        self._thread = None

    def start(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # host TraceMe spans are enough
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self):
        import jax
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        self.log(f"trace written in {time.perf_counter() - t0:.1f}s")

    def schedule(self, seconds):
        """From a thread: trace min(TRACE_SECONDS, seconds / 2), starting
        two fifths into the window."""
        def body():
            time.sleep(0.4 * seconds)
            self.start()
            time.sleep(min(TRACE_SECONDS, seconds / 2))
            self.stop()
        self._thread = threading.Thread(target=body, daemon=True)
        self._thread.start()

    def join(self):
        self._thread.join()

    def reduce(self):
        from jax.profiler import ProfileData
        files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(files) != 1:
            raise RuntimeError(f"expected one .xplane.pb under {self.dir}, "
                               f"found {files}")
        t0 = time.perf_counter()
        red = reduce_xspace(ProfileData.from_file(files[0]))
        self.log(f"trace reduced in {time.perf_counter() - t0:.1f}s "
                 f"({os.path.getsize(files[0])} bytes)")
        return red


def main(argv):
    """python benchmark/trace.py <file.xplane.pb>: what a trace holds,
    for looking at one by hand: planes, lines, event counts, the most
    frequent names of each line with one event's description, and the
    reduction."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(argv[0])
    for plane in pd.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            count, total, sample = {}, {}, {}
            for ev in line.events:
                count[ev.name] = count.get(ev.name, 0) + 1
                total[ev.name] = total.get(ev.name, 0.0) + ev.duration_ns
                if ev.name not in sample:
                    sample[ev.name] = _describe(ev)
            print(f"  line {line.name!r}: {sum(count.values())} events, "
                  f"{len(count)} names")
            for name in sorted(total, key=lambda n: -total[n])[:12]:
                print(f"    {total[name] / 1e6:10.3f} ms {count[name]:7d} x "
                      f"{sample[name][:600]}")
    red = reduce_xspace(pd)
    if red:
        print({k: red[k] for k in ("window_s", "busy_s", "chips")})
        print(breakdown(red))


if __name__ == "__main__":
    import sys
    main(sys.argv[1:])
