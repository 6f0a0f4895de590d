"""Dev aid: the device time of each executed step program in a profiler
trace of a running engine, split by what the host packed into the step,
and whether the trace's two planes agree.

    python scripts/step_kinds_from_trace.py <file.xplane.pb> [out.json]

A `--trace 1` run of a serving cell leaves its trace under
`.bench_trace/<cell>/`. The device plane's `XLA Modules` line holds one
event an executed program; the `XLA Ops` inside its interval are summed
into: busy ms (union), ms under `ptk:ragged_walk` and under
`ptk:grouped_phase1`. Each execution is joined to the host's
`serving::launch` that started last before it, and that launch's
`tokens` argument (the packed tokens of the step, as the host planned
them) splits the steps: one that packs more than `DECODE_MAX_TOKENS`
HOLDS A CHUNK; one that packs fewer is decode-only or holds a prompt's
short tail. The step program is the module most of whose executions a
launch and the fetch after it bracket.

`clock` says whether the planes share one clock and how far the device
plane reaches: of the step's executions, how many lie between their
launch's start and the end of the fetch that follows it; the host's
launches against the device's executions; and the last device
operation's time against the end of the window (the last event of any
plane). Needs no chip: run it with JAX_PLATFORMS=cpu.
"""
import bisect
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import trace  # noqa: E402

# the most slots of a serving cell's engine: a step that packs more
# tokens than this holds a prefill chunk
DECODE_MAX_TOKENS = 16
NEEDLES = {"walk_ms": "ptk:ragged_walk", "phase1_ms": "ptk:grouped_phase1"}
LAUNCH, FETCH = "serving::launch", "serving::fetch"


def steps_of(pd):
    """-> ({module name: [{"start", "end", "busy_ms", "walk_ms",
    "phase1_ms"}]}, last device op's end in ns) for the first device
    plane that ran anything."""
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {line.name: line for line in plane.lines}
        if "XLA Modules" not in lines or trace.OPS_LINE not in lines:
            continue
        kinds = {}
        ops = []
        for ev in lines[trace.OPS_LINE].events:
            if ev.name not in kinds:
                text = trace._describe(ev)
                kinds[ev.name] = [k for k, n in NEEDLES.items() if n in text]
            ops.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                        kinds[ev.name]))
        ops.sort()
        starts = [o[0] for o in ops]
        out = {}
        for ev in lines["XLA Modules"].events:
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            rec = {"start": s, "end": e, "busy_ms": 0.0, "walk_ms": 0.0,
                   "phase1_ms": 0.0}
            edge = s
            for o0, o1, tags in ops[bisect.bisect_left(starts, s):
                                    bisect.bisect_left(starts, e)]:
                rec["busy_ms"] += max(0, o1 - max(o0, edge)) / 1e6
                edge = max(edge, o1)
                for tag in tags:
                    rec[tag] += (o1 - o0) / 1e6
            out.setdefault(ev.name, []).append(rec)
        if out:
            return out, max(o[1] for o in ops)
    return {}, None


def host_spans(pd):
    """-> ({name: sorted [(start, end, args)]} of the launches and
    fetches on the host planes, (lo, hi): the window over every event of
    every plane, as `trace.reduce_xspace` takes it)."""
    out, lo, hi = {LAUNCH: [], FETCH: []}, None, None
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                lo = s if lo is None else min(lo, s)
                hi = e if hi is None else max(hi, e)
                if plane.name.startswith("/host:") and ev.name in out:
                    out[ev.name].append((s, e, dict(ev.stats)))
    return {n: sorted(v) for n, v in out.items()}, (lo, hi)


def join(recs, spans):
    """Give each execution its launch's `tokens` and whether that launch
    and the fetch after it bracket the execution."""
    launches, fetches = spans[LAUNCH], spans[FETCH]
    l_starts = [s for s, _, _ in launches]
    f_starts = [s for s, _, _ in fetches]
    for r in recs:
        i = bisect.bisect_right(l_starts, r["start"]) - 1
        r["tokens"], r["bracketed"] = None, False
        if i < 0:
            continue
        ls, _, args = launches[i]
        r["tokens"] = args.get("tokens")
        j = bisect.bisect_left(f_starts, ls)
        r["bracketed"] = j < len(fetches) and fetches[j][1] >= r["end"]
    return recs


def summary(recs):
    def med(rows, key):
        return statistics.median(r[key] for r in rows) if rows else None
    known = [r for r in recs if r["tokens"] is not None]
    split = {"decode_only": [r for r in known
                             if r["tokens"] <= DECODE_MAX_TOKENS],
             "with_chunk": [r for r in known
                            if r["tokens"] > DECODE_MAX_TOKENS]}
    out = {"steps": len(recs), "without_launch": len(recs) - len(known)}
    for name, rows in split.items():
        out[name] = {"steps": len(rows),
                     **{k: med(rows, k) for k in
                        ("tokens", "busy_ms", "walk_ms", "phase1_ms")},
                     "busy_ms_max": max((r["busy_ms"] for r in rows),
                                        default=None)}
    out["each"] = [[r["tokens"]] + [round(r[k], 3) for k in
                                    ("busy_ms", "walk_ms", "phase1_ms")]
                   for r in recs]
    return out


def main(argv):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(argv[0])
    mods, last_op = steps_of(pd)
    spans, (lo, hi) = host_spans(pd)
    mods = {name: join(recs, spans) for name, recs in mods.items()}
    step = max(mods, default=None,
               key=lambda n: sum(r["bracketed"] for r in mods[n]))
    res = {}
    if step is not None:
        recs = mods[step]
        res[step] = summary(recs)
        res["clock"] = {
            "step_executions": len(recs),
            "bracketed": sum(r["bracketed"] for r in recs),
            "host_launches": len(spans[LAUNCH]),
            "launches_after_last_op": sum(
                s > last_op for s, _, _ in spans[LAUNCH]),
            "window_ms": (hi - lo) / 1e6,
            "last_op_to_window_end_ms": (hi - last_op) / 1e6}
    text = json.dumps(res)
    if len(argv) > 1:
        with open(argv[1], "w") as f:
            f.write(text + "\n")
    for name, s in res.items():
        print(name, json.dumps({k: v for k, v in s.items() if k != "each"}))
    if not res:
        print("no step program on a device plane; modules:",
              {n: len(r) for n, r in mods.items()})


if __name__ == "__main__":
    main(sys.argv[1:])
