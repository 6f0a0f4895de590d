"""Dev aid: the device time of each executed step program in a profiler
trace of a running engine, and the page walk's part of it.

    python scripts/step_kinds_from_trace.py <file.xplane.pb> [out.json]

A `--trace 1` run of a serving cell leaves its trace under
`.bench_trace/<cell>/`. The device plane's `XLA Modules` line holds one
event an executed program; the `XLA Ops` inside its interval are summed
into: busy ms (union), ms under `ptk:ragged_walk` and under
`ptk:grouped_phase1`. A step is taken to HOLD A CHUNK where its walk is
more than `CHUNK_FACTOR` times the median step's (a decoding row's
query block computes over 16 rows of its key blocks, a chunk's over all
its rows); the split is by what it measures, so read the lists, not only
the two medians: where every step holds a chunk (`docs_backlog`) the
median step is one of them and the split says nothing. Needs no chip: run it with JAX_PLATFORMS=cpu.
"""
import bisect
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import trace  # noqa: E402

CHUNK_FACTOR = 2.0
NEEDLES = {"walk_ms": "ptk:ragged_walk", "phase1_ms": "ptk:grouped_phase1"}


def steps_of(pd):
    """-> {module name: [{"start_ms", "busy_ms", "walk_ms", "phase1_ms"}]}
    for the first device plane that ran anything."""
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
            rec = {"start_ms": s / 1e6, "busy_ms": 0.0, "walk_ms": 0.0,
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
            return out
    return {}


def summary(recs):
    def med(rows, key):
        return statistics.median(r[key] for r in rows) if rows else None
    cut = CHUNK_FACTOR * med(recs, "walk_ms")
    split = {"decode_only": [r for r in recs if r["walk_ms"] <= cut],
             "with_chunk": [r for r in recs if r["walk_ms"] > cut]}
    out = {"steps": len(recs), "walk_ms_cut": cut}
    for name, rows in split.items():
        out[name] = {"steps": len(rows),
                     **{k: med(rows, k)
                        for k in ("busy_ms", "walk_ms", "phase1_ms")},
                     "busy_ms_max": max((r["busy_ms"] for r in rows),
                                        default=None)}
    out["each"] = [[round(r[k], 3) for k in ("busy_ms", "walk_ms",
                                              "phase1_ms")] for r in recs]
    return out


def main(argv):
    from jax.profiler import ProfileData
    mods = steps_of(ProfileData.from_file(argv[0]))
    # the step program is the one that ran the walk
    res = {name: summary(recs) for name, recs in mods.items()
           if any(r["walk_ms"] for r in recs)}
    text = json.dumps(res)
    if len(argv) > 1:
        with open(argv[1], "w") as f:
            f.write(text + "\n")
    for name, s in res.items():
        print(name, json.dumps({k: v for k, v in s.items() if k != "each"}))
    if not res:
        print("no module ran a page walk; modules:",
              {n: len(r) for n, r in mods.items()})


if __name__ == "__main__":
    main(sys.argv[1:])
