"""Writes BENCHMARK.json from the benchmark's own files, so that the two
cannot disagree:

    python benchmark/manifest.py            # prints it
    python benchmark/manifest.py --write    # writes ../BENCHMARK.json
    python benchmark/manifest.py --check    # fails if ../BENCHMARK.json differs

`manifest.json` holds what belongs to no cell (command, paths,
run_seconds). Everything else is read from configs/, workloads/ and
metrics/; rehearsal/ is never listed.
"""
import glob
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def _files(sub):
    for path in sorted(glob.glob(os.path.join(HERE, sub, "*.json"))):
        with open(path) as f:
            yield os.path.basename(path)[:-len(".json")], json.load(f)


def build():
    with open(os.path.join(HERE, "manifest.json")) as f:
        out = json.load(f)
    cells = dict(_files("workloads"))
    out["configs"] = [
        {"name": n, "source": c["source"], "file": f"benchmark/configs/{n}.json",
         "reduced": c["reduced"], "why": c["why"]}
        for n, c in _files("configs")
        if any(w["config"] == n for w in cells.values())]
    out["workloads"] = [
        {"name": n, "config": w["config"], "traffic": w["traffic"],
         "chips": w["chips"], "why": w["why"]} for n, w in cells.items()]
    out["end_to_end"], out["per_layer"] = [], []
    for n, m in _files("metrics"):
        every = m.get("cells") == "all"
        where = sorted(set(() if every else m.get("cells", ()))
                       | {c for c, w in cells.items()
                          if n in w.get("metrics", ())})
        entry = {"name": n, "unit": m["unit"], "better": m["better"]}
        if m["group"] == "end_to_end":
            entry.update(bound=m["bound"], source=m["source"])
        else:
            entry.update(source=m["source"], layer=m["layer"],
                         moves=m["moves"])
        if not every:
            entry["workloads"] = where
        out[m["group"]].append(entry)
    return out


def main(argv):
    text = json.dumps(build(), indent=1) + "\n"
    target = os.path.join(os.path.dirname(HERE), "BENCHMARK.json")
    if "--write" in argv:
        with open(target, "w") as f:
            f.write(text)
    elif "--check" in argv:
        with open(target) as f:
            if f.read() != text:
                raise SystemExit("BENCHMARK.json differs from the files "
                                 "under benchmark/: run manifest.py --write")
    else:
        sys.stdout.write(text)


if __name__ == "__main__":
    main(sys.argv[1:])
