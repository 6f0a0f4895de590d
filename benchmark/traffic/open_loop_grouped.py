"""Open loop, requests of like length together: `open_loop_poisson`'s
schedule (the same arrival instants, the same multiset of (prompt
length, max_tokens) pairs, both from the mix's `shape_seed`) with the
pairs RE-DEALT to the instants so that requests of like length arrive
one after another, which is what a replica sees behind a router that
sends it one length class at a time:

    "group": 6

The schedule's pairs are sorted by prompt length (a stable sort: equal
lengths keep their order), cut into consecutive groups of `group`, and
the groups laid over the unchanged instants in an order drawn from
`shape_seed`; inside a group the pairs keep their sorted order. The
work offered and when it is offered are those of the ungrouped mix; only
which request arrives at which instant differs. (`drive` is a copy of
`open_loop_poisson.drive`, which finds its schedule as a module global
and is not this PR's to edit; benchmark/rehearsal/test_keye_cell.py
holds the two texts equal, as test_dsv2_cell.py holds
`open_loop_bursts.drive`.)
"""
import threading
import time

import numpy as np

from benchmark.traffic import open_loop_poisson


def schedule(mix, seconds):
    """[(due_s, prompt_len, max_tokens)] for due_s in [0, ramp + seconds):
    `open_loop_poisson.schedule`'s instants, its pairs re-dealt by
    length."""
    plan = open_loop_poisson.schedule(mix, seconds)
    pairs = sorted(((p, m) for _, p, m in plan), key=lambda pm: pm[0])
    size = int(mix["group"])
    groups = [pairs[i:i + size] for i in range(0, len(pairs), size)]
    order = np.random.default_rng([mix["shape_seed"], 8]) \
        .permutation(len(groups))
    dealt = [pm for g in order for pm in groups[g]]
    return [(d, p, m) for (d, _, _), (p, m) in zip(plan, dealt)]


def drive(mix, seed, seconds, vocab, send, cut, on_window_start,
          on_window_end):
    """Replays the schedule against `send(prompt, max_tokens, stream)`,
    waits at most `drain_s` after the window for what is in flight, then
    `cut()`s the rest (a record without `t_done` never returned).
    Returns the records of the requests DUE inside the window, each with
    `due` (absolute, host clock), `sent`, and what `send` returned, plus
    the generator's lateness over all requests."""
    plan = schedule(mix, seconds)
    tok = np.random.default_rng([seed, 4])
    prompts = [tok.integers(0, vocab, size=p).tolist() for _, p, _ in plan]
    ramp, records, threads = mix["ramp_s"], [None] * len(plan), []

    def client(rec):
        rec["sent"] = time.perf_counter()
        rec.update(send(rec["prompt"], rec["max_tokens"], mix["stream"]))

    t0 = time.perf_counter() + 0.05
    marks = [(ramp, on_window_start), (ramp + seconds, on_window_end)]
    events = sorted([(d, i) for i, (d, _, _) in enumerate(plan)]
                    + [(t, -1 - k) for k, (t, _) in enumerate(marks)])
    for due, i in events:
        wait = t0 + due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        if i < 0:
            marks[-1 - i][1]()
            continue
        records[i] = {"due": t0 + due, "prompt": prompts[i],
                      "prompt_len": plan[i][1], "max_tokens": plan[i][2]}
        th = threading.Thread(target=client, args=(records[i],), daemon=True)
        threads.append(th)
        th.start()
    deadline = time.perf_counter() + mix["drain_s"]
    for th in threads:
        th.join(max(0.0, deadline - time.perf_counter()))
    cut()
    late = [r["sent"] - r["due"] for r in records if "sent" in r]
    counted = [dict(r) for (d, _, _), r in zip(plan, records) if d >= ramp]
    return {"records": counted, "lateness_s": late, "offered": len(plan)}
