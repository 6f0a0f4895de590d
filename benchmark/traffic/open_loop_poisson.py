"""Open loop: requests are sent on a schedule whether or not earlier
ones have finished, each from a thread of its own.

The schedule (arrival instants, prompt length and max_tokens of each
arrival) comes from the mix's `shape_seed`, NOT from the run's seed: at
four fifths of the knee the tail of time-to-first-token depends on
which long prompt lands in which burst, so the order is part of the
cell's definition and every run offers the same work at the same
instants. The run's seed draws the token ids (and the weights). Another
order is another mix file with another `shape_seed`: data only.

Arrivals are unit exponentials divided by `rate_rps`, so a sweep over
rates replays the same sizes in the same order, only faster.
"""
import threading
import time

import numpy as np

from benchmark.traffic.lengths import lognormal_ints


def schedule(mix, seconds):
    """[(due_s, prompt_len, max_tokens)] for due_s in [0, ramp + seconds)."""
    horizon = mix["ramp_s"] + seconds
    arr = np.random.default_rng([mix["shape_seed"], 1])
    due, t = [], 0.0
    while True:
        t += arr.exponential(1.0) / mix["rate_rps"]
        if t >= horizon:
            break
        due.append(t)
    # a stream of its own for each quantity, so a shorter run is a prefix
    plen, mtok = (lognormal_ints(np.random.default_rng([mix["shape_seed"], k]),
                                 mix[what], len(due))
                  for k, what in ((2, "prompt_len"), (3, "max_tokens")))
    return [(d, int(p), int(m)) for d, p, m in zip(due, plen, mtok)]


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
