"""Open loop over shared prefixes: every request is one of a few long
system prompts plus a fresh tail, sent on a Poisson schedule whether or
not earlier ones have finished.

As in open_loop_poisson.py the SCHEDULE comes from the mix's
`shape_seed`: arrival instants, which system prompt each arrival takes
(Zipf over `system_prompts.count`), its tail length and max_tokens; and
so do the system prompts' own token ids, which are part of the mix (the
same few prompts in every run). The run's seed draws the tails' token
ids (and the weights). Arrivals are unit exponentials divided by
`rate_rps`, so a sweep replays the same requests, only faster.

Before the schedule starts, every system prompt is sent once (with the
shortest tail, one new token), all together at the ramp's first instant,
each from a thread of its own: eight prompts of 1024 tokens prefill
side by side in about nine steps, well inside the ramp, so a prefix
cache holds each when the window opens, as a server's does that has
been up for a while. Those requests are not counted, like everything
else due inside the ramp; `drive` reports when the last of them
returned (`residents_done_s`, from the ramp's start) and prints it.
"""
import threading
import time

import numpy as np

from benchmark.traffic.lengths import lognormal_ints


def zipf_shares(n, s):
    w = 1.0 / np.arange(1, n + 1) ** s
    return w / w.sum()


def system_prompts(mix, vocab):
    """The mix's system prompts, [count][tokens] token ids."""
    sp = mix["system_prompts"]
    rng = np.random.default_rng([mix["shape_seed"], 5])
    return rng.integers(0, vocab, size=(sp["count"], sp["tokens"]))


def schedule(mix, seconds):
    """[(due_s, system prompt, tail_len, max_tokens)] for due_s in
    [0, ramp + seconds)."""
    horizon = mix["ramp_s"] + seconds
    arr = np.random.default_rng([mix["shape_seed"], 1])
    due, t = [], 0.0
    while True:
        t += arr.exponential(1.0) / mix["rate_rps"]
        if t >= horizon:
            break
        due.append(t)
    # a stream of its own for each quantity, so a shorter run is a prefix
    tail, mtok = (lognormal_ints(np.random.default_rng([mix["shape_seed"], k]),
                                 mix[what], len(due))
                  for k, what in ((2, "tail_len"), (3, "max_tokens")))
    sp = mix["system_prompts"]
    which = np.random.default_rng([mix["shape_seed"], 4]).choice(
        sp["count"], size=len(due), p=zipf_shares(sp["count"], sp["zipf"]))
    return [(d, int(w), int(n), int(m))
            for d, w, n, m in zip(due, which, tail, mtok)]


def drive(mix, seed, seconds, vocab, send, cut, on_window_start,
          on_window_end):
    """The same contract as open_loop_poisson.drive."""
    plan = schedule(mix, seconds)
    heads = system_prompts(mix, vocab)
    tok = np.random.default_rng([seed, 4])
    prompts = [heads[w].tolist() + tok.integers(0, vocab, size=n).tolist()
               for _, w, n, _ in plan]
    first = [h.tolist() + tok.integers(
        0, vocab, size=mix["tail_len"]["min"]).tolist() for h in heads]
    ramp, records, threads = mix["ramp_s"], [None] * len(plan), []

    def client(rec):
        rec["sent"] = time.perf_counter()
        rec.update(send(rec["prompt"], rec["max_tokens"], mix["stream"]))

    resident_at = []

    def resident(p):
        send(p, 1, False)
        resident_at.append(time.perf_counter())

    began = time.perf_counter()
    t0 = began + 0.05
    warm = [threading.Thread(target=resident, args=(p,), daemon=True)
            for p in first]
    for th in warm:
        th.start()
    marks = [(ramp, on_window_start), (ramp + seconds, on_window_end)]
    events = sorted([(d, i) for i, (d, _, _, _) in enumerate(plan)]
                    + [(t, -1 - k) for k, (t, _) in enumerate(marks)])
    for due, i in events:
        wait = t0 + due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        if i < 0:
            marks[-1 - i][1]()
            continue
        records[i] = {"due": t0 + due, "prompt": prompts[i],
                      "prompt_len": len(prompts[i]),
                      "max_tokens": plan[i][3], "system_prompt": plan[i][1]}
        th = threading.Thread(target=client, args=(records[i],), daemon=True)
        threads.append(th)
        th.start()
    deadline = time.perf_counter() + mix["drain_s"]
    for th in threads + warm:
        th.join(max(0.0, deadline - time.perf_counter()))
    cut()
    done = max(resident_at) - began if len(resident_at) == len(first) else None
    print(f"sessions: {len(resident_at)} of {len(first)} system prompts "
          f"returned" + ("" if done is None else f", the last {done:.2f} s")
          + f" after the ramp's start (ramp {ramp} s)", flush=True)
    late = [r["sent"] - r["due"] for r in records if "sent" in r]
    counted = [dict(r) for (d, _, _, _), r in zip(plan, records)
               if d >= ramp]
    return {"records": counted, "lateness_s": late, "offered": len(plan),
            "residents_done_s": done}
