"""Open loop in bursts: `open_loop_poisson`'s schedule and driver with
the arrival rate switched on and off by the mix's `burst` (`drive` is
a copy of that module's, which finds its schedule as a module global and
is not this PR's to edit; benchmark/rehearsal/test_dsv2_cell.py holds
the two texts equal until the benchmark gives `drive` a `schedule=`):

    "burst": {"on_s": 2, "off_s": 4, "on_factor": 3}

For `on_s` seconds requests arrive as a Poisson process at `on_factor` x
`rate_rps`, for `off_s` seconds none do, and so on; `on_factor` must be
(on_s + off_s) / on_s, so that the mean rate over a cycle is `rate_rps`
and a sweep over rates means what it means for the steady mix. Where
the first cycle starts (the phase) comes from the mix's `shape_seed`,
like the arrivals and the lengths: every run offers the same bursts at
the same instants; the run's seed draws the token ids.
"""
import threading
import time

import numpy as np

from benchmark.traffic.lengths import lognormal_ints


def cycle(mix):
    """(on_s, period, seconds into a cycle at which the run starts)."""
    b = mix["burst"]
    period = b["on_s"] + b["off_s"]
    if abs(b["on_factor"] * b["on_s"] - period) > 1e-9 * period:
        raise ValueError(f"burst {b}: on_factor must be (on_s + off_s) / "
                         f"on_s for the mean rate to be rate_rps")
    phase = np.random.default_rng([mix["shape_seed"], 7]).uniform(0, period)
    return b["on_s"], period, float(phase)


def schedule(mix, seconds):
    """[(due_s, prompt_len, max_tokens)] for due_s in [0, ramp + seconds):
    a Poisson process on the clock of the on phases alone, laid out over
    the cycles."""
    horizon = mix["ramp_s"] + seconds
    on_s, period, phase = cycle(mix)
    on_rate = mix["rate_rps"] * mix["burst"]["on_factor"]
    arr = np.random.default_rng([mix["shape_seed"], 1])
    due, u = [], 0.0
    while True:
        u += arr.exponential(1.0) / on_rate
        t = (u // on_s) * period + u % on_s - phase
        if t >= horizon:
            break
        if t >= 0.0:
            due.append(t)
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
