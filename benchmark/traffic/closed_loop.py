"""Closed loop: `clients` callers, each sending its next request when
the last one returns. Every client's sequence of prompt lengths comes
from the mix's `shape_seed` (the same work in the same order in every
run, see open_loop_poisson.py); the run's seed draws the token ids.

A request counts if it COMPLETED inside the window. What is in flight
when the window ends is cut and counted as neither done nor failed.
"""
import threading
import time

import numpy as np

from benchmark.traffic.lengths import lognormal_ints

_PER_CLIENT = 4096      # lengths drawn per client; far more than a run uses


def drive(mix, seed, seconds, vocab, send, cut, on_window_start,
          on_window_end):
    n = mix["clients"]
    rng = np.random.default_rng([mix["shape_seed"], 2])
    plen = lognormal_ints(rng, mix["prompt_len"], n * _PER_CLIENT)
    mtok = lognormal_ints(rng, mix["max_tokens"], n * _PER_CLIENT)
    stop = threading.Event()
    records = [[] for _ in range(n)]

    def client(c):
        tok = np.random.default_rng([seed, 4, c])
        for k in range(_PER_CLIENT):
            if stop.is_set():
                return
            j = c * _PER_CLIENT + k
            prompt = tok.integers(0, vocab, size=int(plen[j])).tolist()
            rec = {"sent": time.perf_counter(), "prompt": prompt,
                   "prompt_len": int(plen[j]), "max_tokens": int(mtok[j])}
            rec.update(send(prompt, int(mtok[j]), mix["stream"]))
            if stop.is_set() and rec["error"]:
                return                      # cut at the window's end
            records[c].append(rec)

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(n)]
    for th in threads:
        th.start()
    time.sleep(mix["ramp_s"])
    t_start = time.perf_counter()
    on_window_start()
    time.sleep(max(0.0, t_start + seconds - time.perf_counter()))
    t_end = time.perf_counter()
    stop.set()
    on_window_end()
    cut()
    for th in threads:
        th.join(30)
    counted = [r for rs in records for r in rs
               if t_start <= r["t_done"] < t_end]
    return {"records": counted, "lateness_s": [], "offered": len(counted)}
