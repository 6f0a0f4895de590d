"""kind serve_http: one ServingEngine behind serving.http.serve on a
loopback port, driven over HTTP by the cell's traffic generator.

Copied from what ran on the chip in PR 23 (chip_smoke.py: `_build_gpt`,
the engine's shape, `_complete`) and from scripts/serving_bench.py's
`http_trace` (the streaming client loop), with that loop's three faults
corrected: requests are timed from the instant they were DUE, the
generator's lateness is reported, and the model is the configuration's,
not a toy. The copies are deliberate: later PRs change the program, not
the yardstick.
"""
import http.client
import json
import threading
import time

import numpy as np

from benchmark import ref, trace
from benchmark.stats import percentile


def build_gpt(model_kwargs, dtype, seed):
    import paddle_tpu as paddle
    from paddle_tpu.nlp import GPTConfig, GPTForCausalLM
    paddle.seed(seed)
    model = GPTForCausalLM(GPTConfig(hidden_dropout_prob=0.0,
                                     attention_probs_dropout_prob=0.0,
                                     **model_kwargs))
    model.to(dtype=dtype)
    model.eval()
    return model


class Client:
    """POSTs /v1/completions on loopback and timestamps every token
    frame on the host clock. Keeps its open connections so that the
    run can cut what is in flight."""

    def __init__(self, server):
        self.host, self.port = server.server_address[:2]
        self._open, self._lock = set(), threading.Lock()

    def send(self, prompt, max_tokens, stream):
        out = {"status": None, "tokens": [], "t_tokens": [], "error": None}
        conn = http.client.HTTPConnection(self.host, self.port, timeout=600)
        with self._lock:
            self._open.add(conn)
        try:
            conn.request("POST", "/v1/completions", json.dumps(
                {"prompt": prompt, "max_tokens": max_tokens,
                 "stream": stream}), {"Content-Type": "application/json"})
            resp = conn.getresponse()
            out["status"] = resp.status
            if not stream:
                body = json.loads(resp.read())
                out["t_tokens"] = [time.perf_counter()]
                out["tokens"] = body["choices"][0]["token_ids"]
                out["finish"] = body["choices"][0]["finish_reason"]
            else:
                while True:
                    line = resp.readline()
                    if not line or line.strip() == b"data: [DONE]":
                        break
                    if not line.startswith(b"data: "):
                        continue
                    now = time.perf_counter()
                    choice = json.loads(line[6:])["choices"][0]
                    if choice["token"] is not None:
                        out["tokens"].append(choice["token"])
                        out["t_tokens"].append(now)
                    if choice.get("finish_reason"):
                        out["finish"] = choice["finish_reason"]
        except Exception as e:      # boundary: a failed request is a record
            out["error"] = f"{type(e).__name__}: {e}"
        finally:
            out["t_done"] = time.perf_counter()
            with self._lock:
                self._open.discard(conn)
            conn.close()
        return out

    def cut(self):
        """Closes every open connection: the server sees the client gone
        and frees the slot."""
        with self._lock:
            conns = list(self._open)
        for c in conns:
            try:
                if c.sock is not None:
                    c.sock.shutdown(2)
            except OSError:
                pass


def _ok(rec, vocab):
    return (rec["error"] is None and rec["status"] == 200
            and len(rec["tokens"]) == rec["max_tokens"]
            and rec.get("finish") == "length"
            and all(0 <= t < vocab for t in rec["tokens"]))


class EngineWindow:
    """Engine metrics over the window: counters as the difference of two
    snapshot()s, histograms as the samples recorded since the window's
    start (count then and now index the histogram's ring of 8192)."""
    HISTS = ("ttft_s", "queue_wait_s", "decode_step_s", "inter_token_s")

    def __init__(self, engine):
        self.m = engine.metrics

    def _take(self):
        """(snapshot, {histogram: (count, ring)}) under the metrics' lock."""
        with self.m._lock:
            return self.m._snapshot_locked(), {
                h: (getattr(self.m, h).count, list(getattr(self.m, h)._recent))
                for h in self.HISTS}

    def start(self):
        self.snap0, self.hist0 = self._take()

    def stop(self):
        snap1, hist1 = self._take()
        self.counters = {k: snap1[k] - v for k, v in self.snap0.items()
                         if isinstance(v, (int, float))
                         and not isinstance(v, bool)
                         and isinstance(snap1.get(k), (int, float))}
        self.counters["queue_depth_end"] = snap1["queue_depth"]
        self.samples = {}
        for h, (count, ring) in hist1.items():
            n = count - self.hist0[h][0]
            if n > len(ring):
                raise RuntimeError(f"{h}: {n} samples in the window, the "
                                   f"ring holds {len(ring)}")
            self.samples[h] = ring[len(ring) - n:]


class Served:
    """Model, engine, server and client, warmed: the set-up shared by a
    run and by the rate sweep."""

    def __init__(self, ctx):
        from paddle_tpu.serving import ServingEngine
        from paddle_tpu.serving.http import serve
        cfg = ctx.config
        t0 = time.perf_counter()
        self.model = build_gpt(cfg["model"], cfg["dtype"], ctx.seed)
        self.engine = ServingEngine(self.model, **cfg["engine"])
        ctx.log(f"model {cfg['model']} {cfg['dtype']}, engine "
                f"{cfg['engine']} attn_impl={self.engine.attn_impl} pages="
                f"{self.engine.num_pages}: built in "
                f"{time.perf_counter() - t0:.1f}s")
        self.server = serve([self.engine])
        self.client = Client(self.server)
        self.vocab = cfg["model"]["vocab_size"]
        rng = np.random.default_rng([ctx.seed, 5])
        t0 = time.perf_counter()
        warm = [(rng.integers(0, self.vocab, size=p).tolist(), m)
                for p, m in ctx.mix["warmup"]]
        # the first request pays for the compilation of the unified step
        first = self.client.send(warm[0][0], warm[0][1], False)
        if first["error"] or first["status"] != 200:
            raise RuntimeError(f"warm-up request failed: {first}")
        ctx.log(f"first request (compile or cache load of the unified "
                f"step) {time.perf_counter() - t0:.1f}s")
        # then the traffic's own shapes together: several chunks at once
        ths = [threading.Thread(target=self.client.send,
                                args=(p, m, ctx.mix["stream"]))
               for p, m in warm[1:]]
        for th in ths:
            th.start()
        for th in ths:
            th.join()
        # and one of them again: a prompt the prefix cache now holds takes
        # the copy-on-write page copy, a program of its own. Two random
        # prompts that share their first tokens would compile it inside
        # the window (about one run in ten at 50304 tokens and 100 prompts)
        self.client.send(warm[-1][0], warm[-1][1], False)
        self._age(ctx, rng)

    def _age(self, ctx, rng):
        """Brings the KV page pool to the state of a server that has been
        up for a while. Finished requests leave their pages in the prefix
        cache; once the pool is full every new page first spills an old
        one to the host tier (one compiled device-to-host copy a page),
        and once that is full too, evicts. A fresh engine would pass
        through all three regimes inside the window, so set-up pushes
        more tokens through than both tiers hold."""
        e = self.engine
        need = 1.1 * (e.num_pages + e.host_pages) * e.page_size
        plen = ctx.mix["prompt_len"]["max"]
        todo = list(range(int(need // plen) + 1))
        t0 = time.perf_counter()

        def worker():
            while todo:
                todo.pop()
                r = self.client.send(
                    rng.integers(0, self.vocab, size=plen).tolist(), 1, False)
                if r["error"] or r["status"] != 200:
                    raise RuntimeError(f"ageing request failed: {r}")
        ths = [threading.Thread(target=worker) for _ in range(e.num_slots)]
        for th in ths:
            th.start()
        for th in ths:
            th.join()
        snap = e.metrics.snapshot()
        ctx.log(f"pool aged with {int(need // plen) + 1} prompts of {plen} "
                f"tokens in {time.perf_counter() - t0:.1f}s: device pages "
                f"{snap['pool']['pages_used']} used + "
                f"{snap['pool']['pages_cached']} cached of "
                f"{snap['pool']['pages_total']}, host tier "
                f"{snap['host_pool']['pages_used']} of "
                f"{snap['host_pool']['pages_total']}")

    def drive(self, ctx, mix, seconds, on_start, on_end):
        """One pass of the traffic; returns (good records, all counted
        records, lateness)."""
        res = ctx.traffic.drive(mix, ctx.seed, seconds, self.vocab,
                                self.client.send, self.client.cut, on_start,
                                on_end)
        recs = res["records"]
        for r in recs:
            if "t_done" not in r:
                r.update(error="cut: not finished when the drain ended",
                         status=None, tokens=[], t_tokens=[], t_done=None)
        return [r for r in recs if _ok(r, self.vocab)], recs, \
            sorted(res["lateness_s"])

    def close(self):
        self.server.drain()


def client_times(good):
    """(time to first token from the due instant, or from the send where
    the loop is closed; every gap between consecutive token frames of one
    request), seconds on the client's clock."""
    return ([r["t_tokens"][0] - r.get("due", r["sent"]) for r in good],
            [b - a for r in good
             for a, b in zip(r["t_tokens"], r["t_tokens"][1:])])


def run(ctx):
    sv = Served(ctx)
    win = EngineWindow(sv.engine)
    tracer = trace.Session(ctx) if ctx.trace else None
    marks = {}

    def on_start():
        marks["t0"] = time.perf_counter()
        ctx.window_opened()
        win.start()
        if tracer:
            tracer.schedule(ctx.seconds)

    def on_end():
        marks["t1"] = time.perf_counter()
        win.stop()
        ctx.window_closed()

    try:
        good, recs, late = sv.drive(ctx, ctx.mix, ctx.seconds, on_start,
                                    on_end)
        if tracer:
            tracer.join()
    finally:
        sv.close()
    peak = ctx.memory_peak()
    ctx.log(f"memory: peak {peak} bytes; {ctx.devices[0].memory_stats()}")
    if late:
        ctx.log(f"generator lateness (sent - due) over {len(late)} requests:"
                f" median {1e3 * percentile(late, 50):.2f} ms, p95 "
                f"{1e3 * percentile(late, 95):.2f} ms, max "
                f"{1e3 * late[-1]:.2f} ms")
    t0, t1 = marks["t0"], marks["t1"]
    inside = [r for r in good if t0 <= r["t_done"] < t1]
    ttft, gaps = client_times(good)
    obs = {
        "window_s": t1 - t0,
        "client": {
            "ttft_s": ttft, "gap_s": gaps,
            "tokens_completed": sum(r["prompt_len"] + len(r["tokens"])
                                    for r in inside),
        },
        "engine": dict(win.samples, **win.counters),
        "trace": tracer.reduce() if tracer else None,
    }
    if ttft:
        ctx.log(f"client TTFT over {len(ttft)} requests: p50 "
                f"{1e3 * percentile(ttft, 50):.1f} ms, p90 "
                f"{1e3 * percentile(ttft, 90):.1f} ms, mean "
                f"{1e3 * sum(ttft) / len(ttft):.1f} ms; median of "
                f"{len(gaps)} token gaps "
                f"{1e3 * percentile(gaps or [0.0], 50):.1f} ms")
    ctx.log(f"window {t1 - t0:.2f}s: {len(recs)} requests counted, "
            f"{len(good)} good, {len(inside)} completed inside it; engine "
            f"steps {win.counters.get('unified_steps')}, queue depth at the "
            f"end {win.counters['queue_depth_end']}")
    for r in recs:
        if not _ok(r, sv.vocab):
            ctx.log(f"first failed request: status {r['status']}, error "
                    f"{r['error']}, {len(r['tokens'])} of "
                    f"{r['max_tokens']} tokens")
            break
    # correctness, outside every timing: a seeded sample of completed
    # requests against the plain reference (ref.py)
    chk = ctx.config["check"]
    pick = np.random.default_rng([ctx.seed, 6]).permutation(len(good))
    sample = [good[i] for i in pick[:chk["sample"]]]
    correct = False
    if sample:
        tc = time.perf_counter()
        gap, match = ref.dense_gaps(
            ref.gpt_weights(sv.model), ctx.config["model"], [r["prompt"] for r in sample],
            [r["tokens"] for r in sample], ref.check_width(ctx.mix))
        ctx.log(f"reference check on {len(sample)} requests: max gap "
                f"{gap:.4f} logit units (tolerance {chk['tolerance']}), "
                f"exact argmax on {match:.3f} of tokens (at least "
                f"{chk['min_match']}); {time.perf_counter() - tc:.1f}s")
        correct = gap <= chk["tolerance"] and match >= chk["min_match"]
    return {"correct": correct, "attempted": len(recs),
            "failed": len(recs) - len(good), "obs": obs,
            "memory_peak_bytes": peak}
