"""kind serve_http_laguna: `LagunaForCausalLM` in one ServingEngine behind
serving.http.serve on a loopback port, driven over HTTP by the cell's
traffic generator, checked against ref_laguna.py.

What it shares with kinds/serve_http.py it imports from there (the
client, the window over the engine's metrics, the per-request check, the
client's times); what differs is here:

- the model is built in bfloat16 sublayer by sublayer (`dtype` is one of
  LagunaConfig's keys: 5.6 B parameters do not fit the chip in float32
  first), from the configuration file's own
  top-level keys, which are the source's; `num_experts` there counts the
  experts HELD HERE, so the router's width is that times `ep_size`;
- token ids are drawn over the vocabulary slice held here (the
  generators draw from `vocab`, which is the slice);
- the pool is aged only as far as the engine has tiers: with window
  layers it has no prefix cache and no host tier, so nothing is parked
  and there is nothing to age;
- the trace session also takes the engine's counters when the trace
  starts and stops (`obs["engine_traced"]`), so that a reader can set
  what the program counted beside the device time of the same seconds;
- the engine and its KV pools are dropped before the reference runs, so
  that the reference's float32 blocks fit beside the bf16 weights;
- `correct` is ref_laguna.laguna_gaps against the configuration's
  `check`: tolerance, tie_margin, tie_tolerance, max_tie_share,
  min_match (see ref_laguna.py, NEAR TIES).
"""
import gc
import threading
import time

import numpy as np

from benchmark import ref_laguna, trace
from benchmark.kinds import serve_http
from benchmark.kinds.serve_http import (Client, EngineWindow, _ok,
                                        client_times)
from benchmark.stats import percentile

# the configuration file's keys that are not the model's
NOT_MODEL = ("kind", "source", "why", "published", "engine", "reduced",
             "assumed", "deployment", "notes", "check")


def model_config(cfg):
    """LagunaConfig keyword arguments from the configuration file."""
    kw = {k: v for k, v in cfg.items() if k not in NOT_MODEL}
    kw["num_experts"] = cfg["num_experts"] * cfg.get("ep_size", 1)
    want = cfg.get("published", {}).get("num_experts")
    if want is not None and kw["num_experts"] != want:
        raise SystemExit(f"{cfg['num_experts']} experts held x ep_size "
                         f"{cfg['ep_size']} is not the published {want}")
    return kw


def reference_config(cfg):
    """What ref_laguna reads: the same keys (it takes the share from
    ep_size / ep_rank and the held experts from the weights)."""
    return {k: v for k, v in cfg.items()
            if k not in NOT_MODEL + ("dtype",)}


def build_laguna(cfg, seed):
    import paddle_tpu as paddle
    from paddle_tpu.nlp import LagunaConfig, LagunaForCausalLM
    paddle.seed(seed)
    model = LagunaForCausalLM(LagunaConfig(**model_config(cfg)))
    model.eval()
    return model


class Served(serve_http.Served):
    """Model, engine, server and client, warmed: the set-up shared by a
    run and by the rate sweep. serve_http.Served's `drive` and `close`
    over a set-up of its own."""

    def __init__(self, ctx):
        import warnings
        from paddle_tpu.serving import ServingEngine
        from paddle_tpu.serving.http import serve
        cfg = ctx.config
        t0 = time.perf_counter()
        self.model = build_laguna(cfg, ctx.seed)
        with warnings.catch_warnings(record=True) as said:
            warnings.simplefilter("always")
            self.engine = ServingEngine(self.model, **cfg["engine"])
        for w in said:
            ctx.log(f"engine: {w.message}")
        n_par = sum(int(np.prod(p.shape)) for p in self.model.parameters())
        e = self.engine
        ctx.log(f"model: {n_par / 1e6:.1f}M parameters {cfg['dtype']}, "
                f"engine {cfg['engine']} attn_impl={e.attn_impl} pages="
                f"{e.num_pages}, window layers {sorted(e.kv_windows)} on "
                f"rings of {e.ring_pages} pages a slot: built in "
                f"{time.perf_counter() - t0:.1f}s")
        self.server = serve([self.engine])
        self.client = Client(self.server)
        self.vocab = cfg["vocab_size"]
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
        if e.prefix_cache is not None:
            raise RuntimeError(
                "this engine has a prefix cache: its pool wants ageing as "
                "serve_http.Served._age does, which this kind does not do")
        ctx.log(f"warmed in {time.perf_counter() - t0:.1f}s; no prefix "
                f"cache and no host tier, so no pool to age: "
                f"{e.metrics.snapshot()['pool']}")


class CountedSession(trace.Session):
    """The trace session, with the engine's counters taken when the
    trace starts and when it stops."""

    def __init__(self, ctx, engine):
        super().__init__(ctx)
        self.window = EngineWindow(engine)

    def start(self):
        super().start()
        self.window.start()

    def stop(self):
        self.window.stop()
        super().stop()


def run(ctx):
    sv = Served(ctx)
    win = EngineWindow(sv.engine)
    tracer = CountedSession(ctx, sv.engine) if ctx.trace else None
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
        "engine_traced": tracer.window.counters if tracer else None,
        "trace": tracer.reduce() if tracer else None,
    }
    if ttft:
        ctx.log(f"client TTFT over {len(ttft)} requests: p50 "
                f"{1e3 * percentile(ttft, 50):.1f} ms, p90 "
                f"{1e3 * percentile(ttft, 90):.1f} ms, mean "
                f"{1e3 * sum(ttft) / len(ttft):.1f} ms; median of "
                f"{len(gaps)} token gaps "
                f"{1e3 * percentile(gaps or [0.0], 50):.1f} ms")
    c = win.counters
    ctx.log(f"window {t1 - t0:.2f}s: {len(recs)} requests counted, "
            f"{len(good)} good, {len(inside)} completed inside it; engine "
            f"steps {c.get('unified_steps')}, queue depth at the end "
            f"{c['queue_depth_end']}; experts hit a layer-step "
            f"{c['moe_experts_hit_total'] / max(1, c['moe_layer_steps_total']):.1f}"
            f", window pages walked {c['kv_window_pages_walked_total']} "
            f"skipped {c['kv_window_pages_skipped_total']}")
    for r in recs:
        if not _ok(r, sv.vocab):
            ctx.log(f"first failed request: status {r['status']}, error "
                    f"{r['error']}, {len(r['tokens'])} of "
                    f"{r['max_tokens']} tokens")
            break
    # correctness, outside every timing: a seeded sample of completed
    # requests against the plain reference. The engine and its pools go
    # first: the reference's float32 blocks need the room
    chk = ctx.config["check"]
    pick = np.random.default_rng([ctx.seed, 6]).permutation(len(good))
    sample = [good[i] for i in pick[:chk["sample"]]]
    weights = ref_laguna.laguna_weights(sv.model)
    # (the server's threads may still hold the engine: empty it)
    sv.engine._ct = sv.engine._last_logits = None
    sv.engine._unified_args_tail = None
    del sv, win, tracer
    gc.collect()
    correct = False
    if sample:
        tc = time.perf_counter()
        got = ref_laguna.laguna_gaps(
            weights, reference_config(ctx.config),
            [r["prompt"] for r in sample], [r["tokens"] for r in sample],
            ref_laguna.check_width(ctx.mix), chk["tie_margin"])
        ctx.log(f"reference check on {len(sample)} requests, "
                f"{got['tokens']} tokens: max gap {got['gap']:.4f} logit "
                f"units (tolerance {chk['tolerance']}); over the "
                f"{got['tie_share']:.3f} of tokens whose top-k margin is "
                f"under {chk['tie_margin']} (at most "
                f"{chk['max_tie_share']}) {got['tie_gap']:.4f} (tolerance "
                f"{chk['tie_tolerance']}); least margin "
                f"{got['min_margin']:.2e}; exact argmax on "
                f"{got['match']:.3f} of tokens (at least "
                f"{chk['min_match']}); {time.perf_counter() - tc:.1f}s")
        correct = (got["gap"] <= chk["tolerance"]
                   and got["tie_gap"] <= chk["tie_tolerance"]
                   and got["tie_share"] <= chk["max_tie_share"]
                   and got["match"] >= chk["min_match"])
    return {"correct": correct, "attempted": len(recs),
            "failed": len(recs) - len(good), "obs": obs,
            "memory_peak_bytes": peak}
