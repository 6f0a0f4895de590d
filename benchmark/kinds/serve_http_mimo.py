"""kind serve_http_mimo: `MiMoV2ForCausalLM` (MiMo-V2-Flash) in one
ServingEngine behind serving.http.serve on a loopback port, driven over
HTTP by the cell's traffic generator, checked against ref_mimo_v2.py.

What it shares with kinds/serve_http.py, serve_http_laguna.py and
serve_http_dsv2.py it imports from there (the client, the window over
the engine's metrics, the per-request check, the client's times, the
trace session that takes the engine's counters as
`obs["engine_traced"]`, the rounding to fp8); what differs is here:

- the model is built in bfloat16 sublayer by sublayer from the
  configuration file's own top-level keys, which are the source's;
  `n_routed_experts` there counts the experts HELD HERE, so the
  router's width is that times `ep_size`;
- no pool ageing: an engine with window layers has no prefix cache and
  no host tier (it says so once, which is logged);
- `memory_peak_bytes` is read, and the engine and its pools dropped,
  before the reference runs;
- `correct` is ref_mimo_v2.passes over ref_mimo_v2.judge_choices
  against the configuration's `check`: the mean gap and the argmax
  match over every emitted token, the largest gap of the tokens whose
  8th and 9th biased router scores are `tie_margin` apart or more, and
  a looser one for the near-tied others, whose share is logged. The
  sample is seeded, and the LONGEST completed request is always in it;
  each sampled request is checked at its own length rounded up to
  ref_mimo_v2.WIDTH_STEP;
- `run(ctx, controls=...)` (scripts/mimo_controls_reading.py; the
  harness passes none) puts VARIANTS of the reference through the same
  comparison on the same sampled requests, as if each had been the
  system under test: at every emitted token's position the variant's
  own argmax over the engine's context. `CONTROLS` names them: the
  same weights without the window layers' sinks, without the selection
  bias, and every weight matrix in float8_e4m3fn; each must come out
  NOT correct.
"""
import gc
import threading
import time

import numpy as np

from benchmark import ref_mimo_v2 as ref
from benchmark.kinds import serve_http
from benchmark.kinds.serve_http import (Client, EngineWindow, _ok,
                                        client_times)
from benchmark.kinds.serve_http_dsv2 import _round_fp8
from benchmark.kinds.serve_http_laguna import NOT_MODEL, CountedSession
from benchmark.stats import percentile
# (a program without the model fails here, at once: the harness imports
# the kind after it has found its device)
from paddle_tpu.nlp import MiMoV2Config, MiMoV2ForCausalLM


def model_config(cfg):
    """MiMoV2Config keyword arguments from the configuration file."""
    kw = {k: v for k, v in cfg.items() if k not in NOT_MODEL}
    kw["n_routed_experts"] = cfg["n_routed_experts"] * cfg.get("ep_size", 1)
    want = cfg.get("published", {}).get("n_routed_experts")
    if want is not None and kw["n_routed_experts"] != want:
        raise SystemExit(f"{cfg['n_routed_experts']} experts held x ep_size "
                         f"{cfg['ep_size']} is not the published {want}")
    return kw


def reference_config(cfg):
    """What ref_mimo_v2 reads: the same keys (it takes the share from
    ep_size / ep_rank and the held experts from the weights)."""
    return {k: v for k, v in cfg.items() if k not in NOT_MODEL + ("dtype",)}


def build(cfg, seed):
    import paddle_tpu as paddle
    paddle.seed(seed)
    model = MiMoV2ForCausalLM(MiMoV2Config(**model_config(cfg)))
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
        self.model = build(cfg, ctx.seed)
        with warnings.catch_warnings(record=True) as said:
            warnings.simplefilter("always")
            self.engine = ServingEngine(self.model, **cfg["engine"])
        for w in said:
            ctx.log(f"engine: {w.message}")
        n_par = sum(int(np.prod(p.shape)) for p in self.model.parameters())
        e = self.engine
        ctx.log(f"model: {n_par / 1e6:.1f}M parameters {cfg['dtype']}, "
                f"engine {cfg['engine']} attn_impl={e.attn_impl} pages="
                f"{e.num_pages} of {e.page_bytes} bytes; (kv heads, key, "
                f"value widths, sink) a layer {e.kv_geometry}, window "
                f"layers {sorted(e.kv_windows)} on rings of "
                f"{e.ring_pages} pages a slot: built in "
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
        # then the traffic's own shapes together: chunks, decoding rows
        # and a window that wraps its ring
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


class Window(EngineWindow):
    """EngineWindow without the engine's token gaps (`inter_token_s`):
    32 slots decoding at ~10 tokens/s each record ~16,000 gaps in a
    window of 51 s at steady occupancy, twice the ring of 8192 that
    EngineWindow refuses to overrun; this cell reads the client's gaps
    (`itl_p95_ms`) and no metric of it reads the engine's."""
    HISTS = ("ttft_s", "queue_wait_s", "decode_step_s")


# name -> (what is done to the weights before the reference runs again,
# the reference's keywords): in this order, because the rounding stays
CONTROLS = {"no_sinks": (lambda weights: None, {"sinks": False}),
            "no_bias": (lambda weights: None, {"bias": False}),
            "all_matrices_fp8": (_round_fp8, {})}


def _reading(got, chk):
    each = got["each"]
    return (f"{got['tokens']} tokens: mean gap {got['mean_gap']:.4f} "
            f"logit units (at most {chk['mean_gap']}), largest "
            f"{got['gap']:.4f} on the tokens whose router margin is at "
            f"least {chk['tie_margin']} (at most {chk['tolerance']}) and "
            f"{got['tie_gap']:.4f} on the other {got['tie_share']:.3f} of "
            f"tokens (at most {chk['tie_tolerance']}), exact argmax on "
            f"{got['match']:.3f} of tokens (at least {chk['min_match']}); "
            f"not limited: gap p95 {np.quantile(each['gap'], 0.95):.4f}, "
            f"p99 {np.quantile(each['gap'], 0.99):.4f}; least router "
            f"margin {got['min_margin']:.2e}; router margin m -> share of "
            f"tokens under it, largest gap at or above it: " + ", ".join(
                f"{m:g} -> {(each['margin'] < m).mean():.3f}, "
                f"{each['gap'][each['margin'] >= m].max(initial=0.0):.4f}"
                for m in (0.0005, 0.001, 0.002, 0.005, 0.01, 0.02)))


def run(ctx, controls=()):
    sv = Served(ctx)
    win = Window(sv.engine)
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
                f"{1e3 * percentile(gaps or [0.0], 50):.1f} ms, p95 "
                f"{1e3 * percentile(gaps or [0.0], 95):.1f} ms")
    c = win.counters

    def per(n, d):
        return c[n] / max(1, c[d])
    ctx.log(f"window {t1 - t0:.2f}s: {len(recs)} requests counted, "
            f"{len(good)} good, {len(inside)} completed inside it; engine "
            f"steps {c.get('unified_steps')}, queue depth at the end "
            f"{c['queue_depth_end']}; experts hit a layer-step "
            f"{per('moe_experts_hit_total', 'moe_layer_steps_total'):.1f}, "
            f"assignments moved by the bias "
            f"{per('moe_bias_reranked_total', 'moe_assignments_total'):.3f}"
            f"; keys a row: full layers "
            f"{per('split_walk_keys_total', 'split_walk_rows_total'):.0f}, "
            f"window layers "
            f"{per('sink_walk_keys_total', 'sink_walk_rows_total'):.0f} "
            f"over {c['split_walk_rows_total']} and "
            f"{c['sink_walk_rows_total']} layer-rows")
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
    # the longest completed request first, then the seeded order
    longest = max(range(len(good)), default=None,
                  key=lambda i: good[i]["prompt_len"] + len(good[i]["tokens"]))
    pick = [i for i in [longest, *pick] if i is not None]
    sample = [good[i] for i in dict.fromkeys(pick)][:chk["sample"]]
    weights = ref.mimo_weights(sv.model)
    # (the server's threads may still hold the engine: empty it)
    sv.engine._ct = sv.engine._last_logits = None
    sv.engine._unified_args_tail = None
    del sv, win, tracer
    gc.collect()
    correct = False
    if sample:
        tc = time.perf_counter()
        prompts = [r["prompt"] for r in sample]
        tokens = [r["tokens"] for r in sample]
        rcfg = reference_config(ctx.config)
        reference = ref.teacher_forced(weights, rcfg, prompts, tokens)
        got = ref.judge_choices(reference, tokens, chk["tie_margin"])
        correct = ref.passes(got, chk)
        ctx.log(f"reference check on {len(sample)} requests of "
                f"{[r['prompt_len'] for r in sample]} prompt tokens: "
                f"{_reading(got, chk)}; {time.perf_counter() - tc:.1f}s")
        for name in sorted(controls, key=list(CONTROLS).index):
            tc = time.perf_counter()
            change, kw = CONTROLS[name]
            change(weights)
            variant = ref.teacher_forced(weights, rcfg, prompts, tokens,
                                         **kw)
            ctl = ref.judge_choices(
                reference, [v[0].argmax(-1) for v in variant],
                chk["tie_margin"])
            ctx.log(f"control {name}: correct "
                    f"{str(ref.passes(ctl, chk)).lower()}: "
                    f"{_reading(ctl, chk)}; "
                    f"{time.perf_counter() - tc:.1f}s")
    return {"correct": correct, "attempted": len(recs),
            "failed": len(recs) - len(good), "obs": obs,
            "memory_peak_bytes": peak}
