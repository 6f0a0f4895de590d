"""kind train_step: forward + backward + AdamW in one XLA program
(`paddle_tpu.jit.compile_train_step`), one step per fresh batch, the
loss fetched once per group of steps.

The builder is copied from bench.build_train_step and the set-up check
from chip_smoke.train_phase (both ran on the chip in PR 23); bench.py's
statistic (best of 3 x 20 steps on one repeated batch) is not: the rate
here is over all the steps and all the time of the window.
"""
import math
import time

import numpy as np

from benchmark import ref, trace


def build_train_step(cfg, seed):
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu import jit
    from paddle_tpu.nlp import GPTConfig, GPTForCausalLM
    paddle.set_matmul_precision("default")
    paddle.seed(seed)
    model = GPTForCausalLM(GPTConfig(hidden_dropout_prob=0.0,
                                     attention_probs_dropout_prob=0.0,
                                     **cfg["model"]))
    model.to(dtype=cfg["dtype"])    # MXU-native weights; f32 AdamW moments
    optimizer = opt.AdamW(parameters=model.parameters(), **cfg["optimizer"])
    step = jit.compile_train_step(
        lambda ids, labels: model(ids, labels=labels), model, optimizer)
    return step, model


def run(ctx):
    import paddle_tpu as paddle
    cfg, chk = ctx.config, ctx.config["check"]
    batch, seqlen = cfg["batch"], cfg["seqlen"]
    vocab = cfg["model"]["vocab_size"]
    group = ctx.mix["group_steps"]
    t0 = time.perf_counter()
    step, model = build_train_step(cfg, ctx.seed)
    n_model = sum(int(np.prod(p.shape)) for p in model.parameters())
    if n_model != ref.n_params(cfg["model"]):
        raise RuntimeError(f"the model has {n_model} parameters, ref.py "
                           f"counts {ref.n_params(cfg['model'])}")
    rng = np.random.default_rng([ctx.seed, 7])
    fixed = (rng.integers(0, vocab, size=(batch, seqlen)),
             rng.integers(0, vocab, size=(batch, seqlen)))
    ref_loss = ref.gpt_loss(ref.gpt_weights(model), cfg["model"], *fixed)
    ids, labels = (paddle.to_tensor(a) for a in fixed)
    # set-up check (chip_smoke.train_phase): the kernels are in the
    # program, the loss starts at the reference's, is finite and falls
    text = step.compile_info(ids, labels).as_text()
    kernels = {k: text.count(k) for k in chk["kernels"]}
    losses = [float(step(ids, labels)) for _ in range(chk["steps"])]
    ctx.log(f"model {cfg['model']} {cfg['dtype']} {n_model} parameters, "
            f"batch {batch} x {seqlen}; built, compiled and "
            f"{chk['steps']} steps in {time.perf_counter() - t0:.1f}s; "
            f"kernels in the step {kernels}; losses on one fixed batch "
            f"{[round(x, 4) for x in losses]} (reference's first "
            f"{ref_loss:.4f})")
    correct = (all(math.isfinite(x) for x in losses)
               and losses[0] - losses[-1] >= chk["min_fall"]
               and abs(losses[0] - ref_loss) <= chk["ref_loss_tolerance"]
               and (ctx.rehearsal or all(kernels.values())))
    stream = ctx.traffic.batches(ctx.mix, ctx.seed, batch, seqlen, vocab)

    def one_group():
        for _ in range(group):
            a, b = next(stream)
            loss = step(paddle.to_tensor(a), paddle.to_tensor(b))
        return float(loss), time.perf_counter()     # the fetch is the barrier

    one_group()                         # warms the input path
    first_loss, t_first = one_group()   # its fetch opens the window
    ctx.window_opened()
    tracer = trace.Session(ctx) if ctx.trace else None
    trace_from = t_first + 0.4 * ctx.seconds
    trace_to = None
    fetched, last = [], t_first
    while True:
        if tracer and trace_to is None and last >= trace_from:
            tracer.start()
            trace_to = time.perf_counter() + min(trace.TRACE_SECONDS,
                                                 ctx.seconds / 2)
        loss, t = one_group()
        fetched.append((t, loss))
        if tracer and trace_to and t >= trace_to:
            tracer.stop()
            trace_to = math.inf
        # stop before the group that would end outside the window
        if t - t_first + (t - last) > ctx.seconds:
            break
        last = t
    ctx.window_closed()
    if tracer and trace_to != math.inf:
        tracer.stop()
    peak = ctx.memory_peak()
    ctx.log(f"memory: peak {peak} bytes; {ctx.devices[0].memory_stats()}")
    times = [t for t, _ in fetched]
    window_s = times[-1] - t_first
    vals = [v for _, v in fetched]
    ctx.log(f"window {window_s:.2f}s: {len(fetched)} groups of {group} "
            f"steps; fetched losses first {first_loss:.4f}, min "
            f"{min(vals):.4f}, max {max(vals):.4f}, last {vals[-1]:.4f} "
            f"(ln vocab = {math.log(vocab):.4f})")
    bad = [v for v in vals
           if not math.isfinite(v) or v > first_loss + chk["window_rise"]]
    obs = {
        "window_s": window_s,
        "train": {
            "tokens": len(fetched) * group * batch * seqlen,
            "tokens_per_step": batch * seqlen,
            "step_s": [(b - a) / group
                       for a, b in zip([t_first] + times, times)],
            "flops_per_token": ref.train_flops_per_token(cfg["model"],
                                                         seqlen),
        },
        "trace": tracer.reduce() if tracer else None,
    }
    return {"correct": correct and not bad, "attempted": len(fetched) * group,
            "failed": len(bad) * group, "obs": obs,
            "memory_peak_bytes": peak}
