"""BERT-base finetune step bisection: where does the non-roofline time
go, and is the flash kernel really VPU-bound at L=384?

Times the full compiled train step (bs16x384, masks + dropout — the
bert_bench.py configuration) against ablated variants, each as one
compiled program with ONE device sync per timed batch of iters (host
timings of single dispatches are noise; see BASELINE.md op-bench
caveat). The deltas attribute time to attention dropout,
hidden dropout, the padding mask, the fused LN kernel, and fwd vs bwd.

Run on the real chip AFTER the decode roofline (one chip user at a
time):  python scripts/bert_roofline.py
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
os.environ.setdefault("JAX_DEFAULT_MATMUL_PRECISION", "default")

_PEAK = {"v5p": 459e12, "v5e": 197e12, "v5 lite": 197e12,
         "v4": 275e12, "v6": 918e12, "v3": 123e12, "v2": 45e12}


def build_step(cfg_kw, batch, seqlen, with_mask=True, fwd_only=False,
               bs_override=None):
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu import jit
    from paddle_tpu.nlp.bert import BertConfig, \
        BertForSequenceClassification

    if bs_override:
        batch = bs_override
    cfg = BertConfig(**cfg_kw)
    paddle.seed(0)
    model = BertForSequenceClassification(cfg, num_classes=2)
    model.to(dtype="bfloat16")
    model.train()
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size,
                                       (batch, seqlen)))
    lens = rng.randint(seqlen // 2, seqlen + 1, (batch,))
    mask_np = (np.arange(seqlen)[None, :] < lens[:, None])
    mask = paddle.to_tensor(mask_np[:, None, None, :])
    labels = paddle.to_tensor(rng.randint(0, 2, (batch,)))

    if fwd_only:
        import jax

        state = [p for p in model.parameters()] + \
            [b for _, b in model.named_buffers()]

        def fwd(vals, ids_v, mask_v, labels_v):
            orig = [t._value for t in state]
            from paddle_tpu.core import random as rmod
            rmod.push_trace_key(jax.random.PRNGKey(0))
            try:
                for t, v in zip(state, vals):
                    t._value = v
                from paddle_tpu.core.tensor import Tensor
                out = model(Tensor(ids_v),
                            attention_mask=Tensor(mask_v) if with_mask
                            else None,
                            labels=Tensor(labels_v))
                return out._value
            finally:
                rmod.pop_trace_key()
                for t, v in zip(state, orig):
                    t._value = v

        jfwd = jax.jit(fwd)
        vals = [t._value for t in state]

        def run(_i):
            return jfwd(vals, ids._value, mask._value, labels._value)
        return run, batch * seqlen

    optimizer = opt.AdamW(learning_rate=2e-5,
                          parameters=model.parameters(),
                          weight_decay=0.01)
    if with_mask:
        step = jit.compile_train_step(
            lambda i, m, l: model(i, attention_mask=m, labels=l),
            model, optimizer)

        def run(_):
            return step(ids, mask, labels)
    else:
        step = jit.compile_train_step(
            lambda i, l: model(i, labels=l), model, optimizer)

        def run(_):
            return step(ids, labels)
    return run, batch * seqlen


def time_variant(run, iters=20, batches=3, warmup=3):
    import jax
    for _ in range(warmup):
        out = run(0)
    jax.block_until_ready(getattr(out, "_value", out))
    best = float("inf")
    for _ in range(batches):
        t0 = time.perf_counter()
        for i in range(iters):
            out = run(i)
        jax.block_until_ready(getattr(out, "_value", out))
        best = min(best, (time.perf_counter() - t0) / iters)
    return best


def main():
    import jax
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu:
        print(json.dumps({"error": "run on the chip"}))
        return
    batch, seqlen = 16, 384
    base_kw = dict()  # BERT-base defaults: dropout 0.1/0.1
    peak = next((v for k, v in _PEAK.items()
                 if k in (dev.device_kind or "").lower()), 197e12)

    report = {}

    def note(k, v):
        report[k] = v
        print(f"  {k}: {v}", flush=True)

    variants = [
        ("full", base_kw, dict()),
        ("no_attn_dropout", dict(attention_probs_dropout_prob=0.0),
         dict()),
        ("no_dropout_at_all", dict(attention_probs_dropout_prob=0.0,
                                   hidden_dropout_prob=0.0), dict()),
        ("no_mask", base_kw, dict(with_mask=False)),
        ("fwd_only", base_kw, dict(fwd_only=True)),
        ("bs32", base_kw, dict(bs_override=32)),
    ]
    for name, kw, extra in variants:
        run, tokens = build_step(kw, batch, seqlen, **extra)
        dt = time_variant(run)
        note(f"{name}_ms", round(dt * 1e3, 2))
        note(f"{name}_tok_per_s", round(tokens / dt))

    # unfused-LN variant needs a fresh process env; record via env relaunch
    n_params = 110e6
    fpt = 6 * n_params + 12 * 12 * 768 * seqlen
    full_dt = report["full_ms"] / 1e3
    note("mfu_full", round(
        (batch * seqlen / full_dt) * fpt / peak, 4))
    note("mfu_bs32", round(
        (32 * seqlen / (report["bs32_ms"] / 1e3)) * fpt / peak, 4))
    note("ideal_step_ms_at_peak", round(
        batch * seqlen * fpt / peak * 1e3, 2))
    print(json.dumps(report))


if __name__ == "__main__":
    main()
