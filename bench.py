"""Benchmark: GPT pretraining throughput on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
vs_baseline = achieved MFU / 0.35 (the BASELINE.md target for config #4).

Single-chip GPT-124M config in bf16, whole train step compiled into one
XLA program (forward+backward+AdamW, donated buffers). It measures a TPU
and fails when JAX finds none, or one whose bf16 peak it does not know.
A CPU run exists only as a smoke of the script itself, only when the
caller asked for the CPU (`JAX_PLATFORMS=cpu` or
`PADDLE_TPU_FORCE_CPU_DEVICES`), at a toy size and under its own metric
name — never under the device metric's.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

# fast matmul path for the benchmark
os.environ.setdefault("JAX_DEFAULT_MATMUL_PRECISION", "default")


# bf16 peak FLOP/s per chip, keyed by jax's `device_kind` (Google Cloud
# TPU documentation, per-chip peaks)
_PEAK_FLOPS = {
    "TPU v2": 45e12, "TPU v3": 123e12, "TPU v4": 275e12,
    "TPU v5 lite": 197e12, "TPU v5e": 197e12, "TPU v5p": 459e12,
    "TPU v5": 459e12, "TPU v6 lite": 918e12, "TPU v6e": 918e12,
}

GPT_124M = dict(vocab_size=50304, hidden_size=768, num_hidden_layers=12,
                num_attention_heads=12, max_position_embeddings=1024)
TPU_BATCH, TPU_SEQLEN = 16, 1024


def peak_flops(kind: str) -> float:
    if kind not in _PEAK_FLOPS:
        raise ValueError(
            f"unknown device_kind {kind!r}: add its bf16 peak to "
            f"bench._PEAK_FLOPS (known: {sorted(_PEAK_FLOPS)})")
    return _PEAK_FLOPS[kind]


def cpu_requested() -> bool:
    """True iff the caller asked for the CPU through the means that
    exist; what JAX happens to find never decides it."""
    return (os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
            or bool(os.environ.get("PADDLE_TPU_FORCE_CPU_DEVICES")))


def tpu_expected(smoke=False) -> bool:
    """How an entry script sizes itself: False (the CPU toy) only when
    the caller asked for the CPU or for `--smoke`; otherwise True, and
    then JAX's first device must be a TPU or this raises."""
    if smoke or cpu_requested():
        return False
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"a TPU was expected and JAX found {dev.platform!r} "
            f"({dev.device_kind}); for a CPU smoke ask for it with "
            f"JAX_PLATFORMS=cpu")
    return True


def build_train_step(cfg_kwargs, batch, seqlen, seed=0):
    """GPT in bf16 + AdamW under `jit.compile_train_step`, and one
    fixed random batch. Returns (step, ids, labels, model)."""
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu import jit
    from paddle_tpu.nlp import GPTConfig, GPTForCausalLM

    paddle.set_matmul_precision("default")
    cfg = GPTConfig(hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0, **cfg_kwargs)
    paddle.seed(seed)
    model = GPTForCausalLM(cfg)
    model.to(dtype="bfloat16")  # MXU-native weights; fp32 Adam moments
    optimizer = opt.AdamW(learning_rate=1e-4,
                          parameters=model.parameters(),
                          weight_decay=0.01)
    step = jit.compile_train_step(
        lambda ids, labels: model(ids, labels=labels), model, optimizer)
    rng = np.random.RandomState(seed)
    ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size,
                                       (batch, seqlen)))
    labels = paddle.to_tensor(rng.randint(0, cfg.vocab_size,
                                          (batch, seqlen)))
    return step, ids, labels, model


def main():
    from paddle_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    on_tpu = tpu_expected()
    if on_tpu:
        import jax
        dev = jax.devices()[0]
        peak = peak_flops(dev.device_kind)
        cfg_kwargs = GPT_124M
        batch, seqlen, iters, warmup = TPU_BATCH, TPU_SEQLEN, 20, 3
    else:  # smoke of the script on the CPU the caller asked for
        cfg_kwargs = dict(vocab_size=2048, hidden_size=256,
                          num_hidden_layers=4, num_attention_heads=8,
                          max_position_embeddings=256)
        batch, seqlen, iters, warmup = 4, 256, 5, 2

    step, ids, labels, model = build_train_step(cfg_kwargs, batch, seqlen)

    for _ in range(warmup):
        loss = step(ids, labels)
    # fetching the value to the host is the execution barrier: the
    # call returns when the step is enqueued, not when it has run
    float(loss)

    # best of 3 timing windows, each ending in a host fetch. Kept as
    # the earlier rounds measured; median, spread and sample count
    # belong to the benchmark PR (ROADMAP S0), not to this script.
    best_dt = float("inf")
    for _rep in range(3 if on_tpu else 1):
        t0 = time.perf_counter()
        for _ in range(iters):
            loss = step(ids, labels)
        float(loss)
        best_dt = min(best_dt, time.perf_counter() - t0)
    dt = best_dt

    tokens = batch * seqlen * iters
    tok_per_sec = tokens / dt
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    if not on_tpu:
        print(json.dumps({
            "metric": "cpu_smoke_tokens_per_sec",
            "value": round(tok_per_sec, 2),
            "unit": f"tokens/s (cpu smoke of the script, "
                    f"{n_params/1e6:.0f}M params, bs{batch}x{seqlen}; "
                    f"not a device metric)",
            "vs_baseline": 0.0,
        }))
        return

    # parameter count & 6N flops/token (+ attention term)
    flops_per_token = 6 * n_params + \
        12 * cfg_kwargs["num_hidden_layers"] * \
        cfg_kwargs["hidden_size"] * seqlen
    mfu = tok_per_sec * flops_per_token / peak
    print(json.dumps({
        "metric": "gpt_pretrain_tokens_per_sec_per_chip",
        "value": round(tok_per_sec, 2),
        "unit": f"tokens/s (tpu, {dev.device_kind}, "
                f"{n_params/1e6:.0f}M params, bs{batch}x{seqlen}, "
                f"mfu={mfu:.3f})",
        "vs_baseline": round(mfu / 0.35, 4),
    }))


if __name__ == "__main__":
    main()
