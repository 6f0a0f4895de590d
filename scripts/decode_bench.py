"""Decode throughput: compiled autoregressive generation on the chip.

Measures the one-XLA-program generate() (static KV cache +
lax.while_loop — paddle_tpu/nlp/generation.py) on a GPT-124M-ish config
and prints one JSON line with decode tokens/s. The reference's analogue
is the fused_multi_transformer inference path
(/root/reference/paddle/fluid/operators/fused/fused_multi_transformer_op.cu).
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


def main():
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.nlp import GPTConfig, GPTForCausalLM

    paddle.set_matmul_precision("default")
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"

    if on_tpu:
        cfg = GPTConfig(vocab_size=50304, hidden_size=768,
                        num_hidden_layers=12, num_attention_heads=12,
                        max_position_embeddings=2048,
                        hidden_dropout_prob=0.0,
                        attention_probs_dropout_prob=0.0)
        batch, prompt_len, new_tokens = 16, 128, 512
    else:
        cfg = GPTConfig(vocab_size=2048, hidden_size=256,
                        num_hidden_layers=4, num_attention_heads=8,
                        max_position_embeddings=512,
                        hidden_dropout_prob=0.0,
                        attention_probs_dropout_prob=0.0)
        batch, prompt_len, new_tokens = 4, 32, 64

    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    model.to(dtype="bfloat16")
    rng = np.random.RandomState(0)
    prompt = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, prompt_len)))

    out = model.generate(prompt, max_new_tokens=new_tokens)  # warm/trace
    _ = out.numpy()

    best_dt = float("inf")
    for _ in range(3 if on_tpu else 1):
        t0 = time.perf_counter()
        out = model.generate(prompt, max_new_tokens=new_tokens)
        _ = out.numpy()  # host fetch = execution barrier
        best_dt = min(best_dt, time.perf_counter() - t0)

    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    tok_per_sec = batch * new_tokens / best_dt
    print(json.dumps({
        "metric": "gpt_decode_tokens_per_sec_per_chip",
        "value": round(tok_per_sec, 2),
        "unit": f"tokens/s ({'tpu' if on_tpu else 'cpu-smoke'}, "
                f"{n_params / 1e6:.0f}M params, bs{batch}, "
                f"prompt {prompt_len} + {new_tokens} new, bf16)",
        "vs_baseline": 0.0,
    }))

    # weight-only quantized decode (nn.quant): int8/int4 weight streams.
    # Decode is weight-bandwidth-bound (BASELINE.md roofline), so
    # narrowing the weight stream converts directly into tokens/s.
    bf16_out = out.numpy()
    # Quantized variants are opt-in (--quant): under the r5
    # weights-as-constants regime bf16 is the fastest stable config at
    # this model size (BASELINE.md decode roofline), int8 weights
    # measure 0.87x, and the int8 KV cache — despite a probe-proven
    # 1.32 ms/step ceiling — tripped an XLA/Mosaic fault at
    # full generation length in the builders' earlier chip runs (worker
    # crash; documented in BASELINE.md, not re-run on this round's
    # chip). Keep the driver bench deterministic.
    runs = ()
    if "--quant" in sys.argv:
        runs = (
            # (weight algo, group, kv dtype, tag)
            ("weight_only_int8", None, None, "int8"),
            (None, None, "int8", "kv8"),
        )
    for algo, gsz, kvdt, tag in runs:
        from paddle_tpu.nn import quant as nnq
        paddle.seed(0)
        qmodel = GPTForCausalLM(cfg)
        qmodel.to(dtype="bfloat16")
        if algo is not None:
            nnq.quantize_for_decode(qmodel, algo=algo, group_size=gsz)
        qout = qmodel.generate(prompt, max_new_tokens=new_tokens,
                               kv_cache_dtype=kvdt)
        qnp = qout.numpy()
        agree = float((qnp[:, prompt_len:] ==
                       bf16_out[:, prompt_len:]).mean())
        best_q = float("inf")
        for _ in range(3 if on_tpu else 1):
            t0 = time.perf_counter()
            qout = qmodel.generate(prompt, max_new_tokens=new_tokens,
                                   kv_cache_dtype=kvdt)
            _ = qout.numpy()
            best_q = min(best_q, time.perf_counter() - t0)
        print(json.dumps({
            "metric": f"gpt_decode_{tag}_tokens_per_sec_per_chip",
            "value": round(batch * new_tokens / best_q, 2),
            "unit": f"tokens/s ({'tpu' if on_tpu else 'cpu-smoke'}, "
                    f"{n_params / 1e6:.0f}M params, bs{batch}, {tag}, "
                    f"greedy-token agreement vs bf16 {agree:.2f})",
            "vs_baseline": round(best_dt / best_q, 3),
        }))
        del qmodel

    # compiled beam search (reference: beam_search.cu) — whole search is
    # one XLA program; throughput counted in kept (best-beam) tokens
    beams = 4
    bbatch, bnew = (batch // 2, new_tokens // 2) if on_tpu else (2, 16)
    bprompt = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (bbatch, prompt_len)))
    out = model.generate(bprompt, max_new_tokens=bnew,
                         decode_strategy="beam_search", num_beams=beams)
    _ = out.numpy()
    best_dt = float("inf")
    for _ in range(3 if on_tpu else 1):
        t0 = time.perf_counter()
        out = model.generate(bprompt, max_new_tokens=bnew,
                             decode_strategy="beam_search",
                             num_beams=beams)
        _ = out.numpy()
        best_dt = min(best_dt, time.perf_counter() - t0)
    print(json.dumps({
        "metric": "gpt_beam_search_tokens_per_sec_per_chip",
        "value": round(bbatch * bnew / best_dt, 2),
        "unit": f"tokens/s ({'tpu' if on_tpu else 'cpu-smoke'}, "
                f"{beams} beams, bs{bbatch}, prompt {prompt_len} + "
                f"{bnew} new, bf16)",
        "vs_baseline": 0.0,
    }))


if __name__ == "__main__":
    main()
