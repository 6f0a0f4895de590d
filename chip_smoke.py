"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Run with no arguments on a machine with one TPU chip:

    python chip_smoke.py

One process, phases in order:

- *device*: JAX's first device must be a TPU, and nothing may ask for the
  CPU or for interpret-mode kernels. The script never falls back.
- *serve*: `GPTForCausalLM` at the GPT-3 1.3B widths (24 layers, hidden
  2048, 16 heads x 128, vocab 50304, 2048 positions; bf16, weights from
  `paddle.seed`), a `ServingEngine` (page_size 16, max_len 2048, 8 slots,
  chunk 128, default attention), `serving.http.serve` on a loopback port,
  and a handful of overlapping `/v1/completions` requests. Checks status,
  token counts and finish reasons, that the Pallas page walk is in the
  program that ran, and that every token the engine emitted is, under the
  model's own dense forward of the same sequence (which never touches the
  paged walk), within a stated tolerance of the dense argmax.
- *train*: `bench.py`'s TPU configuration (GPT-124M, bs 16 x 1024, bf16,
  AdamW, `jit.compile_train_step`), five steps on one fixed batch: finite
  loss, lower at step 5 than at step 1, flash-attention and fused
  LayerNorm kernels in the program.

`--chips 4` (run by hand on a four-chip host) runs ONLY the cross-chip
path and what it is compared with: the tensor-parallel serving replica
(`ServingEngine(mesh="dp1mp4")`, then `dp2mp2`) at the 1.3B widths
against a one-device engine on the same prompts, with the collective
census and a check that pools and weights really sit on four devices.
The hybrid-parallel TRAIN legs (`__graft_entry__.dryrun_multichip`) are
not in it: their Pallas kernels are not yet run per device under a
training mesh, so on real chips they stop at "Mosaic kernels cannot be
automatically partitioned" (ROADMAP S8).

Any failed phase raises, so the exit code is non-zero and no result line
is printed. On success the LAST line of standard output is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Each phase is a function of a small config object, so tests rehearse it
on the CPU at a tiny size (tests/test_chip_smoke.py) without any option
on the script. Compile cache: `paddle_tpu.utils.compile_cache`.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import threading
import time
import urllib.request

GPT3_1P3B = dict(vocab_size=50304, hidden_size=2048, num_hidden_layers=24,
                 num_attention_heads=16, max_position_embeddings=2048)


@dataclasses.dataclass
class ServeConfig:
    model: dict = dataclasses.field(default_factory=lambda: dict(GPT3_1P3B))
    dtype: str = "bfloat16"
    num_slots: int = 8
    max_len: int = 2048
    page_size: int = 16
    chunk_len: int = 128
    # (prompt length, stream?) of the overlapping requests
    requests: tuple = ((5, False), (300, False), (1500, False), (40, True))
    max_tokens: int = 32
    # the engine's token may trail the dense forward's best logit by at
    # most this much (logit units). bf16 logits of magnitude 2..4 are
    # 2^-6 apart, so 0.0625 is 4 bf16 steps at the top of the row; a
    # wrong page or mask is off by the row's whole spread (several
    # units). The first run on a v5e measured 0.0156 and 0.977.
    tolerance: float = 0.0625
    # and at least this share of tokens must BE the dense argmax
    min_match: float = 0.9
    on_chip: bool = True       # require tpu_custom_call + memory stats
    seed: int = 0


@dataclasses.dataclass
class TrainConfig:
    model: dict = None         # default: bench.GPT_124M
    batch: int = None          # default: bench.TPU_BATCH
    seqlen: int = None         # default: bench.TPU_SEQLEN
    steps: int = 5
    on_chip: bool = True       # require the flash + LN kernels
    seed: int = 0


@dataclasses.dataclass
class MultiChipConfig(ServeConfig):
    """ServeConfig's engine and tolerances; depth cut to 4 layers for
    chip time (four chips cost four times as much a second)."""
    model: dict = dataclasses.field(default_factory=lambda: dict(
        GPT3_1P3B, num_hidden_layers=4))
    meshes: tuple = ("dp1mp4", "dp2mp2")
    requests: tuple = ((5, False), (300, False), (700, False))
    max_tokens: int = 16


class SmokeFailure(RuntimeError):
    """A phase's check did not hold."""


def _require(ok, what):
    # not `assert`: the checks must survive `python -O`
    if not ok:
        raise SmokeFailure(str(what))


def check_device(n_chips: int = 1):
    """First thing: a TPU, asked for by nothing but the default."""
    for var in ("PADDLE_TPU_PALLAS_INTERPRET", "PADDLE_TPU_FORCE_CPU_DEVICES"):
        if os.environ.get(var):
            raise RuntimeError(f"chip_smoke: {var} is set; this script "
                               f"runs the real kernels on the real chip")
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise RuntimeError(f"chip_smoke: JAX found no TPU "
                           f"(platform {devs[0].platform!r})")
    if len(devs) < n_chips:
        raise RuntimeError(f"chip_smoke: --chips {n_chips} needs "
                           f"{n_chips} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _build_gpt(model_kwargs, dtype, seed):
    import paddle_tpu as paddle
    from paddle_tpu.nlp import GPTConfig, GPTForCausalLM
    paddle.seed(seed)
    model = GPTForCausalLM(GPTConfig(hidden_dropout_prob=0.0,
                                     attention_probs_dropout_prob=0.0,
                                     **model_kwargs))
    model.to(dtype=dtype)
    model.eval()
    return model


def _complete(url, prompt, max_tokens, stream):
    """POST one /v1/completions; returns (status, token_ids,
    finish_reason, usage)."""
    body = {"prompt": prompt, "max_tokens": max_tokens, "stream": stream}
    req = urllib.request.Request(
        url + "/v1/completions", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    opener = urllib.request.build_opener(urllib.request.ProxyHandler({}))
    with opener.open(req, timeout=1000) as resp:
        if not stream:
            out = json.loads(resp.read())
            ch = out["choices"][0]
            return (resp.status, ch["token_ids"], ch["finish_reason"],
                    out["usage"])
        toks, final = [], None
        for line in resp:
            line = line.strip()
            if not line.startswith(b"data: ") or line == b"data: [DONE]":
                continue
            frame = json.loads(line[len(b"data: "):])
            tok = frame["choices"][0]["token"]
            if tok is None:
                final = frame
            else:
                toks.append(tok)
        return (resp.status, toks, final["choices"][0]["finish_reason"],
                final["usage"])


def _dense_gaps(model, prompts, outputs):
    """Teacher-forced comparison with the model's own dense forward:
    one `model(ids)` over every prompt + emitted tokens (right-padded;
    causal, so padding cannot reach back). For each emitted token,
    gap = best dense logit at its position - dense logit of the token
    the engine chose. Returns (max gap, share of exact argmax hits)."""
    import numpy as np
    import jax.numpy as jnp
    import paddle_tpu as paddle
    seqs = [list(p) + list(o) for p, o in zip(prompts, outputs)]
    width = -(-max(len(s) for s in seqs) // 128) * 128
    ids = np.zeros((len(seqs), width), np.int64)
    for i, s in enumerate(seqs):
        ids[i, :len(s)] = s
    with paddle.no_grad():      # eager: no tape of 24 layers' activations
        logits = model(paddle.to_tensor(ids))._value   # [N, width, V]
    gaps, hits, total = [], 0, 0
    for i, (p, o) in enumerate(zip(prompts, outputs)):
        rows = logits[i, len(p) - 1:len(p) - 1 + len(o)].astype(jnp.float32)
        best = np.asarray(rows.max(axis=-1))
        chosen = np.asarray(rows[jnp.arange(len(o)), jnp.asarray(o)])
        pred = np.asarray(rows.argmax(axis=-1))
        gaps.append(float((best - chosen).max()))
        hits += int((pred == np.asarray(o)).sum())
        total += len(o)
    return max(gaps), hits / total


def serve_phase(cfg: ServeConfig) -> dict:
    import numpy as np
    import jax
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.serving.http import serve

    t0 = time.perf_counter()
    model = _build_gpt(cfg.model, cfg.dtype, cfg.seed)
    engine = ServingEngine(model, num_slots=cfg.num_slots,
                           max_len=cfg.max_len, page_size=cfg.page_size,
                           chunk_len=cfg.chunk_len)
    print(f"serve: model {cfg.model} {cfg.dtype}, engine slots="
          f"{cfg.num_slots} max_len={cfg.max_len} page_size="
          f"{cfg.page_size} chunk={cfg.chunk_len} attn_impl="
          f"{engine.attn_impl} pages={engine.num_pages}; built in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    rng = np.random.RandomState(cfg.seed)
    vocab = cfg.model["vocab_size"]
    prompts = [rng.randint(0, vocab, size=n).tolist()
               for n, _ in cfg.requests]
    server = serve([engine])
    try:
        # the first request pays for the compilation of the one step
        t0 = time.perf_counter()
        status, toks, _, _ = _complete(server.url, prompts[0][:4], 2,
                                       False)
        first_s = time.perf_counter() - t0
        _require(status == 200 and len(toks) == 2, (status, toks))
        print(f"serve: first request (compilation of the unified step "
              f"included) {first_s:.1f}s", flush=True)
        results = [None] * len(prompts)

        def client(i):
            results[i] = _complete(server.url, prompts[i],
                                   cfg.max_tokens, cfg.requests[i][1])

        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
    finally:
        server.drain()
    outputs = []
    for i, res in enumerate(results):
        _require(res is not None,
                 f"request {i} raised in its client thread")
        status, toks, reason, usage = res
        _require(status == 200, (i, status))
        _require(len(toks) == cfg.max_tokens, (i, len(toks)))
        _require(reason == "length", (i, reason))
        _require(usage["prompt_tokens"] == len(prompts[i]), (i, usage))
        _require(usage["completion_tokens"] == cfg.max_tokens, (i, usage))
        _require(all(0 <= t < vocab for t in toks), (i, toks))
        outputs.append(toks)
    print(f"serve: {len(prompts)} overlapping requests, prompts "
          f"{[len(p) for p in prompts]} tokens, stream "
          f"{[s for _, s in cfg.requests]}, each {cfg.max_tokens} new "
          f"tokens, status 200, finish_reason length; {wall:.1f}s wall "
          f"for the batch", flush=True)
    n_kernels = engine.lowered_unified_step().as_text().count(
        "tpu_custom_call")
    print(f"serve: unified step holds {n_kernels} tpu_custom_call sites",
          flush=True)
    if cfg.on_chip:
        _require(n_kernels > 0, "the Pallas page walk is not in the step")
    gap, match = _dense_gaps(model, prompts, outputs)
    print(f"serve: dense reference (model(ids), teacher-forced): "
          f"tolerance {cfg.tolerance} logit units, measured max gap "
          f"{gap:.4f}; exact argmax on {match:.3f} of tokens (at least "
          f"{cfg.min_match})", flush=True)
    _require(gap <= cfg.tolerance, f"gap {gap} > {cfg.tolerance}")
    _require(match >= cfg.min_match, f"argmax share {match}")
    stats = jax.devices()[0].memory_stats()
    if cfg.on_chip:
        print(f"serve: peak_bytes_in_use "
              f"{stats['peak_bytes_in_use']}", flush=True)
    return {"first_request_s": first_s, "gap": gap, "match": match,
            "kernels": n_kernels, "outputs": outputs}


def train_phase(cfg: TrainConfig) -> dict:
    import numpy as np
    import bench

    t0 = time.perf_counter()
    step, ids, labels, _ = bench.build_train_step(
        cfg.model or bench.GPT_124M, cfg.batch or bench.TPU_BATCH,
        cfg.seqlen or bench.TPU_SEQLEN, seed=cfg.seed)
    text = step.compile_info(ids, labels).as_text()
    kernels = {name: text.count(name)
               for name in ("_fa_kernel", "_fa_dq_kernel",
                            "_fa_dkv_kernel", "_ln_fwd_kernel",
                            "_ln_bwd_kernel")}
    print(f"train: kernels in the step {kernels}", flush=True)
    if cfg.on_chip:
        missing = [k for k, n in kernels.items() if n == 0]
        _require("tpu_custom_call" in text and not missing, missing)
    losses = []
    for i in range(cfg.steps):
        losses.append(float(step(ids, labels)))      # host fetch
        if i == 0:
            print(f"train: first step (compilation included) "
                  f"{time.perf_counter() - t0:.1f}s", flush=True)
    print(f"train: ids {tuple(ids.shape)}, losses "
          f"{[round(x, 4) for x in losses]}", flush=True)
    _require(all(np.isfinite(losses)), losses)
    _require(losses[-1] < losses[0], losses)
    return {"losses": losses, "kernels": kernels}


def multichip_phase(cfg: MultiChipConfig) -> dict:
    """The tensor-parallel serving replica over each mesh of
    `cfg.meshes`, and the one-device engine it is compared with, on
    the same prompts: every engine's tokens are held to the dense
    forward like the serve phase's; the mesh engines also to the
    collective census of serving/tp.py (one output all-gather per
    layer, no all-reduce) and to pools and sharded weights that sit on
    distinct devices with 1/mp of the bytes each. Whether the mesh
    engines' tokens equal the one-device engine's is printed."""
    import numpy as np
    from paddle_tpu.serving import SamplingParams, ServingEngine

    model = _build_gpt(cfg.model, cfg.dtype, cfg.seed)
    print(f"multichip: model {cfg.model} {cfg.dtype} (depth cut for "
          f"chip time; widths as published)", flush=True)
    rng = np.random.RandomState(cfg.seed)
    prompts = [rng.randint(0, cfg.model["vocab_size"], size=n).tolist()
               for n, _ in cfg.requests]
    tokens = {}
    for mesh in (None,) + tuple(cfg.meshes):
        t0 = time.perf_counter()
        eng = ServingEngine(model, num_slots=cfg.num_slots,
                            max_len=cfg.max_len, page_size=cfg.page_size,
                            chunk_len=cfg.chunk_len, mesh=mesh)
        outs = eng.generate(
            [np.asarray(p, np.int64) for p in prompts],
            [SamplingParams(max_new_tokens=cfg.max_tokens)
             for _ in prompts])
        tokens[mesh] = [list(o.token_ids) for o in outs]
        _require(all(len(t) == cfg.max_tokens for t in tokens[mesh]),
                 tokens[mesh])
        n_kernels = eng.lowered_unified_step().as_text().count(
            "tpu_custom_call")
        gap, match = _dense_gaps(model, prompts, tokens[mesh])
        print(f"multichip: engine mesh={mesh}: {len(prompts)} prompts "
              f"{[len(p) for p in prompts]} x {cfg.max_tokens} tokens in "
              f"{time.perf_counter() - t0:.1f}s (compilation included); "
              f"{n_kernels} tpu_custom_call sites; dense reference "
              f"tolerance {cfg.tolerance}, max gap {gap:.4f}, exact "
              f"argmax {match:.3f} (at least {cfg.min_match})",
              flush=True)
        _require(gap <= cfg.tolerance and match >= cfg.min_match,
                 (mesh, gap, match))
        if cfg.on_chip:
            _require(n_kernels > 0,
                     "the Pallas page walk is not in the step")
        if mesh is None:
            continue
        mp, size = eng.tp.mp, eng.tp.size
        coll = eng.collective_counts()
        _require(coll["all_reduce"] == 0, coll)
        _require(coll["all_gather"] == eng.n_layers, coll)
        # placement: code that has only seen virtual devices may put
        # everything on the first
        pool = eng._ct[0][0]
        per_dev = pool.addressable_shards[0].data.nbytes
        _require(len({s.device for s in pool.addressable_shards}) == size,
                 "KV pool is not on every device of the mesh")
        _require(per_dev * mp == pool.nbytes, (per_dev, pool.nbytes, mp))
        sharded_w = [v for v in eng._state_vals
                     if not v.sharding.is_fully_replicated]
        _require(sharded_w, "no weight is sharded over the mesh")
        for v in sharded_w:
            _require(len({s.device for s in v.addressable_shards}) == size
                     and v.addressable_shards[0].data.nbytes * mp
                     == v.nbytes, "a sharded weight is not 1/mp per device")
        same = tokens[mesh] == tokens[None]
        print(f"multichip: engine mesh={mesh}: collectives {coll}; each "
              f"KV pool on {size} devices, {per_dev} of {pool.nbytes} "
              f"bytes per device; {len(sharded_w)} weights sharded "
              f"1/{mp}; tokens identical to the one-device engine: "
              f"{same}", flush=True)
    return {"tokens": tokens}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    device = check_device(args.chips)
    # (before anything is printed: alone in a directory, without the
    # program, the script ends here)
    from paddle_tpu.utils.compile_cache import use_compile_cache
    use_compile_cache()
    print(f"device: {device}", flush=True)
    if args.chips == 4:
        multichip_phase(MultiChipConfig())
    else:
        serve_phase(ServeConfig())
        # the 1.3B weights and the KV pools must be gone before the
        # trainer fills the chip
        gc.collect()
        train_phase(TrainConfig())
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
