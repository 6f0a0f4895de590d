"""The plain references and the comparisons that decide `correct`.

`gpt_forward` is GPT-2/GPT-3's decoder (Radford et al. 2019; Brown et
al. 2020) in straightforward jax.numpy and float32: learned position
embeddings, pre-LayerNorm blocks (LN, fused QKV, causal softmax
attention, projection; LN, 4x MLP with tanh-GELU), final LayerNorm, LM
head tied to the token embedding. No kernels, no cache, no batching
tricks; matmuls at "highest" precision (on a TPU a float32 matmul runs
in lower precision otherwise). It reads the program's weights by name
(`gpt_weights`) and shares no code with it. Departures from the papers:
the vocabulary is padded to 50304; GPT-3's alternating dense and locally
banded sparse attention is dense throughout, as in the program.

Weight layout as paddle_tpu.nlp.gpt stores it: Linear weights are
[in, out]; the fused QKV output is [heads, (q | k | v) x head_dim].
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def gpt_weights(model):
    """{name: jax array} of the program's GPTForCausalLM, as stored."""
    return {n: p._value for n, p in model.named_parameters()}


def _ln(x, w, b, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _f32(w, name):
    return w[name].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("heads",))
def _block(x, w, heads):
    """One pre-LN decoder block; `w` holds this layer's tensors under
    their names inside the layer."""
    b, l, h = x.shape
    d = h // heads
    a = _ln(x, _f32(w, "ln1.weight"), _f32(w, "ln1.bias"))
    qkv = jnp.einsum("blh,ho->blo", a, _f32(w, "attn.qkv_proj.weight"),
                     precision=_HI) + _f32(w, "attn.qkv_proj.bias")
    q, k, v = jnp.split(qkv.reshape(b, l, heads, 3 * d), 3, axis=-1)
    s = jnp.einsum("bqnd,bknd->bnqk", q, k, precision=_HI) / np.sqrt(d)
    s = jnp.where(jnp.tril(jnp.ones((l, l), bool)), s, -jnp.inf)
    o = jnp.einsum("bnqk,bknd->bqnd", jax.nn.softmax(s, axis=-1), v,
                   precision=_HI).reshape(b, l, h)
    x = x + jnp.einsum("blh,ho->blo", o, _f32(w, "attn.out_proj.weight"),
                       precision=_HI) + _f32(w, "attn.out_proj.bias")
    m = _ln(x, _f32(w, "ln2.weight"), _f32(w, "ln2.bias"))
    m = jnp.einsum("blh,hf->blf", m, _f32(w, "mlp.fc1.weight"),
                   precision=_HI) + _f32(w, "mlp.fc1.bias")
    m = jax.nn.gelu(m, approximate=True)
    return x + jnp.einsum("blf,fh->blh", m, _f32(w, "mlp.fc2.weight"),
                          precision=_HI) + _f32(w, "mlp.fc2.bias")


@jax.jit
def _embed(ids, wte, wpe):
    return (wte[ids] + wpe[jnp.arange(ids.shape[1])]).astype(jnp.float32)


@jax.jit
def _head(x, rows, cols, lnw, lnb, wte):
    """Float32 logits [n, V] of the hidden states at (rows, cols)."""
    h = _ln(x[rows, cols], lnw.astype(jnp.float32), lnb.astype(jnp.float32))
    return jnp.einsum("nh,vh->nv", h, wte.astype(jnp.float32), precision=_HI)


def gpt_hidden(weights, model_cfg, ids):
    """Hidden states [B, L, H] before the final LayerNorm. One jitted
    block called layer by layer and ONE SEQUENCE AT A TIME: one small
    program whatever the depth or batch, one layer's float32 weights
    alive at a time, and score matrices of one sequence only (at
    "highest" precision the TPU splits every float32 product into
    several bf16 passes, each with temporaries of the full result: a
    batch of 16 x 1024 needed 18 GB)."""
    wte = weights["gpt.embeddings.word_embeddings.weight"]
    wpe = weights["gpt.embeddings.position_embeddings.weight"]
    heads = model_cfg["num_attention_heads"]
    layers = []
    for i in range(model_cfg["num_hidden_layers"]):
        pre = f"gpt.layers.{i}."
        layers.append({n[len(pre):]: v for n, v in weights.items()
                       if n.startswith(pre)})
    out = []
    for row in np.asarray(ids):
        x = _embed(jnp.asarray(row[None]), wte, wpe)
        for layer in layers:
            x = _block(x, layer, heads=heads)
        out.append(x)
    return jnp.concatenate(out)


def check_width(mix):
    """One padded width per traffic mix: its longest prompt plus its
    longest answer, rounded up to 128."""
    longest = mix["prompt_len"]["max"] + mix["max_tokens"]["max"]
    return -(-longest // 128) * 128


def dense_gaps(weights, model_cfg, prompts, outputs, width):
    """Teacher-forced comparison with the reference: one forward over
    every prompt + emitted tokens, right-padded to `width` (causal, so
    padding cannot reach back; one width, so one compiled program for
    every run). For each emitted token, gap = best reference logit at its
    position - reference logit of the token the engine chose. Returns
    (max gap, share of tokens that ARE the reference argmax).

    Logits and not tokens are compared because with random weights the
    largest logit changes on rounding: bf16 logits of magnitude 2..4 are
    2^-6 apart, so a tolerance of 0.0625 is 4 bf16 steps at the top of
    the row, while a wrong page, mask or position is off by the row's
    whole spread (several units). Computing the engine in a lower
    precision than bf16 would fail it."""
    seqs = [list(p) + list(o) for p, o in zip(prompts, outputs)]
    if max(len(s) for s in seqs) > width:
        raise ValueError(f"a sampled sequence is longer than {width}")
    ids = np.zeros((len(seqs), width), np.int32)
    for i, s in enumerate(seqs):
        ids[i, :len(s)] = s
    x = gpt_hidden(weights, model_cfg, ids)
    # every emitted token's position, padded to one shape per cell
    n_out = max(len(o) for o in outputs)
    rows = np.repeat(np.arange(len(seqs)), n_out)
    cols = np.concatenate([
        np.minimum(len(p) - 1 + np.arange(n_out), len(p) + len(o) - 2)
        for p, o in zip(prompts, outputs)])
    logits = np.asarray(_head(
        x, jnp.asarray(rows), jnp.asarray(cols), weights["gpt.ln_f.weight"],
        weights["gpt.ln_f.bias"],
        weights["gpt.embeddings.word_embeddings.weight"]))
    logits = logits.reshape(len(seqs), n_out, -1)
    worst, hits, total = 0.0, 0, 0
    for i, o in enumerate(outputs):
        lg = logits[i, :len(o)]
        chosen = lg[np.arange(len(o)), np.asarray(o)]
        worst = max(worst, float((lg.max(-1) - chosen).max()))
        hits += int((lg.argmax(-1) == np.asarray(o)).sum())
        total += len(o)
    return worst, hits / total


def gpt_loss(weights, model_cfg, ids, labels):
    """Mean cross-entropy of labels under the reference's logits, as
    GPTForCausalLM(ids, labels=labels) defines it (no shift: the caller
    supplies the labels)."""
    x = gpt_hidden(weights, model_cfg, ids)
    return float(_loss(x, jnp.asarray(labels), weights["gpt.ln_f.weight"],
                       weights["gpt.ln_f.bias"],
                       weights["gpt.embeddings.word_embeddings.weight"]))


@jax.jit
def _loss(x, labels, lnw, lnb, wte):
    h = _ln(x, lnw.astype(jnp.float32), lnb.astype(jnp.float32))

    def per_row(args):          # one sequence at a time: [L, V] logits
        hr, lr = args
        lg = jnp.einsum("lh,vh->lv", hr, wte.astype(jnp.float32),
                        precision=_HI)
        return (jax.nn.logsumexp(lg, -1)
                - jnp.take_along_axis(lg, lr[:, None], -1)[:, 0]).mean()
    return jax.lax.map(per_row, (h, labels)).mean()


def n_params(model_cfg):
    """Parameters of the decoder, tied head counted once."""
    v, h = model_cfg["vocab_size"], model_cfg["hidden_size"]
    f = model_cfg.get("intermediate_size") or 4 * h
    per_layer = (3 * h * h + 3 * h) + (h * h + h) + (h * f + f) \
        + (f * h + h) + 4 * h
    return (v * h + model_cfg["max_position_embeddings"] * h
            + model_cfg["num_hidden_layers"] * per_layer + 2 * h)


def train_flops_per_token(model_cfg, seqlen):
    """Operations the forward and backward passes require per token:
    6 N for the matmuls over N parameters plus 12 L h s for attention's
    score and value products (bench.py's formula; recomputation not
    counted)."""
    return 6 * n_params(model_cfg) + 12 * model_cfg["num_hidden_layers"] \
        * model_cfg["hidden_size"] * seqlen
