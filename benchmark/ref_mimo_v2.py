"""The plain reference for MiMo-V2-Flash and the comparison that decides
`correct` in its cells.

The layer equations of the configuration's source, in straightforward
jax.numpy and float32 with every product at "highest" precision; no
kernels, no cache, no batching, and no code shared with `paddle_tpu`.
It reads the program's weights by name and upcasts them a layer, and
the routed experts a block of `EXPERT_BLOCK` experts, at a time, so that
it fits on the chip beside the bf16 weights themselves. One sequence at
a time, its queries in blocks of `QUERY_BLOCK` and its tokens through
the FFNs in blocks of `TOKEN_BLOCK`, so that a 30k-token prompt fits.

T tokens, h hidden, eps `layernorm_epsilon`, no biases:

    x1 = x + Attn_l(RMSNorm(x));  y = x1 + FFN_l(RMSNorm(x1))
    final RMSNorm, untied head over the vocabulary held here

Attn_l (`hybrid_layer_pattern[l]`: 0 full, 1 window): q = u Wq (H heads
of Dk), k = u Wk (n_kv heads of Dk), v = u Wv (n_kv heads of Dv), with
(H, n_kv, Dk, Dv) the kind's (`num_attention_heads`,
`num_key_value_heads`, `head_dim`, `v_head_dim`, or their `swa_`
forms); rope on the first int(`partial_rotary_factor` x Dk) dims of q
and k, dim i paired with i + rot/2, theta `rope_theta` or
`swa_rope_theta`; query head j reads kv head j // (H / n_kv);
s = q.k / sqrt(Dk); key i visible to query t iff i <= t, and in a
window layer t - `sliding_window` < i. A window layer's softmax has
the head's sink logit b in its denominator (`add_swa_attention_sink_bias`):
p_i = exp(s_i - m) / (exp(b - m) + sum exp(s - m)), m the max over the
scores and b. a = `attention_value_scale` x sum_i p_i v_i; x += a Wo.
FFN of a dense layer (`moe_layer_freq` 0): (silu(x Wg) * (x Wu)) Wd.
Of an expert layer: r = x Wr over all `n_routed_experts` in float32,
sigma = sigmoid(r); S = the top `num_experts_per_tok` of sigma + c
(c = `e_score_correction_bias`, choosing only); w_e = sigma_e /
sum_{e' in S} sigma_e'; FFN(x) = sum_{e in S, e held here} w_e E_e(x),
every expert a SwiGLU. "Held here": experts ep_rank * E_local onward
(the configuration's share of a deployment; with ep_size 1 every
expert).

`sinks=False` leaves the sink out of every softmax and `bias=False` the
correction bias out of every choice: the two controls of the check.

NEAR TIES. The program's activations are bf16, so where a token's 8th
and 9th biased router scores nearly tie its expert set can differ from
the float32 reference's by that one expert, and the token's hidden state
then differs by more than rounding. Each position's margin is the least,
over the expert layers, of the 8th minus the 9th biased score;
`judge_choices` reports the largest gap on either side of `tie_margin`
and the near-tied tokens' share.
"""
import functools
import math

import numpy as np
import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
EXPERT_BLOCK = 8        # experts upcast to float32 at a time
QUERY_BLOCK = 64        # queries whose score rows are alive at a time
TOKEN_BLOCK = 4096      # tokens an FFN takes at a time
WIDTH_STEP = 4096       # a sequence is checked at its length rounded up


def mimo_weights(model):
    """{name: jax array} of the program's MiMoV2ForCausalLM, as stored."""
    return {n: p._value for n, p in model.named_parameters()}


def geometry(cfg, layer):
    """(H, n_kv, Dk, Dv, theta, window or None, sink) of the layer."""
    if cfg["hybrid_layer_pattern"][layer]:
        return (cfg["swa_num_attention_heads"],
                cfg["swa_num_key_value_heads"], cfg["swa_head_dim"],
                cfg["swa_v_head_dim"], float(cfg["swa_rope_theta"]),
                cfg["sliding_window"], cfg["add_swa_attention_sink_bias"])
    return (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["v_head_dim"], float(cfg["rope_theta"]),
            None, cfg["add_full_attention_sink_bias"])


def _rms(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _mm(a, b):
    return jnp.matmul(a, b.astype(jnp.float32), precision=_HI)


def rope_tables(theta, rot, n_pos):
    """(cos, sin) float32 [n_pos, rot / 2]: inv_freq theta^(-2i/rot)."""
    inv = theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    ang = np.arange(n_pos, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def _rope(x, cos, sin):
    """x [T, n, d]; rotary over the first 2 * cos.shape[1] dimensions."""
    half = cos.shape[1]
    a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * c - b * s, b * c + a * s, rest], -1)


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "n_kv", "dk", "dv", "window", "eps", "value_scale"))
def _attention(x, w, cos, sin, sink, *, n_heads, n_kv, dk, dv, window, eps,
               value_scale):
    """x [T, h] -> x + Attn(RMSNorm(x)); `w` this layer's tensors, sink
    f32 [H] or None."""
    t = x.shape[0]
    a = _rms(x, w["input_layernorm.weight"], eps)
    rep = n_heads // n_kv
    q = _rope(_mm(a, w["self_attn.q_proj.weight"]).reshape(t, n_heads, dk),
              cos, sin).reshape(t, n_kv, rep, dk)
    k = _rope(_mm(a, w["self_attn.k_proj.weight"]).reshape(t, n_kv, dk),
              cos, sin)
    v = _mm(a, w["self_attn.v_proj.weight"]).reshape(t, n_kv, dv)
    # a window layer's block of queries reads the block and the window
    # before it only (zeros in front of position 0, masked)
    span = t if window is None else QUERY_BLOCK + window
    if window is not None:
        k = jnp.pad(k, ((window, 0), (0, 0), (0, 0)))
        v = jnp.pad(v, ((window, 0), (0, 0), (0, 0)))

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, QUERY_BLOCK, 0)
        q_pos = (start + jnp.arange(QUERY_BLOCK))[:, None]
        if window is None:
            kb, vb, key_pos = k, v, jnp.arange(t)[None, :]
        else:
            kb = jax.lax.dynamic_slice_in_dim(k, start, span, 0)
            vb = jax.lax.dynamic_slice_in_dim(v, start, span, 0)
            key_pos = (start - window + jnp.arange(span))[None, :]
        s = jnp.einsum("qgrd,kgd->grqk", qb, kb, precision=_HI) \
            / math.sqrt(dk)
        live = key_pos <= q_pos
        if window is not None:
            live = live & (key_pos > q_pos - window) & (key_pos >= 0)
        s = jnp.where(live[None, None], s, -jnp.inf)
        m = s.max(-1, keepdims=True)
        if sink is not None:
            b = sink.astype(jnp.float32).reshape(n_kv, rep, 1, 1)
            m = jnp.maximum(m, b)
        p = jnp.exp(s - m)
        den = p.sum(-1, keepdims=True)
        if sink is not None:
            den = den + jnp.exp(b - m)
        return jnp.einsum("grqk,kgd->qgrd", p / den, vb, precision=_HI)
    # T is padded to a multiple of QUERY_BLOCK by the caller
    o = jax.lax.map(block, jnp.arange(0, t, QUERY_BLOCK))
    o = o.reshape(t, n_heads * dv) * value_scale
    return x + _mm(o, w["self_attn.o_proj.weight"])


def _swiglu(a, wg, wu, wd):
    return _mm(jax.nn.silu(_mm(a, wg)) * _mm(a, wu), wd)


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_ffn(x, w, *, eps):
    a = _rms(x, w["post_attention_layernorm.weight"], eps)
    return x + _swiglu(a, w["mlp.gate_proj.weight"],
                       w["mlp.up_proj.weight"], w["mlp.down_proj.weight"])


@functools.partial(jax.jit, static_argnames=("top_k", "scaling", "norm",
                                             "eps"))
def _route(x, w, c, *, top_k, scaling, norm, eps):
    """-> (normed input, weight of every expert for every token
    [T, E], 0 outside the token's top-k; the token's margin [T]: its
    k-th biased score minus its (k+1)-th). c: the selection bias or
    zeros."""
    a = _rms(x, w["post_attention_layernorm.weight"], eps)
    sig = jax.nn.sigmoid(_mm(a, w["mlp.gate.weight"]))
    pick, idx = jax.lax.top_k(sig + c[None, :], top_k + 1)
    margin = pick[:, top_k - 1] - pick[:, top_k]
    idx = idx[:, :top_k]
    top = jnp.take_along_axis(sig, idx, axis=-1)
    if norm:
        top = top / top.sum(-1, keepdims=True)
    weight = jnp.zeros_like(sig).at[jnp.arange(sig.shape[0])[:, None],
                                    idx].set(top * scaling)
    return a, weight, margin


@jax.jit
def _expert_block(a, weight, wg, wu, wd):
    """sum over this block's experts of weight[:, e] * E_e(a): every
    expert over every token, the unrouted ones weighted 0."""
    g = jnp.einsum("th,ehf->etf", a, wg.astype(jnp.float32), precision=_HI)
    u = jnp.einsum("th,ehf->etf", a, wu.astype(jnp.float32), precision=_HI)
    y = jnp.einsum("etf,efh->eth", jax.nn.silu(g) * u,
                   wd.astype(jnp.float32), precision=_HI)
    return jnp.einsum("eth,te->th", y, weight, precision=_HI)


def sparse_ffn(x, w, cfg, share=None, bias=True, residual=True):
    """x [T, h] -> (x + FFN(RMSNorm(x)), margin [T]). `share` =
    (ep_size, ep_rank): only the experts that rank holds contribute;
    None takes the configuration's. `bias` False leaves the selection
    bias out. With `residual` False the result is the routed part ALONE:
    what the share test adds up."""
    c = w["mlp.e_score_correction_bias"].astype(jnp.float32)
    a, weight, margin = _route(
        x, w, c if bias else jnp.zeros_like(c),
        top_k=cfg["num_experts_per_tok"],
        scaling=float(cfg.get("routed_scaling_factor") or 1.0),
        norm=bool(cfg["norm_topk_prob"]),
        eps=float(cfg["layernorm_epsilon"]))
    size, rank = share or (cfg.get("ep_size", 1), cfg.get("ep_rank", 0))
    n_local = weight.shape[1] // size
    if w["mlp.experts_gate"].shape[0] != n_local:
        raise ValueError(f"the weights hold {w['mlp.experts_gate'].shape[0]} "
                         f"experts, the share {n_local}")
    routed = jnp.zeros_like(x)
    for e0 in range(0, n_local, EXPERT_BLOCK):
        e1 = min(e0 + EXPERT_BLOCK, n_local)
        routed = routed + _expert_block(
            a, weight[:, rank * n_local + e0:rank * n_local + e1],
            w["mlp.experts_gate"][e0:e1], w["mlp.experts_up"][e0:e1],
            w["mlp.experts_down"][e0:e1])
    return (x + routed if residual else routed), margin


def mimo_hidden(weights, cfg, ids, sinks=True, bias=True):
    """One sequence: ids [T] -> (hidden states [T, h] before the final
    norm, margin [T]: the least over the expert layers). T is padded on
    the right to a multiple of QUERY_BLOCK (causal, so padding cannot
    reach back) and cut again."""
    t = len(ids)
    pad = -(-t // QUERY_BLOCK) * QUERY_BLOCK
    row = np.zeros((pad,), np.int32)
    row[:t] = ids
    x = weights["model.embed_tokens.weight"][jnp.asarray(row)] \
        .astype(jnp.float32)
    eps = float(cfg["layernorm_epsilon"])
    margin = jnp.full((pad,), jnp.inf, jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        pre = f"model.layers.{i}."
        w = {n[len(pre):]: v for n, v in weights.items()
             if n.startswith(pre)}
        n_heads, n_kv, dk, dv, theta, window, sink = geometry(cfg, i)
        cos, sin = rope_tables(theta, int(dk * cfg["partial_rotary_factor"]),
                               pad)
        x = _attention(
            x, w, cos, sin, w["self_attn.sinks"] if sink and sinks else None,
            n_heads=n_heads, n_kv=n_kv, dk=dk, dv=dv, window=window, eps=eps,
            value_scale=float(cfg["attention_value_scale"]))
        # position-wise: a block of tokens at a time
        out = []
        for b0 in range(0, pad, TOKEN_BLOCK):
            xb = x[b0:b0 + TOKEN_BLOCK]
            if cfg["moe_layer_freq"][i]:
                xb, m = sparse_ffn(xb, w, cfg, bias=bias)
                margin = margin.at[b0:b0 + TOKEN_BLOCK].min(m)
            else:
                xb = _dense_ffn(xb, w, eps=eps)
            out.append(xb)
        x = jnp.concatenate(out)
    return x[:t], margin[:t]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm_w, head_w, *, eps):
    return _mm(_rms(x, norm_w, eps), head_w)


def mimo_logits(weights, cfg, ids, positions=None, sinks=True, bias=True):
    """Float32 logits [len(positions), V] of one sequence (all its
    positions by default), and the positions' margins."""
    x, margin = mimo_hidden(weights, cfg, ids, sinks, bias)
    if positions is not None:
        x, margin = x[jnp.asarray(positions)], margin[jnp.asarray(positions)]
    return _head(x, weights["model.norm.weight"], weights["lm_head.weight"],
                 eps=float(cfg["layernorm_epsilon"])), margin


def teacher_forced(weights, cfg, prompts, outputs, sinks=True, bias=True):
    """[(logits [n, V], margin [n])] a request: one forward pass over its
    prompt + emitted tokens, right-padded to its length rounded up to
    WIDTH_STEP (so that a run compiles a handful of widths), read at the
    position before each emitted token."""
    out = []
    for p, o in zip(prompts, outputs):
        seq = list(p) + list(o)
        ids = np.zeros((-(-len(seq) // WIDTH_STEP) * WIDTH_STEP,), np.int32)
        ids[:len(seq)] = seq
        pos = len(p) - 1 + np.arange(len(o))
        lg, margin = mimo_logits(weights, cfg, ids, pos, sinks, bias)
        out.append((np.asarray(lg), np.asarray(margin)))
    return out


def judge_choices(reference, chosen, tie_margin):
    """`reference` as `teacher_forced` gives it, `chosen` the token
    picked at each of its positions (the engine's emitted tokens; or,
    for a control, the argmax of a variant's logits over the same
    contexts). For each, gap = best reference logit - reference logit
    of the chosen token: logits and not tokens are compared, because
    with random weights the largest logit changes on rounding. Returns
    `gap` (the largest over the tokens whose router margin is at least
    `tie_margin`), `tie_gap` (over the others; 0.0 if none),
    `tie_share` (their share), `mean_gap` (over ALL tokens), `match`
    (share of ALL tokens that are the reference's argmax), `tokens`,
    `min_margin`, and `each`: every token's gap and margin."""
    gaps, margins, hits = [], [], 0
    for (lg, margin), o in zip(reference, chosen):
        o = np.asarray(o)
        gaps.extend((lg.max(-1) - lg[np.arange(len(o)), o]).tolist())
        margins.extend(margin.tolist())
        hits += int((lg.argmax(-1) == o).sum())
    gaps, margins = np.asarray(gaps), np.asarray(margins)
    tie = margins < tie_margin
    return {"gap": float(gaps[~tie].max(initial=0.0)),
            "tie_gap": float(gaps[tie].max(initial=0.0)),
            "tie_share": float(tie.mean()), "match": hits / len(gaps),
            "mean_gap": float(gaps.mean()), "tokens": len(gaps),
            "min_margin": float(margins.min()),
            "each": {"gap": gaps, "margin": margins}}


def passes(got, check):
    """The comparison that decides `correct`: `judge_choices`' numbers
    against the configuration's `check`."""
    return bool(got["mean_gap"] <= check["mean_gap"]
                and got["gap"] <= check["tolerance"]
                and got["tie_gap"] <= check["tie_tolerance"]
                and got["match"] >= check["min_match"])


# -- what a step's two walks must do at least ---------------------------
# (the `where.zzzz.sink_walk.roofline_share.long` and
# `where.zzzz.split_walk.roofline_share.long` metrics' operations and
# bytes: they count the work, not the implementation.) A scored (query,
# key) pair costs every query head a score over Dk values and a weighted
# sum over Dv; a distinct key a row reads has its K and V rows of every
# kv head read once, a row and layer.

def walk_step_flops(cfg, pairs=0, window=False):
    n_heads, _, dk, dv = geometry_of_kind(cfg, window)[:4]
    return pairs * 2 * n_heads * (dk + dv)


def walk_step_bytes(cfg, keys=0, window=False, itemsize=2):
    _, n_kv, dk, dv = geometry_of_kind(cfg, window)[:4]
    return keys * n_kv * (dk + dv) * itemsize


def geometry_of_kind(cfg, window):
    """`geometry` of the first layer of the kind (window or full)."""
    kind = 1 if window else 0
    return geometry(cfg, cfg["hybrid_layer_pattern"].index(kind))
