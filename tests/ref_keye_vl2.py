"""The plain reference for Keye-VL-2.0's language model and the
comparison that decides `correct` in its cells.

The layer equations of ISSUE 36 (the source's text config and
`sa_config`, whose indexer is DeepSeek-V3.2-Exp's published
formulation), in straightforward jax.numpy and float32 with every
product at "highest" precision; no kernels, no cache, no batching, and
no code shared with `paddle_tpu`. It reads the program's weights by
name and upcasts them a layer, and the routed experts a block of
`EXPERT_BLOCK` experts, at a time, and computes the indexer's scores,
the selection and the attention (over each query's selected keys and
values, gathered) a block of `QUERY_BLOCK` queries at a time, so that a
sequence of 32768 tokens fits on the chip beside the bf16 weights
themselves. One sequence at a time.

T tokens, h hidden, eps from the configuration, no biases; softmax and
norms in float32; every norm an RMSNorm but the indexer key's:

    x1 = x + Attn(norm_a(x));  y = x1 + FFN(norm_f(x1))
    final norm, untied head

Attn, u = norm_a(x):
    q = rope(rms_head(u W_q)) (H heads of D), k = rope(rms_head(u W_k)),
    v = u W_v (H_kv heads; head j reads kv head j // (H / H_kv))
    qI = rope(u W_qI) (Hi heads of Di), kI = rope(LayerNorm(u W_kI))
    (ONE head of Di, for all), w = u W_w (Hi)
    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]),  s <= t
    S_t = the positions of the `topk` largest I[t, s], s <= t, of equal
    scores the lower position first (`jax.lax.top_k`'s order); every
    s <= t while t < topk
    p = softmax over s in S_t of q[t] . k[s] / sqrt(D);  o = sum p v
    Attn = concat(o) W_o
Rope: theta from the configuration, dimension i paired with i + d/2
over the WHOLE head (D for q and k, Di for qI and kI).
FFN: g = softmax(n W_r) over all `num_experts` in float32; S = the
`num_experts_per_tok` largest; w_e = g_e / sum_S g (norm_topk_prob);
FFN(n) = sum_{e in S, e held here} w_e E_e(n), every expert a SwiGLU.
"Held here": experts ep_rank * E_local onward; nothing stands in for
the others.

NEAR TIES. Two choices in a layer are discrete: the router's top-k and
the indexer's top-`topk`. Under the program's bf16 activations a token
whose k-th and (k+1)-th router scores nearly tie may take the other
expert, and a query whose `topk`-th and (`topk`+1)-th indexer scores
nearly tie the other key; its hidden state then differs by more than
rounding. The reference reports each position's ROUTER margin, the
least over the layers of (c_k - c_k+1) / c_k, and its SELECTION margin,
the least over the layers of (I_(topk) - I_(topk+1)) / |I_(topk)| (inf
where no more than topk positions are visible). `judge_choices` tells
the tokens under `tie_margin` (router) apart and reports the largest
gap on either side of it; what is LIMITED holds every emitted token,
near-tied or not, as in ref_deepseek_v2.py: `mean_gap` and `match`.
"""
import functools
import math

import numpy as np
import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
EXPERT_BLOCK = 4        # experts upcast to float32 at a time
QUERY_BLOCK = 64        # queries whose score rows are alive at a time


def keye_weights(model):
    """{name: jax array} of the program's KeyeVL2ForCausalLM, as
    stored."""
    return {n: p._value for n, p in model.named_parameters()}


def _f32(w):
    return w.astype(jnp.float32)


def _mm(a, b):
    return jnp.matmul(a, _f32(b), precision=_HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _layer_norm(x, w, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * _f32(w) + _f32(b)


def rope_tables(theta, rot, n_pos):
    """(cos, sin) float32 [n_pos, rot / 2]."""
    inv = float(theta) ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    ang = np.arange(n_pos, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def _rope(x, cos, sin):
    """x [T, heads, d]; rotary over all d = 2 * cos.shape[1] dimensions,
    dimension i paired with i + d / 2."""
    half = x.shape[-1] // 2
    c, s = cos[:, None, :], sin[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], -1)


def selection(score, seen, topk):
    """score [Q, T] float32 indexer scores, seen [Q, T] bool -> (at
    [Q, k] int32: the positions of each query's k = min(topk, T) largest
    seen scores, of equal ones the lower position first; chosen [Q, k]
    bool: False where the query sees fewer than that and the entry is
    no position of its own; margin [Q]: (I_(topk) - I_(topk+1)) /
    |I_(topk)|, inf where no more than topk are seen)."""
    q, t = score.shape
    k = min(int(topk), t)
    top, at = jax.lax.top_k(jnp.where(seen, score, -jnp.inf), min(k + 1, t))
    if top.shape[1] > k:
        last, nxt = top[:, k - 1], top[:, k]
        margin = jnp.where(jnp.isfinite(nxt), (last - nxt) / jnp.maximum(
            jnp.abs(last), 1e-30), jnp.inf)
    else:
        margin = jnp.full((q,), jnp.inf, jnp.float32)
    return at[:, :k], jnp.isfinite(top[:, :k]), margin


@functools.partial(jax.jit, static_argnames=(
    "n", "n_kv", "d", "hi", "di", "topk", "eps", "select"))
def _attention(x, w, cos, sin, cos_i, sin_i, *, n, n_kv, d, hi, di, topk,
               eps, select=True):
    """x [T, h] -> (x + Attn(norm_a(x)), selection margin [T]); `w` this
    layer's tensors. With `select` False every visible key is attended:
    the control that leaves the selection out."""
    t = x.shape[0]
    u = _rms(x, w["input_layernorm.weight"], eps)
    q = _rope(_rms(_mm(u, w["self_attn.q_proj.weight"]).reshape(t, n, d),
                   w["self_attn.q_norm.weight"], eps), cos, sin)
    k = _rope(_rms(_mm(u, w["self_attn.k_proj.weight"]).reshape(t, n_kv, d),
                   w["self_attn.k_norm.weight"], eps), cos, sin)
    v = _mm(u, w["self_attn.v_proj.weight"]).reshape(t, n_kv, d)
    q_i = _rope(_mm(u, w["self_attn.index_q_proj.weight"])
                .reshape(t, hi, di), cos_i, sin_i)
    k_i = _rope(_layer_norm(_mm(u, w["self_attn.index_k_proj.weight"]),
                            w["self_attn.index_k_norm_weight"],
                            w["self_attn.index_k_norm_bias"], eps)
                .reshape(t, 1, di), cos_i, sin_i)[:, 0]
    w_i = _mm(u, w["self_attn.index_w_proj.weight"])
    key_pos = jnp.arange(t)[None, :]
    q5 = q.reshape(t, n_kv, n // n_kv, d)

    def block(start):
        sl = functools.partial(jax.lax.dynamic_slice_in_dim,
                               start_index=start, slice_size=QUERY_BLOCK,
                               axis=0)
        seen = key_pos <= (start + jnp.arange(QUERY_BLOCK))[:, None]
        score = jnp.einsum("qjd,kd->qjk", sl(q_i), k_i, precision=_HI)
        score = (sl(w_i)[:, :, None] * jnp.maximum(score, 0.0)).sum(1)
        at, chosen, margin = selection(score, seen, topk)
        if not select:
            s = jnp.einsum("qgrd,kgd->qgrk", sl(q5), k, precision=_HI) \
                / math.sqrt(d)
            p = jax.nn.softmax(jnp.where(seen[:, None, None, :], s,
                                         -jnp.inf), axis=-1)
            return jnp.einsum("qgrk,kgd->qgrd", p, v, precision=_HI), margin
        # the selected keys and values of each query, gathered
        s = jnp.einsum("qgrd,qkgd->qgrk", sl(q5), k[at], precision=_HI) \
            / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(chosen[:, None, None, :], s, -jnp.inf),
                           axis=-1)
        return jnp.einsum("qgrk,qkgd->qgrd", p, v[at], precision=_HI), margin

    # T is padded to a multiple of QUERY_BLOCK by the caller
    o, margin = jax.lax.map(block, jnp.arange(0, t, QUERY_BLOCK))
    return (x + _mm(o.reshape(t, n * d), w["self_attn.o_proj.weight"]),
            margin.reshape(t))


def choose_experts(g, top_k):
    """g [T, E] router scores -> (the token's `top_k` experts
    [T, top_k], their scores, margin [T] = (c_k - c_k+1) / c_k)."""
    top, idx = jax.lax.top_k(g, top_k + 1)
    margin = (top[:, top_k - 1] - top[:, top_k]) / top[:, top_k - 1]
    return idx[:, :top_k], top[:, :top_k], margin


@functools.partial(jax.jit, static_argnames=("top_k", "norm", "eps"))
def _route(x, w, *, top_k, norm, eps):
    """-> (normed input, weight of every expert for every token [T, E],
    0 outside the token's set; the token's router margin [T])."""
    a = _rms(x, w["post_attention_layernorm.weight"], eps)
    g = jax.nn.softmax(jnp.matmul(
        a, w["mlp.router.weight"].astype(jnp.float32), precision=_HI), -1)
    idx, picked, margin = choose_experts(g, top_k)
    if norm:
        picked = picked / picked.sum(-1, keepdims=True)
    weight = jnp.zeros_like(g).at[jnp.arange(g.shape[0])[:, None], idx] \
        .set(picked)
    return a, weight, margin


@jax.jit
def _expert_block(a, weight, wg, wu, wd):
    """sum over this block's experts of weight[:, e] * E_e(a): every
    expert over every token, the unrouted ones weighted 0."""
    g = jnp.einsum("th,ehf->etf", a, _f32(wg), precision=_HI)
    u = jnp.einsum("th,ehf->etf", a, _f32(wu), precision=_HI)
    y = jnp.einsum("etf,efh->eth", jax.nn.silu(g) * u, _f32(wd),
                   precision=_HI)
    return jnp.einsum("eth,te->th", y, weight, precision=_HI)


def sparse_ffn(x, w, cfg, share=None, residual=True):
    """x [T, h] -> (x + FFN(norm_f(x)), router margin [T]). `share` =
    (ep_size, ep_rank): only the experts that rank holds contribute;
    None takes the configuration's. With `residual` False the result
    is the routed part ALONE: what the share test adds up."""
    a, weight, margin = _route(
        x, w, top_k=cfg["num_experts_per_tok"],
        norm=bool(cfg["norm_topk_prob"]), eps=float(cfg["rms_norm_eps"]))
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


def layer_weights(weights, i):
    pre = f"model.layers.{i}."
    return {n[len(pre):]: v for n, v in weights.items()
            if n.startswith(pre)}


def attention(x, w, cfg, tables, select=True):
    """One layer's (x + Attn(norm_a(x)), selection margin)."""
    sa = cfg["sa_config"]
    return _attention(
        x, w, *tables, n=cfg["num_attention_heads"],
        n_kv=cfg["num_key_value_heads"], d=cfg["head_dim"],
        hi=sa["indexer_num_heads"], di=sa["indexer_head_dim"],
        topk=sa["topk"], eps=float(cfg["rms_norm_eps"]), select=select)


def tables_for(cfg, n_pos):
    theta = cfg["rope_theta"]
    return rope_tables(theta, cfg["head_dim"], n_pos) + rope_tables(
        theta, cfg["sa_config"]["indexer_head_dim"], n_pos)


def keye_hidden(weights, cfg, ids, select=True):
    """One sequence: ids [T] -> (hidden states [T, h] before the final
    norm, router margin [T], selection margin [T]: each the least over
    the layers). T is padded on the right to a multiple of QUERY_BLOCK
    (causal, so padding cannot reach back) and cut again."""
    t = len(ids)
    pad = -(-t // QUERY_BLOCK) * QUERY_BLOCK
    row = np.zeros((pad,), np.int32)
    row[:t] = ids
    x = weights["model.embed_tokens.weight"][jnp.asarray(row)] \
        .astype(jnp.float32)
    tables = tables_for(cfg, pad)
    margin = jnp.full((pad,), jnp.inf, jnp.float32)
    sel_margin = margin
    for i in range(cfg["num_hidden_layers"]):
        w = layer_weights(weights, i)
        x, sm = attention(x, w, cfg, tables, select)
        x, m = sparse_ffn(x, w, cfg)
        margin, sel_margin = jnp.minimum(margin, m), \
            jnp.minimum(sel_margin, sm)
    return x[:t], margin[:t], sel_margin[:t]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm_w, head_w, *, eps):
    return _mm(_rms(x, norm_w, eps), head_w)


def keye_logits(weights, cfg, ids, positions=None, select=True):
    """Float32 logits [len(positions), V] of one sequence (all its
    positions by default), and the positions' router and selection
    margins."""
    x, margin, sel_margin = keye_hidden(weights, cfg, ids, select)
    if positions is not None:
        at = jnp.asarray(positions)
        x, margin, sel_margin = x[at], margin[at], sel_margin[at]
    return (_head(x, weights["model.norm.weight"], weights["lm_head.weight"],
                  eps=float(cfg["rms_norm_eps"])), margin, sel_margin)


def check_width(n_tokens):
    """The padded width a sampled sequence of n_tokens is checked at:
    rounded up to 4096 (to QUERY_BLOCK under 4096), so that a run's
    samples share a few compiled programs (seven widths to 28,672 + 256
    tokens; a program a width compiles in seconds) and none pays for
    the mix's longest."""
    step = 4096 if n_tokens > 4096 else QUERY_BLOCK
    return -(-n_tokens // step) * step


def keye_teacher_forced(weights, cfg, prompts, outputs, select=True):
    """One forward pass over each prompt + emitted tokens, right-padded
    to `check_width` -> a list of (logits [emitted, V], router margin
    [emitted], selection margin [emitted]), numpy, at the positions
    that predict each emitted token."""
    out = []
    for p, o in zip(prompts, outputs):
        seq = list(p) + list(o)
        ids = np.zeros((check_width(len(seq)),), np.int32)
        ids[:len(seq)] = seq
        pos = len(p) - 1 + np.arange(len(o))
        out.append(tuple(np.asarray(a) for a in keye_logits(
            weights, cfg, ids, pos, select)))
    return out


def judge_choices(reference, chosen, tie_margin):
    """`reference` as `keye_teacher_forced` gives it, `chosen` the token
    picked at each of its positions (the engine's emitted tokens; or,
    for a control, the argmax of a variant's logits over the same
    contexts). For each, gap = best reference logit - reference logit
    of the chosen token: logits and not tokens are compared, because
    with random weights the largest logit changes on rounding. Returns
    `gap` (the largest over the tokens whose router margin is at least
    `tie_margin`), `tie_gap` (over the others; 0.0 if none),
    `tie_share` (their share), `mean_gap` (the mean of the gap over
    ALL tokens), `match` (share of ALL tokens that are the reference's
    argmax), `tokens`, `min_margin`, `min_sel_margin`, and `each`:
    every token's gap and both margins, for whoever sets the limits."""
    gaps, margins, sels, hits = [], [], [], 0
    for (lg, margin, sel), o in zip(reference, chosen):
        o = np.asarray(o)
        gaps.extend((lg.max(-1) - lg[np.arange(len(o)), o]).tolist())
        margins.extend(margin.tolist())
        sels.extend(sel.tolist())
        hits += int((lg.argmax(-1) == o).sum())
    gaps, margins, sels = (np.asarray(a) for a in (gaps, margins, sels))
    tie = margins < tie_margin
    return {"gap": float(gaps[~tie].max(initial=0.0)),
            "tie_gap": float(gaps[tie].max(initial=0.0)),
            "tie_share": float(tie.mean()), "match": hits / len(gaps),
            "mean_gap": float(gaps.mean()),
            "tokens": len(gaps), "min_margin": float(margins.min()),
            "min_sel_margin": float(sels.min()),
            "each": {"gap": gaps, "margin": margins, "sel_margin": sels}}


def passes(got, check):
    """The comparison that decides `correct`: `judge_choices`' numbers
    against the configuration's `check`."""
    return bool(got["mean_gap"] <= check["mean_gap"]
                and got["match"] >= check["min_match"])


# -- what a step's indexer and attention must do at least ---------------
# (the `where.z.sparse_index.roofline_share.long` and
# `where.z.sparse_walk.roofline_share.long` metrics' operations and
# bytes: they count the work by what ANY form must do, not by what the
# implementation does, so that neither share can pass 100% whichever
# form a later PR takes.) The indexer must score every VISIBLE (query,
# key) pair and read every key of a slot's context once; the attention
# must weigh every SELECTED pair, and read at least the distinct keys a
# slot's live queries can select between them: min(context, queries x
# topk). A walk that reads and multiplies every visible key and masks
# (the form this PR built) therefore reads low, honestly.

def sparse_step_flops(cfg, selected=0, scored=0):
    """A selected pair: every head's score over head_dim values and its
    weighted sum over as many. A scored pair: every indexer head's dot
    over indexer_head_dim values."""
    sa = cfg["sa_config"]
    return (selected * cfg["num_attention_heads"] * 2 * cfg["head_dim"] * 2
            + scored * sa["indexer_num_heads"] * sa["indexer_head_dim"] * 2)


def sparse_step_bytes(cfg, floor_keys=0, context_keys=0, itemsize=2):
    """A key the attention must read: its K and V rows of every kv
    head. A key the indexer must read: its indexer row (the padding not
    counted)."""
    return (floor_keys * 2 * cfg["num_key_value_heads"] * cfg["head_dim"]
            + context_keys * cfg["sa_config"]["indexer_head_dim"]) * itemsize
