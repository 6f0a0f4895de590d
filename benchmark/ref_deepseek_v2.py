"""The plain reference for DeepSeek-V2 and the comparison that decides
`correct` in its cells.

The layer equations of ISSUE 34 (arXiv:2405.04434; the source's
`modeling_deepseek.py`), in straightforward jax.numpy and float32 with
every product at "highest" precision; no kernels, no cache, no batching,
and no code shared with `paddle_tpu`. It reads the program's weights by
name and upcasts them a layer, and the routed experts a block of
`EXPERT_BLOCK` experts, at a time, and computes attention a block of
`QUERY_BLOCK` queries and `HEAD_BLOCK` heads at a time, so that a
sequence of 16384 tokens fits on the chip beside the bf16 weights
themselves. One sequence at a time.

T tokens, h hidden, n heads, eps from the configuration, no biases;
softmax and norms in float32; every norm an RMSNorm:

    x1 = x + Attn(norm_a(x));  y = x1 + FFN_l(norm_f(x1))
    final norm, untied head

Attn, u = norm_a(x). EXPANDED: every head's keys and values are built.
    c_q = norm(u W_qa);  [q_nope | q_pe] = c_q W_qb   (a head)
    [c_kv | k_r] = u W_kva;  c = norm(c_kv);  k_pe = rope(k_r), one for
    all heads;  [k_nope_j | v_j] = c W_kvb
    score(t, j, s) = (q_nope_tj . k_nope_sj + rope(q_pe_tj) . k_pe_s)
                     * (nope + rope)^-0.5 * m^2,  s <= t,
    m = 0.1 * mscale_all_dim * ln(factor) + 1 (YaRN, factor > 1)
    softmax over s;  a_tj = sum_s p_tjs v_sj;  Attn = concat_j(a_j) W_o
(The program carries q_nope through W_UK = W_kvb's k_nope columns and
the weighted sum of c through W_UV = its v columns, caching [c | k_pe]
only: the same mathematics, `score = (q_nope W_UK^T) . c + ...`, in
another order.)
Rope: dimension 2i paired with 2i + 1 (the configuration's `assumed`);
YaRN frequencies (per frequency inv_freq / factor blended with inv_freq
over the linear ramp between the correction dimensions of beta_fast and
beta_slow turns in the original context); cos and sin times mscale's
factor over mscale_all_dim's (1 for the source).
FFN of the first `first_k_dense_replace` layers: (silu(x Wg) * (x Wu)) Wd.
Of the others: g = softmax(x W_r) over all `n_routed_experts`; a group
(a run of E / n_group neighbouring experts) scores as its best expert;
the `topk_group` best groups keep their g, the others count as 0;
S = top-k of what is left; w_e = scaling * g_e, NOT renormalised
(norm_topk_prob false; normalised over S where true);
FFN(x) = sum_{e in S, e held here} w_e E_e(x) + S(x), every expert a
SwiGLU, S one SwiGLU of n_shared_experts x the experts' width. "Held
here": experts ep_rank * E_local onward.

NEAR TIES. The program's activations are bf16, so where a token's k-th
and (k+1)-th router choices, or its last kept and first dropped group,
nearly tie, its expert set can differ from the float32 reference's by
one expert (or one group's), and its hidden state then differs by more
than rounding. The reference reports each position's ROUTER margin, the
least over the expert layers of (c_k - c_k+1) / c_k over the chosen
scores and of (G_3 - G_4) / G_3 over the group scores; `judge_choices`
tells the tokens under `tie_margin` apart, as ref_laguna.py does, and
reports the largest gap on either side of it. NO LARGEST GAP IS LIMITED
(the configuration's `check.why` has the readings): with seeded random
weights 81-85% of the tokens lie under a margin of 0.05, on the chip
the engine swapped an expert up to a margin between 0.05 and 0.1, and
one swapped token reads a gap of up to 1.95 where the reference in
fp8 reads 1.31-3.54 as its largest: a maximum is set by the one token
in some hundreds that swapped, and no limit on it has room on both
sides at any margin that leaves tokens to judge. What is limited holds
EVERY emitted token, near-tied or not: `mean_gap`, the mean of the gap
(a swapped expert moves one token in some hundreds, rounding every
matrix moves half of them), and `match`.
"""
import functools
import math

import numpy as np
import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
EXPERT_BLOCK = 2        # experts upcast to float32 at a time
QUERY_BLOCK = 64        # queries whose score rows are alive at a time
HEAD_BLOCK = 32         # heads whose keys and values are alive at a time


def dsv2_weights(model):
    """{name: jax array} of the program's DeepseekV2ForCausalLM, as
    stored."""
    return {n: p._value for n, p in model.named_parameters()}


def _f32(w):
    return w.astype(jnp.float32)


def _mm(a, b):
    return jnp.matmul(a, _f32(b), precision=_HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _mscale(rope, key):
    if not rope or rope["factor"] <= 1:
        return 1.0
    return 0.1 * rope.get(key, 0) * math.log(rope["factor"]) + 1.0


def softmax_scale(cfg):
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 \
        * _mscale(cfg.get("rope_scaling"), "mscale_all_dim") ** 2


def rope_tables(cfg, n_pos):
    """(cos, sin) float32 [n_pos, rot / 2] over the rope part."""
    rot, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    rope = cfg.get("rope_scaling")
    inv = theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    factor = 1.0
    if rope:
        scale = float(rope["factor"])
        orig = float(rope["original_max_position_embeddings"])

        def dim_of(turns):      # the dimension that makes `turns` turns
            return rot * math.log(orig / (turns * 2 * math.pi)) \
                / (2 * math.log(theta))
        lo = max(math.floor(dim_of(float(rope["beta_fast"]))), 0)
        hi = min(math.ceil(dim_of(float(rope["beta_slow"]))), rot - 1)
        hi = hi + 0.001 if hi == lo else hi
        ramp = np.clip((np.arange(rot // 2) - lo) / (hi - lo), 0.0, 1.0)
        inv = (inv / scale) * ramp + inv * (1.0 - ramp)
        factor = _mscale(rope, "mscale") / _mscale(rope, "mscale_all_dim")
    ang = np.arange(n_pos, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(ang) * factor, jnp.float32),
            jnp.asarray(np.sin(ang) * factor, jnp.float32))


def _rope(x, cos, sin):
    """x [T, ..., d]; rotary over all d = 2 * cos.shape[1] dimensions,
    dimension 2i paired with 2i + 1."""
    pair = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (cos.shape[1],)
    c, s = cos.reshape(shape), sin.reshape(shape)
    a, b = pair[..., 0], pair[..., 1]
    return jnp.stack([a * c - b * s, b * c + a * s], -1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=(
    "n", "nope", "rope", "dv", "scale", "eps"))
def _attention(x, w, cos, sin, *, n, nope, rope, dv, scale, eps):
    """x [T, h] -> x + Attn(norm_a(x)); `w` this layer's tensors."""
    t = x.shape[0]
    u = _rms(x, w["input_layernorm.weight"], eps)
    c_q = _rms(_mm(u, w["self_attn.q_a_proj.weight"]),
               w["self_attn.q_a_layernorm.weight"], eps)
    kv = _mm(u, w["self_attn.kv_a_proj_with_mqa.weight"])
    latent = kv.shape[1] - rope
    c = _rms(kv[:, :latent], w["self_attn.kv_a_layernorm.weight"], eps)
    k_pe = _rope(kv[:, latent:], cos, sin)
    w_qb = w["self_attn.q_b_proj.weight"].reshape(-1, n, nope + rope)
    w_kvb = w["self_attn.kv_b_proj.weight"].reshape(latent, n, nope + dv)
    key_pos = jnp.arange(t)[None, :]
    out = []
    # a block of heads' keys and values for the whole sequence, and a
    # block of queries' score rows, at a time
    for h0 in range(0, n, HEAD_BLOCK):
        hs = slice(h0, min(h0 + HEAD_BLOCK, n))
        wk = _f32(w_kvb[:, hs])
        k_nope = jnp.einsum("tc,cnd->tnd", c, wk[..., :nope], precision=_HI)
        v = jnp.einsum("tc,cnd->tnd", c, wk[..., nope:], precision=_HI)
        wq = _f32(w_qb[:, hs])

        def block(start, k_nope=k_nope, v=v, wq=wq):
            sl = functools.partial(jax.lax.dynamic_slice_in_dim,
                                   start_index=start,
                                   slice_size=QUERY_BLOCK, axis=0)
            q = jnp.einsum("tc,cnd->tnd", sl(c_q), wq, precision=_HI)
            q_nope = q[..., :nope]
            q_pe = _rope(q[..., nope:], sl(cos), sl(sin))
            seen = key_pos <= (start + jnp.arange(QUERY_BLOCK))[:, None]
            s = (jnp.einsum("qnd,knd->nqk", q_nope, k_nope, precision=_HI)
                 + jnp.einsum("qnd,kd->nqk", q_pe, k_pe,
                              precision=_HI)) * scale
            s = jnp.where(seen[None], s, -jnp.inf)
            return jnp.einsum("nqk,knd->qnd", jax.nn.softmax(s, axis=-1), v,
                              precision=_HI)
        # T is padded to a multiple of QUERY_BLOCK by the caller
        o = jax.lax.map(block, jnp.arange(0, t, QUERY_BLOCK))
        out.append(o.reshape(t, -1, dv))
    o = jnp.concatenate(out, axis=1).reshape(t, n * dv)
    return x + _mm(o, w["self_attn.o_proj.weight"])


def _swiglu(a, wg, wu, wd):
    return _mm(jax.nn.silu(_mm(a, wg)) * _mm(a, wu), wd)


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_ffn(x, w, *, eps):
    a = _rms(x, w["post_attention_layernorm.weight"], eps)
    return x + _swiglu(a, w["mlp.gate_proj.weight"],
                       w["mlp.up_proj.weight"], w["mlp.down_proj.weight"])


def choose_experts(g, top_k, n_group, topk_group):
    """g [T, E] router scores -> (the token's `top_k` experts [T, top_k]
    under the group limit, their scores, margin [T]: the lesser of
    (c_k - c_k+1) / c_k over the scores left after the limit and
    (G_last kept - G_first dropped) / G_last kept over the groups)."""
    t, e = g.shape
    groups = g.reshape(t, n_group, e // n_group)
    g_top, g_idx = jax.lax.top_k(groups.max(-1),
                                 min(topk_group + 1, n_group))
    kept = jnp.zeros((t, n_group), bool).at[
        jnp.arange(t)[:, None], g_idx[:, :topk_group]].set(True)
    left = jnp.where(kept[:, :, None], groups, 0.0).reshape(t, e)
    top, idx = jax.lax.top_k(left, top_k + 1)
    margin = (top[:, top_k - 1] - top[:, top_k]) / top[:, top_k - 1]
    if topk_group < n_group:
        margin = jnp.minimum(margin, (
            g_top[:, topk_group - 1] - g_top[:, topk_group])
            / g_top[:, topk_group - 1])
    return idx[:, :top_k], top[:, :top_k], margin


@functools.partial(jax.jit, static_argnames=(
    "top_k", "n_group", "topk_group", "scaling", "norm", "eps"))
def _route(x, w, *, top_k, n_group, topk_group, scaling, norm, eps):
    """-> (normed input, weight of every expert for every token [T, E],
    0 outside the token's set; the token's router margin [T])."""
    a = _rms(x, w["post_attention_layernorm.weight"], eps)
    g = jax.nn.softmax(jnp.matmul(
        a, w["mlp.router.weight"].astype(jnp.float32), precision=_HI), -1)
    idx, picked, margin = choose_experts(g, top_k, n_group, topk_group)
    if norm:
        picked = picked / picked.sum(-1, keepdims=True)
    weight = jnp.zeros_like(g).at[jnp.arange(g.shape[0])[:, None], idx] \
        .set(picked * scaling)
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


@jax.jit
def _shared(x, a, routed, w):
    return x + routed + _swiglu(
        a, w["mlp.shared_experts.gate_proj.weight"],
        w["mlp.shared_experts.up_proj.weight"],
        w["mlp.shared_experts.down_proj.weight"])


def sparse_ffn(x, w, cfg, share=None, shared_experts=True):
    """x [T, h] -> (x + FFN(norm_f(x)), router margin [T]). `share` =
    (ep_size, ep_rank): only the experts that rank holds contribute;
    None takes the configuration's. With `shared_experts` False the
    result is the routed part ALONE (no residual, no shared experts):
    what the share test adds up."""
    a, weight, margin = _route(
        x, w, top_k=cfg["num_experts_per_tok"], n_group=cfg["n_group"],
        topk_group=cfg["topk_group"],
        scaling=float(cfg["routed_scaling_factor"]),
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
    if not shared_experts:
        return routed, margin
    return _shared(x, a, routed, w), margin


def layer_weights(weights, i):
    pre = f"model.layers.{i}."
    return {n[len(pre):]: v for n, v in weights.items()
            if n.startswith(pre)}


def attention(x, w, cfg, cos, sin):
    """One layer's x + Attn(norm_a(x))."""
    return _attention(
        x, w, cos, sin, n=cfg["num_attention_heads"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
        dv=cfg["v_head_dim"], scale=softmax_scale(cfg),
        eps=float(cfg["rms_norm_eps"]))


def dsv2_hidden(weights, cfg, ids):
    """One sequence: ids [T] -> (hidden states [T, h] before the final
    norm, router margin [T]: the least over the expert layers). T is
    padded on the right to a multiple of QUERY_BLOCK (causal, so padding
    cannot reach back) and cut again."""
    t = len(ids)
    pad = -(-t // QUERY_BLOCK) * QUERY_BLOCK
    row = np.zeros((pad,), np.int32)
    row[:t] = ids
    x = weights["model.embed_tokens.weight"][jnp.asarray(row)] \
        .astype(jnp.float32)
    eps = float(cfg["rms_norm_eps"])
    cos, sin = rope_tables(cfg, pad)
    margin = jnp.full((pad,), jnp.inf, jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        w = layer_weights(weights, i)
        x = attention(x, w, cfg, cos, sin)
        if i < cfg.get("first_k_dense_replace", 1):
            x = _dense_ffn(x, w, eps=eps)
        else:
            x, m = sparse_ffn(x, w, cfg)
            margin = jnp.minimum(margin, m)
    return x[:t], margin[:t]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm_w, head_w, *, eps):
    return _mm(_rms(x, norm_w, eps), head_w)


def dsv2_logits(weights, cfg, ids, positions=None):
    """Float32 logits [len(positions), V] of one sequence (all its
    positions by default), and the positions' router margins."""
    x, margin = dsv2_hidden(weights, cfg, ids)
    if positions is not None:
        at = jnp.asarray(positions)
        x, margin = x[at], margin[at]
    return _head(x, weights["model.norm.weight"], weights["lm_head.weight"],
                 eps=float(cfg["rms_norm_eps"])), margin


def check_width(n_tokens):
    """The padded width a sampled sequence of n_tokens is checked at:
    rounded up to 2048 (to QUERY_BLOCK under 2048), so that a run's
    samples share a few compiled programs and none pays for the mix's
    longest."""
    step = 2048 if n_tokens > 2048 else QUERY_BLOCK
    return -(-n_tokens // step) * step


def dsv2_teacher_forced(weights, cfg, prompts, outputs):
    """One forward pass over each prompt + emitted tokens, right-padded
    to `check_width` -> a list of (logits [emitted, V], router margin
    [emitted]), numpy, at the positions that predict each emitted
    token."""
    out = []
    for p, o in zip(prompts, outputs):
        seq = list(p) + list(o)
        ids = np.zeros((check_width(len(seq)),), np.int32)
        ids[:len(seq)] = seq
        pos = len(p) - 1 + np.arange(len(o))
        out.append(tuple(np.asarray(a) for a in dsv2_logits(
            weights, cfg, ids, pos)))
    return out


def judge_choices(reference, chosen, tie_margin):
    """`reference` as `dsv2_teacher_forced` gives it, `chosen` the token
    picked at each of its positions (the engine's emitted tokens; or,
    for a control, the argmax of a variant's logits over the same
    contexts). For each, gap = best reference logit - reference logit
    of the chosen token: logits and not tokens are compared, because
    with random weights the largest logit changes on rounding. Returns
    `gap` (the largest over the tokens whose router margin is at least
    `tie_margin`), `tie_gap` (over the others; 0.0 if none),
    `tie_share` (their share), `mean_gap` (the mean of the gap over
    ALL tokens), `match` (share of ALL tokens that are the reference's
    argmax), `tokens`, `min_margin`, and `each`: every token's gap and
    margin, for whoever sets the limits."""
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
            "mean_gap": float(gaps.mean()),
            "tokens": len(gaps), "min_margin": float(margins.min()),
            "each": {"gap": gaps, "margin": margins}}


def passes(got, check):
    """The comparison that decides `correct`: `judge_choices`' numbers
    against the configuration's `check`."""
    return bool(got["mean_gap"] <= check["mean_gap"]
                and got["match"] >= check["min_match"])


# -- what the walk of one step must do at least -------------------------
# (the `where.z.mla_walk.roofline_share.docs` metric's operations and
# bytes: they count the work by what ANY form of the attention must do,
# not by what the implementation does, so that the share cannot pass
# 100% whichever form a later PR takes). Operations go by (query, key)
# PAIRS at the EXPANDED form's price, the cheapest a pair: every head's
# score over nope + rope values and its weighted sum over v values (the
# absorbed form pays 2 x (576 + 512) a head and pair for the same
# result, 3.4 x as much, so an absorbed, compute-bound chunk tops out
# near 29% of this roofline). Bytes go by DISTINCT keys: the queries of
# one chunk see the same keys, and an implementation may read a key
# once for all of them, so a byte count a pair would be no lower bound.

def mla_step_bytes(keys_distinct, cfg, itemsize=2):
    """Each key a slot's queries see has its cached row (latent + rope
    values, the padding not counted) read once a step."""
    return keys_distinct * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) \
        * itemsize


def mla_step_flops(pairs, cfg):
    """A (query, key) pair: every head's score over nope + rope values
    and its weighted sum over v values."""
    return pairs * cfg["num_attention_heads"] * 2 * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])
