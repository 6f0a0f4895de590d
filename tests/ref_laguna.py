"""The plain reference for Laguna-S-2.1 and the comparison that decides
`correct` in its cells.

The layer equations of ISSUE 29, in straightforward jax.numpy and
float32 with every product at "highest" precision; no kernels, no
cache, no batching, and no code shared with `paddle_tpu`. It reads the
program's weights by name and upcasts them a layer, and the routed
experts a block of `EXPERT_BLOCK` experts, at a time, so that it fits
on the chip beside the bf16 weights themselves. One sequence at a time.

T tokens, h hidden, d head size, eps from the configuration, no biases:

    x1 = x + Attn_l(RMSNorm(x));  y = x1 + FFN_l(RMSNorm(x1))
    final RMSNorm, untied head

Attn_l: n_l query heads (`num_attention_heads_per_layer`), 8 KV heads,
query head j reading KV head j // (n_l / 8); rope on q and k; causal
softmax attention at scale d^-0.5; in a `sliding_attention` layer key i
is visible to query t iff t - window < i <= t; g = sigmoid(x Wg), one
scalar a head, multiplied into the head's output before Wo.
Rope (`rope_parameters[layer type]`): on the first
`partial_rotary_factor` x d dimensions, dimension i paired with
i + rot/2; `default`: inv_freq = theta^(-2i/rot); `yarn`: per frequency
inv_freq / factor blended with inv_freq over the linear ramp between
the correction dimensions of beta_fast and beta_slow, cos and sin
multiplied by attention_factor.
FFN of a `dense` layer: (silu(x Wg) * (x Wu)) Wd. Of a `sparse` layer:
s = softmax(x Wr) over all `router_outputs`; S = top-k of s;
w_e = scaling * s_e / sum_{e' in S} s_e';
FFN(x) = sum_{e in S, e held here} w_e E_e(x) + E_shared(x), every
expert a SwiGLU. "Held here": experts ep_rank * E_local onward (the
configuration's share of a deployment; with ep_size 1 every expert).

Departures from the source, each an `assumed` entry of the
configuration file: softmax router scores, shared expert ungated, the
gate's form and place, the rotate_half pairing, no q/k norm.

NEAR TIES. The program's activations are bf16, so where a token's 10th
and 11th router scores nearly tie its top-k set can differ from the
float32 reference's by that one expert, and the token's hidden state
then differs by more than rounding. The reference reports each
position's margin, (s_(k) - s_(k+1)) / s_(k), the least over the sparse
layers; `laguna_gaps` counts the emitted tokens whose margin is under
`tie_margin`, holds them to `tie_tolerance` and the others to
`tolerance`, and reports their share, which the configuration caps.
"""
import functools
import math

import numpy as np
import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
EXPERT_BLOCK = 16       # experts upcast to float32 at a time
QUERY_BLOCK = 512       # queries whose score rows are alive at a time


def laguna_weights(model):
    """{name: jax array} of the program's LagunaForCausalLM, as stored."""
    return {n: p._value for n, p in model.named_parameters()}


def _rms(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _mm(a, b):
    return jnp.matmul(a, b.astype(jnp.float32), precision=_HI)


def rope_tables(rope, d, n_pos):
    """(cos, sin) float32 [n_pos, rot / 2] of one rope block."""
    rot = int(round(d * float(rope.get("partial_rotary_factor", 1.0))))
    theta = float(rope["rope_theta"])
    inv = theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    factor = 1.0
    if rope.get("rope_type", "default") == "yarn":
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
        factor = float(rope["attention_factor"])
    ang = np.arange(n_pos, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(ang) * factor, jnp.float32),
            jnp.asarray(np.sin(ang) * factor, jnp.float32))


def _rope(x, cos, sin):
    """x [T, n, d]; rotary over the first 2 * cos.shape[1] dimensions."""
    half = cos.shape[1]
    a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * c - b * s, b * c + a * s, rest], -1)


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv", "d",
                                             "window", "eps"))
def _attention(x, w, cos, sin, *, n_heads, n_kv, d, window, eps):
    """x [T, h] -> x + Attn(RMSNorm(x)); `w` this layer's tensors."""
    t = x.shape[0]
    a = _rms(x, w["input_layernorm.weight"], eps)
    q = _rope(_mm(a, w["self_attn.q_proj.weight"]).reshape(t, n_heads, d),
              cos, sin)
    k = _rope(_mm(a, w["self_attn.k_proj.weight"]).reshape(t, n_kv, d),
              cos, sin)
    v = _mm(a, w["self_attn.v_proj.weight"]).reshape(t, n_kv, d)
    gate = jax.nn.sigmoid(_mm(a, w["self_attn.g_proj.weight"]))
    rep = n_heads // n_kv
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    key_pos = jnp.arange(t)[None, :]

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, QUERY_BLOCK, 0)
        q_pos = (start + jnp.arange(QUERY_BLOCK))[:, None]
        s = jnp.einsum("qnd,knd->nqk", qb, k, precision=_HI) / math.sqrt(d)
        live = key_pos <= q_pos
        if window is not None:
            live = live & (key_pos > q_pos - window)
        s = jnp.where(live[None], s, -jnp.inf)
        return jnp.einsum("nqk,knd->qnd", jax.nn.softmax(s, axis=-1), v,
                          precision=_HI)
    # T is padded to a multiple of QUERY_BLOCK by the caller
    o = jax.lax.map(block, jnp.arange(0, t, QUERY_BLOCK))
    o = o.reshape(t, n_heads, d) * gate[:, :, None]
    return x + _mm(o.reshape(t, n_heads * d), w["self_attn.o_proj.weight"])


def _swiglu(a, wg, wu, wd):
    return _mm(jax.nn.silu(_mm(a, wg)) * _mm(a, wu), wd)


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_ffn(x, w, *, eps):
    a = _rms(x, w["post_attention_layernorm.weight"], eps)
    return x + _swiglu(a, w["mlp.gate_proj.weight"],
                       w["mlp.up_proj.weight"], w["mlp.down_proj.weight"])


@functools.partial(jax.jit, static_argnames=("top_k", "scaling", "norm",
                                             "eps"))
def _route(x, w, *, top_k, scaling, norm, eps):
    """-> (normed input, weight of every expert for every token
    [T, E], 0 outside the token's top-k; the token's margin [T])."""
    a = _rms(x, w["post_attention_layernorm.weight"], eps)
    s = jax.nn.softmax(_mm(a, w["mlp.router.weight"]), axis=-1)
    top, idx = jax.lax.top_k(s, top_k + 1)
    margin = (top[:, top_k - 1] - top[:, top_k]) / top[:, top_k - 1]
    top, idx = top[:, :top_k], idx[:, :top_k]
    if norm:
        top = top / top.sum(-1, keepdims=True)
    weight = jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None],
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


@jax.jit
def _shared(x, a, routed, w):
    return x + routed + _swiglu(a, w["mlp.shared_expert.gate_proj.weight"],
                                w["mlp.shared_expert.up_proj.weight"],
                                w["mlp.shared_expert.down_proj.weight"])


def sparse_ffn(x, w, cfg, share=None, shared_expert=True):
    """x [T, h] -> (x + FFN(RMSNorm(x)), margin [T]). `share` =
    (ep_size, ep_rank): only the experts that rank holds contribute;
    None takes the configuration's. With `shared_expert` False the
    result is the routed part ALONE (no residual, no shared expert):
    what the share test adds up."""
    a, weight, margin = _route(
        x, w, top_k=cfg["num_experts_per_tok"],
        scaling=float(cfg["moe_routed_scaling_factor"]),
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
    if not shared_expert:
        return routed, margin
    return _shared(x, a, routed, w), margin


def laguna_hidden(weights, cfg, ids):
    """One sequence: ids [T] -> (hidden states [T, h] before the final
    norm, margin [T]: the least over the sparse layers). T is padded
    on the right to a multiple of QUERY_BLOCK (causal, so padding
    cannot reach back) and cut again."""
    t = len(ids)
    pad = -(-t // QUERY_BLOCK) * QUERY_BLOCK
    row = np.zeros((pad,), np.int32)
    row[:t] = ids
    x = weights["laguna.embed_tokens.weight"][jnp.asarray(row)] \
        .astype(jnp.float32)
    d, eps = cfg["head_dim"], float(cfg["rms_norm_eps"])
    tables = {kind: rope_tables(rope, d, pad)
              for kind, rope in cfg["rope_parameters"].items()}
    margin = jnp.full((pad,), jnp.inf, jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        pre = f"laguna.layers.{i}."
        w = {n[len(pre):]: v for n, v in weights.items()
             if n.startswith(pre)}
        kind = cfg["layer_types"][i]
        cos, sin = tables[kind]
        x = _attention(
            x, w, cos, sin, n_heads=cfg["num_attention_heads_per_layer"][i],
            n_kv=cfg["num_key_value_heads"], d=d, eps=eps,
            window=cfg["sliding_window"] if kind == "sliding_attention"
            else None)
        if cfg["mlp_layer_types"][i] == "dense":
            x = _dense_ffn(x, w, eps=eps)
        else:
            x, m = sparse_ffn(x, w, cfg)
            margin = jnp.minimum(margin, m)
    return x[:t], margin[:t]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm_w, head_w, *, eps):
    return _mm(_rms(x, norm_w, eps), head_w)


def laguna_logits(weights, cfg, ids, positions=None):
    """Float32 logits [len(positions), V] of one sequence (all its
    positions by default), and the positions' margins."""
    x, margin = laguna_hidden(weights, cfg, ids)
    if positions is not None:
        x, margin = x[jnp.asarray(positions)], margin[jnp.asarray(positions)]
    return _head(x, weights["laguna.norm.weight"], weights["lm_head.weight"],
                 eps=float(cfg["rms_norm_eps"])), margin


def check_width(mix):
    """One padded width per traffic mix: its longest prompt plus its
    longest answer, rounded up to QUERY_BLOCK."""
    longest = mix["prompt_len"]["max"] + mix["max_tokens"]["max"]
    return -(-longest // QUERY_BLOCK) * QUERY_BLOCK


def laguna_gaps(weights, cfg, prompts, outputs, width, tie_margin):
    """Teacher-forced comparison with the reference: one forward pass
    over each prompt + emitted tokens, right-padded to `width` (one
    width, so one compiled program a run). For each emitted token,
    gap = best reference logit at its position - reference logit of the
    token the engine chose. Returns a dict: `gap` (max over the tokens
    whose margin is at least `tie_margin`), `tie_gap` (max over the
    others; 0.0 if none), `tie_share` (their share of the emitted
    tokens), `match` (share of ALL emitted tokens that are the
    reference argmax), `tokens`, `min_margin`.

    Logits and not tokens are compared because with random weights the
    largest logit changes on rounding (ref.py `dense_gaps`)."""
    gaps, ties, hits = [], [], 0
    margins = []
    for p, o in zip(prompts, outputs):
        seq = list(p) + list(o)
        if len(seq) > width:
            raise ValueError(f"a sampled sequence is longer than {width}")
        ids = np.zeros((width,), np.int32)
        ids[:len(seq)] = seq
        pos = len(p) - 1 + np.arange(len(o))
        lg, margin = laguna_logits(weights, cfg, ids, pos)
        lg, margin = np.asarray(lg), np.asarray(margin)
        chosen = lg[np.arange(len(o)), np.asarray(o)]
        gap = lg.max(-1) - chosen
        tie = margin < tie_margin
        gaps.extend(gap[~tie].tolist())
        ties.extend(gap[tie].tolist())
        margins.extend(margin.tolist())
        hits += int((lg.argmax(-1) == np.asarray(o)).sum())
    n = len(gaps) + len(ties)
    return {"gap": max(gaps, default=0.0), "tie_gap": max(ties, default=0.0),
            "tie_share": len(ties) / n, "match": hits / n, "tokens": n,
            "min_margin": min(margins)}


# -- what the routed experts of one layer-step must do at least --------
# (the `where.x.moe_experts.roofline_share.code` metric's operations and
# bytes: they count the work, not the implementation)

def expert_step_flops(assignments_here, hidden, width):
    """Three products a (token, expert) assignment: gate, up, down."""
    return assignments_here * 6 * hidden * width


def expert_step_bytes(experts_hit, assignments_here, hidden, width,
                      itemsize=2):
    """Each expert that received a token has its three matrices read
    once; each assignment's row is read and its result written."""
    return (experts_hit * 3 * hidden * width
            + assignments_here * 2 * hidden) * itemsize
