"""Laguna family (poolside Laguna-S-2.1): a decoder whose layers differ
in kind, served by `ServingEngine` like GPT and Llama. Inference only:
the ops below register no backward pass.

What the family has that `llama.py` does not:

- layers of two kinds (`layer_types`): full attention and a sliding
  window of `sliding_window` keys (the query's own position included),
  with a different number of QUERY heads a kind
  (`num_attention_heads_per_layer`) over the same KV heads;
- two rotary schemes in one model (`rope_parameters`, one block a
  kind): YaRN-scaled frequencies on the first `partial_rotary_factor`
  of each head in full layers, plain rope on the whole head in window
  layers, both pairing dimension i with i + rot/2 (`rotate_half`);
- a per-head sigmoid gate on the attention output, a linear map of the
  layer's normed input, applied before the output projection;
- a leading dense SwiGLU MLP (`mlp_layer_types`), then routed blocks:
  softmax router over `num_experts`, top `num_experts_per_tok`
  renormalised and scaled by `moe_routed_scaling_factor`, plus one
  shared expert added ungated.

EXPERT PARALLELISM, one chip's share. `ep_size` chips share each layer;
this one is `ep_rank` and holds experts `ep_rank * E / ep_size` onward.
The router keeps its `num_experts` outputs and its top-k; the block
computes `sum_{e in top-k, e held here} w_e E_e(x) + E_shared(x)` with
w_e normalised over all k, and that partial result goes on to the next
layer. Nothing stands in for the absent chips or their exchange. The
vocabulary may be a slice too: `vocab_size` is what is held here.

The engine learns the layer kinds from `_decode_cache_spec()`, whose
fourth entry lists each layer's window (None: full attention).
"""
from __future__ import annotations

import math

import numpy as np
import jax
import jax.numpy as jnp

from .. import nn
from ..nn import functional as F
from ..core.tensor import Tensor
from ..core.dispatch import register_op
from ..ops._helpers import apply_op
from ..nn.initializer import Normal
from .generation import head_columns
from .moe_common import (MOE_STEP_STAT_COUNTERS, NormalByExpert, SwiGLU,
                         cast, linear, moe_stats, valid_columns)

__all__ = ["LagunaConfig", "LagunaModel", "LagunaForCausalLM"]

FULL, SLIDING = "full_attention", "sliding_attention"


class LagunaConfig:
    """The source's `config.json` keys (defaults: Laguna-S-2.1's), plus
    `ep_size` and `ep_rank`, and `dtype`: the parameters' dtype, given
    here because every sublayer is cast as it is built (5.6 B
    parameters in float32 first would not fit the chip they serve
    from in bfloat16; None leaves the framework's float32). Keys of
    the source that say nothing this code reads (`model_type`,
    `gating_types`, ...) are accepted and kept; those that would
    change the mathematics are checked."""

    def __init__(self, vocab_size=100352, hidden_size=3072,
                 intermediate_size=12288, num_hidden_layers=48,
                 num_attention_heads=48, num_key_value_heads=8,
                 head_dim=128, max_position_embeddings=1048576,
                 rms_norm_eps=1e-6, num_experts=256,
                 num_experts_per_tok=10, moe_intermediate_size=1024,
                 shared_expert_intermediate_size=1024,
                 norm_topk_prob=True, mlp_only_layers=(0,),
                 mlp_layer_types=None, gating="per-head",
                 sliding_window=512, rope_parameters=None,
                 layer_types=None, num_attention_heads_per_layer=None,
                 moe_routed_scaling_factor=2.5,
                 moe_router_logit_softcapping=0,
                 moe_apply_router_weight_on_input=False,
                 attention_bias=False, tie_word_embeddings=False,
                 ep_size=1, ep_rank=0, initializer_range=0.02,
                 dtype=None, **source_keys):
        n = int(num_hidden_layers)
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.intermediate_size = int(intermediate_size)
        self.num_hidden_layers = n
        self.num_attention_heads = int(num_attention_heads)
        self.num_key_value_heads = int(num_key_value_heads)
        self.head_dim = int(head_dim)
        self.max_position_embeddings = int(max_position_embeddings)
        self.rms_norm_eps = float(rms_norm_eps)
        self.num_experts = int(num_experts)
        self.num_experts_per_tok = int(num_experts_per_tok)
        self.moe_intermediate_size = int(moe_intermediate_size)
        self.shared_expert_intermediate_size = \
            int(shared_expert_intermediate_size)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.sliding_window = int(sliding_window)
        self.moe_routed_scaling_factor = float(moe_routed_scaling_factor)
        self.layer_types = list(layer_types) if layer_types else [
            FULL if i % 4 == 0 else SLIDING for i in range(n)]
        self.mlp_layer_types = list(mlp_layer_types) if mlp_layer_types \
            else ["dense" if i in tuple(mlp_only_layers) else "sparse"
                  for i in range(n)]
        self.num_attention_heads_per_layer = \
            [int(h) for h in num_attention_heads_per_layer] \
            if num_attention_heads_per_layer \
            else [self.num_attention_heads] * n
        self.rope_parameters = rope_parameters or {
            FULL: {"rope_type": "default", "rope_theta": 10000.0,
                   "partial_rotary_factor": 1.0},
            SLIDING: {"rope_type": "default", "rope_theta": 10000.0,
                      "partial_rotary_factor": 1.0}}
        self.gating = gating
        self.ep_size, self.ep_rank = int(ep_size), int(ep_rank)
        self.initializer_range = float(initializer_range)
        self.dtype = dtype
        self.source_keys = source_keys
        for name, per_layer in (
                ("layer_types", self.layer_types),
                ("mlp_layer_types", self.mlp_layer_types),
                ("num_attention_heads_per_layer",
                 self.num_attention_heads_per_layer)):
            if len(per_layer) != n:
                raise ValueError(f"{name} has {len(per_layer)} entries "
                                 f"for {n} layers")
        if set(self.layer_types) - {FULL, SLIDING}:
            raise ValueError(f"layer_types {set(self.layer_types)}")
        if any(h % self.num_key_value_heads
               for h in self.num_attention_heads_per_layer):
            raise ValueError("every layer's query heads must be a "
                             "multiple of num_key_value_heads")
        if self.num_experts % self.ep_size or \
                not 0 <= self.ep_rank < self.ep_size:
            raise ValueError(
                f"ep_size {ep_size} must divide num_experts "
                f"{num_experts}, and 0 <= ep_rank {ep_rank} < ep_size")
        if gating not in ("per-head", "per_head"):
            raise ValueError(f"gating {gating!r}: only the per-head "
                             f"gate is built")
        unbuilt = {"moe_router_logit_softcapping":
                   moe_router_logit_softcapping,
                   "moe_apply_router_weight_on_input":
                   moe_apply_router_weight_on_input,
                   "attention_bias": attention_bias,
                   "tie_word_embeddings": tie_word_embeddings}
        if any(unbuilt.values()):
            raise ValueError(f"not built: {unbuilt}")

    @property
    def num_local_experts(self):
        return self.num_experts // self.ep_size

    def window_of(self, layer):
        """The layer's sliding window, or None for full attention."""
        return self.sliding_window \
            if self.layer_types[layer] == SLIDING else None


def rotary_frequencies(rope, rot):
    """(inv_freq float64 [rot / 2], factor on cos and sin) of one
    `rope_parameters` block over `rot` rotary dimensions. `default`:
    1 / theta^(2i/rot). `yarn` (Peng et al. 2023, as the source's
    library computes it): per frequency a blend of that and the same
    divided by `factor`, over the linear ramp between the correction
    dimensions of `beta_fast` and `beta_slow` turns in the original
    context; cos and sin are multiplied by `attention_factor`."""
    theta = float(rope["rope_theta"])
    inv = 1.0 / theta ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    if rope.get("rope_type", "default") == "default":
        return inv, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}")
    factor = float(rope["factor"])
    orig = float(rope["original_max_position_embeddings"])

    def correction_dim(turns):
        return rot * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(correction_dim(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rope["beta_slow"]))),
               rot - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rot // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    att = rope.get("attention_factor")
    if att is None:
        att = 0.1 * math.log(factor) + 1.0
    return inv / factor * ramp + inv * (1.0 - ramp), float(att)


def _rope_half_fwd(x, pos, inv_freq, rot, factor):
    """x [B, L, H, D] at positions pos + 0..L-1 (pos: int scalar, or
    [B], one start a row); rotary over the first `rot` dimensions,
    dimension i paired with i + rot/2; the rest passes through."""
    l = x.shape[1]
    p = pos.astype(jnp.float32)
    steps = jnp.arange(l, dtype=jnp.float32)
    t = (p[:, None] + steps[None]) if p.ndim == 1 else (p + steps)[None]
    ang = t[:, :, None] * inv_freq[None, None, :]      # [B|1, L, rot/2]
    cos = (jnp.cos(ang) * factor)[:, :, None, :]
    sin = (jnp.sin(ang) * factor)[:, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :rot // 2], xf[..., rot // 2:rot]
    out = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
    if rot < x.shape[-1]:
        out.append(xf[..., rot:])
    return jnp.concatenate(out, axis=-1).astype(x.dtype)


register_op("rope_half", _rope_half_fwd, nondiff=True)


def _head_gate_fwd(a, g):
    """a [B, L, H, D] attention output, g [B, L, H] gate logits ->
    sigmoid(g) * a, one scalar a head."""
    return (a.astype(jnp.float32)
            * jax.nn.sigmoid(g.astype(jnp.float32))[..., None]
            ).astype(a.dtype)


register_op("head_gate", _head_gate_fwd, nondiff=True)


def _rms_norm(cfg):
    return cast(nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps), cfg)


class LagunaAttention(nn.Layer):
    def __init__(self, cfg: LagunaConfig, layer: int):
        super().__init__()
        self.n_heads = cfg.num_attention_heads_per_layer[layer]
        self.n_kv = cfg.num_key_value_heads
        self.head_dim = d = cfg.head_dim
        self.window = cfg.window_of(layer)
        rope = cfg.rope_parameters[cfg.layer_types[layer]]
        self.rot = int(round(d * float(rope.get("partial_rotary_factor",
                                                1.0))))
        inv, self.rope_factor = rotary_frequencies(rope, self.rot)
        # a constant of the trace, not a weight
        self._inv_freq = np.asarray(inv, np.float32)
        h = cfg.hidden_size
        self.q_proj = linear(h, self.n_heads * d, cfg)
        self.k_proj = linear(h, self.n_kv * d, cfg)
        self.v_proj = linear(h, self.n_kv * d, cfg)
        self.g_proj = linear(h, self.n_heads, cfg)
        self.o_proj = linear(self.n_heads * d, h, cfg)

    def _rope(self, x, pos):
        return apply_op("rope_half", x, pos,
                        Tensor(jnp.asarray(self._inv_freq)),
                        attrs=dict(rot=self.rot,
                                   factor=float(self.rope_factor)))

    def forward(self, x, cache=None):
        """x is the layer's NORMED input (the gate reads it too)."""
        from ..ops import manipulation
        from .generation import DecodeCache, update_and_attend
        b, l = x.shape[0], x.shape[1]
        q = manipulation.reshape(self.q_proj(x),
                                 [b, l, self.n_heads, self.head_dim])
        k = manipulation.reshape(self.k_proj(x),
                                 [b, l, self.n_kv, self.head_dim])
        v = manipulation.reshape(self.v_proj(x),
                                 [b, l, self.n_kv, self.head_dim])
        new_cache = None
        if isinstance(cache, DecodeCache):
            q, k = self._rope(q, cache.pos), self._rope(k, cache.pos)
            out, new_cache = update_and_attend(
                q, k, v, cache, training=False, window=self.window)
        else:
            zero = Tensor(jnp.zeros((), jnp.int32))
            q, k = self._rope(q, zero), self._rope(k, zero)
            rep = self.n_heads // self.n_kv
            if rep > 1:
                k = manipulation.repeat_interleave(k, rep, axis=2)
                v = manipulation.repeat_interleave(v, rep, axis=2)
            i = np.arange(l)[:, None]
            j = np.arange(l)[None, :]
            live = j <= i
            if self.window is not None:
                live &= j > i - self.window
            out = F.scaled_dot_product_attention(
                q, k, v, attn_mask=Tensor(jnp.asarray(live[None, None])),
                is_causal=False, training=False)
        out = apply_op("head_gate", out, self.g_proj(x))
        out = self.o_proj(manipulation.reshape(
            out, [b, l, self.n_heads * self.head_dim]))
        return out, new_cache


class LagunaSparseMoE(nn.Layer):
    """Router over all `num_experts`, the experts held here, and the
    shared expert (module doc: expert parallelism). `last_stats` holds
    the routed op's counts of the latest call (int32 [3] Tensor)."""

    def __init__(self, cfg: LagunaConfig):
        super().__init__()
        h, f = cfg.hidden_size, cfg.moe_intermediate_size
        n = cfg.num_local_experts
        self.top_k = cfg.num_experts_per_tok
        self.scale = cfg.moe_routed_scaling_factor
        self.norm_topk = cfg.norm_topk_prob
        self.first = cfg.ep_rank * n
        init = NormalByExpert(0.0, cfg.initializer_range)
        self.router = linear(h, cfg.num_experts, cfg)
        self.experts_gate = self.create_parameter(
            [n, h, f], dtype=cfg.dtype, default_initializer=init)
        self.experts_up = self.create_parameter(
            [n, h, f], dtype=cfg.dtype, default_initializer=init)
        self.experts_down = self.create_parameter(
            [n, f, h], dtype=cfg.dtype, default_initializer=init)
        self.shared_expert = SwiGLU(
            cfg, cfg.shared_expert_intermediate_size)
        self.last_stats = None

    def forward(self, x, valid=None):
        if valid is None:
            valid = Tensor(jnp.ones(tuple(x.shape[:2]), bool))
        routed, self.last_stats = apply_op(
            "moe_routed_experts", x, valid, self.router.weight,
            self.experts_gate, self.experts_up, self.experts_down,
            attrs=dict(top_k=self.top_k, scale=self.scale,
                       norm_topk=self.norm_topk, first=self.first))
        return routed + self.shared_expert(x)


class LagunaDecoderLayer(nn.Layer):
    def __init__(self, cfg: LagunaConfig, layer: int):
        super().__init__()
        self.input_layernorm = _rms_norm(cfg)
        self.self_attn = LagunaAttention(cfg, layer)
        self.post_attention_layernorm = _rms_norm(cfg)
        self.mlp = (SwiGLU(cfg, cfg.intermediate_size)
                    if cfg.mlp_layer_types[layer] == "dense"
                    else LagunaSparseMoE(cfg))

    def forward(self, x, cache=None, valid=None):
        h, new_cache = self.self_attn(self.input_layernorm(x),
                                      cache=cache)
        x = x + h
        x = x + self.mlp(self.post_attention_layernorm(x), valid)
        return x, new_cache


class LagunaModel(nn.Layer):
    def __init__(self, cfg: LagunaConfig):
        super().__init__()
        self.config = cfg
        self.embed_tokens = cast(nn.Embedding(
            cfg.vocab_size, cfg.hidden_size, weight_attr=nn.ParamAttr(
                initializer=Normal(0.0, cfg.initializer_range))), cfg)
        self.layers = nn.LayerList([LagunaDecoderLayer(cfg, i)
                                    for i in range(cfg.num_hidden_layers)])
        self.norm = _rms_norm(cfg)

    def forward(self, input_ids, caches=None):
        x = self.embed_tokens(input_ids)
        valid = valid_columns(int(x.shape[1]), caches)
        new_caches = [] if caches is not None else None
        for i, layer in enumerate(self.layers):
            x, c = layer(x, cache=None if caches is None else caches[i],
                         valid=valid)
            if caches is not None:
                new_caches.append(c)
        x = self.norm(x)
        if caches is not None:
            return x, new_caches
        return x

    def moe_stats(self):
        return moe_stats(self.layers)


class LagunaForCausalLM(nn.Layer):
    def __init__(self, cfg: LagunaConfig):
        super().__init__()
        self.laguna = LagunaModel(cfg)
        self.lm_head = linear(cfg.hidden_size, cfg.vocab_size, cfg)
        self.config = cfg

    def forward(self, input_ids, caches=None, columns=None):
        if caches is not None:
            h, new_caches = self.laguna(input_ids, caches=caches)
            return self.lm_head(head_columns(h, columns)), new_caches
        return self.lm_head(head_columns(self.laguna(input_ids), columns))

    def _decode_cache_spec(self):
        """(layers, kv heads, head size, each layer's sliding window or
        None): the four-entry form `ServingEngine` reads layer kinds
        from."""
        cfg = self.config
        return (cfg.num_hidden_layers, cfg.num_key_value_heads,
                cfg.head_dim, tuple(cfg.window_of(i)
                                    for i in range(cfg.num_hidden_layers)))

    def _step_stats(self):
        """Counts the latest forward pass made on the device, for the
        engine to carry out of its step (`STEP_STAT_COUNTERS` names
        them): see `moe_stats`."""
        return self.laguna.moe_stats()

    STEP_STAT_COUNTERS = MOE_STEP_STAT_COUNTERS
