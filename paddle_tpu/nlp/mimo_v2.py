"""MiMo-V2-Flash (`model_type` mimo_v2_flash, XiaomiMiMo/MiMo-V2-Flash):
a decoder served by `ServingEngine` like GPT, Laguna, DeepSeek-V2 and
Keye-VL-2.0. Inference only: the ops below register no backward pass.
The multi-token-prediction layers are not built (no key of the
configuration describes them).

What the family has that `laguna.py` does not:

- each layer KIND has its own KV geometry: window layers
  (`hybrid_layer_pattern` 1) `swa_num_key_value_heads` heads, full
  layers (0) `num_key_value_heads`, and in both keys of `head_dim` (192)
  and values of `v_head_dim` (128) values a head, so a query head's
  scores are 192 wide and its output 128 wide;
- a LEARNED SINK a query head in the window layers' softmax
  (`add_swa_attention_sink_bias`): with s the scaled scores of the
  visible keys and b_h the head's sink logit,

      p_j = exp(s_j - m) / (exp(b_h - m) + sum_i exp(s_i - m)),
      m = max(b_h, max_i s_i)

  the sink takes mass and gives no value;
- the attention output scaled by `attention_value_scale` before the
  output projection;
- rotary on the first int(`partial_rotary_factor` x 192) = 64 dims of
  each q and k head (`rotate_half` pairing), theta `rope_theta` in full
  layers and `swa_rope_theta` in window layers;
- a window of `sliding_window` keys, the query's own position included;
- routing by SIGMOID over `n_routed_experts` (`scoring_func`), the
  top `num_experts_per_tok` chosen by sigmoid + `e_score_correction_bias`
  (`topk_method` noaux_tc: the bias selects and does not weigh), the
  chosen sigmoids renormalised (`norm_topk_prob`), no shared expert, a
  leading dense SwiGLU layer (`moe_layer_freq` 0).

With a cache each layer's keys and values live in pools of SPLIT widths
(the engine's cache-spec contract, fifth entry "split"): the walk of a
window layer is `ptk:sink_walk`, of a full layer `ptk:split_walk`.
Without one (`model(ids)`) the published form: every score, a masked
softmax with the sink. Same mathematics; tests hold the two equal.

EXPERT PARALLELISM, one chip's share: as `laguna.py` (`ep_size`,
`ep_rank`; `vocab_size` is what is held here; `n_routed_experts` is
the router's whole width).
"""
from __future__ import annotations

import math

import numpy as np
import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..core.dispatch import register_op
from ..ops._helpers import apply_op
from ..nn.initializer import Normal
from .laguna import rotary_frequencies
from .generation import head_columns
from .moe_common import (NormalByExpert, SwiGLU, cast, linear, moe_stats,
                         valid_columns)

__all__ = ["MiMoV2Config", "MiMoV2Model", "MiMoV2ForCausalLM"]

FULL, WINDOW = 0, 1


class MiMoV2Config:
    """The source's `config.json` keys (defaults: MiMo-V2-Flash's), plus
    `ep_size`, `ep_rank` and `dtype` as `LagunaConfig` has them, and the
    two seeds' spreads of what a fresh checkpoint does not fix:
    `sink_init` (mean, std) of the sink logits and
    `correction_bias_std` of the selection bias (zero in a fresh
    checkpoint; the configuration file's `assumed` says why not here).
    Keys of the source that say nothing this code reads (`model_type`,
    `attention_chunk_size`: a blocking hint, no part of the result) are
    accepted and kept; those that would change the mathematics are
    checked."""

    def __init__(self, vocab_size=152576, hidden_size=4096,
                 intermediate_size=16384, num_hidden_layers=48,
                 num_attention_heads=64, num_key_value_heads=4,
                 head_dim=192, v_head_dim=128, swa_num_attention_heads=64,
                 swa_num_key_value_heads=8, swa_head_dim=192,
                 swa_v_head_dim=128, layernorm_epsilon=1e-5,
                 rope_theta=5000000.0, swa_rope_theta=10000.0,
                 partial_rotary_factor=0.334, sliding_window=128,
                 sliding_window_size=None, hybrid_layer_pattern=None,
                 moe_layer_freq=None, add_swa_attention_sink_bias=True,
                 add_full_attention_sink_bias=False,
                 attention_value_scale=0.707, moe_intermediate_size=2048,
                 n_routed_experts=256, n_shared_experts=None,
                 num_experts_per_tok=8, norm_topk_prob=True,
                 scoring_func="sigmoid", n_group=1, topk_group=1,
                 topk_method="noaux_tc", routed_scaling_factor=None,
                 hidden_act="silu", attention_bias=False,
                 tie_word_embeddings=False, max_position_embeddings=262144,
                 ep_size=1, ep_rank=0, initializer_range=0.02,
                 sink_init=(0.0, 1.0), correction_bias_std=0.0,
                 dtype=None, **source_keys):
        n = int(num_hidden_layers)
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.intermediate_size = int(intermediate_size)
        self.num_hidden_layers = n
        self.num_attention_heads = int(num_attention_heads)
        self.num_key_value_heads = int(num_key_value_heads)
        self.head_dim, self.v_head_dim = int(head_dim), int(v_head_dim)
        self.swa_num_attention_heads = int(swa_num_attention_heads)
        self.swa_num_key_value_heads = int(swa_num_key_value_heads)
        self.swa_head_dim = int(swa_head_dim)
        self.swa_v_head_dim = int(swa_v_head_dim)
        self.layernorm_epsilon = float(layernorm_epsilon)
        self.rope_theta = float(rope_theta)
        self.swa_rope_theta = float(swa_rope_theta)
        self.partial_rotary_factor = float(partial_rotary_factor)
        self.sliding_window = int(sliding_window)
        self.hybrid_layer_pattern = [int(k) for k in hybrid_layer_pattern] \
            if hybrid_layer_pattern is not None else [
                FULL if i % 6 == 0 or i == n - 1 else WINDOW
                for i in range(n)]
        self.moe_layer_freq = [int(k) for k in moe_layer_freq] \
            if moe_layer_freq is not None else [int(i > 0)
                                                for i in range(n)]
        self.add_swa_attention_sink_bias = bool(add_swa_attention_sink_bias)
        self.add_full_attention_sink_bias = \
            bool(add_full_attention_sink_bias)
        self.attention_value_scale = float(attention_value_scale)
        self.moe_intermediate_size = int(moe_intermediate_size)
        self.n_routed_experts = int(n_routed_experts)
        self.num_experts_per_tok = int(num_experts_per_tok)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.routed_scaling_factor = 1.0 if routed_scaling_factor is None \
            else float(routed_scaling_factor)
        self.max_position_embeddings = int(max_position_embeddings)
        self.ep_size, self.ep_rank = int(ep_size), int(ep_rank)
        self.initializer_range = float(initializer_range)
        self.sink_init = tuple(float(a) for a in sink_init)
        self.correction_bias_std = float(correction_bias_std)
        self.dtype = dtype
        self.source_keys = source_keys
        for name, per_layer in (
                ("hybrid_layer_pattern", self.hybrid_layer_pattern),
                ("moe_layer_freq", self.moe_layer_freq)):
            if len(per_layer) != n or set(per_layer) - {0, 1}:
                raise ValueError(f"{name} must hold {n} entries of 0 or 1, "
                                 f"got {per_layer}")
        if sliding_window_size is not None and \
                int(sliding_window_size) != self.sliding_window:
            raise ValueError(f"sliding_window {sliding_window} and "
                             f"sliding_window_size {sliding_window_size}")
        for kind, heads, kv in (
                ("full", self.num_attention_heads,
                 self.num_key_value_heads),
                ("window", self.swa_num_attention_heads,
                 self.swa_num_key_value_heads)):
            if heads % kv:
                raise ValueError(f"{kind} layers: {heads} query heads over "
                                 f"{kv} kv heads")
        if self.n_routed_experts % self.ep_size or \
                not 0 <= self.ep_rank < self.ep_size:
            raise ValueError(
                f"ep_size {ep_size} must divide n_routed_experts "
                f"{n_routed_experts}, and 0 <= ep_rank {ep_rank} < "
                f"ep_size")
        unbuilt = {"n_shared_experts": n_shared_experts,
                   "scoring_func": scoring_func != "sigmoid",
                   "topk_method": topk_method != "noaux_tc",
                   "n_group": int(n_group) != 1,
                   "topk_group": int(topk_group) != 1,
                   "hidden_act": hidden_act != "silu",
                   "attention_bias": attention_bias,
                   "tie_word_embeddings": tie_word_embeddings}
        if any(unbuilt.values()):
            raise ValueError(f"not built: {unbuilt}")

    @property
    def num_local_experts(self):
        return self.n_routed_experts // self.ep_size

    def window_of(self, layer):
        """The layer's sliding window, or None for full attention."""
        return self.sliding_window \
            if self.hybrid_layer_pattern[layer] == WINDOW else None

    def geometry(self, layer):
        """(query heads, kv heads, key width, value width, rope theta,
        whether a sink joins the softmax) of the layer's kind."""
        if self.hybrid_layer_pattern[layer] == WINDOW:
            return (self.swa_num_attention_heads,
                    self.swa_num_key_value_heads, self.swa_head_dim,
                    self.swa_v_head_dim, self.swa_rope_theta,
                    self.add_swa_attention_sink_bias)
        return (self.num_attention_heads, self.num_key_value_heads,
                self.head_dim, self.v_head_dim, self.rope_theta,
                self.add_full_attention_sink_bias)


def _sink_attend_fwd(q, k, v, sink=None, *, window=None):
    """The published attention without a cache: q [B, L, H, Dk], k
    [B, L, H_kv, Dk], v [B, L, H_kv, Dv] (rope applied), sink f32 [H]
    or None -> [B, L, H, Dv]. Scores and softmax in float32; query t
    sees keys j <= t, and j > t - window in a window layer."""
    b, l, h, dk = q.shape
    hkv = k.shape[2]
    rep = h // hkv
    i = jnp.arange(l)[:, None]
    j = jnp.arange(l)[None, :]
    live = j <= i
    if window is not None:
        live = live & (j > i - window)
    s = jnp.einsum("blgrd,bmgd->bgrlm", q.reshape(b, l, hkv, rep, dk), k,
                   preferred_element_type=jnp.float32) \
        * jnp.float32(1.0 / math.sqrt(dk))
    s = jnp.where(live, s, jnp.float32(-1e30))
    m = jnp.max(s, axis=-1, keepdims=True)
    if sink is not None:
        sk = sink.astype(jnp.float32).reshape(1, hkv, rep, 1, 1)
        m = jnp.maximum(m, sk)
    p = jnp.exp(s - m)
    den = jnp.sum(p, axis=-1, keepdims=True)
    if sink is not None:
        den = den + jnp.exp(sk - m)
    out = jnp.einsum("bgrlm,bmgd->blgrd", (p / den).astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, l, h, v.shape[-1]).astype(q.dtype)


register_op("mimo_sink_attend", _sink_attend_fwd, nondiff=True)


def _rms_norm(cfg):
    return cast(nn.RMSNorm(cfg.hidden_size, epsilon=cfg.layernorm_epsilon),
                cfg)


class MiMoV2Attention(nn.Layer):
    def __init__(self, cfg: MiMoV2Config, layer: int):
        super().__init__()
        (self.n_heads, self.n_kv, self.dk, self.dv, theta,
         has_sink) = cfg.geometry(layer)
        self.window = cfg.window_of(layer)
        self.rot = int(self.dk * cfg.partial_rotary_factor)
        inv, _ = rotary_frequencies({"rope_theta": theta}, self.rot)
        # a constant of the trace, not a weight
        self._inv_freq = np.asarray(inv, np.float32)
        self.value_scale = cfg.attention_value_scale
        h = cfg.hidden_size
        self.q_proj = linear(h, self.n_heads * self.dk, cfg)
        self.k_proj = linear(h, self.n_kv * self.dk, cfg)
        self.v_proj = linear(h, self.n_kv * self.dv, cfg)
        self.o_proj = linear(self.n_heads * self.dv, h, cfg)
        self.sinks = None
        if has_sink:
            mean, std = cfg.sink_init
            self.sinks = self.create_parameter(
                [self.n_heads], dtype=cfg.dtype,
                default_initializer=Normal(mean, std))

    def _rope(self, x, pos):
        return apply_op("rope_half", x, pos,
                        Tensor(jnp.asarray(self._inv_freq)),
                        attrs=dict(rot=self.rot, factor=1.0))

    def forward(self, x, cache=None):
        """x is the layer's NORMED input."""
        from ..ops import manipulation
        from .generation import DecodeCache, update_and_attend_split
        b, l = x.shape[0], x.shape[1]
        q = manipulation.reshape(self.q_proj(x),
                                 [b, l, self.n_heads, self.dk])
        k = manipulation.reshape(self.k_proj(x), [b, l, self.n_kv, self.dk])
        v = manipulation.reshape(self.v_proj(x), [b, l, self.n_kv, self.dv])
        sink = () if self.sinks is None else (self.sinks,)
        new_cache = None
        if isinstance(cache, DecodeCache):
            q, k = self._rope(q, cache.pos), self._rope(k, cache.pos)
            out, new_cache = update_and_attend_split(
                q, k, v, cache, window=self.window, sink=self.sinks)
        else:
            zero = Tensor(jnp.zeros((), jnp.int32))
            q, k = self._rope(q, zero), self._rope(k, zero)
            out = apply_op("mimo_sink_attend", q, k, v, *sink,
                           attrs=dict(window=self.window))
        out = manipulation.reshape(out, [b, l, self.n_heads * self.dv])
        return self.o_proj(out * self.value_scale), new_cache


class MiMoV2SparseMoE(nn.Layer):
    """Router over all `n_routed_experts` with its selection bias, and
    the experts held here (module doc: expert parallelism).
    `last_stats` holds the routed op's counts of the latest call (int32
    [4] Tensor: `moe_route`'s, the assignments the bias moved last)."""

    def __init__(self, cfg: MiMoV2Config):
        super().__init__()
        h, f = cfg.hidden_size, cfg.moe_intermediate_size
        n = cfg.num_local_experts
        self.top_k = cfg.num_experts_per_tok
        self.scale = cfg.routed_scaling_factor
        self.norm_topk = cfg.norm_topk_prob
        self.first = cfg.ep_rank * n
        init = NormalByExpert(0.0, cfg.initializer_range)
        self.gate = linear(h, cfg.n_routed_experts, cfg)
        # float32, as the source keeps it: it decides near ties
        self.e_score_correction_bias = self.create_parameter(
            [cfg.n_routed_experts], dtype="float32",
            default_initializer=Normal(0.0, cfg.correction_bias_std))
        self.experts_gate = self.create_parameter(
            [n, h, f], dtype=cfg.dtype, default_initializer=init)
        self.experts_up = self.create_parameter(
            [n, h, f], dtype=cfg.dtype, default_initializer=init)
        self.experts_down = self.create_parameter(
            [n, f, h], dtype=cfg.dtype, default_initializer=init)
        self.last_stats = None

    def forward(self, x, valid=None):
        if valid is None:
            valid = Tensor(jnp.ones(tuple(x.shape[:2]), bool))
        out, self.last_stats = apply_op(
            "moe_routed_experts", x, valid, self.gate.weight,
            self.experts_gate, self.experts_up, self.experts_down,
            self.e_score_correction_bias,
            attrs=dict(top_k=self.top_k, scale=self.scale,
                       norm_topk=self.norm_topk, first=self.first,
                       scoring="sigmoid"))
        return out


class MiMoV2DecoderLayer(nn.Layer):
    def __init__(self, cfg: MiMoV2Config, layer: int):
        super().__init__()
        self.input_layernorm = _rms_norm(cfg)
        self.self_attn = MiMoV2Attention(cfg, layer)
        self.post_attention_layernorm = _rms_norm(cfg)
        self.mlp = (MiMoV2SparseMoE(cfg) if cfg.moe_layer_freq[layer]
                    else SwiGLU(cfg, cfg.intermediate_size))

    def forward(self, x, cache=None, valid=None):
        h, new_cache = self.self_attn(self.input_layernorm(x),
                                      cache=cache)
        x = x + h
        x = x + self.mlp(self.post_attention_layernorm(x), valid)
        return x, new_cache


class MiMoV2Model(nn.Layer):
    def __init__(self, cfg: MiMoV2Config):
        super().__init__()
        self.config = cfg
        self.embed_tokens = cast(nn.Embedding(
            cfg.vocab_size, cfg.hidden_size, weight_attr=nn.ParamAttr(
                initializer=Normal(0.0, cfg.initializer_range))), cfg)
        self.layers = nn.LayerList([MiMoV2DecoderLayer(cfg, i)
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


class MiMoV2ForCausalLM(nn.Layer):
    def __init__(self, cfg: MiMoV2Config):
        super().__init__()
        self.model = MiMoV2Model(cfg)
        self.lm_head = linear(cfg.hidden_size, cfg.vocab_size, cfg)
        self.config = cfg

    def forward(self, input_ids, caches=None, columns=None):
        if caches is not None:
            h, new_caches = self.model(input_ids, caches=caches)
            return self.lm_head(head_columns(h, columns)), new_caches
        return self.lm_head(head_columns(self.model(input_ids), columns))

    def _decode_cache_spec(self):
        """(layers, the full layers' kv heads and key width, each
        layer's window or None, "split", each layer's (kv heads, key
        width, value width, sink)): the split form of the engine's
        contract."""
        cfg = self.config
        n = cfg.num_hidden_layers
        return (n, cfg.num_key_value_heads, cfg.head_dim,
                tuple(cfg.window_of(i) for i in range(n)), "split",
                tuple(cfg.geometry(i)[1:4] + (cfg.geometry(i)[5],)
                      for i in range(n)))

    def _step_stats(self):
        """Counts the latest forward pass made on the device, for the
        engine to carry out of its step (`STEP_STAT_COUNTERS` names
        them): see `moe_stats`."""
        return moe_stats(self.model.layers)

    STEP_STAT_COUNTERS = ("moe_assignments_total",
                          "moe_assignments_here_total",
                          "moe_experts_hit_total",
                          "moe_bias_reranked_total",
                          "moe_layer_steps_total")
