"""Keye-VL-2.0's language model (`model_type` KeyeVL2; the text config
and `sa_config` of Kwai-Keye/Keye-VL-2.0-30B-A3B): a decoder served by
`ServingEngine` like GPT, Llama, Laguna and DeepSeek-V2. Inference only:
the ops below register no backward pass. The vision tower and its
projector are not built: for text the three components of the
multimodal rope's position are equal, which is plain rope.

What the family has that `laguna.py` does not:

- LEARNED SPARSE ATTENTION (`sa_config`, the DeepSeek-Sparse-Attention
  indexer): beside grouped-query attention's q, k, v a layer computes
  an indexer's queries qI (`indexer_num_heads` x `indexer_head_dim`),
  ONE indexer key kI a token (shared by the indexer's heads and by all
  attention heads) and a weight a head w, and scores

      I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])    (float32, s <= t)

  A query attends only the `topk` positions that score highest (all of
  them while t < topk; of equal scores the lower position). The cache
  holds rope(k), v AND rope(kI) a token and layer (kI in a row of
  `index_row` values: whole tiles of 128 lanes, zeros behind). With a
  cache the selection runs on the paged pools
  (`ops/pallas/sparse.py`); without one (`model(ids)`) the published
  form: every score, `jax.lax.top_k`, a masked softmax. Same
  mathematics; tests hold the two equal.
- an RMSNorm over each head's values of q and k before rope, and a
  LayerNorm on kI before rope (the configuration file's `assumed`).
- every layer's FFN is routed: softmax over `num_experts`, the
  `num_experts_per_tok` largest renormalised to sum 1, no shared
  expert, no dense layer.

EXPERT PARALLELISM, one chip's share: as `laguna.py` (`ep_size`,
`ep_rank`; `vocab_size` is what is held here).

The engine learns the cache's kind from `_decode_cache_spec()`, whose
fifth entry names the sparse kind and whose sixth gives the indexer
row's width and `topk`.
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
from ..ops.pallas.sparse import LANES
from ..nn.initializer import Constant, Normal
from .laguna import rotary_frequencies
from .generation import head_columns
from .moe_common import (MOE_STEP_STAT_COUNTERS, NormalByExpert, cast,
                         linear, moe_stats, valid_columns)

__all__ = ["KeyeVL2Config", "KeyeVL2Model", "KeyeVL2ForCausalLM"]


class KeyeVL2Config:
    """The source's `config.json` keys (defaults: Keye-VL-2.0-30B-A3B's
    text config), plus `ep_size`, `ep_rank` and `dtype` as
    `LagunaConfig` has them. Keys of the source that say nothing this
    code reads (`model_type`, `intermediate_size`, which names no
    matrix, `num_local_experts`, `max_window_layers`, `sa_config`'s two
    chunk sizes: the blocking its indexer is computed in, not part of
    the result) are accepted and kept; those that would change the
    mathematics are checked."""

    def __init__(self, vocab_size=151936, hidden_size=2048,
                 intermediate_size=6144, moe_intermediate_size=768,
                 num_hidden_layers=48, num_attention_heads=32,
                 num_key_value_heads=4, head_dim=128, num_experts=128,
                 num_experts_per_tok=8, norm_topk_prob=True,
                 decoder_sparse_step=1, mlp_only_layers=(),
                 rms_norm_eps=1e-6, rope_theta=10000000.0,
                 rope_scaling=None, sa_config=None,
                 max_position_embeddings=262144, attention_bias=False,
                 hidden_act="silu", tie_word_embeddings=False,
                 sliding_window=None, use_sliding_window=False, ep_size=1,
                 ep_rank=0, initializer_range=0.02, dtype=None,
                 **source_keys):
        for name in ("vocab_size", "hidden_size", "intermediate_size",
                     "moe_intermediate_size", "num_hidden_layers",
                     "num_attention_heads", "num_key_value_heads",
                     "head_dim", "num_experts", "num_experts_per_tok",
                     "max_position_embeddings", "ep_size", "ep_rank"):
            setattr(self, name, int(locals()[name]))
        self.norm_topk_prob = bool(norm_topk_prob)
        self.rms_norm_eps = float(rms_norm_eps)
        self.rope_theta = float(rope_theta)
        self.rope_scaling = dict(rope_scaling) if rope_scaling else None
        self.sa_config = dict(sa_config or {
            "indexer_head_dim": 64, "indexer_num_heads": 16,
            "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
            "q_chunk_size": 512, "topk": 2048})
        self.initializer_range = float(initializer_range)
        self.dtype = dtype
        self.source_keys = source_keys
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of "
                             "num_key_value_heads")
        if self.num_experts % self.ep_size or \
                not 0 <= self.ep_rank < self.ep_size:
            raise ValueError(
                f"ep_size {ep_size} must divide num_experts "
                f"{num_experts}, and 0 <= ep_rank {ep_rank} < ep_size")
        rope_type = (self.rope_scaling or {}).get(
            "rope_type", (self.rope_scaling or {}).get("type", "default"))
        built = {"hidden_act": (hidden_act, "silu"),
                 "decoder_sparse_step": (int(decoder_sparse_step), 1),
                 "mlp_only_layers": (len(tuple(mlp_only_layers)), 0),
                 "rope_type": (rope_type, "default"),
                 "indexer_num_kv_heads":
                 (int(self.sa_config.get("indexer_num_kv_heads", 1)), 1),
                 "attention_bias": (bool(attention_bias), False),
                 "tie_word_embeddings": (bool(tie_word_embeddings), False),
                 "use_sliding_window": (bool(use_sliding_window), False)}
        wrong = {k: got for k, (got, want) in built.items() if got != want}
        if wrong:
            raise ValueError(f"not built: {wrong}")

    @property
    def experts_here(self):
        return self.num_experts // self.ep_size

    @property
    def index_heads(self):
        return int(self.sa_config["indexer_num_heads"])

    @property
    def index_dim(self):
        return int(self.sa_config["indexer_head_dim"])

    @property
    def topk(self):
        return int(self.sa_config["topk"])

    @property
    def index_row(self):
        """The indexer key's width in the cache: `index_dim` rounded up
        to the device's 128 lanes, zeros behind (the tiling pads a row to
        that in HBM whatever its shape says, and the indexer's kernel
        reads a page by one DMA: `ops/pallas/sparse.py`)."""
        return -(-self.index_dim // LANES) * LANES


def _layer_norm_fwd(x, w, b, eps):
    """LayerNorm over the last axis of x [..., D], weight and bias, in
    float32."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, -1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)
            + b.astype(jnp.float32)).astype(x.dtype)


register_op("keye_layer_norm", _layer_norm_fwd, nondiff=True)


def _pad_last_fwd(x, width):
    """x [..., d] -> [..., width], zeros behind."""
    pad = width - x.shape[-1]
    if not pad:
        return x
    return jnp.concatenate(
        [x, jnp.zeros(x.shape[:-1] + (pad,), x.dtype)], axis=-1)


register_op("keye_pad_last", _pad_last_fwd, nondiff=True)


def _published_attention_fwd(q, k, v, q_idx, k_idx, w_idx, topk):
    """The published form over one whole sequence a row, no cache:
    q [B, L, H, D], k / v [B, L, H_kv, D], q_idx [B, L, Hi, Di], k_idx
    [B, L, Di], w_idx [B, L, Hi] -> [B, L, H, D]. Every indexer score
    in float32, each query's `topk` largest among the positions at or
    below it by `jax.lax.top_k` (of equal scores the lower position),
    a softmax over those alone."""
    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    b, l, h, d = q.shape
    hkv = k.shape[2]
    score = jnp.einsum("bthd,bsd->bths", q_idx.astype(f32),
                       k_idx.astype(f32), precision=hi)
    score = jnp.sum(w_idx.astype(f32)[..., None] * jnp.maximum(score, 0.0),
                    axis=2)                                  # [B, L, L]
    seen = jnp.arange(l)[None, :] <= jnp.arange(l)[:, None]
    _, at = jax.lax.top_k(jnp.where(seen[None], score, -jnp.inf),
                          min(int(topk), l))
    rows = jnp.arange(l)[None, :, None]
    chosen = jnp.zeros((b, l, l), bool).at[
        jnp.arange(b)[:, None, None], rows, at].set(True) & seen[None]
    q5 = q.astype(f32).reshape(b, l, hkv, h // hkv, d)
    s = jnp.einsum("btgrd,bsgd->btgrs", q5, k.astype(f32),
                   precision=hi) / math.sqrt(d)
    p = jax.nn.softmax(jnp.where(chosen[:, :, None, None, :], s, -jnp.inf),
                       axis=-1)
    return jnp.einsum("btgrs,bsgd->btgrd", p, v.astype(f32), precision=hi) \
        .reshape(b, l, h, d).astype(q.dtype)


register_op("keye_published_attention", _published_attention_fwd,
            nondiff=True)


class KeyeVL2Attention(nn.Layer):
    def __init__(self, cfg: KeyeVL2Config):
        super().__init__()
        self.n_heads, self.n_kv = (cfg.num_attention_heads,
                                   cfg.num_key_value_heads)
        self.head_dim = d = cfg.head_dim
        self.index_heads, self.index_dim = cfg.index_heads, cfg.index_dim
        self.index_row, self.topk = cfg.index_row, cfg.topk
        self.eps = cfg.rms_norm_eps
        rope = {"rope_type": "default", "rope_theta": cfg.rope_theta}
        # constants of the trace, not weights: the attention's rope over
        # the whole head, the indexer's over its whole head, one theta
        self._inv_freq = np.asarray(rotary_frequencies(rope, d)[0],
                                    np.float32)
        self._inv_freq_index = np.asarray(
            rotary_frequencies(rope, self.index_dim)[0], np.float32)
        h = cfg.hidden_size
        self.q_proj = linear(h, self.n_heads * d, cfg)
        self.k_proj = linear(h, self.n_kv * d, cfg)
        self.v_proj = linear(h, self.n_kv * d, cfg)
        self.o_proj = linear(self.n_heads * d, h, cfg)
        self.q_norm = cast(nn.RMSNorm(d, epsilon=self.eps), cfg)
        self.k_norm = cast(nn.RMSNorm(d, epsilon=self.eps), cfg)
        self.index_q_proj = linear(h, self.index_heads * self.index_dim,
                                   cfg)
        self.index_k_proj = linear(h, self.index_dim, cfg)
        self.index_w_proj = linear(h, self.index_heads, cfg)
        self.index_k_norm_weight = self.create_parameter(
            [self.index_dim], dtype=cfg.dtype,
            default_initializer=Constant(1.0))
        self.index_k_norm_bias = self.create_parameter(
            [self.index_dim], dtype=cfg.dtype,
            default_initializer=Constant(0.0))

    def _rope(self, x, pos, inv_freq):
        return apply_op("rope_half", x, pos, Tensor(jnp.asarray(inv_freq)),
                        attrs=dict(rot=int(x.shape[-1]), factor=1.0))

    def forward(self, x, cache=None):
        """x is the layer's NORMED input (the indexer reads it too)."""
        from ..ops import manipulation
        from .generation import DecodeCache, update_and_attend_sparse
        b, l, d = x.shape[0], x.shape[1], self.head_dim
        pos = cache.pos if isinstance(cache, DecodeCache) \
            else Tensor(jnp.zeros((), jnp.int32))

        def heads(t, n, width, norm=None):
            """[b, l, n * width] -> [b, l, n, width], each head normed
            over its own values where the layer has a norm for it."""
            t = manipulation.reshape(t, [b, l, n, width])
            return t if norm is None else norm(t)

        q = self._rope(heads(self.q_proj(x), self.n_heads, d, self.q_norm),
                       pos, self._inv_freq)
        k = self._rope(heads(self.k_proj(x), self.n_kv, d, self.k_norm),
                       pos, self._inv_freq)
        v = heads(self.v_proj(x), self.n_kv, d)
        q_idx = self._rope(heads(self.index_q_proj(x), self.index_heads,
                                 self.index_dim), pos, self._inv_freq_index)
        k_idx = apply_op("keye_layer_norm", self.index_k_proj(x),
                         self.index_k_norm_weight, self.index_k_norm_bias,
                         attrs=dict(eps=self.eps))
        k_idx = self._rope(heads(k_idx, 1, self.index_dim), pos,
                           self._inv_freq_index)
        w_idx = self.index_w_proj(x)
        new_cache = None
        if isinstance(cache, DecodeCache):
            wide = dict(width=self.index_row)
            out, new_cache = update_and_attend_sparse(
                q, k, v, apply_op("keye_pad_last", q_idx, attrs=wide), w_idx,
                apply_op("keye_pad_last", k_idx, attrs=wide)[:, :, 0],
                cache, topk=self.topk)
        else:
            out = apply_op("keye_published_attention", q, k, v, q_idx,
                           k_idx[:, :, 0], w_idx, attrs=dict(topk=self.topk))
        out = self.o_proj(manipulation.reshape(out,
                                               [b, l, self.n_heads * d]))
        return out, new_cache


class KeyeVL2SparseMoE(nn.Layer):
    """Router over all `num_experts` and the experts held here
    (`laguna.py`: expert parallelism); no shared expert. `last_stats`:
    the routed op's counts of the latest call."""

    def __init__(self, cfg: KeyeVL2Config):
        super().__init__()
        h, f = cfg.hidden_size, cfg.moe_intermediate_size
        n = cfg.experts_here
        self.attrs = dict(top_k=cfg.num_experts_per_tok, scale=1.0,
                          norm_topk=cfg.norm_topk_prob,
                          first=cfg.ep_rank * n)
        init = NormalByExpert(0.0, cfg.initializer_range)
        self.router = linear(h, cfg.num_experts, cfg)
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
        routed, self.last_stats = apply_op(
            "moe_routed_experts", x, valid, self.router.weight,
            self.experts_gate, self.experts_up, self.experts_down,
            attrs=self.attrs)
        return routed


class KeyeVL2DecoderLayer(nn.Layer):
    def __init__(self, cfg: KeyeVL2Config):
        super().__init__()
        self.input_layernorm = cast(nn.RMSNorm(
            cfg.hidden_size, epsilon=cfg.rms_norm_eps), cfg)
        self.self_attn = KeyeVL2Attention(cfg)
        self.post_attention_layernorm = cast(nn.RMSNorm(
            cfg.hidden_size, epsilon=cfg.rms_norm_eps), cfg)
        self.mlp = KeyeVL2SparseMoE(cfg)

    def forward(self, x, cache=None, valid=None):
        h, new_cache = self.self_attn(self.input_layernorm(x), cache=cache)
        x = x + h
        x = x + self.mlp(self.post_attention_layernorm(x), valid)
        return x, new_cache


class KeyeVL2Model(nn.Layer):
    def __init__(self, cfg: KeyeVL2Config):
        super().__init__()
        self.config = cfg
        self.embed_tokens = cast(nn.Embedding(
            cfg.vocab_size, cfg.hidden_size, weight_attr=nn.ParamAttr(
                initializer=Normal(0.0, cfg.initializer_range))), cfg)
        self.layers = nn.LayerList([KeyeVL2DecoderLayer(cfg)
                                    for _ in range(cfg.num_hidden_layers)])
        self.norm = cast(nn.RMSNorm(cfg.hidden_size,
                                    epsilon=cfg.rms_norm_eps), cfg)

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


class KeyeVL2ForCausalLM(nn.Layer):
    def __init__(self, cfg: KeyeVL2Config):
        super().__init__()
        self.model = KeyeVL2Model(cfg)
        self.lm_head = linear(cfg.hidden_size, cfg.vocab_size, cfg)
        self.config = cfg

    def forward(self, input_ids, caches=None, columns=None):
        if caches is not None:
            h, new_caches = self.model(input_ids, caches=caches)
            return self.lm_head(head_columns(h, columns)), new_caches
        return self.lm_head(head_columns(self.model(input_ids), columns))

    def _decode_cache_spec(self):
        """The six-entry form of `ServingEngine`'s cache-spec contract:
        (layers, kv heads, head size, no windows, "sparse", (the
        indexer row's width, topk)): every layer caches a key and a
        value of every kv head AND one indexer row a token."""
        cfg = self.config
        return (cfg.num_hidden_layers, cfg.num_key_value_heads,
                cfg.head_dim, (None,) * cfg.num_hidden_layers, "sparse",
                (cfg.index_row, cfg.topk))

    def _step_stats(self):
        return moe_stats(self.model.layers)

    STEP_STAT_COUNTERS = MOE_STEP_STAT_COUNTERS
