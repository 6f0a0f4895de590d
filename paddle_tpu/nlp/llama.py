"""Llama family (BASELINE config #5: sharding-stage3/GSPMD scale-out).

RMSNorm + rotary embeddings + SwiGLU + GQA — exercises rms_norm, the
flash/ring attention paths and sharded training. TP via Column/Row
parallel projections when the "mp" axis is live.
"""
from __future__ import annotations

import math

import numpy as np
import jax.numpy as jnp

from .. import nn
from ..nn import functional as F
from ..core.tensor import Tensor
from ..core.dispatch import register_op
from ..ops._helpers import apply_op, as_tensor
from ..nn.initializer import Normal
from .generation import head_columns
from .gpt import _make_linear, _mp_active, _sep_active

__all__ = ["LlamaConfig", "LlamaModel", "LlamaForCausalLM"]


class LlamaConfig:
    def __init__(self, vocab_size=32000, hidden_size=4096,
                 num_hidden_layers=32, num_attention_heads=32,
                 num_key_value_heads=None, intermediate_size=11008,
                 max_position_embeddings=4096, rms_norm_eps=1e-6,
                 rope_theta=10000.0, initializer_range=0.02,
                 use_recompute=False, sequence_parallel=False):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads or \
            num_attention_heads
        self.intermediate_size = intermediate_size
        self.max_position_embeddings = max_position_embeddings
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.initializer_range = initializer_range
        self.use_recompute = use_recompute
        self.sequence_parallel = sequence_parallel
        self.hidden_dropout_prob = 0.0


def _rope_fwd(x, offset, theta):
    """x: [B, L, H, D] -> rotary-embedded."""
    b, l, h, d = x.shape
    pos = jnp.arange(offset, offset + l, dtype=jnp.float32)
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    freqs = jnp.outer(pos, inv)                       # [L, D/2]
    cos = jnp.cos(freqs)[None, :, None, :]
    sin = jnp.sin(freqs)[None, :, None, :]
    x1 = x[..., 0::2]
    x2 = x[..., 1::2]
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    out = jnp.stack([o1, o2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


register_op("rope", _rope_fwd)


def _rope_dyn_fwd(x, offset, theta):
    """Rope with a TRACED position offset (static-cache decode): a
    scalar int32 array, or a per-row vector [B] (continuous-batching
    decode, every slot at its own position)."""
    b, l, h, d = x.shape
    off = offset.astype(jnp.float32)
    steps = jnp.arange(l, dtype=jnp.float32)
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    if off.ndim == 1:
        freqs = (off[:, None] + steps[None])[:, :, None] * \
            inv[None, None, :]                        # [B, L, D/2]
        cos = jnp.cos(freqs)[:, :, None, :]
        sin = jnp.sin(freqs)[:, :, None, :]
    else:
        freqs = jnp.outer(off + steps, inv)           # [L, D/2]
        cos = jnp.cos(freqs)[None, :, None, :]
        sin = jnp.sin(freqs)[None, :, None, :]
    x1 = x[..., 0::2]
    x2 = x[..., 1::2]
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    return jnp.stack([o1, o2], axis=-1).reshape(x.shape).astype(x.dtype)


register_op("rope_dyn", _rope_dyn_fwd)


def apply_rotary(x, offset=0, theta=10000.0):
    if isinstance(offset, Tensor):
        return apply_op("rope_dyn", as_tensor(x), offset,
                        attrs=dict(theta=float(theta)))
    return apply_op("rope", as_tensor(x),
                    attrs=dict(offset=int(offset), theta=float(theta)))


class LlamaAttention(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.n_heads = cfg.num_attention_heads
        self.n_kv = cfg.num_key_value_heads
        self.head_dim = cfg.hidden_size // cfg.num_attention_heads
        self.theta = cfg.rope_theta
        h = cfg.hidden_size
        self.q_proj = _make_linear(h, self.n_heads * self.head_dim, cfg,
                                   parallel="column")
        self.k_proj = _make_linear(h, self.n_kv * self.head_dim, cfg,
                                   parallel="column")
        self.v_proj = _make_linear(h, self.n_kv * self.head_dim, cfg,
                                   parallel="column")
        self.o_proj = _make_linear(self.n_heads * self.head_dim, h, cfg,
                                   parallel="row")

    def forward(self, x, cache=None):
        from ..ops import manipulation
        b, l = x.shape[0], x.shape[1]
        from .generation import DecodeCache, update_and_attend
        # multi-tenant LoRA (serving/adapters.py): the cache carries
        # this layer's PER-ROW gathered A/B pairs; the low-rank delta
        # adds to each projection BEFORE rope (merged-weight
        # equivalence: rope((W + BA)x) == rope(Wx + BAx))
        lora = (cache.lora if isinstance(cache, DecodeCache)
                else None)
        # megakernel mode: rope sits between the projections and the
        # attend (rope((W + BA)x) != rope(Wx) + BAx rearranged into
        # the attend's prologue), so llama CANNOT bundle its deltas
        # into megakernel_decode — each projection takes the
        # standalone paged-gather op instead (the adapter page still
        # streams through the fused kernel, once per projection).
        lora_paged = (cache.lora_paged
                      if isinstance(cache, DecodeCache) else None)
        qf, kf, vf = self.q_proj(x), self.k_proj(x), self.v_proj(x)
        if lora is not None:
            aq, bq, ak, bk, av, bv, ao, bo, sc = lora
            qf = qf + apply_op("lora_delta", x, aq, bq, sc)
            kf = kf + apply_op("lora_delta", x, ak, bk, sc)
            vf = vf + apply_op("lora_delta", x, av, bv, sc)
        elif lora_paged is not None:
            (aq, bq, ak, bk, av, bv, ao, bo, apage,
             ascale) = lora_paged
            qf = qf + apply_op("lora_delta_paged", x, aq, bq, apage,
                               ascale)
            kf = kf + apply_op("lora_delta_paged", x, ak, bk, apage,
                               ascale)
            vf = vf + apply_op("lora_delta_paged", x, av, bv, apage,
                               ascale)
        q = manipulation.reshape(qf,
                                 [b, l, self.n_heads, self.head_dim])
        k = manipulation.reshape(kf, [b, l, self.n_kv, self.head_dim])
        v = manipulation.reshape(vf, [b, l, self.n_kv, self.head_dim])
        if isinstance(cache, DecodeCache):
            q = apply_rotary(q, cache.pos, self.theta)
            k = apply_rotary(k, cache.pos, self.theta)
            out, new_cache = update_and_attend(q, k, v, cache,
                                               training=False)
            out = manipulation.reshape(
                out, [b, l, self.n_heads * self.head_dim])
            o = self.o_proj(out)
            if lora is not None:
                o = o + apply_op("lora_delta", out, ao, bo, sc)
            elif lora_paged is not None:
                o = o + apply_op("lora_delta_paged", out, ao, bo,
                                 apage, ascale)
            return o, new_cache
        offset = cache[0].shape[1] if cache is not None else 0
        q = apply_rotary(q, offset, self.theta)
        k = apply_rotary(k, offset, self.theta)
        if cache is not None:
            k = manipulation.concat([cache[0], k], axis=1)
            v = manipulation.concat([cache[1], v], axis=1)
            new_cache = (k, v)
        else:
            new_cache = None
        if self.n_kv != self.n_heads:
            rep = self.n_heads // self.n_kv
            k = manipulation.repeat_interleave(k, rep, axis=2)
            v = manipulation.repeat_interleave(v, rep, axis=2)
        if _sep_active() and cache is None:
            from ..distributed import ring_attention
            out = ring_attention(q, k, v, causal=True)
        else:
            out = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                                 training=self.training)
        out = manipulation.reshape(out, [b, l,
                                         self.n_heads * self.head_dim])
        out = self.o_proj(out)
        if new_cache is not None:
            return out, new_cache
        return out


class LlamaMLP(nn.Layer):
    """SwiGLU."""

    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.gate_proj = _make_linear(cfg.hidden_size,
                                      cfg.intermediate_size, cfg,
                                      parallel="column")
        self.up_proj = _make_linear(cfg.hidden_size,
                                    cfg.intermediate_size, cfg,
                                    parallel="column")
        self.down_proj = _make_linear(cfg.intermediate_size,
                                      cfg.hidden_size, cfg, parallel="row")

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size,
                                          epsilon=cfg.rms_norm_eps)
        self.self_attn = LlamaAttention(cfg)
        self.post_attention_layernorm = nn.RMSNorm(
            cfg.hidden_size, epsilon=cfg.rms_norm_eps)
        self.mlp = LlamaMLP(cfg)
        self.use_recompute = cfg.use_recompute

    def _body(self, x):
        x = x + self.self_attn(self.input_layernorm(x))
        x = x + self.mlp(self.post_attention_layernorm(x))
        return x

    def forward(self, x, cache=None):
        if cache is not None:
            h, new_cache = self.self_attn(self.input_layernorm(x),
                                          cache=cache)
            x = x + h
            x = x + self.mlp(self.post_attention_layernorm(x))
            return x, new_cache
        if self.use_recompute and self.training:
            from ..distributed.fleet.utils import recompute
            return recompute(self._body, x)
        return self._body(x)


class LlamaModel(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.config = cfg
        init = nn.ParamAttr(initializer=Normal(0.0, cfg.initializer_range))
        if _mp_active():
            from ..distributed import fleet
            self.embed_tokens = fleet.VocabParallelEmbedding(
                cfg.vocab_size, cfg.hidden_size, weight_attr=init)
        else:
            self.embed_tokens = nn.Embedding(cfg.vocab_size,
                                             cfg.hidden_size,
                                             weight_attr=init)
        self.layers = nn.LayerList([LlamaDecoderLayer(cfg)
                                    for _ in range(cfg.num_hidden_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)

    def forward(self, input_ids, caches=None):
        x = self.embed_tokens(input_ids)
        new_caches = [] if caches is not None else None
        for i, layer in enumerate(self.layers):
            if caches is not None:
                x, c = layer(x, cache=caches[i])
                new_caches.append(c)
            else:
                x = layer(x)
        x = self.norm(x)
        if caches is not None:
            return x, new_caches
        return x


class LlamaForCausalLM(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.llama = LlamaModel(cfg)
        self.lm_head = _make_linear(cfg.hidden_size, cfg.vocab_size, cfg,
                                    parallel="column", gather_output=True)
        self.config = cfg

    def forward(self, input_ids, labels=None, caches=None, columns=None):
        if caches is not None:
            h, new_caches = self.llama(input_ids, caches=caches)
            return self.lm_head(head_columns(h, columns)), new_caches
        h = self.llama(input_ids)
        logits = self.lm_head(head_columns(h, columns))
        if labels is not None:
            return F.cross_entropy(logits, labels)
        return logits

    def _decode_cache_spec(self):
        cfg = self.config
        return (cfg.num_hidden_layers, cfg.num_key_value_heads,
                cfg.hidden_size // cfg.num_attention_heads)

    def generate(self, input_ids, max_new_tokens=16, temperature=1.0,
                 top_k=None, top_p=None, eos_token_id=None,
                 pad_token_id=0, decode_strategy=None, num_beams=4,
                 length_penalty=0.0, num_return_sequences=1,
                 kv_cache_dtype=None):
        """Compiled autoregressive decoding (one XLA program: static KV
        cache + lax.while_loop with EOS early exit — nlp/generation.py)."""
        from .generation import CompiledGenerator
        key = (float(temperature), top_k, top_p, eos_token_id,
               int(pad_token_id), decode_strategy, int(num_beams),
               float(length_penalty), int(num_return_sequences),
               kv_cache_dtype)
        gens = getattr(self, "_compiled_generators", None)
        if gens is None:
            gens = self._compiled_generators = {}
        gen = gens.get(key)
        if gen is None:
            gen = CompiledGenerator(
                self, self._decode_cache_spec(), temperature=temperature,
                top_k=top_k, top_p=top_p, eos_token_id=eos_token_id,
                pad_token_id=pad_token_id,
                decode_strategy=decode_strategy, num_beams=num_beams,
                length_penalty=length_penalty,
                num_return_sequences=num_return_sequences,
                kv_cache_dtype=kv_cache_dtype)
            gens[key] = gen
        return gen(input_ids, max_new_tokens)
