"""DeepSeek-V2 (arXiv:2405.04434; `model_type` deepseek_v2): a decoder
served by `ServingEngine` like GPT, Llama and Laguna. Inference only:
the ops below register no backward pass.

What the family has that `laguna.py` does not:

- LATENT attention: the query goes through a low-rank path
  (`q_lora_rank`, an RMSNorm on the latent); keys and values come from
  ONE latent row a token (`kv_lora_rank` values, RMSNorm) plus a rope
  part of `qk_rope_head_dim` values that all heads share; a head's key
  is [its up-projected `qk_nope_head_dim` values | the shared rope
  part], its value `v_head_dim` up-projected values. The cache holds
  the latent row and the rope part only (in a row of `cache_row`
  values: whole tiles of 128 lanes, zeros behind). With a cache the
  ABSORBED form runs: the query's no-position part is carried through
  the key up-projection W_UK (so every head scores against the one
  cached row, `ops/pallas/mla.py`) and the softmax-weighted sum of
  latent rows through the value up-projection W_UV afterwards; without
  one (`model(ids)`) the EXPANDED form as published, which builds every
  head's keys and values. Same mathematics; tests hold the two equal.
- rope pairs dimension 2i with 2i + 1 (the source de-interleaves the
  pairs and then rotates halves: the same scores); YaRN frequencies,
  cos and sin times mscale's factor over mscale_all_dim's (1 for the
  source), and a softmax scale times
  (0.1 * mscale_all_dim * ln(factor) + 1)^2.
- routed experts chosen from softmax scores under a GROUP LIMIT
  (`topk_method` group_limited_greedy: the experts of the `topk_group`
  best of `n_group` groups only; `ops/pallas/moe._top_experts`),
  weighted by their scores times `routed_scaling_factor` without
  renormalising, plus `n_shared_experts` shared experts (one SwiGLU of
  their summed width) added ungated; `first_k_dense_replace` leading
  dense layers.

EXPERT PARALLELISM, one chip's share: as `laguna.py` (`ep_size`,
`ep_rank`; `vocab_size` is what is held here). With `ep_size` equal to
`n_group` the chip holds one routing group, which is how the paper
deploys a layer's experts.

The engine learns the cache's kind from `_decode_cache_spec()`, whose
fifth entry names the latent kind.
"""
from __future__ import annotations

import contextlib
import math

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental.xla_metadata import set_xla_metadata

from .. import nn
from ..core.tensor import Tensor
from ..core.dispatch import register_op
from ..ops._helpers import apply_op
from ..ops.pallas.mla import LANES
from ..nn.initializer import Normal
from .laguna import rotary_frequencies
from .generation import head_columns
from .moe_common import (MOE_STEP_STAT_COUNTERS, NormalByExpert, SwiGLU,
                         cast, linear, moe_stats, valid_columns)

__all__ = ["DeepseekV2Config", "DeepseekV2Model", "DeepseekV2ForCausalLM"]

PROJECT_SCOPE = "ptk:mla_project"


@contextlib.contextmanager
def project_scope():
    """The name of the projections around the walk (W_qa, W_qb, W_kva
    and the two absorbs), where the device trace can find it: as
    `ops/pallas/moe.route_scope`."""
    with jax.named_scope(PROJECT_SCOPE), set_xla_metadata(ptk=PROJECT_SCOPE):
        yield


class DeepseekV2Config:
    """The source's `config.json` keys (defaults: DeepSeek-V2's), plus
    `ep_size`, `ep_rank` and `dtype` as `LagunaConfig` has them. Keys of
    the source that say nothing this code reads are accepted and kept;
    those that would change the mathematics are checked."""

    def __init__(self, vocab_size=102400, hidden_size=5120,
                 intermediate_size=12288, moe_intermediate_size=1536,
                 num_hidden_layers=60, num_attention_heads=128,
                 num_key_value_heads=128, q_lora_rank=1536,
                 kv_lora_rank=512, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128,
                 n_routed_experts=160, n_shared_experts=2,
                 num_experts_per_tok=6, n_group=8, topk_group=3,
                 first_k_dense_replace=1, moe_layer_freq=1,
                 norm_topk_prob=False, routed_scaling_factor=16.0,
                 scoring_func="softmax",
                 topk_method="group_limited_greedy", rms_norm_eps=1e-6,
                 rope_theta=10000.0, rope_scaling=None,
                 max_position_embeddings=163840, attention_bias=False,
                 hidden_act="silu", tie_word_embeddings=False, ep_size=1,
                 ep_rank=0, initializer_range=0.02, dtype=None,
                 **source_keys):
        for name in ("vocab_size", "hidden_size", "intermediate_size",
                     "moe_intermediate_size", "num_hidden_layers",
                     "num_attention_heads", "q_lora_rank", "kv_lora_rank",
                     "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                     "n_routed_experts", "n_shared_experts",
                     "num_experts_per_tok", "n_group", "topk_group",
                     "first_k_dense_replace", "max_position_embeddings",
                     "ep_size", "ep_rank"):
            setattr(self, name, int(locals()[name]))
        self.norm_topk_prob = bool(norm_topk_prob)
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.rms_norm_eps = float(rms_norm_eps)
        self.rope_theta = float(rope_theta)
        self.rope_scaling = dict(rope_scaling) if rope_scaling else None
        self.initializer_range = float(initializer_range)
        self.dtype = dtype
        self.source_keys = source_keys
        if self.n_routed_experts % self.ep_size or \
                not 0 <= self.ep_rank < self.ep_size:
            raise ValueError(
                f"ep_size {ep_size} must divide n_routed_experts "
                f"{n_routed_experts}, and 0 <= ep_rank {ep_rank} < ep_size")
        if self.n_routed_experts % self.n_group \
                or not 1 <= self.topk_group <= self.n_group \
                or self.num_experts_per_tok > self.topk_group \
                * (self.n_routed_experts // self.n_group):
            raise ValueError(
                f"n_group {n_group} / topk_group {topk_group} / top-"
                f"{num_experts_per_tok} over {n_routed_experts} experts")
        if self.rope_scaling and self.rope_scaling.get("type") != "yarn":
            raise ValueError(f"rope_scaling {rope_scaling}: only yarn")
        built = {"scoring_func": (scoring_func, "softmax"),
                 "topk_method": (topk_method, "group_limited_greedy"),
                 "hidden_act": (hidden_act, "silu"),
                 "moe_layer_freq": (int(moe_layer_freq), 1),
                 "num_key_value_heads": (int(num_key_value_heads),
                                         self.num_attention_heads),
                 "attention_bias": (bool(attention_bias), False),
                 "tie_word_embeddings": (bool(tie_word_embeddings), False)}
        wrong = {k: got for k, (got, want) in built.items() if got != want}
        if wrong:
            raise ValueError(f"not built: {wrong}")

    @property
    def num_local_experts(self):
        return self.n_routed_experts // self.ep_size

    @property
    def latent_row(self):
        """Values one token's cached attention row holds."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_row(self):
        """The row's width in the cache: `latent_row` rounded up to the
        device's 128 lanes, zeros behind. The tiling pads a row to that
        in HBM whatever its shape says, and the walk's one DMA a page
        (`ops/pallas/mla.py`) wants rows of whole tiles."""
        return -(-self.latent_row // LANES) * LANES

    def _yarn_factor(self, key):
        """0.1 * rope_scaling[key] * ln(factor) + 1 (1 without YaRN)."""
        rope = self.rope_scaling
        if not rope or float(rope["factor"]) <= 1:
            return 1.0
        return 0.1 * float(rope.get(key, 0) or 0) \
            * math.log(float(rope["factor"])) + 1.0

    def softmax_scale(self):
        """(nope + rope)^-0.5, times YaRN's factor squared."""
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 \
            * self._yarn_factor("mscale_all_dim") ** 2

    def rope_frequencies(self):
        """(inv_freq, factor on cos and sin) over the rope part: YaRN as
        `laguna.rotary_frequencies` computes it."""
        if not self.rope_scaling:
            return rotary_frequencies(
                {"rope_type": "default", "rope_theta": self.rope_theta},
                self.qk_rope_head_dim)
        return rotary_frequencies(
            dict(self.rope_scaling, rope_type="yarn",
                 rope_theta=self.rope_theta,
                 attention_factor=self._yarn_factor("mscale")
                 / self._yarn_factor("mscale_all_dim")),
            self.qk_rope_head_dim)


def _rope_pairs_fwd(x, pos, inv_freq, factor):
    """x [B, L, ..., D] at positions pos + 0..L-1 (pos: int scalar or
    [B]); rotary over all D dimensions, dimension 2i paired with
    2i + 1."""
    l, d = x.shape[1], x.shape[-1]
    p = pos.astype(jnp.float32)
    steps = jnp.arange(l, dtype=jnp.float32)
    t = (p[:, None] + steps[None]) if p.ndim == 1 else (p + steps)[None]
    ang = t[:, :, None] * inv_freq[None, None, :]      # [B|1, L, D/2]
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[2:])
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    pair = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    a, b = pair[..., 0], pair[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1) \
        .reshape(x.shape).astype(x.dtype)


register_op("rope_pairs", _rope_pairs_fwd, nondiff=True)


def _kv_b(w, heads, nope):
    """kv_b weight [latent, heads * (nope + v)] -> (W_UK [latent, heads,
    nope], W_UV [latent, heads, v])."""
    w3 = w.reshape(w.shape[0], heads, -1)
    return w3[:, :, :nope], w3[:, :, nope:]


def _absorb_q_fwd(q_nope, q_rope, w, heads, nope, row):
    """q_nope [B, L, H, nope] through W_UK -> [B, L, H, latent], beside
    q_rope and the zeros that fill the cache's row: the query that
    scores against the cached row, [B, L, H, row]."""
    w_uk, _ = _kv_b(w, heads, nope)
    with project_scope():
        q_lat = jnp.einsum("blhd,chd->blhc", q_nope, w_uk,
                           preferred_element_type=jnp.float32) \
            .astype(q_nope.dtype)
        pad = row - q_lat.shape[-1] - q_rope.shape[-1]
        parts = [q_lat, q_rope] + ([jnp.zeros(
            q_lat.shape[:-1] + (pad,), q_lat.dtype)] if pad else [])
        return jnp.concatenate(parts, axis=-1)


def _expand_v_fwd(o, w, heads, nope):
    """o [B, L, H, latent] (weighted sums of latent rows) through W_UV
    -> [B, L, H, v]."""
    _, w_uv = _kv_b(w, heads, nope)
    with project_scope():
        return jnp.einsum("blhc,chd->blhd", o, w_uv,
                          preferred_element_type=jnp.float32) \
            .astype(o.dtype)


register_op("mla_absorb_q", _absorb_q_fwd, nondiff=True)
register_op("mla_expand_v", _expand_v_fwd, nondiff=True)


def _expanded_attention_fwd(q_nope, q_rope, c_kv, k_rope, w, heads, nope,
                            scale):
    """The EXPANDED form, as published, over one whole sequence a row,
    no cache: q_nope [B, L, H, nope], q_rope [B, L, H, rope], c_kv
    [B, L, latent], k_rope [B, L, rope], kv_b weight w -> [B, L, H, v].
    Every head's keys and values are built; softmax in float32."""
    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    l = q_nope.shape[1]
    w_uk, w_uv = _kv_b(w.astype(f32), heads, nope)
    c = c_kv.astype(f32)
    k_nope = jnp.einsum("bsc,chd->bshd", c, w_uk, precision=hi)
    v = jnp.einsum("bsc,chd->bshd", c, w_uv, precision=hi)
    s = (jnp.einsum("bthd,bshd->bhts", q_nope.astype(f32), k_nope,
                    precision=hi)
         + jnp.einsum("bthd,bsd->bhts", q_rope.astype(f32),
                      k_rope.astype(f32), precision=hi)) * scale
    seen = jnp.arange(l)[None, :] <= jnp.arange(l)[:, None]
    p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhts,bshd->bthd", p, v, precision=hi) \
        .astype(q_nope.dtype)


register_op("mla_expanded_attention", _expanded_attention_fwd,
            nondiff=True)


def _rms(width, cfg):
    return cast(nn.RMSNorm(width, epsilon=cfg.rms_norm_eps), cfg)


class DeepseekV2Attention(nn.Layer):
    def __init__(self, cfg: DeepseekV2Config):
        super().__init__()
        self.n_heads = h = cfg.num_attention_heads
        self.nope, self.rope_dim = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        self.v_dim, self.latent = cfg.v_head_dim, cfg.kv_lora_rank
        self.cache_row = cfg.cache_row
        self.scale = cfg.softmax_scale()
        inv, self.rope_factor = cfg.rope_frequencies()
        # a constant of the trace, not a weight
        self._inv_freq = np.asarray(inv, np.float32)
        hid = cfg.hidden_size
        self.q_a_proj = linear(hid, cfg.q_lora_rank, cfg)
        self.q_a_layernorm = _rms(cfg.q_lora_rank, cfg)
        self.q_b_proj = linear(cfg.q_lora_rank,
                                h * (self.nope + self.rope_dim), cfg)
        self.kv_a_proj_with_mqa = linear(hid, self.latent + self.rope_dim,
                                          cfg)
        self.kv_a_layernorm = _rms(self.latent, cfg)
        self.kv_b_proj = linear(self.latent,
                                 h * (self.nope + self.v_dim), cfg)
        self.o_proj = linear(h * self.v_dim, hid, cfg)

    def _rope(self, x, pos):
        return apply_op("rope_pairs", x, pos,
                        Tensor(jnp.asarray(self._inv_freq)),
                        attrs=dict(factor=float(self.rope_factor)))

    def forward(self, x, cache=None):
        """x is the layer's NORMED input."""
        from ..ops import manipulation
        from .generation import DecodeCache, update_and_attend_latent
        b, l, h = x.shape[0], x.shape[1], self.n_heads
        pos = cache.pos if isinstance(cache, DecodeCache) \
            else Tensor(jnp.zeros((), jnp.int32))
        with project_scope():
            c_q = self.q_a_layernorm(self.q_a_proj(x))
            q = manipulation.reshape(self.q_b_proj(c_q),
                                     [b, l, h, self.nope + self.rope_dim])
            q_nope = q[:, :, :, :self.nope]
            q_rope = self._rope(q[:, :, :, self.nope:], pos)
            kv = self.kv_a_proj_with_mqa(x)
            c_kv = self.kv_a_layernorm(kv[:, :, :self.latent])
            k_rope = self._rope(kv[:, :, self.latent:], pos)
        heads = dict(heads=h, nope=self.nope)
        new_cache = None
        if isinstance(cache, DecodeCache):
            parts = [c_kv, k_rope]
            pad = self.cache_row - self.latent - self.rope_dim
            if pad:
                parts.append(Tensor(jnp.zeros((b, l, pad),
                                              c_kv._value.dtype)))
            q_abs = apply_op("mla_absorb_q", q_nope, q_rope,
                             self.kv_b_proj.weight,
                             attrs=dict(heads, row=self.cache_row))
            out, new_cache = update_and_attend_latent(
                q_abs, manipulation.concat(parts, axis=-1), cache,
                d_v=self.latent, scale=self.scale)
            out = apply_op("mla_expand_v", out, self.kv_b_proj.weight,
                           attrs=heads)
        else:
            out = apply_op("mla_expanded_attention", q_nope, q_rope, c_kv,
                           k_rope, self.kv_b_proj.weight,
                           attrs=dict(heads, scale=self.scale))
        out = self.o_proj(manipulation.reshape(out,
                                               [b, l, h * self.v_dim]))
        return out, new_cache


class DeepseekV2MoE(nn.Layer):
    """Router over all `n_routed_experts`, the experts held here, and
    the shared experts (`laguna.py`: expert parallelism). `last_stats`:
    the routed op's counts of the latest call."""

    def __init__(self, cfg: DeepseekV2Config):
        super().__init__()
        h, f = cfg.hidden_size, cfg.moe_intermediate_size
        n = cfg.num_local_experts
        self.attrs = dict(
            top_k=cfg.num_experts_per_tok, scale=cfg.routed_scaling_factor,
            norm_topk=cfg.norm_topk_prob, first=cfg.ep_rank * n,
            n_group=cfg.n_group, topk_group=cfg.topk_group)
        init = NormalByExpert(0.0, cfg.initializer_range)
        self.router = linear(h, cfg.n_routed_experts, cfg)
        self.experts_gate = self.create_parameter(
            [n, h, f], dtype=cfg.dtype, default_initializer=init)
        self.experts_up = self.create_parameter(
            [n, h, f], dtype=cfg.dtype, default_initializer=init)
        self.experts_down = self.create_parameter(
            [n, f, h], dtype=cfg.dtype, default_initializer=init)
        self.shared_experts = SwiGLU(
            cfg, cfg.moe_intermediate_size * cfg.n_shared_experts)
        self.last_stats = None

    def forward(self, x, valid=None):
        if valid is None:
            valid = Tensor(jnp.ones(tuple(x.shape[:2]), bool))
        routed, self.last_stats = apply_op(
            "moe_routed_experts", x, valid, self.router.weight,
            self.experts_gate, self.experts_up, self.experts_down,
            attrs=self.attrs)
        return routed + self.shared_experts(x)


class DeepseekV2DecoderLayer(nn.Layer):
    def __init__(self, cfg: DeepseekV2Config, layer: int):
        super().__init__()
        self.input_layernorm = _rms(cfg.hidden_size, cfg)
        self.self_attn = DeepseekV2Attention(cfg)
        self.post_attention_layernorm = _rms(cfg.hidden_size, cfg)
        self.mlp = (SwiGLU(cfg, cfg.intermediate_size)
                    if layer < cfg.first_k_dense_replace
                    else DeepseekV2MoE(cfg))

    def forward(self, x, cache=None, valid=None):
        h, new_cache = self.self_attn(self.input_layernorm(x), cache=cache)
        x = x + h
        x = x + self.mlp(self.post_attention_layernorm(x), valid)
        return x, new_cache


class DeepseekV2Model(nn.Layer):
    def __init__(self, cfg: DeepseekV2Config):
        super().__init__()
        self.config = cfg
        self.embed_tokens = cast(nn.Embedding(
            cfg.vocab_size, cfg.hidden_size, weight_attr=nn.ParamAttr(
                initializer=Normal(0.0, cfg.initializer_range))), cfg)
        self.layers = nn.LayerList([DeepseekV2DecoderLayer(cfg, i)
                                    for i in range(cfg.num_hidden_layers)])
        self.norm = _rms(cfg.hidden_size, cfg)

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


class DeepseekV2ForCausalLM(nn.Layer):
    def __init__(self, cfg: DeepseekV2Config):
        super().__init__()
        self.model = DeepseekV2Model(cfg)
        self.lm_head = linear(cfg.hidden_size, cfg.vocab_size, cfg)
        self.config = cfg

    def forward(self, input_ids, caches=None, columns=None):
        if caches is not None:
            h, new_caches = self.model(input_ids, caches=caches)
            return self.lm_head(head_columns(h, columns)), new_caches
        return self.lm_head(head_columns(self.model(input_ids), columns))

    def _decode_cache_spec(self):
        """The five-entry form of `ServingEngine`'s cache-spec contract:
        (layers, 1, the cached row's width, no windows, "latent"):
        every layer caches one row a token, key and value of every head
        at once."""
        cfg = self.config
        return (cfg.num_hidden_layers, 1, cfg.cache_row,
                (None,) * cfg.num_hidden_layers, "latent")

    def _step_stats(self):
        return moe_stats(self.model.layers)

    STEP_STAT_COUNTERS = MOE_STEP_STAT_COUNTERS
