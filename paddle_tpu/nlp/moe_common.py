"""What the decoders with routed experts share (`laguna.py`,
`deepseek_v2.py`, `keye_vl2.py`, `mimo_v2.py`): sublayers built in the
configuration's dtype, the SwiGLU that is a dense MLP or a shared
expert, the stacked experts' initialiser, the routed op, and the counts
an expert layer hands to `ServingEngine` out of its step. A `cfg` here
is any such model's configuration: it has `dtype`, `initializer_range`, `hidden_size`.
"""
import jax
import jax.numpy as jnp

from .. import nn
from ..nn import functional as F
from ..core.tensor import Tensor
from ..core.dispatch import register_op
from ..ops.pallas.moe import routed_experts
from ..nn.initializer import Normal


def _routed_experts_fwd(x, valid, router_w, w_gate, w_up, w_down,
                        bias=None, *, top_k, scale, norm_topk, first,
                        n_group=1, topk_group=1, scoring="softmax"):
    b, l, h = x.shape
    out, stats = routed_experts(
        x.reshape(b * l, h), valid.reshape(b * l), router_w, w_gate,
        w_up, w_down, top_k=top_k, scale=scale, norm_topk=norm_topk,
        first=first, n_group=n_group, topk_group=topk_group,
        scoring=scoring, bias=bias)
    return out.reshape(b, l, h), stats


register_op("moe_routed_experts", _routed_experts_fwd, nondiff=True)


class NormalByExpert(Normal):
    """Normal(0, std) over [experts, ...], drawn a block of experts at
    a time: the base class samples in float32 and casts, which for one
    layer's 128 experts in bfloat16 is 3 GB of temporaries beside
    11 GB of weights. Each block is waited for: dispatch is
    asynchronous, and a queue of float32 blocks not yet cast peaked
    4.3 GB above the weights (my chip run, PR 29)."""
    BLOCK = 16

    def _generate(self, shape, np_dtype, key):
        keys = jax.random.split(key, -(-shape[0] // self.BLOCK))
        return jnp.concatenate([
            jax.block_until_ready(Normal._generate(
                self, (min(self.BLOCK, shape[0] - i * self.BLOCK),)
                + tuple(shape[1:]), np_dtype, k))
            for i, k in enumerate(keys)])


def cast(layer, cfg):
    """`layer` in the configuration's dtype, as soon as it exists."""
    if cfg.dtype is not None:
        layer.to(dtype=cfg.dtype)
    return layer


def linear(in_f, out_f, cfg):
    return cast(nn.Linear(in_f, out_f, weight_attr=nn.ParamAttr(
        initializer=Normal(0.0, cfg.initializer_range)), bias_attr=False),
        cfg)


class SwiGLU(nn.Layer):
    """SwiGLU of a given width: a dense layer's MLP, or the shared
    expert(s)."""

    def __init__(self, cfg, width: int):
        super().__init__()
        self.gate_proj = linear(cfg.hidden_size, width, cfg)
        self.up_proj = linear(cfg.hidden_size, width, cfg)
        self.down_proj = linear(width, cfg.hidden_size, cfg)

    def forward(self, x, valid=None):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


def valid_columns(width, caches):
    """bool [B, width] Tensor, or None outside the unified step: the
    step's rows are padded to one width, and a column at or past the
    row's q_len is no token, and is routed to no expert."""
    if caches is None or caches[0].q_len is None:
        return None
    return Tensor(jnp.arange(width, dtype=jnp.int32)[None, :]
                  < caches[0].q_len._value[:, None])


def moe_stats(layers):
    """int32 [4] Tensor over the expert layers of the latest call (the
    layers whose `mlp` holds `last_stats`): assignments routed (all
    experts), assignments computed here, local experts that received a
    token, (with a selection bias, [5]: the assignments the bias moved,)
    expert layers run. None without such a layer."""
    stats = [layer.mlp.last_stats for layer in layers
             if getattr(layer.mlp, "last_stats", None) is not None]
    if not stats:
        return None
    total = stats[0]._value
    for s in stats[1:]:
        total = total + s._value
    return Tensor(jnp.concatenate(
        [total, jnp.full((1,), len(stats), jnp.int32)]))


# the names `ServingEngine` files a model's `_step_stats()` under
MOE_STEP_STAT_COUNTERS = ("moe_assignments_total",
                          "moe_assignments_here_total",
                          "moe_experts_hit_total",
                          "moe_layer_steps_total")
