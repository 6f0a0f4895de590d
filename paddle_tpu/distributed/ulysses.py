"""Ulysses (all-to-all) sequence parallelism.

The second long-context mode next to ring attention (NEW capability vs
the reference — SURVEY.md §2.3 records SP as absent upstream; DeepSpeed-
Ulysses is the public recipe). Where ring attention keeps the sequence
sharded and rotates K/V blocks around the "sep" axis, Ulysses RESHARDS:
sequence-sharded activations all-to-all into head-sharded layout, each
device runs the full-sequence flash kernel on its local heads, and the
output all-to-alls back. Comm volume is O(B*L*D*H/n) per hop on ICI;
compute per device is the unmodified Pallas flash kernel.

Under GSPMD both all-to-alls are just the sharding boundary of a
shard_map whose in/out specs are head-sharded while the operands live
sequence-sharded — XLA emits the all-to-all pair.
"""
from __future__ import annotations

import math

import jax
from jax import shard_map
from jax.sharding import PartitionSpec, NamedSharding

__all__ = ["ulysses_attention", "ulysses_attention_sharded"]

_ulysses_ops: dict = {}


def ulysses_attention_sharded(q, k, v, mesh, axis_name="sep",
                              causal=False, scale=None):
    """jax-level entry: q/k/v are [B, L, H, D] global arrays, sequence
    dim sharded over `axis_name`. Returns [B, L, H, D] sequence-sharded.
    H must be divisible by the axis size."""
    from ..nn.functional.attention import _use_pallas, _sdpa_ref
    n_dev = mesh.shape[axis_name]
    if q.shape[2] % n_dev != 0:
        raise ValueError(
            f"ulysses needs heads ({q.shape[2]}) divisible by the "
            f"'{axis_name}' axis size ({n_dev}); use ring_attention")
    head_spec = PartitionSpec(None, None, axis_name, None)
    seq_spec = PartitionSpec(None, axis_name, None, None)

    def local(q, k, v):
        # full sequence, H/n local heads: the unmodified flash kernel on
        # TPU, the XLA reference elsewhere (same gating as SDPA)
        if _use_pallas(q.shape[1], q.shape[3]):
            from ..ops.pallas.flash_attention import flash_attention_blhd
            return flash_attention_blhd(q, k, v, causal=causal,
                                        scale=scale)
        s = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
        return _sdpa_ref(q, k, v, None, causal, s, 0.0, None)

    out = shard_map(local, mesh=mesh,
                    in_specs=(head_spec, head_spec, head_spec),
                    out_specs=head_spec, check_vma=False)(q, k, v)
    # back to the sequence-sharded layout the surrounding layers use
    return jax.lax.with_sharding_constraint(
        out, NamedSharding(mesh, seq_spec))


def ulysses_attention(query, key, value, causal=False, mesh=None,
                      axis_name="sep", scale=None):
    """Tensor-level API mirroring distributed.ring_attention: falls back
    to plain SDPA when no sequence axis is active; tape-registered
    (differentiable via jax.vjp of the whole resharded program)."""
    from ..core.tensor import apply_op
    from ..core.dispatch import OpDef
    from .mesh import get_mesh, shard_tensor
    pm = mesh or get_mesh()
    if pm is None or axis_name not in pm.dim_names \
            or pm.get_dim_size(axis_name) == 1:
        if scale is not None:
            # plain-SDPA fallback must honor the custom scale (parity
            # between single-device and sharded runs)
            return apply_op("sdpa", query, key, value,
                            attrs=dict(causal=bool(causal),
                                       scale=float(scale),
                                       dropout_p=0.0))
        from ..nn.functional.attention import scaled_dot_product_attention
        return scaled_dot_product_attention(query, key, value,
                                            is_causal=causal)
    jmesh = pm.jax_mesh
    seq_spec = PartitionSpec(None, axis_name, None, None)
    for t in (query, key, value):
        shard_tensor(t, pm, spec=seq_spec)
    key_ = (id(jmesh), axis_name, bool(causal),
            None if scale is None else float(scale))
    op = _ulysses_ops.get(key_)
    if op is None:
        if len(_ulysses_ops) > 8:
            # mesh-keyed closures pin dead meshes + compiled traces
            # across fleet re-inits; a tiny cache bound is enough
            _ulysses_ops.clear()
        def fwd(q, k, v, _m=jmesh, _ax=axis_name, _c=causal):
            return ulysses_attention_sharded(q, k, v, _m, _ax, _c,
                                             scale)
        op = OpDef(f"ulysses_attention::{axis_name}", fwd)
        _ulysses_ops[key_] = op
    return apply_op(op, query, key, value)
