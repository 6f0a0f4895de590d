"""Ring attention: context/sequence parallelism over a mesh axis.

NEW capability — the reference has none (verified: SURVEY.md §5
"Long-context / sequence parallelism: Absent"). Design per the ring
attention literature (see PAPERS.md): shard the sequence over the "sep"
mesh axis; each device holds a Q shard and streams K/V shards around the
ring with `ppermute`, accumulating online-softmax partial results, so
attention memory is O(L/n) per device and the K/V transfers overlap with
compute on ICI. The inner block kernel is the same math as the Pallas
flash kernel (paddle_tpu/ops/pallas/flash_attention.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec
from jax import shard_map

__all__ = ["ring_attention", "ring_attention_sharded"]

_NEG_INF = -1e30


def _block_attn(q, k, v, scale, mask):
    """One (q-shard, kv-shard) block: returns (o_partial, m, l) for the
    online-softmax merge. q: [B, Lq, H, D], k/v: [B, Lkv, H, D]."""
    s = jnp.einsum("blhd,bmhd->bhlm", q, k).astype(jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)              # [B,H,Lq,1]
    # all-masked rows: keep m finite so exp() stays well-defined
    m = jnp.maximum(m, -1e29)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhlm,bmhd->bhld", p.astype(v.dtype), v)
    return o.astype(jnp.float32), m, l


def _ring_body(axis_name, q, k, v, scale, causal, n_dev):
    """Runs on each device inside shard_map. q/k/v: local shards
    [B, L/n, H, D] (sequence-sharded)."""
    idx = jax.lax.axis_index(axis_name)
    b, lq, h, d = q.shape
    acc = jnp.zeros((b, h, lq, d), jnp.float32)
    m_run = jnp.full((b, h, lq, 1), _NEG_INF, jnp.float32)
    l_run = jnp.zeros((b, h, lq, 1), jnp.float32)
    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

    def step(carry, r):
        k_cur, v_cur, acc, m_run, l_run = carry
        # kv block r originated on device (idx - r) mod n
        src = (idx - r) % n_dev
        if causal:
            # query global position block = idx; key block = src.
            # full-block decisions + intra-block triangle when equal.
            q_pos = idx * lq + jax.lax.broadcasted_iota(
                jnp.int32, (lq, k_cur.shape[1]), 0)
            k_pos = src * k_cur.shape[1] + jax.lax.broadcasted_iota(
                jnp.int32, (lq, k_cur.shape[1]), 1)
            mask = (q_pos >= k_pos)[None, None]
        else:
            mask = None
        o_p, m_p, l_p = _block_attn(q, k_cur, v_cur, scale, mask)
        m_new = jnp.maximum(m_run, m_p)
        alpha = jnp.exp(m_run - m_new)
        beta = jnp.exp(m_p - m_new)
        acc = acc * alpha + o_p * beta
        l_new = l_run * alpha + l_p * beta
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (k_nxt, v_nxt, acc, m_new, l_new), None

    (k_f, v_f, acc, m_run, l_run), _ = jax.lax.scan(
        step, (k, v, acc, m_run, l_run), jnp.arange(n_dev))
    out = acc / jnp.maximum(l_run, 1e-30)
    return jnp.einsum("bhld->blhd", out).astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh, axis_name="sep", causal=False,
                           scale=None):
    """jax-level entry: q/k/v are [B, L, H, D] arrays (global view),
    sequence dim sharded over `axis_name`. Returns [B, L, H, D] with the
    same sharding. Call inside or outside jit."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    n_dev = mesh.shape[axis_name]
    spec = PartitionSpec(None, axis_name, None, None)
    body = functools.partial(_ring_body, axis_name, scale=scale,
                             causal=causal, n_dev=n_dev)

    def wrapped(q, k, v):
        return body(q, k, v)

    return shard_map(wrapped, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)


def ring_attention(query, key, value, causal=False, mesh=None,
                   axis_name="sep", scale=None):
    """Tensor-level API: context-parallel attention over the sequence
    axis. Registered on the tape (differentiable via jax.vjp of the whole
    ring program — recompute-style, like the reference's recompute pass)."""
    from ..core.tensor import apply_op
    from ..core.dispatch import OpDef
    from .mesh import get_mesh
    pm = mesh or get_mesh()
    if pm is None or axis_name not in pm.dim_names \
            or pm.get_dim_size(axis_name) == 1:
        # no sequence axis: plain flash/SDPA path
        from ..nn.functional.attention import scaled_dot_product_attention
        return scaled_dot_product_attention(query, key, value,
                                            is_causal=causal)
    jmesh = pm.jax_mesh
    # place inputs sequence-sharded on the mesh (rebinding is placement-
    # only: values unchanged, tape edges intact)
    from .mesh import shard_tensor
    seq_spec = PartitionSpec(None, axis_name, None, None)
    for t in (query, key, value):
        shard_tensor(t, pm, spec=seq_spec)
    key_ = (id(jmesh), axis_name, bool(causal),
            None if scale is None else float(scale))
    op = _ring_ops.get(key_)
    if op is None:
        if len(_ring_ops) > 8:  # bound mesh-pinning closure cache
            _ring_ops.clear()
        def fwd(q, k, v, _m=jmesh, _ax=axis_name, _c=causal):
            return ring_attention_sharded(q, k, v, _m, _ax, _c, scale)
        op = OpDef(f"ring_attention::{axis_name}", fwd)
        _ring_ops[key_] = op
    return apply_op(op, query, key, value)


_ring_ops: dict = {}
