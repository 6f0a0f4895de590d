"""Latent attention on the serving engine's paged pools (DeepSeek-V2's
multi-head latent attention, served in its absorbed form).

A layer of this kind caches, per token, ONE row: the normed key/value
latent and the rope part of the key (`latent` + `rope` values, padded
with zeros to whole tiles of 128 lanes). The row is key AND value for
every query head: each head's query has been carried through the key
up-projection (`nlp/deepseek_v2.py`), so all heads score against the
one cached row, and the softmax-weighted sum of the rows' first `d_v`
values goes through the value up-projection afterwards. That is
multi-query attention with a key of `latent + rope` values and a value
that is a slice of the key.

`mla_walk` (`ptk:mla_walk`) is flash-style attention of a step's live
query rows over their cached rows, causal, and reads the slot's PAGES
IN PLACE: the pool stays in HBM, the page table rides in as a
scalar-prefetch operand, and a grid step brings the pages of its key
block into VMEM by one DMA a page while the step before it computes
(`paged_attention._walk_paged`, the walker the head-carrying page walk
runs on too: this kernel asks it for whole key blocks of one pool).
Nothing gathers a row's `max_len` view, so a row pays for the pages
under its horizon only.

A query block's rows are ordered (query, head), which is the order the
step's `[B, l, H, D]` operand lies in: it is reshaped, never
transposed, on the way in and on the way out; all heads of a query
block are the rows of ONE matmul against the shared cached rows.

WORK ITEMS. The grid's first axis runs over the step's LIVE query
blocks only (`paged_attention._work_items`: a list of (row, query
block) pairs that rides in as scalar-prefetch operands, live ones
first, and `_live_query_blocks`, their count, as the axis' dynamic
bound); its second over the key blocks of the longest live context
(`_key_block_bound`, the rule of `paged_attention.walk_grid_bounds`).
A step of one prefill chunk beside fifteen decoding rows therefore has
no grid step for the query blocks that hold nothing.
Rows that decode (one live query) take the same kernel a second time
at a query block of ONE row: in a block of `Q_BLOCK` their matmuls
would be padding but for one query's heads.

Off-TPU (and not in interpret mode) `latent_attend` is the dense jnp
form over gathered views.
"""
from __future__ import annotations

import functools
import os

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kernel_id as _kernel_id, trace32 as _trace32
from .paged_attention import (_key_block_bound, _live_query_blocks, _prec,
                              _walk_paged, _walk_scratch, _work_items)

__all__ = ["latent_attend", "latent_attend_reference", "mla_walk",
           "gather_view", "count_latent_keys", "KERNELS", "LANES"]

_INTERPRET = os.environ.get("PADDLE_TPU_PALLAS_INTERPRET", "0") == "1"

KERNELS = {name: _kernel_id(name, fn) for name, fn in (
    ("mla_walk", "_mla_walk_kernel"),
)}

_NEG_INF = -1e30
# a cached row is whole tiles of this many values: Mosaic slices HBM by
# whole tiles, and the tiling pads a row to that in HBM whatever its
# shape says
LANES = 128
# query rows of one block where a row holds a chunk (times the heads:
# the rows of the block's matmuls); a decoding row's block is 1
Q_BLOCK = 8
# keys a grid step takes
K_BLOCK = 512
_VMEM_LIMIT = 96 * 1024 * 1024


def _use_kernel():
    return _INTERPRET or jax.devices()[0].platform == "tpu"


def _blocks(l, pool, page_table):
    """(query block, query blocks, padded l, key block, keys a row) for
    l query positions over rows of `page_table`'s pages of `pool`."""
    ps = pool.shape[1]
    n = page_table.shape[1] * ps
    qb = Q_BLOCK if l > 1 else 1
    nqb = -(-l // qb)
    kb = min(K_BLOCK, n)
    if n % kb or kb % ps:
        raise ValueError(f"a row of {n} keys in pages of {ps} does not "
                         f"tile by the key block {kb}: max_len must")
    return qb, nqb, nqb * qb, kb, n


def gather_view(pool, page_table):
    """pool [P, page_size, D] + page_table [B, max_pages] -> each row's
    keys in position order [B, max_pages * page_size, D] (the dense
    form's operand; the kernel never builds it)."""
    g = jnp.take(pool, page_table.astype(jnp.int32), axis=0)
    return g.reshape(g.shape[0], -1, pool.shape[-1])


def _pad_queries(x, l_pad):
    """x [B, l, ...] -> [B, l_pad, ...], zeros behind."""
    l = x.shape[1]
    if l_pad == l:
        return x
    return jnp.concatenate(
        [x, jnp.zeros((x.shape[0], l_pad - l) + x.shape[2:], x.dtype)],
        axis=1)


def _per_query(x, qb, heads):
    """x [qb, kb], one row a query -> [qb * heads, kb], each row once
    for every head of its query (the rows' order in a block)."""
    if qb == 1:
        return jnp.broadcast_to(x, (heads, x.shape[1]))
    return jnp.concatenate(
        [jnp.broadcast_to(x[i:i + 1], (heads, x.shape[1]))
         for i in range(qb)], axis=0)


def _mla_walk_kernel(ib_ref, it_ref, pos_ref, qlen_ref, pt_ref, q_ref,
                     pool_ref, o_ref, buf, sem, cnt, m_ref, l_ref, acc_ref,
                     *, qb, kb, heads, d_v, scale):
    i, k = pl.program_id(0), pl.program_id(1)
    b, t = ib_ref[i], it_ref[i]
    pos_b, qlen_b = pos_ref[b], qlen_ref[b]
    ppb = buf.shape[1]
    max_pages = pt_ref.shape[0] // pos_ref.shape[0]

    def item(j):
        """`_walk_paged`'s account of work item j: its key blocks whole
        (no first page), to the block that holds its last query's
        position."""
        bj, tj = ib_ref[j], it_ref[j]
        last_qi = jnp.minimum((tj + 1) * qb, qlen_ref[bj]) - 1
        return (bj * max_pages, tj * qb < qlen_ref[bj], None,
                (pos_ref[bj] + last_qi) // (kb // ppb))

    @pl.when(k == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, jnp.float32(_NEG_INF))
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def compute(slot, blk):
        kv = buf[slot].reshape(kb, buf.shape[3])        # [kb, d]
        q = q_ref[0]                                    # [qb * heads, d]
        prec = _prec(q.dtype)
        s = jax.lax.dot_general(
            q, kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=prec) * jnp.float32(scale)        # [qb * heads, kb]
        qi = t * qb + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 0)
        kpos = blk * kb + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 1)
        seen = (kpos <= pos_b + qi) & (qi < qlen_b)
        s = s + _per_query(jnp.where(seen, 0.0, jnp.float32(_NEG_INF)), qb,
                           heads)
        m_prev, l_prev = m_ref[:, :1], l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # a dead query of a live block keeps m at -1e30; its
        # exp(s - m) would be 1 for every masked key
        pexp = jnp.where(s > jnp.float32(_NEG_INF / 2),
                         jnp.exp(s - m_new), 0.0)
        l_ref[...] = jnp.broadcast_to(
            alpha * l_prev + jnp.sum(pexp, axis=1, keepdims=True),
            l_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            pexp.astype(kv.dtype), kv[:, :d_v],
            preferred_element_type=jnp.float32, precision=prec)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    _walk_paged(item, ppb, cnt, compute, pt_ref, (pool_ref,), (buf,), sem)

    @pl.when(k == pl.num_programs(1) - 1)
    def _store():
        l = jnp.maximum(l_ref[:, :1], jnp.float32(1e-30))
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def mla_walk(q, pool, page_table, pos, q_len, *, d_v, scale):
    """q [B, l, H, D] absorbed queries (D the cached row's width), pool
    [P, page_size, D] the layer's rows, page_table [B, max_pages],
    pos / q_len int32 [B] -> [B, l, H, d_v]: softmax(scale * q . row)
    over the rows at or below each query's position, times the rows'
    first d_v values, accumulated in float32. Dead queries come out
    zero."""
    b, l, h, d = q.shape
    ps = pool.shape[1]
    if d % LANES and not _INTERPRET:
        raise ValueError(
            f"rows of {d} values cannot be read a page at a time: Mosaic "
            f"slices HBM by whole tiles of {LANES} lanes (pad the row: "
            f"nlp/deepseek_v2.py `cache_row`)")
    qb, nqb, l_pad, kb, n = _blocks(l, pool, page_table)
    q3 = _pad_queries(q, l_pad).reshape(b, l_pad * h, d)
    rows = qb * h
    with _trace32():
        ib, it = _work_items(q_len, qb, nqb)
        n_items = _live_query_blocks(q_len, qb, nqb)
        n_kblk = _key_block_bound(pos, q_len, kb, n // kb)

        def r_idx(i, k, ib, it, pos, ql, pt):
            return (ib[i], it[i], 0)

        out = pl.pallas_call(
            functools.partial(_mla_walk_kernel, qb=qb, kb=kb, heads=h,
                              d_v=d_v, scale=scale),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=5,
                grid=(n_items, n_kblk),
                in_specs=[pl.BlockSpec((1, rows, d), r_idx),
                          pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec((1, rows, d_v), r_idx),
                scratch_shapes=_walk_scratch([pool], kb // ps) + [
                    pltpu.VMEM((rows, LANES), jnp.float32),
                    pltpu.VMEM((rows, LANES), jnp.float32),
                    pltpu.VMEM((rows, d_v), jnp.float32)]),
            out_shape=jax.ShapeDtypeStruct((b, l_pad * h, d_v), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=_INTERPRET,
            **KERNELS["mla_walk"],
        )(ib, it, pos, q_len, page_table.astype(jnp.int32).reshape(-1), q3,
          pool)
    out = out.reshape(b, l_pad, h, d_v)[:, :l]
    alive = jnp.arange(l, dtype=jnp.int32)[None, :] < q_len[:, None]
    return jnp.where(alive[:, :, None, None], out,
                     jnp.zeros((), out.dtype))


def latent_attend_reference(q, view, pos, q_len, *, d_v, scale):
    """The dense jnp form of `latent_attend` over each row's gathered
    view [B, N, D]: every score, a causal mask, a softmax in float32."""
    l, n = q.shape[1], view.shape[1]
    hi = jax.lax.Precision.HIGHEST
    f32 = jnp.float32
    s = jnp.einsum("blhd,bnd->blhn", q.astype(f32), view.astype(f32),
                   precision=hi) * scale
    kpos = jnp.arange(n, dtype=jnp.int32)[None, None, :]
    qpos = pos[:, None, None] + jnp.arange(l, dtype=jnp.int32)[None, :,
                                                              None]
    p = jax.nn.softmax(jnp.where((kpos <= qpos)[:, :, None, :], s,
                                 _NEG_INF), axis=-1)
    out = jnp.einsum("blhn,bnd->blhd", p, view[..., :d_v].astype(f32),
                     precision=hi)
    alive = jnp.arange(l, dtype=jnp.int32)[None, :] < q_len[:, None]
    return jnp.where(alive[:, :, None, None], out, 0.0).astype(q.dtype)


def latent_attend(q, pool, page_table, pos, q_len, *, d_v, scale):
    """Attention of the step's query rows over their cached rows (module
    doc). q [B, l, H, D] absorbed queries, pool [P, page_size, D],
    page_table [B, max_pages] the rows' pages, pos / q_len int32 [B] ->
    [B, l, H, d_v] in q's dtype."""
    kw = dict(d_v=int(d_v), scale=float(scale))
    pos = pos.astype(jnp.int32)
    q_len = q_len.astype(jnp.int32)
    if not _use_kernel():
        return latent_attend_reference(q, gather_view(pool, page_table),
                                       pos, q_len, **kw)
    if q.shape[1] == 1:
        return mla_walk(q, pool, page_table, pos, q_len, **kw)
    # rows that decode (one live query) apart, at a query block of one
    one = q_len == 1
    many = mla_walk(q, pool, page_table, pos, jnp.where(one, 0, q_len),
                    **kw)
    single = mla_walk(q[:, :1], pool, page_table, pos,
                      one.astype(jnp.int32), **kw)
    first = one[:, None] & (jnp.arange(q.shape[1]) == 0)[None, :]
    return jnp.where(first[:, :, None, None], single, many)


def count_latent_keys(pos, q_len):
    """Host-side (numpy) count over one latent layer's step: ((query,
    key) pairs, distinct keys, live query rows). Row b's query i stands
    at position pos + i and sees pos + i + 1 keys: the pairs are what
    the arithmetic is proportional to. A slot's queries share their
    keys, so what must be READ at least once is the pos + q_len keys
    its last query sees."""
    pos = np.asarray(pos, np.int64)
    q_len = np.asarray(q_len, np.int64)
    first, last = pos + 1, pos + q_len          # contexts of query 0..
    pairs = (first + last) * q_len // 2
    return (int(pairs.sum()), int(last[q_len > 0].sum()),
            int(q_len.sum()))
