"""Learned sparse attention on the serving engine's paged pools (the
DeepSeek-Sparse-Attention indexer, as Keye-VL-2.0's `sa_config` names
it: `nlp/keye_vl2.py`).

A layer of this kind caches, per token, a key and a value of `n_kv`
heads (the ordinary pools) AND one row for a small INDEXER (its key,
`index_head_dim` values, padded with zeros to whole tiles of 128
lanes). A query at position t scores every key position s <= t,

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])      (float32)

over the indexer's heads j, keeps the `topk` positions that score
highest (every position while t < topk; of equal scores the lower
position), and attends over those alone.

Three kernels a layer, each over the step's LIVE work only (work items
and dynamic grid bounds, as the page walk's: `paged_attention`):

- `sparse_index` (`ptk:sparse_index`): I for a row's live queries
  against its slot's cached indexer rows, the pages read in place
  (`_walk_paged`, one pool without a head axis, as `mla.py` reads its
  rows). Scores leave the kernel as ORDERED KEYS: the int32 whose
  signed order is the float32's (`ordered_key`), which is what the two
  others compare.
- `sparse_select` (`ptk:sparse_select`): for each live query the
  `topk`-th largest of its visible keys, EXACT and without a sort: 32
  counting passes decide the bits of the answer from the highest down
  (`tau`), and, where more keys equal `tau` than there is room for,
  the same over the bits of the last admitted position (`tie`). A
  query selects s iff key > tau, or key == tau and s <= tie.
- `sparse_walk` (`ptk:sparse_walk`): the page walk of
  `paged_attention._ragged_kernel` (K and V pages in place, a query
  block as wide as a kv head's matmul wants rows, decoding rows at a
  narrow block) over all visible keys, with every key the query did not
  select masked out of the softmax: in exact arithmetic the attention
  over the selected set.

Off-TPU (and not in interpret mode) `sparse_attend` is the three jnp
forms over gathered views, the selection by `jax.lax.top_k`: the forms
the kernels are tested against.
"""
from __future__ import annotations

import functools
import math
import os

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kernel_id as _kernel_id, trace32 as _trace32
from . import paged_attention as _pa
from .paged_attention import (_attend_block, _block_keys, _by_width,
                              _clear_values, _key_block_bound, _key_blocks,
                              _live_block, _live_query_blocks, _prec,
                              _query_blocks, _virgin, _walk_item,
                              _walk_paged, _walk_scratch, _work_items,
                              _zero_dead_queries)

__all__ = ["sparse_attend", "sparse_index", "sparse_select", "sparse_walk",
           "index_reference", "select_reference", "walk_reference",
           "ordered_key", "blocked", "count_sparse_work", "KERNELS",
           "LANES"]

_INTERPRET = os.environ.get("PADDLE_TPU_PALLAS_INTERPRET", "0") == "1"

KERNELS = {name: _kernel_id(name, fn) for name, fn in (
    ("sparse_index", "_sparse_index_kernel"),
    ("sparse_select", "_sparse_select_kernel"),
    ("sparse_walk", "_sparse_walk_kernel"),
)}

LANES = 128
_INT_MIN = -2 ** 31
# keys a grid step of the indexer takes, and a counting pass of the
# selection takes at a time
INDEX_K_BLOCK = 512
# queries of one selection item (a tile of sublanes), and the live
# queries up to which the indexer computes a row at that width
SELECT_ROWS = 8
_VMEM_LIMIT = 96 * 1024 * 1024


def _use_kernel():
    return _INTERPRET or jax.devices()[0].platform == "tpu"


def ordered_key(x):
    """float32 -> the int32 whose signed order is the float's, and back
    (-0.0 sorts below +0.0, a NaN nowhere sensible)."""
    def flip(bits):
        return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    if x.dtype == jnp.float32:
        return flip(jax.lax.bitcast_convert_type(x, jnp.int32))
    return jax.lax.bitcast_convert_type(flip(x), jnp.float32)


def blocked(keys, kb):
    """[B, l, N] -> [B, N / kb, l, kb], the layout the kernels hand the
    indexer's keys on in: a key block's scores are one tile-aligned
    block, written by one grid step of `sparse_index` and picked by its
    index, not cut out of a row, by the two that read them."""
    b, l, n = keys.shape
    return keys.reshape(b, l, n // kb, kb).transpose(0, 2, 1, 3)


def _key_block(pool, page_table):
    """(keys a grid step of the indexer takes, keys a row's table
    spans)."""
    ps = pool.shape[1]
    n = page_table.shape[1] * ps
    kb = min(INDEX_K_BLOCK, n)
    if n % kb or kb % ps:
        raise ValueError(f"a row of {n} keys in pages of {ps} does not "
                         f"tile by the key block {kb}: max_len must")
    return kb, n


def _pad_axis(x, axis, size):
    if x.shape[axis] == size:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, size - x.shape[axis])
    return jnp.pad(x, pad)


# -- the indexer ---------------------------------------------------------

def _sparse_index_kernel(ib_ref, it_ref, pos_ref, qlen_ref, pt_ref, q_ref,
                         w_ref, pool_ref, o_ref, buf, sem, cnt, *, kb,
                         heads):
    i = pl.program_id(0)
    b = ib_ref[i]
    ppb = buf.shape[1]
    ps = kb // ppb
    max_pages = pt_ref.shape[0] // pos_ref.shape[0]
    rows = o_ref.shape[2]

    def item(j):
        bj = ib_ref[j]
        last = jnp.maximum(pos_ref[bj] + qlen_ref[bj] - 1, 0)
        return bj * max_pages, qlen_ref[bj] > 0, None, last // ps

    def compute(slot, blk):
        kv = buf[slot].reshape(kb, buf.shape[3])            # [kb, row]
        prec = _prec(kv.dtype)

        def scores(n):
            acc = jnp.zeros((n, kb), jnp.float32)
            for j in range(heads):
                s = jax.lax.dot_general(
                    q_ref[0, j, :n, :], kv, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32, precision=prec)
                acc = acc + w_ref[0, :n, j:j + 1] * jnp.maximum(s, 0.0)
            o_ref[0, 0, :n, :] = ordered_key(acc)

        if rows <= SELECT_ROWS:
            scores(rows)
        else:
            few = qlen_ref[b] <= SELECT_ROWS
            pl.when(few)(lambda: scores(SELECT_ROWS))
            pl.when(jnp.logical_not(few))(lambda: scores(rows))

    _walk_paged(item, ppb, cnt, compute, pt_ref, (pool_ref,), (buf,), sem)


def sparse_index(q_idx, w_idx, pool, page_table, pos, q_len):
    """q_idx [B, l, Hi, R] the indexer's queries (R the cached row's
    width, zeros behind its values), w_idx [B, l, Hi] its head weights,
    pool [P, page_size, R] the layer's indexer rows, page_table
    [B, max_pages], pos / q_len int32 [B] -> int32 [B, N / kb, l8, kb]
    (`blocked`): `ordered_key(I)` of live query t of row b against
    position s, for every s in the key blocks at or below the row's
    last query (what lies past them, and a dead row's, is never
    written); l8 = l rounded up to `SELECT_ROWS`, N the keys a table
    spans, kb `INDEX_K_BLOCK` of them."""
    b, l, hi, r = q_idx.shape
    ps = pool.shape[1]
    if r % LANES and not _INTERPRET:
        raise ValueError(
            f"rows of {r} values cannot be read a page at a time: Mosaic "
            f"slices HBM by whole tiles of {LANES} lanes (pad the row)")
    kb, n = _key_block(pool, page_table)
    l8 = -(-l // SELECT_ROWS) * SELECT_ROWS
    q4 = _pad_axis(q_idx, 1, l8).transpose(0, 2, 1, 3)     # [B, Hi, l8, R]
    w3 = _pad_axis(_pad_axis(w_idx.astype(jnp.float32), 1, l8), 2, LANES)
    with _trace32():
        ib, it = _work_items(q_len, l8, 1)
        n_items = _live_query_blocks(q_len, l8, 1)
        n_kblk = _key_block_bound(pos, q_len, kb, n // kb)

        def row(i, k, ib, *_):
            return (ib[i], 0, 0, 0)

        def out_idx(i, k, ib, it, pos, ql, pt):
            # held at the row's last block where the step has nothing
            # to do: an unchanged block index, so nothing is written
            bi = ib[i]
            last = jnp.maximum(pos[bi] + ql[bi] - 1, 0) // kb
            return (bi, jnp.minimum(k, last), 0, 0)

        return pl.pallas_call(
            functools.partial(_sparse_index_kernel, kb=kb, heads=hi),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=5,
                grid=(n_items, n_kblk),
                in_specs=[pl.BlockSpec((1, hi, l8, r), row),
                          pl.BlockSpec((1, l8, LANES),
                                       lambda *a: row(*a)[:3]),
                          pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec((1, 1, l8, kb), out_idx),
                scratch_shapes=_walk_scratch([pool], kb // ps)),
            out_shape=jax.ShapeDtypeStruct((b, n // kb, l8, kb), jnp.int32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=_INTERPRET,
            **KERNELS["sparse_index"],
        )(ib, it, pos, q_len, page_table.astype(jnp.int32).reshape(-1), q4,
          w3, pool)


def index_reference(q_idx, w_idx, view):
    """The jnp form of `sparse_index` over each row's gathered view
    [B, N, R]: every score, in float32 -> int32 [B, l8, N]."""
    l = q_idx.shape[1]
    f32 = jnp.float32
    s = jnp.einsum("blhd,bnd->blhn", q_idx.astype(f32), view.astype(f32),
                   precision=jax.lax.Precision.HIGHEST)
    score = jnp.sum(w_idx.astype(f32)[..., None] * jnp.maximum(s, 0.0),
                    axis=2)
    return _pad_axis(ordered_key(score),
                     1, -(-l // SELECT_ROWS) * SELECT_ROWS)


# -- the selection -------------------------------------------------------

def _sparse_select_kernel(ib_ref, it_ref, pos_ref, qlen_ref, keys_ref,
                          tau_ref, tie_ref, *, topk, chunk, pos_bits):
    i = pl.program_id(0)
    b, t = ib_ref[i], it_ref[i]
    pos_b, qlen_b = pos_ref[b], qlen_ref[b]
    rows = keys_ref.shape[2]
    qi = t * rows + jax.lax.broadcasted_iota(jnp.int32, (rows, chunk), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, chunk), 1)
    # the highest position each query sees; none for a dead query
    limit = jnp.where(qi < qlen_b, pos_b + qi, -1)
    last = pos_b + jnp.minimum((t + 1) * rows, qlen_b) - 1
    n_chunks = last // chunk + 1

    def count(pred):
        """int32 [rows, 1]: each query's visible keys that `pred(keys,
        positions)` holds of."""
        def body(c, acc):
            kpos = c * chunk + lane
            hit = (kpos <= limit) & pred(keys_ref[0, c], kpos)
            return acc + jnp.where(hit, 1, 0)
        acc = jax.lax.fori_loop(0, n_chunks, body,
                                jnp.zeros((rows, chunk), jnp.int32))
        return jnp.sum(acc, axis=1, keepdims=True)

    # the topk-th largest key, bit by bit from the sign down: the
    # largest value that at least topk visible keys reach (INT_MIN, which
    # every key exceeds, where fewer than topk are visible)
    tau = jnp.where(count(lambda k, _: k >= 0) >= topk,
                    jnp.int32(0), jnp.int32(_INT_MIN))

    def bit(n, tau):
        cand = tau + jnp.left_shift(jnp.int32(1), jnp.int32(30) - n)
        return jnp.where(count(lambda k, _: k >= cand) >= topk, cand, tau)

    tau = jax.lax.fori_loop(0, 31, bit, tau)
    # of the keys that equal tau, the lowest positions fill what room the
    # larger keys leave: tie is the last of them, found the same way
    room = topk - count(lambda k, _: k > tau)

    def pbit(n, tie):
        cand = tie + jnp.left_shift(jnp.int32(1),
                                    jnp.int32(pos_bits - 1) - n)
        below = count(lambda k, kpos: (k == tau) & (kpos < cand))
        return jnp.where(below < room, cand, tie)

    tie = jax.lax.fori_loop(0, pos_bits, pbit,
                            jnp.zeros((rows, 1), jnp.int32))
    tau_ref[0] = jnp.broadcast_to(tau, (rows, LANES))
    tie_ref[0] = jnp.broadcast_to(tie, (rows, LANES))


def sparse_select(keys, pos, q_len, *, topk):
    """keys int32 [B, N / kb, l8, kb] (`sparse_index`), pos / q_len int32
    [B] -> (tau, tie) int32 [B, l8, 128], a value a query broadcast over
    the lanes: live query t of row b selects position s <= pos + t iff
    its key there > tau, or == tau and s <= tie; those are its `topk`
    largest visible keys, ties to the lower position (all of them where
    it sees no more than topk). A dead query's are never written."""
    b, n_blocks, l8, chunk = keys.shape
    n = n_blocks * chunk
    rows = SELECT_ROWS
    nqb = l8 // rows
    with _trace32():
        ib, it = _work_items(q_len, rows, nqb)
        n_items = _live_query_blocks(q_len, rows, nqb)

        def idx(i, ib, it, *_):
            return (ib[i], it[i], 0)

        def keys_idx(i, ib, it, *_):
            return (ib[i], 0, it[i], 0)

        out = jax.ShapeDtypeStruct((b, l8, LANES), jnp.int32)
        return pl.pallas_call(
            functools.partial(_sparse_select_kernel, topk=int(topk),
                              chunk=chunk,
                              pos_bits=max(1, int(n - 1).bit_length())),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4,
                grid=(n_items,),
                in_specs=[pl.BlockSpec((1, n_blocks, rows, chunk),
                                       keys_idx)],
                out_specs=[pl.BlockSpec((1, rows, LANES), idx)] * 2),
            out_shape=[out, out],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=_INTERPRET,
            **KERNELS["sparse_select"],
        )(ib, it, pos, q_len, keys)


def select_reference(keys, pos, q_len, *, topk):
    """The jnp form of `sparse_select`, by `jax.lax.top_k` (of equal
    keys the lower position first) over each query's visible keys."""
    b, l8, n = keys.shape
    k = min(int(topk), n)
    kpos = jnp.arange(n, dtype=jnp.int32)[None, None, :]
    qi = jnp.arange(l8, dtype=jnp.int32)[None, :, None]
    seen = kpos <= pos[:, None, None] + qi
    top, at = jax.lax.top_k(jnp.where(seen, keys, _INT_MIN), k)
    full = jnp.sum(seen, axis=-1) >= topk
    tau = jnp.where(full, top[..., -1], _INT_MIN)
    tie = jnp.where(full, jnp.max(
        jnp.where(top == tau[..., None], at, -1), axis=-1), n)
    return tuple(jnp.broadcast_to(x[..., None].astype(jnp.int32),
                                  (b, l8, LANES)) for x in (tau, tie))


def _selected(keys, tau, tie, kpos):
    """bool: the (query, key) pairs the selection keeps; keys [.., q, k],
    tau / tie [.., q, 1], kpos broadcastable key positions."""
    return (keys > tau) | ((keys == tau) & (kpos <= tie))


# -- attention over the selected keys ------------------------------------

def _sparse_walk_kernel(ib_ref, it_ref, pos_ref, qlen_ref, pt_ref, q_ref,
                        k_pool, v_pool, keys_ref, tau_ref, tie_ref, o_ref,
                        k_buf, v_buf, sem, cnt, m_ref, l_ref, acc_ref, *,
                        ps, ppb, qblk, rep, scale):
    """`paged_attention._ragged_kernel` without its lanes (groups, a
    user mask, the quantised pools, a window), and with each query's
    selection folded into the block's live mask."""
    pre = (ib_ref, it_ref, pos_ref, qlen_ref, pt_ref)
    pools, bufs = (k_pool, v_pool), (k_buf, v_buf)
    parts = (m_ref, l_ref, acc_ref)
    i, k = pl.program_id(0), pl.program_id(1)
    max_pages = pt_ref.shape[0] // pos_ref.shape[0]
    kb = ppb * ps
    rows = q_ref.shape[3]

    def item(j):
        b, _, live, lo, hi = _walk_item(j, pre, ps=ps, qblk=qblk,
                                        grouped=False, window=None)
        return b * max_pages, live, lo, hi

    b, t = ib_ref[i], it_ref[i]
    pos_b, qlen_b = pos_ref[b], qlen_ref[b]
    by_width = functools.partial(_by_width, qlen_b - t * qblk, rep, rows)

    @pl.when(k == 0)
    def _init():
        by_width(lambda n: _virgin(*(p.at[:, :n] for p in parts)))

    def compute(slot, blk):
        kpos = blk * kb + jax.lax.broadcasted_iota(jnp.int32, (qblk, kb), 1)
        chosen = _selected(keys_ref[0, 0], tau_ref[0, :, :1],
                           tie_ref[0, :, :1], kpos)
        chosen = jnp.where(chosen, 1.0, 0.0).astype(jnp.bfloat16)

        def attend(n):
            # a query's choice once for each head of the group, in the
            # rows' (query, head) order: by a 0/1 matmul, which is exact
            if rep & (rep - 1):
                shape = (-(-n // rep), rep, qblk)
                of = jax.lax.broadcasted_iota(jnp.int32, shape, 0) \
                    .reshape(shape[0] * rep, qblk)[:n]
            else:
                of = jnp.right_shift(
                    jax.lax.broadcasted_iota(jnp.int32, (n, qblk), 0),
                    rep.bit_length() - 1)
            pick = of == jax.lax.broadcasted_iota(jnp.int32, (n, qblk), 1)
            mine = jnp.dot(jnp.where(pick, 1.0, 0.0).astype(jnp.bfloat16),
                           chosen, preferred_element_type=jnp.float32,
                           precision=jax.lax.Precision.DEFAULT) > 0.5
            live = _live_block(t * qblk, blk * kb, pos_b, qlen_b, n=n,
                               rep=rep, kb=kb) & mine
            _attend_block(q_ref[0, 0, :, :n], *_block_keys(pools, bufs, slot),
                          None, None, live, None,
                          *(p.at[:, :n] for p in parts), scale=scale,
                          fp8=False)

        by_width(attend)

    _clear_values(v_buf)
    _walk_paged(item, ppb, cnt, compute, pt_ref, pools, bufs, sem)

    @pl.when(k == pl.num_programs(1) - 1)
    def _finalize():
        def store(n):
            l = jnp.maximum(l_ref[:, :n, :1], jnp.float32(1e-30))
            o_ref[0, 0, :, :n] = (acc_ref[:, :n] / l).astype(o_ref.dtype)

        by_width(store)


def sparse_walk(q, k_pool, v_pool, page_table, pos, q_len, keys, tau, tie):
    """q [B, l, H, D]; pools [P, page_size, H_kv, D]; keys (`blocked`) /
    tau / tie as `sparse_index` and `sparse_select` give them ->
    [B, l, H, D]:
    softmax(q . k / sqrt(D)) over each live query's SELECTED positions
    at or below its own, times v, in float32. Dead queries come out
    zero. A query block that is fully masked at a key block leaves its
    partials as they were but for a weight of exp(-1e30 - m) = 0."""
    b, lq, h, d = q.shape
    _, ps, hkv, _ = k_pool.shape
    if d % LANES and not _INTERPRET:
        raise ValueError(f"heads of {d} values: the pools are read a page "
                         f"at a time, in whole tiles of {LANES} lanes")
    mp = page_table.shape[1]
    rep = h // hkv
    qblk, nqb = _query_blocks(lq, rep)
    ppb, n_blocks = _key_blocks(ps, mp)
    kb = ppb * ps
    lq_pad = nqb * qblk
    kbi = keys.shape[3]
    if lq % SELECT_ROWS or kbi % kb:
        raise ValueError(
            f"{lq} query positions a row and key blocks of {kbi} scored / "
            f"{kb} walked: the step's width must be a multiple of "
            f"{SELECT_ROWS} and the indexer's key block of the walk's")
    keys = _pad_axis(keys, 2, lq_pad)
    tau, tie = _pad_axis(tau, 1, lq_pad), _pad_axis(tie, 1, lq_pad)
    rows = -(-qblk * rep // _pa._NARROW_ROWS) * _pa._NARROW_ROWS
    q5 = jnp.pad(_pad_axis(q, 1, lq_pad)
                 .reshape(b, nqb, qblk, hkv, rep, d)
                 .transpose(0, 1, 3, 2, 4, 5)
                 .reshape(b, nqb, hkv, qblk * rep, d),
                 ((0, 0),) * 3 + ((0, rows - qblk * rep), (0, 0)))
    item = functools.partial(_walk_item, ps=ps, qblk=qblk, grouped=False,
                             window=None)

    def q_map(i, k, ib, it, *_):
        return (ib[i], it[i], 0, 0, 0)

    def keys_idx(i, k, *pre):
        row, t, _, lo, hi = item(i, pre)
        blk = jnp.minimum(lo // ppb + k, hi // ppb)
        return (row, blk // (kbi // kb), t, blk % (kbi // kb))

    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    q_spec = pl.BlockSpec((1, 1, hkv, rows, d), q_map)
    per_query = pl.BlockSpec((1, qblk, LANES), lambda *a: q_map(*a)[:3])
    with _trace32():
        ib, it = _work_items(q_len, qblk, nqb)
        n_items = _live_query_blocks(q_len, qblk, nqb)
        n_kblk = _key_block_bound(pos, q_len, kb, n_blocks)
        out = pl.pallas_call(
            functools.partial(_sparse_walk_kernel, ps=ps, ppb=ppb,
                              qblk=qblk, rep=rep, scale=1.0 / math.sqrt(d)),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=5,
                grid=(n_items, n_kblk),
                in_specs=[q_spec, hbm, hbm,
                          pl.BlockSpec((1, 1, qblk, kb), keys_idx),
                          per_query, per_query],
                out_specs=q_spec,
                scratch_shapes=_walk_scratch([k_pool, v_pool], ppb) + [
                    pltpu.VMEM((hkv, rows, w), jnp.float32)
                    for w in (LANES, LANES, d)]),
            out_shape=jax.ShapeDtypeStruct(q5.shape, q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=_INTERPRET,
            **KERNELS["sparse_walk"],
        )(ib, it, pos, q_len, page_table.astype(jnp.int32).reshape(-1), q5,
          k_pool, v_pool, keys, tau, tie)
    out = out[:, :, :, :qblk * rep].reshape(b, nqb, hkv, qblk, rep, d) \
        .transpose(0, 1, 3, 2, 4, 5).reshape(b, lq_pad, h, d)[:, :lq]
    return _zero_dead_queries(out, q_len)


def walk_reference(q, k_view, v_view, pos, q_len, keys, tau, tie):
    """The jnp form of `sparse_walk` over each row's gathered views
    [B, N, H_kv, D]: every score, the causal mask and the selection, a
    softmax in float32."""
    b, l, h, d = q.shape
    n, hkv = k_view.shape[1], k_view.shape[2]
    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    q5 = q.astype(f32).reshape(b, l, hkv, h // hkv, d)
    s = jnp.einsum("blgrd,bngd->blgrn", q5, k_view.astype(f32),
                   precision=hi) / math.sqrt(d)
    kpos = jnp.arange(n, dtype=jnp.int32)[None, None, :]
    qpos = pos[:, None, None] + jnp.arange(l, dtype=jnp.int32)[None, :, None]
    keep = (kpos <= qpos) & _selected(keys[:, :l, :n], tau[:, :l, :1],
                                      tie[:, :l, :1], kpos)
    p = jax.nn.softmax(jnp.where(keep[:, :, None, None, :], s, -1e30), -1)
    out = jnp.einsum("blgrn,bngd->blgrd", p, v_view.astype(f32),
                     precision=hi).reshape(b, l, h, d)
    return _zero_dead_queries(out, q_len).astype(q.dtype)


# -- the op --------------------------------------------------------------

def _view(pool, page_table):
    """Each row's pages in position order [B, N, ...]: the jnp forms'
    operand (`paged_attention._row_view`; the kernels never build it)."""
    return _pa._row_view(pool, page_table.astype(jnp.int32),
                         page_table.shape[1] * pool.shape[1])


@functools.partial(jax.jit, static_argnames=("topk", "traced_for"))
def _sparse_attend_local(q, q_idx, w_idx, k_pool, v_pool, idx_pool,
                         page_table, pos, q_len, *, topk, traced_for):
    """The three kernels, one program inside the step's: a model's
    layers call it with the same shapes, so it is traced and lowered
    once a step program (as `_ragged_attention_local`)."""
    del traced_for
    keys = sparse_index(q_idx, w_idx, idx_pool, page_table, pos, q_len)
    tau, tie = sparse_select(keys, pos, q_len, topk=topk)
    return sparse_walk(q, k_pool, v_pool, page_table, pos, q_len, keys, tau,
                       tie)


def sparse_attend(q, q_idx, w_idx, k_pool, v_pool, idx_pool, page_table,
                  pos, q_len, *, topk):
    """Attention of the step's query rows over the `topk` cached
    positions their indexer picks (module doc). q [B, l, H, D]; q_idx
    [B, l, Hi, R], w_idx [B, l, Hi] the indexer's queries and head
    weights; k_pool / v_pool [P, page_size, H_kv, D] and idx_pool
    [P, page_size, R] the layer's pools, the step's new rows already
    written; page_table [B, max_pages]; pos / q_len int32 [B] ->
    [B, l, H, D] in q's dtype."""
    pos = pos.astype(jnp.int32)
    q_len = q_len.astype(jnp.int32)
    if _use_kernel():
        return _sparse_attend_local(
            q, q_idx, w_idx, k_pool, v_pool, idx_pool, page_table, pos,
            q_len, topk=int(topk),
            traced_for=(_pa.K_BLOCK, _pa._Q_ROWS, INDEX_K_BLOCK, _INTERPRET))
    keys = index_reference(q_idx, w_idx, _view(idx_pool, page_table))
    tau, tie = select_reference(keys, pos, q_len, topk=int(topk))
    return walk_reference(q, _view(k_pool, page_table),
                          _view(v_pool, page_table), pos, q_len, keys, tau,
                          tie)


def count_sparse_work(pos, q_len, topk):
    """Host-side (numpy) count over one sparse layer's step: (visible
    (query, key) pairs: what the indexer scores; selected pairs: what
    the attention weighs; the distinct keys any form of the attention
    must read, min(context, live queries x topk) a slot; the indexer
    keys any form must read, a slot's context once; live query rows)."""
    pos = np.asarray(pos, np.int64)
    q_len = np.asarray(q_len, np.int64)
    first, last = pos + 1, pos + q_len          # contexts of query 0..
    visible = (first + last) * q_len // 2
    # sum over i < q_len of min(pos + 1 + i, topk)
    under = np.clip(topk - pos, 0, q_len)       # queries that see <= topk
    selected = (2 * pos + under + 1) * under // 2 + (q_len - under) * topk
    context = np.where(q_len > 0, last, 0)
    return (int(visible.sum()), int(selected.sum()),
            int(np.minimum(context, q_len * topk).sum()),
            int(context.sum()), int(q_len.sum()))
