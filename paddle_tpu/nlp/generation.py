"""Compiled autoregressive generation with a static in-place KV cache.

TPU-native replacement for the reference's inference workhorse — the
fused decoder layer with in-place KV cache
(/root/reference/paddle/fluid/operators/fused/fused_multi_transformer_op.cu)
plus PaddleNLP's Python GenerationMixin decode loop. The reference runs
one CUDA megakernel per layer per token from an eager Python loop; here
the ENTIRE generation — prefill and the token loop — is ONE XLA program:

- The KV cache is a static max-length buffer per layer, written in place
  with `lax.dynamic_update_slice` (XLA aliases the buffer across loop
  iterations, so the update is a true in-place write on device).
- The token loop is a `lax.while_loop` that early-exits as soon as every
  row has emitted `eos_token_id` — no per-token host round trip, no
  recompile, static shapes throughout.
- Sampling (greedy / temperature / top-k) runs on device with threefry
  keys split inside the loop.

Attention over the static cache masks positions `> pos + i` (a windowed
causal mask), which makes prefill and decode the same code path: prefill
is a length-L write at pos 0, decode a length-1 write at pos L+i.
"""
from __future__ import annotations

import os
import threading

import numpy as np
import jax
import jax.numpy as jnp

from ..core.dispatch import register_op
from ..core.tensor import Tensor
from ..core import dtype as dtypes
from ..ops._helpers import apply_op, as_tensor
from ..ops.pallas import kernel_mesh
from ..ops.pallas.mla import latent_attend
from ..ops.pallas.sparse import sparse_attend
from ..ops.pallas.paged_attention import (dequantize_paged_q8,
                                          gqa_attend_reference,
                                          paged_decode_attention,
                                          ragged_paged_attention,
                                          ragged_paged_attention_q8,
                                          ragged_paged_attention_grouped,
                                          ragged_paged_attention_grouped_q8,
                                          ragged_paged_attention_split,
                                          FP8_DTYPE,
                                          quantize_kv_rowwise,
                                          paged_scatter,
                                          paged_scatter_q8,
                                          lora_delta,
                                          lora_delta_paged,
                                          megakernel_decode,
                                          megakernel_decode_q8,
                                          decode_greedy_argmax,
                                          spec_verify_accept)

__all__ = ["DecodeCache", "init_decode_caches", "update_and_attend",
           "update_and_attend_latent", "update_and_attend_sparse",
           "update_and_attend_split",
           "CompiledGenerator", "decode_model_step", "head_columns",
           "sample_logits",
           "resolve_paged_attn_impl", "PAGED_ATTN_IMPLS",
           "quantize_kv_rowwise"]

PAGED_ATTN_IMPLS = ("kernel", "gather")


def resolve_paged_attn_impl(override=None):
    """Which implementation the paged l==1 decode branch uses:
    "kernel" (default) — the Pallas ragged paged-attention kernel that
    walks the page table and streams only live pages (pure-JAX
    reference off-TPU); "gather" — the original `paged_kv_gather` +
    dense SDPA path, kept so bit-equivalence can always be
    cross-checked. An explicit override wins; otherwise the
    PADDLE_TPU_PAGED_ATTN env var (read at TRACE time — a compiled
    serving step keeps the impl it was built with)."""
    impl = override or os.environ.get("PADDLE_TPU_PAGED_ATTN", "kernel")
    if impl not in PAGED_ATTN_IMPLS:
        raise ValueError(
            f"paged attention impl must be one of {PAGED_ATTN_IMPLS} "
            f"(PADDLE_TPU_PAGED_ATTN / attn_impl), got {impl!r}")
    return impl


class DecodeCache:
    """Static max-length KV cache for one attention layer.

    k/v: [B, max_len, n_kv_heads, head_dim] Tensors; pos: scalar int32
    Tensor — the number of valid positions already written. Unlike the
    eager `MultiHeadAttention.Cache` (which grows by concat and forces a
    recompile per step), the buffers here never change shape.

    Paged mode (serving): when `page_table` is set, k/v are SHARED pools
    [num_pages, page_size, n_kv_heads, head_dim] and `page_table` is
    [B, max_pages] int32 — row b's logical position p lives in
    pool[page_table[b, p // page_size], p % page_size]. `pos` is the
    per-row position vector [B]. Page 0 is reserved as a trash page:
    rows of retired/free slots point every entry at it, and writes past
    a row's allocated pages are redirected there, so one fixed-shape
    program serves any mix of live/free rows (Ragged Paged Attention,
    PAPERS.md).
    """

    __slots__ = ("k", "v", "pos", "k_scale", "v_scale", "fresh",
                 "page_table", "attn_impl", "q_len", "group",
                 "out_shard", "lora", "lora_paged", "megakernel", "rows")

    def __init__(self, k, v, pos, k_scale=None, v_scale=None,
                 fresh=False, page_table=None, attn_impl=None,
                 q_len=None, group=None, out_shard=None, lora=None,
                 lora_paged=None, megakernel=False, rows=None):
        self.k = k
        self.v = v
        # a layer of the SPARSE kind (the engine's cache-spec contract):
        # the pool of its indexer's rows [num_pages, page_size, row],
        # one a token, beside k and v and under the same page table
        self.rows = rows
        self.pos = pos
        # paged mode: [B, max_pages] int32 page ids into the k/v pools
        self.page_table = page_table
        # paged decode impl override ("kernel"/"gather"); None defers
        # to PADDLE_TPU_PAGED_ATTN (see resolve_paged_attn_impl)
        self.attn_impl = attn_impl
        # ragged paged mode (the serving engine's UNIFIED step): per-row
        # valid query count [B] int32 — row b's tokens occupy positions
        # pos[b] .. pos[b] + q_len[b] - 1 of a width-l padded batch;
        # queries past q_len are dead padding. None = every row uses
        # the full width l (the classic prefill/decode shapes).
        self.q_len = q_len
        # prefix-sharing groups (the serving engine's grouped walk): a
        # (group_id, group_leader,
        # group_cnt) triple of [B] int32 Tensors declaring which rows
        # share a physical-page prefix — pure HBM-traffic hint, None =
        # the per-row walk
        self.group = group
        # tensor-parallel serving (ServingEngine(mesh=...)): a
        # jax.sharding.NamedSharding the ATTENTION OUTPUT is
        # constrained to before it leaves update_and_attend. With the
        # KV pools and QKV projections sharded over the mesh's "mp"
        # axis (kv-head / head dim), every upstream op is either
        # replicated or head-sharded compute with NO cross-shard
        # reduction; this one constraint makes GSPMD materialize the
        # single bit-exact output ALL-GATHER per layer (never a
        # partial-sum all-reduce, which would reassociate the fp math
        # and break the mp=1 token-identity oracle). None = no
        # constraint (single-device serving, the default).
        self.out_shard = out_shard
        # int8 cache modes, told apart by the scale SHAPE:
        # - dense (page_table None): k/v hold int8 codes laid out
        #   [B, H_kv, max_len, D]; *_scale are per-head [H_kv] f32
        #   CONSTANTS from calibration (layout + constant scales are
        #   what let XLA fuse the dequant — see _kv_update_q8_fwd);
        # - paged (page_table set): k/v are int8 CODE POOLS
        #   [num_pages, page_size, H_kv, D] and *_scale are rowwise
        #   SCALE POOLS [num_pages, page_size, H_kv] f32 — one scale
        #   per (position, kv head), written at scatter time
        #   (quantize_kv_rowwise; no calibration pass), so a page and
        #   its scales always travel together (COW/swap/prefix share).
        self.k_scale = k_scale
        self.v_scale = v_scale
        # multi-tenant LoRA serving (serving/adapters.py): this
        # layer's PER-ROW gathered low-rank weights — a 9-tuple of
        # Tensors (Aq [B, h, R], Bq [B, R, Hq*D], Ak, Bk, Av, Bv
        # [B, ..., H_kv*D], Ao [B, Hq*D, R], Bo [B, R, h],
        # scale [B]) the attention module fuses into its q/k/v/o
        # projections via the `lora_delta` op. None (the default) =
        # no adapter path traced at all — the base engine's program
        # is unchanged. Rows at page 0 / scale 0 (base model, idle)
        # see an exactly-zero delta.
        self.lora = lora
        # megakernel LoRA operands (PADDLE_TPU_MEGAKERNEL + adapters):
        # this layer's FULL paged adapter pools plus the per-row page
        # ids/scales — a 10-tuple of Tensors (Aq [P, h, R],
        # Bq [P, R, Hq*D], Ak, Bk, Av, Bv [P, ..., H_kv*D],
        # Ao [P, Hq*D, R], Bo [P, R, h], apage [B] int32,
        # ascale [B] f32). Unlike `lora` (per-row pairs gathered
        # in-trace by XLA), the gather happens INSIDE the fused op:
        # the megakernel's q/k/v prologue streams row b's page once,
        # and the o-delta goes through the standalone
        # `lora_delta_paged` op. Mutually exclusive with `lora`.
        self.lora_paged = lora_paged
        # decode megakernel gate (PADDLE_TPU_MEGAKERNEL, default off):
        # routes the unified ragged step through the fused
        # megakernel_decode[_q8] op — scatter(+quantize) + attend (+
        # LoRA prologue) in ONE dispatch — instead of the op-pair
        # path below. Requires q_len (unified mode), impl "kernel",
        # and no user mask; identical outputs by construction.
        self.megakernel = megakernel
        # True only on caches straight out of init_decode_caches (pos
        # is provably 0 even when it traces as a jit constant): the
        # int8 multi-token prefill guard keys on this
        self.fresh = fresh


def _kv_update_fwd(buf, upd, pos):
    p = pos.astype(jnp.int32)
    if p.ndim == 1:
        # per-row positions (continuous batching): each batch row writes
        # its own offset — a batched dynamic-update-slice, which keeps
        # the serving decode step ONE fixed-shape program while every
        # slot sits at a different sequence position
        z = jnp.zeros((), jnp.int32)

        def row(b, u, q):
            return jax.lax.dynamic_update_slice(
                b, u.astype(b.dtype), (q,) + (z,) * (b.ndim - 1))

        return jax.vmap(row)(buf, upd, p)
    z = jnp.zeros((), jnp.int32)
    starts = [z, p.reshape(())] + [z] * (buf.ndim - 2)
    return jax.lax.dynamic_update_slice(buf, upd.astype(buf.dtype),
                                        starts)


register_op("kv_cache_update", _kv_update_fwd)


# Per-row batched LoRA delta (multi-tenant adapter serving): the
# shared expression body lives in pallas/paged_attention.lora_delta —
# the megakernel's fused LoRA prologue composes the SAME floats, which
# is what keeps gate-on/gate-off serving bit-identical on CPU.
register_op("lora_delta", lora_delta)


# Paged KV scatter: fwd is pallas/paged_attention.paged_scatter (the
# shared address math + trash-page redirect the megakernel's Pallas
# write stage prefetches) — see its docstring for the slot map.
register_op("kv_cache_update_paged", paged_scatter, nondiff=True)


def _paged_gather_fwd(pool, page_table):
    """Gather each row's pages into its contiguous logical view:
    pool [P, page_size, H, D] + page_table [B, max_pages] ->
    [B, max_pages * page_size, H, D] — the same layout the dense cache
    holds, so the existing window_causal_mask + SDPA path attends over
    it unchanged. Rows of the view belonging to unallocated entries
    show trash-page contents; the additive -1e30 mask at positions
    >= pos hides them exactly (trash is finite, never NaN: pools are
    zero-init and only ever written with real K/V)."""
    g = jnp.take(pool, page_table.astype(jnp.int32), axis=0)
    if jnp.dtype(pool.dtype) == jnp.dtype(FP8_DTYPE):
        # fp8 KV lane (PADDLE_TPU_KV_DTYPE=fp8): the gather IS the
        # dequant — a pure convert, no scale pages exist — so chunked
        # prefill and the gather A/B impl attend over f32 as usual
        g = g.astype(jnp.float32)
    b, m, ps = g.shape[0], g.shape[1], g.shape[2]
    return g.reshape((b, m * ps) + pool.shape[2:])


register_op("paged_kv_gather", _paged_gather_fwd, nondiff=True)


# Quantize-then-scatter in ONE jitted program (int8 branch of
# `kv_cache_update_paged`): fwd is
# pallas/paged_attention.paged_scatter_q8 — quantize_kv_rowwise (also
# re-exported here; tests and decode_roofline import it from this
# module) + the shared scatter address math. The megakernel's q8
# write stage fuses the SAME quantization expressions into its Pallas
# pass, so both pipelines commit bit-identical (codes, scales).
register_op("kv_cache_update_paged_q8", paged_scatter_q8,
            nondiff=True)

# Dequantizing multi-token gather over the int8 pool: codes + rowwise
# scales -> the dense f32 logical view (the layout paged_kv_gather
# yields), so chunked prefill and the gather A/B impl attend over the
# int8 cache through the unchanged window-mask + SDPA path. The fwd is
# pallas/paged_attention.dequantize_paged_q8 — the SAME elementwise
# dequant the q8 ragged reference uses, which is what keeps the kernel
# lane and this gather path bit-identical on CPU.
register_op("paged_kv_gather_q8", dequantize_paged_q8, nondiff=True)

# Pallas ragged paged-attention decode: reads KV pages in place (walks
# the page table, streams only pages below ceil((pos+1)/page_size)) —
# no [B, max_pages * page_size, H, D] gather materialized. Off-TPU the
# fwd runs the pure-JAX reference, so CPU tier-1 tests exercise the op.
register_op("paged_decode_attention", paged_decode_attention,
            nondiff=True)

# Ragged generalization: per-row query lengths, so ONE kernel/step
# serves a mixed batch — decode rows (q_len == 1) next to mid-prefill
# rows (q_len == chunk) — over the same paged pool. The serving
# engine's unified step attends through this op; off-TPU the fwd runs
# the pure-JAX ragged reference.
register_op("ragged_paged_attention", ragged_paged_attention,
            nondiff=True)

# int8 lane of the ragged kernel: code pages + rowwise scale pages
# stream into VMEM together, dequant fused into the online-softmax
# loop — decode's dominant HBM stream at half the bytes. Off-TPU the
# fwd runs the q8 reference (dequantize_paged_q8 + the fp reference's
# ragged mask math), bit-identical to the quantized-gather path.
register_op("ragged_paged_attention_q8", ragged_paged_attention_q8,
            nondiff=True)

# Prefix-sharing-aware grouped walk: rows whose page tables share a
# physical-page prefix declare it via (group_id, group_leader,
# group_cnt) scalar operands and the TPU kernel streams each shared
# page from HBM once per GROUP (two-phase walk) instead of once per
# row — the dominant shared-prefix decode traffic drops ~Nx. Output
# identical to the ungrouped op; off-TPU the fwd IS the ungrouped
# reference, so the grouped/flat engine A/B stays bit-token-identical
# on CPU by construction. The q8 variant moves code + scale pages
# through the same grouped stream.
register_op("ragged_paged_attention_grouped",
            ragged_paged_attention_grouped, nondiff=True)
register_op("ragged_paged_attention_grouped_q8",
            ragged_paged_attention_grouped_q8, nondiff=True)

# ---- decode megakernel ops (PADDLE_TPU_MEGAKERNEL, default off) ----
# One registered op per attention layer replaces the unfused
# scatter(+quantize) -> attend op pair (and, with adapters, the three
# per-projection lora_delta dispatches): LoRA prologue + KV write +
# the unchanged ragged/grouped walk in one dispatch. Off-TPU each
# stage IS the unfused ops' shared forward (paged_scatter[_q8],
# lora_delta, the ragged references), so gate-on CPU serving stays
# bit-identical to gate-off — the oracle tests/test_megakernel.py
# pins. The q8 variant also returns the updated rowwise scale pools.
register_op("megakernel_decode", megakernel_decode, nondiff=True)
register_op("megakernel_decode_q8", megakernel_decode_q8,
            nondiff=True)

# Paged LoRA delta with the page gather INSIDE the op (the
# megakernel's fused adapter stream, also used standalone for the
# o-projection and for rope models whose deltas can't ride the
# attend): full pools + per-row page ids/scales in, delta out.
register_op("lora_delta_paged", lora_delta_paged, nondiff=True)

# Sampling/acceptance epilogues over the logits tile (megakernel
# mode): greedy argmax (Pallas on-tile reduction on TPU/interpret,
# jnp.argmax off-TPU — bit-identical first-max tie-breaking) and the
# fused spec-decode acceptance (the unified step's exact expressions;
# grammar bias masks are additive operand data added upstream).
register_op("decode_greedy_argmax", decode_greedy_argmax,
            nondiff=True)
register_op("spec_verify_accept", spec_verify_accept, nondiff=True)


# Grouped-query decode attention: attends q [B, l, H, D] over the full
# K/V buffers [B, lmax, H_kv, D] WITHOUT repeat_interleave — queries
# group per kv head, so the H -> H_kv fold of the cache is never
# copied, and the per-group unroll keeps the output bit-identical to
# the old repeated path (see gqa_attend_reference).
register_op("gqa_decode_attend", gqa_attend_reference, nondiff=True)


def _kv_update_q8_fwd(buf, upd, pos, scale):
    """Quantize upd [B, l, H, D] with the per-head CONSTANT scales [H]
    and write it into the int8 [B, H, max_len, D] cache at pos.

    Design (measured, scripts/decode_roofline.py probes 9-11): the int8
    cache halves the decode step's dominant HBM stream, but XLA only
    fuses the dequant into the attention reads when (a) the cache is
    laid out [B, H, L, D] and (b) the scale is a constant broadcast —
    per-position runtime scales force a materialized dequantized copy
    and LOSE 2x. Calibrated per-(layer, head) constants give
    1.76 -> 1.32 ms/step on GPT-124M bs16. Reference analogue: the
    int8 KV of fused_multi_transformer_int8_op.cu (also static scales).
    """
    z = jnp.zeros((), jnp.int32)
    p = pos.astype(jnp.int32)
    u = upd.astype(jnp.float32).transpose(0, 2, 1, 3)  # [B,H,l,D]
    q = jnp.clip(jnp.round(u / scale[None, :, None, None]),
                 -127, 127).astype(jnp.int8)
    if p.ndim == 1:
        # per-row positions (continuous batching over the int8 cache):
        # each row quantizes with the same constant scales and writes
        # at its own offset — the rowwise analogue of the float-cache
        # vmap'd dynamic-update-slice above
        def row(b, u8, q_):
            return jax.lax.dynamic_update_slice(b, u8, (z, q_, z))

        return jax.vmap(row)(buf, q, p)
    return jax.lax.dynamic_update_slice(buf, q, (z, z, p.reshape(()), z))


register_op("kv_cache_update_q8", _kv_update_q8_fwd, nondiff=True)


def _kv8_attend_fwd(q, k8, v8, kscale, vscale, mask):
    """Decode attention over the int8 [B, H_kv, L, D] cache: dequant
    (convert * constant scale) fuses into the einsum operand reads.
    q: [B, l, H, D]; mask: additive f32 [1, 1, l, L]; GQA handled by
    grouping query heads over the kv heads."""
    b, l, h, d = q.shape
    hkv = k8.shape[1]
    rep = h // hkv
    if mask.dtype == jnp.bool_:
        mask = jnp.where(mask, jnp.float32(0.0), jnp.float32(-1e30))
    qf = q.transpose(0, 2, 1, 3).astype(jnp.float32) \
        .reshape(b, hkv, rep * l, d)
    kf = k8.astype(jnp.float32) * kscale[None, :, None, None]
    s = jnp.einsum("bgqd,bgkd->bgqk", qf, kf) / np.sqrt(d)
    s = s.reshape(b, h, l, -1) + mask
    a = jax.nn.softmax(s, axis=-1).reshape(b, hkv, rep * l, -1)
    vf = v8.astype(jnp.float32) * vscale[None, :, None, None]
    o = jnp.einsum("bgqk,bgkd->bgqd", a, vf)
    return o.reshape(b, h, l, d).transpose(0, 2, 1, 3).astype(q.dtype)


register_op("kv8_attend", _kv8_attend_fwd, nondiff=True)


def _window_mask_fwd(pos, l, lmax, window=None):
    """Bool mask: key j visible to query i iff j <= pos + i (causal
    within the valid window of a static cache) and, in a layer with a
    sliding `window`, j > pos + i - window. Scalar pos ->
    [1, 1, l, lmax]; per-row pos vector [B] -> [B, 1, l, lmax]."""
    p = pos.astype(jnp.int32)
    i = jnp.arange(l, dtype=jnp.int32)[:, None]
    j = jnp.arange(lmax, dtype=jnp.int32)[None, :]
    if p.ndim == 1:
        q_pos = i[None] + p[:, None, None]
        j = j[None]
        axis = 1
    else:
        q_pos = i + p
        axis = (0, 1)
    live = j <= q_pos
    if window is not None:
        live = live & (j > q_pos - window)
    return jnp.expand_dims(live, axis)


register_op("window_causal_mask", _window_mask_fwd, nondiff=True)


def init_decode_caches(n_layers, batch_size, max_len, n_kv_heads,
                       head_dim, dtype=None, kv_scales=None):
    """Fresh zeroed caches (list of DecodeCache, one per layer).

    kv_scales: per-layer [(k_scale [H_kv], v_scale [H_kv])] float
    arrays -> build the int8 cache (codes laid out [B, H_kv, L, D],
    scales baked as constants; see _kv_update_q8_fwd for why)."""
    if dtype is None:
        dtype = dtypes.get_default_dtype().np_dtype
    caches = []
    for li in range(n_layers):
        if kv_scales is not None:
            ks, vs = kv_scales[li]
            k = Tensor(jnp.zeros(
                (batch_size, n_kv_heads, max_len, head_dim), jnp.int8),
                stop_gradient=True)
            v = Tensor(jnp.zeros(
                (batch_size, n_kv_heads, max_len, head_dim), jnp.int8),
                stop_gradient=True)
            caches.append(DecodeCache(
                k, v, Tensor(jnp.zeros((), jnp.int32),
                             stop_gradient=True),
                Tensor(jnp.asarray(ks, jnp.float32),
                       stop_gradient=True),
                Tensor(jnp.asarray(vs, jnp.float32),
                       stop_gradient=True), fresh=True))
            continue
        k = Tensor(jnp.zeros((batch_size, max_len, n_kv_heads, head_dim),
                             dtype), stop_gradient=True)
        v = Tensor(jnp.zeros((batch_size, max_len, n_kv_heads, head_dim),
                             dtype), stop_gradient=True)
        caches.append(DecodeCache(k, v, Tensor(jnp.zeros((), jnp.int32),
                                               stop_gradient=True)))
    return caches


def _merge_mask_fwd(window, user):
    """window bool [1,1,l,lmax] + user mask (bool or additive float,
    broadcastable, last dim == lmax) -> additive f32 mask."""
    add = jnp.where(window, jnp.float32(0.0), jnp.float32(-1e30))
    if user.dtype == jnp.bool_:
        add = add + jnp.where(user, jnp.float32(0.0),
                              jnp.float32(-1e30))
    else:
        add = add + user.astype(jnp.float32)
    return add


register_op("decode_merge_mask", _merge_mask_fwd, nondiff=True)


def _tp_gather_out(out, cache):
    """Tensor-parallel serving: constrain the attention output to the
    cache's `out_shard` (normally: replicated over the engine mesh).
    With pools/projections sharded over kv-heads, the output is the
    ONE tensor still head-sharded here — the constraint is where GSPMD
    inserts the single bit-exact per-layer all-gather. No-op (and zero
    cost) without a mesh."""
    if cache.out_shard is None:
        return out
    return Tensor(jax.lax.with_sharding_constraint(out._value,
                                                   cache.out_shard))


def update_and_attend(q, k_new, v_new, cache: DecodeCache,
                      dropout_p=0.0, training=False, attn_mask=None,
                      lora_x=None, window=None):
    """Write k_new/v_new at cache.pos, attend q over the valid prefix.

    q: [B, l, H, D]; k_new/v_new: [B, l, H_kv, D] (GQA repeat handled
    here when H > H_kv). attn_mask (optional): user padding/attention
    mask over the CACHE axis (last dim must equal the cache max_len);
    combined with the window-causal validity mask. Returns
    (out [B, l, H, D], advanced cache).

    Dispatch matrix: dense fp, dense int8 (calibrated per-head
    constant scales; single-token / fresh-prefill only), paged fp
    (scatter + ragged kernel or gather), and paged int8 — rowwise
    code+scale pools, quantize-then-scatter, reads through the ragged
    kernel's fused-dequant q8 lane (impl "kernel") or the
    dequantizing gather (impl "gather" / multi-token chunked
    prefill). The paged int8 mode has none of the dense int8 mode's
    write-pattern limits.

    lora_x (optional, megakernel mode): the attention input hidden
    states [B, l, h] — with `cache.lora_paged` set, the fused op
    computes the per-row q/k/v LoRA deltas from it inside the kernel
    (q/k_new/v_new then carry the BASE projections only; the caller
    handles the o-delta via `lora_delta_paged`). Ignored otherwise.

    window (optional, static): the calling LAYER's sliding window, the
    query's own position included — query at position t then attends
    keys t - window < j <= t only. Served by the unified ragged walk
    (which starts at the window's first page) and by the masked read
    over a float cache, dense or paged; the int8 lanes, the megakernel
    and the single-token decode kernel have no window and refuse one.
    """
    from ..nn import functional as F
    from ..ops import manipulation
    quant = cache.k_scale is not None
    paged = cache.page_table is not None
    l = int(q.shape[1])
    if window is not None and (quant or cache.megakernel or (
            paged and cache.q_len is None and l == 1
            and resolve_paged_attn_impl(cache.attn_impl) == "kernel")):
        raise NotImplementedError(
            "a sliding-window layer is served by the unified ragged "
            "step or the gather fallback over a float cache; the int8 "
            "lanes, the megakernel and the single-token decode kernel "
            "take no window")
    win_attr = {} if window is None else {"window": int(window)}
    if (paged and cache.megakernel and cache.q_len is not None
            and attn_mask is None
            and resolve_paged_attn_impl(cache.attn_impl) == "kernel"):
        # DECODE MEGAKERNEL (PADDLE_TPU_MEGAKERNEL): the layer's whole
        # KV path — optional fused LoRA prologue, (quantize-then-)
        # scatter of the new K/V, and the ragged/grouped walk — as ONE
        # registered op instead of the 2-op (or, with adapters, 5-op)
        # soup below. Same shared forwards, same floats; see the op
        # registrations above.
        grouped = cache.group is not None
        lora = cache.lora_paged is not None and lora_x is not None
        rest = []
        if grouped:
            rest.extend(cache.group)
        if lora:
            aq, bq, ak, bk, av, bv = cache.lora_paged[:6]
            apage, ascale = cache.lora_paged[8], cache.lora_paged[9]
            rest.extend([lora_x, aq, bq, ak, bk, av, bv, apage,
                         ascale])
        attrs = dict(grouped=grouped, lora=lora)
        if quant:
            out, k_buf, v_buf, k_sc, v_sc = apply_op(
                "megakernel_decode_q8", q, k_new, v_new, cache.k,
                cache.v, cache.k_scale, cache.v_scale,
                cache.page_table, cache.pos, cache.q_len, *rest,
                attrs=attrs)
        else:
            out, k_buf, v_buf = apply_op(
                "megakernel_decode", q, k_new, v_new, cache.k,
                cache.v, cache.page_table, cache.pos, cache.q_len,
                *rest, attrs=attrs)
            k_sc = v_sc = None
        out = _tp_gather_out(out, cache)
        return out, DecodeCache(k_buf, v_buf, cache.pos + cache.q_len,
                                k_sc, v_sc,
                                page_table=cache.page_table,
                                attn_impl=cache.attn_impl,
                                q_len=cache.q_len, group=cache.group,
                                out_shard=cache.out_shard,
                                lora_paged=cache.lora_paged,
                                megakernel=True)
    k_sc = v_sc = None
    if quant and paged:
        # int8 PAGED pool: rowwise scale pools ride in k_scale/v_scale
        # — quantize-then-scatter in one program, dequantizing
        # gather / fused-dequant kernel on the read side (the dispatch
        # below). The dense calibrated mode's per-head constants make
        # no sense against a shared pool: reject the mix loudly.
        if getattr(cache.k_scale._value, "ndim", 0) != 3:
            raise ValueError(
                "int8 paged KV pool needs rowwise scale pools "
                "[num_pages, page_size, n_kv_heads] in "
                "k_scale/v_scale, one scale per (position, kv head); "
                "got the dense cache's calibrated per-head constants "
                "— the dense int8 mode and the paged pool cannot mix "
                "(build pools via ServingEngine(kv_dtype='int8'))")
        k_buf, k_sc = apply_op("kv_cache_update_paged_q8", cache.k,
                               cache.k_scale, k_new, cache.pos,
                               cache.page_table)
        v_buf, v_sc = apply_op("kv_cache_update_paged_q8", cache.v,
                               cache.v_scale, v_new, cache.pos,
                               cache.page_table)
    elif quant:
        if getattr(cache.pos._value, "ndim", 0) == 1 and l != 1:
            raise NotImplementedError(
                "int8 KV cache: per-row position vectors support "
                "single-token (decode) writes only; multi-token "
                "chunks need the dequantized read path — use the "
                "bf16/f32 cache (or the int8 PAGED pool, which "
                "dequantizes multi-token reads) for chunked prefill")
        k_buf = apply_op("kv_cache_update_q8", cache.k, k_new,
                         cache.pos, cache.k_scale)
        v_buf = apply_op("kv_cache_update_q8", cache.v, v_new,
                         cache.pos, cache.v_scale)
    elif paged:
        k_buf = apply_op("kv_cache_update_paged", cache.k, k_new,
                         cache.pos, cache.page_table)
        v_buf = apply_op("kv_cache_update_paged", cache.v, v_new,
                         cache.pos, cache.page_table)
    else:
        k_buf = apply_op("kv_cache_update", cache.k, k_new, cache.pos)
        v_buf = apply_op("kv_cache_update", cache.v, v_new, cache.pos)
    if paged:
        # logical view length: every row sees max_pages full pages
        lmax = int(cache.page_table.shape[1]) * int(cache.k.shape[1])
    else:
        lmax = k_buf.shape[2] if quant else k_buf.shape[1]
    user_m = None
    if attn_mask is not None:
        m = as_tensor(attn_mask)
        if int(m.shape[-1]) != int(lmax):
            if paged:
                raise ValueError(
                    f"decode attn_mask last dim {m.shape[-1]} does not "
                    f"match the PAGED cache's logical view: page_table "
                    f"width {cache.page_table.shape[1]} pages x "
                    f"page_size {cache.k.shape[1]} = {lmax} slots. A "
                    "mask sized for the dense max_len must be padded "
                    "to the page-aligned width (padding positions are "
                    "hidden by the positional window anyway)")
            raise ValueError(
                f"decode attn_mask last dim {m.shape[-1]} must equal "
                f"the cache max_len {lmax} (mask indexes cache slots)")
        while m.ndim < 4:
            m = manipulation.unsqueeze(m, axis=0)
        user_m = m
    if paged and l == 1 and cache.q_len is None and \
            resolve_paged_attn_impl(cache.attn_impl) == "kernel":
        # Pallas ragged paged-attention: walks page_table[b, :] and
        # streams only live pages (flash-style online softmax across
        # page blocks, GQA grouped in-kernel) — the dense logical view
        # is never materialized and the user mask composes in-kernel.
        # The int8 pool rides the ragged kernel's q8 lane at q_len 1
        # (identical attend window: query 0 sees keys j <= pos).
        if quant:
            ones = Tensor(jnp.ones((int(q.shape[0]),), jnp.int32))
            args = [q, k_buf, v_buf, k_sc, v_sc, cache.page_table,
                    cache.pos, ones]
            if user_m is not None:
                args.append(user_m)
            out = _tp_gather_out(
                apply_op("ragged_paged_attention_q8", *args), cache)
            return out, DecodeCache(k_buf, v_buf, cache.pos + l,
                                    k_sc, v_sc,
                                    page_table=cache.page_table,
                                    attn_impl=cache.attn_impl)
        args = [q, k_buf, v_buf, cache.page_table, cache.pos]
        if user_m is not None:
            args.append(user_m)
        out = _tp_gather_out(
            apply_op("paged_decode_attention", *args), cache)
        return out, DecodeCache(k_buf, v_buf, cache.pos + l,
                                page_table=cache.page_table,
                                attn_impl=cache.attn_impl)
    if paged and cache.q_len is not None and \
            resolve_paged_attn_impl(cache.attn_impl) == "kernel":
        # UNIFIED ragged step (per-row q_len over a width-l padded
        # batch): one kernel invocation serves decode rows (q_len 1)
        # and mid-prefill rows (q_len up to l) together — query i of
        # row b attends keys j <= pos[b] + i, dead queries past q_len
        # are masked in-kernel (outputs unspecified, the engine drops
        # them). The int8 pool takes the q8 lane: code + scale pages
        # stream together, dequant fused into the softmax loop. With
        # prefix-sharing groups attached (cache.group — the engine's
        # grouped walk) the grouped op streams each shared page once
        # per group; same output, less HBM.
        grouped = cache.group is not None
        if quant:
            args = [q, k_buf, v_buf, k_sc, v_sc, cache.page_table,
                    cache.pos, cache.q_len]
            op = ("ragged_paged_attention_grouped_q8" if grouped
                  else "ragged_paged_attention_q8")
        else:
            args = [q, k_buf, v_buf, cache.page_table, cache.pos,
                    cache.q_len]
            op = ("ragged_paged_attention_grouped" if grouped
                  else "ragged_paged_attention")
        if grouped:
            args.extend(cache.group)
        if user_m is not None:
            args.append(user_m)
        # (the grouped and int8 walks take no window, which was refused
        # above or by the engine)
        attrs = win_attr if op == "ragged_paged_attention" else None
        out = _tp_gather_out(
            apply_op(op, *args, attrs=attrs or None), cache)
        return out, DecodeCache(k_buf, v_buf, cache.pos + cache.q_len,
                                k_sc, v_sc,
                                page_table=cache.page_table,
                                attn_impl=cache.attn_impl,
                                q_len=cache.q_len, group=cache.group)
    mask = apply_op("window_causal_mask", cache.pos,
                    attrs=dict(l=int(l), lmax=int(lmax), **win_attr))
    if user_m is not None:
        mask = apply_op("decode_merge_mask", mask, user_m)
    if quant and paged:
        # int8 paged READ path — multi-token chunked prefill and the
        # "gather" A/B impl: dequantize the rows' code+scale pages
        # into the dense f32 logical view (paged_kv_gather_q8, the
        # same elementwise dequant the q8 kernel reference fuses
        # in-VMEM) and attend through the unchanged window-mask path.
        # Ragged rows (q_len set, gather impl) ride the same window
        # mask: dead queries past q_len produce unspecified outputs
        # the engine drops, exactly like the fp gather path.
        kf = apply_op("paged_kv_gather_q8", k_buf, k_sc,
                      cache.page_table)
        vf = apply_op("paged_kv_gather_q8", v_buf, v_sc,
                      cache.page_table)
        new_cache = DecodeCache(k_buf, v_buf, cache.pos + l,
                                k_sc, v_sc,
                                page_table=cache.page_table,
                                attn_impl=cache.attn_impl,
                                q_len=cache.q_len)
    elif quant and l == 1:
        # decode step over the int8 cache: the dequant (convert x
        # constant per-head scale) fuses into the attention reads
        # (decode_roofline probes 9-11)
        out = apply_op("kv8_attend", q, k_buf, v_buf,
                       cache.k_scale, cache.v_scale, mask)
        return out, DecodeCache(k_buf, v_buf, cache.pos + l,
                                cache.k_scale, cache.v_scale)
    elif quant:
        # multi-token PREFILL on the DENSE int8 cache: attend over the
        # raw float K/V of this chunk. Routing prefill through the
        # int8 cache read makes XLA lower the l x L einsum over
        # dequantized operands as a serial wide-while loop (measured
        # 46 GB accessed per generate). Attending only the chunk is
        # exact ONLY when the cache holds nothing yet — reject chunked
        # prefill rather than silently dropping cached context. (The
        # PAGED int8 pool has no such limit: its dequantizing gather
        # branch above serves any multi-token read.)
        if not (cache.fresh or _is_zero_pos(cache.pos)):
            raise NotImplementedError(
                "dense int8 KV cache: multi-token writes are only "
                "supported at pos==0 (single prefill). Chunked "
                "prefill / multi-token continuation needs the "
                "dequantized read path — use the bf16 cache or the "
                "int8 PAGED pool for that call pattern.")
        kf, vf = k_new, v_new
        # first l cache slots ARE this chunk: slice the merged mask
        mask = mask[:, :, :, :l]
        new_cache = DecodeCache(k_buf, v_buf, cache.pos + l,
                                cache.k_scale, cache.v_scale)
    elif paged:
        # attend over the row's pages gathered into the dense logical
        # layout; the window mask (and trash-page rule, see the paged
        # ops above) makes this bit-identical to the dense-cache read
        kf = apply_op("paged_kv_gather", k_buf, cache.page_table)
        vf = apply_op("paged_kv_gather", v_buf, cache.page_table)
        new_cache = DecodeCache(k_buf, v_buf, cache.pos + l,
                                page_table=cache.page_table,
                                attn_impl=cache.attn_impl)
    else:
        kf, vf = k_buf, v_buf
        new_cache = DecodeCache(k_buf, v_buf, cache.pos + l)
    n_rep = q.shape[2] // kf.shape[2]
    if n_rep > 1 and l == 1 and dropout_p == 0.0 and not training:
        # decode-step GQA without materializing the cache H -> H_kv
        # fold: queries grouped per kv head (bit-compatible with the
        # repeat_interleave path — tests/test_paged_attention.py)
        out = _tp_gather_out(
            apply_op("gqa_decode_attend", q, kf, vf, mask), cache)
        return out, new_cache
    if n_rep > 1:
        kf = manipulation.repeat_interleave(kf, n_rep, axis=2)
        vf = manipulation.repeat_interleave(vf, n_rep, axis=2)
    out = F.scaled_dot_product_attention(
        q, kf, vf, attn_mask=mask, dropout_p=dropout_p, is_causal=False,
        training=training)
    return _tp_gather_out(out, cache), new_cache


def _is_zero_pos(pos):
    """True iff the cache position is provably 0 (a concrete zero).
    Inside the compiled generator the prefill pos is the concrete
    jnp.zeros(()) from init_decode_caches, so this stays decidable
    under trace; a data-dependent pos is treated as non-zero."""
    v = pos._value
    if isinstance(v, jax.core.Tracer):
        return False
    return int(np.asarray(v)) == 0


# Tracing an engine program swaps TRACERS into the model's tensors (the
# weights are the program's first argument) until the trace ends.
# Replicas may share one model, each tracing from its own pump thread,
# and the solo `CompiledGenerator` reads the same tensors: this one
# process-wide lock makes every swap -> restore window exclusive, so
# none of them ever reads, or restores, another's tracers. Compiled
# steps never take it. (Running the model EAGERLY from another thread
# while an engine compiles is not covered.)
_STATE_SWAP_LOCK = threading.RLock()


def _swap_state(tensors, vals):
    """Trace-time only: bind `vals` into `tensors`, returning the
    originals. Takes _STATE_SWAP_LOCK, which `_restore_state` (the
    caller's `finally`) gives back."""
    _STATE_SWAP_LOCK.acquire()
    originals = [t._value for t in tensors]
    for t, v in zip(tensors, vals):
        t._value = v
    return originals


def _restore_state(tensors, originals):
    for t, v in zip(tensors, originals):
        t._value = v
    _STATE_SWAP_LOCK.release()


class _StepProgram:
    """One jitted engine program whose leading operand is the
    engine's weight list. Callers pass the remaining operands; `lower`
    and `_cache_size` (the retrace probes' view) go to the one
    underlying `jax.jit`. `donate` names the caller's operands (0 is
    the first after the weights) whose buffers the program may write
    its outputs into: the caller hands them over, must not read them
    again, and takes back what the program returns in their place."""

    def __init__(self, fn, state_vals, mesh=None, donate=()):
        self._jit = jax.jit(fn,
                            donate_argnums=tuple(1 + i for i in donate))
        self._state_vals = state_vals
        # the tensor-parallel replica's device mesh: named while the
        # program is traced, so its Pallas kernels run per device
        self._mesh = mesh

    def __call__(self, *args):
        with kernel_mesh(self._mesh, "mp"):
            return self._jit(self._state_vals, *args)

    def lower(self, *args):
        with kernel_mesh(self._mesh, "mp"):
            return self._jit.lower(self._state_vals, *args)

    def _cache_size(self):
        return self._jit._cache_size()


# Latent attention in its absorbed form over the paged pool of a
# layer's latent rows (pallas/mla.py): the pages read in place by the
# kernel; the dense jnp form over gathered views off-TPU.
register_op("latent_paged_attention", latent_attend, nondiff=True)


def update_and_attend_latent(q, row_new, cache: DecodeCache, *, d_v,
                             scale):
    """`update_and_attend` for a layer of the LATENT kind (the engine's
    cache-spec contract): `cache.k` is the pool of the layer's rows
    [num_pages, page_size, row], key and value of every head at once,
    under the slot's one page table; `cache.v` is None. Writes row_new
    [B, l, row] at cache.pos, then attends q [B, l, H, row] (the
    absorbed queries) over the rows at or below each query. Returns
    (out [B, l, H, d_v]: the weighted sums of the rows' first d_v
    values, advanced cache). Served in the unified ragged step only
    (paged, per-row q_len). The write is the XLA row scatter: the
    Pallas scatter's blocks are one token's [1, heads, D] tile, and a
    row without a head axis is no such tile."""
    if cache.page_table is None or cache.q_len is None \
            or cache.k_scale is not None or cache.megakernel:
        raise NotImplementedError(
            "a latent-attention layer is served by the unified ragged "
            "step over a float paged pool (ServingEngine)")
    rows = apply_op("kv_cache_update_paged", cache.k, row_new, cache.pos,
                    cache.page_table)
    out = apply_op("latent_paged_attention", q, rows, cache.page_table,
                   cache.pos, cache.q_len,
                   attrs=dict(d_v=int(d_v), scale=float(scale)))
    return out, DecodeCache(rows, None, cache.pos + cache.q_len,
                            page_table=cache.page_table,
                            attn_impl=cache.attn_impl, q_len=cache.q_len)


# Learned sparse attention over the paged pools of a layer's keys,
# values and indexer rows (pallas/sparse.py): the pages read in place by
# the three kernels; the dense jnp forms over gathered views off-TPU.
register_op("sparse_paged_attention", sparse_attend, nondiff=True)


def update_and_attend_sparse(q, k_new, v_new, q_idx, w_idx, row_new,
                             cache: DecodeCache, *, topk):
    """`update_and_attend` for a layer of the SPARSE kind (the engine's
    cache-spec contract): `cache.k` / `cache.v` are the ordinary pools
    [num_pages, page_size, n_kv, head_dim], `cache.rows` the pool of the
    layer's indexer rows [num_pages, page_size, row], all three under
    the slot's one page table. Writes k_new / v_new [B, l, n_kv, D] and
    row_new [B, l, row] at cache.pos, ALL THREE BEFORE ANY IS READ (a
    chunk's own new keys are scored and may be selected), then attends
    q [B, l, H, D] over the `topk` positions at or below each query
    that q_idx [B, l, Hi, row] / w_idx [B, l, Hi] score highest against
    the rows. Returns (out [B, l, H, D], advanced cache). Served in the
    unified ragged step only (paged, per-row q_len); the writes are the
    XLA row scatter."""
    if cache.page_table is None or cache.q_len is None \
            or cache.rows is None or cache.k_scale is not None \
            or cache.megakernel or cache.group is not None:
        raise NotImplementedError(
            "a sparse-attention layer is served by the unified ragged "
            "step over float paged pools (ServingEngine)")
    k_buf, v_buf, rows = (
        apply_op("kv_cache_update_paged", pool, new, cache.pos,
                 cache.page_table)
        for pool, new in ((cache.k, k_new), (cache.v, v_new),
                          (cache.rows, row_new)))
    out = apply_op("sparse_paged_attention", q, q_idx, w_idx, k_buf, v_buf,
                   rows, cache.page_table, cache.pos, cache.q_len,
                   attrs=dict(topk=int(topk)))
    return out, DecodeCache(k_buf, v_buf, cache.pos + cache.q_len,
                            page_table=cache.page_table,
                            attn_impl=cache.attn_impl, q_len=cache.q_len,
                            rows=rows)


# The page walk over pools of split widths (pallas/paged_attention.py
# `ragged_paged_attention_split`): a token's kv heads side by side, keys
# and values of their own widths, and a learned sink where a layer has
# one. The forms with and without a sink are one op, two signatures.
register_op("ragged_paged_attention_split", ragged_paged_attention_split,
            nondiff=True)


def update_and_attend_split(q, k_new, v_new, cache: DecodeCache, *,
                            window=None, sink=None):
    """`update_and_attend` for a layer whose pools are of SPLIT widths
    (the engine's cache-spec contract): `cache.k` / `cache.v` are
    [num_pages, page_size, n_kv * Dk] and [num_pages, page_size,
    n_kv * Dv], a token's kv heads side by side. Writes k_new
    [B, l, n_kv, Dk] and v_new [B, l, n_kv, Dv] at cache.pos, then
    attends q [B, l, H, Dk] over the keys at or below each query (the
    last `window` of them in a sliding-window layer), with the layer's
    learned `sink` logits (a Tensor [H] or None) in the softmax.
    Returns (out [B, l, H, Dv], advanced cache). Served in the unified
    ragged step only (paged, per-row q_len); the writes are the XLA row
    scatter."""
    if cache.page_table is None or cache.q_len is None \
            or cache.k_scale is not None or cache.megakernel \
            or cache.group is not None:
        raise NotImplementedError(
            "a layer of split K/V widths is served by the unified ragged "
            "step over float paged pools (ServingEngine)")
    b, l, hkv = (int(n) for n in k_new.shape[:3])
    k_buf, v_buf = (
        apply_op("kv_cache_update_paged", pool,
                 new.reshape([b, l, int(pool.shape[2])]), cache.pos,
                 cache.page_table)
        for pool, new in ((cache.k, k_new), (cache.v, v_new)))
    attrs = dict(heads=hkv, window=None if window is None else int(window))
    args = [q, k_buf, v_buf, cache.page_table, cache.pos, cache.q_len]
    if sink is not None:
        args.append(sink)
    out = apply_op("ragged_paged_attention_split", *args, attrs=attrs)
    return out, DecodeCache(k_buf, v_buf, cache.pos + cache.q_len,
                            page_table=cache.page_table,
                            attn_impl=cache.attn_impl, q_len=cache.q_len)


def _pack_caches(caches):
    """DecodeCache list -> loop-carry pytree: per layer
    (k, v, k_scale|None, v_scale|None). None entries keep the pytree
    structure identical whether or not the int8 cache is active (and
    v is None for a layer of the latent kind: its rows are `k`). A
    layer of the sparse kind has a fifth entry, its indexer's rows."""
    return tuple(
        (c.k._value, None if c.v is None else c.v._value,
         None if c.k_scale is None else c.k_scale._value,
         None if c.v_scale is None else c.v_scale._value)
        + (() if c.rows is None else (c.rows._value,))
        for c in caches)


def _unpack_caches(ct, pos, page_table=None, attn_impl=None,
                   q_len=None, group=None, out_shard=None, lora=None,
                   lora_paged=None, megakernel=False):
    """page_table (optional [B, max_pages] raw int32 array) switches
    every layer's cache into paged-pool mode; the table is shared
    across layers (one page id addresses the same page in each
    layer's pool). attn_impl pins the paged decode implementation
    ("kernel"/"gather") for the trace being built. q_len (optional
    [B] raw int32 array) switches the paged caches into RAGGED mode —
    the serving engine's unified prefill+decode step, where each row
    carries its own live query count over a shared padded width.
    group (optional (group_id, group_leader, group_cnt) triple of [B]
    raw int32 arrays) attaches prefix-sharing groups: the ragged read
    takes the GROUPED walk — each physically shared page streamed
    once per group — with identical outputs. lora (optional, one
    entry PER LAYER: a 9-tuple of raw arrays — the per-row gathered
    A/B pairs for q/k/v/o plus the per-row scale, see
    serving/adapters.py) attaches that layer's multi-tenant LoRA
    weights; the attention modules fuse the per-row delta into their
    projections. lora_paged (optional, megakernel mode — mutually
    exclusive with lora): one entry PER LAYER, a 10-tuple of raw
    arrays — the layer's FULL paged adapter pools for q/k/v/o plus
    the per-row page ids and scales (see DecodeCache.lora_paged);
    the gather happens inside the fused op. megakernel=True routes
    every layer's unified attend through megakernel_decode[_q8]."""
    pt = None if page_table is None else Tensor(page_table)
    ql = None if q_len is None else Tensor(q_len)
    grp = None if group is None else tuple(Tensor(g) for g in group)
    lora = ([None] * len(ct) if lora is None
            else [tuple(Tensor(a) for a in layer) for layer in lora])
    lora_paged = ([None] * len(ct) if lora_paged is None
                  else [tuple(Tensor(a) for a in layer)
                        for layer in lora_paged])
    return [DecodeCache(Tensor(k), None if v is None else Tensor(v),
                        Tensor(pos),
                        None if ks is None else Tensor(ks),
                        None if vs is None else Tensor(vs),
                        page_table=pt, attn_impl=attn_impl, q_len=ql,
                        group=grp, out_shard=out_shard, lora=lo,
                        lora_paged=lp, megakernel=megakernel,
                        rows=Tensor(rows[0]) if rows else None)
            for (k, v, ks, vs, *rows), lo, lp in zip(ct, lora, lora_paged)]


def decode_model_step(model, tokens, caches):
    """One fixed-shape decode step, shared by CompiledGenerator's loop
    body and the serving engine (serving/engine.py): feed `tokens`
    [B, l] (a raw int array) through the model against the static
    caches and return (last-position logits as f32 [B, V], advanced
    caches). With a per-row `pos` vector in the caches this is the
    continuous-batching step: every row advances from its own position
    inside one compiled program."""
    lg, caches = model(Tensor(tokens), caches=caches)
    return lg._value[:, -1, :].astype(jnp.float32), caches


def head_columns(h, columns):
    """What a causal-LM wrapper's head reads of the hidden states h
    [B, L, H]: with `columns` (int [B, C] Tensor) the C columns it
    names a row, [B, C, H]; without, h itself. The unified serving
    step keeps one column a row of its W (1 + k with speculation), so
    the vocabulary-wide matmul and its logits run on those alone."""
    if columns is None:
        return h
    return Tensor(jnp.take_along_axis(h._value,
                                      columns._value[:, :, None], axis=1))


def sample_logits(logits, key, temperature=1.0, top_k=None, top_p=None,
                  strategy=None):
    """Next-token selection over f32 logits [B, V] — the sampling half
    of the decode step, factored out of CompiledGenerator._build so the
    serving engine shares it. strategy None keeps the legacy rule:
    argmax unless top_k/top_p request sampling."""
    if strategy == "greedy":
        return jnp.argmax(logits, axis=-1)
    if temperature != 1.0:
        logits = logits / temperature
    stochastic = (strategy == "sampling") or top_k or top_p
    if top_k:
        vals, _ = jax.lax.top_k(logits, int(top_k))
        logits = jnp.where(logits < vals[:, -1:], -1e30, logits)
    if top_p:
        logits = _top_p_filter(logits, float(top_p))
    if stochastic:
        return jax.random.categorical(key, logits, axis=-1)
    return jnp.argmax(logits, axis=-1)


def _top_p_filter(logits, p):
    """Nucleus filter: keep the smallest prefix of the sorted vocab whose
    probability mass reaches p; mask the rest to -1e30.

    The reference exposes top-p via PaddleNLP's TopPProcess (and the
    top_p_sampling fused op); here it is a sorted-cumsum mask that XLA
    fuses into the sampling step — no host round trip per token.
    """
    sorted_desc = -jnp.sort(-logits, axis=-1)
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # exclusive cumsum < p: the first token is always kept
    keep = (cum - probs) < p
    thresh = jnp.min(jnp.where(keep, sorted_desc, jnp.inf), axis=-1,
                     keepdims=True)
    return jnp.where(logits < thresh, jnp.float32(-1e30), logits)


class CompiledGenerator:
    """One-XLA-program generate() for a causal LM.

    `model(input_ids, caches=[DecodeCache...])` must return
    `(logits, new_caches)`; `cache_spec` is
    (n_layers, n_kv_heads, head_dim). One trace per
    (batch, prompt_len, max_new_tokens) signature, cached.

    decode_strategy:
      - None (default): argmax, or temperature/top-k/top-p sampling as
        soon as any of top_k/top_p is set (legacy behavior)
      - "greedy": argmax
      - "sampling": categorical over temperature/top-k/top-p logits
      - "beam_search": compiled beam search (see _build_beam) — the TPU
        form of the reference beam-search op
        (/root/reference/paddle/fluid/operators/math/beam_search.cu:1)
    """

    def __init__(self, model, cache_spec, temperature=1.0, top_k=None,
                 eos_token_id=None, pad_token_id=0, top_p=None,
                 decode_strategy=None, num_beams=4, length_penalty=0.0,
                 num_return_sequences=1, kv_cache_dtype=None):
        self.model = model
        self.n_layers, self.n_kv, self.head_dim = cache_spec
        if kv_cache_dtype not in (None, "int8"):
            raise ValueError(
                f"kv_cache_dtype must be None (model dtype) or 'int8', "
                f"got {kv_cache_dtype!r}")
        self.kv_int8 = kv_cache_dtype == "int8"
        self._kv_scales = None   # per-layer (k[Hkv], v[Hkv]) constants
        self.temperature = float(temperature)
        self.top_k = top_k
        self.top_p = top_p
        if decode_strategy == "greedy_search":  # reference spelling
            decode_strategy = "greedy"
        if decode_strategy not in (None, "greedy", "sampling",
                                   "beam_search"):
            raise ValueError(
                f"unknown decode_strategy {decode_strategy!r}; expected "
                "'greedy'/'greedy_search', 'sampling' or 'beam_search'")
        self.decode_strategy = decode_strategy
        self.num_beams = int(num_beams)
        self.length_penalty = float(length_penalty)
        self.num_return_sequences = int(num_return_sequences)
        if decode_strategy == "beam_search" and \
                self.num_return_sequences > self.num_beams:
            raise ValueError(
                f"num_return_sequences {self.num_return_sequences} > "
                f"num_beams {self.num_beams}")
        self.eos_token_id = eos_token_id
        self.pad_token_id = int(pad_token_id)
        params = list(model.parameters())
        buffers = [b for _, b in model.named_buffers()]
        self.state_tensors = params + buffers
        self._state_ids = tuple(id(t._value) for t in self.state_tensors)
        self._traces = {}

    def _calibrate_kv_scales(self, ids):
        """One eager bf16-cache prefill over the first prompt measures
        per-(layer, head) K/V absmax; scales (x1.27 headroom for later
        tokens, /127) are then baked into the int8 cache as constants
        (see _kv_update_q8_fwd). The reference's int8 decoder likewise
        ships calibrated static scales
        (fused_multi_transformer_int8_op.cu)."""
        from ..core.tensor import no_grad
        batch, plen = int(ids.shape[0]), int(ids.shape[1])
        fp = next((t._value.dtype for t in self.state_tensors
                   if jnp.issubdtype(t._value.dtype, jnp.floating)),
                  dtypes.get_default_dtype().np_dtype)
        with no_grad():
            caches = init_decode_caches(self.n_layers, batch, plen,
                                        self.n_kv, self.head_dim,
                                        dtype=fp)
            _, caches = self.model(ids, caches=caches)
        scales = []
        for c in caches:
            ka = np.asarray(jnp.max(jnp.abs(
                c.k._value.astype(jnp.float32)), axis=(0, 1, 3)))
            va = np.asarray(jnp.max(jnp.abs(
                c.v._value.astype(jnp.float32)), axis=(0, 1, 3)))
            scales.append((np.maximum(ka * 1.27, 1e-6) / 127.0,
                           np.maximum(va * 1.27, 1e-6) / 127.0))
        return scales

    def _sample(self, logits, key):
        return sample_logits(logits, key, temperature=self.temperature,
                             top_k=self.top_k, top_p=self.top_p,
                             strategy=self.decode_strategy)

    def _build(self, batch, prompt_len, max_new):
        model = self.model
        state_tensors = self.state_tensors
        max_len = prompt_len + max_new
        eos = self.eos_token_id
        pad = self.pad_token_id
        fp = next((t._value.dtype for t in state_tensors
                   if jnp.issubdtype(t._value.dtype, jnp.floating)),
                  dtypes.get_default_dtype().np_dtype)

        # Weights enter the jit as CLOSED-OVER CONSTANTS, not call
        # arguments: XLA assigns the matmul-optimal layout to constants
        # and schedules their HBM streams tighter. Measured on GPT-124M
        # bs16 decode this is the difference between 3.0 and
        # 1.8 ms/step (scripts/decode_roofline.py, loop64 vs
        # loop64_weights_as_args). Inference weights are frozen, so
        # constant-folding them is free; __call__ rebuilds the trace if
        # the model's parameters are rebound (e.g. re-quantized).
        def gen(state_vals, prompt, key):
            originals = [t._value for t in state_tensors]
            try:
                for t, v in zip(state_tensors, state_vals):
                    t._value = v
                caches = init_decode_caches(
                    self.n_layers, batch, max_len, self.n_kv,
                    self.head_dim, dtype=fp,
                    kv_scales=self._kv_scales if self.kv_int8
                    else None)
                logits_t, caches = model(Tensor(prompt), caches=caches)
                last = logits_t._value[:, -1, :].astype(jnp.float32)
                ct = _pack_caches(caches)
                out0 = jnp.full((batch, max_new), pad, prompt.dtype)
                done0 = jnp.zeros((batch,), bool)

                def step_token(i, last, ct, out, key, done):
                    key, sub = jax.random.split(key)
                    nxt = self._sample(last, sub).astype(out.dtype)
                    if eos is not None:
                        nxt = jnp.where(done,
                                        jnp.asarray(pad, out.dtype),
                                        nxt)
                    out = jax.lax.dynamic_update_slice(
                        out, nxt[:, None], (jnp.int32(0), i))
                    if eos is not None:
                        done = done | (nxt == eos)
                    pos = prompt_len + i
                    caches = _unpack_caches(ct, pos)
                    last, caches = decode_model_step(model, nxt[:, None],
                                                     caches)
                    return last, _pack_caches(caches), out, key, done

                if eos is None:
                    # no early exit possible: lax.scan's static trip
                    # count lets XLA schedule the loop tighter than
                    # while_loop (decode_roofline.py loop64 probe)
                    def body(carry, i):
                        last, ct, out, key, done = carry
                        return step_token(i, last, ct, out, key,
                                          done), None

                    (last, ct, out, key, done), _ = jax.lax.scan(
                        body, (last, ct, out0, key, done0),
                        jnp.arange(max_new, dtype=jnp.int32))
                    return out

                def cond(carry):
                    i = carry[0]
                    done = carry[5]
                    return (i < max_new) & ~jnp.all(done)

                def body(carry):
                    i, last, ct, out, key, done = carry
                    last, ct, out, key, done = step_token(
                        i, last, ct, out, key, done)
                    return (i + jnp.int32(1), last, ct, out, key,
                            done)

                final = jax.lax.while_loop(
                    cond, body,
                    (jnp.int32(0), last, ct, out0, key, done0))
                return final[3]
            finally:
                for t, v in zip(state_tensors, originals):
                    t._value = v

        state_vals = [t._value for t in state_tensors]
        return jax.jit(lambda prompt, key: gen(state_vals, prompt, key))

    def _build_beam(self, batch, prompt_len, max_new):
        """Beam search as ONE XLA program.

        All beam state is static-shaped: scores [B,K], tokens
        [B,K,max_new], KV caches carried at batch B*K and reordered each
        step with a flat gather (the in-place analogue of the reference
        kernel's parent-idx chase, beam_search.cu:1). Finished beams emit
        pad with frozen score. Final selection normalizes cumulative
        log-prob by gen_len**length_penalty (0.0 = pure sum, the
        reference default).
        """
        model = self.model
        state_tensors = self.state_tensors
        K = self.num_beams
        max_len = prompt_len + max_new
        eos = self.eos_token_id
        pad = self.pad_token_id
        lp = self.length_penalty
        fp = next((t._value.dtype for t in state_tensors
                   if jnp.issubdtype(t._value.dtype, jnp.floating)),
                  dtypes.get_default_dtype().np_dtype)

        def gen(state_vals, prompt, key):
            del key  # beam search is deterministic
            originals = [t._value for t in state_tensors]
            try:
                for t, v in zip(state_tensors, state_vals):
                    t._value = v
                BK = batch * K
                # every beam starts from the same prompt: prefill at B*K
                prompt_k = jnp.repeat(prompt, K, axis=0)  # [B*K, L]
                caches = init_decode_caches(
                    self.n_layers, BK, max_len, self.n_kv,
                    self.head_dim, dtype=fp,
                    kv_scales=self._kv_scales if self.kv_int8
                    else None)
                logits_t, caches = model(Tensor(prompt_k), caches=caches)
                last = logits_t._value[:, -1, :].astype(jnp.float32)
                V = last.shape[-1]
                ct = _pack_caches(caches)
                # beam 0 live, beams 1..K-1 muted so step 1 spreads over
                # the top-K tokens of the (identical) distributions
                scores0 = jnp.tile(
                    jnp.asarray([0.0] + [-1e30] * (K - 1), jnp.float32),
                    (batch, 1))
                tokens0 = jnp.full((batch, K, max_new), pad,
                                   prompt.dtype)
                done0 = jnp.zeros((batch, K), bool)
                len0 = jnp.zeros((batch, K), jnp.int32)
                # one-hot-ish row for finished beams: pad with logp 0,
                # everything else impossible
                pad_row = jnp.full((V,), -jnp.inf, jnp.float32) \
                    .at[pad].set(0.0)

                def cond(carry):
                    i = carry[0]
                    done = carry[5]
                    return (i < max_new) & ~jnp.all(done)

                def body(carry):
                    (i, last, ct, tokens, scores, done, lens) = carry
                    logp = jax.nn.log_softmax(
                        last.reshape(batch, K, V), axis=-1)
                    logp = jnp.where(done[:, :, None], pad_row[None, None],
                                     logp)
                    total = scores[:, :, None] + logp  # [B,K,V]
                    top_val, top_idx = jax.lax.top_k(
                        total.reshape(batch, K * V), K)  # [B,K]
                    beam_src = top_idx // V            # parent beam
                    tok = (top_idx % V).astype(tokens.dtype)
                    # reorder per-beam state by parent
                    take = lambda a: jnp.take_along_axis(a, beam_src,
                                                         axis=1)
                    tokens = jnp.take_along_axis(
                        tokens, beam_src[:, :, None], axis=1)
                    done = take(done)
                    lens = take(lens)
                    tokens = jax.lax.dynamic_update_slice(
                        tokens, tok[:, :, None],
                        (jnp.int32(0), jnp.int32(0), i))
                    lens = lens + (~done).astype(jnp.int32)
                    if eos is not None:
                        done = done | (tok == eos)
                    scores = top_val
                    # flat gather reorders the KV caches (and their int8
                    # scales, when present) to parent beams
                    flat = (jnp.arange(batch, dtype=jnp.int32)[:, None]
                            * K + beam_src).reshape(-1)
                    ct = tuple(
                        (jnp.take(k, flat, axis=0),
                         jnp.take(v, flat, axis=0), ks, vs)
                        for (k, v, ks, vs) in ct)
                    pos = prompt_len + i
                    caches = _unpack_caches(ct, pos)
                    last, caches = decode_model_step(
                        model, tok.reshape(BK, 1), caches)
                    return (i + jnp.int32(1), last, _pack_caches(caches),
                            tokens, scores, done, lens)

                final = jax.lax.while_loop(
                    cond, body,
                    (jnp.int32(0), last, ct, tokens0, scores0,
                     done0, len0))
                tokens, scores, lens = final[3], final[4], final[6]
                norm = scores / jnp.maximum(
                    lens.astype(jnp.float32), 1.0) ** lp
                nret = self.num_return_sequences
                # top-n beams per row (paddle/HF convention: rows are
                # [b0 seq0..seqn-1, b1 seq0..], best first)
                top_norm, best = jax.lax.top_k(norm, nret)  # [B, n]
                out = jnp.take_along_axis(
                    tokens, best[:, :, None], axis=1)      # [B,n,max_new]
                out = out.reshape(batch * nret, max_new)
                return out, top_norm.reshape(batch * nret)
            finally:
                for t, v in zip(state_tensors, originals):
                    t._value = v

        state_vals = [t._value for t in state_tensors]
        return jax.jit(lambda prompt, key: gen(state_vals, prompt, key))

    def __call__(self, input_ids, max_new_tokens=16,
                 return_scores=False):
        # reads the model's tensors and may trace: see _STATE_SWAP_LOCK
        with _STATE_SWAP_LOCK:
            return self._generate(input_ids, max_new_tokens,
                                  return_scores)

    def _generate(self, input_ids, max_new_tokens, return_scores):
        from ..core import random as random_mod
        ids = as_tensor(input_ids)
        beam = self.decode_strategy == "beam_search"
        if return_scores and not beam:
            raise ValueError("return_scores is only available with "
                             "decode_strategy='beam_search'")
        nret = self.num_return_sequences
        if nret > 1 and not beam:
            if self.decode_strategy == "greedy" or not (
                    self.decode_strategy == "sampling" or self.top_k
                    or self.top_p):
                raise ValueError(
                    "num_return_sequences > 1 needs a stochastic "
                    "strategy (sampling/top_k/top_p) or beam_search")
            # expanded rows sample independently through one trace
            from ..ops import manipulation
            ids = manipulation.repeat_interleave(ids, nret, axis=0)
        batch, prompt_len = int(ids.shape[0]), int(ids.shape[1])
        sig = (batch, prompt_len, int(max_new_tokens), beam)
        # weights are baked into the trace as constants (see _build);
        # ANY model-state change — a parameter rebind, a layer swap
        # (quantize_for_decode replaces Linears), a new buffer —
        # invalidates EVERY cached executable (stale traces would both
        # compute with old weights and pin their full weight snapshot
        # in HBM). Re-enumerate the live model state each call.
        cur_state = [p for p in self.model.parameters()] + \
            [b for _, b in self.model.named_buffers()]
        state_ids = tuple(id(t._value) for t in cur_state)
        if state_ids != self._state_ids:
            self._traces.clear()
            self.state_tensors = cur_state
            self._state_ids = state_ids
            self._kv_scales = None     # weights changed: recalibrate
        if self.kv_int8 and self._kv_scales is None:
            was_training = getattr(self.model, "training", False)
            self.model.eval()
            try:
                self._kv_scales = self._calibrate_kv_scales(ids)
            finally:
                if was_training:
                    self.model.train()
        cached = self._traces.get(sig)
        if cached is None:
            if len(self._traces) >= 8:
                # each trace holds a full constant-folded weight copy:
                # bound the signature cache
                self._traces.clear()
            fn = (self._build_beam if beam else self._build)(*sig[:3])
            self._traces[sig] = fn
        else:
            fn = cached
        was_training = getattr(self.model, "training", False)
        self.model.eval()
        try:
            key = random_mod.next_key_host()
            res = fn(ids._value, key)
        finally:
            if was_training:
                self.model.train()
        new_tokens, scores = res if beam else (res, None)
        from ..ops import manipulation
        if beam and nret > 1:
            # beam rows are [b0 seq0..seqn-1, b1 ...]: tile the prompt
            ids = manipulation.repeat_interleave(ids, nret, axis=0)
        out = manipulation.concat(
            [ids, Tensor(new_tokens, stop_gradient=True)], axis=1)
        if return_scores:
            return out, Tensor(scores, stop_gradient=True)
        return out
