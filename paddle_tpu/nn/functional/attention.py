"""Attention functional ops.

TPU-native replacement for Paddle's fused attention CUDA
(reference: paddle/fluid/operators/fused/fused_attention_op.cu, fmha_ref.h,
python/paddle/nn/functional/flash_attention.py in later snapshots).
The reference hand-fuses QKV+FMHA+proj per CUDA arch; here one pure
function lowers to XLA (which fuses the softmax chain), and on TPU the
inner attention is swapped for a Pallas flash-attention kernel
(paddle_tpu/ops/pallas/flash_attention.py) with identical semantics.
"""
from __future__ import annotations

import functools
import math

import numpy as np
import jax
import jax.numpy as jnp

from ...core.dispatch import register_op
from ...core.tensor import Tensor
from ...core import random as random_mod
from ...ops._helpers import as_tensor, apply_op

__all__ = ["scaled_dot_product_attention", "flash_attention",
           "sparse_attention"]


def _use_pallas(q_len, head_dim):
    import jax
    return (jax.devices()[0].platform == "tpu" and q_len >= 128
            and head_dim in (64, 128, 256))


def _sdpa_ref(q, k, v, mask, causal, scale, dropout_p, key):
    """Reference attention: [B, L, H, D] layout (paddle convention)."""
    dt = q.dtype
    logits = jnp.einsum("blhd,bmhd->bhlm", q, k) * scale
    logits = logits.astype(jnp.float32)
    if causal:
        L, M = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((L, M), dtype=bool), M - L)
        logits = jnp.where(cm, logits, -1e30)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, -1e30)
        else:
            logits = logits + mask.astype(logits.dtype)
    probs = jax.nn.softmax(logits, axis=-1).astype(dt)
    if dropout_p > 0.0 and key is not None:
        keep = 1.0 - dropout_p
        from .common import _fast_bits_key
        m = jax.random.bernoulli(_fast_bits_key(key), keep, probs.shape)
        probs = jnp.where(m, probs / keep, 0.0).astype(dt)
    return jnp.einsum("bhlm,bmhd->blhd", probs, v)


def _mask_to_kernel_operands(mask, B, H, Lq, Lk):
    """Map a paddle attn_mask onto the kernel's operands, or None if
    unsupported. Returns (bias, kvec): bias [Bb, Hb, Lq, Lk] additive
    f32 streamed block-wise, kvec [B, Lk] additive f32 — the O(L)
    padding-mask fast path (the BERT finetune shape [B, 1, 1, Lk])."""
    if mask.ndim != 4:
        return None
    mb, mh, ml, mk = mask.shape
    if mb not in (1, B) or mh not in (1, H) or ml not in (1, Lq) \
            or mk != Lk:
        return None
    if mask.dtype == jnp.bool_:
        add = jnp.where(mask, jnp.float32(0.0), jnp.float32(-1e30))
    else:
        add = mask.astype(jnp.float32)
    if ml == 1 and mh == 1:
        kv = add.reshape(mb, mk)
        if mb == 1 and B > 1:
            kv = jnp.broadcast_to(kv, (B, mk))
        return ("kvec", kv)
    if ml != Lq:
        # per-head key masks ([*, H, 1, Lk]): the bias operand streams
        # blocks along Lq, and a singleton Lq would be zero-PADDED, not
        # broadcast — route to the XLA reference instead
        return None
    return ("bias", add)


def _sdpa_impl(q, k, v, mask, key, causal, scale, dropout_p,
               mask_trainable=False, block_q=None, block_k=None):
    """Unified route: Pallas flash kernel whenever the device/head-dim
    support it — including padding masks, additive bias, and dropout
    (in-kernel position-hash mask) — else the XLA reference. A
    TRAINABLE mask needs real bias gradients, which the kernel does not
    produce — that case stays on the reference path. block_q/block_k
    override the kernel tiling (set by incubate.autotune)."""
    B, Lq, H, D = q.shape
    Lk = k.shape[1]
    if _use_pallas(Lq, D) and not (mask_trainable and mask is not None):
        from ...ops.pallas import flash_attention as fa
        bias = kvec = None
        ok = True
        if mask is not None:
            mapped = _mask_to_kernel_operands(mask, B, H, Lq, Lk)
            if mapped is None:
                ok = False
            elif mapped[0] == "kvec":
                kvec = mapped[1]
            else:
                bias = mapped[1]
        if ok:
            seeds = None
            if dropout_p > 0.0 and key is not None:
                seeds = jax.lax.bitcast_convert_type(
                    key.reshape(-1)[:2], jnp.int32)
            return fa.flash_attention_blhd(
                q, k, v, bias, kvec, seeds, causal=causal, scale=scale,
                dropout_p=float(dropout_p) if seeds is not None else 0.0,
                block_q=block_q or fa.DEFAULT_BLOCK_Q,
                block_k=block_k or fa.DEFAULT_BLOCK_K)
    return _sdpa_ref(q, k, v, mask, causal, scale, dropout_p, key)


register_op("sdpa",
            lambda q, k, v, causal, scale, dropout_p, block_q=None,
            block_k=None:
            _sdpa_impl(q, k, v, None, None, causal, scale, dropout_p,
                       block_q=block_q, block_k=block_k))
register_op("sdpa_mask",
            lambda q, k, v, mask, causal, scale, dropout_p,
            mask_trainable=False, block_q=None, block_k=None:
            _sdpa_impl(q, k, v, mask, None, causal, scale, dropout_p,
                       mask_trainable, block_q=block_q,
                       block_k=block_k))
register_op("sdpa_dropout",
            lambda q, k, v, key, causal, scale, dropout_p, block_q=None,
            block_k=None:
            _sdpa_impl(q, k, v, None, key, causal, scale, dropout_p,
                       block_q=block_q, block_k=block_k))
register_op("sdpa_mask_dropout",
            lambda q, k, v, mask, key, causal, scale, dropout_p,
            mask_trainable=False, block_q=None, block_k=None:
            _sdpa_impl(q, k, v, mask, key, causal, scale, dropout_p,
                       mask_trainable, block_q=block_q,
                       block_k=block_k))


def _autotuned_blocks(q, k, attrs):
    """Consult the incubate.autotune kernel cache for this signature;
    on an eager call with an empty cache, run the timing sweep (a
    traced call only reuses whatever the cache holds)."""
    from ...incubate import autotune as at
    B, Lq, H, D = q.shape
    Lk = k.shape[1]
    if not _use_pallas(Lq, D):
        return None
    sig = (B, Lq, Lk, H, D, str(q._value.dtype), attrs["causal"])
    # never sweep while a static Program records (the timing calls would
    # be captured as dead program nodes) or under a trace
    from ...static import in_static_mode
    eager = not isinstance(q._value, jax.core.Tracer) and \
        not in_static_mode()

    def measure(bq, bk):
        import time
        a = dict(attrs, block_q=bq, block_k=bk)
        out = apply_op("sdpa", q, k, k, attrs=a)  # v=k: same shapes
        out._value.block_until_ready()
        t0 = time.perf_counter()
        out = apply_op("sdpa", q, k, k, attrs=a)
        out._value.block_until_ready()
        return time.perf_counter() - t0

    return at.kernel_blocks_for(sig, measure if eager else None)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, name=None):
    """Inputs [batch, seq, num_heads, head_dim] (paddle layout)."""
    q, k, v = as_tensor(query), as_tensor(key), as_tensor(value)
    scale = 1.0 / math.sqrt(q.shape[-1])
    p = float(dropout_p) if training else 0.0
    attrs = dict(causal=bool(is_causal), scale=scale, dropout_p=p)
    blocks = _autotuned_blocks(q, k, attrs)
    if blocks is not None:
        attrs["block_q"], attrs["block_k"] = blocks
    if attn_mask is None and p == 0.0:
        return apply_op("sdpa", q, k, v, attrs=attrs)
    if attn_mask is None:
        rk = Tensor(random_mod.next_key())
        return apply_op("sdpa_dropout", q, k, v, rk, attrs=attrs)
    m = as_tensor(attn_mask)
    attrs["mask_trainable"] = not m.stop_gradient
    if p == 0.0:
        return apply_op("sdpa_mask", q, k, v, m, attrs=attrs)
    rk = Tensor(random_mod.next_key())
    return apply_op("sdpa_mask_dropout", q, k, v, m, rk, attrs=attrs)


def _softmax_probs(q, k, causal, scale):
    logits = jnp.einsum("blhd,bmhd->bhlm", q, k) * scale
    logits = logits.astype(jnp.float32)
    if causal:
        L, M = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((L, M), dtype=bool), M - L)
        logits = jnp.where(cm, logits, -1e30)
    return jax.nn.softmax(logits, axis=-1).astype(q.dtype)


register_op("sdpa_probs",
            lambda q, k, causal, scale:
            _softmax_probs(q, k, causal, scale), nondiff=True)


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None,
                    rng_name="", training=True, name=None):
    """paddle.nn.functional.flash_attention parity. return_softmax=True
    materializes the [B, H, L, L] softmax via the reference path (the
    kernel never forms it — that is the point of flash attention), so
    use it for debugging only."""
    out = scaled_dot_product_attention(query, key, value, None, dropout,
                                       causal, training)
    if return_softmax:
        q, k = as_tensor(query), as_tensor(key)
        scale = 1.0 / math.sqrt(q.shape[-1])
        probs = apply_op("sdpa_probs", q, k,
                         attrs=dict(causal=bool(causal), scale=scale))
        return out, probs
    return out, None


def _sparse_attention_fwd(q, k, v, rows, cols, kpm, am, scale):
    """CSR-pattern attention: scores computed ONLY at (rows, cols)
    coordinates, softmax over each query row's stored entries, scatter
    back through V. q/k/v [B,H,L,D]; rows/cols [B,H,nnz] int32;
    kpm [B,L] additive or None; am [L,L] additive or None."""
    B, H, L, D = q.shape
    nnz = rows.shape[-1]
    qg = jnp.take_along_axis(q, rows[..., None], axis=2)   # [B,H,nnz,D]
    kg = jnp.take_along_axis(k, cols[..., None], axis=2)
    vg = jnp.take_along_axis(v, cols[..., None], axis=2)
    s = (qg.astype(jnp.float32) * kg.astype(jnp.float32)).sum(-1) * scale
    if kpm is not None:
        s = s + jnp.take_along_axis(
            jnp.broadcast_to(kpm[:, None, :].astype(jnp.float32),
                             (B, H, L)), cols, axis=2)
    if am is not None:
        s = s + am.astype(jnp.float32)[rows, cols]
    # segment softmax per (b, h, query-row)
    bh = jnp.arange(B * H, dtype=jnp.int32).reshape(B, H, 1)
    seg = (bh * L + rows).reshape(-1)
    flat = s.reshape(-1)
    n_seg = B * H * L
    mx = jax.ops.segment_max(flat, seg, num_segments=n_seg)
    e = jnp.exp(flat - mx[seg])
    z = jax.ops.segment_sum(e, seg, num_segments=n_seg)
    probs = (e / jnp.maximum(z[seg], 1e-30)).astype(q.dtype)
    weighted = probs.reshape(B, H, nnz, 1) * vg
    out = jnp.zeros_like(q)
    b_idx = jnp.arange(B).reshape(B, 1, 1)
    h_idx = jnp.arange(H).reshape(1, H, 1)
    bb = jnp.broadcast_to(b_idx, (B, H, nnz))
    hh = jnp.broadcast_to(h_idx, (B, H, nnz))
    return out.at[bb, hh, rows].add(weighted)


from ...core.dispatch import OpDef  # noqa: E402

register_op("sparse_attention", _sparse_attention_fwd)
# module-level OpDefs: a fresh lambda per call would defeat the jit cache
_SPARSE_ATTN_OPS = {
    "kpm": OpDef("sparse_attention_kpm",
                 lambda q, k, v, r, c, m, scale:
                 _sparse_attention_fwd(q, k, v, r, c, m, None, scale)),
    "am": OpDef("sparse_attention_am",
                lambda q, k, v, r, c, m, scale:
                _sparse_attention_fwd(q, k, v, r, c, None, m, scale)),
    "plain": OpDef("sparse_attention_plain",
                   lambda q, k, v, r, c, scale:
                   _sparse_attention_fwd(q, k, v, r, c, None, None,
                                         scale)),
}


def sparse_attention(query, key, value, sparse_csr_offset,
                     sparse_csr_columns, key_padding_mask=None,
                     attn_mask=None, name=None):
    """paddle.nn.functional.sparse_attention parity (reference:
    python/paddle/nn/functional/sparse_attention.py over the CUDA 11.3
    block-sparse kernel). The attention matrix is evaluated only at the
    CSR pattern's coordinates — an SDDMM + row-segment softmax + SpMM
    pipeline on TPU. offset [B,H,L+1] int32, columns [B,H,nnz] int32."""
    q, k, v = as_tensor(query), as_tensor(key), as_tensor(value)
    B, H, L, D = q.shape
    off = np.asarray(sparse_csr_offset._value
                     if isinstance(sparse_csr_offset, Tensor)
                     else sparse_csr_offset).astype(np.int64)
    cols = as_tensor(sparse_csr_columns).astype("int32")
    counts = np.diff(off, axis=-1)                       # [B,H,L]
    nnz = int(counts.sum(axis=-1).max())
    if not (counts.sum(axis=-1) == nnz).all():
        raise ValueError("sparse_attention: every (batch, head) must "
                         "hold the same nnz (fixed CSR columns width)")
    rows = np.repeat(
        np.tile(np.arange(L, dtype=np.int32), B * H),
        counts.reshape(-1)).reshape(B, H, nnz)
    scale = 1.0 / math.sqrt(D)
    args = [q, k, v, Tensor(jnp.asarray(rows)), cols]
    attrs = dict(scale=scale)
    if key_padding_mask is not None and attn_mask is not None:
        return apply_op("sparse_attention", *args,
                        as_tensor(key_padding_mask),
                        as_tensor(attn_mask), attrs=attrs)
    if key_padding_mask is not None:
        return apply_op(_SPARSE_ATTN_OPS["kpm"], *args,
                        as_tensor(key_padding_mask), attrs=attrs)
    if attn_mask is not None:
        return apply_op(_SPARSE_ATTN_OPS["am"], *args,
                        as_tensor(attn_mask), attrs=attrs)
    return apply_op(_SPARSE_ATTN_OPS["plain"], *args, attrs=attrs)
