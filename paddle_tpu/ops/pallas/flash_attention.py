"""Flash attention (Pallas, TPU) — fused forward AND backward, with
additive bias / key-padding masks and in-kernel dropout.

TPU-native replacement for the reference's fused FMHA CUDA
(paddle/fluid/operators/fused/fused_attention_op.cu, fmha_ref.h — whose
grad kernel is fused too; mask+dropout semantics per fmha_ref.h's
softmax-then-dropout). Online softmax over K/V blocks: running
(m, l, acc) scratch in VMEM, one MXU dot per (q-block, k-block) pair, no
[L, L] logits materialized in HBM.

Mask operands (both optional, combinable with causal):
  * ``bias``  — additive float bias [Bb, Hb, Lq, Lk] with Bb in {1, B}
    and Hb in {1, H}; streamed block-by-block (never materialized at
    [B, H, L, L] in HBM unless the caller already did).
  * ``kvec``  — additive per-key vector [B, Lk]: the padding-mask fast
    path (BERT finetune); O(L) HBM traffic.

Dropout (softmax-then-dropout, normalizer uses the UNDROPPED row sum,
matching the reference) uses a position-keyed counter hash: the keep
decision for (bh, q_pos, k_pos) depends only on the seed and the
position, so forward and the two backward kernels — whose grids
iterate in different orders — regenerate identical masks by
construction, and the plain-jnp hash doubles as the test oracle.

Forward stores per-row logsumexp; backward is two Pallas kernels
(structure mirrors jax.experimental.pallas.ops.tpu.flash_attention
without importing it):
  dq : grid (BH, nQ, nK), accumulates ds @ K over k-blocks in VMEM
  dkv: grid (BH, nK, nQ), accumulates p^T @ dO and ds^T @ Q over q-blocks
Both recompute p = exp(s - lse) from q/k (flash recompute trade), so
nothing O(L^2) ever hits HBM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from . import kernel_id as _kernel_id, trace32 as _trace32

import os

# interpret mode: run kernels on CPU for testing (conftest sets this)
_INTERPRET = os.environ.get("PADDLE_TPU_PALLAS_INTERPRET", "0") == "1"

# trace name -> pallas_call keywords, one entry per call site in this
# file (ops/pallas/__init__.py `kernel_id`); no name contains another
KERNELS = {name: _kernel_id(name, fn) for name, fn in (
    ("flash_fwd", "_fa_kernel"),
    ("flash_dq", "_fa_dq_kernel"),
    ("flash_dkv", "_fa_dkv_kernel"),
)}

def _prec(dt):
    # 'highest' (the package-wide default) is invalid for bf16 operands
    # under Mosaic; bf16 x bf16 -> f32 on the MXU is exact at DEFAULT.
    return (jax.lax.Precision.DEFAULT if jnp.dtype(dt) == jnp.bfloat16
            else jax.lax.Precision.HIGHEST)


# Large blocks amortize per-grid-step overhead (the kernel is VPU-bound
# on softmax bookkeeping; profiled on v5e: 128->512 blocks cut the GPT
# step's attention time 4x). Shrunk automatically for short sequences.
DEFAULT_BLOCK_Q = int(os.environ.get("PADDLE_TPU_FA_BLOCK_Q", "512"))
DEFAULT_BLOCK_K = int(os.environ.get("PADDLE_TPU_FA_BLOCK_K", "1024"))


def _fit_block(block, length):
    """Cap the block at the 128-padded sequence length."""
    return max(128, min(block, -(-length // 128) * 128))
_NEG_INF = -1e30
_LANES = 128


def _fmix32(h):
    """murmur3 finalizer: full-avalanche 32-bit mix (VPU int ops only —
    runs identically under Mosaic, interpret mode, and plain jnp)."""
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return h


def dropout_keep(seed0, seed1, bh, q_pos, k_pos, thresh):
    """Position-keyed keep mask: True where the attention weight at
    (bh, q_pos, k_pos) survives dropout. Pure jnp — the same function
    is the kernel's mask generator and the test oracle."""
    hq = _fmix32(jnp.uint32(seed0)
                 + q_pos.astype(jnp.uint32) * jnp.uint32(2654435761))
    hk = _fmix32(jnp.uint32(seed1)
                 + k_pos.astype(jnp.uint32) * jnp.uint32(0x27D4EB2F))
    h = _fmix32(hq + hk
                + jnp.uint32(bh) * jnp.uint32(0x9E3779B9))
    return h >= jnp.uint32(thresh)


def _drop_thresh(p):
    """uint32 threshold: hash < thresh <=> dropped (prob p)."""
    return min(int(p * 4294967296.0), 4294967295)


def _block_keep(seed_ref, bh_id, qb, kb, block_q, block_k, thresh):
    """Keep-mask for the (qb, kb) block — THE single definition of the
    position arithmetic all three kernels share. Separability makes it
    cheap: hq depends only on the row and hk only on the column, so
    feeding the oracle (block_q,1)/(1,block_k) position VECTORS runs
    the first two fmix32 rounds on vectors; only the final mix touches
    the full block (5 int ops/element instead of 15 — the hash was the
    kernel's VPU hot spot)."""
    q_pos = qb * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0)
    k_pos = kb * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_k), 1)
    # same single definition as the test oracle — the hq/hk fmix rounds
    # run on the (block_q,1)/(1,block_k) vectors and broadcast at the
    # final mix, bit- and formula-identical to full-matrix positions
    return dropout_keep(seed_ref[0], seed_ref[1], bh_id, q_pos, k_pos,
                        thresh)


def _biased_logits(q_ref, k_ref, R, scale32, prec):
    """Scaled q k^T for the current block, plus the optional streamed
    additive bias / key-vector operands."""
    s = jax.lax.dot_general(
        q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=prec) * scale32      # [bq, bk]
    if R.bias is not None:
        s = s + R.bias[0, 0].astype(jnp.float32)
    if R.kvec is not None:
        s = s + R.kvec[0].astype(jnp.float32)
    return s


class _Refs:
    """Positional-ref parser shared by the three kernels."""

    def __init__(self, refs, *, drop, has_bias, has_kvec, n_main):
        i = 0
        self.seed = None
        if drop:
            self.seed = refs[0]
            i = 1
        self.main = refs[i:i + n_main]
        i += n_main
        self.bias = None
        if has_bias:
            self.bias = refs[i]
            i += 1
        self.kvec = None
        if has_kvec:
            self.kvec = refs[i]
            i += 1
        self.rest = refs[i:]


def _fa_kernel(*refs, scale, causal, block_q, block_k, q_len, kv_len,
               drop_thresh, inv_keep, has_bias, has_kvec):
    drop = drop_thresh is not None
    R = _Refs(refs, drop=drop, has_bias=has_bias, has_kvec=has_kvec,
              n_main=3)
    q_ref, k_ref, v_ref = R.main
    o_ref, lse_ref, m_ref, l_ref, acc_ref = R.rest
    prec = _prec(q_ref.dtype)
    bh_id = pl.program_id(0)
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    n_kv = pl.num_programs(2)

    neg_inf = jnp.float32(_NEG_INF)
    scale32 = jnp.float32(scale)

    @pl.when(kj == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, neg_inf)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # bottom-right causal alignment (matches the XLA reference: query i may
    # see keys j <= i + (kv_len - q_len)); whole k-blocks past the last
    # query of this q-block are predicated away.
    offset = kv_len - q_len
    run = True
    if causal:
        run = kj * block_k <= qi * block_q + block_q - 1 + offset

    # Mask generation (two iotas + compares + where) is pure VPU cost;
    # with d=64 the MXU work per block pair is tiny, so interior blocks
    # take a mask-free fast path and only diagonal/ragged-edge blocks
    # pay for the mask.
    ragged = (kv_len % block_k) != 0
    edge = (kj == pl.num_programs(2) - 1) if ragged else False
    if causal:
        full = kj * block_k + block_k - 1 <= qi * block_q + offset
        need_mask = jnp.logical_and(
            run, jnp.logical_or(jnp.logical_not(full), edge)) \
            if ragged else jnp.logical_and(run, jnp.logical_not(full))
        no_mask = jnp.logical_and(run, jnp.logical_and(
            full, jnp.logical_not(edge)) if ragged else full)
    else:
        need_mask = edge
        no_mask = jnp.logical_not(edge) if ragged else True

    def _accum(s):
        m_prev = m_ref[:, :1]              # [bq, 1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        # normalizer tracks the FULL softmax sum (dropout applies after
        # the softmax in the reference, so l never sees the mask)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        p_eff = p
        if drop:
            keep = _block_keep(R.seed, bh_id, qi, kj, block_q, block_k,
                               drop_thresh)
            p_eff = jnp.where(keep, p * jnp.float32(inv_keep), 0.0)
        v = v_ref[0]
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p_eff.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=prec)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    def _logits():
        return _biased_logits(q_ref, k_ref, R, scale32, prec)

    @pl.when(no_mask)
    def _compute_fast():
        _accum(_logits())

    @pl.when(need_mask)
    def _compute_masked():
        s = _logits()
        k_pos = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = k_pos < kv_len
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            valid = jnp.logical_and(valid, q_pos + offset >= k_pos)
        _accum(jnp.where(valid, s, neg_inf))

    @pl.when(kj == n_kv - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], jnp.float32(1e-30))
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        lse = m_ref[:, :1] + jnp.log(l)
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _fa_dq_kernel(*refs, scale, causal, block_q, block_k, q_len,
                  kv_len, drop_thresh, inv_keep, has_bias, has_kvec):
    drop = drop_thresh is not None
    R = _Refs(refs, drop=drop, has_bias=has_bias, has_kvec=has_kvec,
              n_main=6)
    q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref = R.main
    dq_ref, acc_ref = R.rest
    prec = _prec(q_ref.dtype)
    bh_id = pl.program_id(0)
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    n_kv = pl.num_programs(2)
    scale32 = jnp.float32(scale)

    @pl.when(kj == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    offset = kv_len - q_len
    run = True
    if causal:
        run = kj * block_k <= qi * block_q + block_q - 1 + offset

    ragged = (kv_len % block_k) != 0
    edge = (kj == pl.num_programs(2) - 1) if ragged else False
    if causal:
        full = kj * block_k + block_k - 1 <= qi * block_q + offset
        base = jnp.logical_or(jnp.logical_not(full), edge) if ragged \
            else jnp.logical_not(full)
        need_mask = jnp.logical_and(run, base)
        no_mask = jnp.logical_and(run, jnp.logical_and(
            full, jnp.logical_not(edge)) if ragged else full)
    else:
        need_mask = edge
        no_mask = jnp.logical_not(edge) if ragged else True

    def _accum(s):
        k = k_ref[0]                       # [bk, d]
        v = v_ref[0]                       # [bk, d]
        do = do_ref[0]                     # [bq, d]
        lse = lse_ref[:, :, :1][0]         # [bq, 1]
        di = di_ref[:, :, :1][0]           # [bq, 1]
        p = jnp.exp(s - lse)    # masked s = -1e30 underflows to p = 0
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=prec)                # [bq, bk]
        if drop:
            keep = _block_keep(R.seed, bh_id, qi, kj, block_q, block_k,
                               drop_thresh)
            dp = jnp.where(keep, dp * jnp.float32(inv_keep), 0.0)
        ds = p * (dp - di) * scale32
        acc_ref[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=prec)

    def _logits():
        return _biased_logits(q_ref, k_ref, R, scale32, prec)

    @pl.when(no_mask)
    def _compute_fast():
        _accum(_logits())

    @pl.when(need_mask)
    def _compute_masked():
        s = _logits()
        k_pos = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = k_pos < kv_len
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            valid = jnp.logical_and(valid, q_pos + offset >= k_pos)
        _accum(jnp.where(valid, s, jnp.float32(_NEG_INF)))

    @pl.when(kj == n_kv - 1)
    def _finalize():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _fa_dkv_kernel(*refs, scale, causal, block_q, block_k, q_len,
                   kv_len, drop_thresh, inv_keep, has_bias, has_kvec):
    drop = drop_thresh is not None
    R = _Refs(refs, drop=drop, has_bias=has_bias, has_kvec=has_kvec,
              n_main=6)
    k_ref, v_ref, q_ref, do_ref, lse_ref, di_ref = R.main
    dk_ref, dv_ref, dk_acc, dv_acc = R.rest
    prec = _prec(q_ref.dtype)
    bh_id = pl.program_id(0)
    ki = pl.program_id(1)
    qj = pl.program_id(2)
    n_q = pl.num_programs(2)
    scale32 = jnp.float32(scale)

    @pl.when(qj == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    offset = kv_len - q_len
    run = True
    if causal:
        run = ki * block_k <= qj * block_q + block_q - 1 + offset

    ragged = (kv_len % block_k) != 0
    edge = (ki == pl.num_programs(1) - 1) if ragged else False
    if causal:
        full = ki * block_k + block_k - 1 <= qj * block_q + offset
        base = jnp.logical_or(jnp.logical_not(full), edge) if ragged \
            else jnp.logical_not(full)
        need_mask = jnp.logical_and(run, base)
        no_mask = jnp.logical_and(run, jnp.logical_and(
            full, jnp.logical_not(edge)) if ragged else full)
    else:
        need_mask = edge
        no_mask = jnp.logical_not(edge) if ragged else True

    def _accum(s):
        v = v_ref[0]                       # [bk, d]
        q = q_ref[0]                       # [bq, d]
        do = do_ref[0]                     # [bq, d]
        lse = lse_ref[:, :, :1][0]         # [bq, 1]
        di = di_ref[:, :, :1][0]           # [bq, 1]
        p = jnp.exp(s - lse)    # masked s = -1e30 underflows to p = 0
        if drop:
            keep = _block_keep(R.seed, bh_id, qj, ki, block_q, block_k,
                               drop_thresh)
            p_eff = jnp.where(keep, p * jnp.float32(inv_keep), 0.0)
        else:
            p_eff = p
        dv_acc[:] += jax.lax.dot_general(
            p_eff.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=prec)                # [bk, d]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=prec)                # [bq, bk]
        if drop:
            dp = jnp.where(keep, dp * jnp.float32(inv_keep), 0.0)
        ds = p * (dp - di) * scale32
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=prec)                # [bk, d]

    def _logits():
        return _biased_logits(q_ref, k_ref, R, scale32, prec)

    @pl.when(no_mask)
    def _compute_fast():
        _accum(_logits())

    @pl.when(need_mask)
    def _compute_masked():
        s = _logits()
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = k_pos < kv_len
        if causal:
            q_pos = qj * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            valid = jnp.logical_and(valid, q_pos + offset >= k_pos)
        _accum(jnp.where(valid, s, jnp.float32(_NEG_INF)))

    @pl.when(qj == n_q - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _pad_to(x, axis, mult):
    n = x.shape[axis]
    rem = (-n) % mult
    if rem == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, rem)
    return jnp.pad(x, pads)


def _mask_specs(bias, kvec, h, block_q, block_k, transpose=False):
    """(padded operands, in_specs) for the optional mask inputs.
    transpose=True is the dkv grid, where program_id(1) walks k-blocks
    and program_id(2) walks q-blocks."""
    ops, specs = [], []
    if bias is not None:
        Bb, Hb = bias.shape[0], bias.shape[1]
        bp = _pad_to(_pad_to(bias, 2, block_q), 3, block_k)

        def bias_idx(b, i, j):
            bi = 0 if Bb == 1 else b // h
            hi = 0 if Hb == 1 else b % h
            return ((bi, hi, j, i) if transpose else (bi, hi, i, j))
        ops.append(bp)
        specs.append(pl.BlockSpec((1, 1, block_q, block_k), bias_idx))
    if kvec is not None:
        B = kvec.shape[0]
        # [B, 1, Lk]: Mosaic needs the last-two block dims (sublane,
        # lane) to divide (8, 128) or equal the array dims — a middle
        # singleton satisfies the sublane rule
        kp = _pad_to(kvec, 1, block_k)[:, None, :]

        def kvec_idx(b, i, j):
            bi = 0 if B == 1 else b // h
            return ((bi, 0, i) if transpose else (bi, 0, j))
        ops.append(kp)
        specs.append(pl.BlockSpec((1, 1, block_k), kvec_idx))
    return ops, specs


def _seed_ops(seeds, drop):
    if not drop:
        return [], []
    return ([jnp.asarray(seeds, jnp.int32)],
            [pl.BlockSpec(memory_space=pltpu.SMEM)])


def _flash_fwd_bhld(q, k, v, bias, kvec, seeds, h, causal, scale,
                    dropout_p, block_q, block_k):
    """q: [BH, Lq, D], k/v: [BH, Lk, D] -> ([BH, Lq, D], lse)."""
    bh, lq, d = q.shape
    lk = k.shape[1]
    block_q = _fit_block(block_q, lq)
    block_k = _fit_block(block_k, lk)
    qp = _pad_to(q, 1, block_q)
    kp = _pad_to(k, 1, block_k)
    vp = _pad_to(v, 1, block_k)
    n_q = qp.shape[1] // block_q
    n_k = kp.shape[1] // block_k
    drop = dropout_p > 0.0

    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, q_len=lq, kv_len=lk,
        drop_thresh=_drop_thresh(dropout_p) if drop else None,
        inv_keep=1.0 / (1.0 - dropout_p) if drop else 1.0,
        has_bias=bias is not None, has_kvec=kvec is not None)
    seed_ops, seed_specs = _seed_ops(seeds, drop)
    mask_ops, mask_specs = _mask_specs(bias, kvec, h, block_q, block_k)
    # Mosaic rejects i64 index arithmetic; trace the kernel in 32-bit
    # mode regardless of the global jax_enable_x64 (paddle int64 parity)
    with _trace32():
        out, lse = pl.pallas_call(
            kernel,
            grid=(bh, n_q, n_k),
            in_specs=seed_specs + [
                pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            ] + mask_specs,
            out_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_q, _LANES),
                             lambda b, i, j: (b, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct(qp.shape, q.dtype),
                jax.ShapeDtypeStruct((bh, qp.shape[1], _LANES),
                                     jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, _LANES), jnp.float32),
                pltpu.VMEM((block_q, _LANES), jnp.float32),
                pltpu.VMEM((block_q, d), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=_INTERPRET,
            **KERNELS["flash_fwd"],
        )(*seed_ops, qp, kp, vp, *mask_ops)
    return out[:, :lq], lse


def _flash_bwd_bhld(q, k, v, o, lse, do, bias, kvec, seeds, h, causal,
                    scale, dropout_p, block_q, block_k):
    """All [BH, L, D] (lse [BH, Lqp, 128]) -> (dq, dk, dv)."""
    bh, lq, d = q.shape
    lk = k.shape[1]
    block_q = _fit_block(block_q, lq)
    block_k = _fit_block(block_k, lk)
    qp = _pad_to(q, 1, block_q)
    kp = _pad_to(k, 1, block_k)
    vp = _pad_to(v, 1, block_k)
    dop = _pad_to(do, 1, block_q)
    lqp, lkp = qp.shape[1], kp.shape[1]
    n_q, n_k = lqp // block_q, lkp // block_k
    offset = lk - lq
    drop = dropout_p > 0.0
    statics = dict(
        scale=scale, causal=causal, block_q=block_q, block_k=block_k,
        q_len=lq, kv_len=lk,
        drop_thresh=_drop_thresh(dropout_p) if drop else None,
        inv_keep=1.0 / (1.0 - dropout_p) if drop else 1.0,
        has_bias=bias is not None, has_kvec=kvec is not None)

    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                 axis=-1)                                    # [bh, lq]
    di = _pad_to(di, 1, block_q)
    di = jnp.broadcast_to(di[..., None], (bh, lqp, _LANES))

    qspec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    lmspec = pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, 0))

    if causal:
        def kv_idx(b, i, j):
            # skipped kv blocks prefetch block 0 (they are predicated off)
            ok = j * block_k <= i * block_q + block_q - 1 + offset
            return (b, jax.lax.select(ok, j, 0), 0)
    else:
        def kv_idx(b, i, j):
            return (b, j, 0)
    kvspec = pl.BlockSpec((1, block_k, d), kv_idx)

    seed_ops, seed_specs = _seed_ops(seeds, drop)
    mask_ops, mask_specs = _mask_specs(bias, kvec, h, block_q, block_k)

    dq_kernel = functools.partial(_fa_dq_kernel, **statics)
    with _trace32():
        dq = pl.pallas_call(
            dq_kernel,
            grid=(bh, n_q, n_k),
            in_specs=seed_specs
            + [qspec, kvspec, kvspec, qspec, lmspec, lmspec]
            + mask_specs,
            out_specs=pl.BlockSpec((1, block_q, d),
                                   lambda b, i, j: (b, i, 0)),
            out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=_INTERPRET,
            **KERNELS["flash_dq"],
        )(*seed_ops, qp, kp, vp, dop, lse, di, *mask_ops)

    # dkv grid: (bh, n_k, n_q) — q is the sequential (accumulated) axis
    kspec2 = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0))
    if causal:
        def q_idx(b, i, j):
            # q blocks strictly above the diagonal band are predicated
            # off; prefetch the first contributing q block instead
            ok = i * block_k <= j * block_q + block_q - 1 + offset
            first = jnp.maximum((i * block_k - offset) // block_q, 0)
            return (b, jax.lax.select(ok, j, first), 0)
    else:
        def q_idx(b, i, j):
            return (b, j, 0)
    qspec2 = pl.BlockSpec((1, block_q, d), q_idx)
    lmspec2 = pl.BlockSpec((1, block_q, _LANES),
                           lambda b, i, j: q_idx(b, i, j))
    mask_ops2, mask_specs2 = _mask_specs(bias, kvec, h, block_q,
                                         block_k, transpose=True)

    dkv_kernel = functools.partial(_fa_dkv_kernel, **statics)
    with _trace32():
        dk, dv = pl.pallas_call(
            dkv_kernel,
            grid=(bh, n_k, n_q),
            in_specs=seed_specs
            + [kspec2, kspec2, qspec2, qspec2, lmspec2, lmspec2]
            + mask_specs2,
            out_specs=[
                pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct(kp.shape, k.dtype),
                jax.ShapeDtypeStruct(vp.shape, v.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, d), jnp.float32),
                pltpu.VMEM((block_k, d), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=_INTERPRET,
            **KERNELS["flash_dkv"],
        )(*seed_ops, kp, vp, qp, dop, lse, di, *mask_ops2)

    return dq[:, :lq], dk[:, :lk], dv[:, :lk]


def _ref_blhd(q, k, v, causal, scale):
    logits = jnp.einsum("blhd,bmhd->bhlm", q, k).astype(jnp.float32) * scale
    if causal:
        lq, lk = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((lq, lk), dtype=bool), lk - lq)
        logits = jnp.where(cm, logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhlm,bmhd->blhd", probs, v)


def _to_bhld(x):
    b, l, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, l, d)


def _from_bhld(x, b, h):
    bh, l, d = x.shape
    return x.reshape(b, h, l, d).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def flash_attention_blhd(q, k, v, bias=None, kvec=None, seeds=None,
                         causal=False, scale=None, dropout_p=0.0,
                         block_q=DEFAULT_BLOCK_Q,
                         block_k=DEFAULT_BLOCK_K):
    """Flash attention over [batch, seq, heads, head_dim] inputs.

    bias: optional additive [Bb, Hb, Lq, Lk] (Bb in {1,B}, Hb in {1,H});
    kvec: optional additive per-key vector [B, Lk] (padding masks);
    seeds: int32[2] dropout seed (required when dropout_p > 0)."""
    return _fa_fwd(q, k, v, bias, kvec, seeds, causal, scale,
                   dropout_p, block_q, block_k)[0]


def _fa_fwd(q, k, v, bias, kvec, seeds, causal, scale, dropout_p,
            block_q, block_k):
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    b, lq, h, d = q.shape
    out, lse = _flash_fwd_bhld(
        _to_bhld(q), _to_bhld(k), _to_bhld(v), bias, kvec, seeds, h,
        causal, scale, dropout_p, block_q, block_k)
    out = _from_bhld(out, b, h)
    return out, (q, k, v, bias, kvec, seeds, out, lse)


def _fa_bwd(causal, scale, dropout_p, block_q, block_k, res, g):
    q, k, v, bias, kvec, seeds, o, lse = res
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    b, lq, h, d = q.shape
    dq, dk, dv = _flash_bwd_bhld(
        _to_bhld(q), _to_bhld(k), _to_bhld(v), _to_bhld(o), lse,
        _to_bhld(g), bias, kvec, seeds, h, causal, scale, dropout_p,
        block_q, block_k)
    dbias = None if bias is None else jnp.zeros_like(bias)
    dkvec = None if kvec is None else jnp.zeros_like(kvec)
    dseeds = None if seeds is None else jnp.zeros_like(seeds)
    return (_from_bhld(dq, b, h).astype(q.dtype),
            _from_bhld(dk, b, h).astype(k.dtype),
            _from_bhld(dv, b, h).astype(v.dtype),
            dbias, dkvec, dseeds)


flash_attention_blhd.defvjp(_fa_fwd, _fa_bwd)
