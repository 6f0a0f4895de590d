"""Fused LayerNorm Pallas kernels (fwd + hand-written bwd).

TPU-native replacement for the reference's fused LN CUDA kernels
(paddle/phi/kernels/gpu/layer_norm_kernel.cu,
operators/fused/fused_layernorm_residual_dropout_bias.h). XLA lowers an
unfused LN into separate stats-reduce and normalize passes, and its
backward into several more — on a BERT-base train step the 25 LN sites
cost ~12 ms of a 60 ms step. These kernels do:

- fwd: ONE read of x per row-block -> y
- bwd: ONE read of (dy, x) -> dx plus per-block partial dw/db, summed
  outside (tiny [8*n_blocks, C] matrices). Row statistics are
  recomputed in-kernel from the x block already in VMEM — cheaper than
  round-tripping [R]-shaped stats through HBM (and Mosaic has no
  1-D output tiling anyway).

Stats and arithmetic are f32 regardless of IO dtype (reference
semantics); tested against the jnp path in
tests/test_pallas_layer_norm.py.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from . import kernel_id as _kernel_id, trace32 as _trace32

_INTERPRET = os.environ.get("PADDLE_TPU_PALLAS_INTERPRET", "0") == "1"

# trace name -> pallas_call keywords, one entry per call site in this
# file (ops/pallas/__init__.py `kernel_id`); no name contains another
KERNELS = {name: _kernel_id(name, fn) for name, fn in (
    ("layer_norm_fwd", "_ln_fwd_kernel"),
    ("layer_norm_bwd", "_ln_bwd_kernel"),
)}

DEFAULT_BLOCK_R = 256

# VMEM budget for one grid step's operands+temporaries. The bwd kernel
# holds dy, x, xhat, a, dx (~6 [BR, C] f32 buffers): with the default
# BR=256 a large C (>= 8192 f32) would blow VMEM and fail Mosaic
# compilation at runtime — shrink BR as C grows instead (ADVICE r4).
_VMEM_BUDGET_BYTES = 12 * 1024 * 1024
_BWD_BUFFERS = 6


def _auto_block_r(block, c):
    cap = _VMEM_BUDGET_BYTES // (_BWD_BUFFERS * 4 * max(c, 1))
    cap = max(8, (cap // 8) * 8)
    return min(block, cap)


def _fit(block, n):
    return max(8, min(block, n))


def _stats(x, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    return mean, jax.lax.rsqrt(var + eps)


def _ln_fwd_kernel(x_ref, w_ref, b_ref, y_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)           # [BR, C]
    mean, rstd = _stats(x, eps)
    y = (x - mean) * rstd
    y = y * w_ref[0].astype(jnp.float32) + b_ref[0].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)


def _ln_bwd_kernel(dy_ref, x_ref, w_ref, dx_ref, dw_ref, db_ref, *,
                   eps):
    dy = dy_ref[...].astype(jnp.float32)         # [BR, C]
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)
    mean, rstd = _stats(x, eps)
    xhat = (x - mean) * rstd
    a = dy * w
    m1 = jnp.mean(a, axis=-1, keepdims=True)
    m2 = jnp.mean(a * xhat, axis=-1, keepdims=True)
    dx = rstd * (a - m1 - xhat * m2)
    dx_ref[...] = dx.astype(dx_ref.dtype)
    # per-block partials over the row axis; summed outside. Mosaic
    # wants >=8 sublanes per output tile: broadcast the row-sum over an
    # (8, C) tile, read back row 0 only
    dw_ref[...] = jnp.broadcast_to(
        jnp.sum(dy * xhat, axis=0, keepdims=True), dw_ref.shape)
    db_ref[...] = jnp.broadcast_to(
        jnp.sum(dy, axis=0, keepdims=True), db_ref.shape)


def _rows(x):
    r = 1
    for s in x.shape[:-1]:
        r *= s
    return r


def _pad_rows(x2, br):
    pad = (-x2.shape[0]) % br
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    return x2, pad


def _ln_fwd(x, w, b, eps, block_r):
    c = x.shape[-1]
    r = _rows(x)
    block_r = _auto_block_r(block_r, c)
    x2, pad = _pad_rows(x.reshape(r, c), _fit(block_r, r))
    br = _fit(block_r, r)
    n = x2.shape[0] // br
    # 32-bit trace inside the kernel regardless of the global
    # jax_enable_x64 (paddle int64 parity): Mosaic cannot legalize the
    # i64 index-map constants x64 mode would produce
    with _trace32():
        y = _fwd_call(x2, w, b, br, c, n, eps)
    if pad:
        y = y[:r]
    return y.reshape(x.shape)


def _fwd_call(x2, w, b, br, c, n, eps):
    return pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=eps),
        grid=(n,),
        in_specs=[pl.BlockSpec((br, c), lambda i: (i, 0)),
                  pl.BlockSpec((1, c), lambda i: (0, 0)),
                  pl.BlockSpec((1, c), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((br, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x2.dtype),
        interpret=_INTERPRET,
        **KERNELS["layer_norm_fwd"],
    )(x2, w.reshape(1, c), b.reshape(1, c))


def _ln_bwd(dy, x, w, eps, block_r):
    c = x.shape[-1]
    r = _rows(x)
    br = _fit(_auto_block_r(block_r, c), r)
    dy2, pad = _pad_rows(dy.reshape(r, c), br)
    x2, _ = _pad_rows(x.reshape(r, c), br)
    n = dy2.shape[0] // br
    with _trace32():
        dx, dw_p, db_p = _bwd_call(dy2, x2, w, br, c, n, eps)
    if pad:
        dx = dx[:r]
    dw = dw_p.reshape(n, 8, c)[:, 0].sum(axis=0)
    db = db_p.reshape(n, 8, c)[:, 0].sum(axis=0)
    return (dx.reshape(x.shape), dw, db)


def _bwd_call(dy2, x2, w, br, c, n, eps):
    return pl.pallas_call(
        functools.partial(_ln_bwd_kernel, eps=eps),
        grid=(n,),
        in_specs=[pl.BlockSpec((br, c), lambda i: (i, 0)),
                  pl.BlockSpec((br, c), lambda i: (i, 0)),
                  pl.BlockSpec((1, c), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((br, c), lambda i: (i, 0)),
                   pl.BlockSpec((8, c), lambda i: (i, 0)),
                   pl.BlockSpec((8, c), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct(dy2.shape, x2.dtype),
                   jax.ShapeDtypeStruct((8 * n, c), jnp.float32),
                   jax.ShapeDtypeStruct((8 * n, c), jnp.float32)],
        interpret=_INTERPRET,
        **KERNELS["layer_norm_bwd"],
    )(dy2, x2, w.reshape(1, c))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def layer_norm_fused(x, w, b, eps=1e-5, block_r=DEFAULT_BLOCK_R):
    return _ln_fwd(x, w, b, eps, block_r)


def _vjp_fwd(x, w, b, eps, block_r):
    return _ln_fwd(x, w, b, eps, block_r), (x, w)


def _vjp_bwd(eps, block_r, res, dy):
    x, w = res
    dx, dw, db = _ln_bwd(dy, x, w, eps, block_r)
    return dx, dw.astype(w.dtype), db.astype(w.dtype)


layer_norm_fused.defvjp(_vjp_fwd, _vjp_bwd)


def supported(x, w, b, n_norm_axes):
    """Kernel eligibility: last-axis-only LN, lane-aligned C, affine
    params matching the axis."""
    if n_norm_axes != 1 or w is None or b is None:
        return False
    c = x.shape[-1]
    # beyond this C even an 8-row block exceeds the VMEM budget
    if _BWD_BUFFERS * 4 * 8 * c > _VMEM_BUDGET_BYTES:
        return False
    return (c % 128 == 0 and x.ndim >= 2
            and tuple(w.shape) == (c,) and tuple(b.shape) == (c,)
            and x.dtype in (jnp.bfloat16, jnp.float32, jnp.float16))
