"""Normalization functional ops.

TPU-native replacement for Paddle's norm kernels (reference:
paddle/phi/kernels/gpu/batch_norm_kernel.cu, layer_norm_kernel.cu,
python/paddle/nn/functional/norm.py). Stats + affine fuse into one XLA
kernel; there is no cuDNN fast-path split. Running-stat updates are extra
functional outputs (buffers rebind outside), keeping ops pure for pjit.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ...core.dispatch import register_op
from ...ops.pallas import per_device
from ...ops._helpers import as_tensor, apply_op

__all__ = ["batch_norm", "layer_norm", "instance_norm", "group_norm",
           "local_response_norm", "normalize", "rms_norm"]


def _channel_axis(ndim, data_format):
    if data_format.startswith("NC"):
        return 1
    return ndim - 1


def _bn_stats_axes(ndim, c_axis):
    return tuple(i for i in range(ndim) if i != c_axis)


def _bcast(v, ndim, c_axis):
    shape = [1] * ndim
    shape[c_axis] = -1
    return v.reshape(shape)


def _one_pass_stats(xf, axes):
    """Shifted one-pass mean/variance (keepdims): E[(x-s)^2] - E[x-s]^2
    with s a per-slice sample of x (index 0 along each reduced axis).

    Still ONE read of x — the subtraction is elementwise and fuses into
    the reductions (jnp.var would re-read x after the mean
    materializes, a full extra activation pass). The shift bounds the
    cancellation of the raw E[x^2]-mean^2 form, which loses most
    precision when |mean| >> std (ADVICE r4); variance is
    shift-invariant, so the result matches the two-pass formula to f32
    rounding. The Pallas LN kernel uses the centered two-pass form —
    with the shift both paths agree on ill-conditioned inputs
    (tests/test_nn_layers.py::TestNormLargeOffset)."""
    ax = set(a % xf.ndim for a in axes)
    idx = tuple(slice(0, 1) if i in ax else slice(None)
                for i in range(xf.ndim))
    s = xf[idx]
    xs = xf - s
    m = jnp.mean(xs, axis=tuple(ax), keepdims=True)
    var = jnp.maximum(
        jnp.mean(jnp.square(xs), axis=tuple(ax), keepdims=True)
        - jnp.square(m), 0.0)
    return m + s, var


def _bn_train_fwd(x, mean_buf, var_buf, weight, bias, momentum, epsilon,
                  c_axis, use_global):
    if use_global:
        y = _bn_apply(x, mean_buf, var_buf, weight, bias, epsilon, c_axis)
        return y, mean_buf, var_buf
    axes = _bn_stats_axes(x.ndim, c_axis)
    xf = x.astype(jnp.float32) if x.dtype in (jnp.bfloat16, jnp.float16) else x
    # shifted one-pass stats (see _one_pass_stats): single read of x,
    # fused into the producing conv's epilogue, cancellation-safe
    mean_k, var_k = _one_pass_stats(xf, axes)
    mean = mean_k.reshape(-1)
    var = var_k.reshape(-1)
    y = _bn_apply(x, mean, var, weight, bias, epsilon, c_axis)
    new_mean = momentum * mean_buf + (1.0 - momentum) * mean.astype(mean_buf.dtype)
    new_var = momentum * var_buf + (1.0 - momentum) * var.astype(var_buf.dtype)
    return y, new_mean, new_var


def _bn_apply(x, mean, var, weight, bias, epsilon, c_axis):
    dt = x.dtype
    xf = x.astype(jnp.float32) if dt in (jnp.bfloat16, jnp.float16) else x
    inv = jax.lax.rsqrt(var.astype(xf.dtype) + epsilon)
    y = (xf - _bcast(mean.astype(xf.dtype), x.ndim, c_axis)) * \
        _bcast(inv, x.ndim, c_axis)
    if weight is not None:
        y = y * _bcast(weight.astype(xf.dtype), x.ndim, c_axis)
    if bias is not None:
        y = y + _bcast(bias.astype(xf.dtype), x.ndim, c_axis)
    return y.astype(dt)


register_op("batch_norm_train",
            lambda x, m, v, w, b, momentum, epsilon, c_axis, use_global:
            _bn_train_fwd(x, m, v, w, b, momentum, epsilon, c_axis,
                          use_global))
register_op("batch_norm_infer",
            lambda x, m, v, w, b, epsilon, c_axis:
            _bn_apply(x, m, v, w, b, epsilon, c_axis))


def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-5,
               data_format="NCHW", use_global_stats=None, name=None):
    """Returns y in eval mode; (y, new_mean, new_var) in training mode.

    The Layer wrapper rebinds its buffers from the extra outputs — this is
    the functional analogue of the in-place running-stat update in the
    reference kernel (paddle/phi/kernels/gpu/batch_norm_kernel.cu).
    """
    x = as_tensor(x)
    c_axis = _channel_axis(x.ndim, data_format)
    w = as_tensor(weight) if weight is not None else None
    b = as_tensor(bias) if bias is not None else None
    m, v = as_tensor(running_mean), as_tensor(running_var)
    if (w is None) != (b is None):
        raise ValueError("batch_norm needs both or neither of weight/bias")
    if training:
        use_global = bool(use_global_stats) if use_global_stats is not None \
            else False
        if w is None:
            return apply_op("batch_norm_train_noaffine", x, m, v,
                            attrs=dict(momentum=float(momentum),
                                       epsilon=float(epsilon), c_axis=c_axis,
                                       use_global=use_global))
        return apply_op("batch_norm_train", x, m, v, w, b,
                        attrs=dict(momentum=float(momentum),
                                   epsilon=float(epsilon), c_axis=c_axis,
                                   use_global=use_global))
    if w is None:
        return apply_op("batch_norm_infer_noaffine", x, m, v,
                        attrs=dict(epsilon=float(epsilon), c_axis=c_axis))
    return apply_op("batch_norm_infer", x, m, v, w, b,
                    attrs=dict(epsilon=float(epsilon), c_axis=c_axis))


register_op("batch_norm_train_noaffine",
            lambda x, m, v, momentum, epsilon, c_axis, use_global:
            _bn_train_fwd(x, m, v, None, None, momentum, epsilon, c_axis,
                          use_global))
register_op("batch_norm_infer_noaffine",
            lambda x, m, v, epsilon, c_axis:
            _bn_apply(x, m, v, None, None, epsilon, c_axis))


# -- layer norm --------------------------------------------------------------

def _use_pallas_ln():
    import os
    if os.environ.get("PADDLE_TPU_FUSED_LN", "1") == "0":
        return False  # escape hatch
    if os.environ.get("PADDLE_TPU_PALLAS_INTERPRET", "0") == "1":
        return True
    return jax.devices()[0].platform == "tpu"


def _ln_fwd(x, w, b, n_norm_axes, epsilon):
    if w is not None and b is not None:
        # fused Pallas path: one read for fwd, one for bwd (XLA's
        # unfused lowering costs ~12ms of a 60ms BERT-base step across
        # 25 LN sites; reference fuses in layer_norm_kernel.cu)
        from ...ops.pallas import layer_norm as pln
        if pln.supported(x, w, b, n_norm_axes) and _use_pallas_ln():
            # under a kernel mesh (the tensor-parallel serving replica)
            # activations are replicated: every device normalizes them
            return per_device(
                lambda x, w, b: pln.layer_norm_fused(x, w, b,
                                                     float(epsilon)),
                (P(), P(), P()), P())(x, w, b)
    axes = tuple(range(x.ndim - n_norm_axes, x.ndim))
    dt = x.dtype
    xf = x.astype(jnp.float32) if dt in (jnp.bfloat16, jnp.float16) else x
    mean, var = _one_pass_stats(xf, axes)
    y = (xf - mean) * jax.lax.rsqrt(var + epsilon)
    if w is not None:
        y = y * w.astype(y.dtype)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y.astype(dt)


register_op("layer_norm",
            lambda x, w, b, n_norm_axes, epsilon:
            _ln_fwd(x, w, b, n_norm_axes, epsilon))
register_op("layer_norm_noaffine",
            lambda x, n_norm_axes, epsilon:
            _ln_fwd(x, None, None, n_norm_axes, epsilon))


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5,
               name=None):
    x = as_tensor(x)
    if isinstance(normalized_shape, (int, np.integer)):
        normalized_shape = (int(normalized_shape),)
    n_norm = len(tuple(normalized_shape))
    if weight is None and bias is None:
        return apply_op("layer_norm_noaffine", x,
                        attrs=dict(n_norm_axes=n_norm, epsilon=float(epsilon)))
    if weight is None or bias is None:
        raise ValueError("layer_norm needs both or neither of weight/bias")
    return apply_op("layer_norm", x, as_tensor(weight), as_tensor(bias),
                    attrs=dict(n_norm_axes=n_norm, epsilon=float(epsilon)))


def _rms_fwd(x, w, epsilon):
    dt = x.dtype
    xf = x.astype(jnp.float32) if dt in (jnp.bfloat16, jnp.float16) else x
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(ms + epsilon)
    return (y * w.astype(y.dtype)).astype(dt)


register_op("rms_norm", lambda x, w, epsilon: _rms_fwd(x, w, epsilon))


def rms_norm(x, weight, epsilon=1e-6, name=None):
    """RMSNorm — new capability (Llama-family); absent from the reference."""
    return apply_op("rms_norm", as_tensor(x), as_tensor(weight),
                    attrs=dict(epsilon=float(epsilon)))


# -- instance / group norm ---------------------------------------------------

def _in_fwd(x, w, b, epsilon, c_axis):
    axes = tuple(i for i in range(2, x.ndim)) if c_axis == 1 else \
        tuple(i for i in range(1, x.ndim - 1))
    dt = x.dtype
    xf = x.astype(jnp.float32) if dt in (jnp.bfloat16, jnp.float16) else x
    mean, var = _one_pass_stats(xf, axes)
    y = (xf - mean) * jax.lax.rsqrt(var + epsilon)
    if w is not None:
        y = y * _bcast(w.astype(y.dtype), x.ndim, c_axis)
    if b is not None:
        y = y + _bcast(b.astype(y.dtype), x.ndim, c_axis)
    return y.astype(dt)


register_op("instance_norm",
            lambda x, w, b, epsilon, c_axis: _in_fwd(x, w, b, epsilon, c_axis))
register_op("instance_norm_noaffine",
            lambda x, epsilon, c_axis: _in_fwd(x, None, None, epsilon, c_axis))


def instance_norm(x, running_mean=None, running_var=None, weight=None,
                  bias=None, use_input_stats=True, momentum=0.9, eps=1e-5,
                  data_format="NCHW", name=None):
    x = as_tensor(x)
    c_axis = _channel_axis(x.ndim, data_format)
    if weight is None and bias is None:
        return apply_op("instance_norm_noaffine", x,
                        attrs=dict(epsilon=float(eps), c_axis=c_axis))
    return apply_op("instance_norm", x, as_tensor(weight), as_tensor(bias),
                    attrs=dict(epsilon=float(eps), c_axis=c_axis))


def _gn_fwd(x, w, b, groups, epsilon, channel_last):
    dt = x.dtype
    xf = x.astype(jnp.float32) if dt in (jnp.bfloat16, jnp.float16) else x
    if channel_last:
        c = x.shape[-1]
        gs = xf.reshape(x.shape[:-1] + (groups, c // groups))
        axes = tuple(range(1, x.ndim - 1)) + (x.ndim,)
        mean, var = _one_pass_stats(gs, axes)
        y = ((gs - mean) * jax.lax.rsqrt(var + epsilon)).reshape(x.shape)
        if w is not None:
            y = y * w.astype(y.dtype)
        if b is not None:
            y = y + b.astype(y.dtype)
    else:
        c = x.shape[1]
        gs = xf.reshape((x.shape[0], groups, c // groups) + x.shape[2:])
        axes = tuple(range(2, gs.ndim))
        mean, var = _one_pass_stats(gs, axes)
        y = ((gs - mean) * jax.lax.rsqrt(var + epsilon)).reshape(x.shape)
        if w is not None:
            y = y * _bcast(w.astype(y.dtype), x.ndim, 1)
        if b is not None:
            y = y + _bcast(b.astype(y.dtype), x.ndim, 1)
    return y.astype(dt)


register_op("group_norm",
            lambda x, w, b, groups, epsilon, channel_last:
            _gn_fwd(x, w, b, groups, epsilon, channel_last))
register_op("group_norm_noaffine",
            lambda x, groups, epsilon, channel_last:
            _gn_fwd(x, None, None, groups, epsilon, channel_last))


def group_norm(x, num_groups, epsilon=1e-5, weight=None, bias=None,
               data_format="NCHW", name=None):
    x = as_tensor(x)
    channel_last = not data_format.startswith("NC")
    if weight is None and bias is None:
        return apply_op("group_norm_noaffine", x,
                        attrs=dict(groups=int(num_groups),
                                   epsilon=float(epsilon),
                                   channel_last=channel_last))
    return apply_op("group_norm", x, as_tensor(weight), as_tensor(bias),
                    attrs=dict(groups=int(num_groups), epsilon=float(epsilon),
                               channel_last=channel_last))


# -- misc --------------------------------------------------------------------

def _lrn_fwd(x, size, alpha, beta, k, channel_last):
    c_axis = x.ndim - 1 if channel_last else 1
    sq = jnp.square(x)
    half = size // 2
    pads = [(0, 0)] * x.ndim
    pads[c_axis] = (half, size - half - 1)
    sq = jnp.pad(sq, pads)
    win = [1] * x.ndim
    win[c_axis] = size
    acc = jax.lax.reduce_window(sq, 0.0, jax.lax.add, tuple(win),
                                (1,) * x.ndim, "valid")
    # paddle normalizes by the window MEAN (avg_pool of squares), not sum
    return x / jnp.power(k + alpha * acc / size, beta)


register_op("local_response_norm",
            lambda x, size, alpha, beta, k, channel_last:
            _lrn_fwd(x, size, alpha, beta, k, channel_last))


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0,
                        data_format="NCHW", name=None):
    x = as_tensor(x)
    channel_last = not data_format.startswith("NC")
    return apply_op("local_response_norm", x,
                    attrs=dict(size=int(size), alpha=float(alpha),
                               beta=float(beta), k=float(k),
                               channel_last=channel_last))


register_op("p_normalize",
            lambda x, p, axis, epsilon:
            x / jnp.maximum(jnp.linalg.norm(x, ord=p, axis=axis,
                                            keepdims=True), epsilon))


def normalize(x, p=2, axis=1, epsilon=1e-12, name=None):
    return apply_op("p_normalize", as_tensor(x),
                    attrs=dict(p=float(p), axis=int(axis),
                               epsilon=float(epsilon)))
