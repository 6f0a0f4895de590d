"""Flag system (reference: paddle/phi/core/flags.h PADDLE_DEFINE_EXPORTED_*,
python/paddle/fluid/framework.py set_flags/get_flags).

Flags are plain process-level key/values; FLAGS_* env vars seed them at
import, mirroring __bootstrap__'s --tryfromenv.

Audit of the reference flag surface (review r3 weak #8) — every flag
falls in one of three buckets, enforced by set_flags:

- MAPPED (change behavior here): FLAGS_check_nan_inf (per-op scan
  hook), FLAGS_use_autotune (Pallas kernel tiling sweep),
  FLAGS_default_compute_dtype.
- ACCEPTED-INERT (meaningful on CUDA/CPU runtimes, no TPU analogue;
  recorded so get_flags round-trips, with the reason in _INERT):
  allocator/memory knobs (PJRT owns allocation), cudnn/cublas/mkldnn
  algo knobs (XLA owns kernel selection), device-list knobs (PJRT
  owns placement). FLAGS_cudnn_deterministic is inert because TPU
  executions are deterministic already.
- UNKNOWN: set_flags raises ValueError, exactly like the reference's
  "cannot set its value" path; unknown FLAGS_* env vars are ignored at
  bootstrap (the reference's tryfromenv reads registered flags only).
"""
from __future__ import annotations

import os

_FLAGS: dict = {}

_DEFAULTS = {
    "FLAGS_check_nan_inf": False,
    "FLAGS_cudnn_deterministic": False,
    # matches incubate.autotune's own default (sweep opt-in)
    "FLAGS_use_autotune": False,
    "FLAGS_allocator_strategy": "auto_growth",
    "FLAGS_eager_delete_tensor_gb": 0.0,
    "FLAGS_default_compute_dtype": "float32",
}

# accepted-and-recorded, with the reason they have no TPU effect
_INERT = {
    # PJRT owns allocation:
    "FLAGS_allocator_strategy": "PJRT owns device allocation",
    "FLAGS_eager_delete_tensor_gb": "PJRT owns device allocation",
    "FLAGS_fraction_of_gpu_memory_to_use": "PJRT owns device allocation",
    "FLAGS_initial_gpu_memory_in_mb": "PJRT owns device allocation",
    "FLAGS_reallocate_gpu_memory_in_mb": "PJRT owns device allocation",
    "FLAGS_gpu_allocator_retry_time": "PJRT owns device allocation",
    "FLAGS_init_allocated_mem": "PJRT owns device allocation",
    "FLAGS_use_pinned_memory": "PJRT owns host staging",
    "FLAGS_fast_eager_deletion_mode": "no GC of device buffers needed",
    "FLAGS_memory_fraction_of_eager_deletion": "no GC needed",
    # XLA owns kernel selection / math modes:
    "FLAGS_cudnn_deterministic": "TPU executions are deterministic",
    "FLAGS_cudnn_exhaustive_search": "XLA owns kernel selection",
    "FLAGS_conv_workspace_size_limit": "XLA owns conv lowering",
    "FLAGS_cudnn_batchnorm_spatial_persistent": "XLA owns BN lowering",
    "FLAGS_enable_cublas_tensor_op_math": "MXU bf16 is the math mode",
    "FLAGS_gemm_use_half_precision_compute_type": "MXU bf16 path",
    "FLAGS_embedding_deterministic": "XLA scatter is deterministic",
    "FLAGS_max_inplace_grad_add": "XLA owns buffer reuse",
    "FLAGS_use_mkldnn": "single XLA backend",
    "FLAGS_tracer_mkldnn_ops_on": "single XLA backend",
    "FLAGS_tracer_mkldnn_ops_off": "single XLA backend",
    # PJRT owns placement:
    "FLAGS_selected_gpus": "PJRT owns device placement",
    "FLAGS_selected_tpus": "PJRT owns device placement",
    "FLAGS_selected_xpus": "PJRT owns device placement",
    # profiling/benchmark modes subsumed by paddle_tpu.profiler:
    "FLAGS_benchmark": "use paddle_tpu.profiler",
    "FLAGS_enable_rpc_profiler": "RPC descoped with PS",
}

_KNOWN = set(_DEFAULTS) | set(_INERT)


def flag_audit():
    """The audit table: flag -> 'mapped' | inert-reason."""
    out = {k: "mapped" for k in _DEFAULTS if k not in _INERT}
    out.update(_INERT)
    return dict(sorted(out.items()))


def _bootstrap():
    for k, v in _DEFAULTS.items():
        _FLAGS[k] = v
    for k, v in os.environ.items():
        if k.startswith("FLAGS_") and k in _KNOWN:
            _FLAGS[k] = _parse(v)
    if _FLAGS.get("FLAGS_check_nan_inf"):
        # env-var activation (FLAGS_check_nan_inf=1 python train.py)
        # must wire the hook exactly like set_flags does
        _wire_nan_check()
    if _FLAGS.get("FLAGS_use_autotune"):
        _wire_autotune()


def _wire_autotune():
    from ..incubate import autotune as _at
    _at.set_config({"kernel": {"enable": bool(
        _FLAGS.get("FLAGS_use_autotune"))}})


def _wire_nan_check():
    from ..core import tensor as tensor_mod
    tensor_mod._nan_check_hook = (
        _check_nan_inf if _FLAGS.get("FLAGS_check_nan_inf") else None)


def _parse(v: str):
    low = v.lower()
    if low in ("true", "1"):
        return True
    if low in ("false", "0"):
        return False
    try:
        return int(v)
    except ValueError:
        pass
    try:
        return float(v)
    except ValueError:
        pass
    return v


def get_flags(flags):
    if isinstance(flags, str):
        flags = [flags]
    return {f: _FLAGS.get(f) for f in flags}


def set_flags(flags: dict):
    for k in flags:
        if k not in _KNOWN:
            raise ValueError(
                f"flag {k} is not registered in this build "
                "(utils/flags.py flag_audit() lists the surface; "
                "reference parity: framework.py set_flags rejects "
                "unregistered flags)")
    for k, v in flags.items():
        _FLAGS[k] = v
    if "FLAGS_use_autotune" in flags:
        _wire_autotune()
    if "FLAGS_check_nan_inf" in flags:
        # wire the debug scanner into the op dispatch (reference:
        # framework/details/nan_inf_utils_detail.* hooked at
        # operator.cc:1601 and eager/nan_inf_utils.cc)
        _wire_nan_check()


def _check_nan_inf(op_name, outs):
    """Raise on the FIRST op producing a non-finite value — the
    reference's per-op output scan, eager only (a device sync per op:
    strictly a debugging mode)."""
    import numpy as np
    import jax.numpy as jnp
    for i, o in enumerate(outs):
        if not jnp.issubdtype(o.dtype, jnp.floating):
            continue
        if not bool(jnp.isfinite(o).all()):
            arr = np.asarray(o)
            raise FloatingPointError(
                f"Operator {op_name} output {i} contains "
                f"{int(np.isnan(arr).sum())} nan / "
                f"{int(np.isinf(arr).sum())} inf values "
                f"(shape {list(arr.shape)}); FLAGS_check_nan_inf is on")


def get_flag(name, default=None):
    return _FLAGS.get(name, default)


_bootstrap()
