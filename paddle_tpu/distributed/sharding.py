"""ZeRO-style sharded training.

TPU-native replacement for group_sharded / GroupSharded stages 1-3
(reference: python/paddle/distributed/sharding/group_sharded.py;
fleet/meta_parallel/sharding/group_sharded_optimizer_stage2.py:53,
group_sharded_stage2.py:46, group_sharded_stage3.py:61). The reference
manually partitions optimizer states / grads / params across ranks with
broadcast + reduce-scatter choreography and forward prefetch (TaskFlow).
Under GSPMD the same memory behavior (SURVEY.md §7: "match memory
behavior, not mechanism") comes from sharding annotations:

- stage 1: optimizer accumulators sharded over the "sharding" axis;
- stage 2: + gradients reduce-scattered (XLA picks this when param
  updates consume sharded states);
- stage 3: + parameters sharded over the axis; XLA all-gathers weights
  just-in-time per layer — the TaskFlow prefetch, scheduled by the
  compiler.
"""
from __future__ import annotations

import numpy as np
import jax
from jax.sharding import PartitionSpec as P, NamedSharding

from ..core.tensor import Tensor
from .mesh import get_mesh, shard_tensor

__all__ = ["group_sharded_parallel", "save_group_sharded_model",
           "shard_optimizer_states", "shard_parameters",
           "shard_gradients"]


def _shard_axis_available(axis):
    m = get_mesh()
    return (m is not None and axis in m.dim_names
            and m.get_dim_size(axis) > 1)


def _spec_for(shape, axis, min_size=1):
    """Shard the largest divisible dim over the axis; replicate if none."""
    m = get_mesh()
    n = m.get_dim_size(axis)
    order = sorted(range(len(shape)), key=lambda i: -shape[i])
    for d in order:
        if shape[d] % n == 0 and shape[d] >= n * min_size:
            entries = [None] * len(shape)
            entries[d] = axis
            return P(*entries)
    return P()


def shard_parameters(model, axis="sharding"):
    if not _shard_axis_available(axis):
        return model
    for p in model.parameters():
        spec = _spec_for(tuple(p.shape), axis)
        shard_tensor(p, spec=spec)
    return model


def shard_gradients(model, axis="sharding"):
    """ZeRO stage-2: leaf gradients MATERIALIZE sharded over the axis —
    the tape places each parameter grad onto its 1/n slice the moment it
    is accumulated (core/tensor.py deposit), the eager analogue of the
    reference's explicit reduce-scatter bookkeeping
    (group_sharded_stage2.py:46). Per-device grad memory is
    grad_bytes/n, verified by TestZeroMemoryScaling."""
    if not _shard_axis_available(axis):
        return model
    mesh = get_mesh()
    for p in model.parameters():
        spec = _spec_for(tuple(p.shape), axis)
        if spec == P():
            continue
        sh = NamedSharding(mesh.jax_mesh, spec)
        p._grad_spec = (lambda g, _sh=sh: jax.device_put(g, _sh))
    return model


def _offload_supported():
    """pinned_host memory-kind round-trips through jit on TPU/GPU PJRT;
    the CPU backend hard-aborts on host-kind executable inputs."""
    try:
        return jax.devices()[0].platform in ("tpu", "gpu")
    except Exception:
        return False


def shard_optimizer_states(optimizer, axis="sharding", offload=False):
    """Annotate accumulator specs so states materialize sharded: wraps
    _accumulator_specs to device_put each initial state with a sharded
    layout; the fused update keeps layouts, so optimizer memory is
    state_bytes/n per device.

    offload=True additionally places the states in HOST memory
    (memory_kind="pinned_host") and wraps the update rule with
    host->device / device->host transfers inside the compiled step — the
    TPU-native form of the reference's CPU offload
    (group_sharded_stage3.py:61 offload=True: states live on CPU, are
    fetched for the update, and written back). XLA schedules the
    transfers asynchronously; device memory holds no optimizer state
    between steps."""
    mesh_ok = _shard_axis_available(axis)
    use_host = bool(offload) and _offload_supported()
    if offload and not use_host:
        import warnings
        warnings.warn(
            "optimizer-state offload needs a TPU/GPU backend with "
            "pinned_host memory support; states stay in device memory "
            "(sharding annotations still apply)")
    if not mesh_ok and not use_host:
        return optimizer
    mesh = get_mesh() if mesh_ok else None
    jax_mesh = mesh.jax_mesh if mesh is not None else None
    dev0 = jax.devices()[0]

    def _sharding(shape, kind):
        if jax_mesh is not None:
            return NamedSharding(jax_mesh, _spec_for(tuple(shape), axis),
                                 memory_kind=kind)
        from jax.sharding import SingleDeviceSharding
        return SingleDeviceSharding(dev0, memory_kind=kind)

    orig = optimizer._accumulator_specs

    def sharded_specs(p):
        specs = orig(p)
        kind = "pinned_host" if use_host else "device"
        return {name: jax.device_put(arr, _sharding(arr.shape, kind))
                for name, arr in specs.items()}

    optimizer._accumulator_specs = sharded_specs

    if use_host:
        orig_rule = optimizer._apply_rule

        def offload_rule(p, g, s, gstate, lr):
            # host->device INSIDE the compiled step (XLA schedules the
            # fetch); the device->host write-back happens eagerly after
            # the step via _offload_put — returning host-memory outputs
            # from the entry computation trips AOT layout checks. The new
            # param is pinned to device memory explicitly: with donated
            # host states, XLA's memory-kind inference otherwise leaks
            # pinned_host onto the weight output.
            s_dev = {k: jax.device_put(v, _sharding(v.shape, "device"))
                     for k, v in s.items()}
            new_p, ns = orig_rule(p, g, s_dev, gstate, lr)
            new_p = jax.device_put(new_p, _sharding(new_p.shape,
                                                    "device"))
            return new_p, ns

        def offload_put(state_dict):
            return {k: jax.device_put(v, _sharding(v.shape,
                                                   "pinned_host"))
                    for k, v in state_dict.items()}

        optimizer._apply_rule = offload_rule
        optimizer._offload_put = offload_put
        optimizer._offload = True
    return optimizer


def group_sharded_parallel(model, optimizer, level, scaler=None,
                           group=None, offload=False, sync_buffers=False,
                           buffer_max_size=2 ** 23, segment_size=2 ** 20,
                           sync_comm=False, dp_group=None,
                           exclude_layer=None):
    """reference: distributed/sharding/group_sharded.py
    group_sharded_parallel(model, optimizer, level in {os, os_g, p_g_os}).
    """
    if level not in ("os", "os_g", "p_g_os"):
        raise ValueError(f"level must be os|os_g|p_g_os, got {level}")
    # params must live on the same mesh the sharded states live on (the
    # fused update consumes both in one program); stage 3 re-shards them
    from .parallel import _place_model_on_mesh
    _place_model_on_mesh(model)
    shard_optimizer_states(optimizer, offload=offload)
    if level in ("os_g", "p_g_os"):
        shard_gradients(model)
        if level == "p_g_os":
            shard_parameters(model)
    if scaler is not None:
        return model, optimizer, scaler
    return model, optimizer


def save_group_sharded_model(model, output, optimizer=None):
    """reference: group_sharded.py save_group_sharded_model. Sharded
    jax.Arrays gather transparently in .numpy(), so a plain state_dict
    save is already the 'gather then save' path."""
    import os as _os
    from ..framework.io import save as _save
    _os.makedirs(output, exist_ok=True)
    _save(model.state_dict(), _os.path.join(output, "model.pdmodel"))
    if optimizer is not None:
        _save(optimizer.state_dict(), _os.path.join(output,
                                                    "model.pdopt"))
