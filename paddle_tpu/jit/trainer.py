"""Single-XLA-program training step.

This is SURVEY.md §7's north star made concrete: forward + backward +
optimizer update compiled into ONE XLA computation with donated
parameter/state buffers. The reference needs InterpreterCore + eager
autograd + per-param optimizer ops; here the whole step is one
`PjRtLoadedExecutable` — XLA fuses, schedules collectives over the mesh
axes, and reuses parameter memory in place.

Used by bench.py, __graft_entry__.dryrun_multichip, and available as
`paddle_tpu.jit.compile_train_step` for users.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from ..core.tensor import Tensor, Parameter
from ..core import random as random_mod
from ..core import dtype as dtypes
from ..profiler import RecordEvent

__all__ = ["compile_train_step", "CompiledTrainStep"]

# host span of one call (`profiler.RecordEvent`: in a JAX profiler trace
# it sits on the clock of the device's `XLA Ops`): the launch of the one
# compiled program, for an XProf capture against a running job
SPAN_STEP = "train::step"


class CompiledTrainStep:
    """Owns the functionalized (params, opt-state) pytree and the jitted
    step(params, states, gstate, key, *batch) -> (loss, new_params,
    new_states, new_gstate)."""

    def __init__(self, loss_fn, model, optimizer, donate=True,
                 in_shardings=None, accumulate_steps=None):
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        # gradient merge (reference distributed_strategy.proto:81
        # GradientMergeConfig): k micro-batches scanned INSIDE the one
        # compiled step, optimizer applied once on the averaged grads.
        # Explicit arg wins; else the fleet strategy tag on the optimizer
        self.accumulate_steps = int(
            accumulate_steps
            if accumulate_steps is not None
            else getattr(optimizer, "_gradient_merge_k", 1) or 1)
        self.accumulate_avg = bool(
            getattr(optimizer, "_gradient_merge_avg", True))
        self.params = [p for p in model.parameters()
                       if (p.trainable if isinstance(p, Parameter)
                           else not p.stop_gradient)]
        self.buffers = [b for _, b in model.named_buffers()]
        self.state_tensors = self.params + self.buffers
        self.n_params = len(self.params)
        self.states = [dict(optimizer._state_for(p)) for p in self.params]
        # live global state (beta-pow counters etc.) when the optimizer
        # already has one — a rebuild mid-training (or after a
        # checkpoint load) must not reset bias correction to step 0
        live_g = getattr(optimizer, "_gstate", None)
        self.gstate = (dict(live_g) if live_g else
                       {k: jnp.asarray(v) for k, v in
                        optimizer._global_state_spec().items()})
        self._grad_clip = optimizer._grad_clip
        decay = optimizer._decay if not getattr(optimizer, "_decoupled",
                                                False) else 0.0
        extras = optimizer._per_param_extra(self.params)
        rule = optimizer._apply_rule
        advance = optimizer._advance_global
        n_p = self.n_params
        n_b = len(self.buffers)
        state_tensors = self.state_tensors
        loss_fn_ = loss_fn

        accum = self.accumulate_steps

        def step(param_vals, buffer_vals, states, gstate, lr, key,
                 *batch_vals):
            def loss_of(pvals, bufs, mb_vals, mb_key):
                originals = [t._value for t in state_tensors]
                random_mod.push_trace_key(mb_key)
                try:
                    for t, v in zip(state_tensors,
                                    list(pvals) + list(bufs)):
                        t._value = v
                    batch = [Tensor(b) for b in mb_vals]
                    out = loss_fn_(*batch)
                    loss_val = out._value if isinstance(out, Tensor) \
                        else out
                    new_buf = tuple(t._value
                                    for t in state_tensors[n_p:])
                    return loss_val.astype(jnp.float32), new_buf
                finally:
                    random_mod.pop_trace_key()
                    for t, v in zip(state_tensors, originals):
                        t._value = v

            if accum > 1:
                # micro-batch scan: leading batch dim splits into
                # (accum, per_micro); f32 grad accumulators; one
                # optimizer application on the merged grads. Positional
                # batch args must lead with the batch dim; 0-d scalars
                # are broadcast to every micro-batch unchanged
                split = []
                for ai, b in enumerate(batch_vals):
                    if b.ndim == 0:
                        split.append(None)
                        continue
                    if b.shape[0] % accum:
                        raise ValueError(
                            f"batch arg {ai}: leading dim {b.shape[0]} "
                            f"not divisible by accumulate_steps={accum}")
                    split.append(b.reshape(
                        (accum, b.shape[0] // accum) + b.shape[1:]))

                def micro(carry, xs):
                    acc, bufs = carry
                    idx, mb = xs
                    full = [b if s is None else m
                            for b, s, m in zip(batch_vals, split, mb)]
                    mb_key = jax.random.fold_in(key, idx)
                    (l, nb), g = jax.value_and_grad(
                        loss_of, has_aux=True)(
                            list(param_vals), bufs, full, mb_key)
                    acc = [a + gi.astype(jnp.float32)
                           for a, gi in zip(acc, g)]
                    return (acc, nb), l

                acc0 = [jnp.zeros(p.shape, jnp.float32)
                        for p in param_vals]
                mb_xs = [jnp.zeros((accum,)) if s is None else s
                         for s in split]
                (gsum, new_bufs), losses = jax.lax.scan(
                    micro, (acc0, tuple(buffer_vals)),
                    (jnp.arange(accum), mb_xs))
                # avg=True (default): mean over micro-batches == the
                # full-batch grad; avg=False keeps the reference's sum
                # semantics (GradientMergeConfig.avg)
                denom = accum if self.accumulate_avg else 1
                grads = [(g / denom).astype(p.dtype)
                         for g, p in zip(gsum, param_vals)]
                loss = jnp.mean(losses)
            else:
                (loss, new_bufs), grads = jax.value_and_grad(
                    loss_of, has_aux=True)(
                        list(param_vals), list(buffer_vals),
                        list(batch_vals), key)
            if self._grad_clip is not None:
                from ..nn.clip import apply_grad_clip_values
                grads = apply_grad_clip_values(self._grad_clip, grads)
            new_params, new_states = [], []
            g2 = dict(gstate)
            for i, (p, g, s) in enumerate(zip(param_vals, grads, states)):
                if decay:
                    g = g + decay * p
                optimizer._cur_extra = (extras[i] if extras is not None
                                        else None)
                np_, ns = rule(p, g, s, g2, lr)
                new_params.append(np_)
                new_states.append(ns)
            g2 = advance(g2)
            return loss, new_params, list(new_bufs), new_states, g2

        # ZeRO offload: donated pinned_host state buffers trip
        # unimplemented hbm-to-hbm DMAs in the TPU AOT path — keep
        # params/buffers donated but not the host-resident states
        if getattr(optimizer, "_offload", False):
            donate_args = (0, 1) if donate else ()
        else:
            donate_args = (0, 1, 2, 3) if donate else ()
        self._step = jax.jit(step, donate_argnums=donate_args)
        self._target_mesh = self._harmonize_placements()

    def _harmonize_placements(self):
        """Co-locate params/buffers/optimizer state on one device set.

        One jitted program cannot consume arrays committed to different
        device sets (a model built while a mesh was active mixes 8-device
        and 1-device arrays the moment the mesh context ends). Target:
        the active mesh if set, else the mesh the parameters already live
        on, else the default device. Values already holding a
        NamedSharding on the target mesh keep their layout (TP shards
        survive); stragglers are replicated onto it."""
        from jax.sharding import NamedSharding, PartitionSpec
        from ..distributed.mesh import get_mesh
        pm = get_mesh()
        target = pm.jax_mesh if pm is not None else None
        if target is None:
            for t in self.state_tensors:
                sh = getattr(t._value, "sharding", None)
                if isinstance(sh, NamedSharding) and sh.mesh.size > 1:
                    target = sh.mesh
                    break
        if target is None:
            dev = jax.devices()[0]

            def place(v):
                devs = getattr(getattr(v, "sharding", None),
                               "device_set", None)
                if devs is not None and devs != {dev}:
                    return jax.device_put(v, dev)
                return v
        else:
            rep = NamedSharding(target, PartitionSpec())

            def place(v):
                sh = getattr(v, "sharding", None)
                if isinstance(sh, NamedSharding) and sh.mesh == target:
                    return v
                return jax.device_put(v, rep)

        for t in self.state_tensors:
            t._rebind(place(t._value))
        self.states = [{k: place(v) for k, v in s.items()}
                       for s in self.states]
        self.gstate = {k: place(v) for k, v in self.gstate.items()}
        return target

    def _place_batch(self, v):
        """Batch values must join the step's device set too; anything the
        caller didn't shard (via dist.shard_batch) gets replicated."""
        from jax.sharding import NamedSharding, PartitionSpec
        target = self._target_mesh
        if target is None:
            return v
        sh = getattr(v, "sharding", None)
        if isinstance(sh, NamedSharding) and sh.mesh == target:
            return v
        return jax.device_put(v, NamedSharding(target, PartitionSpec()))

    def __call__(self, *batch):
        batch_vals = [self._place_batch(
            b._value if isinstance(b, Tensor) else jnp.asarray(b))
            for b in batch]
        # host-side scalars/keys: jit transfers them with the call; an
        # eager jnp.asarray here would be one more dispatch per step
        lr = np.float32(self.optimizer.get_lr())
        key = random_mod.next_key_host()
        p_vals = [p._value for p in self.params]
        b_vals = [b._value for b in self.buffers]
        try:
            with RecordEvent(SPAN_STEP):
                loss, new_p, new_b, new_s, new_g = self._step(
                    p_vals, b_vals, self.states, self.gstate, lr, key,
                    *batch_vals)
        except NotImplementedError as e:
            if "Mosaic kernels cannot be automatically partitioned" \
                    in str(e):
                e.add_note(
                    "paddle_tpu: under a training mesh on real chips "
                    "the Pallas kernels (flash attention, fused "
                    "LayerNorm) are not yet run per device — ROADMAP "
                    "S8. The serving replica's are "
                    "(ops/pallas.kernel_mesh).")
            raise
        for p, v in zip(self.params, new_p):
            p._rebind(v)
        for b, v in zip(self.buffers, new_b):
            b._rebind(v)
        off = getattr(self.optimizer, "_offload_put", None)
        if off is not None:  # ZeRO offload: states back to host memory
            new_s = [off(s) for s in new_s]
        self.states = new_s
        self.gstate = new_g
        # keep the eager optimizer's view coherent for state_dict()
        for p, s in zip(self.params, self.states):
            self.optimizer._accumulators[id(p)] = s
        self.optimizer._gstate = self.gstate
        if self.optimizer._lr_scheduler is not None:
            pass  # scheduler stepping stays the caller's choice
        return Tensor(loss)

    def compile_info(self, *batch):
        """Lower + return the compiled HLO text (for inspection)."""
        batch_vals = [self._place_batch(
            b._value if isinstance(b, Tensor) else jnp.asarray(b))
            for b in batch]
        lr = jnp.asarray(0.0, jnp.float32)
        key = random_mod.next_key()
        p_vals = [p._value for p in self.params]
        b_vals = [b._value for b in self.buffers]
        return self._step.lower(p_vals, b_vals, self.states, self.gstate,
                                lr, key, *batch_vals)


def compile_train_step(loss_fn, model, optimizer, donate=True,
                       accumulate_steps=None):
    """loss_fn(*batch_tensors) -> scalar loss Tensor, closing over
    `model`. Returns a callable: step(*batch) -> loss.

    accumulate_steps=k scans k micro-batches (leading batch dim split
    k ways) inside the one compiled program — gradient merge, reference
    distributed_strategy.proto:81."""
    return CompiledTrainStep(loss_fn, model, optimizer, donate=donate,
                             accumulate_steps=accumulate_steps)
