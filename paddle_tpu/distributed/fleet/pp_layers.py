"""Pipeline-parallel layers.

TPU-native replacement for PipelineLayer + schedules (reference:
python/paddle/distributed/fleet/meta_parallel/parallel_layers/
pp_layers.py:209 PipelineLayer, :57 LayerDesc, :93 SegmentLayers;
schedules fleet/meta_parallel/pipeline_parallel.py:119 1F1B, :463
interleaved). The reference runs one stage per process with
partial_send/recv p2p and hand-scheduled 1F1B. Here all stages live in
ONE compiled program:

* The repeated (homogeneous) blocks' parameters are STACKED along a new
  leading layer axis and that axis is sharded over the "pp" mesh axis —
  each pp device group physically holds 1/num_stages of the block
  parameters (the reference's per-process stage ownership, expressed as
  GSPMD placement).
* forward() runs the GPipe fill/drain schedule inside a shard_map over
  "pp": at step t, stage s computes microbatch t-s and hands its
  activation to stage s+1 with `lax.ppermute` (the ICI hop that replaces
  the reference's partial_send/recv p2p). M + S - 1 steps total — the
  standard GPipe bubble. The schedule lives under `lax.scan`, so its
  reverse-mode transpose IS the backward pipeline schedule: jax.vjp
  derives the reference's hand-written backward p2p loop automatically.
* Per-microbatch activation memory is bounded with jax.checkpoint around
  each block (the reference's recompute_interval knob).

Three schedules:

* **GPipe (FThenB, default)**: the fill/drain scan above; backward is
  the AD transpose.
* **Interleaved virtual pipeline** (`num_virtual_pipeline_stages` > 1,
  reference pipeline_parallel.py:463): each pp device owns `vpp`
  non-contiguous block chunks (virtual stages). The scan runs in fine
  ticks of one CHUNK application; a microbatch hops device s chunk c ->
  device s+1 chunk c (wrapping to chunk c+1 at the boundary). Fill/
  drain cost one chunk (L/(S*vpp) layers) per tick instead of a full
  stage, shrinking the pipeline bubble by the vpp factor.
* **1F1B** (`PipelineParallel` schedule "1F1B", reference
  pipeline_parallel.py:119): a manually-differentiated train step —
  one scan interleaves forward and backward micro-steps so at most S
  microbatch activations are ever in flight (ring buffer), vs M+S-1
  live microbatches in the transposed GPipe scan. Embedding (pre),
  head (post) and the loss run INSIDE stage 0 / stage S-1 of the
  schedule — the heterogeneous first/last stages of the reference —
  and the step returns (loss, param grads) directly.

Heterogeneous extras (embedding before, head after the block run)
execute outside the pipelined section in the forward schedules and
inside it in 1F1B. If the layer list has no stackable homogeneous run
(or pp degree is 1), forward falls back to plain sequential execution —
correct, just not pipelined — and warns.
"""
from __future__ import annotations

import functools
import math
import re
import warnings

import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ...nn.layer.layers import Layer
from ...nn.layer.container import LayerList, Sequential
from ...core.tensor import Tensor, Parameter, apply_op
from ...core.dispatch import OpDef
from ...core import random as random_mod

__all__ = ["LayerDesc", "SharedLayerDesc", "PipelineLayer",
           "SegmentLayers", "PipelineParallel"]


class LayerDesc:
    """reference: pp_layers.py:57."""

    def __init__(self, layer_func, *inputs, **kwargs):
        self.layer_func = layer_func
        self.inputs = inputs
        self.kwargs = kwargs
        if not issubclass(layer_func, Layer):
            raise TypeError("LayerDesc expects a Layer subclass")

    def build_layer(self):
        return self.layer_func(*self.inputs, **self.kwargs)

    def __repr__(self):
        return f"LayerDesc({self.layer_func.__name__})"


class SharedLayerDesc(LayerDesc):
    """reference: pp_layers.py:77 — layers shared between stages (e.g.
    embedding/unembedding weight tying)."""

    def __init__(self, key, layer_func, forward_func=None,
                 shared_weight_attr="weight", *inputs, **kwargs):
        super().__init__(layer_func, *inputs, **kwargs)
        self.layer_name = key
        self.forward_func = forward_func
        self.shared_weight_attr = shared_weight_attr


class SegmentLayers:
    """reference: pp_layers.py:93 — split N layers into S stages."""

    def __init__(self, layers_desc, num_parts, method="uniform"):
        self.layers_desc = layers_desc
        self.num_parts = num_parts
        self.method = method

    def do_segment(self):
        n = len(self.layers_desc)
        if self.method == "uniform":
            return self.uniform(n, self.num_parts)
        m = re.match(r"layer:(.+)", self.method)
        if m:
            name = m.group(1)
            hits = [i for i, d in enumerate(self.layers_desc)
                    if (d.layer_func.__name__ if isinstance(d, LayerDesc)
                        else type(d).__name__) == name]
            if len(hits) < self.num_parts:
                raise ValueError(
                    f"cannot split {len(hits)} x {name} into "
                    f"{self.num_parts} stages")
            per = len(hits) // self.num_parts
            extra = len(hits) % self.num_parts
            result = [0]
            idx = 0
            for p in range(self.num_parts):
                take = per + (1 if p < extra else 0)
                idx += take
                result.append(hits[idx - 1] + 1 if idx > 0 else 0)
            result[-1] = n
            return result
        raise ValueError(f"bad segment method {self.method}")

    @staticmethod
    def uniform(num_items, num_parts):
        result = [0] * (num_parts + 1)
        part_size = math.floor(num_items / num_parts)
        extra = num_items % num_parts
        for i in range(1, num_parts + 1):
            result[i] = result[i - 1] + part_size + \
                (1 if i <= extra else 0)
        return result


def _param_signature(layer):
    """(class-name, sorted (param-name, shape, dtype)) — stackability key."""
    sig = tuple(sorted(
        (n, tuple(p.shape), str(p.dtype))
        for n, p in layer.named_parameters()))
    return (type(layer).__name__, sig)


class PipelineLayer(Layer):
    """reference: pp_layers.py:209. Builds ALL stages (single-controller
    owns the whole mesh). The homogeneous block run is stacked along a
    leading layer axis sharded over "pp" (stage-s parameters live on
    stage-s devices), and forward runs the compiled GPipe microbatch
    schedule — see module docstring."""

    def __init__(self, layers, num_stages=None, topology=None,
                 loss_fn=None, seg_method="uniform",
                 recompute_interval=0, recompute_ctx=None,
                 num_virtual_pipeline_stages=None, num_microbatches=None):
        super().__init__()
        self._layers_desc = list(layers)
        if topology is not None:
            self._num_stages = topology.get_dim("pipe")
        else:
            self._num_stages = num_stages or 1
        self._loss_fn = loss_fn
        self._recompute_interval = recompute_interval
        self._n_micro = num_microbatches or max(self._num_stages, 1)
        self._vpp = num_virtual_pipeline_stages or 1
        seg = SegmentLayers(self._layers_desc, self._num_stages,
                            seg_method)
        self.segment_parts = seg.do_segment()

        # Build every desc into a runnable (or callable) first.
        objs, runs = [], []
        self._shared = {}
        for desc in self._layers_desc:
            if isinstance(desc, SharedLayerDesc):
                if desc.layer_name not in self._shared:
                    self._shared[desc.layer_name] = desc.build_layer()
                lyr = self._shared[desc.layer_name]
                fwd = desc.forward_func
                run = (lambda l=lyr, f=fwd:
                       (lambda *x: f(l, *x) if f else l(*x)))()
            elif isinstance(desc, LayerDesc):
                lyr = desc.build_layer()
                run = lyr
            elif isinstance(desc, Layer):
                lyr = desc
                run = lyr
            elif callable(desc):
                lyr = None
                run = desc
            else:
                raise TypeError(f"bad pipeline entry {desc!r}")
            objs.append(lyr)
            runs.append(run)

        lo, hi = self._find_stackable_run(objs, runs)
        self._pipelined = (self._num_stages > 1 and lo is not None)

        built = LayerList()
        self.run_function = []
        self._stage_of = []
        stage_bound = self.segment_parts
        if self._pipelined:
            blocks = objs[lo:hi]
            self._n_blocks = len(blocks)
            if self._vpp > 1 and self._n_blocks % (
                    self._num_stages * self._vpp) != 0:
                warnings.warn(
                    f"{self._n_blocks} pipelined blocks not divisible "
                    f"by pp*vpp = {self._num_stages}*{self._vpp}; "
                    "running without virtual pipeline stages")
                self._vpp = 1
            self._pre_runs = runs[:lo]
            self._post_runs = runs[hi:]
            # template holds the param binding slots; NOT registered as a
            # sublayer (its values are always rebound from the stack).
            object.__setattr__(self, "_template_block", blocks[0])
            object.__setattr__(
                self, "_template_params",
                [p for _, p in sorted(blocks[0].named_parameters())])
            self._stack_block_params(blocks)
            for r in self._pre_runs + self._post_runs:
                if isinstance(r, Layer):
                    built.append(r)
            for lyr in self._shared.values():
                if lyr not in list(built):
                    built.append(lyr)
            # hetero (pre/post/shared) params: pipelined by the 1F1B
            # schedule as the first/last heterogeneous stages. Bare
            # callables are scanned one closure level deep so a
            # function entry referencing a Layer/Parameter (e.g. a
            # tied-weight head) still trains under 1F1B instead of
            # having its weights silently baked as jit constants.
            hp, seen = [], set()

            def _collect(obj):
                if isinstance(obj, Layer):
                    for p in obj.parameters(include_sublayers=True):
                        _collect(p)
                elif isinstance(obj, Tensor):
                    # grads are only deposited on trainable entries,
                    # but every referenced value must be an op INPUT
                    # (not a baked constant) so updates propagate
                    if id(obj) not in seen:
                        seen.add(id(obj))
                        hp.append(obj)

            for r in (list(self._pre_runs) + list(self._post_runs)
                      + list(self._shared.values())):
                if isinstance(r, Layer):
                    _collect(r)
                elif callable(r):
                    for cell in (getattr(r, "__closure__", None) or ()):
                        try:
                            _collect(cell.cell_contents)
                        except ValueError:
                            pass
            self._hetero_params = hp
        else:
            if self._num_stages > 1:
                warnings.warn(
                    "PipelineLayer: no stackable homogeneous block run "
                    f"for pp={self._num_stages}; executing SEQUENTIALLY "
                    "(no pipelining). Make the repeated blocks uniform "
                    "(same class, param shapes, no buffers) to enable "
                    "the compiled pipeline schedules.")
            for i, (lyr, run) in enumerate(zip(objs, runs)):
                stage = next(s for s in range(self._num_stages)
                             if stage_bound[s] <= i < stage_bound[s + 1])
                if lyr is not None:
                    built.append(lyr)
                self.run_function.append(run)
                self._stage_of.append(stage)
        self._built = built
        self._pipe_ops = {}

    # -- stacking ---------------------------------------------------------

    def _find_stackable_run(self, objs, runs):
        """Longest contiguous run of same-class, same-param-shape Layers
        (no buffers, not shared) that divides evenly by num_stages."""
        best = (None, None)
        best_len = 0
        i = 0
        n = len(objs)
        while i < n:
            if objs[i] is None or runs[i] is not objs[i] \
                    or objs[i] in self._shared.values() \
                    or list(objs[i].named_buffers()) \
                    or not list(objs[i].named_parameters()):
                i += 1
                continue
            sig = _param_signature(objs[i])
            j = i + 1
            while j < n and objs[j] is not None and runs[j] is objs[j] \
                    and objs[j] not in self._shared.values() \
                    and not list(objs[j].named_buffers()) \
                    and _param_signature(objs[j]) == sig:
                j += 1
            run_len = j - i
            if run_len > best_len and run_len >= self._num_stages \
                    and run_len % self._num_stages == 0:
                best, best_len = (i, j), run_len
            i = j
        return best

    def _stack_block_params(self, blocks):
        """Stack per-block params into [n_blocks, ...] Parameters, sharded
        over the pp mesh axis when one is active (stage ownership).

        With vpp > 1 the stack order is DEVICE-major: device s's chunks
        (virtual stages s, s+S, ..., s+(vpp-1)S) are contiguous, so the
        plain P("pp") leading-axis sharding still gives each device
        exactly its own blocks."""
        from ..mesh import get_mesh, shard_tensor
        pm = get_mesh()
        pp_on = (pm is not None and "pp" in pm.dim_names
                 and pm.get_dim_size("pp") > 1)
        S, vpp, L = self._num_stages, self._vpp, len(blocks)
        if vpp > 1:
            l_c = L // (S * vpp)
            order = [v * l_c + i
                     for s in range(S)
                     for c in range(vpp)
                     for v in (c * S + s,)
                     for i in range(l_c)]
        else:
            order = list(range(L))
        self._stack_order = order
        # persisted layout witness: the stacked arrays are stored in
        # this block order (device-major under vpp). Loading a
        # checkpoint saved with a different num_virtual_pipeline_stages
        # rebinds this buffer, and _check_stack_layout turns the
        # otherwise-silent block permutation into a loud error.
        self.register_buffer("pp_stack_order",
                             Tensor(jnp.asarray(order, dtype=jnp.int32)))
        names = [n for n, _ in sorted(blocks[0].named_parameters())]
        self._stack_names = names
        self._stacked = []
        for k, name in enumerate(names):
            vals = [dict(blocks[j].named_parameters())[name]._value
                    for j in order]
            p0 = dict(blocks[0].named_parameters())[name]
            arr = jnp.stack(vals)
            sp = Parameter(arr, trainable=(
                p0.trainable if isinstance(p0, Parameter)
                else not p0.stop_gradient))
            attr = "stacked_" + name.replace(".", "_")
            self.add_parameter(attr, sp)
            self._stacked.append(sp)
            if pp_on:
                shard_tensor(sp, pm, spec=P("pp"))

    def _check_stack_layout(self):
        val = self.pp_stack_order._value
        if isinstance(val, jax.core.Tracer):
            # inside a compiled train step the buffer is a traced value
            # (CompiledTrainStep rebinds all buffers); the layout was
            # already validated on the eager warm-up call
            return
        loaded = np.asarray(val).tolist()
        if loaded != self._stack_order:
            raise ValueError(
                "this checkpoint's stacked block layout "
                f"{loaded} does not match the model's "
                f"{self._stack_order} — it was saved with a different "
                "num_virtual_pipeline_stages. Rebuild the PipelineLayer "
                "with the same vpp it was trained with.")

    # -- schedule ---------------------------------------------------------

    def _block_apply(self, h, plist, key):
        """Run the template block with `plist` bound as its parameters.
        Pure given (h, plist, key); usable under any jax trace."""
        tpl_params = self._template_params
        originals = [p._value for p in tpl_params]
        random_mod.push_trace_key(key)
        try:
            for p, v in zip(tpl_params, plist):
                p._value = v
            out = self._template_block(Tensor(h))
            hv = out._value if isinstance(out, Tensor) else out
        finally:
            random_mod.pop_trace_key()
            for p, v in zip(tpl_params, originals):
                p._value = v
        return hv.astype(h.dtype)

    def _stage_scan(self, h, pv_local, key, t, l_per, stage=0):
        """Apply this device's l_per consecutive blocks (a lax.scan)."""
        remat = self._recompute_interval > 0

        def one_layer(carry, xs):
            li = xs[0]
            plist = xs[1:]
            # fold in the GLOBAL layer index (stage*l_per + li): stages run
            # concurrently at the same t and must not share dropout masks
            k = jax.random.fold_in(jax.random.fold_in(key, t),
                                   stage * l_per + li)
            return self._block_apply(carry, plist, k), None

        body = jax.checkpoint(one_layer) if remat else one_layer
        xs = (jnp.arange(l_per),) + tuple(pv_local)
        h, _ = jax.lax.scan(body, h, xs)
        return h

    def _get_pipe_op(self, pm, n_micro):
        """OpDef running the GPipe (vpp=1) or interleaved virtual-
        pipeline (vpp>1) schedule over `pm`'s pp axis."""
        key_ = (id(pm.jax_mesh), n_micro, self._vpp)
        op = self._pipe_ops.get(key_)
        if op is not None:
            return op
        from ..mesh import manual_collective_mode
        mesh = pm.jax_mesh
        S = pm.get_dim_size("pp") if "pp" in pm.dim_names else 1
        L = self._n_blocks
        if S > 1 and L % S != 0:
            raise ValueError(
                f"{L} pipelined blocks not divisible by pp={S}")
        l_per = L // max(S, 1)
        dp_ax = "dp" if ("dp" in pm.dim_names
                         and pm.get_dim_size("dp") > 1) else None
        M = n_micro
        vpp = self._vpp if S > 1 else 1

        def body_interleaved(x_m, key, *pvals):
            # Fine-tick interleaved schedule (reference
            # pipeline_parallel.py:463): tick t, device s runs ONE chunk
            # application — chunk c of microbatch m where, with
            # delta = t - s:  g = delta // (S*vpp), r = delta % (S*vpp),
            # c = r // S, m = g*S + r%S. A chunk output ppermuted to
            # s+1 arrives exactly when virtual stage v+1 is scheduled,
            # including the wrap device S-1 chunk c -> device 0 chunk
            # c+1. Fill/drain cost one CHUNK per tick: bubble is vpp
            # times smaller than GPipe's.
            stage = jax.lax.axis_index("pp")
            l_c = l_per // vpp
            T = M * vpp + S - 1
            pv_r = [p.reshape((vpp, l_c) + p.shape[1:]) for p in pvals]
            state = jnp.zeros_like(x_m[0])
            outs = jnp.zeros_like(x_m)
            perm = [(i, (i + 1) % S) for i in range(S)]

            def sched_step(carry, t):
                state, outs = carry
                delta = t - stage
                g = jnp.maximum(delta, 0) // (S * vpp)
                r = jnp.maximum(delta, 0) % (S * vpp)
                c = r // S
                m = g * S + (r % S)
                valid = jnp.logical_and(delta >= 0, m < M)
                mc = jnp.clip(m, 0, M - 1)
                first = jnp.logical_and(stage == 0, c == 0)
                x_in = jnp.where(first, x_m[mc], state)
                pv_c = [jax.lax.dynamic_index_in_dim(p, c, 0,
                                                     keepdims=False)
                        for p in pv_r]
                v = c * S + stage  # virtual stage: global layer ids
                y = self._stage_scan(x_in, pv_c, key, mc, l_c, stage=v)
                y = jnp.where(valid, y, x_in)
                w_valid = jnp.logical_and(
                    valid, jnp.logical_and(stage == S - 1, c == vpp - 1))
                outs = outs.at[mc].set(jnp.where(w_valid, y, outs[mc]))
                nxt = jax.lax.ppermute(y, "pp", perm)
                return (nxt, outs), None

            (state, outs), _ = jax.lax.scan(
                sched_step, (state, outs), jnp.arange(T))
            outs = jax.lax.psum(
                outs * (stage == S - 1).astype(outs.dtype), "pp")
            return outs

        def body(x_m, key, *pvals):
            # x_m: [M, mb_local, ...]; pvals: [l_per, ...] local shards
            stage = jax.lax.axis_index("pp") if S > 1 else 0
            T = M + S - 1
            state = jnp.zeros_like(x_m[0])
            outs = jnp.zeros_like(x_m)
            perm = [(i, (i + 1) % S) for i in range(S)]

            def sched_step(carry, t):
                state, outs = carry
                mb_idx = jnp.clip(t, 0, M - 1)
                x_in = jnp.where(stage == 0, x_m[mb_idx], state) \
                    if S > 1 else x_m[mb_idx]
                y = self._stage_scan(x_in, pvals, key, t, l_per,
                                     stage=stage)
                w = t - (S - 1)
                wc = jnp.clip(w, 0, M - 1)
                valid = jnp.logical_and(
                    stage == S - 1,
                    jnp.logical_and(w >= 0, w < M))
                outs = outs.at[wc].set(jnp.where(valid, y, outs[wc]))
                nxt = jax.lax.ppermute(y, "pp", perm) if S > 1 else y
                return (nxt, outs), None

            (state, outs), _ = jax.lax.scan(
                sched_step, (state, outs), jnp.arange(T))
            if S > 1:
                # only the last stage holds real outputs; zero the rest
                # and psum so every pp rank returns the same result
                outs = jax.lax.psum(
                    outs * (stage == S - 1).astype(outs.dtype), "pp")
            return outs

        x_spec = P(None, dp_ax)
        p_specs = tuple(P("pp") if S > 1 else P() for _ in self._stacked)

        sched_body = body_interleaved if vpp > 1 else body
        if vpp > 1 and M % S != 0:
            raise ValueError(
                f"interleaved schedule needs num_microbatches ({M}) "
                f"divisible by pp degree ({S})")

        def fwd(xv, keyv, *pvals):
            b = xv.shape[0]
            if b % M:
                raise ValueError(f"batch {b} not divisible by "
                                 f"num_microbatches {M}")
            mb = b // M
            x_m = xv.reshape((M, mb) + xv.shape[1:])
            with manual_collective_mode():
                if S > 1:
                    out = shard_map(
                        sched_body, mesh=mesh,
                        in_specs=(x_spec, P()) + p_specs,
                        out_specs=x_spec, check_vma=False,
                    )(x_m, keyv, *pvals)
                else:
                    out = body(x_m, keyv, *pvals)
            return out.reshape((b,) + out.shape[2:])

        op = OpDef(f"pipeline_gpipe::{S}x{M}v{vpp}", fwd)
        self._pipe_ops[key_] = op
        return op

    # -- 1F1B -------------------------------------------------------------

    def _hetero_call(self, hvals, fn):
        """Run fn() with the hetero (pre/post/shared) Parameters bound
        to `hvals` — the purity shim that lets jax.vjp differentiate
        through layers whose params live outside the stacked buffer."""
        params = self._hetero_params
        olds = [p._value for p in params]
        try:
            for p, v in zip(params, hvals):
                p._value = v
            return fn()
        finally:
            for p, o in zip(params, olds):
                p._value = o

    @staticmethod
    def _run_chain(runs, x):
        t = x if isinstance(x, Tensor) else Tensor(x)
        for run in runs:
            t = run(t) if not isinstance(t, tuple) else run(*t)
        return t._value if isinstance(t, Tensor) else t

    def _get_1f1b_step(self, pm, n_micro):
        """Compiled 1F1B train step (reference
        pipeline_parallel.py:119 _forward_backward_pipeline).

        One scan over ticks t = 0..2(M+S-1)-2 interleaves forward and
        backward micro-steps: stage s runs forward of microbatch f at
        tick 2f+s and backward of microbatch b at tick 2b+2S-2-s (the
        time-synchronous Megatron 1F1B — each stage alternates F and B
        in steady state). Only a ring buffer of S stage-input
        activations is live per stage, vs M+S-1 for the transposed
        GPipe scan — the 1F1B memory bound. Backward recomputes the
        stage forward from the buffered input (remat) and seeds from
        the IN-SCHEDULE loss at stage S-1: embedding/pre runs inside
        stage 0, head/post + loss inside stage S-1 — the heterogeneous
        first/last stages of the reference — and the step returns
        (loss, stacked grads, hetero grads) directly; there is no tape.
        """
        cache = getattr(self, "_f1b_cache", None)
        if cache is None:
            cache = {}
            object.__setattr__(self, "_f1b_cache", cache)
        key_ = (id(pm.jax_mesh), n_micro)
        if key_ in cache:
            return cache[key_]
        from ..mesh import manual_collective_mode
        if self._vpp > 1:
            raise NotImplementedError(
                "interleaved 1F1B is not supported; use "
                "num_virtual_pipeline_stages=1 with schedule='1F1B'")
        if self._loss_fn is None:
            raise ValueError("1F1B schedule needs loss_fn (the loss is "
                             "computed inside the last stage)")
        mesh = pm.jax_mesh
        S = pm.get_dim_size("pp") if "pp" in pm.dim_names else 1
        if S < 2:
            raise ValueError("1F1B needs pp degree >= 2")
        L = self._n_blocks
        l_per = L // S
        M = n_micro
        dp_ax = "dp" if ("dp" in pm.dim_names
                         and pm.get_dim_size("dp") > 1) else None
        loss_fn = self._loss_fn
        n_stack = len(self._stacked)
        n_het = len(self._hetero_params)

        def pre_fn(x_raw, pv, hv, key, f):
            """Stage-0 chain: hetero pre layers + this stage's blocks."""
            def go():
                k = jax.random.fold_in(jax.random.fold_in(key, f), L)
                random_mod.push_trace_key(k)
                try:
                    return self._run_chain(self._pre_runs, x_raw)
                finally:
                    random_mod.pop_trace_key()
            h = self._hetero_call(hv, go)
            return self._stage_scan(h, pv, key, f, l_per, stage=0)

        def mid_fn(x, pv, key, f, stage):
            return self._stage_scan(x, pv, key, f, l_per, stage=stage)

        def last_fn(x, pv, hv, key, f, labels_mb):
            """Stage-(S-1) chain: blocks + hetero post layers + loss."""
            h = self._stage_scan(x, pv, key, f, l_per, stage=S - 1)

            def go():
                k = jax.random.fold_in(jax.random.fold_in(key, f), L + 1)
                random_mod.push_trace_key(k)
                try:
                    logits = self._run_chain(self._post_runs, h)
                finally:
                    random_mod.pop_trace_key()
                out = loss_fn(Tensor(logits), Tensor(labels_mb))
                return out._value if isinstance(out, Tensor) else out
            return self._hetero_call(hv, go)

        def body(x_m, y_m, keyv, *vals):
            pv = tuple(vals[:n_stack])
            hv = tuple(vals[n_stack:])
            stage = jax.lax.axis_index("pp")
            kind = jnp.where(stage == 0, 0,
                             jnp.where(stage == S - 1, 2, 1))
            hid = jax.eval_shape(
                lambda xr: pre_fn(xr, pv, hv, keyv, 0), x_m[0])

            def zx():
                return jnp.zeros(hid.shape, hid.dtype)

            def zgrads():
                return (tuple(jnp.zeros_like(p) for p in pv),
                        tuple(jnp.zeros_like(h) for h in hv))

            T = 2 * M + 2 * S - 3
            perm_f = [(i, (i + 1) % S) for i in range(S)]
            perm_b = [(i, (i - 1) % S) for i in range(S)]

            def tick(carry, t):
                fwd_msg, bwd_msg, buf, gpv, ghv, loss_acc = carry
                delta = t - stage
                f = jnp.clip(jnp.maximum(delta, 0) // 2, 0, M - 1)
                is_f = jnp.logical_and(
                    delta >= 0, jnp.logical_and(delta % 2 == 0,
                                                delta // 2 < M))
                gamma = t - (2 * S - 2 - stage)
                b = jnp.clip(jnp.maximum(gamma, 0) // 2, 0, M - 1)
                is_b = jnp.logical_and(
                    gamma >= 0, jnp.logical_and(gamma % 2 == 0,
                                                gamma // 2 < M))

                # forward micro-step: stage S-1 only banks its input
                # (all its compute happens fused into the backward)
                x_raw_f = x_m[f]

                def do_f():
                    return jax.lax.switch(kind, [
                        lambda: pre_fn(x_raw_f, pv, hv, keyv, f),
                        lambda: mid_fn(fwd_msg, pv, keyv, f, stage),
                        zx,
                    ])

                y = jax.lax.cond(is_f, do_f, zx)
                buf = buf.at[f % S].set(
                    jnp.where(is_f, fwd_msg, buf[f % S]))

                # backward micro-step: remat the stage forward from the
                # banked input, vjp, hand dx to stage s-1
                x_raw_b = x_m[b]
                lab_b = y_m[b]
                x_buf = buf[b % S]

                def do_b():
                    def b_first():
                        _, vjp_fn = jax.vjp(
                            lambda pv_, hv_: pre_fn(
                                x_raw_b, pv_, hv_, keyv, b), pv, hv)
                        dpv, dhv = vjp_fn(bwd_msg)
                        return (zx(), dpv, dhv,
                                jnp.asarray(0.0, jnp.float32))

                    def b_mid():
                        _, vjp_fn = jax.vjp(
                            lambda x_, pv_: mid_fn(
                                x_, pv_, keyv, b, stage), x_buf, pv)
                        dx, dpv = vjp_fn(bwd_msg)
                        return (dx, dpv,
                                tuple(jnp.zeros_like(h) for h in hv),
                                jnp.asarray(0.0, jnp.float32))

                    def b_last():
                        lval, vjp_fn = jax.vjp(
                            lambda x_, pv_, hv_: last_fn(
                                x_, pv_, hv_, keyv, b, lab_b),
                            x_buf, pv, hv)
                        dx, dpv, dhv = vjp_fn(
                            jnp.asarray(1.0 / M, lval.dtype))
                        return (dx, dpv, dhv,
                                (lval / M).astype(jnp.float32))
                    return jax.lax.switch(kind, [b_first, b_mid, b_last])

                def no_b():
                    zp, zh = zgrads()
                    return (zx(), zp, zh, jnp.asarray(0.0, jnp.float32))

                dx, dpv, dhv, lval = jax.lax.cond(is_b, do_b, no_b)
                gpv = tuple(a + d for a, d in zip(gpv, dpv))
                ghv = tuple(a + d for a, d in zip(ghv, dhv))
                loss_acc = loss_acc + lval
                fwd_nxt = jax.lax.ppermute(y, "pp", perm_f)
                bwd_nxt = jax.lax.ppermute(dx, "pp", perm_b)
                return (fwd_nxt, bwd_nxt, buf, gpv, ghv, loss_acc), None

            zp0, zh0 = zgrads()
            init = (zx(), zx(),
                    jnp.zeros((S,) + hid.shape, hid.dtype),
                    zp0, zh0, jnp.asarray(0.0, jnp.float32))
            carry, _ = jax.lax.scan(tick, init, jnp.arange(T))
            _, _, _, gpv, ghv, loss_acc = carry
            loss = jax.lax.psum(
                jnp.where(stage == S - 1, loss_acc, 0.0), "pp")
            ghv = tuple(jax.lax.psum(g, "pp") for g in ghv)
            if dp_ax:
                loss = jax.lax.pmean(loss, dp_ax)
                gpv = tuple(jax.lax.pmean(g, dp_ax) for g in gpv)
                ghv = tuple(jax.lax.pmean(g, dp_ax) for g in ghv)
            return (loss,) + gpv + ghv

        x_spec = P(None, dp_ax)
        p_specs = tuple(P("pp") for _ in range(n_stack))
        h_specs = tuple(P() for _ in range(n_het))

        def step(xv, yv, keyv, *vals):
            bsz = xv.shape[0]
            if bsz % M:
                raise ValueError(f"batch {bsz} not divisible by "
                                 f"num_microbatches {M}")
            mb = bsz // M
            x_m = xv.reshape((M, mb) + xv.shape[1:])
            y_m = yv.reshape((M, mb) + yv.shape[1:])
            with manual_collective_mode():
                return shard_map(
                    body, mesh=mesh,
                    in_specs=(x_spec, x_spec, P()) + p_specs + h_specs,
                    out_specs=(P(),) + p_specs + h_specs,
                    check_vma=False,
                )(x_m, y_m, keyv, *vals)

        fn = jax.jit(step)
        cache[key_] = fn
        return fn

    def train_step_1f1b(self, inputs, labels, num_microbatches=None):
        """Run one 1F1B fwd+bwd: deposits .grad on the stacked and
        hetero params and returns the (graph-free) mean loss Tensor.
        The contract of the reference's PipelineParallel.train_batch
        (pipeline_parallel.py:119) — schedule-internal backward, no
        tape."""
        from ..mesh import get_mesh
        self._check_stack_layout()
        pm = get_mesh()
        if pm is None:
            raise RuntimeError("1F1B needs an active mesh with a 'pp' "
                               "axis (fleet.init with pp_degree > 1)")
        fn = self._get_1f1b_step(pm, num_microbatches or self._n_micro)
        from jax.sharding import NamedSharding

        def _on_mesh(v):
            sh = getattr(v, "sharding", None)
            if getattr(sh, "mesh", None) is pm.jax_mesh:
                return v
            return jax.device_put(jnp.asarray(v),
                                  NamedSharding(pm.jax_mesh, P()))
        keyv = _on_mesh(random_mod.next_key())
        pvals = tuple(p._value for p in self._stacked)
        hvals = tuple(_on_mesh(p._value) for p in self._hetero_params)
        xv = _on_mesh(inputs._value if isinstance(inputs, Tensor)
                      else inputs)
        yv = _on_mesh(labels._value if isinstance(labels, Tensor)
                      else labels)
        outs = fn(xv, yv, keyv, *pvals, *hvals)
        loss = outs[0]
        n_stack = len(self._stacked)
        for p, g in zip(list(self._stacked) + list(self._hetero_params),
                        outs[1:1 + n_stack + len(self._hetero_params)]):
            if getattr(p, "stop_gradient", False):
                continue
            if p.grad is None:
                p.grad = Tensor(g)
            else:
                p.grad = Tensor(p.grad._value + g)
        return Tensor(loss)

    # -- public API -------------------------------------------------------

    def get_num_stages(self):
        return self._num_stages

    @property
    def parameters_by_stage(self):
        if self._pipelined:
            return {s: list(self._stacked)
                    for s in range(self._num_stages)}
        out = {s: [] for s in range(self._num_stages)}
        for run, stage in zip(self.run_function, self._stage_of):
            if isinstance(run, Layer):
                out[stage] += run.parameters()
        return out

    def forward(self, args, num_microbatches=None):
        from ..mesh import get_mesh
        if not self._pipelined:
            x = args
            for run in self.run_function:
                x = run(x) if not isinstance(x, tuple) else run(*x)
            return x
        self._check_stack_layout()
        x = args
        for run in self._pre_runs:
            x = run(x) if not isinstance(x, tuple) else run(*x)
        pm = get_mesh()
        n_micro = num_microbatches or self._n_micro
        if pm is None or "pp" not in pm.dim_names \
                or pm.get_dim_size("pp") <= 1:
            n_micro = 1
            pm = pm or _SingleMesh()
        op = self._get_pipe_op(pm, n_micro)
        key = Tensor(random_mod.next_key(), stop_gradient=True)
        x = apply_op(op, x, key, *self._stacked)
        for run in self._post_runs:
            x = run(x) if not isinstance(x, tuple) else run(*x)
        return x


class _SingleMesh:
    """Stand-in ProcessMesh when no mesh is active: the stacked blocks
    still run (plain lax.scan path, S=1)."""
    dim_names = ()
    jax_mesh = None

    def get_dim_size(self, name):
        return 1


class PipelineParallel(Layer):
    """reference: fleet/meta_parallel/pipeline_parallel.py:119. Provides
    train_batch(): splits the batch into microbatches and runs the
    GPipe-style accumulation loop; grads accumulate across microbatches
    on the tape exactly like the reference's accumulate_steps."""

    def __init__(self, layers, hcg=None, strategy=None):
        super().__init__()
        self._layers = layers
        self._hcg = hcg
        self._strategy = strategy
        cfg = (strategy.pipeline_configs if strategy is not None else
               {"accumulate_steps": 1})
        self._acc_steps = cfg.get("accumulate_steps", 1)
        self._schedule = str(cfg.get(
            "schedule_mode", cfg.get("schedule", "FThenB"))).lower()

    def forward(self, data):
        return self._layers(data)

    def train_batch(self, data, optimizer, lr_scheduler=None, scaler=None):
        from ...ops import manipulation, math as math_ops
        inputs, labels = data
        if (self._schedule == "1f1b"
                and getattr(self._layers, "_pipelined", False)):
            if scaler is not None:
                raise NotImplementedError(
                    "GradScaler with the 1F1B schedule is not supported "
                    "yet; use schedule_mode='FThenB' for AMP")
            loss = self._layers.train_step_1f1b(
                inputs, labels, num_microbatches=self._acc_steps)
            optimizer.step()
            optimizer.clear_grad()
            if lr_scheduler is not None:
                lr_scheduler.step()
            return loss
        if getattr(self._layers, "_pipelined", False):
            # compiled GPipe path: microbatching happens inside the
            # pipeline op (fill/drain schedule), one fwd+bwd per batch
            # honor the configured accumulate_steps exactly (the default 1
            # means no microbatching — not the num_stages fallback)
            out = self._layers(inputs, num_microbatches=self._acc_steps)
            loss = (self._layers._loss_fn(out, labels)
                    if getattr(self._layers, "_loss_fn", None) else out)
            if scaler is not None:
                scaler.scale(loss).backward()
                scaler.step(optimizer)
            else:
                loss.backward()
                optimizer.step()
            optimizer.clear_grad()
            if lr_scheduler is not None:
                lr_scheduler.step()
            return loss
        micro = self._acc_steps
        total = None
        b = inputs.shape[0]
        mb = max(b // micro, 1)
        for i in range(micro):
            xi = manipulation.slice(inputs, [0], [i * mb],
                                    [min((i + 1) * mb, b)])
            yi = manipulation.slice(labels, [0], [i * mb],
                                    [min((i + 1) * mb, b)])
            out = self._layers(xi)
            loss = (self._layers._loss_fn(out, yi)
                    if getattr(self._layers, "_loss_fn", None)
                    else out)
            loss = math_ops.scale(loss, 1.0 / micro)
            if scaler is not None:
                scaler.scale(loss).backward()
            else:
                loss.backward()
            total = loss if total is None else math_ops.add(total, loss)
        if scaler is not None:
            scaler.step(optimizer)
        else:
            optimizer.step()
        optimizer.clear_grad()
        if lr_scheduler is not None:
            lr_scheduler.step()
        return total

    def eval_batch(self, data, compute_loss=True):
        inputs, labels = data
        out = self._layers(inputs)
        if compute_loss and getattr(self._layers, "_loss_fn", None):
            return self._layers._loss_fn(out, labels)
        return out
