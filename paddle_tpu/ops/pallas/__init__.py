"""Pallas TPU kernels — the replacement for the reference's handwritten
fused CUDA (reference: paddle/fluid/operators/fused/, 39.8k LoC).

Each kernel here is an XLA custom-call emitted by `pl.pallas_call`; where
the reference fuses per-arch with cuBLASLt/cuDNN epilogues, these tile
directly onto MXU/VMEM. Kernels degrade gracefully: callers fall back to
plain-XLA reference implementations off-TPU (tested against them on CPU
via interpret mode).

Kernels:
- flash_attention.py — fused attention fwd/bwd (online softmax, bias /
  key-padding masks, in-kernel dropout)
- layer_norm.py — fused LayerNorm fwd/bwd
- paged_attention.py — ragged paged-attention decode for the serving
  engine's paged KV pool (scalar-prefetched page-table walk, streams
  only live pages)
- moe.py — routed experts, dropless (`moe_experts`)
- mla.py — latent attention over one cached row a token (`mla_walk`)
- sparse.py — learned sparse attention: an indexer over its own row
  pool, an exact top-k by counting passes, the walk over the selected
  keys (`sparse_index`, `sparse_select`, `sparse_walk`)
"""
import contextlib as _contextlib
import contextvars as _contextvars

import jax as _jax
from jax.sharding import PartitionSpec as _P


def trace32():
    """Context manager: trace the enclosed `pallas_call` in 32-bit mode.
    The package turns `jax_enable_x64` on globally (paddle int64 parity)
    and Mosaic cannot legalize i64 index arithmetic."""
    return _jax.enable_x64(False)


# The device trace names a Mosaic call by its HLO text, not by the Python
# function behind it, so every `pallas_call` site carries a fixed name:
# `name=` puts it into the instruction's name scope and `kernel_name`,
# `metadata=` into `frontend_attributes={kernel_metadata=...}` of the
# compiled instruction, which is the text a trace reader searches for
# `ptk:<name>`. Names live in one `KERNELS` table at the top of each
# kernel file (18 names: `ragged_walk`, `split_walk`, `sink_walk` (the
# same walk over pools of split widths, without and with a sink),
# `grouped_phase1`,
# `scatter_write`, `scatter_q8_write`, `lora_paged`, `argmax_epilogue`,
# `flash_fwd`, `flash_dq`, `flash_dkv`, `layer_norm_fwd`,
# `layer_norm_bwd`, `moe_experts`, `mla_walk`, `sparse_index`,
# `sparse_select`, `sparse_walk`); none may contain another (a reader's
# needle is a substring). `fn` keeps the kernel function's own name in the lowered
# text, where start-up checks look for it.
KERNEL_TAG = "ptk:"


def kernel_id(name, fn):
    """The `name=` / `metadata=` keywords of one `pallas_call` site:
    `name` is the site's trace name, `fn` the kernel function's name."""
    return {"name": name,
            "metadata": {"kernel": KERNEL_TAG + name, "fn": fn}}


# A Mosaic kernel cannot be partitioned by GSPMD ("Mosaic kernels cannot
# be automatically partitioned. Please wrap the call in a shard_map"), on
# real chips only: off-TPU the jnp references partition like any XLA op,
# which is all a virtual CPU mesh ever exercised. A program that spans a
# mesh therefore names it while it is traced (`kernel_mesh`), and every
# kernel wrapper runs its `pallas_call` per device (`per_device`).
_KERNEL_MESH = _contextvars.ContextVar("paddle_tpu_kernel_mesh",
                                       default=None)


@_contextlib.contextmanager
def kernel_mesh(mesh, head_axis=None):
    """While tracing a program over `mesh` (None: one device, a no-op):
    kernels run per device under `shard_map`. `head_axis` names the mesh
    axis the attention heads (and the KV pools' head dimension) are
    sharded over; everything a kernel wrapper does not declare sharded
    is replicated."""
    token = _KERNEL_MESH.set(None if mesh is None or mesh.size == 1
                             else (mesh, head_axis))
    try:
        yield
    finally:
        _KERNEL_MESH.reset(token)


def per_device(fn, in_specs, out_specs):
    """`fn` under `shard_map` over the current kernel mesh. Specs are
    PartitionSpecs written with the placeholder axis "heads", which is
    replaced by the mesh's head axis (or dropped without one). With no
    kernel mesh this is `fn` itself."""
    cur = _KERNEL_MESH.get()
    if cur is None:
        return fn
    mesh, head_axis = cur

    def named(spec):
        return _P(*(head_axis if a == "heads" else a for a in spec))

    return _jax.shard_map(
        fn, mesh=mesh,
        in_specs=_jax.tree.map(named, in_specs,
                               is_leaf=lambda x: isinstance(x, _P)),
        out_specs=_jax.tree.map(named, out_specs,
                                is_leaf=lambda x: isinstance(x, _P)),
        check_vma=False)
