"""In-program collectives over named mesh axes.

TPU-native replacement for the static collective op zoo (reference:
paddle/fluid/operators/collective/ — c_allreduce_*, c_allgather,
c_reducescatter, global_scatter/global_gather, partial_send/recv; 160
files, 15.1k LoC). Each function here is a thin alias of the XLA
collective HLO it lowers to; used inside shard_map / pjit programs where
GSPMD doesn't already infer the collective. Channel management, comm
streams, and sync ops (c_sync_calc_stream…) have no equivalent — XLA
schedules collectives on ICI itself.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["psum", "pmean", "pmax", "pmin", "ppermute", "all_gather",
           "all_to_all", "reduce_scatter", "axis_index", "axis_size",
           "roll_along_axis"]

psum = jax.lax.psum
pmean = jax.lax.pmean
pmax = jax.lax.pmax
pmin = jax.lax.pmin
ppermute = jax.lax.ppermute
axis_index = jax.lax.axis_index


axis_size = jax.lax.axis_size


def all_gather(x, axis_name, axis=0, tiled=True):
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def all_to_all(x, axis_name, split_axis, concat_axis, tiled=True):
    return jax.lax.all_to_all(x, axis_name, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=tiled)


def reduce_scatter(x, axis_name, scatter_dimension=0, tiled=True):
    return jax.lax.psum_scatter(x, axis_name,
                                scatter_dimension=scatter_dimension,
                                tiled=tiled)


def roll_along_axis(x, axis_name, shift=1):
    """Ring shift: device i sends to device (i+shift) % n — the building
    block of ring attention and pipeline p2p."""
    n = axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return jax.lax.ppermute(x, axis_name, perm)
