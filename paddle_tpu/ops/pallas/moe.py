"""Routed experts on the serving path: dropless, and proportional to
tokens x experts-per-token.

A mixture-of-experts block sends each token to `top_k` of `n_experts`
SwiGLU experts. This module computes the part of that sum given by the
experts HELD HERE (expert parallelism: `first` .. `first + E_local - 1`
of the router's `n_experts` outputs), for the tokens marked `valid`:

    out[t] = sum over e in top_k(t), e held here, of w[t, e] * E_e(x[t])

with w the router's softmax score (of the token's top-k, or of its
top-k within its best groups of experts: `_top_experts`), or its sigmoid
score, the top-k then chosen by that score plus a SELECTION BIAS that
weighs nothing (DeepSeek-V3's `noaux_tc`: `scoring="sigmoid"`), renormalised
over the token's whole top-k set where the model asks for it (the
experts held elsewhere included), and scaled. No token is
dropped and no capacity is fixed: the (token, expert) assignments are
sorted by expert and the experts run over their own rows only.

Three stages, each under a name the device trace shows:

- `route` (`ptk:moe_route`, see `route_scope`): router matmul and
  softmax in float32, top-k, the sort by expert, and the gather of the
  token rows into tiles of `TILE_ROWS` rows that each belong to ONE
  expert (a group is padded to whole tiles; rows of padding read token
  0 and are never read back).
- `experts`: ONE Pallas kernel (`ptk:moe_experts`) over (tile,
  hidden-block): gate and up projections, silu(g) * u, and the down
  projection accumulated in float32. The tile -> expert map rides in as
  a scalar-prefetch operand, so a grid step streams the weights of its
  tile's expert and of no other; the grid's tile axis is a DYNAMIC
  bound, the tiles the step's routing really filled. A step that holds
  16 decode rows therefore reads the weights of the experts those rows
  chose and of no others. Off-TPU (and not in interpret mode) the same
  sorted rows go through `jax.lax.ragged_dot`, one grouped product a
  projection: the candidate the kernel was measured against on the chip
  (PERF.md section 6, PR 29).
- `combine` (under `ptk:moe_route` too): each assignment's row gathered
  back, weighted, and summed over the token's top-k.

Shapes are fixed whatever the routing: T x top_k assignment rows,
T x top_k / TILE_ROWS + E_local tiles at most.

Counts made on the device (`stats`, int32 [3]): assignments routed (all
experts, valid tokens), assignments routed to experts held here, local
experts that received at least one token; with a selection bias a fourth,
the assignments whose expert the unbiased scores would not have chosen.
"""
from __future__ import annotations

import contextlib
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.xla_metadata import set_xla_metadata

from . import kernel_id as _kernel_id, trace32 as _trace32
from .paged_attention import _prec

__all__ = ["moe_route", "moe_experts", "moe_experts_ragged_dot",
           "routed_experts", "KERNELS", "TILE_ROWS"]

_INTERPRET = os.environ.get("PADDLE_TPU_PALLAS_INTERPRET", "0") == "1"

KERNELS = {name: _kernel_id(name, fn) for name, fn in (
    ("moe_experts", "_experts_kernel"),
)}

ROUTE_SCOPE = "ptk:moe_route"

# rows of one tile: every tile belongs to one expert, so a group of n
# rows costs ceil(n / TILE_ROWS) passes over its expert's weights. 128
# rows keep a decode step's tiles (a handful of live rows each) cheap;
# a prefill chunk's ~80 rows an expert still fit one tile
TILE_ROWS = 128
# columns of the experts' hidden width a grid step takes
_F_BLOCK = 512


def _use_kernel():
    return _INTERPRET or jax.devices()[0].platform == "tpu"


@contextlib.contextmanager
def route_scope():
    """The routing's name, where the device trace can find it: a
    frontend attribute on every operation traced inside (a fusion
    keeps it through the TPU compiler, and the trace names an
    operation by its HLO text), beside the `jax.named_scope` that
    names the operations in an HLO dump."""
    with jax.named_scope(ROUTE_SCOPE), set_xla_metadata(ptk=ROUTE_SCOPE):
        yield


def _top_experts(score, top_k, n_group, topk_group):
    """score f32 [T, E] -> (the scores of each token's `top_k` experts
    [T, top_k], their indices). With `n_group` > 1 the choice is
    GROUP-LIMITED (DeepSeek-V2's `group_limited_greedy`): the experts
    lie in `n_group` runs of E / n_group neighbours (a run is a device
    of the deployment), a run's score is its best expert's, only the
    `topk_group` best runs keep their scores, the others count as 0,
    and the top-k is taken over what is left. A kept run is found by
    comparing its score with the `topk_group`-th best (ties between
    runs go to the lower index, as a sort's would): comparisons and
    sorts only, no scatter into a mask."""
    if n_group > 1:
        t, e = score.shape
        per = score.reshape(t, n_group, e // n_group)
        best = jnp.max(per, axis=-1)                        # [T, G]
        # a run's rank among the runs: how many beat it (a lower index
        # wins a tie)
        g = jnp.arange(n_group, dtype=jnp.int32)
        beats = (best[:, None, :] > best[:, :, None]) | (
            (best[:, None, :] == best[:, :, None])
            & (g[None, None, :] < g[None, :, None]))
        keep = jnp.sum(beats, axis=-1, dtype=jnp.int32) < topk_group
        score = jnp.where(keep[:, :, None], per, 0.0).reshape(t, e)
    return jax.lax.top_k(score, top_k)


def moe_route(x, router_w, valid, *, top_k, scale, norm_topk, first,
              n_local, tile_rows=TILE_ROWS, n_group=1, topk_group=1,
              scoring="softmax", bias=None):
    """x [T, h]; router_w [h, n_experts]; valid bool [T] -> a dict of
    the routing's fixed-shape arrays (see the module doc). `n_group`,
    `topk_group`: the group limit on a token's choice (`_top_experts`;
    1: none). `scoring`: "softmax" over the router's outputs, or
    "sigmoid" of each, where `bias` (f32 [n_experts] or None) is added
    to the scores that CHOOSE the top-k and to none that weigh it.
    Assignment
    a = k * T + t is token t's k-th expert (k-major, so that the sum
    over k at the end is over whole [T, h] slabs):

    here bool [top_k * T] (the assignment is computed here), dest int32
    [top_k * T] (its row in the tiled layout), src int32 [M] (the token
    each tiled row reads; 0 for padding), row_weight f32 [M, 1] (the
    router's weight of the row's assignment; 0 for padding),
    tile_expert int32 [n_tiles_max], n_tiles int32 [], group_sizes
    int32 [n_local], order int32 [top_k * T] (the sort by expert) and
    weight_sorted f32 (for the ragged_dot candidate), stats int32 [3].

    Sorts, searches and windows only: on the chip a scatter or a
    gather of single elements moves one element at a time."""
    t = x.shape[0]
    tk = t * top_k
    with route_scope():
        logits = jnp.dot(x.astype(jnp.float32),
                         router_w.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        if scoring == "softmax":
            top_v, top_i = _top_experts(jax.nn.softmax(logits, axis=-1),
                                        top_k, n_group, topk_group)
        elif scoring == "sigmoid":
            score = jax.nn.sigmoid(logits)
            pick = score if bias is None \
                else score + bias.astype(jnp.float32)[None, :]
            _, top_i = _top_experts(pick, top_k, n_group, topk_group)
            top_v = jnp.take_along_axis(score, top_i, axis=-1)
        else:
            raise ValueError(f"router scoring {scoring!r}")
        if norm_topk:
            top_v = top_v / jnp.sum(top_v, axis=-1, keepdims=True)
        weight = (top_v * jnp.float32(scale)).T.reshape(tk)
        expert = top_i.astype(jnp.int32).T.reshape(tk)
        live = jnp.tile(valid, top_k)
        here = live & (expert >= first) & (expert < first + n_local)
        # n_local is the group of what is not computed here: it sorts
        # last and gets no tile
        eid = jnp.where(here, expert - first, n_local).astype(jnp.int32)
        iota = jnp.arange(tk, dtype=jnp.int32)
        sorted_e, order, weight_sorted = jax.lax.sort(
            (eid, iota, weight), num_keys=1, is_stable=True)
        # group e holds the sorted assignments bounds[e] .. bounds[e+1]
        bounds = jnp.searchsorted(
            sorted_e, jnp.arange(n_local + 1, dtype=jnp.int32),
            side="left").astype(jnp.int32)
        gs = bounds[1:] - bounds[:-1]
        tiles = (gs + tile_rows - 1) // tile_rows
        tiles_end = jnp.cumsum(tiles, dtype=jnp.int32)
        tile_start = tiles_end - tiles
        n_tiles_max = -(-tk // tile_rows) + n_local
        tile = jnp.arange(n_tiles_max, dtype=jnp.int32)
        tile_expert = jnp.minimum(
            jnp.searchsorted(tiles_end, tile, side="right"),
            n_local - 1).astype(jnp.int32)
        # a tile holds a WINDOW of its group's sorted assignments: per
        # tile where the window starts and how much of it is filled,
        # per row only its offset in the tile
        rank0 = (tile - tile_start[tile_expert]) * tile_rows
        start = bounds[tile_expert] + rank0
        filled = jnp.where(tile < tiles_end[-1],
                           gs[tile_expert] - rank0, 0)
        in_tile = jnp.arange(tile_rows, dtype=jnp.int32)
        ok = (in_tile[None, :] < filled[:, None]).reshape(-1)

        # (token, weight) of the sorted assignments, side by side so
        # that one windowed read serves both
        pair = jnp.stack([order % t, jax.lax.bitcast_convert_type(
            weight_sorted, jnp.int32)], axis=1)
        pair = jnp.concatenate([pair, jnp.zeros((tile_rows, 2), jnp.int32)])
        win = jax.vmap(lambda s: jax.lax.dynamic_slice(
            pair, (s, jnp.zeros((), jnp.int32)), (tile_rows, 2)))(
            jnp.minimum(start, tk))
        win = win.reshape(-1, 2)
        src = jnp.where(ok, win[:, 0], 0)
        row_weight = jnp.where(ok, jax.lax.bitcast_convert_type(
            win[:, 1], jnp.float32), 0.0)[:, None]
        # sorted assignment j -> its tiled row: j plus its group's
        # padding so far, a step function of j with a step at every
        # group's start; then back to the original order by a second
        # sort, on the permutation itself
        pad = tile_start * tile_rows - bounds[:-1]
        step = pad - jnp.concatenate([jnp.zeros((1,), jnp.int32), pad[:-1]])
        shift = jnp.sum(jnp.where(iota[:, None] >= bounds[None, :-1],
                                  step[None, :], 0), axis=1)
        dest_sorted = jnp.clip(iota + shift, 0,
                               n_tiles_max * tile_rows - 1)
        _, dest = jax.lax.sort((order, dest_sorted), num_keys=1)
        stats = jnp.stack([jnp.sum(live, dtype=jnp.int32), bounds[-1],
                           jnp.sum(gs > 0, dtype=jnp.int32)])
        if bias is not None:
            # the choices the bias changed: experts of the biased top-k
            # that are not among the unbiased top-k
            _, plain = _top_experts(score, top_k, n_group, topk_group)
            moved = jnp.all(top_i[:, :, None] != plain[:, None, :], -1)
            stats = jnp.concatenate([stats, jnp.sum(
                moved & valid[:, None], dtype=jnp.int32)[None]])
    return dict(here=here, dest=dest, src=src, row_weight=row_weight,
                tile_expert=tile_expert, n_tiles=tiles_end[-1],
                group_sizes=gs, order=order, weight_sorted=weight_sorted,
                stats=stats)


def _experts_kernel(te_ref, x_ref, rw_ref, wg_ref, wu_ref, wd_ref, o_ref,
                    acc_ref):
    """Grid (tile, hidden block): this tile's rows through one block
    of its expert's hidden width. The down projection's partial sums
    accumulate in float32 over the hidden blocks; each row leaves
    multiplied by its assignment's router weight, in float32."""
    del te_ref
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    prec = _prec(x.dtype)
    g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32,
                precision=prec)
    u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32,
                precision=prec)
    a = (g * jax.nn.sigmoid(g) * u).astype(x.dtype)
    acc_ref[...] += jnp.dot(a, wd_ref[0], precision=prec,
                            preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _store():
        o_ref[...] = (acc_ref[...] * rw_ref[...]).astype(o_ref.dtype)


def moe_experts(xs, row_weight, tile_expert, n_tiles, w_gate, w_up,
                w_down, *, tile_rows=TILE_ROWS):
    """xs [M, h] rows in tiles of `tile_rows`, tile i belonging to
    expert tile_expert[i]; row_weight f32 [M, 1]; w_gate / w_up
    [E, h, f], w_down [E, f, h] -> [M, h]: each row through its tile's
    expert, times its weight. Only the first `n_tiles` tiles are
    computed; the rows of the others are left as the buffer held
    them."""
    m, h = xs.shape
    f = w_gate.shape[2]
    # the widest block of whole lanes, `_F_BLOCK` at most, that DIVIDES
    # the experts' width: the grid's second axis is f // fb blocks, and
    # columns past them would simply not be computed (512 of a width of
    # 768: my chip run, PR 36, where the roofline share read 113%)
    fb = min(f, _F_BLOCK)
    while f % fb and fb > 128:
        fb -= 128
    if f % fb:
        raise ValueError(f"experts of width {f}: neither at most "
                         f"{_F_BLOCK} nor a multiple of 128")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        # one tile at least: a grid axis of 0 steps is not worth finding
        # out about, and a tile nobody reads back costs one expert
        grid=(jnp.maximum(n_tiles, 1), f // fb),
        in_specs=[
            pl.BlockSpec((tile_rows, h), lambda i, j, te: (i, 0)),
            pl.BlockSpec((tile_rows, 1), lambda i, j, te: (i, 0)),
            pl.BlockSpec((1, h, fb), lambda i, j, te: (te[i], 0, j)),
            pl.BlockSpec((1, h, fb), lambda i, j, te: (te[i], 0, j)),
            pl.BlockSpec((1, fb, h), lambda i, j, te: (te[i], j, 0)),
        ],
        out_specs=pl.BlockSpec((tile_rows, h), lambda i, j, te: (i, 0)),
        scratch_shapes=[pltpu.VMEM((tile_rows, h), jnp.float32)],
    )
    with _trace32():
        return pl.pallas_call(
            _experts_kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((m, h), xs.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=64 * 1024 * 1024),
            interpret=_INTERPRET,
            **KERNELS["moe_experts"],
        )(tile_expert, xs, row_weight, w_gate, w_up, w_down)


def moe_experts_ragged_dot(x, route, w_gate, w_up, w_down):
    """The same experts over the same sorted assignments, one
    `jax.lax.ragged_dot` a projection: rows sorted by expert with no
    padding, those not computed here behind the last group (a ragged
    product leaves rows past its groups at zero). Returns the
    assignments' weighted outputs in their ORIGINAL order
    [top_k * T, h]."""
    with jax.named_scope("ptk:moe_experts"):
        xs = x[route["order"] % x.shape[0]]
        gs = route["group_sizes"]
        prec = _prec(x.dtype)
        g = jax.lax.ragged_dot(xs, w_gate, gs, precision=prec,
                               preferred_element_type=jnp.float32)
        u = jax.lax.ragged_dot(xs, w_up, gs, precision=prec,
                               preferred_element_type=jnp.float32)
        a = (g * jax.nn.sigmoid(g) * u).astype(x.dtype)
        y = jax.lax.ragged_dot(a, w_down, gs, precision=prec,
                               preferred_element_type=jnp.float32)
        y = (y * route["weight_sorted"][:, None]).astype(x.dtype)
    with route_scope():
        _, back = jax.lax.sort(
            (route["order"], jnp.arange(y.shape[0], dtype=jnp.int32)),
            num_keys=1)
        return y[back]


def routed_experts(x, valid, router_w, w_gate, w_up, w_down, *, top_k,
                   scale, norm_topk, first, n_group=1, topk_group=1,
                   scoring="softmax", bias=None):
    """The routed part of a mixture-of-experts block for the experts
    held here (the registered op's forward; module doc). x [T, h],
    valid bool [T]; `n_group`, `topk_group`, `scoring`, `bias`:
    `moe_route`'s; returns (out [T, h] in x's dtype, stats int32 [3],
    or [4] with a bias).
    The expert product is the Pallas kernel on a TPU (and in interpret
    mode) and the ragged_dot form elsewhere."""
    t, h = x.shape
    n_local = w_gate.shape[0]
    route = moe_route(x, router_w, valid, top_k=top_k, scale=scale,
                      norm_topk=norm_topk, first=first, n_local=n_local,
                      n_group=n_group, topk_group=topk_group,
                      scoring=scoring, bias=bias)
    if _use_kernel():
        with route_scope():
            xs = x[route["src"]]
        ys = moe_experts(xs, route["row_weight"], route["tile_expert"],
                         route["n_tiles"], w_gate, w_up, w_down)
        with route_scope():
            y = ys[route["dest"]]
    else:
        y = moe_experts_ragged_dot(x, route, w_gate, w_up, w_down)
    with route_scope():
        # an assignment that is not computed here reads a row nobody
        # wrote: select, do not multiply by zero
        y = jnp.where(route["here"][:, None], y, jnp.zeros((), y.dtype))
        out = y.reshape(top_k, t, h).sum(0, dtype=jnp.float32)
    return out.astype(x.dtype), route["stats"]
