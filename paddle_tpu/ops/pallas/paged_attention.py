"""Ragged paged-attention decode kernel (Pallas, TPU).

The serving engine's paged decode path used to materialize each row's
logical KV view with `paged_kv_gather` — a transient
[S, max_pages * page_size, H, D] HBM stream PER LAYER PER STEP that
scales with the pool horizon, not with the tokens actually resident,
and XLA cannot fuse a data-dependent gather into the attention reads
("Operator Fusion in XLA", PAPERS.md). This kernel is the fix from
"Ragged Paged Attention" (PAPERS.md): walk the page table and stream
ONLY the pages a row actually occupies.

Structure — grid (batch_row, q_block, page):

- `page_table` [B, max_pages], `pos` [B] and `q_len` [B] ride in as
  SCALAR-PREFETCH operands (pltpu.PrefetchScalarGridSpec), so the K/V
  BlockSpec index maps can chase the page table: grid step (b, t, p)
  DMAs pool page `page_table[b, p]` — the WHOLE page, all kv heads
  (block (1, page_size, H_kv, D): Mosaic wants a block's two minor
  dimensions (8, 128)-divisible or whole, so a block cannot take one
  head out of [H_kv, D]; see `_ragged_attention_local`). Steps past
  the row's last live page clamp their index to that page — the
  pipeline skips the re-fetch of an unchanged block, so HBM traffic is
  O(pages actually used) per row, and compute there is predicated off.
- Flash-style online softmax across page blocks: running (m, l, acc)
  scratch in VMEM, exactly the flash_attention.py recurrence with
  page_size-wide key blocks, the kv heads as the batch dimension of
  both dots (`_attend_page`). The partial tail page is handled by
  in-page masking (position > pos[b] -> -inf), which also covers
  trash-page rows: a retired/free slot's page-table row points at the
  reserved page 0 and every position past `pos` contributes -inf.
- GQA without materialization: queries are grouped
  [B, n_qblk, H_kv, qblk * rep, D] so kv head g serves its
  `rep = H // H_kv` query heads from ONE streamed copy of K/V — no
  `repeat_interleave` of the cache.
- The single-token decode op (`paged_decode_attention`) is this walk
  at q_len 1.

Off-TPU the op runs `paged_attention_reference` — the same math as the
gather path (gather pages -> masked grouped softmax), kept around both
as the CPU tier-1 path and as the oracle the kernel is tested against
(tests/test_paged_attention.py runs the kernel in interpret mode).

GROUPED PAGE WALK (`ragged_paged_attention_grouped`): under high
prefix share, N resident rows attend the SAME physical system-prompt
pages, and the per-row walk above streams those pages from HBM N
times per step. The grouped op is the cascade/hydragen-style fix:
rows whose page tables share a physical-page prefix carry a group id,
and three extra scalar-prefetch operands — `group_id` [B] (row ->
group), `group_leader` [B] (group -> a representative row) and
`group_cnt` [B] (group -> shared page count; 0 for singletons) — ride
next to `page_table`/`pos`/`q_len` and drive a TWO-PHASE kernel:

- phase 1 walks each group's shared pages via the LEADER's page table
  (grid (q_block, group x page)), streaming every shared page from
  HBM ONCE PER GROUP while updating the online-softmax partials
  (m, l, acc) of every MEMBER row in VMEM (non-member rows are
  predicated off, so their partials stay bit-exact);
- phase 2 is exactly the per-row walk above, except each row STARTS
  from its phase-1 partials and its page sweep clamps to
  [group_cnt[group_id[b]], last_live] — private tail pages stream
  once per row, shared pages are never re-read.

A group of 1 (group_cnt 0) degenerates to the ungrouped walk: phase 1
never touches the row and phase 2 starts at page 0 with the virgin
(-inf, 0, 0) partials. Phase 1's (group x page) sweep is as long as
the data asks: its grid bound is DYNAMIC, `any(group_cnt > 0)` decided
inside the one compiled step from operand data — the whole sweep on a
step where some rows share, ONE grid step a q_block (which writes the
virgin partials and nothing else) on a step where none do, because a
predicated-off grid step still costs a grid step and the full sweep
has as many as the walk proper. Its q-block axis is the walk proper's
own dynamic bound (below), the same in both phases. Page order per row
is IDENTICAL to the ungrouped kernel (shared pages 0..cnt-1 then
private cnt..last, the same online-softmax recurrence), so outputs
match the ungrouped walk;
off-TPU the op runs the SAME `ragged_attention_reference` as the
ungrouped op — grouping is a pure HBM-traffic hint, bit-identical by
construction. `count_page_block_reads` is the host-side model of both
walks' DMA behavior (the number the serving bench and metrics
report). The q8 lane (`ragged_paged_attention_grouped_q8`) streams
the rowwise scale pages through the same grouped walk.

FP8 LANE: pools may hold float8_e4m3fn — a PURE-CONVERT quantized
cache (no scale pages at all: the e4m3 value IS the number, saturating
round-to-nearest on write). Every kernel and reference detects the
pool dtype and upconverts to f32 in VMEM before the dot — half the
fp16/bf16 HBM bytes (a quarter of f32) with zero extra operands, the
cheapest possible quantized lane. Unlike int8's rowwise codes+scales
there is nothing to keep paired, so COW/swap/spill move fp8 pages
exactly like fp pages.

RAGGED GENERALIZATION (`ragged_paged_attention`): the same walk, but
every row carries its own query length — grid
(batch_row, q_block, page), with `q_len` [B] riding next to
`page_table`/`pos` as a third scalar-prefetch operand. Row b's query
token i sits at global position pos[b] + i and attends keys
j <= pos[b] + i (the causal window of the chunk being written), so ONE
invocation serves a mixed batch: decode rows at q_len == 1 next to
mid-prefill rows at q_len == chunk — the one-kernel/step target of
Ragged Paged Attention (PAPERS.md), with the per-row tail causally
masked in the fused online-softmax loop (the low-precision-friendly
primitive style of Tensor Processing Primitives, PAPERS.md). Query
blocks past q_len[b] and pages past the row's live prefix
ceil((pos[b] + q_len[b]) / page_size) are skipped: their grid steps
clamp the K/V block index to the last live page (no re-fetch) and
predicate compute off, so both HBM traffic and MXU work scale with the
tokens actually packed, not with the padded step shape. The GRID is
as long as the rows ask too: its q-block and page axes are dynamic
bounds (`walk_grid_bounds`: the q-blocks of the row with most live
queries, the pages of the longest live context), decided inside the
one compiled step from `pos` and `q_len`, for every walk: plain,
grouped (both phases), masked, int8, fp8 and windowed. A skipped grid
step still costs a grid step, and the padded shape has tens of times
more of them than a step of decode rows needs. Outputs at query
positions >= q_len[b] are zero (the engine discards them).

MEGAKERNEL (`megakernel_decode` / `megakernel_decode_q8`, gated
PADDLE_TPU_MEGAKERNEL, default off): the decode layer's remaining op
soup — per-row paged LoRA delta gather, KV quantize-then-scatter, and
the attend itself — fused into ONE registered op so the unified step
approaches a handful of launches ("Operator Fusion in XLA", PAPERS.md:
XLA will not fuse across these data-dependent gather/scatter
boundaries on its own; "Tensor Processing Primitives": build the layer
from a small set of fused primitives instead). Composition:

- LoRA prologue (`lora=True`): the per-row adapter page streams
  through VMEM ONCE per layer (`lora_delta_paged` — a Pallas kernel
  whose BlockSpec index maps chase `apage` via scalar prefetch, the
  same trick the page walk plays with `page_table`) and its q/k/v
  deltas are added to the base projections inside the op. Base rows
  ride the all-zero adapter page 0 and contribute exactly 0. The
  unfused path gathers the A/B pairs in-trace per projection — three
  HBM gathers of the same page; the fused op streams it once.
- quantize-on-write: the new tokens' K/V are quantized
  (`quantize_kv_rowwise` — the SAME expression the unfused scatter
  op uses) and scattered into the code+scale pools in the same pass
  (Pallas scatter with `input_output_aliases`: grid step (b, t) DMAs
  one token's [H, D] tile to pool slot `flat[b, t]`, untouched slots
  keep their bytes, trash-slot collisions resolve last-write-wins in
  sequential grid order — exactly the XLA scatter's semantics).
- the attend is the unchanged ragged/grouped walk above (the fused op
  CALLS the same kernel / reference dispatch), so every attention
  guarantee — grouping, q8/fp8 lanes, causal tails — carries over.

Off-TPU the fused op composes the SAME shared jnp expressions the
unfused ops register (`paged_scatter`, `paged_scatter_q8`,
`lora_delta`, the ragged references), so gate-on CPU serving is
bit-identical to gate-off by construction — the oracle the engine
tests pin. Greedy sampling + spec-decode acceptance fuse as separate
epilogue ops over the logits tile (`decode_greedy_argmax`,
`spec_verify_accept` — the verify columns' grammar bias masks are
already additive operand data, so they compose unchanged).
`count_page_block_reads(fused=...)` models both pipelines' HBM bytes
so the cost census can assert bytes-accessed per token drops.

INT8 LANE (`ragged_paged_attention_q8`): the same walk over an int8
POOL — code pages [P, page_size, H_kv, D] int8 plus rowwise scale
pages [P, page_size, H_kv] f32 (one scale per (position, kv head),
written by generation.py's quantized paged scatter). Code and scale
blocks stream into VMEM together and the dequant (convert x rowwise
scale) is FUSED into the online-softmax loop — no HBM-side
dequantized copy is ever materialized, which is the whole point:
decode is HBM-bandwidth-bound, and halving the KV byte stream halves
the dominant HBM traffic (the fused low-precision-primitive idiom of
Tensor Processing Primitives, PAPERS.md). Dead-page / dead-row
clamping is unchanged. Off-TPU the op runs
`ragged_attention_reference_q8`, which dequantizes through EXACTLY the
same elementwise expression as generation.py's `paged_kv_gather_q8`
(`dequantize_paged_q8` is shared), so the CPU kernel lane stays
bit-identical to the quantized-gather path through update_and_attend.
"""
from __future__ import annotations

import functools
import math
import os

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from . import (kernel_id as _kernel_id, per_device as _per_device,
               trace32 as _trace32)

__all__ = ["paged_decode_attention", "paged_attention_reference",
           "gqa_attend_reference", "ragged_paged_attention",
           "ragged_attention_reference", "ragged_paged_attention_q8",
           "ragged_attention_reference_q8", "dequantize_paged_q8",
           "ragged_paged_attention_grouped",
           "ragged_paged_attention_grouped_q8",
           "count_page_block_reads", "count_window_page_reads",
           "count_walk_grid_steps", "walk_grid_bounds",
           "FP8_DTYPE",
           "resolve_megakernel_flag", "MEGAKERNEL_ENV",
           "quantize_kv_rowwise", "paged_scatter", "paged_scatter_q8",
           "lora_delta", "lora_delta_paged", "megakernel_decode",
           "megakernel_decode_q8", "decode_greedy_argmax",
           "spec_verify_accept"]

# interpret mode: run the kernel on CPU for testing (tests set this)
_INTERPRET = os.environ.get("PADDLE_TPU_PALLAS_INTERPRET", "0") == "1"

# trace name -> pallas_call keywords, one entry per call site in this
# file (ops/pallas/__init__.py `kernel_id`); no name contains another
KERNELS = {name: _kernel_id(name, fn) for name, fn in (
    ("ragged_walk", "_ragged_kernel"),
    ("grouped_phase1", "_grouped_phase1_kernel"),
    ("scatter_write", "_scatter_write_kernel"),
    ("scatter_q8_write", "_scatter_q8_write_kernel"),
    ("lora_paged", "_lora_paged_kernel"),
    ("argmax_epilogue", "_argmax_epilogue_kernel"),
)}

_NEG_INF = -1e30
_LANES = 128

# the pure-convert fp8 KV lane's storage dtype: e4m3 "fn" (finite —
# saturates instead of overflowing to inf), the standard KV-cache fp8
FP8_DTYPE = jnp.float8_e4m3fn


def _is_fp8(dt) -> bool:
    return jnp.dtype(dt) == jnp.dtype(FP8_DTYPE)


def _prec(dt):
    # bf16 x bf16 -> f32 on the MXU is exact at DEFAULT; 'highest' is
    # invalid for bf16 operands under Mosaic (see flash_attention.py)
    return (jax.lax.Precision.DEFAULT if jnp.dtype(dt) == jnp.bfloat16
            else jax.lax.Precision.HIGHEST)


def _use_kernel():
    return _INTERPRET or jax.devices()[0].platform == "tpu"


# the decode-megakernel gate (see module doc): opt-in because the
# fused ops trade per-op dispatch for one bigger program — the win is
# real-chip launch overhead + HBM round-trips, which CPU tier-1 can
# only model (count_page_block_reads(fused=...)), not time
MEGAKERNEL_ENV = "PADDLE_TPU_MEGAKERNEL"


def resolve_megakernel_flag(override=None):
    """Resolve the decode-megakernel gate: explicit override wins,
    else the PADDLE_TPU_MEGAKERNEL env var (on|off, default off) —
    the same token set every other serving gate accepts."""
    if override is not None:
        if isinstance(override, bool):
            return override
        flag = str(override)
    else:
        flag = os.environ.get(MEGAKERNEL_ENV, "off")
    low = flag.strip().lower()
    if low in ("on", "1", "true", "yes"):
        return True
    if low in ("off", "0", "false", "no"):
        return False
    raise ValueError(
        f"{MEGAKERNEL_ENV} / megakernel must be on|off, got {flag!r}")


def _mask_to_additive(mask, b, h, lmax, lq=1):
    """User attn_mask (bool or additive float, broadcastable
    [B|1, H|1, lq|1, lmax]) -> additive f32 [B, H, lq, lmax]
    (squeezed to [B, H, lmax] for the single-token kernel)."""
    if mask.dtype == jnp.bool_:
        mask = jnp.where(mask, jnp.float32(0.0), jnp.float32(_NEG_INF))
    mask = mask.astype(jnp.float32)
    out = jnp.broadcast_to(mask, (b, h, lq, lmax))
    return out.reshape(b, h, lmax) if lq == 1 else out


def _attend_page(q, k, v, ks, vs, live, mask, m_ref, l_ref, acc_ref, *,
                 scale, fp8):
    """Fold ONE streamed page into the online-softmax partials of one
    row's query block, for every kv head at once. q [H_kv, R, D] with
    R = qblk * rep query rows per kv head; k/v [ps, H_kv, D] exactly as
    the page sits in the pool (the page block carries ALL kv heads —
    see `_ragged_attention_kernel`); ks/vs the int8 lane's rowwise
    scales [ps, H_kv] f32 or None; live bool [R, ps]; mask additive f32
    [H_kv, R, ps] or None. m/l [H_kv, R, 128] and acc [H_kv, R, D] are
    refs updated in place. The head axis is a batch dimension of both
    dots, so per head this is the flash_attention.py recurrence with
    page_size-wide key blocks."""
    if ks is not None:
        # fused in-VMEM dequant: int8 codes x rowwise scale — the
        # dequantized page never round-trips through HBM
        q = q.astype(jnp.float32)
        k = k.astype(jnp.float32) * ks[:, :, None]
        v = v.astype(jnp.float32) * vs[:, :, None]
    elif fp8:
        # pure-convert fp8 lane: the e4m3 value IS the number —
        # upconvert in VMEM, no scale operand exists
        q = q.astype(jnp.float32)
        k = k.astype(jnp.float32)
        v = v.astype(jnp.float32)
    prec = _prec(q.dtype)
    k = jnp.swapaxes(k, 0, 1)                      # [H_kv, ps, D]
    v = jnp.swapaxes(v, 0, 1)
    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
        precision=prec) * jnp.float32(scale)       # [H_kv, R, ps]
    s = jnp.where(live[None], s, jnp.float32(_NEG_INF))
    if mask is not None:
        s = s + mask
    m_prev = m_ref[:, :, :1]
    l_prev = l_ref[:, :, :1]
    m_cur = jnp.max(s, axis=2, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    pexp = jnp.exp(s - m_new)
    l_ref[...] = jnp.broadcast_to(
        alpha * l_prev + jnp.sum(pexp, axis=2, keepdims=True),
        l_ref.shape)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        pexp.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
        precision=prec)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)


def _live_window(t, p, pos_b, qlen_b, *, ps, qblk, rep, window=None):
    """bool [qblk * rep, ps]: query t*qblk + i (live iff < q_len)
    attends key position p*ps + j iff it is <= pos + query index.
    Masks the partial tail page AND trash-page positions. With a
    sliding `window` (its size, the query's own position included) the
    key must also lie above pos + query index - window: the partial
    page at the window's lower edge."""
    shape = (qblk, rep, ps)
    qi = t * qblk + jax.lax.broadcasted_iota(
        jnp.int32, shape, 0).reshape(qblk * rep, ps)
    k_pos = p * ps + jax.lax.broadcasted_iota(
        jnp.int32, shape, 2).reshape(qblk * rep, ps)
    live = (qi < qlen_b) & (k_pos <= pos_b + qi)
    if window is not None:
        live = live & (k_pos > pos_b + qi - window)
    return live


def _window_pages(window, qblk, ps):
    """Pages one query block of a sliding-window layer can touch: its
    live queries see window - 1 + qblk consecutive positions at most,
    which lie on this many pages whatever their alignment. It is the
    length of the page axis of a window layer's grid."""
    return (window + qblk + ps - 3) // ps + 1


def _window_first_page(pos_b, t, *, ps, qblk, window):
    """The page that holds the lowest key the first query of block t
    sees: the page a window layer's walk of that block starts from."""
    return jnp.maximum(pos_b + t * qblk - (window - 1), 0) // ps


def _ragged_kernel(*refs, ps, qblk, rep, scale, has_mask, has_scale,
                   fp8, grouped, window=None):
    """The per-row page walk — grid (batch_row, q_block, page). With
    `grouped` it is phase 2 of the grouped walk: each row initializes
    from its phase-1 partials and skips pages below its group's shared
    span (their contribution is already folded in), so private tail
    pages stream once per row and shared pages are never re-read. The
    merge IS the online-softmax recurrence continuing where phase 1
    stopped, so the page order per row matches the ungrouped walk.
    With `window` the page axis is RELATIVE: grid step p is the p-th
    page from the one that holds the block's lowest visible key, so
    pages wholly below the window have no grid step at all."""
    refs = list(refs)
    n_pre = 6 if grouped else 3
    pre, refs = refs[:n_pre], refs[n_pre:]
    pos_ref, qlen_ref = pre[1], pre[2]
    q_ref, k_ref, v_ref = refs[:3]
    refs = refs[3:]
    ks_ref = vs_ref = mask_ref = None
    if has_scale:
        # int8 lane: rowwise dequant scales ride next to the code
        # pages — one [ps, H_kv] f32 block per streamed K/V page
        ks_ref, vs_ref = refs[:2]
        refs = refs[2:]
    if has_mask:
        mask_ref, refs = refs[0], refs[1:]
    if grouped:
        m_in, l_in, acc_in = refs[:3]
        refs = refs[3:]
    o_ref, m_ref, l_ref, acc_ref = refs
    b = pl.program_id(0)
    t = pl.program_id(1)
    p = pl.program_id(2)
    n_p = pl.num_programs(2)
    pos_b = pos_ref[b]
    qlen_b = qlen_ref[b]
    # last valid query of THIS block (block-dead when t*qblk >= q_len)
    last_qi = jnp.minimum((t + 1) * qblk, qlen_b) - 1
    # the page this grid step stands for (`p` itself without a window)
    page = p
    if window is not None:
        page = p + _window_first_page(pos_b, t, ps=ps, qblk=qblk,
                                      window=window)

    @pl.when(p == 0)
    def _init():
        if grouped:
            m_ref[...] = m_in[0, 0]
            l_ref[...] = l_in[0, 0]
            acc_ref[...] = acc_in[0, 0]
        else:
            m_ref[...] = jnp.full_like(m_ref, jnp.float32(_NEG_INF))
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

    # a page contributes iff it holds a position some live query of the
    # block attends (j <= pos + last_qi); dead blocks skip every page —
    # fully-dead pages are exactly zero under the online softmax, so
    # skipping them is not an approximation
    go = (t * qblk < qlen_b) & (page * ps <= pos_b + last_qi)
    if grouped:
        gid_ref, gcnt_ref = pre[3], pre[5]
        go = go & (p >= gcnt_ref[gid_ref[b]])

    @pl.when(go)
    def _compute():
        _attend_page(
            q_ref[0, 0], k_ref[0], v_ref[0],
            ks_ref[0] if has_scale else None,
            vs_ref[0] if has_scale else None,
            _live_window(t, page, pos_b, qlen_b, ps=ps, qblk=qblk,
                         rep=rep, window=window),
            mask_ref[0, 0, 0] if has_mask else None,
            m_ref, l_ref, acc_ref, scale=scale, fp8=fp8)

    @pl.when(p == n_p - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :, :1], jnp.float32(1e-30))
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _grouped_phase1_kernel(tab_ref, pos_ref, qlen_ref, gid_ref,
                           gldr_ref, gcnt_ref, q_ref, k_ref, v_ref,
                           *rest, b, mp, ps, qblk, rep, scale,
                           has_scale, fp8):
    """Phase 1 of the grouped walk — grid (q_block, group x
    shared_page): each grid step streams ONE shared page of ONE group
    (via the group leader's page table; the index map clamps dead
    steps so their DMA is skipped) and folds it into the
    online-softmax partials of every MEMBER row. Non-member rows (and
    groups with no shared span) are predicated off, so their partials
    leave this phase exactly as they entered: (-inf, 0, 0) — the
    virgin state phase 2 would have initialized anyway. The partials
    accumulate in the output blocks, which stay resident in VMEM
    across the whole (group x page) sweep of one q_block."""
    del tab_ref, gldr_ref
    if has_scale:
        ks_ref, vs_ref, m_out, l_out, acc_out = rest
    else:
        ks_ref = vs_ref = None
        m_out, l_out, acc_out = rest
    t = pl.program_id(0)
    u = pl.program_id(1)
    grp = u // mp
    sp = u % mp

    @pl.when(u == 0)
    def _init():
        m_out[...] = jnp.full_like(m_out, jnp.float32(_NEG_INF))
        l_out[...] = jnp.zeros_like(l_out)
        acc_out[...] = jnp.zeros_like(acc_out)

    # a step is live iff its group really has this shared page
    @pl.when(sp < gcnt_ref[grp])
    def _page():
        k, v = k_ref[0], v_ref[0]
        ks = ks_ref[0] if has_scale else None
        vs = vs_ref[0] if has_scale else None
        for bi in range(b):
            pos_b = pos_ref[bi]
            qlen_b = qlen_ref[bi]

            @pl.when((gid_ref[bi] == grp) & (t * qblk < qlen_b))
            def _member(bi=bi, pos_b=pos_b, qlen_b=qlen_b):
                _attend_page(
                    q_ref[bi, 0], k, v, ks, vs,
                    _live_window(t, sp, pos_b, qlen_b, ps=ps,
                                 qblk=qblk, rep=rep),
                    None, m_out.at[0, bi], l_out.at[0, bi],
                    acc_out.at[0, bi], scale=scale, fp8=fp8)


def _query_blocks(lq):
    """(query block size, query blocks) a walk over lq query positions
    a row tiles them into."""
    qblk = min(lq, 8)
    return qblk, -(-lq // qblk)


def walk_grid_bounds(pos, q_len, *, lq, page_size, max_pages, xp=jnp):
    """The two dynamic bounds of a full-attention walk's grid, from
    what the step's rows ask: (the q-blocks of the row with most live
    queries, the pages of the longest live context), each at least 1.
    A q-block at or past the first holds dead queries only, and a page
    at or past the second lies beyond every row's causal horizon, so
    the grid steps the bounds remove were predicated off. One
    expression for the traced wrapper (`xp=jnp`, int32 [B] operands of
    the compiled step) and for the host's count of the same step
    (`xp=np`, `count_walk_grid_steps`): they cannot drift."""
    qblk, nqb = _query_blocks(lq)
    n_qblk = xp.clip(xp.max((q_len + qblk - 1) // qblk), 1, nqb)
    n_pages = xp.clip(xp.max(xp.where(
        q_len > 0, (pos + q_len - 1) // page_size + 1, 1)), 1, max_pages)
    return n_qblk, n_pages


def _zero_dead_queries(out, q_len):
    """out [B, lq, H, D] with the queries at or past q_len[b] zeroed:
    the grid never writes the q-blocks past its bound."""
    alive = jnp.arange(out.shape[1], dtype=jnp.int32)[None, :] \
        < q_len[:, None]
    return jnp.where(alive[:, :, None, None], out,
                     jnp.zeros((), out.dtype))


def _ragged_attention_kernel(q, k_pool, v_pool, page_table, pos, q_len,
                             mask, k_scale=None, v_scale=None,
                             group=None, window=None):
    """`_ragged_attention_local` on every device of the kernel mesh
    (ops/pallas/__init__.py): under the tensor-parallel serving
    replica q, the pools, the scale pools and a user mask arrive
    sharded over their HEAD dimension and each device walks the pages
    of its own kv heads — no cross-device traffic; page tables and
    row operands are replicated. On one device it is the local call."""
    heads = P(None, None, "heads", None)
    rows = P()
    ops = dict(q=q, k_pool=k_pool, v_pool=v_pool, page_table=page_table,
               pos=pos, q_len=q_len)
    specs = dict(q=heads, k_pool=heads, v_pool=heads, page_table=rows,
                 pos=rows, q_len=rows)
    if mask is not None:
        ops["mask"], specs["mask"] = mask, P(None, "heads", None, None)
    if k_scale is not None:
        ops["k_scale"], ops["v_scale"] = k_scale, v_scale
        specs["k_scale"] = specs["v_scale"] = P(None, None, "heads")
    if group is not None:
        ops["group"], specs["group"] = tuple(group), (rows, rows, rows)
    extra = {} if window is None else {"window": window}
    return _per_device(
        lambda o: _ragged_attention_local(**{"mask": None, **o}, **extra),
        (specs,), heads)(ops)


def _ragged_attention_local(q, k_pool, v_pool, page_table, pos, q_len,
                            mask, k_scale=None, v_scale=None,
                            group=None, window=None):
    """q [B, lq, H, D]; pools [P, ps, H_kv, D]; page_table
    [B, max_pages] int32; pos/q_len [B] int32; mask None | additive f32
    [B, H, lq, lmax]. lq is padded up to a multiple of the query block
    so the grid tiles evenly; padded queries are dead by q_len.
    k_scale/v_scale (int8 lane): rowwise dequant scale pages
    [P, ps, H_kv] f32 streamed next to the int8 code pools — dequant
    fuses into the in-VMEM compute.

    POOL LAYOUT AND BLOCKS. Mosaic requires the last two dimensions of
    a block to be (8, 128)-divisible or to span the array's, so a page
    block cannot take one head out of the pool's [H_kv, D] minor
    dimensions. The pool keeps its [P, ps, H_kv, D] layout (one
    decision for the fp, int8, fp8 and grouped lanes, the scatter
    write, COW/swap/PKVF frames and the tensor-parallel head shard)
    and a grid step streams the WHOLE page — block (1, ps, H_kv, D),
    scale block (1, ps, H_kv) — with the kv heads as the batch
    dimension of the in-kernel dots. Queries are regrouped outside the
    kernel to [B, n_qblk, H_kv, qblk * rep, D] so a block's minor
    dimensions are whole too.

    group = (group_id, group_leader, group_cnt) selects the grouped
    two-phase walk (see the module doc). Operand contract
    (engine-enforced, host side): rows of one group carry IDENTICAL
    page-table entries for indices [0, group_cnt) — the physically
    shared prefix — and every member's pos already covers the span
    (shared pages hold committed KV). group_leader[g] names a member
    row whose table phase 1 walks; singleton rows ride with group_cnt
    0 and take phase 2 only, which is exactly the ungrouped walk. On a
    step where every group_cnt is 0 phase 1 shrinks to one grid step a
    q_block (`_grouped_phase1`).

    window (a sliding-window layer; None for full attention, which
    leaves this function's program as it was): query i of row b sees
    keys pos + i - window < j <= pos + i. The grid's page axis shrinks
    to `_window_pages` steps, counted from the page that holds the
    block's lowest visible key (`_window_first_page`), so a page wholly
    below the window is neither fetched nor computed, and the partial
    page at the edge is masked in `_live_window`. The page table may be
    a ring over fewer physical pages than it has columns (the serving
    engine's table for such layers is): only the pages of the window
    are ever read. Neither groups nor a user mask combine with it.

    The grid is as long as the step's rows ask, never as long as the
    step's SHAPE allows: a predicated-off grid step still costs a grid
    step (0.12-0.16 us on a v5e; PERF.md section 6, PR 28 and 30), and
    8 rows x 16 q-blocks x 128 pages are 16384 of them a layer where a
    step of decode rows needs a few hundred. So the q-block and page
    axes are DYNAMIC bounds (`walk_grid_bounds`): the q-blocks of the
    row with most live queries and the pages of the longest live
    context (of the window, statically, in a window layer), decided
    inside the one compiled step from `pos` and `q_len`. Groups, a
    user mask and the int8 / fp8 lanes take the same bounds: a page
    past the longest context is past every row's causal horizon
    whatever else selects pages, and phase 1 of the grouped walk runs
    over the same q-blocks, so phase 2 never reads a partial phase 1
    did not write. The q-blocks past the bound are never written, so
    the dead queries' outputs are zeroed after the call."""
    if window is not None and (group is not None or mask is not None):
        raise NotImplementedError(
            "the page walk of a sliding-window layer takes neither "
            "prefix-sharing groups nor a user attention mask")
    b, lq, h, d = q.shape
    _, ps, hkv, _ = k_pool.shape
    mp = page_table.shape[1]
    rep = h // hkv
    scale = 1.0 / math.sqrt(d)
    qblk, nqb = _query_blocks(lq)
    lq_pad = nqb * qblk
    rows = qblk * rep
    if lq_pad != lq:
        padq = jnp.zeros((b, lq_pad - lq, h, d), q.dtype)
        q = jnp.concatenate([q, padq], axis=1)
        if mask is not None:
            padm = jnp.zeros((b, h, lq_pad - lq, mp * ps), jnp.float32)
            mask = jnp.concatenate([mask, padm], axis=2)
    q5 = q.reshape(b, nqb, qblk, hkv, rep, d) \
        .transpose(0, 1, 3, 2, 4, 5).reshape(b, nqb, hkv, rows, d)
    has_scale = k_scale is not None
    grouped = group is not None
    fp8 = _is_fp8(k_pool.dtype)
    prefetch = (page_table, pos, q_len) + (tuple(group) if grouped
                                           else ())

    def live_page(bi, t, p, tab, posr, qlr, *grp):
        # clamp dead steps (block-dead rows, pages past the block's
        # causal horizon and — grouped — pages below the row's shared
        # span, which is phase-1 territory) to a live page: unchanged
        # block index, no re-fetch, compute predicated off in-kernel
        last_qi = jnp.minimum((t + 1) * qblk, qlr[bi]) - 1
        lp = jnp.clip((posr[bi] + last_qi) // ps, 0, mp - 1)
        lo = 0
        if grouped:
            gid, _, gcn = grp
            lo = jnp.minimum(gcn[gid[bi]], lp)
        if window is not None:
            # the walk starts at the window's first page
            lo = jnp.minimum(_window_first_page(
                posr[bi], t, ps=ps, qblk=qblk, window=window), lp)
            p = p + lo
        return tab[bi, jnp.clip(p, lo, lp)]

    def kv_idx(bi, t, p, *pre):
        return (live_page(bi, t, p, *pre), 0, 0, 0)

    def sc_idx(bi, t, p, *pre):
        # int8 lane: the scale pages chase the SAME clamped page-table
        # walk as the code pages, so dead grid steps skip their DMA too
        return (live_page(bi, t, p, *pre), 0, 0)

    q_spec = pl.BlockSpec((1, 1, hkv, rows, d),
                          lambda bi, t, p, *_: (bi, t, 0, 0, 0))
    in_specs = [q_spec,
                pl.BlockSpec((1, ps, hkv, d), kv_idx),
                pl.BlockSpec((1, ps, hkv, d), kv_idx)]
    ops = [q5, k_pool, v_pool]
    if has_scale:
        ops.extend([k_scale, v_scale])
        in_specs.extend([pl.BlockSpec((1, ps, hkv), sc_idx),
                         pl.BlockSpec((1, ps, hkv), sc_idx)])
    if mask is not None:
        # [B, H, lq, lmax] -> [B, n_qblk, max_pages, H_kv, rows, ps]:
        # one block per (row, q_block, page), its minor dims whole and
        # its rows in the kernel's (qblk, rep) score order
        m7 = mask.reshape(b, hkv, rep, nqb, qblk, mp, ps)
        ops.append(m7.transpose(0, 3, 5, 1, 4, 2, 6)
                   .reshape(b, nqb, mp, hkv, rows, ps))
        in_specs.append(pl.BlockSpec(
            (1, 1, 1, hkv, rows, ps),
            lambda bi, t, p, *_: (bi, t, p, 0, 0, 0)))
    with _trace32():
        n_qblk, n_pages = walk_grid_bounds(
            pos, q_len, lq=lq, page_size=ps, max_pages=mp)
        if window is not None:
            n_pages = min(mp, _window_pages(window, qblk, ps))
        if grouped:
            ops.extend(_grouped_phase1(
                prefetch, ops, n_qblk, b=b, mp=mp, ps=ps, hkv=hkv, d=d,
                qblk=qblk, nqb=nqb, rep=rep, scale=scale,
                has_scale=has_scale, fp8=fp8))
            in_specs.extend(
                pl.BlockSpec((1, 1, hkv, rows, w),
                             lambda bi, t, p, *_: (t, bi, 0, 0, 0))
                for w in (_LANES, _LANES, d))
        kernel = functools.partial(
            _ragged_kernel, ps=ps, qblk=qblk, rep=rep, scale=scale,
            has_mask=mask is not None, has_scale=has_scale, fp8=fp8,
            grouped=grouped,
            **({} if window is None else {"window": window}))
        out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(prefetch),
                grid=(b, n_qblk, n_pages),
                in_specs=in_specs,
                out_specs=q_spec,
                scratch_shapes=[
                    pltpu.VMEM((hkv, rows, _LANES), jnp.float32),
                    pltpu.VMEM((hkv, rows, _LANES), jnp.float32),
                    pltpu.VMEM((hkv, rows, d), jnp.float32),
                ]),
            out_shape=jax.ShapeDtypeStruct(q5.shape, q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary",
                                     "arbitrary")),
            interpret=_INTERPRET,
            **KERNELS["ragged_walk"],
        )(*prefetch, *ops)
    out = out.reshape(b, nqb, hkv, qblk, rep, d) \
        .transpose(0, 1, 3, 2, 4, 5).reshape(b, lq_pad, h, d)[:, :lq]
    return _zero_dead_queries(out, q_len)


def _grouped_phase1(prefetch, ops, n_qblk, *, b, mp, ps, hkv, d, qblk,
                    nqb, rep, scale, has_scale, fp8):
    """Run phase 1 of the grouped walk over `ops` (q5, pools and, on
    the int8 lane, scale pools — the operands phase 2 takes too) and
    return the per-row partials (m, l, acc), each
    [nqb, B, H_kv, qblk * rep, 128 | D] f32.

    Both axes of the grid are dynamic bounds. The q-block axis is
    `n_qblk`, the walk proper's own bound (`walk_grid_bounds`, computed
    once by the caller and used by both phases): the partials of the
    q-blocks at or past it are never written, and phase 2, over the
    same q-blocks, never reads them. The (group x page) axis is B * mp
    steps when some group has a shared span, ONE when none has. That
    one step is predicated off like every step of a sweep with nothing
    to do, after its `_init` has written the virgin partials, so the
    results are the full sweep's bit for bit, without its B * mp - 1
    idle grid steps a q_block (0.12 us each on a v5e: a third of the
    serving step's device time where nothing is shared, PERF.md §6)."""
    rows = qblk * rep
    *_, gcn = prefetch
    sweep = jnp.where(jnp.any(gcn > 0), b * mp, 1)

    def shared_page(t, u, tab, posr, qlr, gid, gld, gcn):
        # shared page sp of group grp via the LEADER's page table;
        # dead steps (groups with fewer shared pages, or none) clamp
        # to the last live shared page — unchanged block index, DMA
        # skipped — and empty groups to the trash page 0
        grp = u // mp
        cnt = gcn[grp]
        live = jnp.clip(u % mp, 0, jnp.maximum(cnt - 1, 0))
        return jnp.where(cnt > 0, tab[gld[grp], live], 0)

    kv_spec = pl.BlockSpec(
        (1, ps, hkv, d), lambda t, u, *pre: (shared_page(t, u, *pre),
                                             0, 0, 0))
    in_specs = [pl.BlockSpec((b, 1, hkv, rows, d),
                             lambda t, u, *_: (0, t, 0, 0, 0)),
                kv_spec, kv_spec]
    if has_scale:
        sc_spec = pl.BlockSpec(
            (1, ps, hkv), lambda t, u, *pre: (shared_page(t, u, *pre),
                                              0, 0))
        in_specs.extend([sc_spec, sc_spec])
    widths = (_LANES, _LANES, d)
    return pl.pallas_call(
        functools.partial(
            _grouped_phase1_kernel, b=b, mp=mp, ps=ps, qblk=qblk,
            rep=rep, scale=scale, has_scale=has_scale, fp8=fp8),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(n_qblk, sweep),
            in_specs=in_specs,
            out_specs=[pl.BlockSpec((1, b, hkv, rows, w),
                                    lambda t, u, *_: (t, 0, 0, 0, 0))
                       for w in widths]),
        out_shape=[jax.ShapeDtypeStruct((nqb, b, hkv, rows, w),
                                        jnp.float32) for w in widths],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_INTERPRET,
        **KERNELS["grouped_phase1"],
    )(*prefetch, *ops)



def gqa_attend_reference(q, k, v, mask):
    """Grouped-query attention over un-repeated K/V buffers:
    q [B, l, H, D] against k/v [B, lmax, H_kv, D], mask bool or
    additive float broadcastable [B|1, 1|H, l, lmax].

    Unrolled over the `rep = H / H_kv` group members so every dot has
    EXACTLY the shape the old `repeat_interleave` + SDPA path gave XLA
    — which makes the output bit-identical to that path (a fused
    [rep*l, D] x [D, lmax] grouping reassociates the reduction and
    drifts by an ulp) while never materializing the H-fold copy of the
    cache. rep is a small static (1..8): the unroll is trace-time."""
    b, l, h, d = q.shape
    hkv = k.shape[2]
    rep = h // hkv
    scale = 1.0 / math.sqrt(d)
    qg = q.reshape(b, l, hkv, rep, d)
    is_bool = mask.dtype == jnp.bool_
    outs = []
    for r in range(rep):
        # heads served in this unroll step: h = g*rep + r for every g
        mh = mask if mask.shape[1] == 1 else mask[:, r::rep]
        s = jnp.einsum("blgd,bmgd->bglm", qg[:, :, :, r], k) * scale
        s = s.astype(jnp.float32)
        if is_bool:
            s = jnp.where(mh, s, jnp.float32(_NEG_INF))
        else:
            s = s + mh.astype(jnp.float32)
        a = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        outs.append(jnp.einsum("bglm,bmgd->blgd", a, v))
    return jnp.stack(outs, axis=3).reshape(b, l, h, d)


def paged_attention_reference(q, k_pool, v_pool, page_table, pos,
                              mask=None):
    """Pure-JAX reference: gather the rows' pages into the dense
    logical view and run the masked grouped softmax — the same math as
    `paged_kv_gather` + grouped SDPA, shaped for this op's signature.
    Off-TPU tier-1 runs land here (bit-identical to the gather impl by
    construction); the kernel is tested against it."""
    b, l, h, d = q.shape
    ps, hkv = k_pool.shape[1], k_pool.shape[2]
    mp = page_table.shape[1]
    lmax = mp * ps
    tab = page_table.astype(jnp.int32)
    kf = jnp.take(k_pool, tab, axis=0).reshape(b, lmax, hkv, d)
    vf = jnp.take(v_pool, tab, axis=0).reshape(b, lmax, hkv, d)
    if _is_fp8(k_pool.dtype):
        # fp8 lane: pure-convert dequant of the gathered view — the
        # same upconvert the kernel fuses in VMEM
        kf = kf.astype(jnp.float32)
        vf = vf.astype(jnp.float32)
    j = jnp.arange(lmax, dtype=jnp.int32)[None, :]
    add = jnp.where(j <= pos.astype(jnp.int32)[:, None],
                    jnp.float32(0.0), jnp.float32(_NEG_INF))
    add = add[:, None, None, :]                       # [B, 1, 1, lmax]
    if mask is not None:
        add = add + mask.reshape(b, h, 1, lmax)
    return gqa_attend_reference(q, kf, vf, add)


def paged_decode_attention(q, k_pool, v_pool, page_table, pos,
                           mask=None):
    """Single-token ragged paged-attention decode (the registered op's
    forward). q [B, 1, H, D]; k/v pools [P, page_size, H_kv, D];
    page_table [B, max_pages]; pos [B] (or scalar, broadcast) — the
    per-row count of positions already written BEFORE this step's
    token, i.e. positions 0..pos are attended (the new token's K/V was
    just scattered at pos). mask: optional user attention mask
    (bool or additive float, broadcastable [B|1, H|1, 1, lmax]),
    composed with the positional window in-kernel."""
    b, l, h, d = q.shape
    if l != 1:
        raise ValueError(
            f"paged_decode_attention is a single-token decode kernel; "
            f"got l={l} (chunked prefill stays on the gather path)")
    lmax = page_table.shape[1] * k_pool.shape[1]
    posv = pos.astype(jnp.int32)
    if posv.ndim == 0:
        posv = jnp.broadcast_to(posv[None], (b,))
    if mask is not None:
        mask = _mask_to_additive(mask, b, h, lmax)
    if _use_kernel():
        # the ragged walk at q_len 1: identical attend window (query 0
        # sees keys j <= pos) and page order
        if mask is not None:
            mask = mask.reshape(b, h, 1, lmax)
        return _ragged_attention_kernel(
            q, k_pool, v_pool, page_table.astype(jnp.int32), posv,
            jnp.ones((b,), jnp.int32), mask)
    return paged_attention_reference(q, k_pool, v_pool, page_table,
                                     posv, mask)


def _ragged_mask_attend(q, kf, vf, pos, q_len, mask, window=None):
    """Shared tail of the ragged references: grouped softmax over the
    dense logical K/V views under the ragged causal window — query i of
    row b attends keys j <= pos[b] + i (and, in a sliding-window layer,
    j > pos[b] + i - window), queries at i >= q_len[b] are fully masked
    (their outputs are unspecified)."""
    b, lq, h, _ = q.shape
    lmax = kf.shape[1]
    i = jnp.arange(lq, dtype=jnp.int32)[None, :, None]
    j = jnp.arange(lmax, dtype=jnp.int32)[None, None, :]
    live = (i < q_len.astype(jnp.int32)[:, None, None]) & \
        (j <= pos.astype(jnp.int32)[:, None, None] + i)
    if window is not None:
        live = live & (j > pos.astype(jnp.int32)[:, None, None] + i
                       - window)
    add = jnp.where(live, jnp.float32(0.0), jnp.float32(_NEG_INF))
    add = add[:, None]                            # [B, 1, lq, lmax]
    if mask is not None:
        add = add + mask.reshape(b, h, lq, lmax)
    return gqa_attend_reference(q, kf, vf, add)


def ragged_attention_reference(q, k_pool, v_pool, page_table, pos,
                               q_len, mask=None, window=None):
    """Pure-JAX ragged reference: gather the rows' pages into the dense
    logical view and run the grouped softmax under the ragged causal
    window. At lq == 1 this is EXACTLY `paged_attention_reference`'s
    math (same gather, same mask, same grouped dots), so l==1 rows stay
    bit-identical to the gather path; for l > 1 rows the grouped unroll
    reproduces the dense repeat_interleave + SDPA oracle (the same
    per-group shape argument as gqa_attend_reference)."""
    b, lq, h, d = q.shape
    ps, hkv = k_pool.shape[1], k_pool.shape[2]
    lmax = page_table.shape[1] * ps
    tab = page_table.astype(jnp.int32)
    kf = jnp.take(k_pool, tab, axis=0).reshape(b, lmax, hkv, d)
    vf = jnp.take(v_pool, tab, axis=0).reshape(b, lmax, hkv, d)
    if _is_fp8(k_pool.dtype):
        # fp8 lane: pure-convert dequant of the gathered view
        kf = kf.astype(jnp.float32)
        vf = vf.astype(jnp.float32)
    return _ragged_mask_attend(q, kf, vf, pos, q_len, mask, window)


def dequantize_paged_q8(pool, scale_pool, page_table):
    """int8 code pool [P, ps, H_kv, D] + rowwise scale pool
    [P, ps, H_kv] f32 -> each row's dense DEQUANTIZED f32 logical view
    [B, max_pages * ps, H_kv, D]. This is also the forward of
    generation.py's `paged_kv_gather_q8` op (the multi-token read path
    chunked prefill and the gather A/B impl run on) — the q8 ragged
    reference dequantizes through this SAME elementwise expression, so
    kernel-lane (reference) and gather-path results stay bit-identical
    on CPU."""
    tab = page_table.astype(jnp.int32)
    g = jnp.take(pool, tab, axis=0)               # [B, mp, ps, H, D]
    s = jnp.take(scale_pool, tab, axis=0)         # [B, mp, ps, H]
    deq = g.astype(jnp.float32) * s[..., None]
    b, m, ps = deq.shape[0], deq.shape[1], deq.shape[2]
    return deq.reshape((b, m * ps) + deq.shape[3:])


def ragged_attention_reference_q8(q, k_pool, v_pool, k_scale, v_scale,
                                  page_table, pos, q_len, mask=None):
    """Pure-JAX int8 ragged reference: dequantize the rows' code+scale
    pages into the dense f32 logical view (via `dequantize_paged_q8`,
    shared with the quantized-gather op so the two CPU paths cannot
    drift) and run the same ragged grouped softmax as the fp
    reference."""
    kf = dequantize_paged_q8(k_pool, k_scale, page_table)
    vf = dequantize_paged_q8(v_pool, v_scale, page_table)
    return _ragged_mask_attend(q, kf, vf, pos, q_len, mask)


def ragged_paged_attention(q, k_pool, v_pool, page_table, pos, q_len,
                           mask=None, window=None):
    """Ragged paged attention over per-row query lengths (the
    registered op's forward): one invocation serves a mixed batch of
    mid-prefill rows (q_len > 1) and decoding rows (q_len == 1) against
    the same paged pool. q [B, lq, H, D] — row b's tokens occupy global
    positions pos[b] .. pos[b] + q_len[b] - 1 (their K/V was just
    scattered there); query i attends keys j <= pos[b] + i. Rows may be
    dead (q_len == 0): no position advances and the row's output is
    zero, as is every query's at or past q_len[b] (on the kernel path;
    the reference leaves them unspecified-but-finite). mask: optional
    user attention mask (bool or additive float, broadcastable
    [B|1, H|1, lq|1, lmax]), composed
    with the ragged causal window in-kernel. window (static; None for
    full attention): the layer's sliding window, the query's own
    position included — query i then attends only keys
    j > pos[b] + i - window, and the walk starts at the window's first
    page (`_ragged_attention_local`)."""
    b, lq, h, d = q.shape
    lmax = page_table.shape[1] * k_pool.shape[1]
    posv = pos.astype(jnp.int32)
    if posv.ndim == 0:
        posv = jnp.broadcast_to(posv[None], (b,))
    qlv = q_len.astype(jnp.int32)
    if qlv.ndim == 0:
        qlv = jnp.broadcast_to(qlv[None], (b,))
    if mask is not None:
        mask = _mask_to_additive(mask, b, h, lmax, lq)
        if lq == 1:
            mask = mask.reshape(b, h, 1, lmax)
    if _use_kernel():
        return _ragged_attention_kernel(
            q, k_pool, v_pool, page_table.astype(jnp.int32), posv, qlv,
            mask, window=window)
    return ragged_attention_reference(q, k_pool, v_pool, page_table,
                                      posv, qlv, mask, window)


def ragged_paged_attention_q8(q, k_pool, v_pool, k_scale, v_scale,
                              page_table, pos, q_len, mask=None):
    """Ragged paged attention over an INT8 paged KV pool (the
    registered op's forward): same per-row q_len semantics as
    `ragged_paged_attention`, but k/v are int8 code pools
    [P, page_size, H_kv, D] with rowwise scale pools [P, page_size,
    H_kv] f32 — one scale per (position, kv head), written by the
    quantized paged scatter. On TPU (and in interpret mode) the code
    and scale pages stream into VMEM together and dequant fuses into
    the online-softmax loop; off-TPU the reference dequantizes through
    the same expression as `paged_kv_gather_q8`, keeping the kernel
    lane bit-identical to the quantized-gather path on CPU."""
    b, lq, h, d = q.shape
    lmax = page_table.shape[1] * k_pool.shape[1]
    posv = pos.astype(jnp.int32)
    if posv.ndim == 0:
        posv = jnp.broadcast_to(posv[None], (b,))
    qlv = q_len.astype(jnp.int32)
    if qlv.ndim == 0:
        qlv = jnp.broadcast_to(qlv[None], (b,))
    if mask is not None:
        mask = _mask_to_additive(mask, b, h, lmax, lq)
        if lq == 1:
            mask = mask.reshape(b, h, 1, lmax)
    ks = k_scale.astype(jnp.float32)
    vs = v_scale.astype(jnp.float32)
    if _use_kernel():
        return _ragged_attention_kernel(
            q, k_pool, v_pool, page_table.astype(jnp.int32), posv, qlv,
            mask, k_scale=ks, v_scale=vs)
    return ragged_attention_reference_q8(q, k_pool, v_pool, ks, vs,
                                         page_table, posv, qlv, mask)


def _grouped_operands(b, pos, q_len, group_id, group_leader,
                      group_cnt):
    """Normalize the grouped op's scalar operands to int32 [B]."""
    out = []
    for v in (pos, q_len, group_id, group_leader, group_cnt):
        v = v.astype(jnp.int32)
        if v.ndim == 0:
            v = jnp.broadcast_to(v[None], (b,))
        out.append(v)
    return out


def ragged_paged_attention_grouped(q, k_pool, v_pool, page_table, pos,
                                   q_len, group_id, group_leader,
                                   group_cnt, mask=None):
    """Prefix-sharing-aware ragged paged attention (the registered
    op's forward): same per-row `pos`/`q_len` semantics and the same
    OUTPUT as `ragged_paged_attention`, but rows whose page tables
    share a physical-page prefix declare it via `group_id` [B] (row ->
    group), `group_leader` [B] (group -> a member row whose table
    holds the shared prefix) and `group_cnt` [B] (group -> shared page
    count, 0 for singletons), and the TPU kernel streams each shared
    page from HBM once per GROUP instead of once per row (the
    two-phase grouped walk — see the module doc). Grouping is a pure
    HBM-traffic hint: off-TPU the op runs the SAME ungrouped
    reference, so grouped and ungrouped results are bit-identical on
    CPU by construction. A user mask falls back to the ungrouped
    kernel (the engine never passes one on this path; the outputs are
    identical either way, only the walk differs)."""
    b = q.shape[0]
    posv, qlv, gid, gld, gcn = _grouped_operands(
        b, pos, q_len, group_id, group_leader, group_cnt)
    if _use_kernel() and mask is None:
        return _ragged_attention_kernel(
            q, k_pool, v_pool, page_table.astype(jnp.int32), posv, qlv,
            None, group=(gid, gld, gcn))
    return ragged_paged_attention(q, k_pool, v_pool, page_table, posv,
                                  qlv, mask)


def ragged_paged_attention_grouped_q8(q, k_pool, v_pool, k_scale,
                                      v_scale, page_table, pos, q_len,
                                      group_id, group_leader,
                                      group_cnt, mask=None):
    """int8 lane of the grouped walk: code pages AND their rowwise
    scale pages chase the same two-phase page stream (a page and its
    scales are one unit — exactly the q8 contract everywhere else),
    dequant fused into the in-VMEM softmax loop. Output identical to
    `ragged_paged_attention_q8`; off-TPU it IS the q8 reference."""
    b = q.shape[0]
    posv, qlv, gid, gld, gcn = _grouped_operands(
        b, pos, q_len, group_id, group_leader, group_cnt)
    ks = k_scale.astype(jnp.float32)
    vs = v_scale.astype(jnp.float32)
    if _use_kernel() and mask is None:
        return _ragged_attention_kernel(
            q, k_pool, v_pool, page_table.astype(jnp.int32), posv, qlv,
            None, k_scale=ks, v_scale=vs, group=(gid, gld, gcn))
    return ragged_paged_attention_q8(q, k_pool, v_pool, ks, vs,
                                     page_table, posv, qlv, mask)


# ---------------------------------------------------------------------
# Decode megakernel (PADDLE_TPU_MEGAKERNEL): the op-soup neighbors of
# the walk — LoRA delta gather, quantize-then-scatter KV write, greedy
# argmax / spec acceptance — as fused prologues/epilogues. The shared
# jnp expression bodies live HERE and the unfused registered ops in
# nlp/generation.py delegate to them, so fused and unfused paths are
# the same floating-point program by construction (the CPU bit-identity
# oracle), not two implementations that happen to agree.
# ---------------------------------------------------------------------


def quantize_kv_rowwise(u):
    """Rowwise int8 quantization of K/V values [..., D]: one f32 scale
    per leading row (per (token, kv head) in the paged pool), codes =
    round(u / scale) clipped to [-127, 127]. Unlike the dense cache's
    calibrated per-head CONSTANT scales (see _kv_update_q8_fwd), the
    paged pool quantizes at WRITE time with the row's own absmax —
    serving admits arbitrary traffic with no calibration pass, and the
    scale rides in the page right next to its codes, so preemption
    swap, COW copies and prefix sharing move (codes, scale) as one
    unit and a later reader dequantizes to exactly the same floats.
    Returns (codes int8 same shape, scales f32 u.shape[:-1])."""
    uf = u.astype(jnp.float32)
    amax = jnp.max(jnp.abs(uf), axis=-1)
    # written as a multiply by the f32 constant 1/127 (not a divide):
    # XLA rewrites x / 127 into exactly this under jit, so spelling it
    # out keeps eager and jitted scales BIT-identical — the roundtrip
    # bit-exactness tests depend on it
    scale = jnp.maximum(amax, jnp.float32(1e-8)) \
        * jnp.float32(1.0 / 127.0)
    codes = jnp.clip(jnp.round(uf / scale[..., None]),
                     -127, 127).astype(jnp.int8)
    return codes, scale


def _paged_flat_slots(ps, pos, page_table, l):
    """The ONE paged-write address map, shared by the XLA scatters and
    the Pallas scatter kernels' prefetched indices: row b's token t
    lands at logical position pos[b] + t, i.e. pool slot
    page_table[b, p // page_size] * page_size + p % page_size.
    Positions past the row's addressable window (chunk padding on the
    last prefill chunk) redirect into page 0 — the reserved trash
    page — so the write never needs a branch and never clobbers live
    pages. Returns int32 [B, l] flat pool-slot indices."""
    addressable = page_table.shape[1] * ps
    p = pos.astype(jnp.int32)[:, None] + \
        jnp.arange(l, dtype=jnp.int32)[None, :]          # [B, l] logical
    pidx = jnp.clip(p // ps, 0, page_table.shape[1] - 1)
    ids = jnp.take_along_axis(page_table.astype(jnp.int32), pidx,
                              axis=1)                    # [B, l] pages
    flat = ids * ps + p % ps
    return jnp.where(p < addressable, flat, p % ps)      # OOB -> trash


def paged_scatter(pool, upd, pos, page_table):
    """Scatter upd [B, l, H, D] into the shared pool
    [num_pages, page_size, H, D] (the `kv_cache_update_paged` op's
    forward — see _paged_flat_slots for the address map, including the
    trash-page redirect and the all-zero-table convention for
    free/retired rows). One fixed-shape scatter serves decode (l=1,
    batch B) and chunked prefill (l=chunk, batch 1) alike."""
    ps = pool.shape[1]
    l = upd.shape[1]
    flat = _paged_flat_slots(ps, pos, page_table, l)
    if _is_fp8(pool.dtype):
        # fp8 lane: XLA's f32->e4m3 convert yields NaN past the
        # format's range, not a saturate — clip to +-448 first so a
        # pathological activation can never poison the pool
        upd = jnp.clip(upd.astype(jnp.float32), -448.0, 448.0)
    flat_pool = pool.reshape((-1,) + pool.shape[2:])
    flat_pool = flat_pool.at[flat.reshape(-1)].set(
        upd.astype(pool.dtype).reshape((-1,) + upd.shape[2:]))
    return flat_pool.reshape(pool.shape)


def paged_scatter_q8(pool, scale_pool, upd, pos, page_table):
    """Quantize-then-scatter in ONE program (the
    `kv_cache_update_paged_q8` op's forward): upd [B, l, H, D] is
    rowwise-int8 quantized (quantize_kv_rowwise) and its codes land in
    the int8 pool [num_pages, page_size, H, D] while the per-row
    scales land at the SAME flat slots of the scale pool
    [num_pages, page_size, H]. Address math identical to the float
    scatter. Returns (pool, scale_pool)."""
    ps = pool.shape[1]
    l = upd.shape[1]
    flat = _paged_flat_slots(ps, pos, page_table, l)
    codes, scales = quantize_kv_rowwise(upd)   # [B,l,H,D] i8 / [B,l,H]
    flat_pool = pool.reshape((-1,) + pool.shape[2:])
    flat_pool = flat_pool.at[flat.reshape(-1)].set(
        codes.reshape((-1,) + codes.shape[2:]))
    flat_sc = scale_pool.reshape((-1,) + scale_pool.shape[2:])
    flat_sc = flat_sc.at[flat.reshape(-1)].set(
        scales.reshape((-1,) + scales.shape[2:]))
    return (flat_pool.reshape(pool.shape),
            flat_sc.reshape(scale_pool.shape))


def _scatter_write_kernel(flat_ref, upd_ref, pool_ref, out_ref):
    # grid step i owns token i's [1, H, D] tile; the out BlockSpec
    # routes the write to pool slot flat[i], and the pool->out alias
    # leaves every slot no grid step touches byte-identical
    del flat_ref, pool_ref
    out_ref[...] = upd_ref[...].astype(out_ref.dtype)


def _paged_scatter_kernel(pool, upd, pos, page_table):
    """Pallas paged KV scatter (the megakernel's write stage): the
    flat slot of each of the B*l new tokens is prefetched as a scalar
    and chased by the out BlockSpec's index map, so each grid step
    DMAs one token's [H, D] tile straight into its pool slot.
    `input_output_aliases` pins out to the pool operand — untouched
    slots keep their bytes, and duplicate trash-slot writes resolve
    last-write-wins under the sequential grid, exactly the XLA
    scatter's semantics. fp8 pools clip to +-448 BEFORE the kernel
    (same rationale as paged_scatter)."""
    b, l, h, d = upd.shape
    flat = _paged_flat_slots(pool.shape[1], pos, page_table, l)
    if _is_fp8(pool.dtype):
        upd = jnp.clip(upd.astype(jnp.float32), -448.0, 448.0)
    flat_pool = pool.reshape((-1,) + pool.shape[2:])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * l,),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda i, f: (i, 0, 0)),
            pl.BlockSpec((1, h, d), lambda i, f: (f[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda i, f: (f[i], 0, 0)),
    )
    with _trace32():
        out = pl.pallas_call(
            _scatter_write_kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(flat_pool.shape,
                                           pool.dtype),
            # flattened-input indices COUNT the scalar-prefetch leaf:
            # flat=0, upd=1, pool=2 (the jax megablox gmm convention)
            input_output_aliases={2: 0},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=_INTERPRET,
            **KERNELS["scatter_write"],
        )(flat.reshape(-1), upd.reshape(b * l, h, d), flat_pool)
    return out.reshape(pool.shape)


def _scatter_q8_write_kernel(flat_ref, upd_ref, pool_ref, code_ref,
                             sc_ref):
    # quantize-on-write: the SAME expressions as quantize_kv_rowwise,
    # applied to this grid step's [1, H, D] tile while it is still in
    # VMEM — the codes leave through the aliased pool, the rowwise
    # scales as a dense [1, 1, H] row of the step's own output
    del flat_ref, pool_ref
    uf = upd_ref[...].astype(jnp.float32)
    amax = jnp.max(jnp.abs(uf), axis=-1)
    scale = jnp.maximum(amax, jnp.float32(1e-8)) \
        * jnp.float32(1.0 / 127.0)
    code_ref[...] = jnp.clip(jnp.round(uf / scale[..., None]),
                             -127, 127).astype(code_ref.dtype)
    sc_ref[...] = scale[:, None, :].astype(sc_ref.dtype)


def _paged_scatter_q8_kernel(pool, scale_pool, upd, pos, page_table):
    """Pallas quantize-then-scatter (the megakernel's q8 write stage):
    same prefetched-slot routing as _paged_scatter_kernel, with the
    rowwise int8 quantization fused into the write so the new token's
    f32 K/V never round-trips HBM between projection and pool. The
    codes alias their pool; slot semantics as the fp kernel. A token's
    H scales are one sub-tile row of the [P * ps, H] scale pool, which
    no legal block can address, so the kernel returns the step's
    scales densely ([B * l, 1, H], 1/D of the code bytes) and the same
    XLA scatter as `paged_scatter_q8` lands them at the same slots."""
    b, l, h, d = upd.shape
    flat = _paged_flat_slots(pool.shape[1], pos, page_table, l)
    flat_pool = pool.reshape((-1,) + pool.shape[2:])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * l,),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda i, f: (i, 0, 0)),
            pl.BlockSpec((1, h, d), lambda i, f: (f[i], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, h, d), lambda i, f: (f[i], 0, 0)),
            pl.BlockSpec((1, 1, h), lambda i, f: (i, 0, 0)),
        ],
    )
    with _trace32():
        codes, scales = pl.pallas_call(
            _scatter_q8_write_kernel,
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct(flat_pool.shape, pool.dtype),
                jax.ShapeDtypeStruct((b * l, 1, h), scale_pool.dtype),
            ],
            input_output_aliases={2: 0},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=_INTERPRET,
            **KERNELS["scatter_q8_write"],
        )(flat.reshape(-1), upd.reshape(b * l, h, d), flat_pool)
    flat_sc = scale_pool.reshape((-1,) + scale_pool.shape[2:])
    flat_sc = flat_sc.at[flat.reshape(-1)].set(scales.reshape(b * l, h))
    return (codes.reshape(pool.shape),
            flat_sc.reshape(scale_pool.shape))


def lora_delta(x, a, b, scale):
    """Per-row batched LoRA delta (the `lora_delta` op's forward —
    multi-tenant adapter serving): x [B, W, in] hidden states,
    a [B, in, R] / b [B, R, out] the rows' GATHERED low-rank pairs
    (each row carries ITS OWN adapter's weights — tenant identity is
    operand data, not a trace), scale [B] the per-row LoRA scaling
    (alpha/r; 0 for base-model rows). Returns `(x @ a) @ b * scale`
    in x's dtype — rank-R zero padding and the all-zero base page
    contribute exactly 0, so base rows degenerate bit-exactly."""
    t = jnp.einsum("bwi,bir->bwr", x, a.astype(x.dtype))
    d = jnp.einsum("bwr,bro->bwo", t, b.astype(x.dtype))
    return (d * scale[:, None, None].astype(x.dtype)).astype(x.dtype)


def _lora_paged_kernel(page_ref, x_ref, a_ref, b_ref, s_ref, o_ref):
    del page_ref
    x = x_ref[...]                                # [1, W, IN]
    a = a_ref[...].astype(x.dtype)                # [1, IN, R]
    bw = b_ref[...].astype(x.dtype)               # [1, R, OUT]
    # Mosaic's matmul accumulates in 32 bits; round to x's dtype after
    # each dot, as the reference einsums do
    t = jax.lax.dot_general(
        x[0], a[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=_prec(x.dtype)).astype(x.dtype)
    d = jax.lax.dot_general(
        t, bw[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=_prec(x.dtype)).astype(x.dtype)
    s = s_ref[0, 0, 0].astype(x.dtype)
    o_ref[...] = (d * s).astype(o_ref.dtype)[None]


def lora_delta_paged(x, a_pool, b_pool, apage, ascale):
    """Per-row PAGED LoRA delta (the megakernel's fused gather): the
    same math as `lora_delta`, but each row's A/B pair is gathered
    from the shared paged adapter pools INSIDE the op —
    a_pool [P, in, R] / b_pool [P, R, out] are the WHOLE pools,
    apage [B] int32 the rows' adapter page ids (0 = the reserved
    all-zero base page, contributing exactly 0), ascale [B] f32 the
    per-row scaling. On TPU (and interpret mode) a Pallas kernel's
    BlockSpec index maps chase `apage` via scalar prefetch — row b's
    adapter page streams through VMEM ONCE, the same trick the page
    walk plays with `page_table`, instead of XLA materializing a
    gathered [B, in, R] copy in HBM per projection. ascale rides as a
    [B, 1, 1] f32 VMEM operand (f32 can't share the int32
    scalar-prefetch lane; the two unit minor dims make a one-row block
    legal). Off-TPU the forward IS gather + `lora_delta` — bit-identical
    to the unfused in-trace path by construction."""
    ap = apage.astype(jnp.int32)
    sc = ascale.astype(jnp.float32)
    if not _use_kernel():
        a = jnp.take(a_pool, ap, axis=0)
        b = jnp.take(b_pool, ap, axis=0)
        return lora_delta(x, a, b, sc)
    bsz, w, cin = x.shape
    r, cout = a_pool.shape[2], b_pool.shape[2]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bsz,),
        in_specs=[
            pl.BlockSpec((1, w, cin), lambda i, p: (i, 0, 0)),
            pl.BlockSpec((1, cin, r), lambda i, p: (p[i], 0, 0)),
            pl.BlockSpec((1, r, cout), lambda i, p: (p[i], 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda i, p: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, w, cout), lambda i, p: (i, 0, 0)),
    )
    with _trace32():
        out = pl.pallas_call(
            _lora_paged_kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((bsz, w, cout), x.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=_INTERPRET,
            **KERNELS["lora_paged"],
        )(ap, x, a_pool, b_pool, sc.reshape(bsz, 1, 1))
    return out


def _megakernel_lora_prologue(q, k_new, v_new, rest):
    """Add the rows' paged q/k/v LoRA deltas to the base projections
    (the megakernel's prologue). Deltas are computed on the flat
    [B, W, out] view and reshaped — elementwise add commutes with
    reshape bit-exactly, so this matches the unfused model path that
    adds before the head split."""
    x, aq, bq, ak, bk, av, bv, apage, ascale = rest
    q = q + lora_delta_paged(x, aq, bq, apage, ascale).reshape(q.shape)
    k_new = k_new + lora_delta_paged(x, ak, bk, apage,
                                     ascale).reshape(k_new.shape)
    v_new = v_new + lora_delta_paged(x, av, bv, apage,
                                     ascale).reshape(v_new.shape)
    return q, k_new, v_new


def megakernel_decode(q, k_new, v_new, k_pool, v_pool, page_table,
                      pos, q_len, *rest, grouped=False, lora=False):
    """The fused decode layer (fp / fp8 pools — gated
    PADDLE_TPU_MEGAKERNEL, see module doc): LoRA prologue (when
    `lora`, `rest` carries (x, aq, bq, ak, bk, av, bv, apage,
    ascale) after the group triple) -> paged scatter of the new K/V
    (Pallas in-place kernel on TPU/interpret, the shared XLA scatter
    off-TPU) -> the unchanged ragged[-grouped] walk over the updated
    pools (when `grouped`, `rest` leads with (group_id, group_leader,
    group_cnt)). Returns (out, k_pool, v_pool). Off-TPU every stage
    IS the unfused ops' shared forward, so gate-on CPU serving is
    bit-identical to gate-off by construction."""
    rest = list(rest)
    group = None
    if grouped:
        group, rest = rest[:3], rest[3:]
    if lora:
        q, k_new, v_new = _megakernel_lora_prologue(q, k_new, v_new,
                                                    rest)
    if _use_kernel():
        k_pool = _paged_scatter_kernel(k_pool, k_new, pos, page_table)
        v_pool = _paged_scatter_kernel(v_pool, v_new, pos, page_table)
    else:
        k_pool = paged_scatter(k_pool, k_new, pos, page_table)
        v_pool = paged_scatter(v_pool, v_new, pos, page_table)
    if grouped:
        out = ragged_paged_attention_grouped(
            q, k_pool, v_pool, page_table, pos, q_len, *group)
    else:
        out = ragged_paged_attention(q, k_pool, v_pool, page_table,
                                     pos, q_len)
    return out, k_pool, v_pool


def megakernel_decode_q8(q, k_new, v_new, k_pool, v_pool,
                         k_scale_pool, v_scale_pool, page_table, pos,
                         q_len, *rest, grouped=False, lora=False):
    """int8 lane of the fused decode layer: LoRA prologue ->
    quantize-then-scatter (rowwise codes + scales produced in the
    same kernel pass that reads the new token's K/V) -> the q8
    ragged[-grouped] walk. `rest` layout as megakernel_decode.
    Returns (out, k_pool, v_pool, k_scale_pool, v_scale_pool)."""
    rest = list(rest)
    group = None
    if grouped:
        group, rest = rest[:3], rest[3:]
    if lora:
        q, k_new, v_new = _megakernel_lora_prologue(q, k_new, v_new,
                                                    rest)
    if _use_kernel():
        k_pool, k_scale_pool = _paged_scatter_q8_kernel(
            k_pool, k_scale_pool, k_new, pos, page_table)
        v_pool, v_scale_pool = _paged_scatter_q8_kernel(
            v_pool, v_scale_pool, v_new, pos, page_table)
    else:
        k_pool, k_scale_pool = paged_scatter_q8(
            k_pool, k_scale_pool, k_new, pos, page_table)
        v_pool, v_scale_pool = paged_scatter_q8(
            v_pool, v_scale_pool, v_new, pos, page_table)
    if grouped:
        out = ragged_paged_attention_grouped_q8(
            q, k_pool, v_pool, k_scale_pool, v_scale_pool, page_table,
            pos, q_len, *group)
    else:
        out = ragged_paged_attention_q8(
            q, k_pool, v_pool, k_scale_pool, v_scale_pool, page_table,
            pos, q_len)
    return out, k_pool, v_pool, k_scale_pool, v_scale_pool


_ARGMAX_ROWS = 8


def _argmax_epilogue_kernel(x_ref, o_ref):
    # one grid step per sublane tile of 8 rows; each whole vocab row
    # rides the VMEM block, so the reduction never leaves the tile.
    # first-max tie-breaking == jnp.argmax: min index among positions
    # equal to the row max
    x = x_ref[...].astype(jnp.float32)               # [8, V]
    m = jnp.max(x, axis=1, keepdims=True)
    idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    first = jnp.min(jnp.where(x == m, idx, x.shape[1]), axis=1)
    # int32 output keeps the lane dim: broadcast across _LANES and
    # let the caller slice column 0
    o_ref[...] = jnp.broadcast_to(first[:, None], o_ref.shape)


def decode_greedy_argmax(logits):
    """Greedy-sampling epilogue over the logits tile [B, V] -> int32
    [B] (gated with the megakernel): on TPU/interpret the argmax
    reduces on-tile in a Pallas kernel (first-occurrence tie-breaking,
    bit-identical to jnp.argmax); off-TPU it IS jnp.argmax — the
    exact expression the unfused sampler computes. Rows go through in
    blocks of 8 (a ragged last block reads padding whose results are
    dropped on the write)."""
    if not _use_kernel():
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    b, v = logits.shape
    with _trace32():
        out = pl.pallas_call(
            _argmax_epilogue_kernel,
            grid=(pl.cdiv(b, _ARGMAX_ROWS),),
            in_specs=[pl.BlockSpec((_ARGMAX_ROWS, v),
                                   lambda i: (i, 0))],
            out_specs=pl.BlockSpec((_ARGMAX_ROWS, _LANES),
                                   lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((b, _LANES), jnp.int32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=_INTERPRET,
            **KERNELS["argmax_epilogue"],
        )(logits)
    return out[:, 0]


def spec_verify_accept(logits_v, toks, q_len, is_decode):
    """Fused spec-decode acceptance epilogue: logits_v [B, W, V] the
    verify columns' logits (grammar bias masks, when constrained, are
    ALREADY added upstream — they are additive operand data, so
    violating drafts die in this same greedy acceptance), toks [B, W]
    the packed draft tokens, q_len [B] int32, is_decode [B] bool.
    Returns int32 [B] accepted-prefix lengths — the EXACT acceptance
    expressions the unified step's in-trace epilogue computes, with
    the per-column argmax routed through `decode_greedy_argmax` so the
    gate-on path reduces on-tile."""
    b, w, v = logits_v.shape
    preds = decode_greedy_argmax(
        logits_v.reshape(b * w, v)).reshape(b, w)
    match = toks[:, 1:] == preds[:, :-1]
    dcol = jnp.arange(w - 1, dtype=jnp.int32)[None, :]
    valid = dcol < (q_len.astype(jnp.int32) - 1)[:, None]
    accept = jnp.cumprod(
        jnp.where(match & valid, 1, 0), axis=1).sum(axis=1) \
        .astype(jnp.int32)
    return jnp.where(is_decode, accept, 0)


def count_window_page_reads(pos, q_len, *, page_size, window):
    """Host-side (numpy) model of one sliding-window layer's walk, per
    kv-head walk as `count_page_block_reads` counts: (pages walked,
    pages a walk without the window would have read) summed over the
    live rows. A row's walk covers the pages from the one that holds
    its first query's lowest visible key to the one its last query
    writes; without a window it starts at page 0."""
    pos = np.asarray(pos, np.int64)
    q_len = np.asarray(q_len, np.int64)
    live = q_len > 0
    last = (pos + np.maximum(q_len, 1) - 1) // page_size
    first = np.maximum(pos - (window - 1), 0) // page_size
    return (int(np.where(live, last - first + 1, 0).sum()),
            int(np.where(live, last + 1, 0).sum()))


def count_walk_grid_steps(pos, q_len, *, lq, page_size, max_pages):
    """Host-side (numpy) count of one full-attention layer's walk over
    one step: (grid steps its dynamically bounded grid has, grid steps
    the grid the step's shape alone would give has). The bounds are
    `walk_grid_bounds`, the expression the compiled step evaluates on
    the same `pos` and `q_len`."""
    pos = np.asarray(pos, np.int64)
    q_len = np.asarray(q_len, np.int64)
    n_qblk, n_pages = walk_grid_bounds(
        pos, q_len, lq=lq, page_size=page_size, max_pages=max_pages,
        xp=np)
    rows = int(q_len.shape[0])
    return (rows * int(n_qblk) * int(n_pages),
            rows * _query_blocks(lq)[1] * int(max_pages))


def count_page_block_reads(page_table, pos, q_len, group_id=None,
                           group_cnt=None, *, page_size, n_kv=1,
                           mp=1, fused=None):
    """Host-side (numpy) model of the kernels' page-block DMA traffic
    for ONE (kv_head, layer) walk — the number the serving metrics and
    the `--prefix-share` bench A/B report, and what tests pin.

    Per live row (q_len > 0) the ungrouped walk streams its pages
    0..floor((pos + q_len - 1)/page_size); the grouped walk streams
    each group's shared span ONCE (per the leader's table) plus each
    member's private tail. Returns
    (flat_reads, grouped_reads, group_sizes) where group_sizes lists
    the member count of every group that actually shares (>= 2 live
    members); without group operands grouped_reads == flat_reads.

    Tensor-parallel serving (ServingEngine(mesh=...)): pass the
    model's `n_kv` and the mesh's `mp` degree and the counts become
    what ONE CHIP issues per layer — each of the mp shards walks only
    its n_kv/mp local heads (the heads are the batch dimension of
    the in-kernel dots; the count stays per head though one block now
    carries a page's local heads together), and each block read moves
    a 1/mp page slice, so per-chip
    reads (and the grouped walk's per-chip reads SAVED) drop by mp.
    The defaults (n_kv=1, mp=1) keep the single-walk numbers every
    pre-mesh pin was written against.

    `fused=` (the megakernel's referee): pass a dict
    {"head_dim": D, "kv_elt": bytes/KV element (4 f32, 2 bf16,
    1 int8/fp8), "scale_elt": bytes/scale element per token-head
    (4 when int8 rowwise scales exist, else 0), "lora_bytes": the
    step's adapter-page bytes for ONE projection's A/B stream (0
    without adapters)} and a fourth return slots in: a dict of
    modeled HBM bytes for this (kv_head, layer) walk under BOTH
    pipelines, {"unfused": ..., "fused": ...}. Shared by both:
    `attn` (the grouped walk's page-block K+V stream, codes+scales)
    and `write` (the new tokens' committed pool bytes). The UNFUSED
    pipeline additionally pays `stage` — the new tokens' f32 K/V
    round-tripping HBM between the projection and the standalone
    scatter dispatch (the megakernel consumes them in VMEM) — and
    gathers the adapter page PER PROJECTION (3x lora_bytes for
    q/k/v) where the fused prologue streams it once. The o-delta
    stays outside the megakernel in both pipelines and is excluded.
    fused < unfused whenever any row is live — the strict drop the
    census asserts."""
    pos = np.asarray(pos, np.int64)
    q_len = np.asarray(q_len, np.int64)
    ps = int(page_size)
    live = q_len > 0
    row_pages = np.where(live, (pos + np.maximum(q_len, 1) - 1) // ps
                         + 1, 0)
    local_heads = max(1, int(n_kv) // max(1, int(mp)))
    flat = int(row_pages.sum()) * local_heads
    if group_id is None or group_cnt is None:
        grouped_total = flat
        sizes = []
    else:
        group_id = np.asarray(group_id, np.int64)
        group_cnt = np.asarray(group_cnt, np.int64)
        grouped = 0
        sizes = []
        for g in np.unique(group_id[live]):
            members = np.nonzero(live & (group_id == g))[0]
            cnt = int(group_cnt[g])
            shared = min(cnt, int(row_pages[members].min())) \
                if members.size else 0
            # the shared span streams once; each member walks its tail
            grouped += shared
            grouped += int((row_pages[members] - shared).sum())
            if members.size >= 2 and shared > 0:
                sizes.append(int(members.size))
        grouped_total = grouped * local_heads
    if fused is None:
        return flat, grouped_total, sizes
    d = int(fused["head_dim"])
    kv_elt = int(fused.get("kv_elt", 4))
    scale_elt = int(fused.get("scale_elt", 0))
    lora_bytes = int(fused.get("lora_bytes", 0))
    # K and V streams both (x2); a block moves page_size tokens of
    # (codes + rowwise scales) for one local head
    attn = grouped_total * ps * (d * kv_elt + scale_elt) * 2
    new_tokens = int(q_len[live].sum())
    write = new_tokens * local_heads * (d * kv_elt + scale_elt) * 2
    stage = new_tokens * local_heads * d * 4 * 2
    walk_bytes = {"unfused": attn + write + stage + 3 * lora_bytes,
                  "fused": attn + write + lora_bytes}
    return flat, grouped_total, sizes, walk_bytes
