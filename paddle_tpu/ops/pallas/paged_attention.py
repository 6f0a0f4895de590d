"""Ragged paged-attention decode kernel (Pallas, TPU).

The serving engine's paged decode path used to materialize each row's
logical KV view with `paged_kv_gather` — a transient
[S, max_pages * page_size, H, D] HBM stream PER LAYER PER STEP that
scales with the pool horizon, not with the tokens actually resident,
and XLA cannot fuse a data-dependent gather into the attention reads
("Operator Fusion in XLA", PAPERS.md). This kernel is the fix from
"Ragged Paged Attention" (PAPERS.md): walk the page table and stream
ONLY the pages a row actually occupies.

Structure — grid (work item, key block):

- A WORK ITEM is one live (batch_row, query block) pair; the items
  ride in as SCALAR-PREFETCH operands (pltpu.PrefetchScalarGridSpec)
  next to `pos` [B], `q_len` [B] and the flat `page_table`, the live
  ones first, and their count is the first axis' dynamic bound
  (`_work_items`). A query block is as wide as one kv head's matmul
  wants rows (`_query_blocks`: a whole chunk of 128 where every head
  has its own kv head, 32 or 16 queries where six or nine share one),
  so a chunk's context is read once, not once for every few queries.
- A KEY BLOCK is `K_BLOCK` keys: whole pages, each with ALL its kv
  heads, as the pool [P, ps, H_kv, D] holds them. The pool stays in
  HBM; a grid step copies its block's pages into VMEM through the page
  table, one DMA a page, double-buffered under the arithmetic of the
  step before, and only the pages the row really occupies
  (`_walk_paged`, which `mla.py`'s walk shares): HBM traffic is
  O(pages actually used) per row.
- Flash-style online softmax across key blocks: running (m, l, acc)
  scratch in VMEM, exactly the flash_attention.py recurrence with
  K_BLOCK-wide key blocks, the kv heads as the batch dimension of both
  dots (`_attend_block`). The partial last block is handled by masking
  key positions (position > pos[b] + i -> -inf), which also covers
  trash-page rows: a retired/free slot's page-table row points at the
  reserved page 0 and every position past `pos` contributes -inf.
- GQA without materialization: queries are grouped
  [B, n_qblk, H_kv, qblk * rep, D] so kv head g serves its
  `rep = H // H_kv` query heads from ONE streamed copy of K/V — no
  `repeat_interleave` of the cache.
- A query block with few live queries (a decoding row's one) runs the
  same body over its first `_NARROW_ROWS` rows alone, chosen in the
  kernel from q_len (`_by_width`): a decoding row does not pay a
  chunk's matmuls.
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

- phase 1 walks each sharing group's shared span via the LEADER's
  page table (grid ((q_block, group), key block of the span)),
  bringing every shared page in from HBM ONCE PER GROUP while updating
  the online-softmax partials (m, l, acc) of every MEMBER row in VMEM;
  a member's partials leave for HBM behind the span's last block, and
  the rows of no sharing group are never touched;
- phase 2 is exactly the per-row walk above, except that a row of a
  sharing group STARTS from its phase-1 partials and from the key
  block that holds the first key past the span (the keys below it are
  masked by position) — private tail pages stream once per row,
  shared pages are never re-read.

A group of 1 (group_cnt 0) degenerates to the ungrouped walk: phase 1
never touches the row and phase 2 starts at page 0 with the virgin
(-inf, 0, 0) partials. Phase 1's grid is as long as the data asks: its
work items are the groups that share, so a step where none does has
ONE grid step, which does nothing (a grid step with nothing to do
still costs a grid step, and an idle sweep was once a third of the
step). Where the span ends on a key block's edge the blocks a row
folds, and their order, are IDENTICAL to the ungrouped kernel's;
where it ends inside one, that block is folded in two parts, which
changes rounding only;
off-TPU the op runs the SAME `ragged_attention_reference` as the
ungrouped op — grouping is a pure HBM-traffic hint, bit-identical by
construction. `count_page_block_reads` is the host-side model of both
walks' DMA behavior (the number the serving bench and metrics
report). The q8 lane (`ragged_paged_attention_grouped_q8`) takes the
rows' scales through the same grouped walk.

FP8 LANE: pools may hold float8_e4m3fn — a PURE-CONVERT quantized
cache (no scale pages at all: the e4m3 value IS the number, saturating
round-to-nearest on write). Every kernel and reference detects the
pool dtype and upconverts to f32 in VMEM before the dot — half the
fp16/bf16 HBM bytes (a quarter of f32) with zero extra operands, the
cheapest possible quantized lane. Unlike int8's rowwise codes+scales
there is nothing to keep paired, so COW/swap/spill move fp8 pages
exactly like fp pages.

RAGGED GENERALIZATION (`ragged_paged_attention`): the same walk, but
every row carries its own query length, `q_len` [B] riding next to
`page_table`/`pos` as a scalar-prefetch operand. Row b's query
token i sits at global position pos[b] + i and attends keys
j <= pos[b] + i (the causal window of the chunk being written), so ONE
invocation serves a mixed batch: decode rows at q_len == 1 next to
mid-prefill rows at q_len == chunk — the one-kernel/step target of
Ragged Paged Attention (PAPERS.md), with the per-row tail causally
masked in the fused online-softmax loop (the low-precision-friendly
primitive style of Tensor Processing Primitives, PAPERS.md). Query
blocks past q_len[b] are no work items and pages past the row's live
prefix ceil((pos[b] + q_len[b]) / page_size) are never copied, so both
HBM traffic and MXU work scale with the tokens actually packed, not
with the padded step shape. The GRID is as long as the rows ask too:
both axes are dynamic bounds (`walk_grid_bounds`: the live work
items, the key blocks of the longest live context), decided inside
the one compiled step from `pos` and `q_len`, for every walk: plain,
grouped (both phases), masked, int8, fp8 and windowed. Outputs at
query positions >= q_len[b] are zero (the engine discards them).

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
written by generation.py's quantized paged scatter). A key block's
codes and scales meet in VMEM and the dequant (convert x rowwise
scale) is FUSED into the online-softmax loop — no HBM-side
dequantized copy is ever materialized, which is the whole point
(the scales, 1/32 of the codes' bytes, come as each row's gathered
view: Mosaic cannot cut a [page_size, H_kv] f32 page out of HBM):
decode is HBM-bandwidth-bound, and halving the KV byte stream halves
the dominant HBM traffic (the fused low-precision-primitive idiom of
Tensor Processing Primitives, PAPERS.md). Dead pages and dead rows
are skipped as on every lane. Off-TPU the op runs
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
           "count_walk_grid_steps", "count_walk_pairs", "walk_grid_bounds",
           "ragged_paged_attention_split",
           "ragged_attention_reference_split",
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
    ("split_walk", "_ragged_kernel"),
    ("sink_walk", "_ragged_kernel"),
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


def _attend_block(q, k, v, ks, vs, live, mask, m_ref, l_ref, acc_ref, *,
                  scale, fp8, heads=None):
    """Fold ONE key block into the online-softmax partials of one row's
    query block, for every kv head at once. q [H_kv, R, D] with R query
    rows per kv head; k/v [kb, H_kv, D], the block's pages exactly as
    they sit in the pool, one behind another (a page carries ALL kv
    heads — see `_ragged_attention_local`); ks/vs the int8 lane's
    rowwise scales [kb, H_kv] f32 or None; live bool [R, kb]; mask
    additive f32 [H_kv, R, kb] or None. m/l [H_kv, R, 128] and acc
    [H_kv, R, D] are refs updated in place. The head axis is a batch
    dimension of both dots, so per head this is the flash_attention.py
    recurrence with kb-wide key blocks.

    With `heads` (pools of SPLIT widths, `_ragged_attention_local`) k
    is [kb, heads * Dk] and v [kb, heads * Dv], the heads side by side
    on the lanes, and acc is [H_kv, R, Dv]: each head's keys and values
    are its own run of lanes, and the two dots run a head at a time."""
    if heads is not None:
        dk, dv = k.shape[1] // heads, v.shape[1] // heads
        prec = _prec(q.dtype)
        for g in range(heads):
            s = jax.lax.dot_general(
                q[g], k[:, g * dk:(g + 1) * dk], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=prec) * jnp.float32(scale)     # [R, kb]
            s = jnp.where(live, s, jnp.float32(_NEG_INF))
            v_g = v[:, g * dv:(g + 1) * dv]
            _fold(s, lambda p, v_g=v_g: jax.lax.dot_general(
                p.astype(v_g.dtype), v_g, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=prec),
                m_ref.at[g], l_ref.at[g], acc_ref.at[g])
        return
    if ks is not None:
        # fused in-VMEM dequant: int8 codes x rowwise scale — the
        # dequantized block never round-trips through HBM
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
    k = jnp.swapaxes(k, 0, 1)                      # [H_kv, kb, D]
    v = jnp.swapaxes(v, 0, 1)
    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
        precision=prec) * jnp.float32(scale)       # [H_kv, R, kb]
    s = jnp.where(live[None], s, jnp.float32(_NEG_INF))
    if mask is not None:
        s = s + mask
    _fold(s, lambda p: jax.lax.dot_general(
        p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
        precision=prec), m_ref, l_ref, acc_ref)


def _fold(s, pv, m_ref, l_ref, acc_ref):
    """The online-softmax step over scores s [..., R, kb]: the running
    max, the denominator and the accumulator (m/l [..., R, 128], acc
    [..., R, D] refs) rescaled and `pv(p)`, the weights' product with
    the block's values, added."""
    m_prev = m_ref[..., :1]
    l_prev = l_ref[..., :1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    pexp = jnp.exp(s - m_new)
    l_ref[...] = jnp.broadcast_to(
        alpha * l_prev + jnp.sum(pexp, axis=-1, keepdims=True),
        l_ref.shape)
    acc_ref[...] = acc_ref[...] * alpha + pv(pexp)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)


def _live_block(q0, k0, pos_b, qlen_b, *, n, rep, kb, window=None,
                lo_key=None, hi_key=None):
    """bool [n, kb] for the first n rows of a query block whose first
    query is q0 (rows in (query, head-of-the-group) order): query q0 + i
    (live iff < q_len) attends key position k0 + j iff it is <= pos +
    query index. Masks the partial last block AND trash-page positions.
    With a sliding `window` (its size, the query's own position
    included) the key must also lie above pos + query index - window:
    the partial block at the window's lower edge. `lo_key` / `hi_key`
    keep the keys in [lo_key, hi_key): the two phases of the grouped
    walk split a block at the end of the shared span."""
    shape = (-(-n // rep), rep, kb)
    rows = shape[0] * rep
    qi = q0 + jax.lax.broadcasted_iota(
        jnp.int32, shape, 0).reshape(rows, kb)[:n]
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, (n, kb), 1)
    live = (qi < qlen_b) & (k_pos <= pos_b + qi)
    if window is not None:
        live = live & (k_pos > pos_b + qi - window)
    if lo_key is not None:
        live = live & (k_pos >= lo_key)
    if hi_key is not None:
        live = live & (k_pos < hi_key)
    return live


def _window_blocks(window, qblk, kb):
    """Key blocks one query block of a sliding-window layer can touch:
    its live queries see window - 1 + qblk consecutive positions at
    most, which lie on this many blocks of kb keys whatever their
    alignment. It is the length of the key-block axis of a window
    layer's grid."""
    return (window + qblk + kb - 3) // kb + 1


def _window_first_page(pos_b, t, *, ps, qblk, window):
    """The page that holds the lowest key the first query of block t
    sees: the page a window layer's walk of that block starts from."""
    return jnp.maximum(pos_b + t * qblk - (window - 1), 0) // ps


def _work_items(q_len, qb, nqb):
    """(row of each item, its query block) over the [rows x nqb] query
    blocks of the step, the live ones (those that hold a query below the
    row's q_len: `_live_query_blocks` of them) first, in row order."""
    t = jnp.arange(nqb, dtype=jnp.int32)[None, :]
    live = (t * qb < q_len[:, None]).reshape(-1)
    order = jnp.argsort(jnp.logical_not(live), stable=True) \
        .astype(jnp.int32)
    return order // nqb, order % nqb


def _walk_paged(item, ppb, cnt, compute, pt_ref=None, pools=(), bufs=(),
                sem=None):
    """One grid step (work item i, key block k) of a kernel that reads
    the pools' pages in place. `item(j)` says of work item j (where its
    row's page table starts in `pt_ref`, whether it has anything to do,
    its first page, its last page); its key blocks are the blocks of
    `ppb` pages from the one that holds its first page to the one that
    holds its last, and grid step k is the k-th of them.
    Where that block exists, waits for its pages in `bufs[n][slot]`
    ([2, pages a block, ...a page] VMEM, one DMA a page and pool from
    `pools[n]` in HBM, and only the pages between the item's first and
    last: the rest of the buffer keeps what it held; an item whose first
    page is None asks for its blocks whole, from the row's first, and
    gets every page of a block by a copy written out, not looped) and runs
    `compute(slot, block)`. The block of the NEXT step that computes
    (this item's next block, or the next item's first: items with
    something to do come first) is set off into the other slot before
    the wait, so its copy runs under this step's arithmetic; `cnt`
    (SMEM) counts the steps that computed and gives the slot. Steps past
    an item's last block move nothing. Without `pools` (a caller whose
    keys arrive by BlockSpec) it only says which steps compute."""
    i, k = pl.program_id(0), pl.program_id(1)
    n_items = pl.num_programs(0)

    def span(j):
        base, live, lo, hi = item(j)
        first = 0 if lo is None else jnp.minimum(lo, hi) // ppb
        return base, live, lo, hi, first, hi // ppb

    def move(base, lo, hi, blk, slot, wait):
        if not pools:
            return
        p0 = blk * ppb

        def page(j, carry):
            for n, (pool, buf) in enumerate(zip(pools, bufs)):
                c = pltpu.make_async_copy(
                    pool.at[pt_ref[base + p0 + j]], buf.at[slot, j],
                    sem.at[n, slot])
                if wait:
                    c.wait()
                else:
                    c.start()
            return carry

        if lo is None:
            for j in range(ppb):
                page(j, 0)
        else:
            jax.lax.fori_loop(jnp.maximum(lo - p0, 0),
                              jnp.minimum(hi - p0 + 1, ppb), page, 0)

    @pl.when((i == 0) & (k == 0))
    def _reset():
        cnt[0] = 0

    base, live, lo, hi, first, last = span(i)
    blk = first + k

    @pl.when(live & (blk <= last))
    def _step():
        slot = cnt[0] % 2

        @pl.when(cnt[0] == 0)
        def _first():
            move(base, lo, hi, blk, slot, False)

        nbase, nlive, nlo, nhi, nfirst, _ = span(
            jnp.minimum(i + 1, n_items - 1))
        same = blk < last

        @pl.when(same | ((i + 1 < n_items) & nlive))
        def _ahead():
            move(jnp.where(same, base, nbase),
                 lo if lo is None else jnp.where(same, lo, nlo),
                 jnp.where(same, hi, nhi),
                 jnp.where(same, blk + 1, nfirst), 1 - slot, False)

        move(base, lo, hi, blk, slot, True)
        compute(slot, blk)
        cnt[0] = cnt[0] + 1


def _by_width(left, rep, rows, fn):
    """`fn(n)` at the number of a query block's rows that hold its live
    queries: `_NARROW_ROWS` where the `left` (traced) queries the row
    has from the block's first on, `rep` rows each, fit them, else all
    the block's `rows`."""
    if _NARROW_ROWS >= rows:
        fn(rows)
        return
    narrow = left * rep <= _NARROW_ROWS
    pl.when(narrow)(lambda: fn(_NARROW_ROWS))
    pl.when(jnp.logical_not(narrow))(lambda: fn(rows))


def _clear_values(v_buf):
    """At a call's first grid step: zero the V buffer, whose pages past
    an item's last are never brought in. A masked key weighs 0 in the
    softmax, but 0 x what VMEM happened to hold may be NaN; after this
    it holds zeros or pages of the pool. (A masked key's score is
    replaced, not scaled: K needs nothing.)"""
    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _():
        v_buf[...] = jnp.zeros(v_buf.shape, v_buf.dtype)


def _top_rows(ref, n):
    """The first n rows of every kv head of `ref` [H_kv, rows, ...]; the
    ref itself where those are all its rows (Mosaic cuts HBM by whole
    tiles: a block of one row cannot be cut at its one row)."""
    return ref if n == ref.shape[1] else ref.at[:, :n]


def _virgin(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, jnp.float32(_NEG_INF))
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _block_keys(pools, bufs, slot):
    """The key block as `_attend_block` takes it, k and v [kb, H_kv, D]:
    the pages in `bufs[..][slot]` one behind another, or, without
    buffers, the block of the rows' views that `pools` then are."""
    if not bufs:
        return [p[0] for p in pools]
    return [buf[slot].reshape((-1,) + buf.shape[3:]) for buf in bufs]


def _walk_item(j, pre, *, ps, qblk, grouped, window):
    """Work item j of the walk, from its scalar-prefetch operands (the
    kernel's refs, or an index map's): (row, query block, whether it
    holds a live query, the first page its walk reads, the last)."""
    ib, it, pos, qlen, pt = pre[:5]
    b, t = ib[j], it[j]
    max_pages = pt.shape[0] // pos.shape[0]
    last_qi = jnp.minimum((t + 1) * qblk, qlen[b]) - 1
    hi = jnp.clip((pos[b] + last_qi) // ps, 0, max_pages - 1)
    lo = 0
    if grouped:
        gid, gcnt = pre[5:]
        lo = gcnt[gid[b]]
    if window is not None:
        lo = _window_first_page(pos[b], t, ps=ps, qblk=qblk, window=window)
    return b, t, t * qblk < qlen[b], jnp.minimum(lo, hi), hi


def _ragged_kernel(*refs, ps, ppb, qblk, rep, scale, has_mask, has_scale,
                   fp8, grouped, in_place, window=None, heads=None,
                   has_sink=False):
    """The per-row page walk — grid (work item, key block). A work item
    is one LIVE (row, query block) pair (`_work_items`), a key block
    `K_BLOCK` keys of the row's context, brought in from the pools in
    HBM a page a DMA (`_walk_paged`). With `grouped` it is phase 2 of
    the grouped walk: a row whose group shares starts from its phase-1
    partials and from the key block that holds the first key past the
    shared span (the keys below it, whose contribution is already
    folded in, are masked by position), so private tail pages stream
    once per row and shared pages are never re-read. The merge IS the
    online-softmax recurrence continuing where phase 1 stopped. With
    `window` the walk starts at the block that holds the item's lowest
    visible key, so blocks wholly below the window have no grid step at
    all. A query block whose live queries fit `_NARROW_ROWS` rows (a
    decoding row's one query; a verify row's few, where the heads of a
    group are few) runs its matmuls over those rows alone
    (`_by_width`). With `heads` the pools are of split widths (the
    keys' and the values' lanes a head, `_attend_block`); with
    `has_sink` a learned logit a query row (`sink_ref` [H_kv, rows,
    128] f32) joins each row's softmax at the item's end: it takes its
    share of the denominator and adds no value, so a row whose window
    holds no live key leaves as zeros."""
    refs = list(refs)
    n_pre = 7 if grouped else 5
    pre, refs = refs[:n_pre], refs[n_pre:]
    ib_ref, it_ref, pos_ref, qlen_ref, pt_ref = pre[:5]
    q_ref, refs = refs[0], refs[1:]
    pools, refs = refs[:2], refs[2:]
    ks_ref = vs_ref = mask_ref = None
    if has_scale:
        # int8 lane: the key block's rowwise dequant scales [kb, H_kv]
        ks_ref, vs_ref = refs[:2]
        refs = refs[2:]
    if has_mask:
        mask_ref, refs = refs[0], refs[1:]
    if has_sink:
        sink_ref, refs = refs[0], refs[1:]
    if grouped:
        gid_ref, gcnt_ref = pre[5:]
        parts_in, refs = refs[:3], refs[3:]
    o_ref, refs = refs[0], refs[1:]
    bufs, sem = (), None
    if in_place:
        bufs, sem, refs = refs[:2], refs[2], refs[3:]
    cnt, m_ref, l_ref, acc_ref = refs[:4]
    parts = (m_ref, l_ref, acc_ref)
    i, k = pl.program_id(0), pl.program_id(1)
    max_pages = pt_ref.shape[0] // pos_ref.shape[0]
    kb = ppb * ps
    rows = q_ref.shape[3]

    def item(j):
        b, _, live, lo, hi = _walk_item(j, pre, ps=ps, qblk=qblk,
                                        grouped=grouped, window=window)
        return b * max_pages, live, lo, hi

    b, t = ib_ref[i], it_ref[i]
    pos_b, qlen_b = pos_ref[b], qlen_ref[b]
    shared = gcnt_ref[gid_ref[b]] if grouped else None
    by_width = functools.partial(_by_width, qlen_b - t * qblk, rep, rows)

    @pl.when(k == 0)
    def _init():
        def virgin(n):
            _virgin(*(p.at[:, :n] for p in parts))

        if not grouped:
            by_width(virgin)
            return
        psem = refs[4]

        def fetch(n):
            copies = [pltpu.make_async_copy(_top_rows(src.at[t, b], n),
                                            _top_rows(dst, n), psem.at[0])
                      for src, dst in zip(parts_in, parts)]
            for c in copies:
                c.start()
            for c in copies:
                c.wait()

        pl.when(shared > 0)(lambda: by_width(fetch))
        pl.when(shared == 0)(lambda: by_width(virgin))

    def compute(slot, blk):
        def attend(n):
            _attend_block(
                q_ref[0, 0, :, :n], *_block_keys(pools, bufs, slot),
                ks_ref[0] if has_scale else None,
                vs_ref[0] if has_scale else None,
                _live_block(t * qblk, blk * kb, pos_b, qlen_b, n=n,
                            rep=rep, kb=kb, window=window,
                            lo_key=shared * ps if grouped else None),
                mask_ref[0, 0, 0, :, :n] if has_mask else None,
                *(p.at[:, :n] for p in parts), scale=scale, fp8=fp8,
                heads=heads)

        by_width(attend)

    if in_place:
        _clear_values(bufs[1])
        _walk_paged(item, ppb, cnt, compute, pt_ref, pools, bufs, sem)
    else:
        _walk_paged(item, ppb, cnt, compute)

    @pl.when(k == pl.num_programs(1) - 1)
    def _finalize():
        def store(n):
            if has_sink:
                _fold(sink_ref[:, :n, :1], lambda p: 0.0,
                      *(p.at[:, :n] for p in parts))
            l = jnp.maximum(l_ref[:, :n, :1], jnp.float32(1e-30))
            o_ref[0, 0, :, :n] = (acc_ref[:, :n] / l).astype(o_ref.dtype)

        by_width(store)


def _grouped_phase1_kernel(gi_ref, ti_ref, pos_ref, qlen_ref, pt_ref,
                           gid_ref, gld_ref, gcnt_ref, q_ref, *refs, b,
                           ps, ppb, qblk, rep, scale, has_scale, fp8,
                           in_place):
    """Phase 1 of the grouped walk — grid ((q_block, group), key block
    of the shared span): each grid step brings in ONE key block of ONE
    group's shared pages (via the group leader's page table, the pages
    of the span only) and folds it into the online-softmax partials of
    every MEMBER row, which wait in VMEM through the item's sweep and
    leave for HBM (a DMA a member) behind its last block. A member's
    queries arrive the same way at its first. Rows of no sharing group
    are never touched, here or in HBM: phase 2 starts them virgin. The
    work items are the groups that share, so a step where none does has
    ONE grid step, which does nothing."""
    pools, refs = refs[:2], refs[2:]
    ks_ref = vs_ref = None
    if has_scale:
        ks_ref, vs_ref = refs[:2]
        refs = refs[2:]
    parts_out, refs = refs[:3], refs[3:]
    bufs, sem = (), None
    if in_place:
        bufs, sem, refs = refs[:2], refs[2], refs[3:]
    cnt, q_buf, m_buf, l_buf, acc_buf, psem = refs
    parts = (m_buf, l_buf, acc_buf)
    i, k = pl.program_id(0), pl.program_id(1)
    max_pages = pt_ref.shape[0] // pos_ref.shape[0]
    kb = ppb * ps
    rows = q_ref.shape[3]

    def item(j):
        span = gcnt_ref[gi_ref[j]]
        return (gld_ref[gi_ref[j]] * max_pages, span > 0, 0,
                jnp.maximum(span - 1, 0))

    grp, t = gi_ref[i], ti_ref[i]
    span = gcnt_ref[grp]

    def members(fn):
        """`fn(row, rows of its block that hold its live queries)` for
        every row of the group with a live query in block t."""
        def row(bi, carry):
            left = qlen_ref[bi] - t * qblk
            pl.when((gid_ref[bi] == grp) & (left > 0))(
                lambda: _by_width(left, rep, rows, lambda n: fn(bi, n)))
            return carry

        jax.lax.fori_loop(0, b, row, 0)

    def moves(bi, n, out):
        if out:
            return [pltpu.make_async_copy(_top_rows(src.at[bi], n),
                                          _top_rows(dst.at[t, bi], n),
                                          psem.at[0])
                    for src, dst in zip(parts, parts_out)]
        return [pltpu.make_async_copy(_top_rows(q_ref.at[bi, t], n),
                                      _top_rows(q_buf.at[bi], n),
                                      psem.at[0])]

    def start(bi, n, out):
        for c in moves(bi, n, out):
            c.start()

    def wait(bi, n, out):
        for c in moves(bi, n, out):
            c.wait()

    @pl.when((k == 0) & (span > 0))
    def _arrive():
        members(lambda bi, n: start(bi, n, False))
        members(lambda bi, n: _virgin(*(p.at[bi, :, :n] for p in parts)))
        members(lambda bi, n: wait(bi, n, False))

    def compute(slot, blk):
        def attend(bi, n):
            _attend_block(
                q_buf[bi, :, :n], *_block_keys(pools, bufs, slot),
                ks_ref[0] if has_scale else None,
                vs_ref[0] if has_scale else None,
                _live_block(t * qblk, blk * kb, pos_ref[bi], qlen_ref[bi],
                            n=n, rep=rep, kb=kb, hi_key=span * ps),
                None, *(p.at[bi, :, :n] for p in parts), scale=scale,
                fp8=fp8)

        members(attend)

    if in_place:
        _clear_values(bufs[1])
        _walk_paged(item, ppb, cnt, compute, pt_ref, pools, bufs, sem)
    else:
        _walk_paged(item, ppb, cnt, compute)

    @pl.when((span > 0) & (k == (span - 1) // ppb))
    def _leave():
        members(lambda bi, n: start(bi, n, True))
        members(lambda bi, n: wait(bi, n, True))


# keys a grid step of the walk takes (whole pages of them), the rows of
# one kv head's matmuls a query block may have, and the rows the narrow
# form of a block computes over (one bf16 tile of sublanes)
K_BLOCK = 256
_Q_ROWS = 256
_NARROW_ROWS = 16
_VMEM_LIMIT = 96 * 1024 * 1024


def _query_blocks(lq, rep=1):
    """(query block size, query blocks) a walk tiles a row's lq query
    positions into, where `rep` query heads share a kv head: the largest
    power of two of queries whose rows in one kv head's matmuls (a query
    and head each) stay within `_Q_ROWS`. A chunk of 128 is ONE block
    where every head has its own kv head, 32 or 16 queries where six or
    nine share one."""
    cap = max(1, _Q_ROWS // rep)
    qblk = min(lq, 1 << (cap.bit_length() - 1))
    return qblk, -(-lq // qblk)


def _key_blocks(page_size, max_pages):
    """(pages a key block, key blocks a row of max_pages pages)."""
    ppb = max(1, min(K_BLOCK // page_size, max_pages))
    return ppb, -(-max_pages // ppb)


def _live_query_blocks(q_len, qblk, nqb, xp=jnp):
    """The step's live (row, query block) pairs, at least 1: the work
    items of a walk's grid."""
    return xp.maximum(xp.sum(
        xp.minimum((q_len + qblk - 1) // qblk, nqb)), 1)


def _key_block_bound(pos, q_len, kb, n_blocks, xp=jnp):
    """The blocks of kb keys of the longest live context, from 1 to
    n_blocks: a block at or past it lies beyond every row's causal
    horizon."""
    return xp.clip(xp.max(xp.where(
        q_len > 0, (pos + q_len - 1) // kb + 1, 1)), 1, n_blocks)


def walk_grid_bounds(pos, q_len, *, lq, rep, page_size, max_pages,
                     xp=jnp):
    """The two dynamic bounds of a full-attention walk's grid, from
    what the step's rows ask: (its live work items, the key blocks of
    the longest live context), each at least 1. A (row, query block)
    pair that is no work item holds dead queries only, and a key block
    at or past the second bound lies beyond every row's causal horizon,
    so the grid steps the bounds remove would have done nothing. One
    expression for the traced wrapper (`xp=jnp`, int32 [B] operands of
    the compiled step) and for the host's count of the same step
    (`xp=np`, `count_walk_grid_steps`): they cannot drift."""
    qblk, nqb = _query_blocks(lq, rep)
    ppb, n_blocks = _key_blocks(page_size, max_pages)
    return (_live_query_blocks(q_len, qblk, nqb, xp),
            _key_block_bound(pos, q_len, ppb * page_size, n_blocks, xp))


def _zero_dead_queries(out, q_len):
    """out [B, lq, H, D] with the queries at or past q_len[b] zeroed:
    the grid never writes the q-blocks of no work item."""
    alive = jnp.arange(out.shape[1], dtype=jnp.int32)[None, :] \
        < q_len[:, None]
    return jnp.where(alive[:, :, None, None], out,
                     jnp.zeros((), out.dtype))


def _ragged_attention_kernel(q, k_pool, v_pool, page_table, pos, q_len,
                             mask, k_scale=None, v_scale=None,
                             group=None, window=None, split_heads=None,
                             sink=None):
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
    if sink is not None:
        ops["sink"], specs["sink"] = sink, rows
    # what a trace of the walk reads beside its operands (tests set
    # them): a trace made under other values must not be reused
    extra = {"window": window,
             "traced_for": (K_BLOCK, _Q_ROWS, _INTERPRET)}
    if split_heads is not None:
        extra["heads"] = split_heads
    return _per_device(
        lambda o: _ragged_attention_local(**{"mask": None, **o}, **extra),
        (specs,), heads)(ops)


def _row_view(pool, page_table, n_keys):
    """[P, ps, ...] pages -> each row's in position order
    [B, n_keys, ...], zeros behind the table's last page: how the walk
    takes what Mosaic cannot cut a page of out of HBM (it cuts HBM by
    whole tiles of 128 lanes), a block of keys a grid step."""
    g = jnp.take(pool, page_table, axis=0)
    g = g.reshape((g.shape[0], -1) + pool.shape[2:])
    return jnp.pad(g, ((0, 0), (0, n_keys - g.shape[1]))
                   + ((0, 0),) * (pool.ndim - 2))


def _walk_scratch(pools, ppb):
    """The scratch every kernel over `_walk_paged` leads with: for
    `pools` read in place a two-slot buffer of ppb pages each and a DMA
    semaphore a pool and slot; the count of the steps that computed."""
    in_place = [pltpu.VMEM((2, ppb) + p.shape[1:], p.dtype) for p in pools]
    if pools:
        in_place.append(pltpu.SemaphoreType.DMA((len(pools), 2)))
    return in_place + [pltpu.SMEM((1,), jnp.int32)]


@functools.partial(jax.jit, static_argnames=("window", "traced_for",
                                             "heads"))
def _ragged_attention_local(q, k_pool, v_pool, page_table, pos, q_len,
                            mask, k_scale=None, v_scale=None,
                            group=None, window=None, traced_for=None,
                            heads=None, sink=None):
    """q [B, lq, H, D]; pools [P, ps, H_kv, D]; page_table
    [B, max_pages] int32; pos/q_len [B] int32; mask None | additive f32
    [B, H, lq, lmax]. lq is padded up to a multiple of the query block
    so the grid tiles evenly; padded queries are dead by q_len.
    k_scale/v_scale (int8 lane): rowwise dequant scale pages
    [P, ps, H_kv] f32 brought in next to the int8 code pages — dequant
    fuses into the in-VMEM compute.

    POOL LAYOUT AND BLOCKS. The pool keeps its [P, ps, H_kv, D] layout
    (one decision for the fp, int8, fp8 and grouped lanes, the scatter
    write, COW/swap/PKVF frames and the tensor-parallel head shard) and
    stays in HBM: a grid step's key block is `K_BLOCK` keys, whole
    pages each with ALL its kv heads, copied into VMEM a page a DMA
    through the page table and double-buffered under the step before
    (`_walk_paged`). The kv heads are the batch dimension of the
    in-kernel dots, over the block relaid [H_kv, keys, D] in VMEM.
    Queries are regrouped outside the kernel to
    [B, n_qblk, H_kv, qblk * rep, D]: a query block (`_query_blocks`,
    from lq and the heads a kv head serves) is as wide as a kv head's
    matmul wants rows, so a chunk's context is read once where it used
    to be read once for every 8 queries. What Mosaic cannot cut a page
    out of (it cuts HBM by whole tiles of 128 lanes: heads narrower
    than that, the int8 lane's [ps, H_kv] scale pages) reaches the
    same kernel as each row's gathered view, a block of `K_BLOCK` keys
    a grid step through a BlockSpec (`_row_view`).

    group = (group_id, group_leader, group_cnt) selects the grouped
    two-phase walk (see the module doc). Operand contract
    (engine-enforced, host side): rows of one group carry IDENTICAL
    page-table entries for indices [0, group_cnt) — the physically
    shared prefix — and every member's pos already covers the span
    (shared pages hold committed KV). group_leader[g] names a member
    row whose table phase 1 walks; singleton rows ride with group_cnt
    0 and take phase 2 only, which is exactly the ungrouped walk. On a
    step where every group_cnt is 0 phase 1 shrinks to one grid step
    (`_grouped_phase1`).

    window (a sliding-window layer; None for full attention): query i
    of row b sees keys pos + i - window < j <= pos + i. The grid's
    key-block axis shrinks to `_window_blocks` steps, counted from the
    block that holds the query block's lowest visible key
    (`_window_first_page`), so a page wholly below the window is
    neither fetched nor computed, and the partial block at the edge is
    masked in `_live_block`. The page table may be a ring over fewer
    physical pages than it has columns (the serving engine's table for
    such layers is): only the pages of the window are ever read.
    Neither groups nor a user mask combine with it.

    heads (SPLIT widths; None: the pools' own [P, ps, H_kv, D]): the
    pools are [P, ps, heads * Dk] and [P, ps, heads * Dv], a token's kv
    heads side by side on the lanes, keys Dk wide (q's D) and values Dv
    wide, and the result is [B, lq, H, Dv]. A key width that is no
    multiple of 128 lanes (192) would pad every head of a
    [P, ps, H_kv, Dk] pool to whole tiles in HBM, and a few heads to a
    whole tile of sublanes besides; side by side they pad nothing
    (4 x 192 = 768 lanes), and a page is still one DMA. The kernel is
    the same walk, its dots taken a head at a time (`_attend_block`),
    under the trace name `split_walk`. sink (f32 [H], with `heads`
    only): a learned logit a query head that joins the head's softmax
    denominator and adds no value (a window layer's sink), under the
    trace name `sink_walk`.

    The grid is as long as the step's rows ask, never as long as the
    step's SHAPE allows (a grid step with nothing to do still costs a
    grid step; PERF.md section 6, PR 28 and 30): its first axis runs
    over the step's LIVE (row, query block) pairs, which ride in as
    scalar-prefetch operands with the live ones first (`_work_items`),
    its second over the key blocks of the longest live context (of the
    window, statically, in a window layer). Both are DYNAMIC bounds
    (`walk_grid_bounds`), decided inside the one compiled step from
    `pos` and `q_len`. Groups, a user mask and the int8 / fp8 lanes
    take the same bounds. The query blocks of no work item are never
    written, so the dead queries' outputs are zeroed after the call.

    ROWS WITH FEW LIVE QUERIES do not pay a chunk's rows: a query block
    whose live queries fit `_NARROW_ROWS` rows of a kv head's matmul (a
    decoding row's one query always; the 2-16 of a verify row where
    every head has its own kv head, fewer where heads share one) runs
    the same kernel body over those rows alone, chosen in the kernel
    from q_len (`_by_width`); its block of the output holds nothing
    else that lives.

    A program of its own inside the step's (`jax.jit`): a model's
    layers call it with the same shapes, so JAX traces and lowers the
    walk once a step program and not once a layer, which is most of
    what a serving cell's set-up waits for (PERF.md section 6)."""
    del traced_for
    if window is not None and (group is not None or mask is not None):
        raise NotImplementedError(
            "the page walk of a sliding-window layer takes neither "
            "prefix-sharing groups nor a user attention mask")
    if (heads is None) != (k_pool.ndim == 4) or (
            heads is not None and (group is not None or mask is not None
                                   or k_scale is not None)) or (
            sink is not None and heads is None):
        raise NotImplementedError(
            "pools of split widths take neither groups, a user mask nor "
            "an int8 lane, and a sink rides with split widths only")
    b, lq, h, d = q.shape
    if heads is None:
        _, ps, hkv, _ = k_pool.shape
        dv = d
    else:
        ps, hkv, dv = k_pool.shape[1], heads, v_pool.shape[2] // heads
    mp = page_table.shape[1]
    rep = h // hkv
    scale = 1.0 / math.sqrt(d)
    qblk, nqb = _query_blocks(lq, rep)
    ppb, n_blocks = _key_blocks(ps, mp)
    kb = ppb * ps
    lq_pad = nqb * qblk
    # a query block's rows, in whole tiles of sublanes (Mosaic cuts HBM
    # by whole tiles, and phase 1 brings a member's queries in by DMA):
    # more than qblk * rep only where lq is 1 or odd
    rows = -(-qblk * rep // _NARROW_ROWS) * _NARROW_ROWS
    pad_rows = ((0, 0),) * 3 + ((0, rows - qblk * rep),)
    if lq_pad != lq:
        padq = jnp.zeros((b, lq_pad - lq, h, d), q.dtype)
        q = jnp.concatenate([q, padq], axis=1)
    q5 = jnp.pad(q.reshape(b, nqb, qblk, hkv, rep, d)
                 .transpose(0, 1, 3, 2, 4, 5)
                 .reshape(b, nqb, hkv, qblk * rep, d), pad_rows + ((0, 0),))
    has_scale = k_scale is not None
    grouped = group is not None
    fp8 = _is_fp8(k_pool.dtype)
    # the pools are read in place where Mosaic can cut a page out of
    # them: it cuts HBM by whole tiles of 128 lanes, so heads narrower
    # than that, and the int8 lane's [ps, H_kv] f32 scale pages (1/32
    # of the bytes the code pages are), come as each row's gathered
    # view instead (`_row_view`), a block of kb keys a grid step
    in_place = d % _LANES == 0 if heads is None else \
        k_pool.shape[2] % _LANES == 0 == v_pool.shape[2] % _LANES
    if not in_place:
        # a row's view holds its group's shared pages anyway: phase 1
        # has nothing to save (and its queries could not be cut either)
        group, grouped = None, False
    pools = [k_pool, v_pool]
    views = ([] if in_place else pools) + (
        [k_scale, v_scale] if has_scale else [])
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    item = functools.partial(_walk_item, ps=ps, qblk=qblk, grouped=grouped,
                             window=window)

    def q_idx(i, k, ib, it, *_):
        return (ib[i], it[i], 0, 0, 0)

    def block(i, k, *pre):
        # the key block grid step (i, k) stands for, held at the item's
        # last where the step has nothing to do: an unchanged block
        # index, so nothing is fetched again
        row, t, _, lo, hi = item(i, pre)
        return row, t, jnp.minimum(lo // ppb + k, hi // ppb)

    def view_spec(view):
        def idx(*a):
            row, _, blk = block(*a)
            return (row, blk) + (0,) * (view.ndim - 2)
        return pl.BlockSpec((1, kb) + view.shape[2:], idx)

    views = [_row_view(v, page_table, n_blocks * kb) for v in views]
    q_spec = pl.BlockSpec((1, 1, hkv, rows, d), q_idx)
    in_specs = [q_spec] + ([hbm, hbm] if in_place else []) \
        + [view_spec(v) for v in views]
    ops = [q5] + (pools if in_place else []) + views
    if mask is not None:
        # [B, H, lq, lmax] -> [B, n_qblk, key blocks, H_kv, rows, kb]:
        # one block per (row, q_block, key block), its minor dims whole
        # and its rows in the kernel's (qblk, rep) score order
        mask = jnp.pad(mask, ((0, 0), (0, 0), (0, lq_pad - lq),
                              (0, n_blocks * kb - mp * ps)))
        m7 = mask.reshape(b, hkv, rep, nqb, qblk, n_blocks, kb)
        ops.append(jnp.pad(
            m7.transpose(0, 3, 5, 1, 4, 2, 6)
            .reshape(b, nqb, n_blocks, hkv, qblk * rep, kb),
            ((0, 0),) + pad_rows + ((0, 0),)))
        in_specs.append(pl.BlockSpec(
            (1, 1, 1, hkv, rows, kb), lambda *a: block(*a) + (0, 0, 0)))
    if sink is not None:
        # row r of kv head g's block is query head g * rep + r % rep
        ops.append(jnp.broadcast_to(jnp.pad(
            jnp.tile(sink.astype(jnp.float32).reshape(hkv, 1, rep),
                     (1, qblk, 1)).reshape(hkv, qblk * rep),
            ((0, 0), (0, rows - qblk * rep)))[:, :, None],
            (hkv, rows, _LANES)))
        in_specs.append(pl.BlockSpec((hkv, rows, _LANES),
                                     lambda *a: (0, 0, 0)))
    part_shapes = [pltpu.VMEM((hkv, rows, w), jnp.float32)
                   for w in (_LANES, _LANES, dv)]
    out_spec = q_spec if heads is None else pl.BlockSpec(
        (1, 1, hkv, rows, dv), q_idx)
    with _trace32():
        ib, it = _work_items(q_len, qblk, nqb)
        n_items, n_kblk = walk_grid_bounds(
            pos, q_len, lq=lq, rep=rep, page_size=ps, max_pages=mp)
        if window is not None:
            n_kblk = min(n_blocks, _window_blocks(window, qblk, kb))
        prefetch = (ib, it, pos, q_len, page_table.reshape(-1))
        scratch = _walk_scratch(pools if in_place else [], ppb) \
            + part_shapes
        if grouped:
            gid, _, gcn = group
            prefetch += (gid, gcn)
            ops.extend(_grouped_phase1(
                q5, pools if in_place else [], views, page_table, pos,
                q_len, group, ps=ps, qblk=qblk, rep=rep, scale=scale,
                has_scale=has_scale, fp8=fp8))
            in_specs.extend([hbm] * 3)
            scratch.append(pltpu.SemaphoreType.DMA((1,)))
        kernel = functools.partial(
            _ragged_kernel, ps=ps, ppb=ppb, qblk=qblk, rep=rep,
            scale=scale, has_mask=mask is not None, has_scale=has_scale,
            fp8=fp8, grouped=grouped, in_place=in_place,
            **({} if window is None else {"window": window}),
            heads=heads, has_sink=sink is not None)
        out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(prefetch),
                grid=(n_items, n_kblk),
                in_specs=in_specs,
                out_specs=out_spec,
                scratch_shapes=scratch),
            out_shape=jax.ShapeDtypeStruct(q5.shape[:4] + (dv,), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=_INTERPRET,
            **KERNELS["ragged_walk" if heads is None else
                      "sink_walk" if sink is not None else "split_walk"],
        )(*prefetch, *ops)
    out = out[:, :, :, :qblk * rep].reshape(b, nqb, hkv, qblk, rep, dv) \
        .transpose(0, 1, 3, 2, 4, 5).reshape(b, lq_pad, h, dv)[:, :lq]
    return _zero_dead_queries(out, q_len)


def _grouped_phase1(q5, pools, views, page_table, pos, q_len, group, *,
                    ps, qblk, rep, scale, has_scale, fp8):
    """Run phase 1 of the grouped walk over q5, the `pools` read in
    place and the rows' `views` of what is not (the operands phase 2
    takes too) and return the per-row partials (m, l, acc), each
    [nqb, B, H_kv, rows, 128 | D] f32, in HBM: written for the rows of a
    group that shares and nowhere else (phase 2 reads no other).

    Both axes of the grid are dynamic bounds. The work items are the
    (q-block, group) pairs of the groups that share, over the q-blocks
    of the row with most live queries; the key-block axis is as long as
    the longest shared span. On a step where no group shares that is
    ONE grid step, which does nothing: no query, page or partial moves
    (PERF.md §6: an idle sweep was a third of the serving step's device
    time once)."""
    b, nqb, hkv, rows, d = q5.shape
    mp = page_table.shape[1]
    ppb, n_blocks = _key_blocks(ps, mp)
    gid, gld, gcn = group
    shares = gcn > 0
    # the groups that share first, in order, as `_work_items` puts the
    # live query blocks first
    order = jnp.argsort(jnp.logical_not(shares), stable=True) \
        .astype(jnp.int32)
    n_groups = jnp.maximum(jnp.sum(shares, dtype=jnp.int32), 1)
    n_qblk = jnp.clip(jnp.max((q_len + qblk - 1) // qblk), 1, nqb)
    slot = jnp.arange(nqb * b, dtype=jnp.int32)
    prefetch = (order[slot % n_groups], slot // n_groups, pos, q_len,
                page_table.reshape(-1), gid, gld, gcn)
    n_kblk = jnp.clip(jnp.max((gcn + ppb - 1) // ppb), 1, n_blocks)
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    widths = (_LANES, _LANES, d)

    def view_spec(view):
        # a group's block of a view: its leader's, held at the span's
        # last block
        def idx(i, k, gi, ti, posr, qlr, pt, gid, gld, gcn):
            last = jnp.maximum(gcn[gi[i]] - 1, 0) // ppb
            return (gld[gi[i]], jnp.minimum(k, last)) \
                + (0,) * (view.ndim - 2)
        return pl.BlockSpec((1, ppb * ps) + view.shape[2:], idx)

    return pl.pallas_call(
        functools.partial(
            _grouped_phase1_kernel, b=b, ps=ps, ppb=ppb, qblk=qblk,
            rep=rep, scale=scale, has_scale=has_scale, fp8=fp8,
            in_place=bool(pools)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(n_qblk * n_groups, n_kblk),
            in_specs=[hbm] * (1 + len(pools))
            + [view_spec(v) for v in views],
            out_specs=[hbm] * 3,
            scratch_shapes=_walk_scratch(pools, ppb) + [
                pltpu.VMEM((b, hkv, rows, d), q5.dtype)] + [
                pltpu.VMEM((b, hkv, rows, w), jnp.float32)
                for w in widths] + [pltpu.SemaphoreType.DMA((1,))]),
        out_shape=[jax.ShapeDtypeStruct((nqb, b, hkv, rows, w),
                                        jnp.float32) for w in widths],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_INTERPRET,
        **KERNELS["grouped_phase1"],
    )(*prefetch, q5, *pools, *views)


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


def _gathered_view(pool, page_table, heads, width):
    """A pool [P, ps, ...] gathered by the rows' page tables into their
    dense logical views [B, max_pages * ps, heads, width]."""
    tab = page_table.astype(jnp.int32)
    return jnp.take(pool, tab, axis=0).reshape(
        tab.shape[0], tab.shape[1] * pool.shape[1], heads, width)


def _ragged_live(pos, q_len, lq, lmax, window=None):
    """[B, lq, lmax] bool: the keys query i of row b sees under the
    ragged causal window — j <= pos[b] + i (and, in a sliding-window
    layer, j > pos[b] + i - window), none at i >= q_len[b]."""
    i = jnp.arange(lq, dtype=jnp.int32)[None, :, None]
    j = jnp.arange(lmax, dtype=jnp.int32)[None, None, :]
    live = (i < q_len.astype(jnp.int32)[:, None, None]) & \
        (j <= pos.astype(jnp.int32)[:, None, None] + i)
    if window is not None:
        live = live & (j > pos.astype(jnp.int32)[:, None, None] + i
                       - window)
    return live


def _ragged_mask_attend(q, kf, vf, pos, q_len, mask, window=None):
    """Shared tail of the ragged references: grouped softmax over the
    dense logical K/V views under the ragged causal window — query i of
    row b attends keys j <= pos[b] + i (and, in a sliding-window layer,
    j > pos[b] + i - window), queries at i >= q_len[b] are fully masked
    (their outputs are unspecified)."""
    b, lq, h, _ = q.shape
    lmax = kf.shape[1]
    live = _ragged_live(pos, q_len, lq, lmax, window)
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
    d, hkv = q.shape[3], k_pool.shape[2]
    kf = _gathered_view(k_pool, page_table, hkv, d)
    vf = _gathered_view(v_pool, page_table, hkv, d)
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


def ragged_attention_reference_split(q, k_pool, v_pool, page_table, pos,
                                     q_len, sink=None, *, heads,
                                     window=None):
    """Pure-JAX form of the walk over pools of SPLIT widths
    (`ragged_paged_attention_split`): the rows' pages gathered into
    their dense views [B, lmax, heads, Dk | Dv], scores in float32 under
    the ragged causal window, and a head's `sink` logit, where given,
    one more term of the softmax's denominator. Queries at or past
    q_len are unspecified but finite. The gather and the mask are the
    accepted form's (`ragged_attention_reference`); the softmax is its
    own, since `gqa_attend_reference` has one width for K and V and no
    sink."""
    b, lq, h, dk = q.shape
    dv = v_pool.shape[2] // heads
    rep = h // heads
    kf = _gathered_view(k_pool, page_table, heads, dk)
    vf = _gathered_view(v_pool, page_table, heads, dv)
    live = _ragged_live(pos, q_len, lq, kf.shape[1], window)
    s = jnp.einsum("blgrd,bmgd->bgrlm", q.reshape(b, lq, heads, rep, dk),
                   kf, preferred_element_type=jnp.float32) \
        * jnp.float32(1.0 / math.sqrt(dk))
    s = jnp.where(live[:, None, None], s, jnp.float32(_NEG_INF))
    m = jnp.max(s, axis=-1, keepdims=True)
    if sink is not None:
        sk = sink.astype(jnp.float32).reshape(1, heads, rep, 1, 1)
        m = jnp.maximum(m, sk)
    p = jnp.exp(s - m)
    den = jnp.sum(p, axis=-1, keepdims=True)
    if sink is not None:
        den = den + jnp.exp(sk - m)
    out = jnp.einsum("bgrlm,bmgd->blgrd", (p / den).astype(vf.dtype), vf,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, lq, h, dv).astype(q.dtype)


def ragged_paged_attention_split(q, k_pool, v_pool, page_table, pos, q_len,
                                 sink=None, *, heads, window=None):
    """`ragged_paged_attention` over pools of SPLIT widths (the
    registered op's forward): k/v pools [P, ps, heads * Dk] and
    [P, ps, heads * Dv], a token's `heads` kv heads side by side; q
    [B, lq, H, Dk]; returns [B, lq, H, Dv]. sink (f32 [H] or None): a
    learned logit a query head in the softmax's denominator. On a TPU
    the page walk (`_ragged_attention_local` with `heads`), elsewhere
    `ragged_attention_reference_split`."""
    posv = pos.astype(jnp.int32)
    qlv = q_len.astype(jnp.int32)
    if _use_kernel():
        return _ragged_attention_kernel(
            q, k_pool, v_pool, page_table.astype(jnp.int32), posv, qlv,
            None, window=window, split_heads=heads, sink=sink)
    return ragged_attention_reference_split(
        q, k_pool, v_pool, page_table, posv, qlv, sink, heads=heads,
        window=window)


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


def count_walk_pairs(pos, q_len, window=None):
    """Host-side (numpy) count over one layer's walk of a step: ((query,
    key) pairs its live queries score, distinct keys they see, live
    rows). Query i
    of row b stands at position pos + i and sees its own key and those
    below it, the last `window` of them in a sliding-window layer; a
    row's queries share their keys, so what must be read at least once
    is the keys any of them sees."""
    pos = np.asarray(pos, np.int64)
    q_len = np.asarray(q_len, np.int64)
    if window is None:
        pairs = (2 * pos + q_len + 1) * q_len // 2
        keys = np.where(q_len > 0, pos + q_len, 0)
    else:
        # sum over i < q_len of min(pos + 1 + i, window)
        under = np.clip(window - pos, 0, q_len)
        pairs = (2 * pos + under + 1) * under // 2 \
            + (q_len - under) * window
        keys = np.where(q_len > 0, np.minimum(pos + q_len,
                                              window - 1 + q_len), 0)
    return int(pairs.sum()), int(keys.sum()), int((q_len > 0).sum())


def count_walk_grid_steps(pos, q_len, *, lq, rep, page_size, max_pages):
    """Host-side (numpy) count of one full-attention layer's walk over
    one step: (grid steps its dynamically bounded grid has, grid steps
    the grid the step's shape alone would give has). A grid step is one
    (row, query block) pair over one key block of `K_BLOCK` keys, the
    query block sized from lq and `rep`, the query heads a kv head
    serves (`_query_blocks`). The bounds are `walk_grid_bounds`, the
    expression the compiled step evaluates on the same `pos` and
    `q_len`."""
    pos = np.asarray(pos, np.int64)
    q_len = np.asarray(q_len, np.int64)
    n_items, n_kblk = walk_grid_bounds(
        pos, q_len, lq=lq, rep=rep, page_size=page_size,
        max_pages=max_pages, xp=np)
    return (int(n_items) * int(n_kblk),
            int(q_len.shape[0]) * _query_blocks(lq, rep)[1]
            * _key_blocks(page_size, max_pages)[1])


def count_page_block_reads(page_table, pos, q_len, group_id=None,
                           group_cnt=None, *, page_size, n_kv=1,
                           mp=1, fused=None):
    """Host-side (numpy) model of the kernels' page DMA traffic for ONE
    (kv_head, layer) walk, in PAGES (a key block of the walk is several
    pages, each its own DMA: the unit here is the page, as it always
    was) — the number the serving metrics and the `--prefix-share`
    bench A/B report, and what tests pin. A row whose chunk is more
    than one query block (heads that share a kv head) reads its pages
    once a query block; the model counts them once a row, as it did
    when every 8 queries re-read them.

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
    the in-kernel dots; the count stays per head though one DMA
    carries a page's local heads together), and each page read moves
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
