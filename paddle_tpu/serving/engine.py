"""ServingEngine: continuous batching over the compiled decode path.

The engine owns a PAGED KV pool — per layer one shared block pool
[num_pages, page_size, H, D] — plus per-slot page tables [S, max_pages]
of int32 page ids and one `pos` per slot. A request admitted into a
slot allocates only the pages its prompt + output budget needs
(`ceil((plen + max_new) / page_size)`), so a slot holding a 40-token
request no longer pins `max_len` dense rows; HBM capacity bounds
concurrency by TOKENS IN FLIGHT, not by slots × max_len (Ragged Paged
Attention, PAPERS.md).

By default (PADDLE_TPU_UNIFIED_STEP=on / ServingEngine(unified=...))
exactly ONE program shape touches the pool — the UNIFIED RAGGED
PREFILL+DECODE STEP, a fixed-shape [num_slots, chunk_len] forward in
which every row carries its own live query count (`q_len`) through the
ragged paged-attention op: decoding rows sample their next token from
the held logits (per-slot temperature/top-k/top-p vectors, same math
as CompiledGenerator via `sample_logits`/`_top_p_filter`) and run it
at q_len 1 — or, with SPECULATIVE DECODING on, at q_len 1 + k with k
drafter-proposed tokens riding behind the sampled one (see below);
mid-prefill rows feed up to `chunk_len` prompt tokens in the SAME
invocation (q_len up to chunk_len); idle rows ride dead at q_len 0.
`Scheduler.pack_tokens` decides the packing each step under a
`token_budget` (default the full num_slots * chunk_len step shape):
decode rows always get their token — a long prompt can NEVER stall a
resident decoder — prefill rows split the spare, and draft tokens
take what's left. Membership, page tables, q_lens and sampling params
change BETWEEN invocations only — the one program never retraces,
which is what lets XLA keep the hot loop one fused executable
("Operator Fusion in XLA", PAPERS.md).

SPECULATIVE DECODING (serving/spec.py, PADDLE_TPU_SPEC_DECODE=
off|ngram[:k] / ServingEngine(spec=...), default off) lifts decode
rows past one token per step-latency WITHOUT a new program: a
host-side per-request Drafter (model-free n-gram prompt-lookup by
default) proposes up to k next tokens, the row feeds
[sampled, draft_1..draft_k] at q_len 1+k through the SAME unified
step, and greedy acceptance — computed inside that program — keeps
the longest prefix of drafts matching the model's own argmax chain:
the row's pos advances by 1 + accepted (rejected drafts roll back;
their already-written KV sits past the new pos exactly like padding
columns, overwritten before it is ever attended), the held logits
come from the last ACCEPTED position (so the next step's sample IS
the correction token), and the engine emits the whole verified burst.
Every emitted token is the one sequential greedy decode would have
produced — bit-token-identical on vs off, same oracle pattern as the
other gates — and the prefix cache only ever indexes committed
tokens.

The legacy ALTERNATING path (PADDLE_TPU_UNIFIED_STEP=off) keeps the
two old program families for A/B: one fixed-shape decode step for all
slots, plus one chunked-prefill program per power-of-two chunk bucket
(a batch-1 forward of `chunk_len` prompt tokens, ONE chunk per engine
step interleaved with resident decodes, O(log chunk_len) traces
total). Greedy outputs are token-identical across the gate, asserted
against the solo CompiledGenerator oracle either way.

Free slots and retired requests point their page-table rows at the
reserved trash page 0, so the fixed-shape scatter/gather stays safe for
any live/free mix (see serving/paging.py and the paged DecodeCache).

An AUTOMATIC PREFIX CACHE (serving/prefix.py, default on, gated by
`prefix_cache=...` / PADDLE_TPU_PREFIX_CACHE) sits between the pool and
admission: finished requests' pages are indexed in a token-id radix
tree; a new prompt's longest cached prefix attaches those pages to its
page table (refcount++, zero prefill work) and only the uncached tail
runs chunked prefill — a mid-page match gets its partial page
copy-on-write (one compiled single-page copy) so shared pages are never
written through. Retired pages park in the cache instead of freeing;
admission under page pressure evicts LRU unreferenced leaves before
applying backpressure. None of this changes any compiled program — only
which page ids the host page tables carry — so greedy outputs stay
token-identical with the cache on, off, hot, or thrashing.

OVERLOAD IS A SCHEDULING PROBLEM, NOT A FAILURE MODE (default on,
gated `preempt=...` / PADDLE_TPU_PREEMPT): requests carry a
`priority` (lower = more important) and an optional placement
`deadline_s`; the queue orders by (priority, deadline, arrival). When
the queue head is blocked — no slot, or its page budget doesn't fit —
and a STRICTLY lower-priority resident exists, that resident is
PREEMPTED instead of the head being refused: its emitted tokens are
banked (the client's stream object stays live), its private KV pages
swap out whole-page to a HOST-RAM tier (`HostPagePool`; one compiled
copy program per direction over traced page ids — no retrace), its
shared prefix pages return to the radix tree, and its slot frees. It
re-admits later via swap-in: pos restored from the banked pages, held
logits regenerated by re-prefilling one token, the drafter re-seeded
— greedy output bit-token-identical to never having been preempted.
Queued requests whose placement deadline expires fail fast as typed
`DeadlineExceeded` ("deadline", HTTP 504) instead of silently burning
queue slots. Parked prefix-cache pages may also SPILL to the host
tier under page pressure (restored on the next match) — stage 1 of
the ROADMAP's fleet-scale prefix cache.

QUANTIZED SERVING (default off, gated `kv_dtype=...` /
PADDLE_TPU_KV_DTYPE=fp|int8|fp8): with "int8" the per-layer pools
hold rowwise-int8 CODE pages plus per-page f32 SCALE pages — ~half
the HBM bytes per resident token, so the same HBM budget admits ~2x
the residents AND the decode step's dominant HBM stream halves.
Writes quantize-then-scatter in the same one-trace program; reads
dequantize in the ragged kernel's fused int8 lane (or the
dequantizing gather on the A/B path). Every whole-page move — COW,
preemption swap, prefix spill — carries code and scale pages
together, so int8 streams stay DETERMINISTIC and feature-on/off
token-identical; int8 vs fp output drift is bounded and benched
(serving_bench --quant-ab). "fp8" is the PURE-CONVERT lane: f8_e4m3
pages with NO scale pages (writes clip to +-448 and round; reads
upconvert in VMEM / in the gather) — one byte per element, strictly
fewer bytes than int8's codes+scales, and pages move through
COW/swap/spill exactly like fp pages. Lossier per read than rowwise
int8 but operand-free; deterministic, drift pinned
(tests/test_serving_fp8.py).

PREFIX-SHARING-AWARE GROUPED ATTENTION (default on, gated
`grouped=...` / PADDLE_TPU_GROUPED_ATTN): under high prefix share N
residents' page tables point at the SAME physical system-prompt
pages, yet the per-row kernel walk streams them from HBM N times per
step. Each step the engine groups rows whose page tables share a
physical-page prefix (serving/prefix.py's `shared_prefix_groups` —
host-side, from the very page tables the cache built; a COW'd page
splits its row out at the divergence, eviction and retirement shrink
groups between steps) and passes (group_id, group_leader, group_cnt)
as three extra [S] operands next to pos/q_len — operand DATA, so the
ONE unified trace never retraces. On TPU the grouped op's two-phase
walk streams each shared page once per GROUP (phase 1: all member
rows' online-softmax partials fold in VMEM; phase 2: private tails
merge per row — same page order, bit-identical outputs; phase 1's
sweep is a dynamic grid bound inside the trace, whole only on a step
where some group_cnt is non-zero and one grid step a query block
otherwise, when phase 2 starts from the virgin partials); on CPU it
IS the ungrouped reference, so grouped on/off stays bit-token-
identical by construction. `count_page_block_reads` models the DMA
traffic host-side each step, feeding the page_block_reads /
shared_page_reads_saved counters and the group-size histogram the
`--prefix-share` A/B asserts on; `grouped_walk_steps_total` counts
the steps on which phase 1 swept.

MULTI-TENANT ADAPTERS (serving/adapters.py, default off, gated
`adapters=...` / PADDLE_TPU_ADAPTERS): thousands of LoRA fine-tunes
of one base model share this engine. Registered per-layer A/B pairs
(rank-bucketed, zero-padded to one pool rank so shapes never change)
live in a PAGED ADAPTER POOL with the KV pool's exact PagePool
discipline — refcounted while a resident slot decodes under them,
parked hot when idle, spilled to a host tier or evicted LRU under
pressure, restored on demand. A per-slot adapter-page vector (+
scale) rides next to pos/q_len as operand data; inside the ONE
unified step each layer gathers its rows' A/B pages and the
attention modules fuse the per-row low-rank delta into the q/k/v/o
projections (`lora_delta`). adapter_id 0 is the base model (the
all-zero page 0 — exact degeneration), so mixed-tenant batches
compile to the same single program, and every tenant's stream is
bit-token-identical to a solo engine running the dense-merged
(W + B·A·scale) weights. The prefix cache namespaces its radix tree
by adapter id — tenants never share KV pages.

MULTI-CHIP TENSOR PARALLELISM (serving/tp.py, default off, gated
`mesh=...` / PADDLE_TPU_MESH=dpXmpY): one engine spans a (dp, mp)
device mesh while compiling the SAME one unified step — per-layer KV
pools shard over their kv-head axis (each chip holds a 1/mp slice of
every page: mp x the residents per chip-HBM byte), q/k/v projections
shard column-parallel over whole heads, and everything else — page
tables, pos/q_len, group operands, sampling vectors, scheduler,
prefix cache, preemption, spec decode — stays replicated and
UNCHANGED. The only collective is one bit-exact attention-output
all-gather per layer (zero all-reduces: fp math never reassociates),
so an mp>1 engine is bit-token-identical to the mp=1 oracle;
`collective_counts()` pins that against compiled HLO.

Correctness contract (tests/test_serving.py): a request decoded greedily
through the engine emits tokens bit-identical to running it ALONE
through CompiledGenerator greedy decode — through chunked prefill,
page-table indirection, page reuse after eviction, and
preempt-swap-resume cycles. (With kv_dtype="int8" the oracle is the
int8 engine itself: feature gates stay token-identical, fp drift is
bounded, not zero.)

Weights enter every compiled program as its first ARGUMENT
(`_StepProgram`), never as closed-over constants: at 1.3B constants
put 2.6 GB of literals into the executable — a second copy of the
weights in device memory, a compile of 291 s against 34 s, and an HLO
module past protobuf's 2 GiB limit, so `as_text()` (the collective
and cost censuses) fails (compiles for a described v5e, PR 23) — and
an ahead-of-time compile could not be handed shapes. Construct the
engine AFTER any weight rebinding (quantization etc.) — it snapshots
model state.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import os
import threading
import time
import warnings
from typing import Dict, List, NamedTuple, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from ..core import dtype as dtypes
from ..core import random as random_mod
from ..core import tensor as tensor_mod
from ..core.dispatch import get_op
from ..core.tensor import Tensor, set_dispatch_probe
from ..profiler import RecordEvent
from ..nlp.generation import (_StepProgram, _pack_caches,
                              _restore_state, _swap_state, _top_p_filter,
                              _unpack_caches, decode_model_step,
                              resolve_paged_attn_impl, FP8_DTYPE)
from ..ops.pallas.paged_attention import (count_page_block_reads,
                                          count_walk_grid_steps,
                                          count_window_page_reads,
                                          resolve_megakernel_flag)
from .adapters import (AdapterStore, BASE_ADAPTER,
                       resolve_adapters_flag)
from .draft import DraftConfig, DraftEngine, make_draft_model
from .errors import DeadlineExceeded, EngineClosed, PoisonedRequest
from .fabric import decode_frame, encode_frame, frame_header
from .grammar import (NEG_BIAS, TokenGrammar, resolve_grammar_flag)
from .metrics import ServingMetrics
from .obs import EngineObs, resolve_obs_flag
from .paging import (HostPagePool, PagePool, TRASH_PAGE, chunk_bucket,
                     pages_needed)
from .prefix import (RadixPrefixCache, resolve_prefix_cache_flag,
                     shared_prefix_groups)
from .request import Request, RequestOutput, RequestState, SamplingParams
from .scheduler import Scheduler
from .slo import (SLOTracker, capture_cost_census, model_cost_census,
                  resolve_cost_census, resolve_slo_config)
from .spec import Drafter, ModelDrafter, resolve_spec_config
from .tp import ServingTP, collective_counts, resolve_serving_mesh

__all__ = ["ServingEngine", "resolve_unified_flag",
           "resolve_preempt_flag", "resolve_kv_dtype",
           "resolve_grouped_flag", "resolve_obs_flag",
           "resolve_adapters_flag", "resolve_serving_mesh",
           "resolve_slo_config", "resolve_cost_census",
           "ServingTP"]

# finish reason -> timeline event kind (the 5xx/4xx taxonomy keeps
# its own event names so a timeline's last event says WHY at a
# glance; everything else rides its raw reason)
_TERMINAL_EVENT = {"stop": "finish", "length": "finish",
                   "deadline": "deadline", "poisoned": "poison",
                   "replica_failure": "replica_death"}

# Host spans (`profiler.RecordEvent`): names are constants, ids ride as
# arguments. Each span is also a `jax.profiler.TraceAnnotation`, so a JAX
# profiler session against the running engine holds them on the clock of
# the device's `XLA Ops`. One scheduler round nests as
#   round(step) > admit > {spill(page), restore(page), cow_copy}
#               > plan > {spill, restore}
#               > unified_step > launch, fetch
#               > commit > embed_epilogue
#               > report
# and the seconds of plan/launch/fetch/commit/admit/report/spill also
# feed `metrics.HOST_PHASE_COUNTERS`, one `on_host_phases` call a round.
SPAN_ROUND = "serving::round"
SPAN_ADMIT = "serving::admit"
SPAN_PLAN = "serving::plan"
SPAN_UNIFIED_STEP = "serving::unified_step"
SPAN_LAUNCH = "serving::launch"
SPAN_FETCH = "serving::fetch"
SPAN_COMMIT = "serving::commit"
SPAN_REPORT = "serving::report"
# one page to the host tier (`_extract_page`), and the prefix cache's walk
# of its tree for candidates (`_spill_walk`, wired by `set_host_tier`)
SPAN_SPILL = "serving::spill"
SPAN_RESTORE = "serving::restore"
SPAN_COW_COPY = "serving::cow_copy"
SPAN_EMBED = "serving::embed_epilogue"
# the legacy alternating path's two program families
SPAN_PREFILL = "serving::prefill"
SPAN_DECODE_STEP = "serving::decode_step"

UNIFIED_STEP_MODES = ("on", "off")
PREEMPT_MODES = ("on", "off")
KV_DTYPE_MODES = ("fp", "int8", "fp8")
GROUPED_ATTN_MODES = ("on", "off")


def resolve_grouped_flag(override=None) -> bool:
    """Whether the unified step runs the PREFIX-SHARING-AWARE grouped
    page walk (default on): rows whose page tables share a
    physical-page prefix (the radix cache attached the same pages)
    are grouped host-side each step, and the ragged kernel streams
    each shared page from HBM once per GROUP instead of once per row
    — under high prefix share the dominant decode HBM stream drops
    ~Nx. Outputs are bit-identical either way (on CPU the grouped op
    IS the ungrouped reference); groups are operand DATA, so the one
    unified trace never retraces. An explicit override wins;
    otherwise PADDLE_TPU_GROUPED_ATTN=on|off (read at engine
    construction — the compiled step keeps the op it was traced
    with)."""
    if override is not None:
        return bool(override)
    v = os.environ.get("PADDLE_TPU_GROUPED_ATTN", "on")
    if v not in GROUPED_ATTN_MODES:
        raise ValueError(
            f"PADDLE_TPU_GROUPED_ATTN must be one of "
            f"{GROUPED_ATTN_MODES}, got {v!r}")
    return v == "on"


def resolve_kv_dtype(override=None) -> str:
    """Which dtype the paged KV pool holds: "fp" (the model's float
    dtype, the default), "int8" — rowwise-quantized code pages plus
    per-page scale pages, ~half the HBM bytes per resident token, so
    the same HBM budget admits ~2x the residents AND decode's
    dominant HBM stream halves — or "fp8": PURE-CONVERT f8_e4m3
    pages, NO scale pages at all (the e4m3 value is the number,
    saturating round-to-nearest on write), one byte per element with
    zero extra operands — the cheapest quantized lane, and pages move
    through COW/swap/spill exactly like fp pages. Quantization is
    lossy: greedy outputs with int8/fp8 on are NOT bit-identical to
    fp (drift is bounded and pinned), but every serving feature
    (prefix cache, COW, preemption swap, spec decode, migration)
    stays deterministic and self-consistent at either lane. An
    explicit override wins; otherwise PADDLE_TPU_KV_DTYPE=fp|int8|fp8
    (read at engine construction — the compiled programs keep the
    pool dtype they were traced with)."""
    v = override or os.environ.get("PADDLE_TPU_KV_DTYPE", "fp")
    if v not in KV_DTYPE_MODES:
        raise ValueError(
            f"kv_dtype must be one of {KV_DTYPE_MODES} "
            f"(PADDLE_TPU_KV_DTYPE / ServingEngine(kv_dtype=...)), "
            f"got {v!r}")
    return v


def resolve_preempt_flag(override=None) -> bool:
    """Whether overload turns into PREEMPTION instead of pure
    backpressure (default on): when the ordered queue's head is
    blocked and a strictly lower-priority resident exists, that
    resident is preempted — its emitted tokens banked, its KV pages
    swapped to the host-RAM tier, its slot freed — and it resumes
    later via swap-in, token-identically. An explicit override wins;
    otherwise PADDLE_TPU_PREEMPT=on|off (read at engine construction;
    same gate pattern as PADDLE_TPU_UNIFIED_STEP)."""
    if override is not None:
        return bool(override)
    v = os.environ.get("PADDLE_TPU_PREEMPT", "on")
    if v not in PREEMPT_MODES:
        raise ValueError(
            f"PADDLE_TPU_PREEMPT must be one of {PREEMPT_MODES}, "
            f"got {v!r}")
    return v == "on"


class _StepPlan(NamedTuple):
    """What `_plan_unified` hands to the launch and to `_commit_unified`."""
    decode_slots: list
    grants: dict
    draft_grants: dict
    proposals: dict
    args_tail: tuple
    t0: float           # where `decode_step_s` starts: before the uploads


class _SwapHandle:
    """A preempted request's claim on the host tier: `host_slots[j]`
    holds the KV payload of the page at page-table index `base + j`;
    `kv_len` is how many leading positions of the committed sequence
    hold valid KV. `restores`/`drops` are filled by the resume
    reservation (which host pages swap back in vs. are redundant with
    a fresh prefix-cache match)."""

    __slots__ = ("host_slots", "base", "kv_len", "restores", "drops")

    def __init__(self, host_slots, base, kv_len):
        self.host_slots = list(host_slots)
        self.base = int(base)
        self.kv_len = int(kv_len)
        self.restores = []      # [(host_slot, dst_page), ...]
        self.drops = []         # host slots made redundant by a match


def resolve_unified_flag(override=None) -> bool:
    """Whether the engine runs the UNIFIED ragged prefill+decode step
    (default on): ONE compiled program per engine — decode rows
    (q_len 1) and mid-prefill rows (q_len up to chunk_len) share every
    step through the ragged paged-attention op — instead of the old
    two program families (per-bucket prefill chunks alternating with
    the fixed-shape decode step). An explicit override wins; otherwise
    PADDLE_TPU_UNIFIED_STEP=on|off (read at engine construction; the
    old alternating path is kept for A/B, same oracle pattern as
    PADDLE_TPU_PAGED_ATTN / PADDLE_TPU_PREFIX_CACHE)."""
    if override is not None:
        return bool(override)
    v = os.environ.get("PADDLE_TPU_UNIFIED_STEP", "on")
    if v not in UNIFIED_STEP_MODES:
        raise ValueError(
            f"PADDLE_TPU_UNIFIED_STEP must be one of "
            f"{UNIFIED_STEP_MODES}, got {v!r}")
    return v == "on"


def _sample_rows(logits, key, temps, top_k, top_p, greedy, argmax=None):
    """Per-slot sampling over f32 logits [S, V]: each row applies ITS
    OWN temperature/top-k/top-p (vectors [S]); greedy rows take argmax
    of the raw logits — exactly CompiledGenerator's greedy step, so
    greedy requests stay bit-identical to offline decode. top_k == 0
    and top_p == 1.0 disable the respective filter for that row; the
    nucleus mask is the same `_top_p_filter` the offline path uses.
    `argmax` lets the megakernel path hand in the fused
    decode_greedy_argmax epilogue's result (bit-identical to
    jnp.argmax by the first-occurrence tie rule) instead of computing
    it again here."""
    v = logits.shape[-1]
    g = jnp.argmax(logits, axis=-1) if argmax is None else argmax
    l = logits / temps[:, None]
    sorted_desc = -jnp.sort(-l, axis=-1)
    kidx = (jnp.clip(top_k, 1, v) - 1).astype(jnp.int32)
    kth = jnp.take_along_axis(sorted_desc, kidx[:, None], axis=-1)
    l = jnp.where((top_k > 0)[:, None] & (l < kth), -1e30, l)
    filt = _top_p_filter(l, top_p[:, None])
    l = jnp.where((top_p < 1.0)[:, None], filt, l)
    s = jax.random.categorical(key, l, axis=-1)
    return jnp.where(greedy, g, s)


class ServingEngine:
    """Online inference engine: submit requests at any time, pump
    `step()` (or call `run()`/`generate()`); requests join free slots
    when their page budget fits the pool, prefill chunk by chunk,
    decode together in one compiled step, and retire on EOS /
    max-tokens / timeout / cancellation without perturbing neighbors.
    """

    MIN_CHUNK = 8     # smallest prefill bucket (power of two)

    def __init__(self, model, cache_spec=None, *, num_slots: int = 8,
                 max_len: int = 256, page_size: int = 16,
                 num_pages: Optional[int] = None, chunk_len: int = 32,
                 scheduler: Optional[Scheduler] = None,
                 metrics: Optional[ServingMetrics] = None,
                 max_queue: Optional[int] = None, clock=time.monotonic,
                 attn_impl: Optional[str] = None,
                 prefix_cache=None, unified=None,
                 token_budget: Optional[int] = None, spec=None,
                 preempt=None, host_pages: Optional[int] = None,
                 kv_dtype: Optional[str] = None, grouped=None,
                 obs=None, flight_steps: Optional[int] = None,
                 mesh=None, adapters=None,
                 adapter_pages: Optional[int] = None,
                 adapter_ranks: Optional[Sequence[int]] = None,
                 slo=None, cost_census=None, grammar=None,
                 megakernel=None, session_ttl_s: float = 30.0,
                 draft_pages: Optional[int] = None):
        # THE CACHE-SPEC CONTRACT. `model._decode_cache_spec()` (or
        # the `cache_spec` argument) is
        #   (n_layers, n_kv_heads, head_dim)
        # for a model whose layers all attend their whole context: one
        # KV geometry, one paged pool a layer, and ONE page table a
        # slot that every layer shares; or
        #   (n_layers, n_kv_heads, head_dim, windows)
        # for a model with layer KINDS: `windows[i]` is layer i's
        # sliding window in tokens (the query's own position included)
        # or None for full attention. KV heads and head size are still
        # one pair for all layers (query heads may differ: they never
        # reach the cache). A window layer's attention module passes
        # its window to `update_and_attend`; the engine gives such a
        # layer a pool of its own, a per-slot RING of pages behind a
        # static page table (below), and leaves the full layers on the
        # shared paged pool.
        if cache_spec is None:
            if not hasattr(model, "_decode_cache_spec"):
                raise ValueError(
                    "cache_spec not given and the model has no "
                    "_decode_cache_spec(); pass (n_layers, n_kv_heads, "
                    "head_dim), or (n_layers, n_kv_heads, head_dim, "
                    "windows) with each layer's sliding window or None, "
                    "explicitly")
            cache_spec = model._decode_cache_spec()
        self.model = model
        self.n_layers, self.n_kv, self.head_dim = cache_spec[:3]
        windows = tuple(cache_spec[3]) if len(cache_spec) > 3 \
            else (None,) * self.n_layers
        if len(windows) != self.n_layers:
            raise ValueError(f"cache_spec lists {len(windows)} windows "
                             f"for {self.n_layers} layers")
        # layer index -> window, for the layers that have one
        self.kv_windows = {i: int(w) for i, w in enumerate(windows)
                           if w is not None}
        self.num_slots = int(num_slots)
        self.max_len = int(max_len)
        self.page_size = int(page_size)
        if self.page_size < 1:
            raise ValueError("page_size must be >= 1")
        self.max_pages = -(-self.max_len // self.page_size)
        # default pool = dense-equivalent capacity (+ the trash page):
        # every slot can still hold max_len, and sizing num_pages BELOW
        # this is where the paged pool beats the dense cache — more
        # resident short requests per HBM byte
        self.num_pages = (self.num_slots * self.max_pages + 1
                          if num_pages is None else int(num_pages))
        self.chunk_len = int(chunk_len)
        if self.chunk_len < self.MIN_CHUNK:
            raise ValueError(f"chunk_len must be >= {self.MIN_CHUNK}")
        self.scheduler = scheduler or Scheduler(self.num_slots,
                                                max_queue=max_queue)
        if self.scheduler.num_slots != self.num_slots:
            raise ValueError("scheduler.num_slots != engine num_slots")
        # paged decode attention implementation: "kernel" (Pallas
        # ragged paged attention, the default) or "gather" (the
        # paged_kv_gather + dense SDPA cross-check path). Resolved ONCE
        # here — the compiled decode step keeps the impl it was traced
        # with; flipping PADDLE_TPU_PAGED_ATTN later needs a new engine.
        self.attn_impl = resolve_paged_attn_impl(attn_impl)
        # multi-chip tensor-parallel replica (serving/tp.py, default
        # off, gated ServingEngine(mesh=...) / PADDLE_TPU_MESH=dpXmpY):
        # ONE engine spans a (dp, mp) device mesh while compiling the
        # SAME one unified step — the per-layer KV pools shard over
        # their kv-head axis (each chip holds a 1/mp slice of every
        # page: mp x the residents per chip-HBM byte), the q/k/v
        # projections shard column-parallel over whole heads, and the
        # attention output all-gathers back to replicated ONCE per
        # layer (zero all-reduces — no fp reassociation, so mp>1 is
        # bit-token-identical to the mp=1 oracle). Page tables,
        # pos/q_len/group operands, scheduler, prefix cache,
        # preemption, spec decode: replicated and UNCHANGED.
        self.tp = resolve_serving_mesh(mesh)
        self.mp = self.tp.mp if self.tp is not None else 1
        self.dp = self.tp.dp if self.tp is not None else 1
        if self.tp is not None:
            cfgm = getattr(model, "config", None)
            self.tp.validate_geometry(
                n_kv=self.n_kv,
                n_heads=int(getattr(cfgm, "num_attention_heads",
                                    self.n_kv)),
                hidden=int(getattr(cfgm, "hidden_size",
                                   self.n_kv * self.head_dim)))
        # unified ragged prefill+decode step (default on): ONE compiled
        # program of width chunk_len serves every prefill/decode mix
        # per step — decode rows at q_len 1 (1 + k with speculative
        # drafts riding along), mid-prefill rows at q_len up to
        # chunk_len — and the scheduler PACKS prefill tokens into
        # spare decode-step capacity (token_budget) instead of
        # alternating program families. Gated by
        # ServingEngine(unified=...) / PADDLE_TPU_UNIFIED_STEP.
        self.unified = resolve_unified_flag(unified)
        # per-step packed-token ceiling: decode rows always get their
        # token; prefill packing is throttled to the spare budget.
        # Default = the full compiled step shape (num_slots * chunk_len
        # — no artificial throttle; the [S, chunk_len] trace shape is
        # the bound). Set it LOWER on hardware where attention FLOPs
        # dominate step latency (very long contexts): the ragged
        # kernel's work scales with tokens actually packed, so a
        # smaller budget caps per-step latency for residents at the
        # cost of slower prefill.
        self.token_budget = (self.num_slots * self.chunk_len
                             if token_budget is None
                             else int(token_budget))
        if self.token_budget < 1:
            raise ValueError("token_budget must be >= 1")
        # speculative decoding (serving/spec.py, default off): a
        # SpecConfig when drafting is on, None otherwise. The verify
        # pass IS a unified-step row at q_len 1+k, so speculation
        # requires the unified path — explicitly enabling both spec
        # and the legacy alternating step is a config error.
        self.spec = resolve_spec_config(spec)
        if self.spec is not None and not self.unified:
            raise ValueError(
                "speculative decoding requires the unified ragged "
                "step: the verify pass rides the per-row q_len>1 "
                "path (set unified=True / PADDLE_TPU_UNIFIED_STEP=on "
                "or turn PADDLE_TPU_SPEC_DECODE off)")
        # per-request drafters, created at admission for greedy
        # requests and dropped at retirement (request_id -> Drafter)
        self._drafters: Dict[str, Drafter] = {}
        # the MODEL drafter tier (serving/draft.py): a small draft
        # model resident in THIS engine with its own paged KV pool —
        # draft micro-steps are more ragged rows through the draft
        # model's own ONE compiled program (the engine's second and
        # LAST program). The draft model stays replicated on a mesh
        # (it is tiny and its program has no collectives — the
        # collective census is the target program's, unchanged).
        # `draft_pages` mirrors `num_pages` semantics (total
        # including trash page 0); default = the target pool's page
        # COUNT, which is far fewer bytes (fewer layers per page).
        self._draft: Optional[DraftEngine] = None
        if self.spec is not None and self.spec.mode == "model":
            dm = self.spec.draft_model
            if dm is None:
                dm = make_draft_model(model)
            self._draft = DraftEngine(dm, DraftConfig(
                num_slots=self.num_slots, chunk_len=self.chunk_len,
                page_size=self.page_size,
                num_pages=(self.num_pages if draft_pages is None
                           else int(draft_pages)),
                max_pages=self.max_pages,
                attn_impl=self.attn_impl))
        # grammar-constrained decoding (serving/grammar.py, default
        # off, gated ServingEngine(grammar=...) / PADDLE_TPU_GRAMMAR):
        # constrained requests carry a host-side token automaton (the
        # Drafter lifecycle) whose per-step allow-mask rides as a
        # [S, V] additive-bias operand next to pos/q_len into the ONE
        # unified step. The gate is a BUILD-TIME program shape: with
        # it off, the compiled step carries no bias operand at all and
        # is byte-identical to a pre-grammar engine (the
        # bit-token-identity oracle); with it on, unconstrained rows
        # ride all-zero bias rows, so mixed batches stay one program.
        self.grammar_on = resolve_grammar_flag(grammar)
        if self.grammar_on and not self.unified:
            raise ValueError(
                "grammar-constrained decoding requires the unified "
                "ragged step: the mask operand rides the ONE compiled "
                "program (set unified=True / PADDLE_TPU_UNIFIED_STEP"
                "=on or turn PADDLE_TPU_GRAMMAR off)")
        # per-request automatons, request_id -> TokenGrammar (created
        # at admission, advanced on every committed token, dropped at
        # retirement; preemption/migration re-creates and replays —
        # the committed token history IS the banked state)
        self._grammars: Dict[str, TokenGrammar] = {}
        # session pinning TTL: how long a finished `session=` request
        # keeps its radix prefix pages pinned above LRU
        self.session_ttl_s = float(session_ttl_s)
        # prefix-sharing-aware grouped page walk (default on, gated
        # PADDLE_TPU_GROUPED_ATTN / ServingEngine(grouped=...)): the
        # unified kernel step streams each physically shared page once
        # per GROUP. Only the unified + kernel path has a grouped
        # walk; on the legacy/gather paths the flag is inert.
        self.grouped = (resolve_grouped_flag(grouped) and self.unified
                        and self.attn_impl == "kernel")
        # decode MEGAKERNEL (ops/pallas/paged_attention.py, default
        # off, gated PADDLE_TPU_MEGAKERNEL / megakernel=): the unified
        # step's per-layer scatter(+quantize)+attend op pair — and,
        # with adapters, the per-projection LoRA gathers — collapse
        # into ONE megakernel_decode[_q8] dispatch per layer, with
        # greedy argmax + spec acceptance as fused epilogue ops over
        # the logits tile. Only the unified + kernel path has a fused
        # form (silent downgrade, mirroring the grouped gate); a tp
        # mesh keeps the unfused path — in-place pool aliasing across
        # shards is not in this PR's oracle matrix. Outputs are
        # bit-identical either way (the shared-forward construction);
        # the referees are the launch-count probe and the fused-byte
        # census, not the floats.
        self.megakernel = (resolve_megakernel_flag(megakernel)
                           and self.unified
                           and self.attn_impl == "kernel"
                           and self.tp is None)
        if self.kv_windows:
            # WINDOW LAYERS: what the engine does not do for them yet.
            # Their KV lives in a per-slot ring that holds the last
            # window + chunk positions only, so a page of the shared
            # pool no longer stands for the same tokens in every
            # layer: reusing a prefix (which would need the window
            # layers' KV of the prefix's LAST window positions, kept
            # and copied with it), swapping a resident out to the host
            # tier and preempting one are switched off, loudly;
            # silently wrong reuse is the one outcome that must not
            # happen. ROADMAP.md (Queue 2) says what each would take.
            wanted = [name for name, given in (
                ("prefix_cache", prefix_cache), ("preempt", preempt),
                ("host_pages", host_pages)) if given]
            unbuilt = [name for name, on in (
                ("mesh", self.tp is not None),
                ("unified=False", not self.unified),
                ("megakernel", self.megakernel),
                ("adapters", bool(adapters)),
                ("kv_dtype", resolve_kv_dtype(kv_dtype) != "fp"),
                ("spec", self.spec is not None)) if on]
            if wanted or unbuilt:
                raise ValueError(
                    f"the model has sliding-window layers "
                    f"{sorted(self.kv_windows)}: {wanted + unbuilt} "
                    f"cannot be had with them yet (the prefix cache, "
                    f"the host tier and preemption would reuse or move "
                    f"pages whose window-layer KV is gone; the others "
                    f"have no window in their attention path)")
            warnings.warn(
                f"ServingEngine: sliding-window layers "
                f"{sorted(self.kv_windows)}: the prefix cache, the "
                f"host page tier, preemption and the grouped walk are "
                f"switched off for this model", stacklevel=2)
            prefix_cache, preempt, host_pages = False, False, 0
            self.grouped = False
        self.metrics = metrics or ServingMetrics()
        self.metrics.attn_impl = self.attn_impl
        self.metrics.unified = self.unified
        self.metrics.grouped = self.grouped
        self.metrics.megakernel = self.megakernel
        self.metrics.spec = (None if self.spec is None
                             else self.spec.mode)
        self.metrics.spec_draft_model = self._draft is not None
        if self._draft is not None:
            # seed the capacity gauge so a scrape before the first
            # step already shows the draft tier (host-tier pattern)
            self.metrics.draft_pool_pages_total = \
                self._draft.num_pages - 1
        self.metrics.grammar = self.grammar_on
        self._clock = clock
        self._id_counter = itertools.count()
        self._requests: Dict[str, Request] = {}
        # model-state snapshot: weights are the compiled programs'
        # first operand (see module doc)
        params = list(model.parameters())
        buffers = [b for _, b in model.named_buffers()]
        self._state_tensors = params + buffers
        # the weight values the compiled programs take: on a
        # mesh, the engine's OWN sharded copies (QKV projections
        # column-parallel over heads, the rest replicated) — the
        # model's tensors are never rebound, so oracles and other
        # engines sharing the model see single-device values as ever
        self._state_vals = (
            self.tp.place_state(model, self._state_tensors)
            if self.tp is not None
            else [t._value for t in self._state_tensors])
        self._fp = next(
            (t._value.dtype for t in self._state_tensors
             if jnp.issubdtype(t._value.dtype, jnp.floating)),
            dtypes.get_default_dtype().np_dtype)
        # multi-tenant LoRA adapters (serving/adapters.py, default
        # off, gated ServingEngine(adapters=...) /
        # PADDLE_TPU_ADAPTERS=on): a paged ADAPTER pool next to the
        # paged KV pool — registered LoRA A/B weights live in
        # device-resident pool pages under the PagePool
        # refcount/park/evict/spill discipline, a per-slot
        # adapter-page vector rides next to pos/q_len as step operand
        # data, and each layer's attention fuses the per-row low-rank
        # delta into its q/k/v/o projections inside the ONE unified
        # step. adapter_id 0 is the base model (the all-zero page 0 —
        # exact degeneration), so mixed-tenant batches and pure base
        # traffic compile to the same single program.
        adapters_on = (isinstance(adapters, AdapterStore)
                       or resolve_adapters_flag(adapters))
        if adapters_on and not self.unified:
            raise ValueError(
                "multi-tenant adapters require the unified ragged "
                "step: the per-row gathered LoRA delta rides the ONE "
                "compiled program (set unified=True / "
                "PADDLE_TPU_UNIFIED_STEP=on or drop adapters)")
        if isinstance(adapters, AdapterStore):
            self.adapters: Optional[AdapterStore] = adapters
        elif adapters_on:
            cfgm = getattr(model, "config", None)
            hidden = int(getattr(cfgm, "hidden_size",
                                 self.n_kv * self.head_dim))
            n_heads = int(getattr(cfgm, "num_attention_heads",
                                  self.n_kv))
            self.adapters = AdapterStore(
                self.n_layers, hidden, n_heads * self.head_dim,
                self.n_kv * self.head_dim,
                num_pages=(8 if adapter_pages is None
                           else int(adapter_pages)) + 1,
                rank_buckets=(adapter_ranks or (2, 4, 8)),
                dtype=self._fp, tp=self.tp)
        else:
            self.adapters = None
        # per-slot adapter operands (step DATA, like pos/q_len): the
        # slot's adapter-pool page and LoRA scale — page 0 / scale 0
        # for base-model and idle rows
        self._apage = np.zeros((self.num_slots,), np.int32)
        self._ascale = np.zeros((self.num_slots,), np.float32)
        self._slot_adapter: Dict[int, int] = {}
        # modeled HBM bytes of ONE projection's adapter A/B page for
        # one row (pool rank R): the unfused path streams it once per
        # q/k/v projection, the megakernel streams it once total —
        # the lora term of the fused-byte census
        # (count_page_block_reads fused=)
        self._adapter_row_bytes = 0
        if self.adapters is not None:
            ad = self.adapters
            self._adapter_row_bytes = int(
                (ad.hidden * ad.rank + ad.rank * ad.q_out)
                * jnp.dtype(ad.dtype).itemsize)
        # paged-pool dtype (PADDLE_TPU_KV_DTYPE / kv_dtype=, default
        # "fp"): "int8" swaps every layer's float pools for int8 CODE
        # pages plus rowwise f32 SCALE pages [num_pages, page_size,
        # H_kv] — ~2x residents per HBM byte, and every whole-page
        # move (COW, preemption swap, prefix spill) carries
        # code + scale pages together so int8 streams stay
        # deterministic across all of them.
        self.kv_dtype = resolve_kv_dtype(kv_dtype)
        # window layers' KV: slot s owns pages 1 + s * ring_pages ..
        # of each such layer's pool (page 0 stays the trash page), used
        # as a RING: logical page lp of the slot lives in ring page
        # lp % ring_pages. One step writes at most chunk_len positions
        # ahead of the lowest key any of its queries still sees
        # (window - 1 back), so window - 1 + chunk_len positions are
        # alive at once: whatever max_len is, the ring holds that many
        # plus page rounding. The table is static (never dirty, never
        # trash-masked: a free slot's dead writes land in its own
        # ring, which the next tenant overwrites from position 0 on
        # before it reads).
        self.ring_pages = 0
        self._pt_ring = None
        if self.kv_windows:
            self.ring_pages = min(self.max_pages, -(-(
                max(self.kv_windows.values()) + self.chunk_len)
                // self.page_size) + 1)
            self._pt_ring = jnp.asarray(
                1 + np.arange(self.num_slots)[:, None] * self.ring_pages
                + np.arange(self.max_pages)[None, :] % self.ring_pages,
                jnp.int32)
        # device state: per-layer shared K/V pools, per-slot positions,
        # per-slot held next-token logits (filled by the final prefill
        # chunk, advanced by decode)
        if self.kv_dtype == "int8":
            self._ct = tuple(
                (jnp.zeros((self.num_pages, self.page_size, self.n_kv,
                            self.head_dim), jnp.int8),
                 jnp.zeros((self.num_pages, self.page_size, self.n_kv,
                            self.head_dim), jnp.int8),
                 # zero scales: the trash page dequantizes to exact 0.0
                 jnp.zeros((self.num_pages, self.page_size,
                            self.n_kv), jnp.float32),
                 jnp.zeros((self.num_pages, self.page_size,
                            self.n_kv), jnp.float32))
                for _ in range(self.n_layers))
        else:
            # fp8: pure-convert e4m3 pages ride the fp container shape
            # (no scale pools) — every whole-page program (COW, swap,
            # spill) works on them unchanged
            pool_dt = (FP8_DTYPE if self.kv_dtype == "fp8"
                       else self._fp)
            ring_total = self.num_slots * self.ring_pages + 1
            self._ct = tuple(
                (jnp.zeros((pages, self.page_size, self.n_kv,
                            self.head_dim), pool_dt),
                 jnp.zeros((pages, self.page_size, self.n_kv,
                            self.head_dim), pool_dt),
                 None, None)
                for pages in (ring_total if i in self.kv_windows
                              else self.num_pages
                              for i in range(self.n_layers)))
        if self.tp is not None:
            # shard every pool over its kv-head axis (scale pools
            # alongside their code pools: a page and its scales are
            # one unit on every path, sharding included)
            self._ct = tuple(
                (self.tp.place_pool(k), self.tp.place_pool(v),
                 None if ks is None else self.tp.place_scale(ks),
                 None if vs is None else self.tp.place_scale(vs))
                for k, v, ks, vs in self._ct)
        # HBM bytes one page costs across all layers (K and V, codes
        # + scale pages for int8; fp8 is one byte per element, no
        # scales) — the denominator of the residents-per-HBM-byte
        # economics serving_bench --quant-ab measures, and the byte
        # gauges' unit
        kv_itemsize = (1 if self.kv_dtype in ("int8", "fp8")
                       else jnp.dtype(self._fp).itemsize)
        scale_bytes = 4 if self.kv_dtype == "int8" else 0
        # (a page of the shared pool: the full-attention layers only)
        self.page_bytes = ((self.n_layers - len(self.kv_windows)) * 2
                           * self.page_size * self.n_kv
                           * (self.head_dim * kv_itemsize
                              + scale_bytes))
        self.metrics.kv_dtype = self.kv_dtype
        self.metrics.pool_bytes_per_page = self.page_bytes
        self.metrics.adapters_enabled = self.adapters is not None
        if self.adapters is not None:
            # seed the pool gauges so a scrape before the first step
            # already shows the adapter tier (same pattern as the
            # host-tier capacity gauges below)
            self.metrics.adapter_stats = self.adapters.stats()
        # per-CHIP page cost: each of the mp shards holds a 1/mp
        # kv-head slice of every page — the denominator of the
        # residents-per-chip-HBM economics the --tp-ab bench reports
        self.page_bytes_per_chip = self.page_bytes // self.mp
        self.metrics.mesh = (None if self.tp is None
                             else self.tp.shape)
        self.metrics.mp = self.mp
        self.metrics.dp = self.dp
        self.metrics.pool_shard_bytes_per_page = self.page_bytes_per_chip
        # the attention-output constraint the sharded step carries
        # through _unpack_caches (see serving/tp.py): replicate — the
        # single per-layer all-gather point
        self._out_shard = None if self.tp is None else self.tp.rep
        self._mesh = None if self.tp is None else self.tp.mesh
        self._pos = jnp.zeros((self.num_slots,), jnp.int32)
        if self.tp is not None:
            self._pos = self.tp.replicate(self._pos)
        self._last_logits = None      # [S, V] f32, lazy (V from prefill)
        # host page state: allocator, per-slot page lists, page tables
        # (full for prefill; decode variant trash-masks non-DECODE rows
        # so their ignored writes can't touch live pages)
        self.pool = PagePool(self.num_pages)
        # automatic prefix cache (serving/prefix.py): radix tree of
        # finished requests' pages over the pool. Admission
        # longest-prefix-matches the prompt and attaches shared pages
        # (refcount++) instead of re-prefilling them; gated by
        # ServingEngine(prefix_cache=...) / PADDLE_TPU_PREFIX_CACHE
        # (default on). Greedy outputs are token-identical either way —
        # only the page ids in the host page tables differ.
        self.prefix_cache = (
            RadixPrefixCache(self.pool, self.page_size,
                             clock=self._clock)
            if resolve_prefix_cache_flag(prefix_cache) else None)
        # HOST-RAM page tier (graceful overload degradation + stage 1
        # of the fleet-scale prefix cache): whole-page KV payloads of
        # preempted residents — and, under pressure, of parked prefix
        # pages — live here until swap-in restores them into freshly
        # allocated device pages. Default capacity mirrors the device
        # pool; 0 disables the tier (preemption then degrades to
        # recompute-on-resume).
        self.host_pages = (self.num_pages - 1 if host_pages is None
                           else int(host_pages))
        self.host_pool = HostPagePool(self.host_pages)
        # seed the capacity gauges so a scrape before the first step
        # already shows the tier's (byte) size
        self.metrics.host_pages_total = self.host_pages
        self.metrics.pool_pages_total = self.num_pages - 1
        # fleet KV fabric traffic (serving/fabric.py): committed
        # prefix pages shipped to / grafted from other replicas —
        # mirrored into the metrics counters and folded into the cost
        # census so transfer bytes sit next to compute bytes
        self._fabric_pages_sent = 0
        self._fabric_bytes_sent = 0
        self._fabric_pages_recv = 0
        self._fabric_bytes_recv = 0
        # overload preemption gate (PADDLE_TPU_PREEMPT, default on)
        self.preempt = resolve_preempt_flag(preempt)
        if self.prefix_cache is not None and self.host_pages > 0:
            self.prefix_cache.set_host_tier(self._host_store_page,
                                            self._host_load_page,
                                            self._host_drop_page,
                                            self._spill_walk)
        self._slot_pages: Dict[int, List[int]] = {}
        self._prefill_cursor: Dict[str, int] = {}
        self._pt_host = np.full((self.num_slots, self.max_pages),
                                TRASH_PAGE, np.int32)
        self._pt_dirty = True
        self._pt_full = None
        self._pt_decode = None
        # per-slot sampling vectors, rebuilt when membership changes
        self._vec_dirty = True
        self._temps = np.ones((self.num_slots,), np.float32)
        self._topk = np.zeros((self.num_slots,), np.int32)
        self._topp = np.ones((self.num_slots,), np.float32)
        self._greedy = np.ones((self.num_slots,), bool)
        self._active = np.zeros((self.num_slots,), bool)
        self._prefill_fns: Dict[int, object] = {}   # chunk bucket -> fn
        self._decode_fn = None
        self._unified_fn = None      # the ONE compiled ragged step
        # counters a model keeps on the device (`STEP_STAT_COUNTERS`
        # names them, `_step_stats()` yields them after a forward
        # pass): the unified step appends them to its `accept` output
        self._step_stat_names = tuple(
            getattr(model, "STEP_STAT_COUNTERS", ()))
        # embeddings-lane epilogue (satellite): a pure-READ batched
        # one-token forward through the model BACKBONE (hidden states,
        # no LM head) that recomputes each retiring embed row's
        # last-position hidden state from its already-written KV
        # pages. Jitted once, lazily; a separate small program like
        # the COW/swap helpers — the unified step's cache_size-1
        # probe is untouched.
        self._embed_fn = None
        # mesh engines: the last unified launch's operand tail, kept
        # so collective_counts() can lower the SAME trace and census
        # its collectives against compiled HLO
        self._unified_args_tail = None
        self._copy_page_fn = None    # COW single-page copy, jitted once
        # host-tier swap programs, each jitted ONCE over traced page
        # ids (the PR 5 COW no-retrace discipline): device->host reads
        # one page's K/V across all layers, host->device writes it back
        self._swap_out_fn = None
        self._swap_in_fn = None
        # liveness hook (serving/http/driver.py): called at every step
        # boundary AND immediately before each compiled launch, so a
        # replica grinding through a long round still beats its
        # watchdog heartbeat. None (the default) costs nothing.
        self.heartbeat_hook = None
        # tokens packed into the compiled call currently in flight
        # (0 between launches): the watchdog scales its grace with
        # this, so a legitimately huge packed step is not condemned
        self.step_tokens_inflight = 0
        # fault-injection hook (serving/faults.py): called with the
        # round's participant request ids right BEFORE each compiled
        # launch; a raise aborts the round with no state mutated. The
        # same hook drives the poison-quarantine bisection probes, so
        # a hook that raises deterministically for one request id IS a
        # poisoned request. None (the default) costs nothing.
        self.step_fault_hook = None
        # observability (serving/obs.py, default on, gated
        # ServingEngine(obs=...) / PADDLE_TPU_OBS): request-lifecycle
        # tracer + per-step flight recorder, fed at the same call
        # sites as ServingMetrics. Pure host bookkeeping — no
        # compiled program changes, obs-on/off is token-identical
        # (serving_bench --obs-ab pins the cost within noise).
        self.obs = (EngineObs(flight_steps=flight_steps,
                              clock=self._clock)
                    if resolve_obs_flag(obs) else None)
        # fleet SLO tracker (serving/slo.py, default on, gated
        # ServingEngine(slo=...) / PADDLE_TPU_SLO="off"|"on"|spec):
        # burn-rate evaluation of TTFT p99 / inter-token p99 /
        # deadline-goodput targets over fast+slow sliding windows,
        # per priority class and per adapter id, fed by the SAME
        # metrics hooks that record the histograms. State transitions
        # land as flight-recorder notes, so incident dumps carry
        # "the SLO was already burning" context. Host-only work —
        # the --obs-ab pin covers its cost.
        slo_cfg = resolve_slo_config(slo)
        self.slo = (SLOTracker(slo_cfg, clock=self._clock,
                               on_transition=self._on_slo_transition,
                               track_adapters=self.adapters is not None)
                    if slo_cfg is not None else None)
        self.metrics.slo = self.slo
        # compiled-step COST CENSUS (serving/slo.py, default "model",
        # gated ServingEngine(cost_census=...) /
        # PADDLE_TPU_COST_CENSUS=off|model|lowered|xla): one record
        # per compiled unified step — FLOPs + bytes accessed of the
        # program capacity — captured AT MOST ONCE per compile
        # (lazily for the XLA-backed sources; the jit dispatch cache
        # is never touched, retrace probes stay at cache_size 1).
        # `achieved_util` = packed tokens / capacity tokens is the
        # census's live numerator on every flight-recorder record.
        self.census_mode = resolve_cost_census(cost_census)
        self._census: Optional[dict] = None
        self._census_captures = 0
        self._census_lock = threading.Lock()
        # megakernel referees, refreshed per packed step and attached
        # to the census on read: the launch-count probe's last TRACED
        # dispatch histogram (registered-op launches per unified step
        # — non-None only after a (re)trace; compiled replays run no
        # Python dispatch) and the fused-vs-unfused modeled page-walk
        # bytes of the last step (count_page_block_reads fused=)
        self._dispatch_counts: Optional[dict] = None
        self._last_walk_bytes: Optional[dict] = None
        self.step_capacity_tokens = self.num_slots * self.chunk_len
        self.metrics.step_capacity_tokens = self.step_capacity_tokens
        # engine step counter (timeline/flight step index) + the
        # running round's token-split stats the flight record reads
        self._step_idx = 0
        self._round_stats = {"prefill_tokens": 0, "decode_tokens": 0,
                             "draft_tokens": 0, "accepted_tokens": 0,
                             "draft_seed_tokens": 0,
                             "reads_saved": 0, "collectives": 0,
                             "constrained_rows": 0,
                             "grammar_rejected": 0, "wall_s": 0.0}
        # host-phase seconds since the last `metrics.on_host_phases`
        # (one flush a round; a spill outside a round waits for the next)
        self._host_phases = collections.defaultdict(int)
        # shutdown latch: flipped by drain()/abort_all(); add_request
        # raises EngineClosed once set
        self._closed = False

    @contextlib.contextmanager
    def _phase(self, span: str, counter: str, **args):
        """One host phase: a span, and its seconds added to `counter`
        of the round's account (an exception leaves the account as it
        was: the round is void)."""
        with RecordEvent(span, **args) as ev:
            yield
        self._host_phases[counter] += ev.elapsed_s

    def _obs_event(self, req: "Request", kind: str, **detail):
        """Record one request-timeline event (no-op with obs off)."""
        if self.obs is not None:
            detail.setdefault("slot", req.slot)
            self.obs.tracer.record(req.request_id, kind,
                                   t=self._clock(),
                                   step=self._step_idx, **detail)

    def _on_slo_transition(self, tr: dict):
        """An SLO series changed alert state: note it in the flight
        recorder's step stream, so an incident dump read at 3am shows
        "SLO was already burning" inline with the steps."""
        if self.obs is not None:
            where = tr["scope"] if not tr["label"] \
                else f"{tr['scope']}:{tr['label']}"
            self.obs.flight.note(
                f"slo:{tr['to']}",
                f"{tr['slo']}[{where}] {tr['from']}->{tr['to']} "
                f"burn fast={tr['fast_burn']} slow={tr['slow_burn']}")

    def _slo_snap(self) -> Optional[dict]:
        return None if self.slo is None else self.slo.snapshot()

    def _dispatch(self, name, *vals):
        """Run a registered op's forward on RAW jnp values, firing the
        launch-count probe exactly like apply_op's traced branch. The
        fused epilogue ops (decode_greedy_argmax, spec_verify_accept)
        run inside the unified trace on bare arrays — no Tensor boxing
        — but they must still land in the per-step dispatch histogram
        the megakernel A/B asserts on."""
        probe = tensor_mod._dispatch_probe
        if probe is not None:
            probe(name)
        return get_op(name).fwd(*vals)

    def cost_census(self) -> Optional[dict]:
        """The compiled-step cost census (None with the gate off):
        FLOPs + bytes accessed of THE one unified program's capacity,
        captured AT MOST ONCE per compiled step — "model" computes
        the analytical estimate immediately, "lowered"/"xla" ask the
        step's HLO/executable cost analysis on first access (AOT
        lower/compile: the jit dispatch cache is untouched, so the
        retrace probes still see cache_size 1). The captured record
        is also pushed into the metrics snapshot for /metrics."""
        if self.census_mode == "off":
            return None
        with self._census_lock:
            if self._census is None:
                self._capture_census()
            # fabric wire traffic rides the census record so transfer
            # bytes sit next to compute bytes-accessed in every dump
            # (cumulative counters, refreshed on each read — the
            # per-compile FLOPs/bytes fields above stay immutable)
            self._census["fabric"] = {
                "pages_sent": self._fabric_pages_sent,
                "bytes_sent": self._fabric_bytes_sent,
                "pages_recv": self._fabric_pages_recv,
                "bytes_recv": self._fabric_bytes_recv,
            }
            # megakernel referees ride the same record (refreshed on
            # read, like the fabric counters): fused vs unfused are
            # bit-identical in floats, so launches and modeled bytes
            # ARE the observable difference
            if self._dispatch_counts is not None:
                self._census["unified_dispatch"] = dict(
                    self._dispatch_counts, megakernel=self.megakernel)
            if self._last_walk_bytes is not None:
                wb = self._last_walk_bytes
                tok = max(1, int(wb["tokens"]))
                self._census["page_walk"] = {
                    "megakernel": self.megakernel,
                    "modeled_step_bytes": {"unfused": wb["unfused"],
                                           "fused": wb["fused"]},
                    "modeled_bytes_per_token": {
                        "unfused": wb["unfused"] / tok,
                        "fused": wb["fused"] / tok},
                }
        self.metrics.cost_census = self._census
        return self._census

    def _capture_census(self):
        """Build the census record (callers hold _census_lock)."""
        cfgm = getattr(self.model, "config", None)
        n_params = sum(int(np.prod(t._value.shape))
                       for t in self._state_tensors)
        param_bytes = sum(
            int(np.prod(t._value.shape))
            * jnp.dtype(t._value.dtype).itemsize
            for t in self._state_tensors)
        fallback = model_cost_census(
            n_params=n_params, param_bytes=param_bytes,
            num_slots=self.num_slots, chunk_len=self.chunk_len,
            max_pages=self.max_pages,
            page_bytes=self.page_bytes,
            n_heads=int(getattr(cfgm, "num_attention_heads",
                                self.n_kv)),
            head_dim=self.head_dim, page_size=self.page_size,
            mp=self.mp)
        self._census = capture_cost_census(
            self.census_mode,
            self._unified_fn if self.unified else None,
            ((self._ct, *self._unified_args_tail)
             if self._unified_args_tail is not None else None),
            capacity_tokens=self.step_capacity_tokens,
            fallback=fallback)
        self._census_captures += 1

    # -- compiled programs -------------------------------------------------
    def _swap_state(self, state_vals):
        return _swap_state(self._state_tensors, state_vals)

    def _restore_state(self, originals):
        _restore_state(self._state_tensors, originals)

    def _unpack(self, ct, pos, page_table, rows=None, **kw):
        """`_unpack_caches`, then each window layer's cache on its ring
        table (`rows`: the one slot a batch-1 program serves)."""
        caches = _unpack_caches(ct, pos, page_table,
                                attn_impl=self.attn_impl,
                                out_shard=self._out_shard, **kw)
        if self.kv_windows:
            ring = self._pt_ring
            if rows is not None:
                ring = jax.lax.dynamic_slice(
                    ring, (rows, jnp.zeros((), jnp.int32)),
                    (1, ring.shape[1]))
            for i in self.kv_windows:
                caches[i].page_table = Tensor(ring)
        return caches

    def _build_prefill(self, bucket: int):
        """Compiled once per chunk BUCKET (not per prompt length): a
        batch-1 forward of `bucket` tokens for one slot, scattering the
        chunk's K/V into the slot's pages at positions start..start+l-1
        and recording the logits of the chunk's last REAL token into the
        held-logits row. Host-side padding of the tail chunk rides on
        the trash-page write redirect, so the padded tokens are inert."""
        model = self.model
        state_vals = self._state_vals

        def prefill(state_vals, ct, pos, last_logits, page_table,
                    tokens, slot, start, new_pos, last_idx):
            originals = self._swap_state(state_vals)
            try:
                z = jnp.zeros((), jnp.int32)
                s = slot.astype(jnp.int32).reshape(())
                pt_row = jax.lax.dynamic_slice(
                    page_table, (s, z), (1, page_table.shape[1]))
                caches = self._unpack(ct, start, pt_row, rows=s)
                logits_t, caches = model(Tensor(tokens), caches=caches)
                v = logits_t._value.shape[-1]
                row = jax.lax.dynamic_slice(
                    logits_t._value, (z, last_idx.astype(jnp.int32), z),
                    (1, 1, v))[:, 0, :].astype(jnp.float32)
                new_ct = _pack_caches(caches)
                pos = jax.lax.dynamic_update_slice(
                    pos, new_pos.astype(jnp.int32).reshape(1), (s,))
                last_logits = jax.lax.dynamic_update_slice(
                    last_logits, row, (s, z))
                return new_ct, pos, last_logits
            finally:
                self._restore_state(originals)

        return _StepProgram(prefill, state_vals, self._mesh)

    def _build_decode(self):
        """ONE fixed-shape step for all slots: sample from held logits
        with per-slot params, batched forward with per-row positions
        through the paged pool."""
        model = self.model
        state_vals = self._state_vals

        def step(state_vals, ct, pos, last_logits, page_table, key,
                 temps, top_k, top_p, greedy, active):
            originals = self._swap_state(state_vals)
            try:
                nxt = _sample_rows(last_logits, key, temps, top_k,
                                   top_p, greedy)
                nxt = jnp.where(active, nxt, 0).astype(jnp.int32)
                caches = self._unpack(ct, pos, page_table)
                last, caches = decode_model_step(model, nxt[:, None],
                                                 caches)
                # only occupied slots advance; free/prefilling rows stay
                # frozen (their writes went to the trash page — the
                # decode page table trash-masks non-DECODE rows)
                new_pos = jnp.where(active, pos + 1, pos)
                return _pack_caches(caches), new_pos, last, nxt
            finally:
                self._restore_state(originals)

        return _StepProgram(step, state_vals, self._mesh)

    def _build_unified(self):
        """THE one compiled ragged prefill+decode+verify step: a
        fixed-shape [S, chunk_len] forward where every row carries its
        own live query count (`q_len` — 1 + granted drafts for
        decoding rows, up to chunk_len for mid-prefill rows, 0 for
        idle/free rows) through the ragged paged-attention op. Decode
        rows first sample their next token from the held logits
        (per-slot params, exactly the old decode step's math), feed it
        at column 0 with any speculative drafts behind it; prefill
        rows feed their prompt chunk. GREEDY ACCEPTANCE of drafts is
        fused into the same trace: draft column i+1 is accepted iff it
        equals the argmax of the logits at column i (the token the
        sequential path would commit next), `accept` is the length of
        the matching prefix, a decode row's pos advances by
        1 + accept (REJECTED drafts roll back — their K/V stays past
        the new pos exactly like padding columns, overwritten before
        it is ever attended), and its held logits come from column
        `accept` so the next step's sample is the model's own
        correction token. Prefill rows keep the PR-6 semantics: pos
        advances by q_len, held logits from the last real column.
        With speculation off decode rows simply ride at q_len 1,
        where accept is 0 by construction — SAME program, same trace,
        zero cost; enabling speculation changes only the host-side
        q_len/tokens values (the retrace probe asserts this). ONE
        trace serves every prefill/decode/verify mix, membership
        change and packing decision (the engine's whole point: the
        per-bucket prefill programs AND the separate decode program
        collapse into this)."""
        model = self.model
        state_vals = self._state_vals

        def ustep(state_vals, ct, pos, last_logits, page_table, tokens,
                  q_len, is_decode, key, temps, top_k, top_p, greedy,
                  group=None, lora=None, gsamp=None, gver=None):
            originals = self._swap_state(state_vals)
            try:
                # grammar mask (build-time gated operand): an additive
                # f32 bias [S, V] — 0 allowed, -1e30 forbidden —
                # applied to the HELD logits right where they feed the
                # sampling epilogue, so the masked greedy argmax and
                # the -inf-before-top_p sampled path fall out of the
                # SAME _sample_rows with zero new ops. The bias never
                # touches `lg`/`row_last`: held logits stay pure model
                # output, and the fresh committed-state mask is
                # re-applied at the NEXT sample site (stale per-path
                # biases must not bank).
                samp_in = (last_logits if gsamp is None
                           else last_logits + gsamp)
                # megakernel epilogue: the greedy argmax over the held
                # logits is a registered fused op (bit-identical
                # first-occurrence tie rule), handed into _sample_rows
                # so greedy rows never recompute it
                argmax0 = (self._dispatch("decode_greedy_argmax",
                                          samp_in)
                           if self.megakernel else None)
                nxt = _sample_rows(samp_in, key, temps, top_k,
                                   top_p, greedy, argmax=argmax0)
                nxt = jnp.where(is_decode, nxt, 0).astype(jnp.int32)
                col0 = (jnp.arange(tokens.shape[1], dtype=jnp.int32)
                        == 0)[None, :]
                toks = jnp.where(is_decode[:, None] & col0,
                                 nxt[:, None], tokens)
                # multi-tenant adapters: gather each row's A/B block
                # from the paged adapter pool by the per-slot page
                # operand — pure data movement inside the one trace,
                # so tenant churn/eviction/restore never retraces.
                # Base-model and idle rows gather the all-zero page 0
                # at scale 0: an exactly-zero delta.
                lora_layers = None
                lora_paged_layers = None
                if lora is not None:
                    apools, apage, ascale = lora
                    if self.megakernel:
                        # megakernel mode: hand each layer the FULL
                        # pools plus the per-row page/scale operands —
                        # the gather happens INSIDE the fused attend
                        # prologue (and lora_delta_paged for the
                        # o-projection), one adapter-page stream per
                        # row instead of one per projection
                        lora_paged_layers = [
                            tuple(layer) + (apage, ascale)
                            for layer in apools]
                    else:
                        lora_layers = [
                            tuple(t[apage] for t in layer) + (ascale,)
                            for layer in apools]
                caches = self._unpack(ct, pos, page_table,
                                      q_len=q_len, group=group,
                                      lora=lora_layers,
                                      lora_paged=lora_paged_layers,
                                      megakernel=self.megakernel)
                logits_t, caches = model(Tensor(toks), caches=caches)
                lg = logits_t._value.astype(jnp.float32)   # [S, W, V]
                # greedy draft verification: column i's argmax is the
                # token sequential decode would commit after column i;
                # accept = longest prefix of draft columns 1..q_len-1
                # matching that chain (cumprod kills everything after
                # the first mismatch). Rows without drafts (q_len 1,
                # prefill, idle) get accept 0 for free.
                # grammar x spec (build-time gated): each verify
                # column's argmax is masked with the automaton state
                # REACHED ALONG THE DRAFTED PATH (host-computed walk),
                # so a grammar-violating draft loses the argmax match
                # and is rejected by this same fused greedy acceptance
                # — no second program. Only `preds` sees the bias;
                # row_last below reads the unbiased lg.
                lg_v = lg if gver is None else lg + gver
                if self.megakernel:
                    # fused acceptance epilogue: the registered op is
                    # the SAME expressions as the inline branch below
                    # (argmax -> prefix match -> cumprod -> mask), so
                    # tokens stay bit-identical; it exists so the
                    # whole accept chain is ONE dispatched op the
                    # launch census can count
                    accept = self._dispatch("spec_verify_accept",
                                            lg_v, toks, q_len,
                                            is_decode)
                else:
                    preds = jnp.argmax(lg_v, axis=-1).astype(jnp.int32)
                    match = (toks[:, 1:] == preds[:, :-1])
                    dcol = jnp.arange(tokens.shape[1] - 1,
                                      dtype=jnp.int32)[None, :]
                    valid = dcol < (q_len - 1)[:, None]
                    accept = jnp.cumprod(
                        jnp.where(match & valid, 1, 0), axis=1
                    ).sum(axis=1).astype(jnp.int32)
                    accept = jnp.where(is_decode, accept, 0)
                last_idx = jnp.where(is_decode, accept,
                                     jnp.maximum(q_len - 1, 0))
                row_last = jnp.take_along_axis(
                    lg, last_idx[:, None, None], axis=1)[:, 0]
                live = (q_len > 0)[:, None]
                new_last = jnp.where(live, row_last, last_logits)
                new_pos = pos + jnp.where(is_decode, 1 + accept,
                                          q_len)
                if self._step_stat_names:
                    # what the model counted on the device rides out
                    # behind `accept`, in the fetch the host makes
                    # anyway
                    accept = jnp.concatenate(
                        [accept, model._step_stats()._value
                         .astype(jnp.int32)])
                return (_pack_caches(caches), new_pos, new_last, nxt,
                        accept)
            finally:
                self._restore_state(originals)

        # operand-tail layout (matches _unified_step's args_tail):
        # the 11 base operands, then — each optional, resolved at
        # trace-build time from the engine's gates — the 3 adapter
        # operands (pool pytree, per-slot page, per-slot scale), the
        # 3 grouped-walk operands, the [S, V] grammar sample bias and
        # (with spec also on) the [S, W, V] grammar verify bias.
        # Adapter pools/pages, groups and grammar masks are DATA next
        # to pos/q_len: churn never retraces, and with the grammar
        # gate OFF the program carries no bias operand at all —
        # byte-identical to a pre-grammar engine.
        lora_on, grouped = self.adapters is not None, self.grouped
        gram_on = self.grammar_on
        gram_ver = self.grammar_on and self.spec is not None

        def call(state_vals, ct, *args):
            base, rest = args[:11], args[11:]
            i = 0
            lora = None
            if lora_on:
                lora = (rest[0], rest[1], rest[2])
                i = 3
            group = None
            if grouped:
                group = tuple(rest[i:i + 3])
                i += 3
            gsamp = gver = None
            if gram_on:
                gsamp = rest[i]
                i += 1
            if gram_ver:
                gver = rest[i]
            return ustep(state_vals, ct, *base, group=group,
                         lora=lora, gsamp=gsamp, gver=gver)
        return _StepProgram(call, state_vals, self._mesh)

    def _build_embed(self):
        """Embeddings-lane epilogue: ONE jitted batched single-token
        forward through the model BACKBONE (hidden states before the
        LM head) against the paged KV. An embed row finished its
        chunked prefill, so positions 0..plen-1 hold committed KV;
        re-feeding the LAST prompt token at pos plen-1 recomputes
        exactly the final position's post-norm hidden state — the
        pooled last-hidden-state — at one token of compute, reusing
        the pages the prefill already wrote. The returned caches are
        DISCARDED (this is a pure read: `self._ct` is never
        reassigned), and non-embed rows ride trash-masked page-table
        rows, so the fixed [S, 1] shape serves any retiring subset
        with zero retrace and zero state mutation."""
        backbone = self._model_backbone()
        state_vals = self._state_vals

        def estep(state_vals, ct, pos, page_table, tokens):
            originals = self._swap_state(state_vals)
            try:
                caches = self._unpack(ct, pos, page_table)
                h, _ = backbone(Tensor(tokens), caches=caches)
                return h._value[:, -1, :].astype(jnp.float32)
            finally:
                self._restore_state(originals)

        return _StepProgram(estep, state_vals, self._mesh)

    def _model_backbone(self):
        """The hidden-state trunk under the causal-LM wrapper (GPT:
        `.gpt`, Llama: `.llama`); falls back to the wrapper itself
        for models that already return hidden states."""
        for attr in ("gpt", "llama", "transformer", "backbone"):
            core = getattr(self.model, attr, None)
            if core is not None and callable(core):
                return core
        return self.model

    def _embed_rows(self, rows):
        """Compute pooled last-hidden-state embeddings for retiring
        embed rows ([(slot, req)]): batched through the one jitted
        epilogue, results stored on each request before retirement."""
        if not rows:
            return
        if self._embed_fn is None:
            self._embed_fn = self._build_embed()
        S = self.num_slots
        tok = np.zeros((S, 1), np.int32)
        pos = np.zeros((S,), np.int32)
        pt = np.full((S, self.max_pages), TRASH_PAGE, np.int32)
        for slot, req in rows:
            tok[slot, 0] = int(req.prefill_ids[-1])
            pos[slot] = int(req.prefill_ids.size) - 1
            pt[slot] = self._pt_host[slot]
        with RecordEvent(SPAN_EMBED):
            h = np.asarray(self._embed_fn(
                self._ct, self._dev(pos), self._dev(pt),
                self._dev(tok)))
        for slot, req in rows:
            req.embedding = h[slot].copy()
            self._obs_event(req, "embed", hidden=int(h.shape[-1]))

    def _build_copy_page(self):
        """ONE compiled single-page pool copy for copy-on-write: src and
        dst page ids are traced scalars, so every COW across every
        layer's K and V pools reuses this one program (no retrace across
        cache hit/miss/eviction transitions). On the int8 pool the
        rowwise SCALE pages copy alongside the code pages — a COW'd
        partial page dequantizes to exactly the floats its source
        held (the None check is pytree-static: still one program)."""
        def cp(ct, src, dst):
            out = []
            for k, v, ks, vs in ct:
                out.append((k.at[dst].set(k[src]),
                            v.at[dst].set(v[src]),
                            ks if ks is None else
                            ks.at[dst].set(ks[src]),
                            vs if vs is None else
                            vs.at[dst].set(vs[src])))
            return tuple(out)
        return jax.jit(cp)

    def _copy_page(self, src: int, dst: int):
        if self._copy_page_fn is None:
            self._copy_page_fn = self._build_copy_page()
        with RecordEvent(SPAN_COW_COPY, src=src, dst=dst):
            self._ct = self._copy_page_fn(self._ct, jnp.int32(src),
                                          jnp.int32(dst))

    def _build_swap_out(self):
        """ONE compiled device->host page read: stacks one page's K and
        V across every layer into a [n_layers, 2, page_size, H, D]
        block — on the int8 pool, PLUS the matching
        [n_layers, 2, page_size, H] scale block (codes without their
        scales are meaningless; the pair is the page). The page id is
        a traced scalar, so every swap-out of every page reuses this
        single program (no retrace ever — the COW-copy discipline).
        int8 pages being half the bytes means swap traffic halves
        too."""
        if self.kv_dtype == "int8":
            def so(ct, src):
                codes = jnp.stack([jnp.stack((k[src], v[src]))
                                   for k, v, _, _ in ct])
                scales = jnp.stack([jnp.stack((ks[src], vs[src]))
                                    for _, _, ks, vs in ct])
                return codes, scales
        else:
            def so(ct, src):
                return jnp.stack([jnp.stack((k[src], v[src]))
                                  for k, v, _, _ in ct])
        return jax.jit(so)

    def _build_swap_in(self):
        """ONE compiled host->device page write: scatters a
        [n_layers, 2, page_size, H, D] block (plus, on the int8 pool,
        its scale block) back into page `dst` of every layer's pools.
        dst is a traced scalar — one trace serves every restore."""
        if self.kv_dtype == "int8":
            def si(ct, codes, scales, dst):
                out = []
                for i, (k, v, ks, vs) in enumerate(ct):
                    out.append((
                        k.at[dst].set(codes[i, 0].astype(k.dtype)),
                        v.at[dst].set(codes[i, 1].astype(v.dtype)),
                        ks.at[dst].set(scales[i, 0]),
                        vs.at[dst].set(scales[i, 1])))
                return tuple(out)
        else:
            def si(ct, data, dst):
                out = []
                for i, (k, v, ks, vs) in enumerate(ct):
                    out.append((
                        k.at[dst].set(data[i, 0].astype(k.dtype)),
                        v.at[dst].set(data[i, 1].astype(v.dtype)),
                        ks, vs))
                return tuple(out)
        return jax.jit(si)

    def _extract_page(self, src: int):
        """Read one device page's KV (all layers) to host RAM: an
        ndarray block, or a (codes, scales) ndarray pair on the int8
        pool (HostPagePool payloads are opaque either way)."""
        if self._swap_out_fn is None:
            self._swap_out_fn = self._build_swap_out()
        self._host_phases["kv_spill_pages_total"] += 1
        with self._phase(SPAN_SPILL, "kv_spill_s_total", page=src):
            out = self._swap_out_fn(self._ct, jnp.int32(src))
            if self.kv_dtype == "int8":
                return (np.asarray(out[0]), np.asarray(out[1]))
            return np.asarray(out)

    def _restore_page(self, data, dst: int):
        """Write one host-RAM page payload back into device page
        `dst`."""
        if self._swap_in_fn is None:
            self._swap_in_fn = self._build_swap_in()
        with RecordEvent(SPAN_RESTORE, page=dst):
            if self.kv_dtype == "int8":
                codes, scales = data
                self._ct = self._swap_in_fn(
                    self._ct, jnp.asarray(codes), jnp.asarray(scales),
                    jnp.int32(dst))
            else:
                self._ct = self._swap_in_fn(self._ct,
                                            jnp.asarray(data),
                                            jnp.int32(dst))

    # -- host tier callbacks (prefix-cache spill) --------------------------
    def _spill_walk(self, need: int):
        """Prefix spill: the cache's walk of its tree for `need` pages,
        in the account of the copies that follow (`_extract_page`)."""
        return self._phase(SPAN_SPILL, "kv_spill_s_total", need=need)

    def _host_store_page(self, page: int):
        """Prefix spill: copy a parked page's KV to the host tier;
        returns the host slot (the cache then swap_out's the device
        page) or None when the tier is full."""
        return self.host_pool.store(self._extract_page(page))

    def _host_load_page(self, host_slot: int):
        """Prefix restore: swap a spilled page back into a freshly
        allocated device page, handed back PARKED (cache-resident) so
        the cache's retain path treats it like any other tree page.
        Under pressure another LRU parked page is SPILLED to make room
        (the in-progress match is retained, so it can never be the one
        displaced, and a spill never drops a host copy — unlike evict,
        which could tear down the very node being restored); None when
        no page can be freed — the match simply stops and the tail
        prefills."""
        pages = self.pool.alloc(1)
        if pages is None and self.prefix_cache is not None \
                and self.prefix_cache.spill(1) >= 1:
            pages = self.pool.alloc(1)
        if pages is None:
            return None
        self._restore_page(self.host_pool.load(host_slot), pages[0])
        self.host_pool.free(host_slot)
        self.pool.swapped_restored(1, spill=True)
        self.pool.release(pages)
        self.pool.park(pages)
        self.metrics.on_swap_in(1, 0.0)
        return pages[0]

    def _host_drop_page(self, host_slot: int):
        """A spilled page was evicted from the tree while on host."""
        self.host_pool.free(host_slot)
        self.pool.drop_swapped(1, spill=True)

    # -- fleet KV fabric (serving/fabric.py) -------------------------------
    @property
    def fabric_geometry(self) -> dict:
        """The page geometry a transfer frame must match to be
        graftable here: pages are raw pool blocks, so every axis has
        to agree bit-for-bit."""
        return {"kv_dtype": self.kv_dtype,
                "page_size": self.page_size,
                "n_layers": self.n_layers, "n_kv": self.n_kv,
                "head_dim": self.head_dim}

    def _fabric_fp_dtype(self):
        """The fp/fp8 pool element dtype a frame's blob reinterprets
        as on this engine (int8 frames never need it)."""
        return FP8_DTYPE if self.kv_dtype == "fp8" else \
            np.dtype(self._fp)

    def _fabric_alloc_restore(self, payload):
        """graft/load callback: allocate one device page (spilling a
        parked LRU page to the host tier under pressure — never
        EVICTING, which could tear down the very chain being grafted),
        write the payload into it, hand it back PARKED. None = no
        page; the graft stops cleanly at that depth."""
        pages = self.pool.alloc(1)
        if pages is None and self.prefix_cache is not None \
                and self.prefix_cache.spill(1) >= 1:
            pages = self.pool.alloc(1)
        if pages is None:
            return None
        self._restore_page(payload, pages[0])
        self.pool.release(pages)
        self.pool.park(pages)
        return pages[0]

    def export_prefix_frame(self, tokens, adapter_id: int = 0
                            ) -> Optional[bytes]:
        """Serialize the committed page chain covering `tokens` into
        one transfer frame (None when the tree holds no full page of
        it, or the cache is off). Device pages are read with the same
        swap-out program the host tier uses; spilled pages ship
        straight from host RAM without a device round-trip. Called
        between steps via EngineDriver.call, like every page-table
        touch."""
        if self.prefix_cache is None:
            return None
        depth, refs = self.prefix_cache.collect_chain(
            tokens, adapter_id)
        if depth <= 0:
            return None
        payloads = [self._extract_page(ref) if kind == "page"
                    else self.host_pool.load(ref)
                    for kind, ref in refs]
        tok = np.ascontiguousarray(
            np.asarray(tokens).reshape(-1)[:depth], dtype=np.int64)
        frame = encode_frame(
            kv_dtype=self.kv_dtype, page_size=self.page_size,
            n_layers=self.n_layers, n_kv=self.n_kv,
            head_dim=self.head_dim, tokens=tok, payloads=payloads,
            valid=depth, adapter_id=adapter_id,
            fp_itemsize=(1 if self.kv_dtype in ("int8", "fp8")
                         else jnp.dtype(self._fp).itemsize))
        self._fabric_pages_sent += len(payloads)
        self._fabric_bytes_sent += len(frame)
        self.metrics.on_fabric(sent_pages=len(payloads),
                               sent_bytes=len(frame))
        self.obs.flight.note(
            "fabric:send",
            f"{len(payloads)}p/{depth}tok/{len(frame)}B "
            f"adapter={adapter_id} dtype={self.kv_dtype}")
        return frame

    def import_prefix_frame(self, frame: bytes) -> int:
        """Graft a transfer frame from another replica into this
        engine's tree so the very next admission hits it. The frame's
        geometry header must match `fabric_geometry` exactly — a
        mismatched frame is rejected whole, never half-grafted.
        Returns pages actually grafted (spans already cached cost
        nothing)."""
        if self.prefix_cache is None:
            return 0
        header = frame_header(frame)
        for key, want in self.fabric_geometry.items():
            if header.get(key) != want:
                raise ValueError(
                    f"fabric frame geometry mismatch: {key}="
                    f"{header.get(key)!r}, this engine has {want!r}")
        _, tokens, payloads = decode_frame(
            frame, fp_dtype=self._fabric_fp_dtype())
        grafted = self.prefix_cache.graft(
            tokens, payloads, int(header["valid"]),
            int(header["adapter_id"]),
            alloc_restore=self._fabric_alloc_restore)
        self._fabric_pages_recv += grafted
        self._fabric_bytes_recv += len(frame)
        self.metrics.on_fabric(recv_pages=grafted,
                               recv_bytes=len(frame))
        self.obs.flight.note(
            "fabric:recv",
            f"{grafted}/{header['n_pages']}p grafted "
            f"{len(frame)}B adapter={header['adapter_id']}")
        return grafted

    def export_prefix_state(self) -> Optional[dict]:
        """The whole radix tree — structure + page payloads, device
        AND host tier — as one host-side record, for warm restarts
        (the router snapshots a drained replica before teardown)."""
        if self.prefix_cache is None:
            return None
        snap = self.prefix_cache.snapshot(
            self._extract_page, self.host_pool.load)
        snap["geometry"] = self.fabric_geometry
        self.obs.flight.note(
            "fabric:snapshot", f"{len(snap['nodes'])} nodes")
        return snap

    def import_prefix_state(self, snap: Optional[dict]) -> int:
        """Warm-start this engine from a predecessor's
        `export_prefix_state` record (geometry must match; pages that
        no longer fit are dropped with their subtrees). Returns pages
        restored."""
        if snap is None or self.prefix_cache is None:
            return 0
        geo = snap.get("geometry")
        if geo is not None and dict(geo) != self.fabric_geometry:
            raise ValueError(
                f"prefix snapshot geometry {geo} does not match "
                f"this engine ({self.fabric_geometry})")
        restored = self.prefix_cache.load(
            snap, alloc_restore=self._fabric_alloc_restore)
        self.metrics.on_fabric(restored_pages=restored)
        self.obs.flight.note(
            "fabric:restore",
            f"{restored}/{len(snap['nodes'])} pages warm")
        return restored

    def _beat(self):
        hook = self.heartbeat_hook
        if hook is not None:
            hook()

    # -- request intake ----------------------------------------------------
    @staticmethod
    def _budget_new(sampling: SamplingParams) -> int:
        """Generated-token budget a request reserves KV for: embed
        rows run prefill-only and retire at cursor end, so their page
        budget covers the prompt alone (max_new_tokens is ignored —
        the token-budget packing math is unchanged either way)."""
        return (0 if getattr(sampling, "embed", False)
                else sampling.max_new_tokens)

    def add_request(self, prompt_ids, sampling: Optional[SamplingParams]
                    = None, request_id: Optional[str] = None,
                    on_token=None) -> Request:
        if self._closed:
            raise EngineClosed(
                "engine is draining/closed; no new requests admitted")
        sampling = sampling or SamplingParams()
        if isinstance(prompt_ids, Tensor):
            prompt_ids = prompt_ids.numpy()
        prompt = np.asarray(prompt_ids).reshape(-1)
        if prompt.size >= self.max_len:
            raise ValueError(
                f"prompt length {prompt.size} >= engine max_len "
                f"{self.max_len}")
        if prompt.size + self._budget_new(sampling) > self.max_len:
            raise ValueError(
                f"prompt_len {prompt.size} + max_new_tokens "
                f"{sampling.max_new_tokens} exceeds engine max_len "
                f"{self.max_len}; lower max_new_tokens or grow the "
                "engine's cache")
        if getattr(sampling, "grammar", None) is not None \
                and not self.grammar_on:
            raise ValueError(
                "request carries a grammar constraint but this "
                "engine's grammar gate is off (enable it via "
                "ServingEngine(grammar=True) / PADDLE_TPU_GRAMMAR=on)")
        if getattr(sampling, "embed", False) and not self.unified:
            raise ValueError(
                "the embeddings lane rides the unified ragged step's "
                "prefill packing (set unified=True / "
                "PADDLE_TPU_UNIFIED_STEP=on)")
        need = pages_needed(prompt.size, self._budget_new(sampling),
                            self.page_size)
        if need > self.num_pages - 1:
            raise ValueError(
                f"request needs {need} pages but the pool only has "
                f"{self.num_pages - 1} allocatable pages; grow "
                "num_pages or lower max_new_tokens")
        aid = int(getattr(sampling, "adapter_id", 0) or 0)
        if aid != BASE_ADAPTER:
            if self.adapters is None:
                raise ValueError(
                    f"request carries adapter_id {aid} but this "
                    "engine has no adapter subsystem (enable it via "
                    "ServingEngine(adapters=True) / "
                    "PADDLE_TPU_ADAPTERS=on and register the "
                    "adapter first)")
            if not self.adapters.known(aid):
                raise ValueError(
                    f"unknown adapter_id {aid}: register the adapter "
                    "on this engine's AdapterStore before submitting "
                    "requests under it")
        if request_id is None:
            request_id = f"req-{next(self._id_counter)}"
        if request_id in self._requests:
            raise ValueError(f"duplicate request_id {request_id!r}")
        req = Request(request_id, prompt, sampling, on_token=on_token,
                      arrival_t=self._clock())
        self.scheduler.submit(req)     # may shed load (max_queue)
        self._requests[request_id] = req
        self.metrics.on_submit(req)
        if self.adapters is not None:
            self.metrics.on_adapter_request(aid)
        if getattr(sampling, "grammar", None) is not None:
            self.metrics.on_grammar_request()
        self._obs_event(req, "submit", prompt_len=int(prompt.size),
                        priority=int(sampling.priority),
                        queue_depth=self.scheduler.queue_depth)
        return req

    def cancel(self, request_id: str) -> bool:
        """Mark a request cancelled. Queued requests drop immediately;
        a running one (prefilling or decoding) is evicted at the next
        step boundary and its pages return to the pool."""
        req = self._requests.get(request_id)
        if req is None or req.finished:
            return False
        if req.state in (RequestState.QUEUED, RequestState.PREEMPTED):
            self.scheduler.drop_queued(req)
            # the shared terminal path: releases host-tier KV, retires
            # the id, closes any span — a queued cancel used to leave
            # its _requests entry behind, permanently blocking id reuse
            self._finish_and_free(req, "cancelled", self._clock(), [])
            return True
        req.state = RequestState.CANCELLED
        return True

    def _dev(self, x):
        """Host array -> device step operand: committed REPLICATED on
        the mesh (page tables, tokens, q_len, sampling vectors — the
        control plane never shards), plain jnp.asarray without one.
        Committed placement keeps the jit cache key stable, so the
        one-trace discipline holds on the mesh too."""
        if self.tp is not None:
            return self.tp.replicate(np.asarray(x))
        return jnp.asarray(x)

    # -- page-table device views -------------------------------------------
    def _page_tables(self):
        """(full, decode) device page tables. The decode variant points
        every non-DECODE row at the trash page so the fixed-shape
        decode scatter can't touch a mid-prefill slot's live pages."""
        if self._pt_dirty or self._pt_full is None:
            self._pt_full = self._dev(self._pt_host)
            self._pt_decode = self._dev(
                np.where(self._active[:, None], self._pt_host,
                         TRASH_PAGE).astype(np.int32))
            self._pt_dirty = False
        return self._pt_full, self._pt_decode

    # -- step boundary: retire / admit / prefill / decode ------------------
    def _finalize_request(self, req: Request, *, keep_id: bool = False):
        """The ONE host-side cleanup every path that takes a request
        off a slot/queue must run: drop its prefill cursor, drafter
        and grammar, and retire its id from `_requests` unless it
        stays live (`keep_id=True` — the preemption path: a preempted
        request resumes under the same id and must keep its
        duplicate-id guard). A request's residency is no profiler
        span (it crosses rounds, so it cannot nest in a trace): its
        timeline is `obs.RequestTracer`'s, joined to the trace by the
        step index that `serving::round` carries."""
        self._prefill_cursor.pop(req.request_id, None)
        self._drafters.pop(req.request_id, None)
        self._grammars.pop(req.request_id, None)
        if not keep_id:
            self._requests.pop(req.request_id, None)

    def _finish_and_free(self, req: Request, reason: str, now: float,
                         finished: List[RequestOutput]):
        self._obs_event(req, _TERMINAL_EVENT.get(reason, reason),
                        cause=reason, tokens=len(req.output_tokens))
        if req.slot is not None:
            slot = req.slot
            self.scheduler.retire(slot)
            self._active[slot] = False
            self._vec_dirty = True
            pages = self._slot_pages.pop(slot, None)
            if pages:
                self._retire_pages(req, reason, pages)
            if self._draft is not None:
                # draft KV is recomputable — every slot-freeing path
                # just drops the pages (no host tier, no cache insert)
                self._draft.release(slot)
            req.pages = None
            req._prefix_grant = None
            self._pt_host[slot, :] = TRASH_PAGE
            self._pt_dirty = True
            self._apage[slot] = 0
            self._ascale[slot] = 0.0
            self._slot_adapter.pop(slot, None)
        if req._adapter_held:
            # drop the adapter reference: nobody else using it parks
            # it hot in the pool (the next tenant request pays zero)
            self.adapters.release(
                int(getattr(req.sampling, "adapter_id", 0) or 0))
            req._adapter_held = False
        self._release_swap(req)   # preempted-and-never-resumed cleanup
        # retire the id: duplicate detection guards LIVE requests only,
        # and a router re-placing a migrated request may legitimately
        # reuse its id on this engine later (also caps _requests growth
        # over a long-running server's lifetime)
        self._finalize_request(req)
        req._finish(reason, now)
        self.metrics.on_finish(req, now)
        finished.append(req.output())

    def _retire_pages(self, req: Request, reason: str,
                      pages: List[int]):
        """Route a retiring request's pages: without the prefix cache
        they return to the pool; with it, a normally finished request's
        written pages are INSERTED into the radix tree (multi-turn
        follow-ups re-sending prompt + completion hit them), everything
        else just drops its references — shared pages stay resident for
        their other holders, private ones free."""
        if self.prefix_cache is None:
            self.pool.free(pages)
            return
        if reason in ("stop", "length"):
            # every emitted token's KV was written by the decode step
            # that sampled it, so prompt + output positions are valid
            aid = int(getattr(req.sampling, "adapter_id", 0) or 0)
            seq = np.concatenate([
                req.prompt_ids.astype(np.int64),
                np.asarray(req.output_tokens, np.int64)])
            self.prefix_cache.insert(
                seq, pages,
                req.prompt_ids.size + len(req.output_tokens),
                adapter_id=aid)
            # session pinning: a `session=` request's inserted nodes
            # get a TTL tier above LRU — the conversation's next turn
            # hits warm KV by contract, not by eviction luck
            if getattr(req.sampling, "session", None):
                self.prefix_cache.pin(seq, self.session_ttl_s,
                                      adapter_id=aid)
        else:
            self.prefix_cache.release(pages)

    def _evict(self, now: float, finished: List[RequestOutput]):
        # fail-fast 504: a queued request whose PLACEMENT deadline
        # passed can no longer be served in time — fail it now instead
        # of letting it burn a queue position (overload semantics)
        for req in self.scheduler.deadline_expired(now):
            self.scheduler.drop_queued(req)
            req.error = DeadlineExceeded(
                f"request {req.request_id} missed its placement "
                f"deadline ({req.sampling.deadline_s}s) while queued")
            self._finish_and_free(req, "deadline", now, finished)
            if self.obs is not None:
                # 504 fail-fast: freeze the ring so the postmortem
                # shows what the engine was doing while it starved
                self.obs.flight.incident(
                    "deadline", detail=req.request_id,
                    step=self._step_idx, slo=self._slo_snap())
        for req in self.scheduler.expired(now):
            if req.state in (RequestState.QUEUED,
                             RequestState.PREEMPTED):
                self.scheduler.drop_queued(req)
            self._finish_and_free(req, "timeout", now, finished)
        for req in self.scheduler.cancelled_running():
            self._finish_and_free(req, "cancelled", now, finished)

    def _reserve(self, req: Request) -> bool:
        """Page-aware admission (scheduler callback): grant the slot
        only if the request's WHOLE page budget is available right now —
        otherwise the queue head waits (ordered head-of-line
        backpressure) and nobody behind it can starve it by stealing
        pages. A blocked head is no longer the end of the story: the
        step boundary may PREEMPT a strictly lower-priority resident
        on its behalf (see `_preempt_for_overload`). With the prefix
        cache, "available" is match-then-reserve: the prompt's cached
        prefix attaches shared pages (no fresh allocation for them)
        and LRU cached pages are spilled to the host tier / evicted
        before the head is held back, so backpressure only fires when
        genuinely referenced pages exhaust the pool. A PREEMPTED
        request re-admits through `_reserve_resume` (swap-in) instead.

        With the adapter subsystem on, the request's LoRA adapter is
        claimed FIRST (made device-resident in the paged adapter
        pool, one reference taken — eviction can never touch it while
        this request runs); an adapter pool full of slot-referenced
        adapters refuses exactly like KV page pressure, and a KV
        refusal releases the adapter claim (it parks hot)."""
        aid = int(getattr(req.sampling, "adapter_id", 0) or 0)
        if self.adapters is not None:
            binding = self.adapters.acquire(aid)
            if binding is None:
                return False     # every adapter page is referenced
            req._adapter_binding = binding
            req._adapter_held = True
        ok = (self._reserve_resume(req) if req._swap is not None
              else self._reserve_kv(req))
        if not ok and req._adapter_held:
            self.adapters.release(aid)
            req._adapter_held = False
        return ok

    def _reserve_kv(self, req: Request) -> bool:
        """The KV-page half of `_reserve` (fresh admission)."""
        aid = int(getattr(req.sampling, "adapter_id", 0) or 0)
        if self.prefix_cache is None:
            pages = self.pool.alloc(pages_needed(
                req.prompt_ids.size, self._budget_new(req.sampling),
                self.page_size))
            if pages is None:
                return False
            req.pages = pages
            return True
        grant = self.prefix_cache.acquire(
            req.prompt_ids, self._budget_new(req.sampling),
            adapter_id=aid)
        if grant is None:
            return False
        req.pages = grant.pages
        req.cached_tokens = grant.cached_len
        req._prefix_grant = grant
        return True

    def _reserve_resume(self, req: Request) -> bool:
        """Re-admission of a PREEMPTED request: allocate its full page
        budget for the committed sequence (prompt + banked tokens),
        prefix-matching it against the radix tree when the cache is on
        (the shared prefix released at preemption usually re-attaches
        for free), then plan which host-tier pages swap back into
        which page-table positions. The actual device restores run in
        `_admit` (`_apply_swap_in`); refusal leaves the host copy and
        the queue position untouched — the request just keeps
        waiting."""
        swap = req._swap
        seq = req.prefill_ids
        remaining = (self._budget_new(req.sampling)
                     - len(req.output_tokens))
        ps = self.page_size
        if self.prefix_cache is not None:
            grant = self.prefix_cache.acquire(
                seq, remaining,
                adapter_id=int(getattr(req.sampling, "adapter_id", 0)
                               or 0))
            if grant is None:
                return False
            pages = grant.pages
            m_full = grant.matched_full_pages
            match_cov = grant.cached_len
        else:
            pages = self.pool.alloc(
                pages_needed(seq.size, remaining, ps))
            if pages is None:
                return False
            grant, m_full, match_cov = None, 0, 0
        # plan the restores: host slot j holds page-table index
        # swap.base + j. Indices below the fresh match are shared tree
        # pages that already hold the identical KV (never write
        # through them — drop the redundant host copy); indices at or
        # past it restore into the grant's private fresh pages. The
        # window only extends coverage if it is CONTIGUOUS with the
        # match (m_full >= base); a tree that shrank underneath us
        # leaves a gap, and the gap's tail must re-prefill instead.
        swap.restores, swap.drops = [], []
        cov = match_cov
        if m_full >= swap.base:
            end = min(swap.kv_len,
                      (swap.base + len(swap.host_slots)) * ps)
            for j, host_slot in enumerate(swap.host_slots):
                idx = swap.base + j
                if idx < m_full:
                    swap.drops.append(host_slot)
                else:
                    swap.restores.append((host_slot, pages[idx]))
            if swap.restores and end > m_full * ps:
                # restored pages supersede any partial-page COW the
                # match planned at index m_full: cancel the copy (its
                # content is a strict prefix of the restored page)
                if grant is not None and grant.cow_src is not None:
                    self.prefix_cache.cow_done(grant)
                    grant.cow_dst = None
                    cov = max(m_full * ps, end)
                else:
                    cov = max(match_cov, end)
        else:
            swap.drops = list(swap.host_slots)
        req.pages = pages
        req._prefix_grant = grant
        req.cached_tokens = min(cov, seq.size - 1)
        return True

    def _release_swap(self, req: Request):
        """Discard a preempted request's host-tier KV (it died before
        resuming: cancel / timeout / abort / replica death)."""
        swap = req._swap
        if swap is None:
            return
        for host_slot in swap.host_slots:
            self.host_pool.free(host_slot)
        if swap.host_slots:
            self.pool.drop_swapped(len(swap.host_slots))
        req._swap = None

    def _apply_swap_in(self, req: Request):
        """Execute the restore plan `_reserve_resume` made: swap each
        surviving host page back into its freshly allocated device
        page and release the redundant ones."""
        swap = req._swap
        t0 = time.perf_counter()
        for host_slot, dst in swap.restores:
            self._restore_page(self.host_pool.load(host_slot), dst)
            self.host_pool.free(host_slot)
        if swap.restores:
            self.pool.swapped_restored(len(swap.restores))
        for host_slot in swap.drops:
            self.host_pool.free(host_slot)
        if swap.drops:
            self.pool.drop_swapped(len(swap.drops))
        req._swap = None
        self.metrics.on_swap_in(len(swap.restores),
                                time.perf_counter() - t0)

    # -- preemption (graceful overload degradation) ------------------------
    def _preempt(self, slot: int, req: Request, now: float):
        """Preempt one resident: bank its committed tokens (the stream
        object stays live — the client notices nothing but a gap),
        swap its private KV pages to the host tier (whole-page copies
        through the one compiled swap program), release its shared
        prefix pages back to the tree, free the slot, and requeue it
        by its ORIGINAL arrival key. Resume is `_reserve_resume` +
        `_apply_swap_in`: pos restored from the swapped pages, held
        logits regenerated by re-prefilling the last committed token,
        the drafter re-created from the banked history — greedy output
        provably identical to never having been preempted."""
        pages = self._slot_pages.pop(slot)
        self.scheduler.retire(slot)
        self._active[slot] = False
        self._vec_dirty = True
        self._pt_host[slot, :] = TRASH_PAGE
        self._pt_dirty = True
        if self._draft is not None:
            # draft pages drop outright (no swap — recomputable);
            # resume re-seeds from the banked history via the spare
            # budget, so a preempted stream pays zero dedicated steps
            self._draft.release(slot)
        if req._adapter_held:
            # the adapter reference drops with the slot (the pool may
            # evict/spill it while the request waits); resume
            # re-acquires through the normal reserve path
            self.adapters.release(
                int(getattr(req.sampling, "adapter_id", 0) or 0))
            req._adapter_held = False
        self._apage[slot] = 0
        self._ascale[slot] = 0.0
        self._slot_adapter.pop(slot, None)
        # committed KV: a decode row holds prompt + every emitted
        # token; a mid-prefill row exactly its prefill cursor
        if req.state is RequestState.DECODE:
            kv_len = int(req.prompt_ids.size) + len(req.output_tokens)
        else:
            kv_len = int(self._prefill_cursor.get(req.request_id, 0))
        # keep_id: the preempted request is still live under its id
        self._finalize_request(req, keep_id=True)
        grant = req._prefix_grant
        base = grant.matched_full_pages if grant is not None else 0
        shared, private = pages[:base], pages[base:]
        if shared:
            self.prefix_cache.release(shared)
        n_kv = -(-kv_len // self.page_size)
        n_keep = max(0, min(n_kv - base, len(private)))
        host_slots = []
        for p in private[:n_keep]:
            host_slot = self.host_pool.store(self._extract_page(p))
            if host_slot is None:
                break        # host tier full: the tail recomputes
            host_slots.append(host_slot)
        kept = private[:len(host_slots)]
        if kept:
            self.pool.swap_out(kept)
        rest = private[len(host_slots):]
        if rest:
            self.pool.free(rest)
        req._swap = _SwapHandle(host_slots, base, kv_len)
        req._resume_ids = np.concatenate(
            [req.prompt_ids.astype(np.int64),
             np.asarray(req.output_tokens, np.int64)])
        req.pages = None
        req._prefix_grant = None
        req.slot = None
        req.state = RequestState.PREEMPTED
        req.preemptions += 1
        self.scheduler.requeue(req)
        self.metrics.on_preempt(len(kept))
        self._obs_event(req, "preempt", slot=slot, cause="overload",
                        pages=len(kept), kv_len=kv_len,
                        tokens=len(req.output_tokens))

    def _preempt_for_overload(self, now: float):
        """The overload policy: after admission, a still-queued head
        means backpressure — but if a STRICTLY lower-priority resident
        exists, refusal is the wrong answer. Preempt the least
        important resident, re-run admission, and repeat while the
        (possibly new) head keeps outranking someone. Strict priority
        ordering makes thrash impossible: equal-priority traffic never
        preempts itself, and a preempted request can only be displaced
        again by somebody strictly more important."""
        if not self.preempt:
            return
        for _ in range(self.num_slots):
            head = self.scheduler.peek_queued()
            if head is None:
                break
            victim = self.scheduler.preemption_victim(head)
            if victim is None:
                break
            self._preempt(victim[0], victim[1], now)
            self._admit(now)

    def _admit(self, now: float):
        for slot, req in self.scheduler.assign(reserve=self._reserve):
            req.state = RequestState.PREFILL
            req.admitted_t = now
            self._slot_pages[slot] = req.pages
            self._pt_host[slot, :] = TRASH_PAGE
            self._pt_host[slot, :len(req.pages)] = req.pages
            self._pt_dirty = True
            if self.adapters is not None:
                # the slot's adapter operands: pool page + LoRA scale
                # (page is stable while the slot holds its reference)
                page, scale = req._adapter_binding
                self._apage[slot] = page
                self._ascale[slot] = scale
                aid = int(getattr(req.sampling, "adapter_id", 0) or 0)
                if aid != BASE_ADAPTER:
                    self._slot_adapter[slot] = aid
            self._obs_event(req, "admit", pages=len(req.pages or ()),
                            cached_tokens=int(req.cached_tokens),
                            resumed=req._swap is not None)
            # preemption resume: swap the banked KV pages back in from
            # the host tier before any prefill touches the slot
            if req._swap is not None:
                n_restore = len(req._swap.restores)
                self._apply_swap_in(req)
                self._obs_event(req, "swap_in", pages=n_restore)
            # the slot's write position starts at the first uncached
            # token (0 on a prefix miss): the unified step reads it as
            # the row's pos; the old path's prefill program passes the
            # cursor explicitly and overwrites pos itself
            self._pos = self._pos.at[slot].set(req.cached_tokens)
            # prefix-cache hit: the matched span's KV is already in the
            # attached pages — prefill starts at the first uncached
            # token. A mid-page match first copies the shared partial
            # page into the request's private one (copy-on-write): a
            # shared page is never written through.
            grant = req._prefix_grant
            if grant is not None and grant.cow_src is not None:
                self._copy_page(grant.cow_src, grant.cow_dst)
                self.prefix_cache.cow_done(grant)
            self._prefill_cursor[req.request_id] = req.cached_tokens
            # speculative decoding: one drafter PER REQUEST, seeded by
            # nothing but the token history it is shown each step — a
            # migrated stream's prompt already carries its banked
            # emitted history, so re-seeding is automatic. Only greedy
            # requests speculate (sampled rows would need rejection
            # sampling to stay unbiased).
            if self.spec is not None and req.sampling.greedy:
                drafter = self.spec.make_drafter()
                self._drafters[req.request_id] = drafter
                if (self._draft is not None
                        and isinstance(drafter, ModelDrafter)):
                    # reserve the slot's draft page budget (the same
                    # prompt+max_new bound the target reserved, so
                    # draft writes can never leave the slot's pages).
                    # Refusal = draft-pool pressure: the slot simply
                    # doesn't model-draft until pages free up —
                    # retried each propose, never a correctness event
                    self._draft.admit(slot,
                                      int(req.prompt_ids.size),
                                      self._budget_new(req.sampling))
            # grammar automaton: one per constrained request, the
            # drafter lifecycle — nothing device-side banks grammar
            # state. Re-seeding replays the committed OUTPUT history:
            # after preemption that is req.output_tokens; after a
            # mid-stream migration the banked output arrived as the
            # tail of the new PROMPT, which sampling.grammar_prefix
            # counts (the router bumps it at re-placement).
            if self.grammar_on and \
                    getattr(req.sampling, "grammar", None) is not None:
                self._ensure_last_logits(req)
                g = req.sampling.grammar.make(
                    int(self._last_logits.shape[-1]))
                eos = req.sampling.eos_token_id
                k = int(getattr(req.sampling, "grammar_prefix", 0)
                        or 0)
                replay = list(req.prompt_ids[-k:]) if k else []
                replay.extend(req.output_tokens)
                for t in replay:
                    if eos is None or int(t) != eos:
                        g.advance(int(t))
                self._grammars[req.request_id] = g
            self.metrics.on_admit(req, self._clock())

    def _ensure_last_logits(self, req: Request):
        if self._last_logits is not None:
            return
        vocab = int(getattr(getattr(self.model, "config", None),
                            "vocab_size", 0))
        if not vocab:
            # probe: one eager forward row tells us V
            lg = self.model(Tensor(jnp.asarray(
                req.prompt_ids[None, :1], jnp.int32)))
            vocab = int(lg.shape[-1])
        self._last_logits = jnp.zeros((self.num_slots, vocab),
                                      jnp.float32)
        if self.tp is not None:
            self._last_logits = self.tp.replicate(self._last_logits)

    def _advance_prefills(self, suppress=frozenset()) -> int:
        """One chunk for EACH mid-prefill slot, then back to decode —
        the interleave that keeps long prompts from stalling resident
        decodes for more than one chunk. Slots in `suppress` idle
        (quarantine probes). Returns chunks run."""
        chunks = 0
        for slot, req in sorted(self.scheduler.running.items()):
            if req.state is not RequestState.PREFILL \
                    or slot in suppress:
                continue
            if self.step_fault_hook is not None:
                self.step_fault_hook([req.request_id])
            self._prefill_chunk(slot, req)
            chunks += 1
            if self._prefill_cursor[req.request_id] >= \
                    req.prefill_ids.size:
                self._prefill_cursor.pop(req.request_id, None)
                req.state = RequestState.DECODE
                self._active[slot] = True
                self._vec_dirty = True
                self._pt_dirty = True    # row goes live for decode
                self._obs_event(req, "decode")
        return chunks

    def _prefill_chunk(self, slot: int, req: Request):
        plen = int(req.prefill_ids.size)
        cursor = self._prefill_cursor[req.request_id]
        bucket = chunk_bucket(plen - cursor, self.chunk_len,
                              self.MIN_CHUNK)
        fn = self._prefill_fns.get(bucket)
        if fn is None:
            fn = self._prefill_fns[bucket] = self._build_prefill(bucket)
        self._ensure_last_logits(req)
        real = min(plen - cursor, bucket)
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :real] = req.prefill_ids[cursor:cursor + real]
        pt_full, _ = self._page_tables()
        self.step_tokens_inflight = int(bucket)
        self._beat()
        with RecordEvent(SPAN_PREFILL, slot=slot, cursor=cursor,
                         bucket=bucket):
            self._ct, self._pos, self._last_logits = fn(
                self._ct, self._pos, self._last_logits, pt_full,
                self._dev(tokens), jnp.int32(slot),
                self._dev(np.asarray([cursor], np.int32)),
                jnp.int32(cursor + real), jnp.int32(real - 1))
        self.step_tokens_inflight = 0
        self._beat()
        self._prefill_cursor[req.request_id] = cursor + real
        self.metrics.on_prefill_chunk(real)
        self._round_stats["prefill_tokens"] += real
        if self.tp is not None:
            self._round_stats["collectives"] += \
                self.tp.step_collectives(self.n_layers)
        self._obs_event(req, "prefill_chunk", tokens=real,
                        cursor=cursor + real)

    def _refresh_vectors(self):
        for s in range(self.num_slots):
            req = self.scheduler.running.get(s)
            if req is None:
                self._temps[s], self._topk[s] = 1.0, 0
                self._topp[s], self._greedy[s] = 1.0, True
                continue
            sp = req.sampling
            self._temps[s] = sp.temperature
            self._topk[s] = sp.top_k or 0
            self._topp[s] = sp.top_p if sp.top_p is not None else 1.0
            self._greedy[s] = sp.greedy
        self._vec_dirty = False

    def _decode(self, now_fn, finished: List[RequestOutput],
                suppress=frozenset()):
        if self._decode_fn is None:
            self._decode_fn = self._build_decode()
        if self._vec_dirty:
            self._refresh_vectors()
        # quarantine probes suppress slots: deactivate them for this
        # ONE invocation (their writes trash-mask, pos freezes) and
        # afterwards restore both the active flags and their held
        # logits rows (the decode program recomputes the whole [S, V]
        # block; a suppressed row's output is garbage it must not keep)
        saved_logits = self._last_logits
        saved_active = self._active.copy() if suppress else None
        if suppress:
            for s in suppress:
                self._active[s] = False
            self._pt_dirty = True
        ran = False
        try:
            if not self._active.any():
                return
            if self.step_fault_hook is not None:
                ids = [r.request_id for s, r in
                       sorted(self.scheduler.running.items())
                       if r.state is RequestState.DECODE
                       and s not in suppress]
                if ids:
                    self.step_fault_hook(ids)
            _, pt_decode = self._page_tables()
            key = random_mod.next_key_host()
            self.step_tokens_inflight = int(self._active.sum())
            self._beat()
            t0 = time.perf_counter()
            with RecordEvent(SPAN_DECODE_STEP):
                self._ct, self._pos, self._last_logits, toks = \
                    self._decode_fn(
                        self._ct, self._pos, self._last_logits,
                        pt_decode, key,
                        self._dev(self._temps),
                        self._dev(self._topk),
                        self._dev(self._topp),
                        self._dev(self._greedy),
                        self._dev(self._active))
                toks = np.asarray(toks)   # sync: host sees the tokens
            self.step_tokens_inflight = 0
            self._beat()
            ran = True
            # wall time of the synchronized step (the attn_impl A/B
            # metric); real perf_counter regardless of an injected
            # test clock
            wall = time.perf_counter() - t0
            self.metrics.on_decode_step(wall)
            self._round_stats["decode_tokens"] += int(self._active.sum())
            self._round_stats["wall_s"] += wall
            if self.tp is not None:
                self._round_stats["collectives"] += \
                    self.tp.step_collectives(self.n_layers)
            now = now_fn()
            for slot, req in list(self.scheduler.running.items()):
                if req.state is not RequestState.DECODE \
                        or slot in suppress:
                    continue          # mid-prefill: no token this step
                tok = int(toks[slot])
                prev_t = req._last_token_t
                req._emit(tok, now)
                self.metrics.on_token(req, now)
                if prev_t is not None:
                    self.metrics.on_inter_token(
                        now - prev_t, priority=req.sampling.priority,
                        adapter_id=int(getattr(
                            req.sampling, "adapter_id", 0) or 0),
                        now=now)
                elif self.obs is not None:
                    self._obs_event(req, "first_token")
                sp = req.sampling
                if sp.eos_token_id is not None \
                        and tok == sp.eos_token_id:
                    self._finish_and_free(req, "stop", now, finished)
                elif len(req.output_tokens) >= sp.max_new_tokens:
                    self._finish_and_free(req, "length", now, finished)
        finally:
            if suppress:
                # restore ONLY the suppressed entries — innocents that
                # finished during the probe must stay retired
                for s in suppress:
                    self._active[s] = saved_active[s]
                self._pt_dirty = True
                if ran:
                    ll = np.array(self._last_logits)   # writable copy
                    old = np.asarray(saved_logits)
                    for s in suppress:
                        ll[s] = old[s]
                    self._last_logits = jnp.asarray(ll)

    @staticmethod
    def _grammar_bias(g, left, eos, V) -> np.ndarray:
        """One [V] row of the additive-bias grammar operand from an
        automaton state: 0.0 where the grammar allows the token,
        NEG_BIAS where it forbids it. Budget-aware — with only `left`
        emission slots remaining, one is reserved for EOS, so tokens
        are restricted to those from which an accepting state is still
        reachable within left-1 (the automaton degrades to its
        unrestricted allow-set if acceptance is unreachable: a
        "length"-truncated stream beats steering into a dead end). EOS
        composes in here: allowed iff the automaton accepts now, and
        FORCED (the only allowed token) when the grammar allows
        nothing else — a structurally complete, token-exhausted state
        must terminate rather than emit arbitrary tokens."""
        allow = g.budget_allowed(max(0, left - 1))
        bias = np.where(allow, np.float32(0.0),
                        np.float32(NEG_BIAS)).astype(np.float32)
        if eos is not None and 0 <= eos < V:
            bias[eos] = 0.0 if g.accepting() else NEG_BIAS
        if not (bias == 0.0).any():
            if eos is not None and 0 <= eos < V:
                bias[eos] = 0.0
            else:           # unreachable: SamplingParams requires EOS
                bias[:] = 0.0
        return bias

    def _propose_drafts(self, running, suppress) -> Dict[int, np.ndarray]:
        """Host-side drafting (speculative decoding): ask each greedy
        DECODE slot's drafter for up to k next tokens over the
        request's committed history (prompt + emitted). The per-slot
        cap keeps every transient K/V write inside the request's own
        page budget: drafts <= max_new - emitted - 1 means the deepest
        draft position is plen + max_new - 1, the last slot admission
        reserved — page pressure can never make speculation scribble
        on a neighbor. Returns {slot: proposed token ids}."""
        proposals: Dict[int, np.ndarray] = {}
        model_rows: Dict[int, tuple] = {}
        for slot, req in sorted(running.items()):
            if (req.state is not RequestState.DECODE
                    or slot in suppress or not req.sampling.greedy):
                continue
            drafter = self._drafters.get(req.request_id)
            if drafter is None:
                continue
            budget = (req.sampling.max_new_tokens
                      - len(req.output_tokens) - 1)
            cap = min(self.spec.k, self.chunk_len - 1, budget)
            if cap <= 0:
                continue
            if isinstance(drafter, ModelDrafter):
                # the model tier drafts BATCHED: every speculating
                # row rides one compiled draft call, not per-row
                # Python — collected here, proposed below
                model_rows[slot] = (req, cap)
                continue
            hist = np.concatenate(
                [req.prompt_ids.astype(np.int64),
                 np.asarray(req.output_tokens, np.int64)])
            try:
                prop = np.asarray(drafter.propose(
                    hist, cap, budget=budget)).reshape(-1)
            except TypeError:
                # legacy Drafter subclass without the optional budget
                # arg: the engine-side cap still bounds the grant
                prop = np.asarray(drafter.propose(hist,
                                                  cap)).reshape(-1)
            if prop.size:
                proposals[slot] = prop[:cap].astype(np.int64)
        if model_rows:
            proposals.update(self._propose_model_rows(model_rows))
        return proposals

    def _propose_model_rows(self, rows) -> Dict[int, np.ndarray]:
        """Model-tier drafting: run the k draft micro-steps for EVERY
        speculating slot at once through the draft model's own one
        compiled ragged program. Per slot: sync the draft position
        with the committed stream (the clamp IS the rollback of last
        step's rejected drafts), recompute this step's t0 host-side —
        the [grammar-biased] argmax over the held logits, bit-exact
        with the device greedy pick (same f32 add, same
        first-occurrence tie-break; only greedy rows draft) — and
        feed the catch-up `committed[dpos:] + [t0]` raggedly; the
        harvested argmax chain `[draft_1..draft_k]` is aligned so
        draft_i predicts committed position P+i, exactly what the
        fused greedy acceptance verifies against. Slots lagging more
        than a chunk defer to `_draft_seed_step` (spare-budget
        warming); slots whose t0 is EOS finish this step and skip."""
        d = self._draft
        proposals: Dict[int, np.ndarray] = {}
        if d is None or self._last_logits is None:
            return proposals
        ll_host = None
        entries: Dict[int, tuple] = {}
        caps: Dict[int, int] = {}
        for slot, (req, cap) in sorted(rows.items()):
            if not d.resident(slot) and not d.admit(
                    slot, int(req.prompt_ids.size),
                    self._budget_new(req.sampling)):
                continue            # draft-pool pressure: retry later
            P = int(req.prompt_ids.size) + len(req.output_tokens)
            dpos = d.committed(slot, P)
            if (P - dpos) + 1 > self.chunk_len:
                continue            # too cold: seeding catches it up
            if ll_host is None:
                ll_host = np.asarray(self._last_logits)
            sp = req.sampling
            eos = sp.eos_token_id
            g = self._grammars.get(req.request_id)
            if g is None:
                t0 = int(np.argmax(ll_host[slot]))
            else:
                left = sp.max_new_tokens - len(req.output_tokens)
                t0 = int(np.argmax(
                    ll_host[slot] + self._grammar_bias(
                        g, left, eos, int(ll_host.shape[-1]))))
            if eos is not None and t0 == eos:
                continue            # the row finishes this step
            hist = np.concatenate(
                [req.prompt_ids.astype(np.int64),
                 np.asarray(req.output_tokens, np.int64)])
            entries[slot] = (np.concatenate([hist[dpos:],
                                             [t0]]), cap)
            caps[slot] = cap
        if not entries:
            return proposals
        for slot, p in d.propose_batch(entries).items():
            p = np.asarray(p, np.int64).reshape(-1)[:caps[slot]]
            if p.size:
                proposals[slot] = p
        return proposals

    def _draft_seed_step(self, running, suppress, decode_slots,
                         grants, draft_grants, proposals):
        """Warm lagging slots' draft KV from this step's SPARE token
        budget (what decode + prefill + draft packing left over —
        Scheduler.pack_draft_seed): chunked draft-prefill of each
        lagging slot's committed stream, all riding ONE ragged draft
        call next to the target step. PREFILL rows seed from
        `prefill_ids` (predetermined — a resumed or migrated stream's
        banked history is its tail, so survivor re-seed is this same
        path), DECODE rows from prompt + emitted. Slots that proposed
        this step are skipped: their draft position is legitimately
        AHEAD of the committed stream (speculation), not lagging."""
        d = self._draft
        spare = (self.token_budget - len(decode_slots)
                 - sum(grants.values()) - sum(draft_grants.values()))
        if spare <= 0:
            return
        wanted: Dict[int, int] = {}
        src: Dict[int, np.ndarray] = {}
        for slot, req in sorted(running.items()):
            if slot in suppress or slot in proposals \
                    or not req.sampling.greedy:
                continue
            if not isinstance(self._drafters.get(req.request_id),
                              ModelDrafter):
                continue
            if req.state is RequestState.PREFILL:
                committed = np.asarray(req.prefill_ids, np.int64)
            elif req.state is RequestState.DECODE:
                committed = np.concatenate(
                    [req.prompt_ids.astype(np.int64),
                     np.asarray(req.output_tokens, np.int64)])
            else:
                continue
            if not d.resident(slot) and not d.admit(
                    slot, int(req.prompt_ids.size),
                    self._budget_new(req.sampling)):
                continue            # draft-pool pressure
            dpos = d.committed(slot, int(committed.size))
            lag = int(committed.size) - dpos
            if lag <= 1:
                continue    # propose's own catch-up absorbs this
            wanted[slot] = lag
            src[slot] = committed[dpos:]
        if not wanted:
            return
        seeds = self.scheduler.pack_draft_seed(spare, self.chunk_len,
                                               wanted)
        entries = {slot: src[slot][:take]
                   for slot, take in seeds.items() if take > 0}
        if entries:
            d.seed(entries)
            self._round_stats["draft_seed_tokens"] += sum(
                int(v.size) for v in entries.values())

    def _unified_step(self, finished: List[RequestOutput],
                      suppress=frozenset()) -> int:
        """One UNIFIED ragged step: pack this round's tokens — every
        decoding slot's next token, its granted speculative drafts,
        plus as many prefill prompt tokens as the spare token budget
        allows (Scheduler.pack_tokens) — and run them through THE one
        compiled ragged program. Decode rows come back with a verified
        burst (1 + accepted drafts, each token exactly what sequential
        greedy decode would emit); the program already rolled pos back
        past any rejected draft. Slots in `suppress` ride at q_len 0
        (quarantine probes): positions, cursors and held logits
        untouched by construction, and no drafted-but-unverified token
        can leak — drafts are only ever emitted through the verify
        pass of a step their slot participated in. Returns the number
        of prefill tokens packed alongside the decodes (0 when nothing
        ran)."""
        if not self.scheduler.running:
            return 0
        with self._phase(SPAN_PLAN, "step_plan_s_total"):
            plan = self._plan_unified(suppress)
        if plan is None:
            return 0
        # launch-count probe: count registered-op dispatches while the
        # launch runs. Only a (re)trace walks the Python op layer —
        # compiled replays leave `counts` empty — so the histogram is
        # the per-step LAUNCH census of the one program, captured once
        # per compile at zero steady-state cost. Trace-time counting
        # is deliberate: post-compile HLO computation counts would
        # reflect the backend's fusion heuristics, not this codebase's
        # op granularity.
        counts: Dict[str, int] = {}
        prev_probe = set_dispatch_probe(
            lambda name: counts.__setitem__(name,
                                            counts.get(name, 0) + 1))
        try:
            with RecordEvent(SPAN_UNIFIED_STEP):
                with self._phase(SPAN_LAUNCH, "step_launch_s_total"):
                    self._ct, self._pos, self._last_logits, toks, \
                        accept = self._unified_fn(self._ct,
                                                  *plan.args_tail)
                # sync: the host waits for the device, then sees the
                # tokens
                with self._phase(SPAN_FETCH, "step_fetch_s_total"):
                    toks = np.asarray(toks)
                    accept = np.asarray(accept)
                for name, n in zip(self._step_stat_names,
                                   accept[self.num_slots:]):
                    self._host_phases[name] += int(n)
        finally:
            set_dispatch_probe(prev_probe)
        with self._phase(SPAN_COMMIT, "step_commit_s_total"):
            return self._commit_unified(plan, counts, toks, accept,
                                        finished)

    def _plan_unified(self, suppress) -> Optional["_StepPlan"]:
        """The host's work before the launch (`serving::plan`): pack
        this round's tokens, build the token/q_len arrays, the page
        tables, the prefix-sharing groups and the modeled read count,
        and upload the operands. None when nothing is to run."""
        running = self.scheduler.running
        W = self.chunk_len
        remaining = {
            slot: int(req.prefill_ids.size)
            - self._prefill_cursor[req.request_id]
            for slot, req in running.items()
            if req.state is RequestState.PREFILL
            and slot not in suppress}
        proposals = (self._propose_drafts(running, suppress)
                     if self.spec is not None else {})
        decode_slots, grants, draft_grants = \
            self.scheduler.pack_tokens(
                self.token_budget, W, remaining,
                draft_wanted={s: int(p.size)
                              for s, p in proposals.items()})
        if suppress:
            decode_slots = [s for s in decode_slots
                            if s not in suppress]
            draft_grants = {s: n for s, n in draft_grants.items()
                            if s not in suppress}
        if not decode_slots and not grants:
            return None
        if self._draft is not None:
            # draft-cache warming rides the leftover budget (runs as
            # its own small launch BEFORE the target program — the
            # dispatch probe below wraps only the target launch, so
            # the launch census stays the target's)
            self._draft_seed_step(running, suppress, decode_slots,
                                  grants, draft_grants, proposals)
        if self.step_fault_hook is not None:
            self.step_fault_hook(
                [running[s].request_id for s in decode_slots]
                + [running[s].request_id for s in sorted(grants)])
        tokens = np.zeros((self.num_slots, W), np.int32)
        q_len = np.zeros((self.num_slots,), np.int32)
        is_decode = np.zeros((self.num_slots,), bool)
        for slot in decode_slots:
            m = draft_grants.get(slot, 0)
            if m:
                tokens[slot, 1:1 + m] = proposals[slot][:m]
            q_len[slot] = 1 + m
            is_decode[slot] = True
        for slot, take in grants.items():
            req = running[slot]
            cur = self._prefill_cursor[req.request_id]
            tokens[slot, :take] = req.prefill_ids[cur:cur + take]
            q_len[slot] = take
        self._ensure_last_logits(next(iter(running.values())))
        if self._unified_fn is None:
            self._unified_fn = self._build_unified()
        if self._vec_dirty:
            self._refresh_vectors()
        pt_full, _ = self._page_tables()
        # prefix-sharing groups for this step's walk (host-side, from
        # the page tables — pure operand data) + the modeled page-block
        # read count both walks would issue (the CPU-reference number
        # the --prefix-share A/B and the saved-reads counter report)
        pos_host = np.asarray(self._pos)
        # on a mesh the DMA model counts what ONE CHIP issues per
        # layer (n_kv/mp local head walks over 1/mp page slices) —
        # per-chip reads AND per-chip reads saved drop by mp
        shard = dict(n_kv=self.n_kv, mp=self.mp) \
            if self.tp is not None else {}
        # fused-byte model inputs (megakernel referee): per-element
        # widths of the local KV lane + the per-row adapter stream
        # bytes for rows that actually carry a non-base adapter page
        kv_elt = (1 if self.kv_dtype in ("int8", "fp8")
                  else int(jnp.dtype(self._fp).itemsize))
        scale_elt = 4 if self.kv_dtype == "int8" else 0
        lora_rows = (int(np.count_nonzero(self._apage[q_len > 0]))
                     if self.adapters is not None else 0)
        fused_spec = dict(head_dim=self.head_dim, kv_elt=kv_elt,
                          scale_elt=scale_elt,
                          lora_bytes=lora_rows
                          * self._adapter_row_bytes)
        group_args = ()
        phase1 = False
        if self.grouped:
            gid, gld, gcn = shared_prefix_groups(self._pt_host, q_len)
            phase1 = bool(gcn.any())
            group_args = (self._dev(gid), self._dev(gld),
                          self._dev(gcn))
            flat_reads, step_reads, group_sizes, walk_bytes = \
                count_page_block_reads(self._pt_host, pos_host, q_len,
                                       gid, gcn,
                                       page_size=self.page_size,
                                       fused=fused_spec, **shard)
        else:
            flat_reads, step_reads, group_sizes, walk_bytes = \
                count_page_block_reads(self._pt_host, pos_host, q_len,
                                       page_size=self.page_size,
                                       fused=fused_spec, **shard)
        self.metrics.on_grouped_step(flat_reads, step_reads,
                                     group_sizes, phase1=phase1)
        # the grid the walk's dynamic bounds give this step, of the
        # grid the step's shape alone would (one full-attention layer)
        steps, full = count_walk_grid_steps(
            pos_host, q_len, lq=W, page_size=self.page_size,
            max_pages=self.max_pages)
        self._host_phases["walk_grid_steps_total"] += steps
        self._host_phases["walk_grid_steps_full_total"] += full
        for window in self.kv_windows.values():
            walked, unwindowed = count_window_page_reads(
                pos_host, q_len, page_size=self.page_size,
                window=window)
            self._host_phases["kv_window_pages_walked_total"] += walked
            self._host_phases["kv_window_pages_skipped_total"] += \
                unwindowed - walked
        # per-layer walk bytes -> whole-step modeled bytes: every
        # layer's attention issues the same walk over its own pools
        self._last_walk_bytes = {
            "unfused": int(walk_bytes["unfused"]) * self.n_layers,
            "fused": int(walk_bytes["fused"]) * self.n_layers,
            "tokens": int(q_len.sum()),
        }
        self._round_stats["reads_saved"] += \
            int(flat_reads) - int(step_reads)
        key = random_mod.next_key_host()
        # beat the watchdog heartbeat around the compiled launch and
        # expose the packed size: a legitimately huge packed step gets
        # proportional grace instead of a false-positive condemnation
        self.step_tokens_inflight = int(q_len.sum())
        self._beat()
        t0 = time.perf_counter()
        adapter_args = ()
        if self.adapters is not None:
            # the paged adapter pool rides as an ARGUMENT (like the KV
            # pools), so uploads/evictions swap data under the same
            # trace; the per-slot page + scale vectors are operand
            # data next to pos/q_len
            adapter_args = (self.adapters.pools,
                            self._dev(self._apage),
                            self._dev(self._ascale))
        grammar_args = ()
        if self.grammar_on:
            # per-slot grammar bias operands — DATA, not shape: every
            # row always carries a [V] additive-bias row (all-zero for
            # unconstrained rows), and with spec on every verify
            # column carries one too, so mixed batches stay ONE
            # compiled program
            V = int(self._last_logits.shape[-1])
            gsamp = np.zeros((self.num_slots, V), np.float32)
            gver = (np.zeros((self.num_slots, W, V), np.float32)
                    if self.spec is not None else None)
            ll_host = None
            n_con = n_rej = 0
            for slot in decode_slots:
                req = running.get(slot)
                if req is None:
                    continue
                g = self._grammars.get(req.request_id)
                if g is None:
                    continue
                sp = req.sampling
                eos = sp.eos_token_id
                left = sp.max_new_tokens - len(req.output_tokens)
                bias0 = self._grammar_bias(g, left, eos, V)
                gsamp[slot] = bias0
                n_con += 1
                m = draft_grants.get(slot, 0)
                if m:
                    # walk a FORK down the drafted path [t0, p0, p1,
                    # ...] and give each verify column the bias of the
                    # state it verifies FROM. t0 is recomputed on the
                    # host as the masked argmax over the held logits —
                    # bit-exact with the device's greedy pick (same
                    # f32 elementwise add, same first-occurrence
                    # tie-break), and drafts only exist on greedy rows
                    if ll_host is None:
                        ll_host = np.asarray(self._last_logits)
                    t0 = int(np.argmax(ll_host[slot] + bias0))
                    walk = g.fork()
                    alive = eos is None or t0 != eos
                    if alive:
                        walk.advance(t0)
                    props = proposals[slot]
                    for j in range(m):
                        if not alive:
                            # dead path (EOS or a violating draft
                            # upstream): the acceptance cumprod
                            # already kills these columns — leave
                            # them unconstrained
                            break
                        bias_j = self._grammar_bias(
                            walk, left - 1 - j, eos, V)
                        gver[slot, j] = bias_j
                        p = int(props[j])
                        if eos is not None and p == eos:
                            alive = False
                        elif bias_j[p] < 0.0:
                            # grammar-violating draft: the masked
                            # argmax in this column cannot equal it,
                            # so the SAME fused greedy acceptance
                            # rejects it in-trace
                            n_rej += 1
                            alive = False
                        else:
                            walk.advance(p)
            rs = self._round_stats
            rs["constrained_rows"] += n_con
            rs["grammar_rejected"] += n_rej
            if n_con:
                self.metrics.on_grammar_step(n_con, n_rej)
            grammar_args = (self._dev(gsamp),)
            if gver is not None:
                grammar_args += (self._dev(gver),)
        args_tail = (self._pos, self._last_logits, pt_full,
                     self._dev(tokens), self._dev(q_len),
                     self._dev(is_decode), key,
                     self._dev(self._temps), self._dev(self._topk),
                     self._dev(self._topp), self._dev(self._greedy),
                     *adapter_args, *group_args, *grammar_args)
        # kept for collective_counts() AND the cost census: the exact
        # operand pytree (the live self._ct stands in for the pools)
        # the one trace lowers against — [S]-sized arrays, not pools
        self._unified_args_tail = args_tail
        return _StepPlan(decode_slots, grants, draft_grants, proposals,
                         args_tail, t0)

    def _commit_unified(self, plan: "_StepPlan", counts: Dict[str, int],
                        toks, accept,
                        finished: List[RequestOutput]) -> int:
        """The host's work after the fetch (`serving::commit`): the
        step's wall time and counters, prefill cursors, token emission,
        finish and free. Returns the prefill tokens the step packed."""
        decode_slots, grants, draft_grants, proposals, _, t0 = plan
        running = self.scheduler.running
        if counts:
            self._dispatch_counts = {
                "total": int(sum(counts.values())),
                "ops": dict(sorted(counts.items())),
            }
            self.metrics.unified_dispatch_ops = \
                self._dispatch_counts["total"]
        self.step_tokens_inflight = 0
        self._beat()
        n_prefill = int(sum(grants.values()))
        n_drafts = int(sum(draft_grants.values()))
        wall = time.perf_counter() - t0
        self.metrics.on_unified_step(n_prefill, len(decode_slots),
                                     wall, draft_tokens=n_drafts)
        rs = self._round_stats
        rs["prefill_tokens"] += n_prefill
        rs["decode_tokens"] += len(decode_slots)
        rs["draft_tokens"] += n_drafts
        rs["wall_s"] += wall
        if self.tp is not None:
            # per-launch collective census (the flight recorder's
            # per-step number; collective_counts() checks the model
            # against compiled HLO): one output all-gather per layer
            rs["collectives"] += self.tp.step_collectives(self.n_layers)
        now = self._clock()
        # prefill bookkeeping: advance cursors, flip finished rows to
        # DECODE (their last real token's logits are now held — they
        # sample their first token next step). Embed rows never flip:
        # at cursor end they take the pooled last-hidden-state through
        # the embed epilogue and retire on the spot (prefill-only).
        embed_rows = []
        for slot, take in grants.items():
            req = running[slot]
            cur = self._prefill_cursor[req.request_id] + take
            self._prefill_cursor[req.request_id] = cur
            self.metrics.on_prefill_chunk(take)
            self._obs_event(req, "prefill_chunk", tokens=take,
                            cursor=cur)
            if cur >= req.prefill_ids.size:
                self._prefill_cursor.pop(req.request_id, None)
                if getattr(req.sampling, "embed", False):
                    embed_rows.append((slot, req))
                    continue
                req.state = RequestState.DECODE
                self._active[slot] = True
                self._vec_dirty = True
                self._pt_dirty = True
                self._obs_event(req, "decode")
        if embed_rows:
            # embedding BEFORE retirement: the epilogue reads the
            # row's still-attached pages; _finish_and_free then
            # routes them through the prefix cache as usual
            self._embed_rows(embed_rows)
            for slot, req in embed_rows:
                self._finish_and_free(req, "stop", now, finished)
        # decode emission: the old decode step's retirement, token by
        # token over the verified burst — EOS or the token budget can
        # end the request mid-burst, and the sequential semantics
        # (emit the terminal token, drop everything after it) are
        # exactly what one-at-a-time decode would have done
        spec_drafted = spec_accepted = 0
        spec_burst_sizes: List[int] = []
        for slot in decode_slots:
            req = running.get(slot)
            if req is None or req.state is not RequestState.DECODE:
                continue
            m = draft_grants.get(slot, 0)
            acc = min(int(accept[slot]), m) if m else 0
            burst = [int(toks[slot])]
            if acc:
                burst.extend(int(t) for t in proposals[slot][:acc])
            prev_t = req._last_token_t
            emitted, reason = 0, None
            sp = req.sampling
            gram = self._grammars.get(req.request_id)
            for tok in burst:
                req._emit(tok, now)
                emitted += 1
                self.metrics.on_token(req, now)
                if sp.eos_token_id is not None \
                        and tok == sp.eos_token_id:
                    reason = "stop"
                    break
                if gram is not None:
                    # commit the automaton along the emitted burst
                    # (EOS broke out above — it is terminal, never a
                    # grammar character)
                    gram.advance(tok)
                if len(req.output_tokens) >= sp.max_new_tokens:
                    reason = "length"
                    break
            # a burst lands at one step boundary: attribute the step
            # gap ACROSS its tokens (gap/emitted each) instead of one
            # full gap plus zeros — per-token latency percentiles stay
            # meaningful when >1 token arrives per step
            if prev_t is not None and emitted:
                dt = (now - prev_t) / emitted
                for _ in range(emitted):
                    self.metrics.on_inter_token(
                        dt, priority=sp.priority,
                        adapter_id=int(getattr(sp, "adapter_id", 0)
                                       or 0),
                        now=now)
            elif emitted and self.obs is not None:
                self._obs_event(req, "first_token")
            if m:
                acc_emitted = max(0, emitted - 1)
                spec_drafted += m
                spec_accepted += acc_emitted
                req.accepted_draft_tokens += acc_emitted
            if self.spec is not None:
                spec_burst_sizes.append(emitted)
            if reason is not None:
                self._finish_and_free(req, reason, now, finished)
        if spec_burst_sizes:
            self.metrics.on_spec(spec_drafted, spec_accepted,
                                 spec_burst_sizes)
            self._round_stats["accepted_tokens"] += spec_accepted
        return n_prefill

    def _run_round(self, finished: List[RequestOutput],
                   suppress=frozenset()) -> int:
        """Run one round's compiled work — the unified ragged step, or
        the legacy prefill-chunks-then-decode pair — excluding any
        slots in `suppress` (they idle this round: positions, held
        logits and prefill cursors untouched). Suppression exists for
        `_quarantine_poison`'s bisection probes. Returns prefill
        chunks run ahead of the decode (legacy path only)."""
        if self.unified:
            self._unified_step(finished, suppress=suppress)
            return 0
        chunks = self._advance_prefills(suppress)
        if self._active.any():
            self._decode(self._clock, finished, suppress=suppress)
        return chunks

    def _quarantine_poison(self, finished: List[RequestOutput]) -> bool:
        """A round raised: find the ONE resident request that
        deterministically kills the step, fail it alone (finish reason
        "poisoned", typed `PoisonedRequest`, HTTP 422, never retried)
        and keep the replica serving everyone else. Group-testing
        bisection over the resident slots: each probe re-runs the
        round with half the candidates suppressed — a probe that
        raises exonerates the suppressed half, a probe that succeeds
        convicts it (and the innocents it ran simply made progress).
        The verdict is verified (a round WITHOUT the suspect must
        succeed); an empty batch or a fault that doesn't track one
        request returns False and the original exception propagates as
        replica death. Assumes deterministic faults — the shape
        `FaultInjector.poison` injects and real poison inputs show."""
        candidates = sorted(self.scheduler.running)
        if not candidates:
            return False
        while len(candidates) > 1:
            half = frozenset(candidates[:len(candidates) // 2])
            try:
                self._run_round(finished, suppress=half)
            except Exception:
                survivors = [s for s in candidates if s not in half]
            else:
                survivors = list(half)
            candidates = [s for s in survivors
                          if s in self.scheduler.running]
            if not candidates:
                return False
        slot = candidates[0]
        req = self.scheduler.running.get(slot)
        if req is None:
            return False
        try:     # verdict check: the round must succeed without it
            self._run_round(finished, suppress=frozenset([slot]))
        except Exception:
            return False
        req.error = PoisonedRequest(
            f"request {req.request_id} deterministically kills the "
            "serving step; quarantined")
        self._finish_and_free(req, "poisoned", self._clock(), finished)
        return True

    def step(self) -> List[RequestOutput]:
        """One scheduler round: evict (timeout / cancel / expired
        placement deadline -> fail-fast "deadline"), admit queued
        requests whose pages fit, PREEMPT the least-important resident
        when a strictly higher-priority head is still blocked
        (graceful overload degradation), then run the round's tokens.
        With the unified step (default) that is ONE compiled ragged
        program — decode tokens and packed prefill chunks together, so
        a long prompt never stalls a resident decoder. On the legacy
        alternating path (PADDLE_TPU_UNIFIED_STEP=off) it is one
        prefill chunk per mid-prefill slot, then one compiled decode
        step for every decoding slot. A round that RAISES goes through
        poison quarantine (`_quarantine_poison`): if exactly one
        resident deterministically kills the step, it alone fails and
        the replica keeps serving; otherwise the exception propagates
        (replica death). Returns requests that finished this round."""
        finished: List[RequestOutput] = []
        self._beat()
        self._step_idx += 1
        self._round_stats = {"prefill_tokens": 0, "decode_tokens": 0,
                             "draft_tokens": 0, "accepted_tokens": 0,
                             "draft_seed_tokens": 0,
                             "reads_saved": 0, "collectives": 0,
                             "constrained_rows": 0,
                             "grammar_rejected": 0, "wall_s": 0.0}
        with RecordEvent(SPAN_ROUND, step=self._step_idx):
            self._round(finished)
        self.metrics.on_host_phases(self._host_phases)
        self._host_phases.clear()
        return finished

    def _round(self, finished: List[RequestOutput]):
        """`step()`'s body, inside `serving::round`."""
        now = self._clock()
        with self._phase(SPAN_ADMIT, "round_admit_s_total"):
            self._evict(now, finished)
            self._admit(now)
            self._preempt_for_overload(now)
        chunks = 0
        try:
            chunks = self._run_round(finished)
        except Exception as exc:
            # the black box freezes BEFORE recovery runs: whatever
            # quarantine decides, the postmortem keeps the steps that
            # led here
            if self.obs is not None:
                self.obs.flight.incident("step_fault",
                                         detail=repr(exc),
                                         step=self._step_idx,
                                         slo=self._slo_snap())
            if not self._quarantine_poison(finished):
                if self.obs is not None:
                    self.obs.flight.incident("replica_death",
                                             detail=repr(exc),
                                             step=self._step_idx,
                                             slo=self._slo_snap())
                raise
            if self.obs is not None:
                self.obs.flight.incident("poison_quarantine",
                                         detail=repr(exc),
                                         step=self._step_idx,
                                         slo=self._slo_snap())
        with self._phase(SPAN_REPORT, "round_report_s_total"):
            self._report_round(chunks)

    def _report_round(self, chunks: int):
        """What the observability costs each round (`serving::report`):
        the metrics' gauges and histograms, the cost census's first
        capture, the flight recorder's record."""
        self.metrics.on_step(self.scheduler.queue_depth,
                             self.scheduler.occupancy, self.num_slots,
                             pages_used=self.pool.used_pages,
                             pages_total=self.num_pages - 1,
                             stall_chunks=chunks,
                             pages_cached=self.pool.cached_pages,
                             pages_swapped=self.pool.swapped_pages,
                             host_pages_used=self.host_pool.used_pages,
                             host_pages_total=self.host_pages,
                             draft_pages_used=(
                                 0 if self._draft is None
                                 else self._draft.pool.used_pages),
                             draft_pages_total=(
                                 0 if self._draft is None
                                 else self._draft.num_pages - 1),
                             prefix_stats=(
                                 self.prefix_cache.stats()
                                 if self.prefix_cache is not None
                                 else None),
                             adapter_stats=(
                                 self.adapters.stats()
                                 if self.adapters is not None
                                 else None))
        # capture the free analytical census right after the first
        # round (the XLA-backed sources stay lazy — cost_census());
        # metrics/flight consumers then see it from step 1 on
        if self._census is None \
                and self.census_mode not in ("off", "lowered", "xla"):
            self.cost_census()
        if self.obs is not None:
            rs = self._round_stats
            packed = (rs["prefill_tokens"] + rs["decode_tokens"]
                      + rs["draft_tokens"])
            self.obs.flight.on_step({
                "step": self._step_idx, "t": self._clock(),
                "queue_depth": self.scheduler.queue_depth,
                "residents": len(self.scheduler.running),
                "slots": [[s, r.request_id, r.state.name]
                          for s, r in
                          sorted(self.scheduler.running.items())],
                "prefill_tokens": rs["prefill_tokens"],
                "decode_tokens": rs["decode_tokens"],
                "draft_tokens": rs["draft_tokens"],
                "accepted_tokens": rs["accepted_tokens"],
                # packed-token work / program-capacity work — the
                # per-step MFU-style utilization the cost census
                # anchors (flight_dump's "util" column)
                "achieved_util": round(
                    packed / self.step_capacity_tokens, 4),
                **({} if self.slo is None
                   else {"slo": self.slo.worst_state()}),
                "reads_saved": rs["reads_saved"],
                **({} if not self.grammar_on else {
                    # per-step constrained-row count (+ drafts the
                    # host walk flagged as grammar-violating) — the
                    # flight_dump's structured-output columns
                    "constrained_rows": rs["constrained_rows"],
                    "grammar_rejected": rs["grammar_rejected"]}),
                "pages_used": self.pool.used_pages,
                "pages_total": self.num_pages - 1,
                "pages_cached": self.pool.cached_pages,
                "pages_swapped": self.pool.swapped_pages,
                "host_pages_used": self.host_pool.used_pages,
                **({} if self._draft is None else {
                    # draft-pool occupancy + spare-budget warming
                    # tokens this step (flight_dump's "dpool" column)
                    "draft_pages_used": self._draft.pool.used_pages,
                    "draft_pages_total": self._draft.num_pages - 1,
                    "draft_seed_tokens": rs["draft_seed_tokens"]}),
                "collectives": rs["collectives"],
                "step_wall_ms": round(rs["wall_s"] * 1e3, 4),
                **({} if self.adapters is None else {
                    # resident slot -> adapter id map + adapter-pool
                    # occupancy (the flight_dump "adpt" column)
                    "slot_adapters": sorted(
                        [s, a] for s, a
                        in self._slot_adapter.items()),
                    "adapters_resident":
                        self.adapters.pool.used_pages
                        + self.adapters.pool.cached_pages})})

    # -- shutdown ----------------------------------------------------------
    @property
    def closed(self) -> bool:
        return self._closed

    def drain(self) -> List[RequestOutput]:
        """Graceful shutdown half 1: stop admitting (add_request raises
        EngineClosed), abort still-QUEUED never-started requests
        (reason "aborted" — they never held pages), but let PREEMPTED
        requests RESUME and finish (they already streamed tokens; a
        drain must deliver them), then pump steps until every resident
        finishes normally. On return the scheduler is empty and every
        page is either free or cache-resident, with nothing stranded
        in the host tier (leak-checked). Idempotent."""
        self._closed = True
        finished: List[RequestOutput] = []
        now = self._clock()
        resume: List[Request] = []
        for req in self.scheduler.pop_queued():
            if req.state is RequestState.PREEMPTED:
                resume.append(req)
            else:
                self._finish_and_free(req, "aborted", now, finished)
        for req in resume:
            self.scheduler.requeue(req)
        finished.extend(self.run())
        self.pool.assert_quiesced()
        if self.adapters is not None:
            self.adapters.assert_quiesced()
        if self._draft is not None:
            self._draft.assert_quiesced()
        return finished

    def abort_all(self, reason: str = "aborted") -> List[RequestOutput]:
        """Forced shutdown half 2: retire EVERY request — queued and
        resident — right now with `reason`, freeing their pages, without
        running another compiled step. Residents keep whatever tokens
        they already emitted (the HTTP layer uses reason
        "replica_failure" to decide which are safe to retry)."""
        self._closed = True
        finished: List[RequestOutput] = []
        now = self._clock()
        for req in self.scheduler.pop_queued():
            self._finish_and_free(req, reason, now, finished)
        for slot in sorted(list(self.scheduler.running)):
            self._finish_and_free(self.scheduler.running[slot],
                                  reason, now, finished)
        self.pool.assert_quiesced()
        if self.adapters is not None:
            self.adapters.assert_quiesced()
        if self._draft is not None:
            self._draft.assert_quiesced()
        return finished

    # -- debug introspection ----------------------------------------------
    def debug_state(self) -> dict:
        """Host-side live-state snapshot for `GET /debug/state`:
        residents, queue summary, pools, prefix-cache summary, the
        engine's A/B flags. Pure dict reads — safe to call from a
        scrape thread while the pump steps (the HTTP layer retries
        the rare torn read); never touches device state."""
        sched = self.scheduler
        residents = []
        for slot, req in sorted(sched.running.items()):
            residents.append({
                "slot": slot, "request_id": req.request_id,
                "state": req.state.name,
                "prompt_len": int(req.prompt_ids.size),
                "emitted": len(req.output_tokens),
                "pages": len(self._slot_pages.get(slot) or ()),
                "cached_tokens": int(req.cached_tokens),
                "priority": int(req.sampling.priority),
                "adapter_id": int(getattr(req.sampling, "adapter_id",
                                          0) or 0)})
        return {
            "closed": self._closed,
            "step": self._step_idx,
            "num_slots": self.num_slots,
            "residents": residents,
            "queue": sched.queue_summary(),
            "pool": {"pages_total": self.num_pages - 1,
                     "pages_used": self.pool.used_pages,
                     "pages_cached": self.pool.cached_pages,
                     "pages_swapped": self.pool.swapped_pages,
                     "pages_free": self.pool.free_pages,
                     "bytes_per_page": self.page_bytes},
            "host_pool": {"pages_used": self.host_pool.used_pages,
                          "pages_total": self.host_pages},
            "draft_pool": (None if self._draft is None
                           else self._draft.stats()),
            "prefix_cache": (None if self.prefix_cache is None
                             else self.prefix_cache.stats()),
            "adapters": (None if self.adapters is None else {
                "pool": self.adapters.stats(),
                "registered": self.adapters.debug()}),
            "config": {"unified": self.unified,
                       "grouped": self.grouped,
                       "attn_impl": self.attn_impl,
                       "kv_dtype": self.kv_dtype,
                       "mesh": (None if self.tp is None
                                else self.tp.shape),
                       "mp": self.mp, "dp": self.dp,
                       "preempt": self.preempt,
                       "spec": (None if self.spec is None
                                else self.spec.mode),
                       "spec_draft_model": self._draft is not None,
                       "grammar": self.grammar_on,
                       "num_pages": self.num_pages,
                       "page_size": self.page_size,
                       "chunk_len": self.chunk_len,
                       "max_len": self.max_len,
                       "token_budget": self.token_budget},
            "obs": None if self.obs is None else self.obs.stats(),
            "slo": self._slo_snap(),
            "cost_census": self.cost_census(),
        }

    def collective_counts(self) -> dict:
        """Ground-truth collective census of THE one unified trace
        (mesh engines only): lower the step against the exact operand
        shardings the live trace used and count collective ops in the
        optimized HLO. The multi-chip serving contract the tests and
        `--tp-ab` pin: ZERO all-reduce / reduce-scatter (no
        partial-sum fp reassociation ever — that is what keeps mp>1
        bit-token-identical to the mp=1 oracle) and exactly ONE
        output all-gather per layer per step. Requires a mesh engine
        that has run at least one unified step."""
        if self.tp is None:
            raise ValueError(
                "collective_counts() needs a mesh engine "
                "(ServingEngine(mesh=...) / PADDLE_TPU_MESH)")
        return collective_counts(
            self.lowered_unified_step().compile().as_text())

    def lowered_unified_step(self):
        """The ONE unified step, lowered against the exact operands
        (shapes, shardings) its last dispatch used: `.as_text()` is
        the program as handed to the compiler (a Pallas kernel shows
        as `tpu_custom_call`), `.compile().as_text()` the optimized
        HLO. Requires that at least one unified step has run."""
        if self._unified_fn is None or self._unified_args_tail is None:
            raise ValueError(
                "lowered_unified_step(): no unified step has run yet "
                "— serve at least one request first")
        return self._unified_fn.lower(self._ct,
                                      *self._unified_args_tail)

    # -- conveniences ------------------------------------------------------
    @property
    def has_work(self) -> bool:
        return self.scheduler.has_work

    def run(self, max_steps: Optional[int] = None) -> List[RequestOutput]:
        """Pump steps until idle (or max_steps); returns everything that
        finished along the way."""
        out: List[RequestOutput] = []
        steps = 0
        while self.has_work:
            out.extend(self.step())
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        return out

    def generate(self, prompts: Sequence, sampling=None
                 ) -> List[RequestOutput]:
        """Blocking batch API: submit all prompts, run to completion,
        return outputs in submission order."""
        if sampling is None or isinstance(sampling, SamplingParams):
            sampling = [sampling] * len(prompts)
        elif len(sampling) != len(prompts):
            raise ValueError(
                f"sampling list length {len(sampling)} != number of "
                f"prompts {len(prompts)}; pass one SamplingParams per "
                "prompt (or a single shared instance)")
        reqs = [self.add_request(p, sp) for p, sp in zip(prompts, sampling)]
        self.run()
        return [r.output() for r in reqs]
