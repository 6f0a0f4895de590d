"""A resident draft MODEL inside the serving engine (spec "model" tier).

N-gram speculative decoding (serving/spec.py) collapses on natural
text: prompt-lookup only drafts well when the continuation literally
repeats earlier n-grams, so exactly where production traffic lives —
novel prose, fresh code — acceptance goes to ~zero and speculation
pays for verify columns that never commit. The fix is the classic
draft-MODEL form of speculative decoding, built here with the same
discipline every serving subsystem in this repo follows: the draft
model is just MORE RAGGED ROWS through one compiled program.

`DraftEngine` makes a small model (same architecture family, fewer
layers — `make_draft_model` shrinks the target by truncation with
weight copy, or the operator hands in any model sharing the tokenizer)
RESIDENT in the engine:

- It owns a second, much smaller paged KV pool, reusing
  `PagePool` VERBATIM — trash page 0 absorbing masked writes,
  refcounted alloc/free, `assert_quiesced()` leak checks. The pool is
  cheap: page bytes scale with the draft model's layer count, so a
  half-depth drafter costs half the HBM per resident of the target
  pool's pages (the README's "HBM cost" table).
- Each speculating slot holds a mirrored draft page table plus a
  host-side draft position `dpos` — how many tokens of the slot's
  COMMITTED stream have valid draft KV. `dpos` advances as the draft
  model decodes and ROLLS BACK by clamping to the committed length:
  rejected draft KV simply sits past the clamped `dpos` like padding,
  overwritten before it is ever attended (the PR 8 invariant, applied
  to a second pool). No explicit rollback call exists — the next
  `propose_batch` catch-up feed self-heals any divergence, including
  quarantine probe re-entry and full-accept lag.
- Proposing is k micro-steps of the draft model's OWN unified ragged
  program: ONE `jax.jit` trace (`_fn._cache_size() == 1` — the
  engine's retrace probes count exactly TWO compiled programs, target
  step + draft step), every speculating row batched per micro-step.
  Micro-step 0 feeds each row's ragged catch-up — the committed
  tokens past `dpos` plus the step's host-computed `t0` (the token
  the target WILL commit this step: the masked argmax over the held
  logits, bit-exact with the device sample on greedy rows) — and
  each later micro-step feeds the previous argmax at `q_len` 1.
  Harvested argmaxes are the proposals `[draft_1 .. draft_k]`,
  aligned so draft_i predicts committed position P+i, exactly what
  the target's fused greedy acceptance verifies against.
- Seeding a long prompt rides the SPARE step budget: the engine packs
  chunked draft-prefill (`seed`) for lagging slots next to the target
  step's own work (Scheduler.pack_draft_seed), so draft KV warms
  while the target prefills and a migrated/resumed stream re-seeds
  from its banked history with zero dedicated steps.

The draft pool has NO host tier on purpose: preemption releases a
slot's draft pages outright and resume re-seeds from the committed
history — draft KV is always recomputable, so swapping it would spend
host RAM to save work the spare budget does for free.

The draft model stays REPLICATED on a `(dp, mp)` mesh (it is tiny;
its program contains no collectives), keeps its pool in the model's
float dtype regardless of the target's int8/fp8 KV lanes (the pool is
small; quantizing it would buy bytes nobody is short of and cost a
second quantization code path), and runs outside the engine's
dispatch probe (the launch census stays the TARGET program's).
"""
from __future__ import annotations

import copy
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ..core import dtype as dtypes
from ..core.tensor import Tensor
from ..nlp.generation import (_StepProgram, _pack_caches,
                              _restore_state, _swap_state,
                              _unpack_caches)
from .paging import PagePool, TRASH_PAGE, pages_needed

__all__ = ["DraftConfig", "DraftEngine", "make_draft_model"]


def make_draft_model(model, num_layers: Optional[int] = None):
    """Shrink a target model into a draft model by LAYER TRUNCATION
    with weight copy: keep the first `num_layers` transformer layers
    (default: half, at least 1) plus the embedding / final-norm / LM
    head weights, all COPIED from the target. Truncation keeps the
    tokenizer, vocab and (tied) unembedding identical, and the
    surviving prefix layers were trained as the target's own first
    layers — on greedy decode the truncated model's argmax agrees
    with the target's most of the time, which is all a drafter needs
    (disagreements just cost a rejected draft, never correctness).
    Intended for tests/bench and as the engine default when
    SpecConfig(draft_model=...) is not given; production deployments
    hand in a genuinely trained small model instead."""
    cfg = copy.deepcopy(model.config)
    n = int(cfg.num_hidden_layers)
    keep = (max(1, n // 2) if num_layers is None
            else max(1, min(int(num_layers), n)))
    cfg.num_hidden_layers = keep
    draft = type(model)(cfg)
    want = draft.state_dict()
    have = model.state_dict()
    draft.set_state_dict({k: v for k, v in have.items() if k in want})
    draft.eval()
    return draft


@dataclass
class DraftConfig:
    """Geometry of the draft tier, mirrored from the engine: the slot
    count and step width must MATCH the target's (draft rows are the
    same slots), the page size matches so `pages_needed` math is
    shared, and `num_pages`/`max_pages` default to the target pool's
    (same page COUNT, far fewer bytes per page — the draft model has
    fewer layers)."""

    num_slots: int
    chunk_len: int
    page_size: int
    num_pages: int
    max_pages: int
    attn_impl: Optional[str] = None


class DraftEngine:
    """The draft model + its paged KV pool, resident in one engine.

    Host API (everything the serving engine calls):
    - `admit(slot, prompt_len, max_new)` reserves the slot's full
      draft page budget (False = draft-pool pressure: the slot just
      doesn't model-draft until pages free up; correctness never
      depends on draft residency).
    - `committed(slot, n)` clamps the slot's draft position to the
      committed-stream length `n` — the ROLLBACK: KV past the clamp
      is dead padding, overwritten by the next feed at `dpos`.
    - `propose_batch(entries)` runs the k draft micro-steps for every
      speculating row at once and returns their proposals.
    - `seed(entries)` chunk-prefills lagging rows' draft KV (spare
      step budget; tokens must be committed-stream tokens).
    - `release(slot)` frees the slot's draft pages (retirement,
      preemption, abort). `assert_quiesced()` then proves no page
      leaked — wired into engine drain()/abort_all().
    """

    def __init__(self, model, cfg: DraftConfig):
        self.model = model
        self.cfg = cfg
        self.num_slots = int(cfg.num_slots)
        self.chunk_len = int(cfg.chunk_len)
        self.page_size = int(cfg.page_size)
        self.num_pages = int(cfg.num_pages)
        self.max_pages = int(cfg.max_pages)
        self.attn_impl = cfg.attn_impl
        n_layers, n_kv, head_dim = model._decode_cache_spec()
        self.n_layers, self.n_kv, self.head_dim = \
            int(n_layers), int(n_kv), int(head_dim)
        params = list(model.parameters())
        buffers = [b for _, b in model.named_buffers()]
        self._state_tensors = params + buffers
        self._state_vals = [t._value for t in self._state_tensors]
        self._fp = next(
            (t._value.dtype for t in self._state_tensors
             if jnp.issubdtype(t._value.dtype, jnp.floating)),
            dtypes.get_default_dtype().np_dtype)
        # the draft pool: float pages only (see module doc)
        self._ct = tuple(
            (jnp.zeros((self.num_pages, self.page_size, self.n_kv,
                        self.head_dim), self._fp),
             jnp.zeros((self.num_pages, self.page_size, self.n_kv,
                        self.head_dim), self._fp),
             None, None)
            for _ in range(self.n_layers))
        self.pool = PagePool(self.num_pages)
        self.page_bytes = (self.n_layers * 2 * self.page_size
                           * self.n_kv * self.head_dim
                           * jnp.dtype(self._fp).itemsize)
        self._slot_pages: Dict[int, List[int]] = {}
        self._pt_host = np.full((self.num_slots, self.max_pages),
                                TRASH_PAGE, np.int32)
        self._pt_dirty = True
        self._pt_dev = None
        # committed-stream tokens with valid draft KV, per slot
        self._dpos = np.zeros((self.num_slots,), np.int64)
        self._fn = None        # THE one compiled draft micro-step

    # -- slot lifecycle ----------------------------------------------------
    def resident(self, slot: int) -> bool:
        return slot in self._slot_pages

    def admit(self, slot: int, prompt_len: int, max_new: int) -> bool:
        """Reserve the slot's WHOLE draft page budget (prompt +
        max_new, the same bound the target admission reserves — the
        deepest draft write is position prompt+max_new-1, so pressure
        can never make a draft scribble on a neighbor). Idempotent
        for an already-resident slot."""
        if slot in self._slot_pages:
            return True
        pages = self.pool.alloc(pages_needed(
            int(prompt_len), int(max_new), self.page_size))
        if pages is None:
            return False
        self._slot_pages[slot] = pages
        self._pt_host[slot, :] = TRASH_PAGE
        self._pt_host[slot, :len(pages)] = pages
        self._pt_dirty = True
        self._dpos[slot] = 0
        return True

    def release(self, slot: int):
        """Free the slot's draft pages (no-op for non-resident slots —
        every slot-freeing engine path calls this unconditionally)."""
        pages = self._slot_pages.pop(slot, None)
        if pages:
            self.pool.free(pages)
            self._pt_host[slot, :] = TRASH_PAGE
            self._pt_dirty = True
        self._dpos[slot] = 0

    def committed(self, slot: int, n: int) -> int:
        """Sync the slot's draft position with the committed-stream
        length `n` and return it. Clamping IS the rollback: draft KV
        written past `n` (rejected drafts, quarantine-probe replays)
        becomes dead padding past the returned position, and the next
        feed overwrites it before anything attends that deep."""
        if self._dpos[slot] > n:
            self._dpos[slot] = n
        return int(self._dpos[slot])

    def lag(self, slot: int, n: int) -> int:
        """How many committed tokens the slot's draft KV is missing."""
        return max(0, int(n) - self.committed(slot, int(n)))

    # -- the one compiled draft program ------------------------------------
    def _build_fn(self):
        """ONE fixed-shape [S, chunk_len] ragged forward of the draft
        model — catch-up feeds, single-token micro-steps and seeding
        chunks are all just q_len values through the same trace
        (retrace probe: cache_size 1). Returns the per-row argmax of
        the last REAL column's logits; rows at q_len 0 ride for free
        (no state changes — their page table rows are live but the
        ragged write masks zero-query rows)."""
        model = self.model
        state_vals = self._state_vals

        def dstep(state_vals, ct, pos, page_table, tokens, q_len):
            originals = self._swap_state(state_vals)
            try:
                caches = _unpack_caches(ct, pos, page_table,
                                        attn_impl=self.attn_impl,
                                        q_len=q_len)
                logits_t, caches = model(Tensor(tokens), caches=caches)
                lg = logits_t._value.astype(jnp.float32)
                last_idx = jnp.maximum(q_len - 1, 0)
                row_last = jnp.take_along_axis(
                    lg, last_idx[:, None, None], axis=1)[:, 0]
                nxt = jnp.argmax(row_last, axis=-1).astype(jnp.int32)
                return _pack_caches(caches), nxt
            finally:
                self._restore_state(originals)

        return _StepProgram(dstep, state_vals)

    def _swap_state(self, state_vals):
        return _swap_state(self._state_tensors, state_vals)

    def _restore_state(self, originals):
        _restore_state(self._state_tensors, originals)

    def _micro_step(self, tokens: np.ndarray,
                    q_len: np.ndarray) -> np.ndarray:
        """Run one ragged draft call: per-row `q_len[i]` tokens write
        KV at positions dpos[i]..dpos[i]+q_len[i]-1 and the row's
        last-column argmax comes back. Positions are uploaded FROM
        `_dpos` every call — the host tracker is the single source of
        truth, so a clamp (rollback) needs no device bookkeeping."""
        if self._fn is None:
            self._fn = self._build_fn()
        if self._pt_dirty or self._pt_dev is None:
            self._pt_dev = jnp.asarray(self._pt_host)
            self._pt_dirty = False
        self._ct, nxt = self._fn(
            self._ct, jnp.asarray(self._dpos.astype(np.int32)),
            self._pt_dev, jnp.asarray(tokens.astype(np.int32)),
            jnp.asarray(q_len.astype(np.int32)))
        self._dpos += q_len.astype(np.int64)
        return np.asarray(nxt)

    # -- drafting ----------------------------------------------------------
    def propose_batch(
            self, entries: Dict[int, Tuple[np.ndarray, int]],
    ) -> Dict[int, np.ndarray]:
        """Draft for every speculating row AT ONCE: `entries` maps
        slot -> (catch-up feed, k). The catch-up feed is the slot's
        committed tokens past `dpos` plus the step's t0 (1..chunk_len
        tokens — the caller defers bigger lags to `seed`); micro-step
        0 feeds it raggedly and harvests draft_1, micro-steps 1..k-1
        feed the previous argmax at q_len 1. Rows with smaller k stop
        feeding early (q_len 0 rows are inert). Returns
        slot -> [draft_1 .. draft_k]; the LAST draft is harvested but
        never fed, so after a full accept the slot simply lags by one
        and the next catch-up absorbs it."""
        if not entries:
            return {}
        S, W = self.num_slots, self.chunk_len
        out: Dict[int, list] = {slot: [] for slot in entries}
        pend: Dict[int, int] = {}
        tokens = np.zeros((S, W), np.int32)
        q_len = np.zeros((S,), np.int32)
        k_max = 0
        for slot, (catchup, k) in entries.items():
            c = np.asarray(catchup, np.int64).reshape(-1)
            if not 0 < c.size <= W:
                raise ValueError(
                    f"draft catch-up feed for slot {slot} has "
                    f"{c.size} tokens (want 1..{W})")
            tokens[slot, :c.size] = c
            q_len[slot] = c.size
            pend[slot] = int(k)
            k_max = max(k_max, int(k))
        for _ in range(k_max):
            nxt = self._micro_step(tokens, q_len)
            tokens[:] = 0
            q_len[:] = 0
            for slot in list(pend):
                out[slot].append(int(nxt[slot]))
                pend[slot] -= 1
                if pend[slot] > 0:
                    tokens[slot, 0] = nxt[slot]
                    q_len[slot] = 1
                else:
                    del pend[slot]
        return {slot: np.asarray(v, np.int64)
                for slot, v in out.items()}

    def seed(self, entries: Dict[int, np.ndarray]):
        """Chunked draft-prefill: write `entries[slot]` (the slot's
        next committed tokens past its `dpos`, at most chunk_len) into
        the draft KV. All seeding slots ride ONE ragged call — the
        engine packs this into the step's SPARE token budget, so
        warming a long prompt's draft cache costs no dedicated
        steps."""
        if not entries:
            return
        S, W = self.num_slots, self.chunk_len
        tokens = np.zeros((S, W), np.int32)
        q_len = np.zeros((S,), np.int32)
        for slot, toks in entries.items():
            t = np.asarray(toks, np.int64).reshape(-1)
            if not 0 < t.size <= W:
                raise ValueError(
                    f"draft seed chunk for slot {slot} has {t.size} "
                    f"tokens (want 1..{W})")
            tokens[slot, :t.size] = t
            q_len[slot] = t.size
        self._micro_step(tokens, q_len)

    # -- accounting --------------------------------------------------------
    def stats(self) -> dict:
        return {"pages_used": self.pool.used_pages,
                "pages_total": self.num_pages - 1,
                "bytes_per_page": self.page_bytes,
                "residents": len(self._slot_pages),
                "layers": self.n_layers}

    def assert_quiesced(self):
        self.pool.assert_quiesced()
        assert not self._slot_pages, (
            f"draft slots still resident: {sorted(self._slot_pages)}")
