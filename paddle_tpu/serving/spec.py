"""Draft-then-verify speculative decoding for the serving engine.

Decode throughput of a resident slot is otherwise pinned at ONE token
per compiled-step latency: the step samples a token, writes its KV,
and must run again before the next token exists. Speculative decoding
breaks the pin without changing a single emitted token: a cheap
DRAFTER proposes up to `k` likely next tokens from the request's own
token history, the engine feeds `[sampled, draft_1 .. draft_k]` as a
`q_len = 1 + k` row of THE SAME unified ragged step (PR 6's per-row
`q_len > 1` path through `ragged_paged_attention` is exactly this
verify shape), and greedy acceptance — computed inside the same
compiled program — keeps the longest prefix of drafts that match the
model's own argmax chain. Every accepted draft is a token the
sequential path would have produced in its own full step; a rejected
draft rolls the slot's `pos` back so its (already written) KV is
overwritten by the next real token, exactly like the unified step's
padding columns. Outputs therefore stay bit-token-identical to
one-at-a-time greedy decoding — the contract the
`PADDLE_TPU_SPEC_DECODE` on/off oracle tests pin down.

The subsystem is deliberately split so the expensive part never
changes shape:

- `Drafter` (ABC): host-side proposal source, one instance PER
  REQUEST (created at admission, re-created from prompt + banked
  history when a stream migrates to another replica). Proposing may
  consult engine-resident state (the model tier below), but the
  drafter itself holds no device memory.
- `NgramDrafter`: the model-free default — prompt-lookup over the
  request's own prompt + output history. It finds the most recent
  previous occurrence of the history's tail n-gram and proposes the
  tokens that followed it, extrapolating the implied period when the
  match overlaps the tail (so a repeating pattern drafts a full `k`
  tokens, not just the sliver before history ran out). Zero extra
  weights; big wins on code/templated traffic and on the repetitive
  tails greedy decode produces. Collapses on NATURAL text — no
  repeated n-grams means no proposals.
- `ModelDrafter`: the model tier ("model[:k]"). A small draft MODEL
  resident in the SAME engine (serving/draft.py's `DraftEngine`)
  proposes by actually decoding k tokens ahead through its own tiny
  paged-KV pool. The engine batches every ModelDrafter row into ONE
  compiled draft call per micro-step (`DraftEngine.propose_batch`),
  so this class is just the per-request marker the engine routes on —
  standalone `propose` (outside an engine) proposes nothing.
- `SpecConfig`: the engine-facing knob bundle (`k` drafts per slot
  per step, drafter factory, the `mode` tag, and for the model tier
  an optional `draft_model` the engine makes resident).

Gated `PADDLE_TPU_SPEC_DECODE=off|ngram[:k]|model[:k]` (default off)
or `ServingEngine(spec=...)`; requires the unified ragged step (the
verify pass IS a unified-step row). Only greedy rows speculate: a
sampled row's distribution would need rejection sampling to stay
unbiased, and the serving contract here is exact greedy equivalence.

COMPOSITION with grammar-constrained decoding (serving/grammar.py):
speculation needs no grammar awareness here — the ENGINE forks the
request's automaton, walks it down the drafted path, and biases each
verify column's argmax with that column's automaton state, so a draft
that violates the grammar simply loses the argmax match and is
rejected by the same fused greedy acceptance above. Drafters keep
proposing from raw token history; a grammar-heavy trace just sees a
lower acceptance rate (the --grammar-ab spec arm pins it > 1.0
accepted tokens/step on templated traffic).
"""
from __future__ import annotations

import os
from abc import ABC, abstractmethod
from dataclasses import dataclass, field
from typing import Callable, Optional, Union

import numpy as np

__all__ = ["Drafter", "NgramDrafter", "ModelDrafter", "SpecConfig",
           "resolve_spec_config", "SPEC_DECODE_ENV", "SPEC_MODES"]

SPEC_DECODE_ENV = "PADDLE_TPU_SPEC_DECODE"
SPEC_MODES = ("off", "ngram", "model")

# the one sentence every malformed-spec ValueError ends with, so a
# fat-fingered env var tells the operator the whole legal grammar
# instead of a bare int() traceback
_LEGAL_FORMS = ("legal forms: 'off', 'ngram', 'ngram:<k>', 'model', "
                "'model:<k>' with integer k >= 1")

_EMPTY = np.empty((0,), np.int64)


class Drafter(ABC):
    """Per-request proposal source for draft-then-verify decoding.

    One instance serves ONE request for its whole residency: the
    engine constructs it at admission and calls `propose` once per
    step with the request's full committed history (prompt + every
    emitted token — for a migrated stream that prompt already carries
    the banked tokens from the dead replica, so the drafter is
    re-seeded for free). Proposals are SPECULATIVE: the engine may
    pack fewer than proposed (token budget), and the verify pass may
    reject any suffix — a drafter must not assume its drafts were
    emitted. Committed tokens only ever arrive via the next call's
    `history`.
    """

    @abstractmethod
    def propose(self, history: np.ndarray, k: int,
                budget: Optional[int] = None) -> np.ndarray:
        """Return up to `k` proposed next token ids (int array, may be
        empty) given the committed `history` (1-D int array,
        prompt + emitted tokens, always non-empty). `budget` (None =
        unlimited) is the request's remaining emission budget beyond
        the step's own sampled token: proposing past it wastes verify
        FLOPs on columns that can never be emitted, so drafters should
        cap at min(k, budget). The parameter defaults to None and the
        engine falls back to the 2-arg form, so pre-existing Drafter
        subclasses stay source-compatible."""


class NgramDrafter(Drafter):
    """Model-free prompt-lookup drafter (n-gram suffix matching).

    Finds the most recent PREVIOUS occurrence of the history's final
    `n`-gram (longest `n` first, `max_ngram` down to `min_ngram`) and
    proposes the tokens that followed it. The continuation is read
    cyclically with the period implied by the match distance
    `d = tail_start - match_start`: index `i` proposes
    `history[match_start + n + (i % d)]`. For a distant match this IS
    the plain following-token window (always in bounds); for a match
    overlapping the tail — a repeating pattern, the shape greedy
    decode and templated/code traffic produce constantly — it unrolls
    the period so all `k` drafts are filled instead of stopping where
    history ends. Stateless between calls, so migration re-seeding is
    just "construct a new one"."""

    def __init__(self, max_ngram: int = 3, min_ngram: int = 1):
        if min_ngram < 1:
            raise ValueError("min_ngram must be >= 1")
        if max_ngram < min_ngram:
            raise ValueError("max_ngram must be >= min_ngram")
        self.max_ngram = int(max_ngram)
        self.min_ngram = int(min_ngram)

    def propose(self, history: np.ndarray, k: int,
                budget: Optional[int] = None) -> np.ndarray:
        if budget is not None:
            # never propose columns the request can't emit: with only
            # `budget` emission slots left past the sampled token,
            # deeper drafts are guaranteed-dead verify work
            k = min(int(k), max(0, int(budget)))
        h = np.asarray(history).reshape(-1).astype(np.int64)
        n_h = int(h.size)
        if k <= 0 or n_h < self.min_ngram + 1:
            return _EMPTY
        for n in range(min(self.max_ngram, n_h - 1),
                       self.min_ngram - 1, -1):
            tail = h[n_h - n:]
            # windows over h[:-1] start at 0..n_h-1-n: every previous
            # occurrence, overlapping the tail allowed (that overlap
            # IS the period-detection that makes loops draft well)
            wins = np.lib.stride_tricks.sliding_window_view(
                h[:n_h - 1], n)
            hits = np.nonzero((wins == tail).all(axis=1))[0]
            if hits.size == 0:
                continue
            p = int(hits[-1])              # most recent occurrence
            d = (n_h - n) - p              # implied period, >= 1
            idx = p + n + (np.arange(k) % d)
            return h[idx]
        return _EMPTY


class ModelDrafter(Drafter):
    """Marker drafter for the engine-resident draft-MODEL tier.

    The proposing machinery lives in serving/draft.py: the engine
    keeps ONE `DraftEngine` (small model + its own paged KV pool) and
    routes every slot whose drafter is a ModelDrafter through a
    single batched `propose_batch` call per step — k draft
    micro-steps of one compiled ragged program, all speculating rows
    together, not per-row Python. This class therefore carries no
    state; it exists so the per-request drafter lifecycle (created at
    admission, dropped at retirement, re-created on a migration
    survivor) is IDENTICAL across tiers and the engine can route on
    `isinstance`. Standalone `propose` (outside an engine) has no
    draft KV to decode from and proposes nothing."""

    def propose(self, history: np.ndarray, k: int,
                budget: Optional[int] = None) -> np.ndarray:
        return _EMPTY


def _default_drafter() -> Drafter:
    return NgramDrafter()


def _model_drafter() -> Drafter:
    return ModelDrafter()


_DRAFTER_FACTORIES = {"ngram": _default_drafter, "model": _model_drafter}


@dataclass
class SpecConfig:
    """Engine-facing speculative-decoding knobs.

    `k` is the per-slot per-step draft budget (the verify row runs at
    `q_len = 1 + granted drafts`, further capped by the step width and
    the request's remaining token budget); `drafter` is a zero-arg
    factory producing one `Drafter` PER REQUEST — or one of the tier
    names "ngram"/"model", which also sets `mode`; `mode` is the tag
    metrics/Prometheus report next to `attn_impl`.
    `draft_model` (model tier only) is the resident draft model the
    engine's DraftEngine serves — None makes the engine shrink one
    from the target via `serving.draft.make_draft_model`."""

    k: int = 4
    drafter: Union[str, Callable[[], Drafter]] = \
        field(default=_default_drafter)
    mode: str = "ngram"
    draft_model: Optional[object] = None

    def __post_init__(self):
        if self.k < 1:
            raise ValueError("spec k must be >= 1")
        if isinstance(self.drafter, str):
            # SpecConfig(drafter="model", draft_model=...) — the gate
            # spelling the docs advertise; the tier name IS the mode
            if self.drafter not in _DRAFTER_FACTORIES:
                raise ValueError(
                    f"unknown drafter tier {self.drafter!r}: expected "
                    f"one of {tuple(_DRAFTER_FACTORIES)}")
            self.mode = self.drafter
            self.drafter = _DRAFTER_FACTORIES[self.mode]

    def make_drafter(self) -> Drafter:
        d = self.drafter()
        if not isinstance(d, Drafter):
            raise TypeError(
                f"spec drafter factory returned {type(d).__name__}, "
                "not a serving.spec.Drafter")
        return d


def resolve_spec_config(override=None) -> Optional[SpecConfig]:
    """Resolve the speculative-decoding gate to a SpecConfig (on) or
    None (off). An explicit override wins; otherwise
    PADDLE_TPU_SPEC_DECODE=off|ngram[:k]|model[:k] (read at engine
    construction, default off — same env-gate pattern as
    PADDLE_TPU_PAGED_ATTN / PADDLE_TPU_PREFIX_CACHE). Accepted
    overrides: None (use the env),
    a SpecConfig, a mode string ("off", "ngram", "ngram:8", "model",
    "model:6"), or a bool (True = default ngram config). Every
    malformed spelling — unknown mode, 'off' with a knob, an empty or
    non-integer or < 1 ':k' suffix — raises a ValueError naming the
    legal forms."""
    if override is None:
        spec = os.environ.get(SPEC_DECODE_ENV, "off")
    elif isinstance(override, SpecConfig):
        return override
    elif isinstance(override, bool):
        return SpecConfig() if override else None
    elif isinstance(override, str):
        spec = override
    else:
        raise TypeError(
            f"spec must be None, bool, str or SpecConfig, got "
            f"{type(override).__name__}")
    mode, sep, knob = spec.partition(":")
    if mode not in SPEC_MODES:
        raise ValueError(
            f"invalid {SPEC_DECODE_ENV} spec {spec!r}: unknown mode "
            f"{mode!r}; {_LEGAL_FORMS}")
    if mode == "off":
        if sep:
            raise ValueError(
                f"invalid {SPEC_DECODE_ENV} spec {spec!r}: 'off' "
                f"takes no ':k' suffix; {_LEGAL_FORMS}")
        return None
    if sep and not knob:
        raise ValueError(
            f"invalid {SPEC_DECODE_ENV} spec {spec!r}: empty ':k' "
            f"suffix; {_LEGAL_FORMS}")
    k = None
    if knob:
        try:
            k = int(knob)
        except ValueError:
            raise ValueError(
                f"invalid {SPEC_DECODE_ENV} spec {spec!r}: ':k' "
                f"suffix must be an integer; {_LEGAL_FORMS}") from None
        if k < 1:
            raise ValueError(
                f"invalid {SPEC_DECODE_ENV} spec {spec!r}: k must be "
                f">= 1; {_LEGAL_FORMS}")
    kw = {} if k is None else {"k": k}
    if mode == "model":
        return SpecConfig(drafter="model", **kw)
    return SpecConfig(**kw)
