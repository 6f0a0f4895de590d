"""The unified step's LM head runs on the columns the step reads, not on
all W of its padded rows: one a row (the column whose logits the row
keeps), 1 + k with speculation (a decoding row's token and drafts). The
causal-LM wrappers take those columns (`columns=`, int [S, C]) and
gather the final hidden states there before the head.

The referee: every launch of the step is repeated, on the same operands,
by a program that runs the model over all W columns (the wrapper called
without `columns=`) and keeps a row's logits as the step did before its
head was narrowed: the argmax of every column for the drafts'
acceptance, the held logits from column `accept` of a decoding row and
`q_len - 1` of a prefill row. Its held logits, acceptance counts and
sampled tokens must be the step's, for each served kind, over steps
that mix prefill, decoding and idle rows; with speculation; and with a
grammar's verify bias on the speculated columns.

The structural guard lowers each kind's step and finds no operation of
shape [S, W, V] in it: the full-width logits are not computed at all.
"""
import re
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.nlp import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import GrammarSpec, SamplingParams, ServingEngine
from paddle_tpu.serving.engine import _sample_rows

from test_deepseek_v2 import tiny_dsv2
from test_keye_vl2 import tiny_keye
from test_laguna import tiny_laguna
from test_mimo_v2 import tiny_mimo


@pytest.fixture(autouse=True)
def jnp_forms(monkeypatch):
    """The jnp forms of the kernels on the CPU, whatever an earlier test
    file of this worker asked for at its import."""
    from paddle_tpu.ops.pallas import (flash_attention, layer_norm, mla,
                                       moe, paged_attention)
    for mod in (flash_attention, layer_norm, mla, moe, paged_attention):
        monkeypatch.setattr(mod, "_INTERPRET", False)


def tiny_gpt():
    paddle.seed(7)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=97, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=128, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0))
    model.eval()
    return model


KINDS = {"gpt": tiny_gpt, "laguna": tiny_laguna, "deepseek_v2": tiny_dsv2,
         "keye": tiny_keye, "mimo": tiny_mimo}
EOS = 96


def engine(kind, **kw):
    kw = dict(dict(num_slots=3, max_len=64, page_size=4, chunk_len=16),
              **kw)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        return ServingEngine(KINDS[kind](), **kw)


def full_width_step(eng):
    """The step's model call over all W columns, and the epilogue that
    read them: (sampled tokens, acceptance counts, held logits)."""
    model = eng.model
    lora_on, grouped = eng.adapters is not None, eng.grouped
    gram_on = eng.grammar_on
    gram_ver = eng.grammar_on and eng.spec is not None

    def ref(state_vals, ct, pos, last_logits, page_table, tokens, q_len,
            is_decode, key, temps, top_k, top_p, greedy, *rest):
        assert not lora_on
        i = 3 if grouped else 0
        group = tuple(rest[:3]) if grouped else None
        gsamp = rest[i] if gram_on else None
        gver = rest[i + 1] if gram_ver else None
        originals = eng._swap_state(state_vals)
        try:
            samp_in = last_logits if gsamp is None else last_logits + gsamp
            nxt = _sample_rows(samp_in, key, temps, top_k, top_p, greedy)
            nxt = jnp.where(is_decode, nxt, 0).astype(jnp.int32)
            w = tokens.shape[1]
            col0 = (jnp.arange(w) == 0)[None, :]
            toks = jnp.where(is_decode[:, None] & col0, nxt[:, None],
                             tokens)
            caches = eng._unpack(ct, pos, page_table, q_len=q_len,
                                 group=group)
            logits_t, _ = model(Tensor(toks), caches=caches)
            lg = logits_t._value.astype(jnp.float32)        # [S, W, V]
            lg_v = lg if gver is None else lg + gver
            preds = jnp.argmax(lg_v, axis=-1).astype(jnp.int32)
            match = toks[:, 1:] == preds[:, :-1]
            valid = jnp.arange(w - 1)[None, :] < (q_len - 1)[:, None]
            accept = jnp.cumprod(jnp.where(match & valid, 1, 0),
                                 axis=1).sum(axis=1).astype(jnp.int32)
            accept = jnp.where(is_decode, accept, 0)
            last_idx = jnp.where(is_decode, accept,
                                 jnp.maximum(q_len - 1, 0))
            row_last = jnp.take_along_axis(
                lg, last_idx[:, None, None], axis=1)[:, 0]
            new_last = jnp.where((q_len > 0)[:, None], row_last,
                                 last_logits)
            return nxt, accept, new_last
        finally:
            eng._restore_state(originals)
    return jax.jit(ref)


class Referee:
    """Stands in for the engine's step program: runs the full-width
    reference on the operands of each launch (before the step, which
    takes the pools over), then the step, and keeps both."""

    def __init__(self, eng):
        self.step = eng._build_unified()
        self.ref = full_width_step(eng)
        self.state_vals = eng._state_vals
        self.steps = []

    def __call__(self, ct, *args):
        want = self.ref(self.state_vals, ct, *args)
        out = self.step(ct, *args)
        self.steps.append((args[3], args[4], args[5], want, out))
        return out

    def __getattr__(self, name):
        return getattr(self.step, name)


def templated(rng, n):
    """Prompts of a repeated block inside the grammar's band [A-C]: the
    shape on which the n-gram drafter's drafts are accepted."""
    return [np.concatenate([rng.randint(0, 90, size=2),
                            np.tile(rng.randint(65, 68, size=3), 4)])
            .astype(np.int64) for _ in range(n)]


CASES = {
    # a short prompt decodes while a long one still prefills, one
    # slot idle
    **{kind: dict(kind=kind) for kind in KINDS},
    "gpt.spec": dict(kind="gpt", spec="ngram"),
    "gpt.grammar_spec": dict(kind="gpt", spec="ngram", grammar=True),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_narrow_head_is_the_full_heads_column(case):
    kw = dict(CASES[case])
    kind = kw.pop("kind")
    eng = engine(kind, **kw)
    referee = eng._unified_fn = Referee(eng)
    vocab = int(eng.model.config.vocab_size)
    rng = np.random.RandomState(5)
    if "spec" in kw:
        prompts = templated(rng, 3)
        sp = SamplingParams(max_new_tokens=10)
        if "grammar" in kw:
            sp = SamplingParams(max_new_tokens=10, eos_token_id=EOS,
                                grammar=GrammarSpec(kind="regex",
                                                    pattern="[A-C]+"))
    else:
        prompts = [rng.randint(1, vocab, size=5).astype(np.int64),
                   rng.randint(1, vocab, size=40).astype(np.int64)]
        sp = SamplingParams(max_new_tokens=5)
    eng.generate(prompts, sp)
    eng.drain()
    S, W, C = eng.num_slots, eng.chunk_len, eng._head_cols
    assert C == (1 + min(eng.spec.k, W - 1) if "spec" in kw else 1)
    kinds_of_rows = set()
    accepted = 0
    for tokens, q_len, is_decode, want, out in referee.steps:
        q_len, is_decode = np.asarray(q_len), np.asarray(is_decode)
        kinds_of_rows.add((bool((is_decode & (q_len > 0)).any()),
                           bool((~is_decode & (q_len > 0)).any()),
                           bool((q_len == 0).any())))
        nxt, accept, new_last = (np.asarray(x) for x in want)
        _, _, got_last, got_nxt, got_accept = out
        got_last = np.asarray(got_last)
        np.testing.assert_array_equal(np.asarray(got_nxt), nxt)
        np.testing.assert_array_equal(np.asarray(got_accept)[:S], accept)
        np.testing.assert_allclose(got_last, new_last, rtol=1e-6,
                                   atol=1e-6)
        live = q_len > 0
        np.testing.assert_array_equal(got_last[live].argmax(-1),
                                      new_last[live].argmax(-1))
        accepted += int(accept.sum())
    if "spec" in kw:
        assert accepted > 0           # drafts were verified and kept
    else:
        # a step held a decoding row, a prefill row and an idle one
        assert (True, True, True) in kinds_of_rows
    snap = eng.metrics.snapshot()
    n = len(referee.steps)
    assert snap["step_rows_total"] == n * S * W
    assert snap["lm_head_rows_total"] == n * S * C


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_step_computes_no_full_width_logits(kind):
    eng = engine(kind, num_slots=2)
    eng.generate([np.arange(1, 24, dtype=np.int64)],
                 SamplingParams(max_new_tokens=3))
    text = eng.lowered_unified_step().as_text()
    S, W = eng.num_slots, eng.chunk_len
    V = int(eng._last_logits.shape[-1])
    assert re.search(rf"tensor<{S}x1x{V}x", text)
    assert not re.search(rf"tensor<{S}x{W}x{V}x", text)
