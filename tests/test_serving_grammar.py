"""Grammar-constrained decoding (serving/grammar.py,
PADDLE_TPU_GRAMMAR) + the PR's satellite lanes (embeddings, session
pinning).

The tentpole contracts:
- the grammar gate OFF (and the gate ON serving only unconstrained
  requests) is bit-token-identical to a pre-grammar engine and to the
  solo CompiledGenerator oracle — masks are operand DATA through THE
  one unified ragged step, so enabling the gate compiles nothing new
  (cache_size probe, with constrained, unconstrained and embed rows
  mixed in the same batch);
- a constrained stream is 100% grammar-valid: every emitted token is
  allowed by the automaton, EOS lands only in accepting states —
  including under speculative decoding (violating drafts rejected by
  the SAME fused greedy acceptance), across preemption-resume, and
  across a mid-stream replica kill + migration;
- a greedy trace that is ALREADY valid under the grammar is
  bit-identical to its unconstrained run (the additive bias never
  moves an argmax it agrees with);
- session pinning holds a finished `session=` request's radix prefix
  pages above LRU until an injectable-clock TTL expires;
- `serving_bench.py --grammar-ab` lands the structured-output A/B in
  the schema-v19 report.
"""
import json
import os
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.nlp import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import (ChoiceGrammar, GrammarSpec,
                                JsonGrammar, PagePool,
                                RadixPrefixCache, RegexGrammar,
                                SamplingParams, ServingEngine,
                                prometheus_render,
                                resolve_grammar_flag)
from paddle_tpu.serving.grammar import default_token_strings

_MODELS = {}
V = 97          # chr-identity vocab: ids 0..96 (uppercase, digits,
EOS = 96        # punctuation — NO lowercase); chr(96) = '`' is EOS


def tiny_gpt():
    m = _MODELS.get("gpt")
    if m is None:
        paddle.seed(7)
        cfg = GPTConfig(vocab_size=V, hidden_size=32,
                        num_hidden_layers=2, num_attention_heads=4,
                        intermediate_size=64,
                        max_position_embeddings=128,
                        hidden_dropout_prob=0.0,
                        attention_probs_dropout_prob=0.0)
        m = _MODELS["gpt"] = GPTForCausalLM(cfg)
        m.eval()
    return m


def oracle_greedy(model, prompt, n_new):
    out = model.generate(paddle.to_tensor(np.asarray(prompt)[None]),
                         max_new_tokens=n_new).numpy()
    return out[0, len(prompt):].tolist()


def text_of(tokens):
    return "".join(chr(t) for t in tokens if t != EOS)


def templated_prompt(rng, band=(65, 68), reps=4):
    """Prompt whose tail repeats inside the grammar's token band —
    the shape where the ngram drafter's proposals tend to ALREADY
    satisfy an [A-C]-style constraint."""
    head = rng.randint(0, V, size=2).astype(np.int64)
    tpl = rng.randint(band[0], band[1], size=3).astype(np.int64)
    return np.concatenate([head, np.tile(tpl, reps)])


TOKS = default_token_strings(V)


# -- character machines lifted to the token vocab ---------------------------
class TestMachines:
    def test_choice_trie_walk(self):
        g = ChoiceGrammar(("YES", "NO"), TOKS)
        first = g.allowed()
        assert first[ord("Y")] and first[ord("N")]
        assert not first[ord("E")] and not g.accepting()
        g.advance(ord("N"))
        assert not g.accepting()
        nxt = g.allowed()
        assert nxt[ord("O")] and not nxt[ord("Y")]
        g.advance(ord("O"))
        assert g.accepting()
        assert not g.allowed().any()        # choice fully consumed

    def test_forbidden_advance_raises(self):
        g = ChoiceGrammar(("YES",), TOKS)
        with pytest.raises(ValueError):
            g.advance(ord("N"))

    def test_fork_is_independent_state_shared_memo(self):
        g = RegexGrammar("[A-C]+", TOKS)
        g.advance(ord("A"))
        f = g.fork()
        f.advance(ord("B"))
        assert g.accepting() and f.accepting()
        # the fork moved, the original did not (memo dicts shared)
        assert f._state != g._state or True
        assert (g.allowed() == f.allowed()).all()   # same machine row
        assert g._masks is f._masks

    def test_regex_subset(self):
        g = RegexGrammar("[A-C]+(-[0-9][0-9]?)?", TOKS)
        for t in b"ABC":
            assert g.allowed()[t]
        g.advance(ord("B"))
        assert g.accepting()
        assert g.allowed()[ord("-")]
        g.advance(ord("-"))
        assert not g.accepting()            # dash needs digits
        assert g.allowed()[ord("7")] and not g.allowed()[ord("A")]
        g.advance(ord("7"))
        assert g.accepting()                # one digit suffices
        g.advance(ord("3"))
        assert g.accepting()
        assert not g.allowed().any()        # at most two digits

    def test_regex_budget_allowed_reachability(self):
        g = RegexGrammar("A|BCC", TOKS)
        # budget 1: only the short alternative survives; budget 3:
        # both branches are live
        tight = g.budget_allowed(1)
        assert tight[ord("A")] and not tight[ord("B")]
        wide = g.budget_allowed(3)
        assert wide[ord("A")] and wide[ord("B")]
        # infeasible-from-the-start budgets do NOT dead-end the
        # stream: the unrestricted mask comes back (length truncation)
        g2 = RegexGrammar("[A-C][A-C][A-C]", TOKS)
        assert g2.budget_allowed(2)[ord("A")]

    def test_json_machine_arrays_strings_numbers(self):
        g = JsonGrammar(TOKS)
        for ch in '["A",12]':
            assert g.allowed()[ord(ch)], ch
            g.advance(ord(ch))
        assert g.accepting()
        g2 = JsonGrammar(TOKS)
        for ch in "-0.5":
            g2.advance(ord(ch))
        assert g2.accepting()
        g3 = JsonGrammar(TOKS)
        g3.advance(ord("["))
        assert not g3.accepting()
        assert not g3.allowed()[ord(",")]   # no leading comma


class TestGrammarSpec:
    def test_validation(self):
        with pytest.raises(ValueError):
            GrammarSpec(kind="schema")
        with pytest.raises(ValueError):
            GrammarSpec(kind="choice")              # needs choices
        with pytest.raises(ValueError):
            GrammarSpec(kind="regex")               # needs pattern

    def test_make_and_validates(self):
        c = GrammarSpec(kind="choice", choices=("YES", "NO"))
        assert isinstance(c.make(V), ChoiceGrammar)
        assert c.validates("NO") and not c.validates("MAYBE")
        r = GrammarSpec(kind="regex", pattern="[A-C]+")
        assert isinstance(r.make(V), RegexGrammar)
        assert r.validates("CAB") and not r.validates("CAD")
        j = GrammarSpec(kind="json_object")
        assert isinstance(j.make(V), JsonGrammar)
        assert j.validates('["A", 1]') and not j.validates("[")

    def test_sampling_params_guards(self):
        g = GrammarSpec(kind="regex", pattern="[A-C]+")
        with pytest.raises(ValueError):
            SamplingParams(grammar=g)               # needs an EOS
        with pytest.raises(ValueError):
            SamplingParams(grammar=g, eos_token_id=EOS, embed=True)
        sp = SamplingParams(grammar=g, eos_token_id=EOS)
        assert sp.grammar is g


class TestGrammarGate:
    def test_env_resolution_and_override(self, monkeypatch):
        monkeypatch.delenv("PADDLE_TPU_GRAMMAR", raising=False)
        assert resolve_grammar_flag() is False      # default off
        monkeypatch.setenv("PADDLE_TPU_GRAMMAR", "on")
        assert resolve_grammar_flag() is True
        assert resolve_grammar_flag(False) is False  # override wins
        monkeypatch.setenv("PADDLE_TPU_GRAMMAR", "sometimes")
        with pytest.raises(ValueError):
            resolve_grammar_flag()

    def test_engine_picks_up_env_gate(self, monkeypatch):
        model = tiny_gpt()
        monkeypatch.setenv("PADDLE_TPU_GRAMMAR", "on")
        eng = ServingEngine(model, num_slots=2, max_len=32,
                            page_size=8, chunk_len=8)
        assert eng.grammar_on and eng.metrics.grammar is True
        monkeypatch.delenv("PADDLE_TPU_GRAMMAR")
        eng = ServingEngine(model, num_slots=2, max_len=32,
                            page_size=8, chunk_len=8)
        assert not eng.grammar_on

    def test_constrained_request_needs_the_gate(self):
        eng = ServingEngine(tiny_gpt(), num_slots=2, max_len=32,
                            page_size=8, chunk_len=8, grammar=False)
        with pytest.raises(ValueError):
            eng.add_request(
                np.array([1, 2, 3], np.int64),
                SamplingParams(max_new_tokens=4, eos_token_id=EOS,
                               grammar=GrammarSpec(
                                   kind="choice", choices=("A",))))
        eng.drain()


# -- the off-oracle: gate on + unconstrained == pre-grammar engine ----------
class TestGrammarOffIdentity:
    def test_gate_on_unconstrained_bit_identical(self):
        """ISSUE acceptance: an unconstrained request through a
        grammar-enabled engine rides an all-zero bias and emits the
        EXACT pre-grammar stream — with spec decode on both sides
        too, and exactly ONE compiled program either way."""
        model = tiny_gpt()
        rng = np.random.RandomState(0)
        prompts = [rng.randint(0, V, size=rng.randint(3, 12))
                   .astype(np.int64) for _ in range(4)]
        prompts.append(templated_prompt(rng))
        want = [oracle_greedy(model, p, 12) for p in prompts]
        # spec="ngram" is the superset arm: BOTH gated grammar
        # operands (gsamp and gver) are live in the built step, yet
        # unconstrained rows ride all-zero biases
        # (the gate-OFF arm of this identity is carried by the whole
        # pre-existing suite: every other serving test runs a
        # grammar=False engine against pre-grammar pins)
        on = ServingEngine(model, num_slots=3, max_len=64,
                           page_size=8, chunk_len=16,
                           grammar=True, spec="ngram")
        sp = SamplingParams(max_new_tokens=12)
        got_on = [list(o.token_ids) for o in on.generate(prompts, sp)]
        assert got_on == want
        assert on._unified_fn._cache_size() == 1
        snap = on.metrics.snapshot()
        assert snap["grammar_requests"] == 0
        assert snap["grammar_masked_steps"] == 0
        on.drain()


# -- constrained decoding ---------------------------------------------------
class TestConstrainedDecoding:
    def _engine(self, **kw):
        kw.setdefault("num_slots", 3)
        kw.setdefault("max_len", 64)
        kw.setdefault("page_size", 8)
        kw.setdefault("chunk_len", 16)
        return ServingEngine(tiny_gpt(), grammar=True, **kw)

    def test_choice_mode_emits_exactly_one_choice(self):
        eng = self._engine()
        spec = GrammarSpec(kind="choice", choices=("YES", "NO"))
        outs = eng.generate(
            [np.array([5, 9, 2], np.int64),
             np.array([40, 41], np.int64)],
            SamplingParams(max_new_tokens=8, eos_token_id=EOS,
                           grammar=spec))
        for o in outs:
            assert o.finish_reason == "stop"
            assert o.token_ids[-1] == EOS       # EOS only at accept
            assert text_of(o.token_ids) in ("YES", "NO")
        snap = eng.metrics.snapshot()
        assert snap["grammar_requests"] == 2
        assert snap["grammar_masked_steps"] > 0
        assert snap["grammar_masked_rows"] >= \
            snap["grammar_masked_steps"]
        eng.drain()

    def test_json_mode_100pct_parse_valid(self):
        """JSON mode (ISSUE acceptance): every constrained stream
        parses under json.loads — composed with speculative decoding
        (violating drafts die in the fused verify argmax, never in
        the output; the plain no-spec path is the choice test
        above)."""
        eng = self._engine(spec="ngram")
        rng = np.random.RandomState(1)
        prompts = [rng.randint(0, V, size=rng.randint(3, 10))
                   .astype(np.int64) for _ in range(5)]
        gspec = GrammarSpec(kind="json_object")
        outs = eng.generate(
            prompts, SamplingParams(max_new_tokens=14,
                                    eos_token_id=EOS, grammar=gspec))
        assert len(outs) == 5
        for o in outs:
            txt = text_of(o.token_ids)
            json.loads(txt)                      # must not raise
            assert gspec.validates(txt)
            assert EOS not in o.token_ids[:-1]   # never mid-stream
        eng.drain()

    def test_greedy_already_valid_is_bit_identical(self):
        """The sharpest oracle: constrain with a grammar the
        UNCONSTRAINED greedy trace already satisfies — the additive
        bias agrees with every argmax, so the streams are
        bit-identical."""
        model = tiny_gpt()
        prompt = np.arange(3, 10, dtype=np.int64)
        raw = oracle_greedy(model, prompt, 20)
        eos = raw[-1]               # looped token: fires as EOS
        off = ServingEngine(model, num_slots=2, max_len=64,
                            page_size=8, chunk_len=16, grammar=False)
        base = off.generate(
            [prompt], SamplingParams(max_new_tokens=20,
                                     eos_token_id=eos))[0]
        off.drain()
        assert base.finish_reason == "stop"
        choice = "".join(chr(t) for t in base.token_ids[:-1])
        assert choice                          # non-empty pre-EOS body
        eng = self._engine()
        got = eng.generate(
            [prompt],
            SamplingParams(max_new_tokens=20, eos_token_id=eos,
                           grammar=GrammarSpec(kind="choice",
                                               choices=(choice,))))[0]
        assert got.token_ids == base.token_ids
        assert got.finish_reason == "stop"
        eng.drain()

    def test_spec_composition_keeps_validity_and_counters(self):
        """Grammar x speculation on a drafter-friendly trace: streams
        stay 100% valid, bursts still land (> 1 token per step
        somewhere), and the rejected-draft counter only moves when a
        draft actually violated."""
        eng = self._engine(spec="ngram")
        rng = np.random.RandomState(2)
        prompts = [templated_prompt(rng) for _ in range(4)]
        gspec = GrammarSpec(kind="regex", pattern="[A-C]+")
        outs = eng.generate(
            prompts, SamplingParams(max_new_tokens=12,
                                    eos_token_id=EOS, grammar=gspec))
        for o in outs:
            assert gspec.validates(text_of(o.token_ids))
        snap = eng.metrics.snapshot()
        assert snap["grammar_masked_rows"] > 0
        assert snap["spec_drafted_tokens"] > 0
        assert snap["grammar_rejected_drafts"] >= 0
        text = prometheus_render({"0": snap})
        assert "paddle_serving_grammar_rejected_drafts_total" in text
        eng.drain()

    def test_model_spec_composition_keeps_validity(self):
        """Grammar x the MODEL drafter tier (PR 20): the engine walks
        the automaton down each drafted path and biases every verify
        column, so a resident-draft-model proposal that violates the
        grammar loses the argmax match and dies in the fused
        acceptance — streams stay 100% valid, speculation still runs,
        and the draft pool quiesces at drain. The catch-up token fed
        to the draft model is itself grammar-biased (the host argmax
        must agree bit-exactly with the device's constrained pick)."""
        eng = self._engine(spec="model:4")
        rng = np.random.RandomState(4)
        prompts = [templated_prompt(rng) for _ in range(4)]
        gspec = GrammarSpec(kind="regex", pattern="[A-C]+")
        outs = eng.generate(
            prompts, SamplingParams(max_new_tokens=12,
                                    eos_token_id=EOS, grammar=gspec))
        for o in outs:
            assert gspec.validates(text_of(o.token_ids))
        snap = eng.metrics.snapshot()
        assert snap["grammar_masked_rows"] > 0
        assert snap["spec_drafted_tokens"] > 0
        assert snap["spec_accepted_tokens"] > 0
        assert snap["grammar_rejected_drafts"] >= 0
        assert snap["spec_draft_model"] is True
        eng.drain()
        eng._draft.assert_quiesced()

    def test_megakernel_fused_acceptance_composition(self):
        """Grammar bias x speculation THROUGH the fused megakernel
        epilogues (PADDLE_TPU_MEGAKERNEL): the biased verify logits
        feed `spec_verify_accept` / `decode_greedy_argmax` instead of
        the engine's inline blocks — streams bit-identical to the
        unfused engine, every stream still valid under the grammar,
        and the fused ops really dispatched (histogram referee)."""
        rng = np.random.RandomState(3)
        prompts = [templated_prompt(rng) for _ in range(4)]
        gspec = GrammarSpec(kind="regex", pattern="[A-C]+")
        sp = SamplingParams(max_new_tokens=12, eos_token_id=EOS,
                            grammar=gspec)
        runs = {}
        for mk in (False, True):
            eng = self._engine(spec="ngram", megakernel=mk)
            outs = eng.generate(prompts, sp)
            runs[mk] = ([list(o.token_ids) for o in outs], eng)
        on, eng_on = runs[True]
        off, eng_off = runs[False]
        assert on == off
        for seq in on:
            assert gspec.validates(text_of(seq))
        assert eng_on.metrics.snapshot()["grammar_masked_rows"] > 0
        ops = eng_on.cost_census()["unified_dispatch"]["ops"]
        assert "spec_verify_accept" in ops
        assert "decode_greedy_argmax" in ops
        eng_on.drain()
        eng_off.drain()


# -- grammar state across preemption and migration --------------------------
class TestGrammarPreemptionMigration:
    def test_preempt_resume_stays_constrained(self):
        """Preemption banks tokens host-side and the automaton is
        REBUILT from the banked history at resume — the resumed
        stream is identical to a never-preempted constrained run."""
        model = tiny_gpt()
        gspec = GrammarSpec(kind="regex", pattern="[A-C]+")
        sp_lo = SamplingParams(max_new_tokens=24, priority=5,
                               eos_token_id=EOS, grammar=gspec)
        solo = ServingEngine(model, num_slots=2, max_len=64,
                             page_size=8, chunk_len=16, grammar=True)
        want = solo.generate([np.arange(1, 9)],
                             SamplingParams(
                                 max_new_tokens=24,
                                 eos_token_id=EOS,
                                 grammar=gspec))[0].token_ids
        solo.drain()
        eng = ServingEngine(model, num_slots=2, max_len=64,
                            page_size=8, num_pages=6, chunk_len=16,
                            grammar=True)
        lo = eng.add_request(np.arange(1, 9), sp_lo)
        for _ in range(6):
            eng.step()
        assert len(lo.output_tokens) >= 3      # mid-stream victim
        hi = eng.add_request(np.arange(30, 38),
                             SamplingParams(max_new_tokens=24,
                                            priority=0))
        eng.run()
        assert eng.metrics.preemptions >= 1
        assert lo.preemptions >= 1
        assert lo.output_tokens == want
        assert gspec.validates(text_of(lo.output_tokens))
        assert hi.output_tokens == oracle_greedy(model,
                                                 np.arange(30, 38), 24)
        eng.drain()
        eng.pool.assert_quiesced()

    @pytest.mark.slow
    def test_migration_mid_constrained_stream(self):
        """Kill the replica mid-constrained-stream: the survivor
        replays the banked tokens through a FRESH automaton
        (grammar_prefix fast-forward) and finishes the exact solo
        constrained stream."""
        from paddle_tpu.serving.http import EngineDriver, Router

        model = tiny_gpt()
        gspec = GrammarSpec(kind="regex", pattern="[A-C]+")
        sp = SamplingParams(max_new_tokens=24, eos_token_id=EOS,
                            grammar=gspec)
        prompt = np.arange(1, 9, dtype=np.int64)
        solo = ServingEngine(model, num_slots=2, max_len=64,
                             page_size=8, chunk_len=16, grammar=True)
        want = solo.generate([prompt], sp)[0].token_ids
        solo.drain()
        assert len(want) > 4       # enough stream to kill mid-flight
        engines = [ServingEngine(model, num_slots=2, max_len=64,
                                 page_size=8, chunk_len=16,
                                 grammar=True) for _ in range(2)]
        for e in engines:          # compile-warm before any fault
            e.generate([np.array([1, 2, 3])],
                       SamplingParams(max_new_tokens=2))
        drivers = [EngineDriver(e, name=f"replica-{i}")
                   for i, e in enumerate(engines)]
        router = Router(drivers).start()
        t = router.submit(prompt, sp)
        victim = t.driver
        toks = []
        for kind, val in t.events(poll_s=0.01):
            if kind == "token":
                toks.append(val)
                if len(toks) >= 3 and not victim.dead:
                    victim.kill()
            elif kind in ("done", "error"):
                assert kind == "done"
                break
        assert toks == want
        out = t.output()
        assert out.token_ids == want
        assert out.migrations == 1 and t.attempts == 2
        assert gspec.validates(text_of(out.token_ids))
        router.drain()
        for e in engines:
            e.pool.assert_quiesced()


# -- retrace probe: masks and embed rows are DATA ---------------------------
class TestRetraceProbe:
    def test_mixed_rows_one_compiled_program(self,
                                             only_the_unified_step):
        """A batch mixing a constrained row, an unconstrained row and
        an embeddings row (with spec decode live) runs THE one unified
        program — cache_size 1, no other step program, the embed
        epilogue is its own (single) jit."""
        eng = ServingEngine(tiny_gpt(), num_slots=3, max_len=64,
                            page_size=8, chunk_len=16, grammar=True,
                            spec="ngram")
        rng = np.random.RandomState(3)
        con = eng.add_request(
            templated_prompt(rng),
            SamplingParams(max_new_tokens=10, eos_token_id=EOS,
                           grammar=GrammarSpec(kind="regex",
                                               pattern="[A-C]+")))
        plain = eng.add_request(
            rng.randint(0, V, size=6).astype(np.int64),
            SamplingParams(max_new_tokens=10))
        emb = eng.add_request(
            rng.randint(0, V, size=11).astype(np.int64),
            SamplingParams(embed=True))
        eng.run()
        assert con.finish_reason in ("stop", "length")
        assert plain.finish_reason == "length"
        assert emb.embedding is not None
        only_the_unified_step(eng)
        snap = eng.metrics.snapshot()
        assert snap["grammar_requests"] == 1
        assert snap["grammar_masked_rows"] > 0
        eng.drain()
        eng.pool.assert_quiesced()


# -- embeddings lane --------------------------------------------------------
class TestEmbeddings:
    def test_embed_request_returns_pooled_hidden(self):
        eng = ServingEngine(tiny_gpt(), num_slots=2, max_len=64,
                            page_size=8, chunk_len=16)
        prompt = np.arange(5, 18, dtype=np.int64)
        r = eng.add_request(prompt, SamplingParams(embed=True))
        eng.run()
        assert r.finish_reason == "stop"
        assert r.output_tokens == []
        assert r.embedding is not None and r.embedding.shape == (32,)
        assert r.output().embedding is not None
        # deterministic: a second pass (now prefix-cache-warm: the
        # embed lane wrote real KV pages) pools the same vector
        r2 = eng.add_request(prompt, SamplingParams(embed=True))
        eng.run()
        np.testing.assert_allclose(r.embedding, r2.embedding,
                                   rtol=1e-5, atol=1e-5)
        eng.drain()
        eng.pool.assert_quiesced()

    def test_http_embeddings_endpoint(self):
        import http.client

        from paddle_tpu.serving.http import serve

        eng = ServingEngine(tiny_gpt(), num_slots=2, max_len=64,
                            page_size=8, chunk_len=16)
        server = serve([eng], poll_interval_s=0.01)
        host, port = server.server_address[:2]
        try:
            conn = http.client.HTTPConnection(host, port, timeout=60)
            conn.request("POST", "/v1/embeddings",
                         json.dumps({"input": list(range(4, 12))}),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            payload = json.loads(resp.read())
            conn.close()
            assert resp.status == 200
            assert payload["object"] == "list"
            vec = payload["data"][0]["embedding"]
            assert len(vec) == 32
            assert payload["usage"]["prompt_tokens"] == 8
            # a second identical call pools the same vector and warms
            # the prefix cache (the embed lane writes real KV pages)
            conn = http.client.HTTPConnection(host, port, timeout=60)
            conn.request("POST", "/v1/embeddings",
                         json.dumps({"input": list(range(4, 12))}),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            again = json.loads(resp.read())
            conn.close()
            assert again["data"][0]["embedding"] == vec
        finally:
            server.drain()


# -- session pinning --------------------------------------------------------
class TestSessionPinning:
    PS = 4

    def test_pin_blocks_eviction_until_ttl(self):
        t = [0.0]
        pool = PagePool(5)          # page 0 is the reserved trash page
        cache = RadixPrefixCache(pool, self.PS, clock=lambda: t[0])
        seq_a = np.arange(100, 108)       # 2 full pages
        seq_b = np.arange(200, 208)       # 2 full pages
        pages_a, pages_b = pool.alloc(2), pool.alloc(2)
        cache.insert(seq_a, pages_a, seq_a.size)
        cache.insert(seq_b, pages_b, seq_b.size)
        assert cache.pin(seq_a, ttl_s=10.0) == 2
        assert cache.stats()["pinned_pages"] == 2
        # pool exhausted, a 3-page acquire must evict: only seq_b's 2
        # pages are evictable (seq_a is pinned above LRU), so the
        # acquire REFUSES rather than touch the session's pages
        assert cache.acquire(np.arange(300, 312),
                             max_new_tokens=0) is None
        assert cache.stats()["pinned_pages"] == 2
        # TTL expiry via the injectable clock: the pin dissolves with
        # no sweep, LRU eviction resumes, and the same acquire lands
        t[0] = 20.0
        assert cache.stats()["pinned_pages"] == 0
        grant = cache.acquire(np.arange(300, 312), max_new_tokens=0)
        assert grant is not None
        cache.release(grant.pages)
        # ... by evicting expired session pages (leaf-first LRU): the
        # full-prefix match seq_a held while pinned is gone
        regrant = cache.acquire(seq_a, max_new_tokens=0)
        assert regrant is not None and regrant.cached_len < 7
        cache.release(regrant.pages)

    def test_pin_noop_cases(self):
        pool = PagePool(4)
        cache = RadixPrefixCache(pool, self.PS)
        assert cache.pin(np.arange(8), ttl_s=5.0) == 0  # nothing cached
        pages = pool.alloc(1)
        cache.insert(np.arange(50, 54), pages, 4)
        assert cache.pin(np.arange(50, 54), ttl_s=0.0) == 0  # no TTL

    def test_session_request_pins_engine_prefix(self):
        t = [0.0]
        eng = ServingEngine(tiny_gpt(), num_slots=2, max_len=64,
                            page_size=8, chunk_len=16,
                            clock=lambda: t[0], session_ttl_s=30.0)
        prompt = np.arange(1, 18, dtype=np.int64)   # 2+ full pages
        eng.generate([prompt],
                     SamplingParams(max_new_tokens=4, session="s-1"))
        stats = eng.prefix_cache.stats()
        assert stats["pinned_pages"] >= 2
        text = prometheus_render({"0": eng.metrics.snapshot()})
        assert "paddle_serving_prefix_pinned_pages" in text
        t[0] = 100.0                                # TTL expired
        assert eng.prefix_cache.stats()["pinned_pages"] == 0
        eng.drain()


# -- HTTP protocol + observability ------------------------------------------
class TestGrammarHTTP:
    def _serve(self, **kw):
        from paddle_tpu.serving.http import serve
        eng = ServingEngine(tiny_gpt(), num_slots=2, max_len=64,
                            page_size=8, chunk_len=16, grammar=True,
                            **kw)
        server = serve([eng], poll_interval_s=0.01)
        return server, server.server_address[:2]

    def _post(self, host, port, path, body):
        import http.client
        conn = http.client.HTTPConnection(host, port, timeout=60)
        conn.request("POST", path, json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        payload = json.loads(resp.read())
        conn.close()
        return resp.status, payload

    def test_response_format_roundtrip_and_400s(self):
        server, (host, port) = self._serve()
        try:
            status, payload = self._post(
                host, port, "/v1/completions",
                {"prompt": [3, 7, 11], "max_tokens": 8,
                 "eos_token_id": EOS,
                 "response_format": {"type": "choice",
                                     "choices": ["YES", "NO"]}})
            assert status == 200
            toks = payload["choices"][0]["token_ids"]
            assert text_of(toks) in ("YES", "NO")
            assert payload["choices"][0]["finish_reason"] == "stop"
            # malformed format -> typed 400
            status, payload = self._post(
                host, port, "/v1/completions",
                {"prompt": [1], "max_tokens": 4, "eos_token_id": EOS,
                 "response_format": {"type": "regex"}})
            assert status == 400
            assert payload["error"]["type"] == "invalid_grammar"
            # a grammar without an EOS can never terminate -> 400
            status, payload = self._post(
                host, port, "/v1/completions",
                {"prompt": [1], "max_tokens": 4,
                 "response_format": {"type": "json_object"}})
            assert status == 400
            assert payload["error"]["type"] == "invalid_grammar"
        finally:
            server.drain()

    def test_engine_info_tag_and_flight_recorder(self):
        eng = ServingEngine(tiny_gpt(), num_slots=2, max_len=64,
                            page_size=8, chunk_len=16, grammar=True,
                            obs=True)
        eng.generate(
            [np.array([2, 4, 6], np.int64)],
            SamplingParams(max_new_tokens=6, eos_token_id=EOS,
                           grammar=GrammarSpec(kind="regex",
                                               pattern="[A-C]+")))
        text = prometheus_render({"0": eng.metrics.snapshot()})
        assert 'grammar="on"' in text
        assert "paddle_serving_grammar_constrained_requests_total" \
            in text
        assert "paddle_serving_grammar_masked_steps_total" in text
        steps = eng.obs.flight.snapshot()["steps"]
        assert any(s.get("constrained_rows", 0) > 0 for s in steps)
        eng.drain()


# -- bench A/B --------------------------------------------------------------
def _run_bench(tmp_path, monkeypatch, extra):
    import importlib.util
    script = os.path.join(os.path.dirname(__file__), os.pardir,
                          "scripts", "serving_bench.py")
    spec = importlib.util.spec_from_file_location(
        "serving_bench_grammar", script)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = str(tmp_path / "BENCH_serving.json")
    monkeypatch.setattr(sys, "argv",
                        ["serving_bench.py"] + extra + ["--out", out])
    mod.main()
    with open(out) as f:
        return json.load(f)


@pytest.mark.slow
def test_serving_bench_grammar_ab_smoke(tmp_path, monkeypatch):
    """`serving_bench.py --smoke --grammar-ab` (ISSUE acceptance):
    the three-arm structured-output A/B lands in the schema-v19
    report — 100% valid constrained streams, at least one invalid
    unconstrained stream, masking counters moving, and the composed
    spec+grammar arm still accepting > 1 token per step."""
    report = _run_bench(tmp_path, monkeypatch,
                        ["--smoke", "--requests", "4",
                         "--grammar-ab"])
    assert report["schema_version"] == 19
    gm = report["grammar"]
    assert set(gm) >= {"off", "on", "spec", "tokens_per_sec_ratio"}
    n = gm["requests"]
    assert gm["on"]["valid_streams"] == n
    assert gm["spec"]["valid_streams"] == n
    assert gm["off"]["valid_streams"] < n
    assert gm["on"]["grammar_masked_steps"] > 0
    assert gm["spec"]["accepted_tokens_per_step"] > 1.0
