"""Speculative decoding (serving/spec.py, PADDLE_TPU_SPEC_DECODE).

The tentpole contracts:
- greedy outputs with speculation ON are bit-token-identical to
  speculation OFF and to the solo CompiledGenerator oracle — including
  EOS landing mid-burst, page pressure with LRU eviction live, the
  prefix cache on/off, sampled (non-speculating) slot neighbors, and a
  throttled token budget — the same oracle pattern as
  PADDLE_TPU_PAGED_ATTN / PADDLE_TPU_PREFIX_CACHE;
- enabling speculation adds NO compiled program: drafting is
  host-side, the verify pass rides THE one unified ragged step
  (cache_size probe), and a spec-off engine compiles the exact same
  single program;
- speculation composes with the fault layers: poison-quarantine
  bisection mid-speculation never leaks a drafted-but-unverified
  token, and a stream migrated after a partially-accepted step resumes
  token-identically with its drafter re-seeded from the banked
  history;
- the multi-token emission plumbing holds: SSE framing stays one
  token per frame, `usage.accepted_draft_tokens` surfaces over HTTP
  and merges across migration attempts, and inter-token latency
  divides each burst's step gap instead of recording zeros.
"""
import json
import os
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.nlp import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import (Drafter, ModelDrafter, NgramDrafter,
                                SamplingParams, Scheduler,
                                ServingEngine, SpecConfig,
                                FaultInjector, make_draft_model,
                                prometheus_render,
                                resolve_spec_config)
from paddle_tpu.serving.request import Request, RequestState

_MODELS = {}


def tiny_gpt():
    m = _MODELS.get("gpt")
    if m is None:
        paddle.seed(7)
        cfg = GPTConfig(vocab_size=97, hidden_size=32,
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


def mixed_prompts(rng, n=6):
    """Random prompts of mixed length — greedy decode of the tiny
    model settles into short loops fast, which is exactly the history
    shape the n-gram drafter wins on."""
    return [rng.randint(0, 97, size=rng.randint(3, 14))
            .astype(np.int64) for _ in range(n)]


def templated_prompt(rng, reps=3, tpl_len=6):
    """Code/template-shaped prompt: a repeating block, the
    prompt-lookup sweet spot (drafting can win from the FIRST decode
    step, not just once the output loops)."""
    head = rng.randint(0, 97, size=2).astype(np.int64)
    tpl = rng.randint(0, 97, size=tpl_len).astype(np.int64)
    return np.concatenate([head, np.tile(tpl, reps)])


# -- drafter units ----------------------------------------------------------
class TestNgramDrafter:
    def test_proposes_continuation_of_most_recent_match(self):
        d = NgramDrafter(max_ngram=3)
        out = d.propose(np.array([1, 2, 3, 9, 1, 2, 3]), 3)
        assert out.tolist() == [9, 1, 2]

    def test_periodic_tail_unrolls_full_k(self):
        # history ends in a period-1 loop: the overlapping match
        # extrapolates the loop to all k drafts instead of stopping
        # where history runs out
        d = NgramDrafter()
        out = d.propose(np.array([5, 6, 7, 7, 7]), 4)
        assert out.tolist() == [7, 7, 7, 7]

    def test_period_two_loop(self):
        d = NgramDrafter()
        out = d.propose(np.array([9, 1, 2, 1, 2, 1, 2]), 5)
        assert out.tolist() == [1, 2, 1, 2, 1]

    def test_no_match_and_degenerate_inputs_are_empty(self):
        d = NgramDrafter()
        assert d.propose(np.array([1, 2, 3, 4]), 2).size == 0
        assert d.propose(np.array([1, 2, 3, 2]), 0).size == 0
        assert d.propose(np.array([5]), 4).size == 0

    def test_min_ngram_bounds_matching(self):
        # with min_ngram=2 a lone unigram repeat is not evidence
        assert NgramDrafter(min_ngram=2).propose(
            np.array([1, 5, 1]), 2).size == 0
        assert NgramDrafter(min_ngram=1).propose(
            np.array([1, 5, 1]), 2).size == 2

    def test_validation(self):
        with pytest.raises(ValueError):
            NgramDrafter(min_ngram=0)
        with pytest.raises(ValueError):
            NgramDrafter(max_ngram=1, min_ngram=2)
        with pytest.raises(ValueError):
            SpecConfig(k=0)

    def test_budget_caps_proposals(self):
        """`budget` is the request's remaining emission slots past the
        sampled token: drafting deeper is guaranteed-dead verify work,
        so the drafter stops there. None keeps the unlimited legacy
        behavior; a budget larger than k changes nothing."""
        d = NgramDrafter()
        hist = np.array([5, 6, 7, 7, 7])
        assert d.propose(hist, 4, budget=2).tolist() == [7, 7]
        assert d.propose(hist, 4, budget=0).size == 0
        assert d.propose(hist, 4, budget=None).tolist() == [7, 7, 7, 7]
        assert d.propose(hist, 4, budget=9).tolist() == [7, 7, 7, 7]

    def test_legacy_two_arg_drafter_still_works_in_engine(self):
        """A pre-`budget` Drafter subclass (2-arg propose) stays
        source-compatible: the engine falls back to the legacy call
        shape and the stream stays oracle-identical."""
        class Legacy(Drafter):
            def propose(self, history, k):   # no budget kwarg
                return NgramDrafter().propose(history, k)

        model = tiny_gpt()
        rng = np.random.RandomState(21)
        prompts = [templated_prompt(rng)]
        want = [oracle_greedy(model, p, 10) for p in prompts]
        eng = ServingEngine(model, num_slots=1, max_len=64,
                            page_size=8, chunk_len=16,
                            spec=SpecConfig(k=4, drafter=Legacy))
        outs = eng.generate(prompts,
                            SamplingParams(max_new_tokens=10))
        assert [list(o.token_ids) for o in outs] == want
        assert eng.metrics.snapshot()["spec_accepted_tokens"] > 0
        eng.drain()


# -- gate resolution --------------------------------------------------------
class TestSpecGate:
    def test_env_resolution_and_override(self, monkeypatch):
        monkeypatch.delenv("PADDLE_TPU_SPEC_DECODE", raising=False)
        assert resolve_spec_config() is None             # default off
        monkeypatch.setenv("PADDLE_TPU_SPEC_DECODE", "ngram")
        cfg = resolve_spec_config()
        assert cfg is not None and cfg.mode == "ngram" and cfg.k == 4
        assert resolve_spec_config(False) is None        # override wins
        monkeypatch.setenv("PADDLE_TPU_SPEC_DECODE", "ngram:8")
        assert resolve_spec_config().k == 8
        monkeypatch.setenv("PADDLE_TPU_SPEC_DECODE", "medium")
        with pytest.raises(ValueError):
            resolve_spec_config()
        with pytest.raises(ValueError):
            resolve_spec_config("off:3")
        with pytest.raises(ValueError):
            resolve_spec_config("ngram:lots")
        with pytest.raises(TypeError):
            resolve_spec_config(42)
        own = SpecConfig(k=2)
        assert resolve_spec_config(own) is own

    def test_model_tier_resolution(self, monkeypatch):
        monkeypatch.delenv("PADDLE_TPU_SPEC_DECODE", raising=False)
        cfg = resolve_spec_config("model")
        assert cfg is not None and cfg.mode == "model" and cfg.k == 4
        assert isinstance(cfg.make_drafter(), ModelDrafter)
        assert resolve_spec_config("model:8").k == 8
        monkeypatch.setenv("PADDLE_TPU_SPEC_DECODE", "model:3")
        env_cfg = resolve_spec_config()
        assert env_cfg.mode == "model" and env_cfg.k == 3
        # the SpecConfig(drafter="model") spelling the docs advertise:
        # the tier name sets the mode tag too
        own = SpecConfig(drafter="model")
        assert own.mode == "model"
        assert isinstance(own.make_drafter(), ModelDrafter)
        # standalone ModelDrafter (outside an engine) has no draft KV
        # to decode from and proposes nothing
        assert ModelDrafter().propose(np.array([1, 2, 3]), 4).size == 0

    def test_malformed_specs_name_the_legal_forms(self):
        """Every malformed spelling raises a ValueError that spells
        out the whole legal grammar — a fat-fingered env var tells the
        operator what IS accepted, not just what broke."""
        for bad in ("model:", "model:0", "model:-1", "model:lots",
                    "ngram:x", "ngram:", "off:2", "tree"):
            with pytest.raises(ValueError) as ei:
                resolve_spec_config(bad)
            assert "legal forms" in str(ei.value), bad
            assert "model" in str(ei.value), bad
        with pytest.raises(ValueError):
            SpecConfig(drafter="tree")

    def test_engine_picks_up_model_env_gate(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_SPEC_DECODE", "model:2")
        eng = ServingEngine(tiny_gpt(), num_slots=2, max_len=32,
                            page_size=8, chunk_len=8)
        assert eng.spec is not None and eng.spec.mode == "model"
        assert eng.spec.k == 2
        assert eng._draft is not None      # draft model made resident
        assert eng.metrics.spec == "model"
        assert eng.metrics.spec_draft_model is True
        # the env-gated engine shrank its own draft from the target
        assert eng._draft.stats()["layers"] == 1

    def test_engine_picks_up_env_gate(self, monkeypatch):
        model = tiny_gpt()
        monkeypatch.setenv("PADDLE_TPU_SPEC_DECODE", "ngram:2")
        eng = ServingEngine(model, num_slots=2, max_len=32,
                            page_size=8, chunk_len=8)
        assert eng.spec is not None and eng.spec.k == 2
        assert eng.metrics.spec == "ngram"
        monkeypatch.delenv("PADDLE_TPU_SPEC_DECODE")
        eng = ServingEngine(model, num_slots=2, max_len=32,
                            page_size=8, chunk_len=8)
        assert eng.spec is None and eng.metrics.spec is None

    def test_only_greedy_requests_get_a_drafter(self):
        eng = ServingEngine(tiny_gpt(), num_slots=2, max_len=64,
                            page_size=8, chunk_len=8, spec="ngram")
        g = eng.add_request(np.array([1, 2, 3], np.int64),
                            SamplingParams(max_new_tokens=2))
        s = eng.add_request(np.array([4, 5, 6], np.int64),
                            SamplingParams(max_new_tokens=2, top_k=5))
        eng.step()      # admit
        assert g.request_id in eng._drafters
        assert s.request_id not in eng._drafters
        eng.run()
        assert eng._drafters == {}       # dropped at retirement
        eng.drain()


# -- scheduler draft packing ------------------------------------------------
class TestDraftPacking:
    def _sched(self, states):
        s = Scheduler(num_slots=len(states))
        for i, st in enumerate(states):
            if st is None:
                continue
            r = Request(f"r{i}", np.array([1, 2]), SamplingParams())
            r.state = st
            r.slot = i
            s.running[i] = r
        return s

    def test_prefill_outranks_drafts(self):
        s = self._sched([RequestState.DECODE, RequestState.DECODE,
                         RequestState.PREFILL])
        decode, grants, drafts = s.pack_tokens(
            10, 8, {2: 40}, draft_wanted={0: 4, 1: 4})
        assert decode == [0, 1]
        assert grants == {2: 8}          # prompt tokens ate the spare
        assert drafts == {}

    def test_drafts_take_leftover_spare_width_capped(self):
        s = self._sched([RequestState.DECODE, RequestState.DECODE,
                         RequestState.PREFILL])
        decode, grants, drafts = s.pack_tokens(
            20, 8, {2: 3}, draft_wanted={0: 4, 1: 10})
        assert grants == {2: 3}
        # slot 0 takes its 4; slot 1 capped at width-1=7 (the row's
        # q_len = 1 + drafts must fit the step shape)
        assert drafts == {0: 4, 1: 7}

    def test_draft_wanted_for_non_decode_slot_is_ignored(self):
        s = self._sched([RequestState.DECODE, RequestState.PREFILL])
        _, _, drafts = s.pack_tokens(20, 8, {}, draft_wanted={1: 4})
        assert drafts == {}

    def test_spare_exhaustion_throttles_drafts(self):
        s = self._sched([RequestState.DECODE, RequestState.DECODE])
        _, _, drafts = s.pack_tokens(4, 8, {},
                                     draft_wanted={0: 4, 1: 4})
        assert drafts == {0: 2}          # budget 4 - 2 decodes = 2

    def test_no_draft_dict_keeps_legacy_shape(self):
        s = self._sched([RequestState.DECODE])
        decode, grants, drafts = s.pack_tokens(8, 8, {})
        assert decode == [0] and grants == {} and drafts == {}


# -- token identity: spec on == spec off == solo oracle ---------------------
class TestSpecTokenIdentity:
    def _run(self, prompts, n_new, sampling=None, **kw):
        eng = ServingEngine(tiny_gpt(), max_len=64, page_size=8,
                            **kw)
        outs = eng.generate(
            prompts, sampling or SamplingParams(max_new_tokens=n_new))
        toks = [list(o.token_ids) for o in outs]
        eng.drain()
        eng.pool.assert_quiesced()
        return toks, outs, eng

    def test_mixed_trace_on_off_oracle(self):
        model = tiny_gpt()
        rng = np.random.RandomState(0)
        prompts = mixed_prompts(rng) + [templated_prompt(rng)]
        want = [oracle_greedy(model, p, 16) for p in prompts]
        on, outs_on, eng_on = self._run(
            prompts, 16, num_slots=3, chunk_len=16, spec="ngram")
        off, _, eng_off = self._run(
            prompts, 16, num_slots=3, chunk_len=16, spec=False)
        assert on == want and off == want
        # speculation really happened, and really paid: accepted
        # drafts committed, usage attributed, fewer steps run
        snap = eng_on.metrics.snapshot()
        assert snap["spec_drafted_tokens"] > 0
        assert snap["spec_accepted_tokens"] > 0
        assert snap["spec_tokens_per_step"]["max"] > 1
        assert snap["packed_draft_tokens"] > 0
        assert sum(o.accepted_draft_tokens for o in outs_on) \
            == snap["spec_accepted_tokens"]
        assert snap["unified_steps"] < \
            eng_off.metrics.snapshot()["unified_steps"]
        off_snap = eng_off.metrics.snapshot()
        assert off_snap["spec_drafted_tokens"] == 0
        assert off_snap["spec_tokens_per_step"]["count"] == 0

    def test_eos_mid_burst_stops_exactly_like_sequential(self):
        """EOS surfacing INSIDE an accepted burst: emission stops at
        the terminal token and drops the verified remainder — exactly
        the sequential semantics."""
        model = tiny_gpt()
        rng = np.random.RandomState(1)
        prompts = mixed_prompts(rng)
        raw = [oracle_greedy(model, p, 20) for p in prompts]
        eos = raw[0][-1]        # a looped token: hits mid-burst

        def trunc(seq):
            return (seq[:seq.index(eos) + 1] if eos in seq else seq)

        want = [trunc(s) for s in raw]
        sp = SamplingParams(max_new_tokens=20, eos_token_id=eos)
        got, outs, eng = self._run(prompts, 20, sampling=sp,
                                   num_slots=3, chunk_len=16,
                                   spec="ngram")
        assert got == want
        reasons = {o.finish_reason for o in outs}
        assert "stop" in reasons     # EOS really fired somewhere
        assert eng.metrics.snapshot()["spec_accepted_tokens"] > 0

    @pytest.mark.slow
    def test_page_pressure_prefix_cache_matrix(self):
        """The acceptance matrix: pool smaller than the trace wants
        (LRU eviction live) x prefix cache on/off x spec on/off, all
        token-identical to the oracle — draft K/V writes stay inside
        each request's own page budget even under pressure."""
        model = tiny_gpt()
        rng = np.random.RandomState(2)
        prompts = mixed_prompts(rng) + [templated_prompt(rng, reps=2)]
        want = [oracle_greedy(model, p, 8) for p in prompts]
        for spec in ("ngram", False):
            for pc in (True, False):
                got, _, eng = self._run(
                    prompts, 8, num_slots=3, chunk_len=8,
                    num_pages=16, spec=spec, prefix_cache=pc)
                assert got == want, (spec, pc)

    def test_sampled_neighbors_do_not_speculate(self):
        """A non-greedy slot neighbor never drafts (its distribution
        would need rejection sampling); greedy rows next to it stay
        oracle-identical."""
        model = tiny_gpt()
        rng = np.random.RandomState(3)
        greedy_prompts = mixed_prompts(rng, n=2)
        sampled_prompt = rng.randint(0, 97, size=5).astype(np.int64)
        want = [oracle_greedy(model, p, 12) for p in greedy_prompts]
        eng = ServingEngine(model, num_slots=3, max_len=64,
                            page_size=8, chunk_len=16, spec="ngram")
        sps = [SamplingParams(max_new_tokens=12),
               SamplingParams(max_new_tokens=12),
               SamplingParams(max_new_tokens=12, top_k=5,
                              temperature=0.8)]
        outs = eng.generate(list(greedy_prompts) + [sampled_prompt],
                            sps)
        assert [list(o.token_ids) for o in outs[:2]] == want
        assert len(outs[2].token_ids) == 12
        assert outs[2].accepted_draft_tokens == 0
        eng.drain()

    def test_tight_token_budget_throttles_but_stays_exact(self):
        model = tiny_gpt()
        rng = np.random.RandomState(4)
        prompts = mixed_prompts(rng, n=4)
        want = [oracle_greedy(model, p, 10) for p in prompts]
        got, _, eng = self._run(prompts, 10, num_slots=3,
                                chunk_len=16, spec="ngram",
                                token_budget=5)
        assert got == want
        assert eng.metrics.snapshot()[
            "packed_tokens_per_step"]["max"] <= 5

    def test_megakernel_fused_acceptance_is_exact(self):
        """Speculation THROUGH the fused acceptance epilogue
        (PADDLE_TPU_MEGAKERNEL): the burst accept/reject decision is
        the `spec_verify_accept` op instead of the engine's inline
        argmax/match/cumprod block — tokens stay bit-identical to the
        oracle AND to the unfused spec engine, with the same
        accepted-draft accounting, and the fused engine really runs
        the fused ops (dispatch histogram referees)."""
        model = tiny_gpt()
        rng = np.random.RandomState(6)
        prompts = mixed_prompts(rng, n=4) + [templated_prompt(rng)]
        want = [oracle_greedy(model, p, 12) for p in prompts]
        on, outs_on, eng_on = self._run(
            prompts, 12, num_slots=3, chunk_len=16, spec="ngram",
            megakernel=True)
        off, _, eng_off = self._run(
            prompts, 12, num_slots=3, chunk_len=16, spec="ngram",
            megakernel=False)
        assert on == want and off == want
        s_on = eng_on.metrics.snapshot()
        s_off = eng_off.metrics.snapshot()
        assert s_on["spec_accepted_tokens"] > 0
        assert s_on["spec_accepted_tokens"] \
            == s_off["spec_accepted_tokens"]
        assert sum(o.accepted_draft_tokens for o in outs_on) \
            == s_on["spec_accepted_tokens"]
        d_on = eng_on.cost_census()["unified_dispatch"]
        d_off = eng_off.cost_census()["unified_dispatch"]
        assert "spec_verify_accept" in d_on["ops"]
        assert "megakernel_decode" in d_on["ops"]
        assert "spec_verify_accept" not in d_off["ops"]
        assert d_on["total"] < d_off["total"]


# -- retrace probe: speculation adds NO compiled program --------------------
class TestSpecRetraceProbe:
    def test_verify_rides_the_one_unified_program(
            self, only_the_unified_step):
        """ISSUE acceptance: enabling speculation compiles NOTHING new
        — drafting is host-side and the verify pass is just another
        q_len value through THE one `[num_slots, chunk_len]` ragged
        step. Across accepted bursts, rejected drafts, retirements and
        draft-free steps: exactly ONE program, never retraced, and no
        other step program."""
        model = tiny_gpt()
        eng = ServingEngine(model, num_slots=3, max_len=64,
                            page_size=8, chunk_len=16, spec="ngram")
        rng = np.random.RandomState(5)
        prompts = mixed_prompts(rng, n=6) + [templated_prompt(rng)]
        eng.generate(prompts, SamplingParams(max_new_tokens=10))
        snap = eng.metrics.snapshot()
        assert snap["spec_drafted_tokens"] > 0          # drafts ran
        assert snap["spec_accepted_tokens"] \
            < snap["spec_drafted_tokens"]               # some rejected
        only_the_unified_step(eng)
        # ...and the spec-off engine compiles the SAME single program
        # shape: speculation is a host-side packing decision, not a
        # second executable
        eng_off = ServingEngine(model, num_slots=3, max_len=64,
                                page_size=8, chunk_len=16, spec=False)
        eng_off.generate(prompts[:2],
                         SamplingParams(max_new_tokens=4))
        assert eng_off._unified_fn._cache_size() == 1
        eng.drain()
        eng_off.drain()


# -- model tier: resident draft model (serving/draft.py) --------------------
class TestModelSpecDecoding:
    """The PR-20 tentpole: a small draft MODEL resident in the engine
    (its own paged KV pool, its own single compiled ragged program)
    proposes by actually decoding k ahead; the target verifies through
    the EXISTING fused greedy acceptance. Exactly TWO compiled
    programs ever: the target's unified step and the draft's."""

    def test_make_draft_model_shrinks_and_copies(self):
        model = tiny_gpt()
        d = make_draft_model(model)
        assert len(d.gpt.layers) == 1               # 2 -> 1
        # explicit layer counts clamp to [1, target layers]
        assert len(make_draft_model(model, num_layers=0)
                   .gpt.layers) == 1
        assert len(make_draft_model(model, num_layers=5)
                   .gpt.layers) == 2
        # copied weights, not re-initialized: the draft's first layer
        # IS the target's first layer, so echo-shaped continuations
        # draft well even on a random tiny model
        a = model.gpt.embeddings.word_embeddings.weight.numpy()
        b = d.gpt.embeddings.word_embeddings.weight.numpy()
        assert np.array_equal(a, b)

    def test_identity_two_programs_metrics_and_quiesce(
            self, only_the_unified_step):
        """The consolidated non-slow acceptance: mixed-length greedy
        prompts through spec='model:4' are bit-token-identical to the
        solo oracle, drafting really happened and really paid, the
        engine compiled exactly TWO programs (target unified step +
        draft program, one trace each), the draft pool surfaces in
        metrics/Prometheus/debug_state, and it quiesces at drain."""
        model = tiny_gpt()
        rng = np.random.RandomState(11)
        prompts = mixed_prompts(rng, n=4) + [templated_prompt(rng)]
        want = [oracle_greedy(model, p, 12) for p in prompts]
        eng = ServingEngine(model, num_slots=3, max_len=64,
                            page_size=8, chunk_len=16, spec="model:4")
        outs = eng.generate(prompts,
                            SamplingParams(max_new_tokens=12))
        assert [list(o.token_ids) for o in outs] == want
        snap = eng.metrics.snapshot()
        assert snap["spec"] == "model"
        assert snap["spec_draft_model"] is True
        assert snap["spec_drafted_tokens"] > 0
        assert snap["spec_accepted_tokens"] > 0
        assert snap["spec_tokens_per_step"]["max"] > 1
        assert sum(o.accepted_draft_tokens for o in outs) \
            == snap["spec_accepted_tokens"]
        assert snap["draft_pool"]["pages_total"] > 0
        # exactly TWO compiled programs
        only_the_unified_step(eng)
        assert eng._draft._fn._cache_size() == 1
        # observability surfaces
        text = prometheus_render({"0": snap})
        assert 'spec="model"' in text
        assert 'spec_draft_model="on"' in text
        assert "paddle_serving_draft_pool_pages_used" in text
        assert "paddle_serving_draft_pool_pages_total" in text
        ds = eng.debug_state()
        assert ds["draft_pool"]["layers"] == 1
        assert ds["config"]["spec_draft_model"] is True
        eng.drain()
        eng.pool.assert_quiesced()
        eng._draft.assert_quiesced()
        # ...and an ngram engine reports the draft subsystem OFF
        off = ServingEngine(model, num_slots=2, max_len=32,
                            page_size=8, chunk_len=8, spec="ngram")
        off_snap = off.metrics.snapshot()
        assert off_snap["spec_draft_model"] is False
        assert off_snap["draft_pool"] is None
        assert 'spec_draft_model="off"' in prometheus_render(
            {"0": off_snap})

    def test_draft_pool_pressure_degrades_not_fails(self):
        """A starved draft pool (3 pages for 3 slots) throttles HOW
        MUCH speculation runs, never WHETHER the stream is correct:
        admission to the draft pool simply fails for the slots that
        don't fit and those rows decode plain."""
        model = tiny_gpt()
        rng = np.random.RandomState(12)
        prompts = mixed_prompts(rng, n=4)
        want = [oracle_greedy(model, p, 10) for p in prompts]
        eng = ServingEngine(model, num_slots=3, max_len=64,
                            page_size=8, chunk_len=16, spec="model:4",
                            draft_pages=3)
        outs = eng.generate(prompts,
                            SamplingParams(max_new_tokens=10))
        assert [list(o.token_ids) for o in outs] == want
        assert eng.metrics.snapshot()["draft_pool"]["pages_total"] == 2
        eng.drain()
        eng._draft.assert_quiesced()

    def test_preempt_swap_resume_with_model_spec(self):
        """Preemption RELEASES the victim's draft pages (no host tier
        for the draft pool — it's a pure accelerant); resume re-seeds
        the draft cache from the banked history via spare budget. Both
        streams stay oracle-identical."""
        model = tiny_gpt()
        eng = ServingEngine(model, num_slots=2, max_len=64,
                            page_size=8, num_pages=6, chunk_len=16,
                            spec="model:4")
        lo = eng.add_request(np.arange(1, 9),
                             SamplingParams(max_new_tokens=24,
                                            priority=5))
        # the toy's draft (its own bottom layer) is accepted in full,
        # 5 tokens a step: 3 steps leave the victim mid-stream (10 of
        # 24), 6 would have finished it before `hi` arrives
        for _ in range(3):
            eng.step()
        assert 3 <= len(lo.output_tokens) < 24  # mid-stream victim
        hi = eng.add_request(np.arange(30, 38),
                             SamplingParams(max_new_tokens=24,
                                            priority=0))
        eng.run()
        assert eng.metrics.preemptions >= 1
        assert lo.output_tokens == oracle_greedy(model,
                                                 np.arange(1, 9), 24)
        assert hi.output_tokens == oracle_greedy(model,
                                                 np.arange(30, 38), 24)
        assert eng.metrics.spec_accepted_tokens > 0
        eng.drain()
        eng.pool.assert_quiesced()
        eng._draft.assert_quiesced()

    @pytest.mark.slow
    def test_model_beats_ngram_on_natural_text(self):
        """The tier-separation claim: on NATURAL (non-templated,
        non-repetitive) prompts the n-gram drafter has nothing to
        match and accepts ~nothing, while the draft model — which
        shares the target's own early layers — keeps proposing.
        Accepted tokens per unified step must be strictly higher."""
        model = tiny_gpt()
        rng = np.random.RandomState(13)
        prompts = [rng.randint(0, 97, size=rng.randint(5, 12))
                   .astype(np.int64) for _ in range(6)]
        rates = {}
        for tier in ("model", "ngram"):
            eng = ServingEngine(model, num_slots=3, max_len=64,
                                page_size=8, chunk_len=16,
                                spec=f"{tier}:4")
            eng.generate(prompts, SamplingParams(max_new_tokens=8))
            snap = eng.metrics.snapshot()
            rates[tier] = (snap["spec_accepted_tokens"]
                           / max(1, snap["unified_steps"]))
            eng.drain()
        assert rates["model"] > rates["ngram"]

    @pytest.mark.slow
    def test_quant_kv_prefix_matrix(self):
        """Feature matrix: the draft pool always stays fp (quantizing
        a throwaway draft cache buys nothing), while the TARGET pool
        runs fp/int8/fp8 x prefix cache on/off — every arm
        bit-token-identical to the solo oracle."""
        model = tiny_gpt()
        rng = np.random.RandomState(14)
        prompts = mixed_prompts(rng, n=3) + [templated_prompt(rng)]
        want = [oracle_greedy(model, p, 8) for p in prompts]
        for kv in ("fp", "int8", "fp8"):
            for pc in (True, False):
                eng = ServingEngine(model, num_slots=2, max_len=64,
                                    page_size=8, chunk_len=16,
                                    spec="model:4", kv_dtype=kv,
                                    prefix_cache=pc)
                outs = eng.generate(
                    prompts, SamplingParams(max_new_tokens=8))
                got = [list(o.token_ids) for o in outs]
                assert got == want, (kv, pc)
                eng.drain()
                eng._draft.assert_quiesced()

    @pytest.mark.slow
    def test_poison_bisection_mid_model_speculation(self):
        """Poison quarantine with the draft model live: the poisoned
        request 422s with only VERIFIED tokens (a strict oracle
        prefix), neighbors finish identical, and abort paths leave the
        draft pool quiesced."""
        model = tiny_gpt()
        rng = np.random.RandomState(15)
        prompts = [templated_prompt(rng), mixed_prompts(rng, 1)[0],
                   mixed_prompts(rng, 1)[0]]
        eng = ServingEngine(model, num_slots=3, max_len=64,
                            page_size=8, chunk_len=16, spec="model:4")
        inj = FaultInjector()
        eng.step_fault_hook = \
            lambda ids: inj.on_engine_step("r0", ids)
        reqs = [eng.add_request(p, SamplingParams(max_new_tokens=14))
                for p in prompts]
        for _ in range(4):
            eng.step()
        assert eng.metrics.spec_accepted_tokens > 0
        inj.poison(reqs[0].request_id)
        eng.run()
        assert reqs[0].finish_reason == "poisoned"
        oracle0 = oracle_greedy(model, prompts[0], 14)
        assert reqs[0].output_tokens == \
            oracle0[:len(reqs[0].output_tokens)]
        for i in (1, 2):
            assert reqs[i].finish_reason == "length"
            assert reqs[i].output_tokens == oracle_greedy(
                model, prompts[i], 14), i
        eng.drain()
        eng.pool.assert_quiesced()
        eng._draft.assert_quiesced()

    @pytest.mark.slow
    def test_migration_mid_stream_model_spec(self):
        """Replica kill while the draft model is speculating: the
        survivor re-admits into ITS draft pool, re-seeds from the
        banked history (rides req.prefill_ids through the seed path)
        and keeps accepting. Stream token-identical; both replicas'
        target AND draft pools quiesce."""
        from paddle_tpu.serving.http import EngineDriver, Router

        model = tiny_gpt()
        engines = [ServingEngine(model, num_slots=2, max_len=64,
                                 page_size=8, chunk_len=16,
                                 spec="model:4") for _ in range(2)]
        for e in engines:      # compile-warm before any fault
            e.generate([np.array([1, 2, 3])],
                       SamplingParams(max_new_tokens=2))
        drivers = [EngineDriver(e, name=f"replica-{i}")
                   for i, e in enumerate(engines)]
        router = Router(drivers).start()
        rng = np.random.RandomState(16)
        prompt = templated_prompt(rng)
        want = oracle_greedy(model, prompt, 24)
        t = router.submit(np.asarray(prompt, np.int64),
                          SamplingParams(max_new_tokens=24))
        victim = t.driver
        toks = []
        for kind, val in t.events(poll_s=0.01):
            if kind == "token":
                toks.append(val)
                if len(toks) >= 3 and not victim.dead:
                    victim.kill()
            elif kind in ("done", "error"):
                assert kind == "done" and val == "length"
                break
        assert toks == want
        out = t.output()
        assert out.migrations == 1 and t.attempts == 2
        assert out.accepted_draft_tokens > 0
        survivor = t.driver.engine
        assert survivor is not victim.engine
        assert survivor.metrics.spec_accepted_tokens > 0
        router.drain()
        for e in engines:
            e.pool.assert_quiesced()
            e._draft.assert_quiesced()

    @pytest.mark.slow
    def test_lora_mixed_batch_identity(self):
        """Two LoRA tenants + a base row speculating together: each
        stream bit-identical to its own dense-merged solo oracle. The
        DRAFT model stays base-weights for every row (drafts are just
        proposals — a tenant-biased target simply rejects more), so
        the draft program needs no adapter plumbing."""
        from test_serving_adapters import (gpt_adapters, merged_gpt,
                                           oracle_tokens)
        from test_serving_adapters import tiny_gpt as adapters_gpt

        model = adapters_gpt()
        ws = gpt_adapters(2)
        prompt = np.array([5, 6, 7] * 3, np.int64)
        eng = ServingEngine(model, num_slots=3, max_len=64,
                            adapters=True, adapter_pages=2,
                            spec="model:3")
        ids = [eng.adapters.register(f"t{i}", w)
               for i, w in enumerate(ws)]
        outs = eng.generate(
            [prompt] * 3,
            [SamplingParams(max_new_tokens=10, adapter_id=ids[0]),
             SamplingParams(max_new_tokens=10, adapter_id=ids[1]),
             SamplingParams(max_new_tokens=10)])
        refs = [merged_gpt(ws[0]), merged_gpt(ws[1]), model]
        for i, (o, ref) in enumerate(zip(outs, refs)):
            assert o.token_ids == oracle_tokens(ref, prompt, 10), i
        assert eng.metrics.spec_accepted_tokens > 0
        eng.drain()
        eng._draft.assert_quiesced()

    @pytest.mark.slow
    def test_mesh_dp1mp2_identity_and_census(self):
        """The draft model stays REPLICATED on a (dp, mp) mesh — no
        draft collectives by construction — while the target shards;
        tokens identical to the solo engine and the collective census
        keeps exactly one output all-gather per TARGET layer."""
        model = tiny_gpt()
        rng = np.random.RandomState(17)
        prompts = mixed_prompts(rng, n=3) + [templated_prompt(rng)]
        want = [oracle_greedy(model, p, 10) for p in prompts]
        eng = ServingEngine(model, num_slots=2, max_len=64,
                            page_size=8, chunk_len=16, spec="model:4",
                            mesh="dp1mp2")
        outs = eng.generate(prompts,
                            SamplingParams(max_new_tokens=10))
        assert [list(o.token_ids) for o in outs] == want
        assert eng.metrics.spec_accepted_tokens > 0
        counts = eng.collective_counts()
        assert counts["all_reduce"] == 0
        assert counts["reduce_scatter"] == 0
        assert counts["all_gather"] == eng.n_layers
        eng.drain()
        eng._draft.assert_quiesced()


# -- speculation x faults ---------------------------------------------------
class TestSpecFaults:
    def test_poison_bisection_mid_speculation(self):
        """Poison quarantine during active speculation: suppressed
        slots idle at q_len 0, the poisoned request 422s alone with
        ONLY verified tokens (its emitted stream is a prefix of its
        oracle — no drafted-but-unverified token ever leaked), and
        neighbors finish token-identical."""
        model = tiny_gpt()
        rng = np.random.RandomState(6)
        prompts = [templated_prompt(rng), mixed_prompts(rng, 1)[0],
                   mixed_prompts(rng, 1)[0]]
        eng = ServingEngine(model, num_slots=3, max_len=64,
                            page_size=8, chunk_len=16, spec="ngram")
        inj = FaultInjector()
        eng.step_fault_hook = \
            lambda ids: inj.on_engine_step("r0", ids)
        reqs = [eng.add_request(p, SamplingParams(max_new_tokens=14))
                for p in prompts]
        for _ in range(4):
            eng.step()
        assert eng.metrics.spec_accepted_tokens > 0   # mid-speculation
        inj.poison(reqs[0].request_id)
        eng.run()
        assert reqs[0].finish_reason == "poisoned"
        oracle0 = oracle_greedy(model, prompts[0], 14)
        assert reqs[0].output_tokens == \
            oracle0[:len(reqs[0].output_tokens)]
        for i in (1, 2):
            assert reqs[i].finish_reason == "length"
            assert reqs[i].output_tokens == oracle_greedy(
                model, prompts[i], 14), i
        eng.drain()
        eng.pool.assert_quiesced()

    def test_migration_after_partially_accepted_step(self):
        """Kill the serving replica mid-stream while bursts are
        landing: the ticket banks the verified history, the survivor
        re-prefills prompt + history, and the DRAFTER RE-SEEDS from
        that banked history (the survivor keeps accepting drafts).
        Final stream token-identical to the solo oracle;
        usage.accepted_draft_tokens merges across attempts."""
        from paddle_tpu.serving.http import EngineDriver, Router

        model = tiny_gpt()
        engines = [ServingEngine(model, num_slots=2, max_len=64,
                                 page_size=8, chunk_len=16,
                                 spec="ngram") for _ in range(2)]
        for e in engines:      # compile-warm before any fault
            e.generate([np.array([1, 2, 3])],
                       SamplingParams(max_new_tokens=2))
        drivers = [EngineDriver(e, name=f"replica-{i}")
                   for i, e in enumerate(engines)]
        router = Router(drivers).start()
        rng = np.random.RandomState(7)
        prompt = templated_prompt(rng)
        want = oracle_greedy(model, prompt, 24)
        t = router.submit(np.asarray(prompt, np.int64),
                          SamplingParams(max_new_tokens=24))
        victim = t.driver
        toks = []
        for kind, val in t.events(poll_s=0.01):
            if kind == "token":
                toks.append(val)
                if len(toks) >= 3 and not victim.dead:
                    victim.kill()
            elif kind in ("done", "error"):
                assert kind == "done" and val == "length"
                break
        assert toks == want
        out = t.output()
        assert out.token_ids == want
        assert out.migrations == 1 and t.attempts == 2
        assert out.accepted_draft_tokens > 0
        # the survivor really speculated over the banked history
        survivor = t.driver.engine
        assert survivor is not victim.engine
        assert survivor.metrics.spec_accepted_tokens > 0
        router.drain()
        for e in engines:
            e.pool.assert_quiesced()


# -- metrics, usage and emission plumbing -----------------------------------
class TestSpecMetricsAndUsage:
    def test_snapshot_and_prometheus_series(self):
        model = tiny_gpt()
        rng = np.random.RandomState(8)
        eng = ServingEngine(model, num_slots=2, max_len=64,
                            page_size=8, chunk_len=16, spec="ngram")
        eng.generate([templated_prompt(rng), mixed_prompts(rng, 1)[0]],
                     SamplingParams(max_new_tokens=12))
        snap = eng.metrics.snapshot()
        assert snap["spec"] == "ngram"
        assert snap["spec_drafted_tokens"] > 0
        assert snap["spec_accepted_tokens"] > 0
        assert snap["spec_tokens_per_step"]["count"] > 0
        text = prometheus_render({"0": snap})
        assert 'spec="ngram"' in text
        assert "paddle_serving_spec_drafted_total" in text
        assert "paddle_serving_spec_accepted_total" in text
        assert "paddle_serving_spec_tokens_per_step_bucket" in text
        off = ServingEngine(model, num_slots=2, max_len=64,
                            spec=False)
        assert 'spec="off"' in prometheus_render(
            {"0": off.metrics.snapshot()})
        eng.drain()

    def test_inter_token_burst_attribution_no_zeros(self):
        """A burst of m tokens lands at one step boundary: the metric
        divides the step gap into m equal slices instead of one gap
        plus zeros — every recorded inter-token sample is positive,
        and first-burst tokens (no previous step to measure against)
        record nothing rather than lies."""
        model = tiny_gpt()
        rng = np.random.RandomState(9)
        eng = ServingEngine(model, num_slots=1, max_len=64,
                            page_size=8, chunk_len=16, spec="ngram")
        eng.generate([templated_prompt(rng)],
                     SamplingParams(max_new_tokens=14))
        snap = eng.metrics.snapshot()
        it = snap["inter_token_s"]
        assert snap["spec_tokens_per_step"]["max"] > 1  # bursts ran
        assert 0 < it["count"] < snap["tokens_generated"]
        assert it["min"] > 0.0

    def test_sse_framing_and_usage_over_http(self):
        """Multi-token steps never change the wire shape: one token
        per SSE frame, in order, and the final frame's usage carries
        accepted_draft_tokens. The non-stream JSON body agrees."""
        import http.client

        from paddle_tpu.serving.http import serve

        model = tiny_gpt()
        rng = np.random.RandomState(10)
        prompt = templated_prompt(rng)
        want = oracle_greedy(model, prompt, 12)
        eng = ServingEngine(model, num_slots=2, max_len=64,
                            page_size=8, chunk_len=16, spec="ngram")
        server = serve([eng], poll_interval_s=0.01)
        host, port = server.server_address[:2]
        try:
            body = {"prompt": [int(x) for x in prompt],
                    "max_tokens": 12, "stream": True}
            conn = http.client.HTTPConnection(host, port, timeout=60)
            conn.request("POST", "/v1/completions", json.dumps(body),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            toks, usage, fin = [], None, None
            while True:
                line = resp.readline()
                if not line or line.strip() == b"data: [DONE]":
                    break
                if not line.startswith(b"data: "):
                    continue
                frame = json.loads(line[6:])
                choice = frame["choices"][0]
                if choice["token"] is not None:
                    toks.append(choice["token"])
                if choice["finish_reason"]:
                    fin = choice["finish_reason"]
                    usage = frame.get("usage") or {}
            conn.close()
            assert toks == want and fin == "length"
            assert usage["completion_tokens"] == 12
            assert usage["accepted_draft_tokens"] > 0
            # non-stream: same tokens, same usage surface
            conn = http.client.HTTPConnection(host, port, timeout=60)
            conn.request("POST", "/v1/completions",
                         json.dumps({**body, "stream": False}),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            payload = json.loads(resp.read())
            conn.close()
            assert resp.status == 200
            assert payload["choices"][0]["token_ids"] == want
            assert payload["usage"]["accepted_draft_tokens"] > 0
        finally:
            server.drain()


# -- bench A/B --------------------------------------------------------------
def _run_bench(tmp_path, monkeypatch, extra):
    import importlib.util
    script = os.path.join(os.path.dirname(__file__), os.pardir,
                          "scripts", "serving_bench.py")
    spec = importlib.util.spec_from_file_location(
        "serving_bench_spec", script)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = str(tmp_path / "BENCH_serving.json")
    monkeypatch.setattr(sys, "argv",
                        ["serving_bench.py"] + extra + ["--out", out])
    mod.main()
    with open(out) as f:
        return json.load(f)


@pytest.mark.slow
def test_serving_bench_spec_ab_smoke(tmp_path, monkeypatch):
    """`serving_bench.py --smoke --spec-ab` (ISSUE acceptance): the
    templated trace with speculation off vs ngram on lands in
    BENCH_serving.json's "spec" section (schema v19), token-identical,
    with accepted-tokens-per-step > 1.0 and no tokens/s regression —
    plus the natural-text tier-separation arm, where the resident
    draft MODEL must strictly beat the ngram drafter's acceptance
    while staying bit-identical to the no-spec oracle."""
    report = _run_bench(tmp_path, monkeypatch,
                        ["--smoke", "--requests", "4", "--spec-ab"])
    assert report["schema_version"] == 19
    sp = report["spec"]
    assert set(sp) >= {"on", "off", "accepted_tokens_per_step",
                       "tokens_per_sec_ratio", "token_identical",
                       "natural"}
    assert sp["token_identical"] is True
    assert sp["accepted_tokens_per_step"] > 1.0
    assert sp["on"]["spec_accepted_tokens"] > 0
    # "no tokens/s regression" with the bench's own sub-second
    # scheduler-noise pin (the bench already asserts the tight form;
    # re-asserting strictly here would double the flake surface) —
    # the robust form of the speedup claim is the step-count drop
    assert sp["on"]["tokens_per_sec"] >= \
        sp["off"]["tokens_per_sec"] / 2.0
    assert sp["on"]["unified_steps"] < sp["off"]["unified_steps"]
    assert sp["acceptance_rate"] and 0.0 < sp["acceptance_rate"] <= 1.0
    nat = sp["natural"]
    assert nat["model_token_identical"] is True
    assert nat["ngram_token_identical"] is True
    assert nat["model_accepted_tokens_per_step"] > \
        nat["ngram_accepted_tokens_per_step"]
    assert nat["model"]["spec_accepted_tokens"] > 0
    assert nat["model"]["tokens_per_sec"] >= \
        nat["off"]["tokens_per_sec"] / 2.0
    assert nat["model"]["unified_steps"] < nat["off"]["unified_steps"]


@pytest.mark.slow
def test_spec_ab_soak(tmp_path, monkeypatch):
    """The spec A/B soak (slow marker): a bigger templated trace
    through the full bench path — the same identity + speedup
    contract must hold at load, not just in the smoke sizes."""
    report = _run_bench(
        tmp_path, monkeypatch,
        ["--smoke", "--requests", "24", "--rate", "400", "--spec-ab",
         "--spec-k", "6"])
    sp = report["spec"]
    assert sp["token_identical"] is True
    assert sp["requests"] == 24
    assert sp["accepted_tokens_per_step"] > 1.0
    # the bench's own assert block carries the tokens/s pin (with its
    # sub-second scheduler-noise tolerance); the load-proof speedup
    # claim asserted here is the step-count drop, which is exact
    assert sp["on"]["unified_steps"] < sp["off"]["unified_steps"]
    assert sp["natural"]["model_token_identical"] is True


def test_bench_default_run_has_no_spec_section(tmp_path, monkeypatch):
    """Without --spec-ab the report carries no spec section (schema v7
    keeps the key optional), and the default path still completes."""
    report = _run_bench(tmp_path, monkeypatch,
                        ["--smoke", "--requests", "3"])
    assert report["schema_version"] == 19
    assert "spec" not in report
