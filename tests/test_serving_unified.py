"""Unified ragged prefill+decode step: the engine's one step program.

The contracts:
- greedy outputs are token-identical to the solo CompiledGenerator
  oracle, on mixed prefill/decode traces, under page pressure, and with
  the prefix cache on or off;
- exactly ONE compiled ragged program serves every prefill/decode mix
  (cache_size probe, the technique of test_serving_prefix.py), and the
  engine has no other step program;
- the scheduler PACKS prefill tokens into spare decode-step capacity
  (token budget).
"""
import json

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.nlp import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import (SamplingParams, Scheduler,
                                ServingEngine, prometheus_render)
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
    out = model.generate(paddle.to_tensor(prompt[None]),
                         max_new_tokens=n_new).numpy()
    return list(out[0, prompt.size:])


def mixed_prompts(rng, n=8, shared_prefix=None):
    """Short decode-heavy and long prefill-heavy prompts interleaved,
    optionally sharing a prefix (prefix-cache traffic shape)."""
    out = []
    for i in range(n):
        tail = rng.randint(0, 97, size=rng.randint(1, 14)) \
            .astype(np.int64)
        if shared_prefix is not None and i % 2 == 0:
            tail = np.concatenate([shared_prefix, tail])
        elif i % 3 == 0:
            tail = np.concatenate(
                [tail, rng.randint(0, 97, size=25).astype(np.int64)])
        out.append(tail)
    return out


class TestStepOptions:
    def test_token_budget_validation(self):
        with pytest.raises(ValueError):
            ServingEngine(tiny_gpt(), num_slots=2, max_len=32,
                          page_size=8, chunk_len=8, token_budget=0)


class TestSchedulerPacking:
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

    def test_decode_rows_always_get_their_token(self):
        s = self._sched([RequestState.DECODE, RequestState.DECODE,
                         RequestState.PREFILL])
        decode, grants, _ = s.pack_tokens(2, 16, {2: 40})  # budget == decodes
        assert decode == [0, 1]
        assert grants == {}                              # no spare left

    def test_prefill_packs_into_spare_budget(self):
        s = self._sched([RequestState.DECODE, RequestState.PREFILL,
                         RequestState.PREFILL])
        decode, grants, _ = s.pack_tokens(20, 16, {1: 40, 2: 3})
        assert decode == [0]
        # slot 1 takes min(40, width 16, spare 19) = 16, slot 2 the rest
        assert grants == {1: 16, 2: 3}

    def test_width_caps_single_row_chunk(self):
        s = self._sched([RequestState.PREFILL])
        _, grants, _ = s.pack_tokens(100, 8, {0: 50})
        assert grants == {0: 8}

    def test_spare_exhaustion_stops_in_slot_order(self):
        s = self._sched([RequestState.PREFILL, RequestState.PREFILL])
        _, grants, _ = s.pack_tokens(5, 16, {0: 4, 1: 10})
        assert grants == {0: 4, 1: 1}                    # 5 total


class TestUnifiedTokenIdentity:
    """Greedy outputs == solo oracle."""

    def _run(self, prompts, n_new, **kw):
        eng = ServingEngine(tiny_gpt(), max_len=64, page_size=8,
                            **kw)
        outs = eng.generate(prompts,
                            SamplingParams(max_new_tokens=n_new))
        toks = [list(o.token_ids) for o in outs]
        eng.drain()
        return toks, eng

    def test_mixed_trace_oracle(self):
        model = tiny_gpt()
        rng = np.random.RandomState(0)
        prompts = mixed_prompts(rng)
        want = [oracle_greedy(model, p, 8) for p in prompts]
        got, eng = self._run(prompts, 8, num_slots=3, chunk_len=16)
        assert got == want
        snap = eng.metrics.snapshot()
        assert snap["unified_steps"] > 0
        assert snap["packed_prefill_tokens"] > 0
        assert snap["packed_decode_tokens"] > 0

    def test_under_page_pressure_and_prefix_cache(self):
        """The acceptance matrix: page pressure (pool smaller than the
        trace wants, LRU eviction live) x prefix cache on/off, all
        token-identical to the oracle."""
        model = tiny_gpt()
        rng = np.random.RandomState(1)
        shared = np.arange(1, 20, dtype=np.int64)
        prompts = mixed_prompts(rng, shared_prefix=shared)
        want = [oracle_greedy(model, p, 6) for p in prompts]
        for pc in (True, False):
            got, eng = self._run(prompts, 6, num_slots=3, chunk_len=8,
                                 num_pages=16, prefix_cache=pc)
            assert got == want, pc
            eng.pool.assert_quiesced()

    def test_tight_token_budget_stays_correct(self):
        """A budget barely above the decode load spreads prefill over
        many steps but never changes any token."""
        model = tiny_gpt()
        rng = np.random.RandomState(2)
        prompts = mixed_prompts(rng, n=5)
        want = [oracle_greedy(model, p, 6) for p in prompts]
        got, eng = self._run(prompts, 6, num_slots=3, chunk_len=16,
                             token_budget=4)
        assert got == want
        # the budget really throttled packing: no step packed more
        # than 4 tokens
        snap = eng.metrics.snapshot()
        assert snap["packed_tokens_per_step"]["max"] <= 4


class TestUnifiedRetraceDetection:
    def test_one_compiled_ragged_program_serves_all_mixes(
            self, only_the_unified_step):
        """Across prompt lengths from one token to two chunks,
        admissions, retirements, cancellations and page reuse, the
        engine compiles EXACTLY ONE step program, and has no other."""
        model = tiny_gpt()
        eng = ServingEngine(model, num_slots=3, max_len=64,
                            page_size=8, chunk_len=16)
        rng = np.random.RandomState(0)
        reqs = []
        for plen in [1, 2, 3, 5, 7, 9, 12, 15, 17, 20, 23, 30]:
            reqs.append(eng.add_request(
                rng.randint(0, 97, size=plen).astype(np.int64),
                SamplingParams(max_new_tokens=4)))
        eng.step()
        eng.cancel(reqs[2].request_id)        # eviction mid-run
        eng.run()
        assert all(r.finished for r in reqs)
        only_the_unified_step(eng)


class TestUnifiedMetrics:
    def _load(self):
        model = tiny_gpt()
        rng = np.random.RandomState(4)
        eng = ServingEngine(model, num_slots=2, max_len=64,
                            page_size=8, chunk_len=8)
        # long prompts behind residents: the step must pack
        prompts = [rng.randint(0, 97, size=n).astype(np.int64)
                   for n in [30, 28, 25, 27]]
        eng.generate(prompts, SamplingParams(max_new_tokens=4))
        return eng.metrics.snapshot()

    def test_prometheus_carries_step_counters_and_histogram(self):
        snap = self._load()
        assert snap["packed_tokens_per_step"]["count"] == \
            snap["unified_steps"]
        # packed histogram saw multi-token steps (prefill + decode)
        assert snap["packed_tokens_per_step"]["max"] > 1
        text = prometheus_render({"0": snap})
        assert 'attn_impl="kernel"' in text
        assert "paddle_serving_unified_steps_total" in text
        assert "paddle_serving_packed_tokens_per_step_bucket" in text


def test_chrome_trace_has_unified_step_and_request_spans(tmp_path):
    """Profiler spans of a round: fixed names, ids in the arguments. One
    row of leaves per engine step, admit (with the round's step index),
    plan, launch (with the step's packed tokens), fetch, commit and
    report, and no span around the round or the step; the per-request
    part is RequestTracer's timeline, joined by admit's step."""
    from paddle_tpu import profiler
    model = tiny_gpt()
    eng = ServingEngine(model, num_slots=2, max_len=48)
    with profiler.Profiler(targets=[profiler.ProfilerTarget.CPU]) as p:
        r0 = eng.add_request(np.array([1, 2, 3], np.int64),
                             SamplingParams(max_new_tokens=3))
        eng.run()
    path = str(tmp_path / "unified_trace.json")
    p.export(path)
    with open(path) as f:
        trace = json.load(f)
    events = trace["traceEvents"]
    names = [e["name"] for e in events]
    assert "serving::unified_step" not in names
    assert "serving::round" not in names
    admits = [e for e in events if e["name"] == "serving::admit"]
    assert [e["args"]["step"] for e in admits] == \
        list(range(1, eng._step_idx + 1))
    # a round fetches and commits the step the round before launched,
    # after it launched its own: the first round fetches none, the last
    # launches none
    for phase in ("plan", "report"):
        assert names.count(f"serving::{phase}") == len(admits), phase
    for phase in ("launch", "fetch", "commit"):
        assert names.count(f"serving::{phase}") == len(admits) - 1, phase
    # the prompt's three tokens, then one decoding row a step
    assert [e["args"]["tokens"] for e in events
            if e["name"] == "serving::launch"] == \
        [3] + [1] * (len(admits) - 2)
    second = [e for e in events if e["name"].startswith("serving::")
              and admits[1]["ts"] <= e["ts"] < admits[2]["ts"]]
    assert [e["name"] for e in second] == [
        f"serving::{n}" for n in ("admit", "plan", "launch", "fetch",
                                  "commit", "report")]
    # a row: each leaf ends before the next one starts
    row = second
    for a, b in zip(row, row[1:]):
        assert a["ts"] + a["dur"] <= b["ts"], (a["name"], b["name"])
    # no name is built per call: nothing carries an id in brackets
    assert not any("[" in n for n in names if n.startswith("serving::"))
    # the request's own timeline, on the rounds' step index
    tl = eng.obs.tracer.timeline(r0.request_id)
    kinds = [e["kind"] for e in tl]
    assert kinds[0] == "submit" and kinds[-1] == "finish"
    assert "admit" in kinds and "first_token" in kinds
    steps = {e["args"]["step"] for e in admits}
    assert {e["step"] for e in tl if e["kind"] != "submit"} <= steps
