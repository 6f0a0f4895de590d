"""Unified ragged prefill+decode step (PADDLE_TPU_UNIFIED_STEP).

The tentpole contracts:
- greedy outputs with the unified step ON (default) are token-identical
  to the legacy alternating path AND to the solo CompiledGenerator
  oracle, on mixed prefill/decode traces, under page pressure, and with
  the prefix cache enabled — the same oracle pattern as
  PADDLE_TPU_PAGED_ATTN / PADDLE_TPU_PREFIX_CACHE;
- the per-bucket prefill trace explosion is GONE: with the unified step
  on, exactly ONE compiled ragged program serves every prefill/decode
  mix (cache_size probe, the technique of test_serving_prefix.py) —
  no per-bucket prefill programs, no separate decode program;
- the scheduler PACKS prefill tokens into spare decode-step capacity
  (token budget) instead of alternating program families, so the off
  path's prefill-stall steps never happen with the step on.
"""
import json
import math
import os
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.nlp import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import (SamplingParams, Scheduler,
                                ServingEngine, prometheus_render,
                                resolve_unified_flag)
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


class TestUnifiedFlag:
    def test_env_resolution_and_override(self, monkeypatch):
        monkeypatch.delenv("PADDLE_TPU_UNIFIED_STEP", raising=False)
        assert resolve_unified_flag() is True            # default on
        monkeypatch.setenv("PADDLE_TPU_UNIFIED_STEP", "off")
        assert resolve_unified_flag() is False
        assert resolve_unified_flag(True) is True        # override wins
        monkeypatch.setenv("PADDLE_TPU_UNIFIED_STEP", "maybe")
        with pytest.raises(ValueError):
            resolve_unified_flag()

    def test_engine_picks_up_env_gate(self, monkeypatch):
        model = tiny_gpt()
        monkeypatch.setenv("PADDLE_TPU_UNIFIED_STEP", "off")
        eng = ServingEngine(model, num_slots=2, max_len=32,
                            page_size=8, chunk_len=8)
        assert eng.unified is False
        assert eng.metrics.unified is False
        monkeypatch.delenv("PADDLE_TPU_UNIFIED_STEP")
        eng = ServingEngine(model, num_slots=2, max_len=32,
                            page_size=8, chunk_len=8)
        assert eng.unified is True
        assert eng.metrics.unified is True

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
    """Greedy outputs: unified on == unified off == solo oracle."""

    def _run(self, prompts, n_new, **kw):
        eng = ServingEngine(tiny_gpt(), max_len=64, page_size=8,
                            **kw)
        outs = eng.generate(prompts,
                            SamplingParams(max_new_tokens=n_new))
        toks = [list(o.token_ids) for o in outs]
        eng.drain()
        return toks, eng

    def test_mixed_trace_on_off_oracle(self):
        model = tiny_gpt()
        rng = np.random.RandomState(0)
        prompts = mixed_prompts(rng)
        want = [oracle_greedy(model, p, 8) for p in prompts]
        on, eng_on = self._run(prompts, 8, num_slots=3, chunk_len=16,
                               unified=True)
        off, eng_off = self._run(prompts, 8, num_slots=3, chunk_len=16,
                                 unified=False)
        assert on == want and off == want
        snap = eng_on.metrics.snapshot()
        assert snap["unified_steps"] > 0
        assert snap["packed_prefill_tokens"] > 0
        assert snap["packed_decode_tokens"] > 0
        assert eng_off.metrics.snapshot()["unified_steps"] == 0

    def test_under_page_pressure_and_prefix_cache(self):
        """The acceptance matrix: page pressure (pool smaller than the
        trace wants, LRU eviction live) x prefix cache on/off, unified
        on vs off, all token-identical to the oracle."""
        model = tiny_gpt()
        rng = np.random.RandomState(1)
        shared = np.arange(1, 20, dtype=np.int64)
        prompts = mixed_prompts(rng, shared_prefix=shared)
        want = [oracle_greedy(model, p, 6) for p in prompts]
        for unified in (True, False):
            for pc in (True, False):
                got, eng = self._run(
                    prompts, 6, num_slots=3, chunk_len=8,
                    num_pages=16, unified=unified, prefix_cache=pc)
                assert got == want, (unified, pc)
                eng.pool.assert_quiesced()

    def test_tight_token_budget_stays_correct(self):
        """A budget barely above the decode load spreads prefill over
        many steps but never changes any token."""
        model = tiny_gpt()
        rng = np.random.RandomState(2)
        prompts = mixed_prompts(rng, n=5)
        want = [oracle_greedy(model, p, 6) for p in prompts]
        got, eng = self._run(prompts, 6, num_slots=3, chunk_len=16,
                             unified=True, token_budget=4)
        assert got == want
        # the budget really throttled packing: no step packed more
        # than 4 tokens
        snap = eng.metrics.snapshot()
        assert snap["packed_tokens_per_step"]["max"] <= 4


class TestUnifiedRetraceDetection:
    def test_one_compiled_ragged_program_serves_all_mixes(self):
        """The satellite assertion: the per-bucket prefill trace
        explosion is gone. Across prompt lengths that used to span
        every chunk bucket, admissions, retirements, cancellations and
        page reuse, the unified engine compiles EXACTLY ONE program —
        no prefill buckets, no separate decode step."""
        model = tiny_gpt()
        eng = ServingEngine(model, num_slots=3, max_len=64,
                            page_size=8, chunk_len=16, unified=True)
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
        # the two legacy program families never got built...
        assert eng._decode_fn is None
        assert eng._prefill_fns == {}
        # ...and the one ragged program never retraced
        assert eng._unified_fn._cache_size() == 1

    def test_off_path_still_bucketized(self):
        """The A/B control: with the gate off the legacy families come
        back, bucket-bounded as before."""
        model = tiny_gpt()
        eng = ServingEngine(model, num_slots=2, max_len=64,
                            page_size=8, chunk_len=16, unified=False)
        rng = np.random.RandomState(3)
        for plen in [3, 9, 17, 25]:
            eng.add_request(rng.randint(0, 97, size=plen)
                            .astype(np.int64),
                            SamplingParams(max_new_tokens=3))
        eng.run()
        assert eng._unified_fn is None
        assert eng._decode_fn._cache_size() == 1
        bound = int(math.log2(eng.chunk_len)) + 1
        assert 0 < len(eng._prefill_fns) <= bound


class TestUnifiedMetrics:
    def _load(self, unified):
        model = tiny_gpt()
        rng = np.random.RandomState(4)
        eng = ServingEngine(model, num_slots=2, max_len=64,
                            page_size=8, chunk_len=8, unified=unified)
        # long prompts behind residents: the off path must alternate
        # (stall steps), the on path must pack
        prompts = [rng.randint(0, 97, size=n).astype(np.int64)
                   for n in [30, 28, 25, 27]]
        eng.generate(prompts, SamplingParams(max_new_tokens=4))
        return eng.metrics.snapshot()

    def test_stall_steps_counted_off_killed_on(self):
        off = self._load(unified=False)
        on = self._load(unified=True)
        assert off["prefill_stall_steps"] > 0
        assert on["prefill_stall_steps"] == 0
        assert on["packed_tokens_per_step"]["count"] == \
            on["unified_steps"]
        # packed histogram saw multi-token steps (prefill + decode)
        assert on["packed_tokens_per_step"]["max"] > 1

    def test_prometheus_carries_unified_tag_and_histogram(self):
        snap = self._load(unified=True)
        text = prometheus_render({"0": snap})
        assert 'attn_impl="kernel"' in text
        assert 'unified="on"' in text
        assert "paddle_serving_unified_steps_total" in text
        assert "paddle_serving_prefill_stall_steps_total" in text
        assert "paddle_serving_packed_tokens_per_step_bucket" in text
        off = self._load(unified=False)
        assert 'unified="off"' in prometheus_render({"0": off})


def test_chrome_trace_has_unified_step_and_request_spans(tmp_path):
    """Profiler spans on the unified path: fixed names, ids in the
    arguments. One serving::round per engine step holding admit, plan,
    unified_step (launch + fetch), commit and report; the per-request
    part is RequestTracer's timeline, joined by the round's step."""
    from paddle_tpu import profiler
    model = tiny_gpt()
    eng = ServingEngine(model, num_slots=2, max_len=48, unified=True)
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
    assert names.count("serving::unified_step") >= 3
    rounds = [e for e in events if e["name"] == "serving::round"]
    assert [e["args"]["step"] for e in rounds] == \
        list(range(1, eng._step_idx + 1))
    for phase in ("admit", "plan", "launch", "fetch", "commit",
                  "report"):
        assert names.count(f"serving::{phase}") >= 3, phase

    def inside(child, parent):
        return parent["ts"] <= child["ts"] and \
            child["ts"] + child["dur"] <= parent["ts"] + parent["dur"]
    first = {n: next(e for e in events if e["name"] == f"serving::{n}")
             for n in ("round", "admit", "plan", "unified_step",
                       "launch", "fetch", "commit", "report")}
    for n in ("admit", "plan", "unified_step", "commit", "report"):
        assert inside(first[n], first["round"]), n
    assert inside(first["launch"], first["unified_step"])
    assert inside(first["fetch"], first["unified_step"])
    # no name is built per call: nothing carries an id in brackets
    assert not any("[" in n for n in names if n.startswith("serving::"))
    # the legacy program families never ran
    assert "serving::decode_step" not in names
    assert "serving::prefill" not in names
    # the request's own timeline, on the rounds' step index
    tl = eng.obs.tracer.timeline(r0.request_id)
    kinds = [e["kind"] for e in tl]
    assert kinds[0] == "submit" and kinds[-1] == "finish"
    assert "admit" in kinds and "first_token" in kinds
    steps = {e["args"]["step"] for e in rounds}
    assert {e["step"] for e in tl if e["kind"] != "submit"} <= steps


@pytest.mark.slow
def test_serving_bench_unified_ab_smoke(tmp_path, monkeypatch):
    """`serving_bench.py --smoke --unified-ab` (ISSUE acceptance): the
    same long-prompt-heavy Poisson trace with the unified step on vs
    off lands in BENCH_serving.json's "unified" section (schema v5),
    the off path shows the prefill stalls the on path kills, and TTFT
    p99 does not regress with the unified step on."""
    import importlib.util
    script = os.path.join(os.path.dirname(__file__), os.pardir,
                          "scripts", "serving_bench.py")
    spec = importlib.util.spec_from_file_location(
        "serving_bench_unified", script)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = str(tmp_path / "BENCH_serving.json")
    monkeypatch.setattr(sys, "argv",
                        ["serving_bench.py", "--smoke", "--requests",
                         "4", "--unified-ab", "--out", out])
    mod.main()
    with open(out) as f:
        report = json.load(f)
    assert report["schema_version"] == 19
    uni = report["unified"]
    assert set(uni) >= {"on", "off", "long_prompt_lens", "requests"}
    on, off = uni["on"], uni["off"]
    # the A/B trace is a load SPIKE: at least 2x the slot count
    assert uni["requests"] >= 2 * report["slots"]
    assert on["completed"] == off["completed"] == uni["requests"]
    assert on["unified_steps"] > 0 and off["unified_steps"] == 0
    assert on["prefill_stall_steps"] == 0
    assert off["prefill_stall_steps"] > 0
    assert on["packed_tokens_per_step_max"] > 1
    # the acceptance number: no TTFT p99 regression with the step on
    assert on["ttft_p99_s"] <= off["ttft_p99_s"] * 1.15
