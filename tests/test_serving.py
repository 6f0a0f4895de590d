"""Serving engine: continuous batching over the compiled decode path.

The load-bearing property (ISSUE acceptance): a request's greedy tokens
through `ServingEngine` are BIT-IDENTICAL to running it alone through
`CompiledGenerator` greedy decode, no matter what its slot-neighbors do
— including neighbors joining late, finishing early, or being cancelled
mid-stream.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.nlp import (GPTConfig, GPTForCausalLM, LlamaConfig,
                            LlamaForCausalLM)
from paddle_tpu.serving import (EngineClosed, QueueFull, Request,
                                RequestState, SamplingParams, Scheduler,
                                ServingEngine, ServingMetrics)


_MODELS = {}   # engines/oracles never mutate the model: share per module


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


def tiny_llama():
    m = _MODELS.get("llama")
    if m is None:
        paddle.seed(11)
        cfg = LlamaConfig(vocab_size=89, hidden_size=32,
                          num_hidden_layers=2, num_attention_heads=4,
                          num_key_value_heads=2, intermediate_size=48,
                          max_position_embeddings=128)
        m = _MODELS["llama"] = LlamaForCausalLM(cfg)
        m.eval()
    return m


def oracle_greedy(model, prompt, n_new):
    """The request alone through CompiledGenerator greedy decode."""
    out = model.generate(paddle.to_tensor(prompt[None]),
                         max_new_tokens=n_new).numpy()
    return out[0, prompt.size:]


class TestSchedulerPolicy:
    def test_fifo_admission_and_refill(self):
        s = Scheduler(num_slots=2)
        reqs = [Request(f"r{i}", np.array([1, 2]), SamplingParams())
                for i in range(4)]
        for r in reqs:
            s.submit(r)
        grants = s.assign()
        assert [r.request_id for _, r in grants] == ["r0", "r1"]
        assert s.queue_depth == 2 and s.occupancy == 1.0
        assert s.assign() == []          # no free slot
        s.retire(grants[0][0])
        refill = s.assign()
        assert [r.request_id for _, r in refill] == ["r2"]  # arrival order
        assert refill[0][0] == grants[0][0]                 # freed slot

    def test_max_queue_sheds_load_with_typed_error(self):
        """QueueFull (a RuntimeError subclass — old callers keep
        working) lets the HTTP layer map load shedding to 429 without
        string-matching."""
        s = Scheduler(num_slots=1, max_queue=1)
        s.submit(Request("a", np.array([1]), SamplingParams()))
        with pytest.raises(QueueFull) as ei:
            s.submit(Request("b", np.array([1]), SamplingParams()))
        assert isinstance(ei.value, RuntimeError)
        assert ei.value.retry_after_s > 0

    def test_pop_queued_empties_the_queue(self):
        s = Scheduler(num_slots=1)
        reqs = [Request(f"r{i}", np.array([1]), SamplingParams())
                for i in range(3)]
        for r in reqs:
            s.submit(r)
        assert s.pop_queued() == reqs
        assert s.queue_depth == 0 and s.pop_queued() == []

    def test_expired_finds_deadline_overruns(self):
        s = Scheduler(num_slots=1)
        r = Request("a", np.array([1]),
                    SamplingParams(timeout_s=5.0), arrival_t=100.0)
        s.submit(r)
        assert s.expired(104.0) == []
        assert s.expired(105.0) == [r]


class TestEquivalence:
    def test_staggered_arrivals_match_solo_compiled_greedy(self):
        """>= 3 staggered requests, different prompt lengths: greedy
        tokens identical to per-request CompiledGenerator output."""
        model = tiny_gpt()
        prompts = [np.array([3, 14, 15, 9], np.int64),
                   np.array([26, 5, 35], np.int64),
                   np.array([1, 2, 3, 4, 5, 6], np.int64)]
        want = [oracle_greedy(model, p, 8) for p in prompts]

        eng = ServingEngine(model, num_slots=2, max_len=64)
        reqs = [eng.add_request(prompts[0],
                                SamplingParams(max_new_tokens=8))]
        eng.step()
        eng.step()
        reqs.append(eng.add_request(prompts[1],
                                    SamplingParams(max_new_tokens=8)))
        eng.step()
        # 2 slots busy: third queues, joins whichever slot frees first
        reqs.append(eng.add_request(prompts[2],
                                    SamplingParams(max_new_tokens=8)))
        while eng.has_work:
            eng.step()
        for r, w in zip(reqs, want):
            np.testing.assert_array_equal(np.asarray(r.output_tokens), w)
            assert r.finish_reason == "length"

    def test_llama_gqa_rotary_matches_solo(self):
        """Vector-pos path through GQA + per-row rotary offsets."""
        model = tiny_llama()
        prompts = [np.array([3, 14, 15, 9], np.int64),
                   np.array([26, 5, 35], np.int64),
                   np.array([7, 8], np.int64)]
        want = [oracle_greedy(model, p, 6) for p in prompts]
        eng = ServingEngine(model, num_slots=3, max_len=48)
        reqs = [eng.add_request(prompts[0],
                                SamplingParams(max_new_tokens=6))]
        eng.step()
        reqs.append(eng.add_request(prompts[1],
                                    SamplingParams(max_new_tokens=6)))
        eng.step()
        reqs.append(eng.add_request(prompts[2],
                                    SamplingParams(max_new_tokens=6)))
        while eng.has_work:
            eng.step()
        for r, w in zip(reqs, want):
            np.testing.assert_array_equal(np.asarray(r.output_tokens), w)

    def test_cancellation_frees_slot_without_perturbing_neighbors(self):
        """Mid-stream cancel: the slot is handed to a queued request at
        the next boundary; the surviving neighbor and the late joiner
        both stay bit-identical to solo decode."""
        model = tiny_gpt()
        pa = np.array([3, 14, 15, 9], np.int64)
        pb = np.array([26, 5, 35], np.int64)
        pc = np.array([1, 2, 3, 4, 5], np.int64)
        want_a = oracle_greedy(model, pa, 10)
        want_c = oracle_greedy(model, pc, 6)

        eng = ServingEngine(model, num_slots=2, max_len=64)
        ra = eng.add_request(pa, SamplingParams(max_new_tokens=10))
        rb = eng.add_request(pb, SamplingParams(max_new_tokens=10))
        rc = eng.add_request(pc, SamplingParams(max_new_tokens=6))
        eng.step()
        eng.step()
        eng.step()
        assert rc.state is RequestState.QUEUED   # both slots busy
        assert eng.cancel(rb.request_id)
        outs = eng.step()                        # evict rb, admit rc
        assert [o.request_id for o in outs] == [rb.request_id]
        assert rb.finish_reason == "cancelled"
        assert 0 < len(rb.output_tokens) < 10    # genuinely mid-stream
        assert rc.slot is not None
        while eng.has_work:
            eng.step()
        np.testing.assert_array_equal(np.asarray(ra.output_tokens),
                                      want_a)
        np.testing.assert_array_equal(np.asarray(rc.output_tokens),
                                      want_c)

    def test_eos_retires_slot_and_tokens_match(self):
        model = tiny_gpt()
        p = np.array([3, 14, 15, 9], np.int64)
        free = oracle_greedy(model, p, 6)
        eos = int(free[0])       # first generated token == instant stop
        eng = ServingEngine(model, num_slots=2, max_len=64)
        r_eos = eng.add_request(p, SamplingParams(max_new_tokens=6,
                                                  eos_token_id=eos))
        r_other = eng.add_request(np.array([26, 5, 35], np.int64),
                                  SamplingParams(max_new_tokens=6))
        while eng.has_work:
            eng.step()
        assert r_eos.finish_reason == "stop"
        assert r_eos.output_tokens == [eos]      # eos token included
        assert len(r_other.output_tokens) == 6
        np.testing.assert_array_equal(
            np.asarray(r_other.output_tokens),
            oracle_greedy(model, np.array([26, 5, 35], np.int64), 6))


class TestLifecycleAndPolicy:
    def test_states_progress_and_output_record(self):
        model = tiny_gpt()
        eng = ServingEngine(model, num_slots=1, max_len=32)
        seen = []
        r = eng.add_request(
            np.array([1, 2, 3], np.int64),
            SamplingParams(max_new_tokens=3),
            on_token=lambda req, tok: seen.append(tok))
        assert r.state is RequestState.QUEUED
        outs = eng.run()
        assert r.state is RequestState.FINISHED
        assert seen == r.output_tokens and len(seen) == 3
        [o] = outs
        assert o.request_id == r.request_id
        assert o.finish_reason == "length"
        assert o.token_ids == r.output_tokens
        assert o.ttft_s is not None and o.ttft_s >= 0
        assert o.e2e_s >= o.ttft_s

    def test_timeout_evicts_queued_and_running(self):
        model = tiny_gpt()
        t = [0.0]
        eng = ServingEngine(model, num_slots=1, max_len=32,
                            clock=lambda: t[0])
        run = eng.add_request(np.array([1, 2], np.int64),
                              SamplingParams(max_new_tokens=30,
                                             timeout_s=10.0))
        qd = eng.add_request(np.array([3, 4], np.int64),
                             SamplingParams(max_new_tokens=4,
                                            timeout_s=5.0))
        t[0] = 1.0
        eng.step()           # run admitted; qd waits
        t[0] = 6.0
        eng.step()           # qd's deadline passed while queued
        assert qd.finish_reason == "timeout"
        eng.step()           # the first decode step committed
        t[0] = 11.0
        eng.step()           # run's deadline passed while decoding
        assert run.finish_reason == "timeout"
        assert len(run.output_tokens) > 0
        assert not eng.has_work

    def test_cancel_queued_request(self):
        model = tiny_gpt()
        eng = ServingEngine(model, num_slots=1, max_len=32)
        a = eng.add_request(np.array([1, 2], np.int64),
                            SamplingParams(max_new_tokens=4))
        b = eng.add_request(np.array([3, 4], np.int64),
                            SamplingParams(max_new_tokens=4))
        assert eng.cancel(b.request_id)
        assert b.finish_reason == "cancelled"
        assert b.output_tokens == []
        eng.run()
        assert a.finish_reason == "length"

    def test_capacity_guard(self):
        model = tiny_gpt()
        eng = ServingEngine(model, num_slots=1, max_len=16)
        with pytest.raises(ValueError):
            eng.add_request(np.arange(1, 17, dtype=np.int64))
        with pytest.raises(ValueError):
            eng.add_request(np.arange(1, 9, dtype=np.int64),
                            SamplingParams(max_new_tokens=9))

    def test_per_request_sampling_params_coexist(self):
        """A sampling request next to greedy neighbors: greedy rows stay
        bit-identical, the sampling row emits valid tokens."""
        model = tiny_gpt()
        pg = np.array([3, 14, 15, 9], np.int64)
        want = oracle_greedy(model, pg, 6)
        eng = ServingEngine(model, num_slots=2, max_len=48)
        rg = eng.add_request(pg, SamplingParams(max_new_tokens=6))
        rs = eng.add_request(
            np.array([26, 5, 35], np.int64),
            SamplingParams(max_new_tokens=6, temperature=0.8, top_k=5,
                           top_p=0.9))
        assert not rs.sampling.greedy
        eng.run()
        np.testing.assert_array_equal(np.asarray(rg.output_tokens), want)
        assert len(rs.output_tokens) == 6
        assert all(0 <= t < 97 for t in rs.output_tokens)


class TestMetricsAndTrace:
    def test_snapshot_reports_ttft_throughput_occupancy(self):
        model = tiny_gpt()
        eng = ServingEngine(model, num_slots=2, max_len=48)
        for i in range(3):
            eng.add_request(np.array([1 + i, 2, 3], np.int64),
                            SamplingParams(max_new_tokens=4))
        eng.run()
        snap = eng.metrics.snapshot()
        assert snap["requests"]["received"] == 3
        assert snap["requests"]["completed"] == 3
        assert snap["tokens_generated"] == 12
        assert snap["tokens_per_sec"] is not None \
            and snap["tokens_per_sec"] > 0
        assert snap["ttft_s"]["count"] == 3
        assert snap["ttft_s"]["p99"] >= snap["ttft_s"]["p50"] > 0
        assert snap["inter_token_s"]["count"] == 9   # 3 req x 3 gaps
        assert 0 < snap["occupancy_hist"]["mean"] <= 1.0
        assert snap["slot_occupancy"] == 0.0         # drained
        assert snap["decode_steps"] > 0

    def test_metrics_histogram_percentiles(self):
        m = ServingMetrics()
        for v in [1.0, 2.0, 3.0, 4.0, 5.0]:
            m.ttft_s.record(v)
        s = m.ttft_s.snapshot()
        assert s["count"] == 5 and s["mean"] == 3.0
        assert s["min"] == 1.0 and s["max"] == 5.0
        assert s["p50"] == 3.0 and s["p99"] == 5.0


class TestPagedPoolAndChunkedPrefill:
    """Tentpole invariants of the paged KV pool: bit-identity through
    chunked prefill, page-table indirection and page reuse; ≥2x
    resident requests under a dense-equivalent HBM budget; and a
    bounded compiled-program count (no retrace across membership or
    page-table changes, O(log) prefill buckets)."""

    def test_chunked_prefill_interleaves_and_matches_solo(self):
        """A prompt longer than chunk_len prefills across several steps
        while a resident neighbor keeps decoding — one token per step,
        never stalled — and both stay bit-identical to solo decode."""
        model = tiny_gpt()
        pa = np.array([3, 14, 15, 9], np.int64)
        pb = np.arange(1, 21, dtype=np.int64) % 90      # plen 20 > chunk
        want_a = oracle_greedy(model, pa, 12)
        want_b = oracle_greedy(model, pb, 8)
        eng = ServingEngine(model, num_slots=2, max_len=64,
                            page_size=8, chunk_len=8)
        ra = eng.add_request(pa, SamplingParams(max_new_tokens=12))
        eng.step()
        eng.step()
        rb = eng.add_request(pb, SamplingParams(max_new_tokens=8))
        # plen 20 / chunk 8 -> 3 chunks, ONE per step; ra must emit a
        # token on every one of those steps (prefill never stalls it)
        prefill_steps = 0
        while rb.state is not RequestState.DECODE:
            before = len(ra.output_tokens)
            eng.step()
            prefill_steps += 1
            assert len(ra.output_tokens) == before + 1
        assert prefill_steps == 3
        while eng.has_work:
            eng.step()
        np.testing.assert_array_equal(np.asarray(ra.output_tokens),
                                      want_a)
        np.testing.assert_array_equal(np.asarray(rb.output_tokens),
                                      want_b)

    def test_page_reuse_after_eviction_stays_bit_identical(self):
        """Waves of requests through a pool too small to hold them all
        at once: later waves decode on pages freed by earlier ones and
        still match solo CompiledGenerator decode exactly."""
        model = tiny_gpt()
        prompts = [np.array([3, 14, 15, 9], np.int64),
                   np.array([26, 5, 35], np.int64),
                   np.array([1, 2, 3, 4, 5, 6], np.int64),
                   np.array([42, 17], np.int64)]
        want = [oracle_greedy(model, p, 10) for p in prompts]
        # 4 allocatable pages; each request needs 2 -> two waves
        eng = ServingEngine(model, num_slots=2, max_len=32,
                            page_size=8, num_pages=5, chunk_len=8)
        reqs = [eng.add_request(p, SamplingParams(max_new_tokens=10))
                for p in prompts]
        eng.run()
        for r, w in zip(reqs, want):
            np.testing.assert_array_equal(np.asarray(r.output_tokens), w)
        # accounting closes: nothing referenced — every page is free or
        # parked in the prefix cache (finished requests stay resident)
        assert eng.pool.used_pages == 0
        assert eng.pool.free_pages + eng.pool.cached_pages == 4
        assert eng.prefix_cache.evicted_pages_total > 0   # pool pressure

    def test_2x_residency_under_dense_equivalent_hbm_budget(self):
        """Acceptance: with page_size=16 and the SAME simulated HBM
        budget as a 2-slot dense engine (2 x 96 = 192 KV rows), short
        requests (prompt+output <= 48 tokens) sustain >= 2x the
        concurrent residents (dense: 2)."""
        model = tiny_gpt()
        dense_slots, max_len = 2, 96
        budget_rows = dense_slots * max_len              # 192
        page_size = 16
        num_pages = budget_rows // page_size + 1         # 12 + trash
        eng = ServingEngine(model, num_slots=8, max_len=max_len,
                            page_size=page_size, num_pages=num_pages,
                            chunk_len=16)
        assert (eng.num_pages - 1) * page_size <= budget_rows
        want = None
        reqs = []
        for i in range(8):
            p = np.array([3 + i, 14, 15, 9], np.int64)   # 4 + 28 <= 48
            reqs.append(eng.add_request(
                p, SamplingParams(max_new_tokens=28)))
            if i == 0:
                want = oracle_greedy(model, p, 28)
        peak = 0
        while eng.has_work:
            eng.step()
            peak = max(peak, len(eng.scheduler.running))
        assert peak >= 2 * dense_slots, peak
        # and the pool never lied about its budget
        assert eng.metrics.pool_pages_total == num_pages - 1
        np.testing.assert_array_equal(
            np.asarray(reqs[0].output_tokens), want)


class TestSchedulerEdgeCases:
    """Timeout-while-QUEUED, cancel racing admission, and max_queue
    backpressure interacting with page-aware admission."""

    def test_timeout_fires_while_queued_behind_full_slots(self):
        model = tiny_gpt()
        t = [0.0]
        eng = ServingEngine(model, num_slots=1, max_len=32,
                            clock=lambda: t[0])
        run = eng.add_request(np.array([1, 2], np.int64),
                              SamplingParams(max_new_tokens=20))
        qd = eng.add_request(np.array([3, 4], np.int64),
                             SamplingParams(max_new_tokens=4,
                                            timeout_s=2.0))
        eng.step()
        assert qd.state is RequestState.QUEUED
        t[0] = 3.0
        eng.step()                  # deadline passed while QUEUED
        assert qd.finish_reason == "timeout"
        assert qd.output_tokens == [] and qd.pages is None
        eng.run()
        assert run.finish_reason == "length"

    def test_cancel_races_admission_in_same_step(self):
        """Cancelling a queued request in the same step that would have
        admitted it: the slot (and its pages) go to the next in line."""
        model = tiny_gpt()
        eng = ServingEngine(model, num_slots=1, max_len=32,
                            page_size=8)
        a = eng.add_request(np.array([1, 2], np.int64),
                            SamplingParams(max_new_tokens=3))
        b = eng.add_request(np.array([3, 4], np.int64),
                            SamplingParams(max_new_tokens=3))
        assert eng.cancel(a.request_id)     # before any step ran
        eng.step()
        assert a.finish_reason == "cancelled" and a.output_tokens == []
        assert b.slot is not None           # b won the freed admission
        eng.run()
        assert b.finish_reason == "length"
        assert eng.pool.used_pages == 0      # b's pages parked or free
        assert eng.pool.free_pages + eng.pool.cached_pages \
            == eng.num_pages - 1

    def test_page_backpressure_holds_queue_despite_free_slot(self):
        """A free SLOT is not admission: the queue head waits until its
        page budget is free, and max_queue sheds load measured at the
        queue, independent of pool state."""
        model = tiny_gpt()
        # 2 allocatable pages; each request needs 2 (4 + 20 > 16)
        eng = ServingEngine(model, num_slots=2, max_len=32,
                            page_size=16, num_pages=3, max_queue=1)
        a = eng.add_request(np.array([1, 2, 3, 4], np.int64),
                            SamplingParams(max_new_tokens=20))
        eng.step()                          # a takes the whole pool
        b = eng.add_request(np.array([5, 6, 7, 8], np.int64),
                            SamplingParams(max_new_tokens=4))
        with pytest.raises(RuntimeError):   # queue full (max_queue=1)
            eng.add_request(np.array([9], np.int64))
        eng.step()
        # slot 1 is free but the pool is exhausted: b must wait
        assert a.state is RequestState.DECODE
        assert b.state is RequestState.QUEUED
        assert eng.pool.free_pages == 0
        eng.step()
        assert b.state is RequestState.QUEUED   # still held back
        while a.state is not RequestState.FINISHED:
            eng.step()
        while eng.has_work:
            eng.step()
        assert b.finish_reason == "length"      # admitted after free
        assert len(b.output_tokens) == 4

    def test_generate_rejects_mismatched_sampling_list(self):
        model = tiny_gpt()
        eng = ServingEngine(model, num_slots=2, max_len=32)
        prompts = [np.array([1, 2], np.int64),
                   np.array([3, 4], np.int64)]
        with pytest.raises(ValueError, match="sampling list length"):
            eng.generate(prompts, [SamplingParams(max_new_tokens=2)])
        with pytest.raises(ValueError, match="sampling list length"):
            eng.generate(prompts, [SamplingParams(max_new_tokens=2)] * 3)
        outs = eng.generate(prompts, [SamplingParams(max_new_tokens=2),
                                      SamplingParams(max_new_tokens=3)])
        assert [len(o.token_ids) for o in outs] == [2, 3]


class TestDrainAndAbort:
    """Graceful-shutdown primitives the HTTP layer builds on: drain()
    finishes residents without admitting, abort_all() force-retires
    everything; BOTH return every page to the pool."""

    def test_drain_finishes_residents_aborts_queued_frees_pages(self):
        model = tiny_gpt()
        p = np.array([3, 14, 15, 9], np.int64)
        want = oracle_greedy(model, p, 6)
        eng = ServingEngine(model, num_slots=1, max_len=32, page_size=8)
        resident = eng.add_request(p, SamplingParams(max_new_tokens=6))
        queued = eng.add_request(np.array([26, 5, 35], np.int64),
                                 SamplingParams(max_new_tokens=6))
        eng.step()
        eng.step()
        assert resident.state is RequestState.DECODE
        assert queued.state is RequestState.QUEUED
        outs = eng.drain()
        # resident ran to completion, untouched by the shutdown
        assert resident.finish_reason == "length"
        np.testing.assert_array_equal(
            np.asarray(resident.output_tokens), want)
        # queued never started: aborted, zero tokens, never held pages
        assert queued.finish_reason == "aborted"
        assert queued.output_tokens == [] and queued.pages is None
        assert {o.request_id for o in outs} == {resident.request_id,
                                               queued.request_id}
        # accounting closes (leak-checked inside drain), nothing
        # resident, engine closed for intake; the finished resident's
        # pages stay cache-resident for future prefix hits
        assert eng.pool.used_pages == 0
        assert eng.pool.free_pages + eng.pool.cached_pages \
            == eng.num_pages - 1
        assert not eng.has_work and eng.closed
        with pytest.raises(EngineClosed):
            eng.add_request(p, SamplingParams(max_new_tokens=2))
        assert eng.drain() == []          # idempotent

    def test_abort_all_force_retires_everything_and_frees_pages(self):
        model = tiny_gpt()
        eng = ServingEngine(model, num_slots=2, max_len=32, page_size=8)
        ra = eng.add_request(np.array([3, 14, 15, 9], np.int64),
                             SamplingParams(max_new_tokens=10))
        rb = eng.add_request(np.array([26, 5, 35], np.int64),
                             SamplingParams(max_new_tokens=10))
        rc = eng.add_request(np.array([1, 2], np.int64),
                             SamplingParams(max_new_tokens=4))
        eng.step()
        eng.step()                        # ra/rb decoding, rc queued
        assert eng.pool.used_pages > 0
        outs = eng.abort_all("replica_failure")
        assert len(outs) == 3
        assert all(r.finish_reason == "replica_failure"
                   for r in (ra, rb, rc))
        assert len(ra.output_tokens) > 0      # keeps partial output
        assert rc.output_tokens == []         # unstarted: retry-safe
        assert eng.pool.free_pages == eng.num_pages - 1
        assert not eng.has_work
        assert eng.metrics.requests_aborted == 3
        with pytest.raises(EngineClosed):
            eng.add_request(np.array([1], np.int64))

    def test_abort_all_wakes_stream_readers(self):
        """A thread blocked on Request.stream() unblocks when the
        request is force-retired (the HTTP layer depends on this)."""
        model = tiny_gpt()
        eng = ServingEngine(model, num_slots=1, max_len=64)
        r = eng.add_request(np.array([3, 14, 15, 9], np.int64),
                            SamplingParams(max_new_tokens=30))
        eng.step()
        eng.step()
        eng.abort_all()
        assert r.wait(timeout=1.0)
        assert list(r.stream()) == r.output_tokens


def test_replicas_sharing_a_model_trace_concurrently():
    """Engine programs take the weights as arguments, so tracing one
    swaps TRACERS into the model's tensors. Replicas that share a
    model trace their first step from their own threads, next to the
    solo generator reading the same tensors: without the process-wide
    swap lock one thread restores another's tracers into the model
    (UnexpectedTracerError, or a poisoned request). More threads than
    the race needs, a short switch interval, every join bounded."""
    import sys
    import threading
    model = tiny_gpt()
    prompts = [np.arange(3 + i, 9 + i) for i in range(4)]
    engines = [ServingEngine(model, num_slots=2, max_len=64,
                             page_size=8, chunk_len=16)
               for _ in prompts]
    got, errors = [None] * len(prompts), []

    def first_step(i):
        try:
            out = engines[i].generate(
                [prompts[i]], SamplingParams(max_new_tokens=6))
            got[i] = list(out[0].token_ids)
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(repr(e))

    def solo(i):
        try:
            got.append(list(oracle_greedy(model, prompts[i], 6)))
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(repr(e))

    threads = [threading.Thread(target=first_step, args=(i,))
               for i in range(len(prompts))] + \
        [threading.Thread(target=solo, args=(0,))]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors
    import jax
    assert not any(isinstance(p._value, jax.core.Tracer)
                   for p in model.parameters())
    for i, p in enumerate(prompts):
        assert got[i] == list(oracle_greedy(model, p, 6)), i


def test_serving_bench_smoke_writes_stable_schema(tmp_path,
                                                  monkeypatch):
    """`serving_bench.py --smoke` in-process: one JSON line + a
    stable-schema BENCH_serving.json for the perf trajectory."""
    import importlib.util
    script = os.path.join(os.path.dirname(__file__), os.pardir,
                          "scripts", "serving_bench.py")
    spec = importlib.util.spec_from_file_location("serving_bench",
                                                  script)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = str(tmp_path / "BENCH_serving.json")
    monkeypatch.setattr(sys, "argv",
                        ["serving_bench.py", "--smoke", "--requests",
                         "3", "--out", out])
    mod.main()
    with open(out) as f:
        report = json.load(f)
    assert report["bench"] == "serving"
    assert report["schema_version"] == 19
    for key in ("tokens_per_sec", "ttft_p50_s", "ttft_p99_s",
                "pool_utilization_mean", "pool_utilization_max",
                "prefill_chunks", "page_size", "num_pages",
                "chunk_len", "completed", "attn_impl",
                "decode_step_ms_p50", "ab", "prefix_stats"):
        assert key in report, key
    assert report["completed"] == report["requests"] == 3
    assert report["tokens_per_sec"] > 0
    assert 0 < report["pool_utilization_max"] <= 1.0
    # the A/B: both paged-attention impls ran the same trace to
    # completion, kernel is the default, per-step wall time recorded
    assert report["attn_impl"] == "kernel"
    assert set(report["ab"]) == {"kernel", "gather"}
    for impl, run in report["ab"].items():
        assert run["completed"] == 3, impl
        assert run["decode_step_ms_p50"] > 0, impl
    # prefix-cache counters ride in the default run's report
    assert report["prefix_stats"]["lookups"] > 0
    assert "hit_rate" in report["prefix_stats"]


@pytest.mark.slow
def test_serving_bench_prefix_share_smoke(tmp_path, monkeypatch):
    """`serving_bench.py --smoke --prefix-share 0.8` (ISSUE
    acceptance): the same shared-prefix trace with the cache on does
    strictly fewer prefill chunks per request than with it off, and
    hit-rate/cached-token numbers land in the report."""
    import importlib.util
    script = os.path.join(os.path.dirname(__file__), os.pardir,
                          "scripts", "serving_bench.py")
    spec = importlib.util.spec_from_file_location(
        "serving_bench_prefix", script)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = str(tmp_path / "BENCH_serving.json")
    monkeypatch.setattr(sys, "argv",
                        ["serving_bench.py", "--smoke", "--requests",
                         "6", "--prefix-share", "0.8", "--out", out])
    mod.main()    # bench asserts on < off prefill chunks internally
    with open(out) as f:
        report = json.load(f)
    sec = report["prefix"]
    assert sec["share"] == 0.8
    on, off = sec["on"], sec["off"]
    assert on["completed"] == off["completed"] == 6
    assert on["prefill_chunks_per_request"] \
        < off["prefill_chunks_per_request"]
    assert on["hit_rate"] > 0 and on["cached_tokens"] > 0
    assert off["cached_tokens"] == 0


@pytest.mark.slow
def test_serving_bench_smoke():
    """scripts/serving_bench.py end-to-end (Poisson trace, JSON line)."""
    script = os.path.join(os.path.dirname(__file__), os.pardir,
                          "scripts", "serving_bench.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, script, "--smoke"],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    report = json.loads(out.stdout.strip().splitlines()[-1])
    assert report["bench"] == "serving"
    assert report["completed"] == report["requests"]
    assert report["tokens_per_sec"] > 0
    assert report["ttft_p50_s"] > 0
