"""Multi-tenant LoRA adapter serving (serving/adapters.py).

The load-bearing property (ISSUE 14 acceptance): a request served
under adapter `i` in a MIXED-TENANT batch — other tenants and
base-model rows sharing the same unified step — emits tokens
bit-identical to serving it alone on the DENSE-MERGED model
(`W + B·A·scale` folded into the projection weights), and the ONE
unified trace never retraces across adapter churn, eviction and
spill-restore (cache_size probe, the technique of
test_serving_prefix.py).

Non-slow lane stays lean (tier-1 budget): the tiny 2-layer models,
rank <= 8, K <= 4 adapters, a handful of engine compiles. The full
{int8, fp8, mp=2, spec, preempt} x adapter matrix, the HTTP/migration
e2e and the bench smoke ride the `slow` marker.
"""
import json
import os
import sys
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.nlp import (GPTConfig, GPTForCausalLM, LlamaConfig,
                            LlamaForCausalLM)
from paddle_tpu.serving import (AdapterStore, BASE_ADAPTER,
                                LoRAWeights, RadixPrefixCache,
                                PagePool, SamplingParams,
                                ServingEngine, ServingMetrics,
                                make_random_lora, prometheus_render,
                                resolve_adapters_flag)
from paddle_tpu.serving.http.driver import EngineDriver
from paddle_tpu.serving.http.protocol import (ProtocolError,
                                              parse_completion_request)
from paddle_tpu.serving.http.router import Router


_MODELS = {}      # engines/oracles never mutate the model: share


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


# deterministic test adapters (shared across tests; weights are big
# enough, at amp 0.2-0.25, to flip greedy argmax on the tiny models)
def gpt_adapters(n=3):
    key = f"gpt_adapters_{n}"
    ws = _MODELS.get(key)
    if ws is None:
        rng = np.random.RandomState(5)
        ws = _MODELS[key] = [
            make_random_lora(2, 32, 32, 32, rank=r, rng=rng, amp=0.25)
            for r in (2, 4, 8)[:n]]
    return ws


def merged_gpt(weights):
    """The dense-merged oracle model: rebuild tiny_gpt from its seed,
    fold scale*A@B into the fused qkv_proj (interleaved per-head
    [h, H, 3D] layout) and out_proj."""
    paddle.seed(7)
    cfg = tiny_gpt().config
    m = GPTForCausalLM(cfg)
    m.eval()
    h, H = cfg.hidden_size, cfg.num_attention_heads
    D = h // H
    for li, layer in enumerate(m.gpt.layers):
        att = layer.attn
        w = att.qkv_proj.weight.numpy().copy().reshape(h, H, 3 * D)
        for j, proj in enumerate(("q", "k", "v")):
            A, B = weights.layers[li][proj]
            delta = weights.scale * (np.asarray(A) @ np.asarray(B))
            w[:, :, j * D:(j + 1) * D] += delta.reshape(h, H, D)
        att.qkv_proj.weight.set_value(w.reshape(h, 3 * h))
        A, B = weights.layers[li]["o"]
        att.out_proj.weight.set_value(
            att.out_proj.weight.numpy().copy()
            + weights.scale * (np.asarray(A) @ np.asarray(B)))
    return m


def merged_llama(weights):
    paddle.seed(11)
    cfg = tiny_llama().config
    m = LlamaForCausalLM(cfg)
    m.eval()
    for li, layer in enumerate(m.llama.layers):
        att = layer.self_attn
        for proj, mod in (("q", att.q_proj), ("k", att.k_proj),
                          ("v", att.v_proj), ("o", att.o_proj)):
            A, B = weights.layers[li][proj]
            mod.weight.set_value(
                mod.weight.numpy().copy()
                + weights.scale * (np.asarray(A) @ np.asarray(B)))
    return m


def oracle_tokens(model, prompt, n_new, **engine_kw):
    """The request ALONE through a plain (adapter-free) engine on
    `model` — for a merged model this is THE dense-merged oracle."""
    eng = ServingEngine(model, num_slots=2, max_len=64, **engine_kw)
    out = eng.generate([np.asarray(prompt, np.int64)],
                       SamplingParams(max_new_tokens=n_new))
    return out[0].token_ids


def tiny_store(num_pages=3, rank_buckets=(2, 4), host_pages=None):
    """A standalone AdapterStore over toy dims (1 layer, hidden 4)."""
    return AdapterStore(1, 4, 4, 4, num_pages=num_pages,
                        rank_buckets=rank_buckets,
                        host_pages=host_pages)


def toy_lora(rank=2, seed=0, amp=0.1):
    rng = np.random.RandomState(seed)
    return make_random_lora(1, 4, 4, 4, rank=rank, rng=rng, amp=amp)


# -- the gate ---------------------------------------------------------------
class TestAdapterFlag:
    def test_resolve_flag_env_and_override(self, monkeypatch):
        monkeypatch.delenv("PADDLE_TPU_ADAPTERS", raising=False)
        assert resolve_adapters_flag() is False        # default off
        monkeypatch.setenv("PADDLE_TPU_ADAPTERS", "on")
        assert resolve_adapters_flag() is True
        assert resolve_adapters_flag(False) is False   # override wins
        monkeypatch.setenv("PADDLE_TPU_ADAPTERS", "banana")
        with pytest.raises(ValueError, match="PADDLE_TPU_ADAPTERS"):
            resolve_adapters_flag()

    def test_sampling_adapter_id_validated(self):
        with pytest.raises(ValueError, match="adapter_id"):
            SamplingParams(adapter_id=-1)


# -- the store (paged-pool discipline, no engine) ---------------------------
class TestAdapterStore:
    def test_register_rank_buckets_and_registry(self):
        st = tiny_store()
        a = st.register("a", toy_lora(rank=2))
        b = st.register("b", toy_lora(rank=3, seed=1))   # pads to 4
        assert (a, b) == (1, 2)
        assert st.id_for("a") == 1 and st.id_for("nope") is None
        assert st.name_of(a) == "a" and st.name_of(0) == "base"
        assert st.known(0) and st.known(b) and not st.known(99)
        assert st.bucket_for(3) == 4
        with pytest.raises(ValueError, match="rank bucket"):
            st.register("big", toy_lora(rank=5, seed=2))
        with pytest.raises(ValueError, match="already registered"):
            st.register("a", toy_lora())
        with pytest.raises(ValueError, match="shapes"):
            st.register("bad", LoRAWeights(
                [{"q": (np.zeros((3, 2)), np.zeros((2, 4)))}], rank=2))
        with pytest.raises(ValueError, match="layers"):
            st.register("bad2", LoRAWeights([], rank=2))

    def test_base_adapter_is_the_zero_page(self):
        st = tiny_store()
        assert st.acquire(BASE_ADAPTER) == (0, 0.0)
        st.release(BASE_ADAPTER)                    # no-op, no raise
        assert st.is_hot(BASE_ADAPTER)
        with pytest.raises(ValueError, match="unknown adapter_id"):
            st.acquire(42)

    def test_residency_refcount_park_spill_restore(self):
        st = tiny_store(num_pages=3)    # 2 allocatable adapter pages
        a1 = st.register("a1", toy_lora(seed=1))
        a2 = st.register("a2", toy_lora(seed=2))
        a3 = st.register("a3", toy_lora(seed=3))
        page1, scale1 = st.acquire(a1)
        assert st.pool.refcount(page1) == 1
        assert scale1 == toy_lora(seed=1).scale
        st.acquire(a1)                  # second resident slot
        assert st.pool.refcount(page1) == 2
        st.release(a1)
        st.release(a1)                  # last user: PARKS hot
        assert st.pool.is_cached(page1) and st.is_hot(a1)
        assert st.loads_total == 1
        # fill the pool; a3 must displace the parked a1 (LRU) via a
        # SPILL to the host tier (device page freed, host copy kept)
        st.acquire(a2)
        st.acquire(a3)
        assert st.spills_total == 1 and not st.is_hot(a1)
        assert st.stats()["spilled"] == 1
        assert sorted(st.hot_ids()) == [a2, a3]
        # every page referenced -> acquiring a1 must REFUSE (admission
        # backpressure), never touch a referenced adapter
        assert st.acquire(a1) is None
        # a parked page frees the way: a1 restores FROM THE HOST COPY
        st.release(a2)
        page1b, _ = st.acquire(a1)
        assert st.restores_total == 1 and st.is_hot(a1)
        # quiesce: a held reference is a leak; parked/spilled is fine
        with pytest.raises(RuntimeError, match="leak"):
            st.assert_quiesced()
        st.release(a1)
        st.release(a3)
        st.assert_quiesced()

    def test_eviction_without_host_tier(self):
        st = tiny_store(num_pages=2, host_pages=0)  # 1 page, no host
        a1 = st.register("a1", toy_lora(seed=1))
        a2 = st.register("a2", toy_lora(seed=2))
        st.acquire(a1)
        st.release(a1)
        st.acquire(a2)          # displaces a1: EVICT (host tier full)
        assert st.evictions_total == 1 and st.spills_total == 0
        st.release(a2)
        # a1 re-acquires from the REGISTRY (weights are immutable:
        # eviction loses residency, never data)
        assert st.acquire(a1) is not None
        assert st.loads_total == 3
        st.release(a1)
        st.assert_quiesced()


# -- prefix-cache tenant isolation (unit) -----------------------------------
class TestPrefixTenantIsolation:
    def test_identical_prompts_under_different_adapters_miss(self):
        pool = PagePool(32)
        cache = RadixPrefixCache(pool, page_size=4)
        seq = np.arange(1, 11, dtype=np.int64)        # 10 tokens
        pages = pool.alloc(3)
        cache.insert(seq, pages, 10, adapter_id=1)
        # tenant 1 hits its own pages...
        assert cache.lookup(seq, adapter_id=1) >= 8
        g1 = cache.acquire(seq, 4, adapter_id=1)
        assert g1 is not None and g1.cached_len >= 8
        cache.release(g1.pages)
        if g1.cow_src is not None:
            cache.cow_done(g1)
        # ...tenant 2 and the base model MISS the identical prompt
        assert cache.lookup(seq, adapter_id=2) == 0
        assert cache.lookup(seq, adapter_id=0) == 0
        g2 = cache.acquire(seq, 4, adapter_id=2)
        assert g2 is not None and g2.cached_len == 0
        cache.release(g2.pages)

    def test_eviction_walks_every_namespace(self):
        pool = PagePool(32)
        cache = RadixPrefixCache(pool, page_size=4)
        for aid in (0, 1, 2):
            seq = np.arange(1, 9, dtype=np.int64)
            pages = pool.alloc(2)
            cache.insert(seq, pages, 8, adapter_id=aid)
        assert pool.cached_pages == 6
        freed = cache.evict(6)
        assert freed == 6 and pool.cached_pages == 0
        assert cache.clear() == 0


# -- THE acceptance: mixed-tenant batch vs dense-merged oracle ---------------
class TestMixedTenantOracle:
    def test_mixed_batch_bit_token_identical_with_churn(self):
        """>= 3 adapters + base rows in ONE engine, adapter pool
        deliberately undersized (2 pages for 3 adapters): every
        tenant's stream must be bit-token-identical to its solo
        dense-merged oracle, the one unified trace must never
        retrace across the churn (cache_size 1), spill/evict traffic
        must actually have happened, and drain must leave both the
        KV pool AND the adapter pool quiesced."""
        model = tiny_gpt()
        ws = gpt_adapters(3)
        prompt = np.array([3, 14, 15, 9, 22], np.int64)
        eng = ServingEngine(model, num_slots=4, max_len=64,
                            adapters=True, adapter_pages=2)
        ids = [eng.adapters.register(f"t{i}", w)
               for i, w in enumerate(ws)]
        sp = lambda aid: SamplingParams(max_new_tokens=6,  # noqa: E731
                                        adapter_id=aid)
        outs = eng.generate(
            [prompt] * 6,
            [sp(ids[0]), sp(ids[1]), sp(ids[2]),
             sp(0), sp(ids[0]), sp(0)])
        oracles = {i: oracle_tokens(merged_gpt(w), prompt, 6)
                   for i, w in enumerate(ws)}
        base = oracle_tokens(model, prompt, 6)
        assert outs[0].token_ids == oracles[0]
        assert outs[1].token_ids == oracles[1]
        assert outs[2].token_ids == oracles[2]
        assert outs[3].token_ids == base
        assert outs[4].token_ids == oracles[0]   # repeat, after churn
        assert outs[5].token_ids == base
        # tenants really produce DIFFERENT streams (the deltas bite)
        assert oracles[0] != base and oracles[1] != oracles[0]
        st = eng.adapters.stats()
        assert st["loads_total"] >= 3
        assert st["spills_total"] + st["evictions_total"] >= 1, st
        # ONE trace across tenant mix + churn (the retrace probe)
        assert eng._unified_fn._cache_size() == 1
        # round 2: spill-restore correctness — the SAME requests
        # again (adapters restored from host/registry) repeat their
        # exact streams, still with one trace. Same-adapter prompts
        # now HIT the tenant-namespaced prefix cache.
        outs2 = eng.generate([prompt] * 3,
                             [sp(ids[0]), sp(ids[2]), sp(0)])
        assert outs2[0].token_ids == oracles[0]
        assert outs2[1].token_ids == oracles[2]
        assert outs2[2].token_ids == base
        assert outs2[0].cached_tokens > 0     # same tenant: hit
        assert eng._unified_fn._cache_size() == 1
        eng.drain()       # asserts KV-pool AND adapter-pool quiesce

    def test_prefix_isolation_end_to_end(self):
        """Identical prompts under different adapters must not share
        KV pages: tenant B's first run MISSES (cached_tokens 0)
        even though tenant A just inserted the same token sequence,
        and both still match their oracles; a same-tenant re-run
        HITS."""
        model = tiny_gpt()
        ws = gpt_adapters(2)
        prompt = np.array([5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15, 16,
                           17, 18, 19, 20, 21, 22], np.int64)
        eng = ServingEngine(model, num_slots=2, max_len=64,
                            adapters=True, adapter_pages=3)
        a = eng.adapters.register("a", ws[0])
        b = eng.adapters.register("b", ws[1])
        sp = lambda aid: SamplingParams(max_new_tokens=4,  # noqa: E731
                                        adapter_id=aid)
        out_a1 = eng.generate([prompt], [sp(a)])[0]
        assert out_a1.cached_tokens == 0              # cold
        out_b = eng.generate([prompt], [sp(b)])[0]
        assert out_b.cached_tokens == 0               # ISOLATED miss
        out_base = eng.generate([prompt], [sp(0)])[0]
        assert out_base.cached_tokens == 0            # isolated too
        out_a2 = eng.generate([prompt], [sp(a)])[0]
        assert out_a2.cached_tokens > 0               # same tenant hit
        assert out_a2.token_ids == out_a1.token_ids   # hit is exact
        assert out_a1.token_ids == oracle_tokens(merged_gpt(ws[0]),
                                                 prompt, 4)
        assert out_b.token_ids == oracle_tokens(merged_gpt(ws[1]),
                                                prompt, 4)
        eng.drain()

    def test_llama_gqa_separate_projections(self):
        """The Llama path (separate q/k/v/o projections, GQA
        n_kv < n_heads, rope after the delta) matches its merged
        oracle too."""
        model = tiny_llama()
        rng = np.random.RandomState(9)
        w = make_random_lora(2, 32, 32, 16, rank=4, rng=rng, amp=0.2)
        prompt = np.array([3, 14, 15, 9], np.int64)
        eng = ServingEngine(model, num_slots=2, max_len=64,
                            adapters=True, adapter_pages=2)
        aid = eng.adapters.register("llama-t", w)
        out = eng.generate([prompt, prompt],
                           [SamplingParams(max_new_tokens=6,
                                           adapter_id=aid),
                            SamplingParams(max_new_tokens=6)])
        want = oracle_tokens(merged_llama(w), prompt, 6)
        base = oracle_tokens(model, prompt, 6)
        assert out[0].token_ids == want and want != base
        assert out[1].token_ids == base
        eng.drain()


# -- engine validation ------------------------------------------------------
class TestEngineValidation:
    def test_adapter_id_without_subsystem_rejected(self):
        eng = ServingEngine(tiny_gpt(), num_slots=2, max_len=64)
        with pytest.raises(ValueError, match="no adapter subsystem"):
            eng.add_request(np.array([1, 2, 3]),
                            SamplingParams(adapter_id=1))

    def test_unknown_adapter_id_rejected(self):
        eng = ServingEngine(tiny_gpt(), num_slots=2, max_len=64,
                            adapters=True)
        with pytest.raises(ValueError, match="unknown adapter_id"):
            eng.add_request(np.array([1, 2, 3]),
                            SamplingParams(adapter_id=7))


# -- observability + metrics ------------------------------------------------
class TestAdapterObservability:
    def test_debug_state_flight_and_prometheus(self):
        model = tiny_gpt()
        ws = gpt_adapters(1)
        eng = ServingEngine(model, num_slots=2, max_len=64,
                            adapters=True, adapter_pages=2)
        aid = eng.adapters.register("obs-t", ws[0])
        prompt = np.array([3, 14, 15, 9], np.int64)
        r1 = eng.add_request(prompt, SamplingParams(
            max_new_tokens=4, adapter_id=aid))
        r2 = eng.add_request(prompt + 1, SamplingParams(
            max_new_tokens=4))
        eng.step()
        eng.step()
        # /debug/state: registered adapters w/ refcount + state, and
        # residents tagged with their adapter id
        ds = eng.debug_state()
        assert ds["adapters"] is not None
        reg = ds["adapters"]["registered"]
        assert reg[0]["name"] == "obs-t"
        assert reg[0]["state"] == "resident"
        assert reg[0]["refcount"] == 1
        by_id = {r["request_id"]: r for r in ds["residents"]}
        assert by_id[r1.request_id]["adapter_id"] == aid
        assert by_id[r2.request_id]["adapter_id"] == 0
        # flight recorder: slot->adapter map + pool occupancy
        rec = eng.obs.flight.snapshot()["steps"][-1]
        assert [r1.slot, aid] in rec["slot_adapters"]
        assert rec["adapters_resident"] >= 1
        # flight_dump renders the adapter column
        sys.path.insert(0, os.path.join(
            os.path.dirname(__file__), os.pardir, "scripts"))
        from flight_dump import render_flight
        text = render_flight(eng.obs.flight.snapshot(), name="t")
        header = text.splitlines()[1]
        assert "adapter" in header
        # metrics: pool gauges + per-adapter request counters,
        # engine_info carries adapters="on", exposition renders
        eng.run()
        snap = eng.metrics.snapshot()
        assert snap["adapters_enabled"] is True
        assert snap["adapters"]["loads_total"] >= 1
        assert snap["adapters"]["requests_by_adapter"] == {
            "0": 1, str(aid): 1}
        text = prometheus_render({"r0": snap})
        assert 'adapters="on"' in text
        for series in ("adapter_pool_pages_used",
                       "adapter_pool_pages_cached",
                       "adapter_pool_pages_swapped",
                       "adapter_loads_total",
                       "adapter_evictions_total",
                       "adapter_spills_total"):
            assert f"paddle_serving_{series}" in text, series
        assert ('paddle_serving_adapter_requests_total{adapter="'
                + str(aid)) in text
        # exposition stays parseable: every non-comment line is
        # `name{labels} value`
        import re
        for line in text.strip().splitlines():
            if line.startswith("#"):
                continue
            assert re.match(
                r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? \S+$',
                line), line
        eng.drain()

    def test_per_adapter_counter_cardinality_cap(self):
        m = ServingMetrics()
        for aid in range(20):
            m.on_adapter_request(aid)
        m.on_step(0, 0.0, 1, adapter_stats={"pages_used": 0})
        by = m.snapshot()["adapters"]["requests_by_adapter"]
        assert len(by) == 9                      # 8 ids + "other"
        assert by["other"] == 12

    def test_engine_info_off_by_default(self):
        snap = {"requests": {}, "tokens_generated": 0,
                "queue_depth": 0, "slot_occupancy": 0.0,
                "pool": {"pages_total": 0, "pages_used": 0},
                "ttft_s": {"count": 0, "sum": 0.0},
                "inter_token_s": {"count": 0, "sum": 0.0}}
        text = prometheus_render({"r0": snap})
        assert 'adapters="off"' in text
        assert "adapter_pool_pages_used{" not in text


# -- router affinity + HTTP protocol ----------------------------------------
class TestRouterAndProtocol:
    def test_model_field_parses(self):
        creq = parse_completion_request(json.dumps({
            "prompt": [1, 2, 3], "max_tokens": 4,
            "model": "tenant-a"}).encode())
        assert creq.model == "tenant-a"
        assert creq.sampling.adapter_id == 0     # resolved serverside
        with pytest.raises(ProtocolError):
            parse_completion_request(json.dumps({
                "prompt": [1, 2, 3], "model": 7}).encode())

    def test_resolve_model_and_hot_adapter_affinity(self):
        model = tiny_gpt()
        ws = gpt_adapters(2)
        engines = [ServingEngine(model, num_slots=2, max_len=64,
                                 adapters=True, adapter_pages=2)
                   for _ in range(2)]
        ids = []
        for e in engines:
            ids = [e.adapters.register(f"t{i}", w)
                   for i, w in enumerate(ws)]
        drivers = [EngineDriver(e, name=f"replica-{i}")
                   for i, e in enumerate(engines)]
        router = Router(drivers)
        # registry: same names -> same ids on every replica
        assert router.resolve_model("t0") == ids[0]
        assert router.resolve_model("t1") == ids[1]
        assert router.resolve_model("nope") is None
        # make t0 HOT on replica-1 only (resident-parked)
        engines[1].adapters.acquire(ids[0])
        engines[1].adapters.release(ids[0])
        assert drivers[1].stats()["adapters_hot"] == [ids[0]]
        assert drivers[0].stats()["adapters_hot"] == []
        k0 = router._load_key(drivers[0], ids[0])
        k1 = router._load_key(drivers[1], ids[0])
        assert k1 < k0          # hot beats cold at equal health/load
        # base traffic sees no affinity difference
        assert router._load_key(drivers[0], 0)[1] == \
            router._load_key(drivers[1], 0)[1] == 0


# -- the slow matrix --------------------------------------------------------
@pytest.mark.slow
class TestAdapterMatrixSlow:
    def _mixed(self, **engine_kw):
        """One mixed-tenant run (2 adapters + base) under the given
        engine config; returns (outputs, weights, prompt)."""
        model = tiny_gpt()
        ws = gpt_adapters(2)
        prompt = np.array([3, 14, 15, 9, 22], np.int64)
        eng = ServingEngine(model, num_slots=4, max_len=64,
                            adapters=True, adapter_pages=2,
                            **engine_kw)
        ids = [eng.adapters.register(f"t{i}", w)
               for i, w in enumerate(ws)]
        outs = eng.generate(
            [prompt] * 3,
            [SamplingParams(max_new_tokens=6, adapter_id=ids[0]),
             SamplingParams(max_new_tokens=6, adapter_id=ids[1]),
             SamplingParams(max_new_tokens=6)])
        assert eng._unified_fn._cache_size() == 1
        eng.drain()
        return outs, ws, prompt, model

    @pytest.mark.parametrize("kv", ["int8", "fp8"])
    def test_quantized_kv_lanes(self, kv):
        """Quantized pools: the oracle is the merged engine at the
        SAME kv lane (quantization drifts vs fp, but the tenant delta
        must be exactly the merged weights' effect)."""
        outs, ws, prompt, model = self._mixed(kv_dtype=kv)
        for i, w in enumerate(ws):
            want = oracle_tokens(merged_gpt(w), prompt, 6,
                                 kv_dtype=kv)
            assert outs[i].token_ids == want, (kv, i)
        assert outs[2].token_ids == oracle_tokens(model, prompt, 6,
                                                  kv_dtype=kv)

    def test_spec_decode_identity(self):
        """Draft-then-verify under adapters: the drafter proposes
        from history, verification runs through the lora-fused step
        — tokens stay exactly the merged model's greedy stream."""
        model = tiny_gpt()
        ws = gpt_adapters(2)
        # repeating prompt: the n-gram drafter actually accepts
        prompt = np.array([5, 6, 7, 5, 6, 7, 5, 6, 7], np.int64)
        eng = ServingEngine(model, num_slots=2, max_len=64,
                            adapters=True, adapter_pages=2,
                            spec="ngram:3")
        ids = [eng.adapters.register(f"t{i}", w)
               for i, w in enumerate(ws)]
        outs = eng.generate(
            [prompt, prompt],
            [SamplingParams(max_new_tokens=10, adapter_id=ids[0]),
             SamplingParams(max_new_tokens=10, adapter_id=ids[1])])
        for i, w in enumerate(ws):
            assert outs[i].token_ids == oracle_tokens(
                merged_gpt(w), prompt, 10), i
        eng.drain()

    def test_preempt_swap_resume_identity(self):
        """A preempted tenant resumes token-identically: its adapter
        reference drops at preemption (the pool may churn it) and
        re-acquires at resume."""
        model = tiny_gpt()
        ws = gpt_adapters(1)
        prompt = np.array([3, 14, 15, 9], np.int64)
        # tiny KV pool: the high-priority arrival cannot fit until
        # the low-priority tenant resident is preempted
        eng = ServingEngine(model, num_slots=2, max_len=64,
                            page_size=16, num_pages=3,
                            adapters=True, adapter_pages=2)
        aid = eng.adapters.register("t", ws[0])
        low = eng.add_request(prompt, SamplingParams(
            max_new_tokens=20, adapter_id=aid, priority=5))
        eng.step()
        eng.step()
        hi = eng.add_request(prompt + 1, SamplingParams(
            max_new_tokens=8, priority=0))
        eng.run()
        assert low.preemptions >= 1
        assert low.output_tokens == oracle_tokens(
            merged_gpt(ws[0]), prompt, 20)
        assert hi.output_tokens == oracle_tokens(model, prompt + 1, 8)
        eng.drain()

    def test_mesh_mp2_identity_and_collectives(self):
        """dp1xmp2: A/B pools placed to match the column-parallel
        head sharding — tenant streams stay bit-token-identical to
        the single-device adapters engine (and its merged oracle),
        with zero all-reduces in the compiled step."""
        outs1, ws, prompt, model = self._mixed()
        model2 = tiny_gpt()
        eng = ServingEngine(model2, num_slots=4, max_len=64,
                            adapters=True, adapter_pages=2,
                            mesh="dp1xmp2")
        ids = [eng.adapters.register(f"t{i}", w)
               for i, w in enumerate(ws)]
        outs2 = eng.generate(
            [prompt] * 3,
            [SamplingParams(max_new_tokens=6, adapter_id=ids[0]),
             SamplingParams(max_new_tokens=6, adapter_id=ids[1]),
             SamplingParams(max_new_tokens=6)])
        for a, b in zip(outs1, outs2):
            assert a.token_ids == b.token_ids
        cc = eng.collective_counts()
        assert cc["all_reduce"] == 0
        assert cc["reduce_scatter"] == 0
        eng.drain()

    def test_http_model_field_and_migration(self):
        """End to end over the router: `model=` maps through the
        registry, an unknown model 404s, and a mid-stream replica
        kill migrates the TENANT stream token-identically (the
        adapter id rides the Ticket's sampling)."""
        from paddle_tpu.serving.http.server import ServingHTTPServer
        from urllib.request import Request as UrlReq, urlopen
        from urllib.error import HTTPError

        model = tiny_gpt()
        ws = gpt_adapters(1)
        engines = [ServingEngine(model, num_slots=2, max_len=64,
                                 adapters=True, adapter_pages=2)
                   for _ in range(2)]
        for e in engines:
            e.adapters.register("tenant-a", ws[0])
            e.generate([np.array([1, 2, 3])],
                       SamplingParams(max_new_tokens=2))
        drivers = [EngineDriver(e, name=f"replica-{i}")
                   for i, e in enumerate(engines)]
        router = Router(drivers, max_retries=3, backoff_base_s=0.0)
        srv = ServingHTTPServer(router, port=0).start()
        try:
            prompt = [3, 14, 15, 9]
            body = json.dumps({"prompt": prompt, "max_tokens": 6,
                               "model": "tenant-a"}).encode()
            with urlopen(UrlReq(srv.url + "/v1/completions",
                                data=body,
                                headers={"Content-Type":
                                         "application/json"}),
                         timeout=30) as resp:
                out = json.load(resp)
            want = oracle_tokens(merged_gpt(ws[0]), prompt, 6)
            assert out["choices"][0]["token_ids"] == want
            assert out["model"] == "tenant-a"
            # unknown model -> 404 model_not_found
            bad = json.dumps({"prompt": prompt,
                              "model": "nope"}).encode()
            with pytest.raises(HTTPError) as ei:
                urlopen(UrlReq(srv.url + "/v1/completions", data=bad,
                               headers={"Content-Type":
                                        "application/json"}),
                        timeout=30)
            assert ei.value.code == 404
            # mid-stream migration keeps the tenant stream exact
            want_long = oracle_tokens(merged_gpt(ws[0]), prompt, 20)
            t = router.submit(np.array(prompt, np.int64),
                              SamplingParams(max_new_tokens=20,
                                             adapter_id=1))
            deadline = time.monotonic() + 30
            while not t.request.output_tokens \
                    and time.monotonic() < deadline:
                time.sleep(0.005)
            t.driver.kill()
            toks = []
            for kind, val in t.events(poll_s=0.01):
                if kind == "token":
                    toks.append(val)
                elif kind in ("done", "error"):
                    assert kind == "done" and val == "length"
                    break
            assert toks == want_long
            assert t.migrations == 1
        finally:
            srv.drain(timeout=30)

    def test_bench_lora_ab_smoke(self, tmp_path, monkeypatch):
        import importlib.util
        script = os.path.join(os.path.dirname(__file__), os.pardir,
                              "scripts", "serving_bench.py")
        spec = importlib.util.spec_from_file_location(
            "serving_bench_lora", script)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out = str(tmp_path / "BENCH_serving.json")
        monkeypatch.setattr(sys, "argv",
                            ["serving_bench.py", "--smoke",
                             "--lora-ab", "--out", out])
        mod.main()
        with open(out) as f:
            report = json.load(f)
        assert report["schema_version"] == 19
        lr = report["lora"]
        assert lr["token_identical"] is True
        assert lr["tokens_per_sec_ratio"] > 1.0
        assert lr["adapter_pool"]["loads_total"] >= lr["adapters"]
