"""Radix-tree automatic prefix cache over the paged KV pool.

The load-bearing properties (ISSUE acceptance):

- Greedy outputs with PADDLE_TPU_PREFIX_CACHE=on are TOKEN-IDENTICAL
  to the cache-off path — through full-page sharing, copy-on-write of
  partial pages, multi-turn reinsertion, and LRU eviction under page
  pressure — and no compiled program retraces across cache
  hit/miss/eviction transitions.
- Page accounting closes: after drain, free + cache-resident pages
  equals the pool size, refcount invariants hold, and PagePool raises
  on double free / free-while-referenced (hardening satellite).
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.nlp import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import (HostPagePool, PagePool,
                                RadixPrefixCache,
                                RequestState, SamplingParams,
                                ServingEngine,
                                resolve_prefix_cache_flag)
from paddle_tpu.serving.engine import SPILL_WIDTHS

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
    return out[0, len(prompt):]


def accounting_closes(eng):
    """Free + cache-resident == pool size and nothing referenced."""
    eng.pool.assert_quiesced()
    return (eng.pool.used_pages == 0
            and eng.pool.free_pages + eng.pool.cached_pages
            == eng.num_pages - 1)


class TestPagePoolInvariants:
    """Satellite: refcount hardening — double free, free-while-
    referenced/shared, use-after-free and leak checks all raise."""

    def test_double_free_raises(self):
        pool = PagePool(4)
        pages = pool.alloc(2)
        pool.free(pages)
        with pytest.raises(ValueError, match="double free"):
            pool.free([pages[0]])

    def test_free_while_shared_raises(self):
        pool = PagePool(4)
        [p] = pool.alloc(1)
        pool.retain([p])                 # second holder
        with pytest.raises(ValueError, match="still referenced"):
            pool.free([p])
        assert pool.release([p]) == []   # first holder lets go
        pool.free([p])                   # now sole-owned: legal

    def test_retain_free_page_raises(self):
        pool = PagePool(4)
        [p] = pool.alloc(1)
        pool.free([p])
        with pytest.raises(ValueError, match="use-after-free"):
            pool.retain([p])

    def test_release_unreferenced_raises(self):
        pool = PagePool(4)
        [p] = pool.alloc(1)
        assert pool.release([p]) == [p]
        with pytest.raises(ValueError, match="unreferenced"):
            pool.release([p])

    def test_park_and_retain_roundtrip(self):
        pool = PagePool(4)
        [p] = pool.alloc(1)
        pool.release([p])
        pool.park([p])
        assert pool.cached_pages == 1 and pool.used_pages == 0
        with pytest.raises(ValueError, match="already cache-resident"):
            pool.park([p])
        pool.retain([p])                 # cache hit re-references it
        assert pool.cached_pages == 0 and pool.used_pages == 1
        pool.release([p])
        pool.free([p])                   # eviction path
        assert pool.free_pages == 3

    def test_park_referenced_raises(self):
        pool = PagePool(4)
        [p] = pool.alloc(1)
        with pytest.raises(ValueError, match="referenced"):
            pool.park([p])

    def test_assert_quiesced_detects_leak(self):
        pool = PagePool(4)
        pages = pool.alloc(2)
        with pytest.raises(RuntimeError, match="leak"):
            pool.assert_quiesced()
        pool.release(pages)
        pool.park([pages[0]])
        pool.free([pages[1]])
        pool.assert_quiesced()           # free + cached == pool size

    def test_alloc_refuses_without_side_effects(self):
        pool = PagePool(4)
        assert pool.alloc(4) is None     # only 3 allocatable
        assert pool.free_pages == 3
        assert pool.alloc(3) is not None


class TestHostPagePool:
    """Satellite: host-RAM tier slot invariants at the edges."""

    def test_store_until_full_then_none(self):
        host = HostPagePool(2)
        a, b = host.store("pay-a"), host.store("pay-b")
        assert a is not None and b is not None and a != b
        assert host.store("pay-c") is None     # full: no side effects
        assert host.used_pages == 2 and host.free_pages == 0

    def test_slot_reuse_after_free(self):
        host = HostPagePool(1)
        slot = host.store("x")
        host.free(slot)
        assert host.free_pages == 1
        slot2 = host.store("y")
        assert host.load(slot2) == "y"         # reused slot, new data

    def test_load_dead_slot_raises(self):
        host = HostPagePool(2)
        slot = host.store("x")
        host.free(slot)
        with pytest.raises(ValueError, match="dead host page"):
            host.load(slot)
        with pytest.raises(ValueError, match="dead host page"):
            host.load(99)                      # never stored

    def test_double_free_raises(self):
        host = HostPagePool(2)
        slot = host.store("x")
        host.free(slot)
        with pytest.raises(ValueError, match="double free"):
            host.free(slot)

    def test_zero_capacity_tier(self):
        host = HostPagePool(0)
        assert host.store("x") is None         # spill path degrades
        with pytest.raises(ValueError):
            HostPagePool(-1)


class TestRadixTreeUnit:
    """Cache mechanics against a bare pool (no engine, no device)."""

    PS = 4

    def make(self, num_pages=16):
        pool = PagePool(num_pages)
        return pool, RadixPrefixCache(pool, self.PS)

    def insert_seq(self, pool, cache, tokens):
        """Simulate a finished request: alloc pages, insert, return
        the page ids it used."""
        tokens = np.asarray(tokens, np.int64)
        n = -(-tokens.size // self.PS)
        pages = pool.alloc(n)
        cache.insert(tokens, pages, tokens.size)
        return pages

    def test_full_page_match_shares_and_refcounts(self):
        pool, cache = self.make()
        seq = np.arange(100, 112)                 # 3 full pages
        pages = self.insert_seq(pool, cache, seq)
        assert pool.cached_pages == 3
        prompt = np.concatenate([seq, [7, 8, 9]])
        grant = cache.acquire(prompt, max_new_tokens=4)
        # all 3 full pages shared, cached_len == 12, fresh tail pages
        assert grant.cached_len == 12
        assert grant.pages[:3] == pages
        assert grant.cow_src is None
        assert all(pool.refcount(p) == 1 for p in pages)
        assert pool.cached_pages == 0             # re-referenced
        cache.release(grant.pages)                # request retires
        assert pool.cached_pages == 3             # parked again

    def test_partial_tail_match_is_copy_on_write(self):
        pool, cache = self.make()
        seq = np.arange(50, 56)                   # 1 full + partial 2
        self.insert_seq(pool, cache, seq)
        partial_page = cache.root.children[
            np.asarray(seq[:4], np.int64).tobytes()].partials[0].page
        prompt = np.asarray(list(seq[:6]) + [1, 2], np.int64)
        grant = cache.acquire(prompt, max_new_tokens=2)
        assert grant.cached_len == 6              # 4 full + 2 via COW
        assert grant.cow_src == partial_page
        assert grant.cow_dst == grant.pages[1]    # the private copy
        assert pool.refcount(grant.cow_src) == 1  # copy-protection ref
        cache.cow_done(grant)
        assert pool.refcount(partial_page) == 0   # parked again
        cache.release(grant.pages)

    def test_match_never_covers_whole_prompt(self):
        """At least one token always prefills (the sampler needs the
        last prompt token's logits)."""
        pool, cache = self.make()
        seq = np.arange(10, 18)                   # 2 full pages
        self.insert_seq(pool, cache, seq)
        grant = cache.acquire(seq, max_new_tokens=4)   # same 8 tokens
        assert grant.cached_len <= seq.size - 1
        cache.cow_done(grant)
        cache.release(grant.pages)

    def test_divergent_prompts_split_at_page_boundary(self):
        pool, cache = self.make()
        a = np.asarray([1, 2, 3, 4, 5, 6, 7, 8], np.int64)
        b = np.asarray([1, 2, 3, 4, 9, 9, 9, 9], np.int64)
        self.insert_seq(pool, cache, a)
        self.insert_seq(pool, cache, b)
        root_child = cache.root.children[a[:4].tobytes()]
        assert len(root_child.children) == 2      # both second pages
        grant = cache.acquire(np.concatenate([b, [1]]), 2)
        assert grant.cached_len == 8
        cache.release(grant.pages)

    def test_duplicate_insert_freed_not_double_indexed(self):
        pool, cache = self.make()
        seq = np.arange(30, 38)
        first = self.insert_seq(pool, cache, seq)
        before = pool.free_pages
        self.insert_seq(pool, cache, seq)         # same span again
        assert pool.free_pages == before          # dup pages freed
        assert cache.tree_pages == 2
        key = np.asarray(seq[:4], np.int64).tobytes()
        assert cache.root.children[key].page == first[0]

    def test_lru_eviction_leaf_to_root_skips_referenced(self):
        pool, cache = self.make(num_pages=9)      # 8 allocatable
        old = self.insert_seq(pool, cache, np.arange(0, 8))    # 2 pages
        new = self.insert_seq(pool, cache, np.arange(20, 28))  # 2 pages
        # touch the OLD path so "new" becomes the LRU victim
        grant = cache.acquire(np.asarray(list(range(0, 8)) + [1],
                                         np.int64), 3)
        assert grant.cached_len == 8              # holds refs on `old`
        # 3 free pages left; ask for more than free -> must evict,
        # and must NOT touch the referenced `old` chain
        assert pool.free_pages == 3
        freed = cache.evict(4)
        assert freed == 2                         # only `new` was free
        assert all(pool.refcount(p) == 1 for p in old)
        assert cache.evicted_pages_total == 2
        # leaf evicted before its parent existed-> chain fully gone
        assert np.asarray(np.arange(20, 24),
                          np.int64).tobytes() not in cache.root.children
        cache.release(grant.pages)

    def test_acquire_refusal_rolls_back_cleanly(self):
        pool, cache = self.make(num_pages=5)      # 4 allocatable
        shared = self.insert_seq(pool, cache, np.arange(0, 8))
        # prompt hits both cached pages but needs 3 fresh (8+4 tokens,
        # page 4 -> 5 total); only 2 exist even after evicting nothing
        # (the matched pages are protected)
        grant = cache.acquire(np.asarray(list(range(0, 8)) + [1, 2, 3],
                                         np.int64), 9)
        assert grant is None
        assert pool.cached_pages == 2             # match re-parked
        assert all(pool.refcount(p) == 0 for p in shared)
        pool.assert_quiesced()

    def test_restore_of_dropped_host_page_degrades_to_prefill(self):
        """Satellite: a spilled node whose host payload was dropped
        behind the cache's back. The acquire walk stops at the failed
        restore and the tail prefills — a shorter hit, never a stale
        or torn page."""
        pool, cache = self.make()
        host = HostPagePool(4)
        alive = {"load": True}
        cache.set_host_tier(
            store=lambda pages: [host.store(("kv", p)) for p in pages],
            load=lambda slot: (pool.alloc(1) or [None])[0]
            if alive["load"] else None,
            drop=host.free)
        seq = np.arange(100, 112)                 # 3 full pages
        self.insert_seq(pool, cache, seq)
        assert cache.spill(1) == 1                # LRU = root page
        assert cache.stats()["spilled_nodes"] == 1
        alive["load"] = False                     # tier lost the page
        prompt = np.concatenate([seq, [1, 2]])
        grant = cache.acquire(prompt, max_new_tokens=2)
        # the ROOT page was the spilled one: restore fails at depth 0
        assert grant.cached_len == 0
        assert cache.stats()["spilled_nodes"] == 1  # still marked
        cache.release(grant.pages)
        pool.assert_quiesced()


    def test_spill_walk_hook_wraps_each_walk_of_the_tree(self):
        """The fourth host-tier callback: `spill` enters it once a call
        with the pages it was asked for, around the walk that picks the
        candidates and before the pages are stored, all in one call (the
        engine puts its `serving::spill` span and `kv_spill_s_total`
        there)."""
        import contextlib
        pool, cache = self.make()
        host = HostPagePool(4)
        seen = []

        @contextlib.contextmanager
        def walk(need):
            seen.append(("enter", need))
            yield
            seen.append(("exit", need))

        cache.set_host_tier(
            store=lambda pages: seen.append(("store", list(pages)))
            or [host.store(("kv", p)) for p in pages],
            load=lambda slot: None, drop=host.free, spill_walk=walk)
        self.insert_seq(pool, cache, np.arange(100, 112))   # 3 pages
        assert cache.spill(2) == 2
        # ONE store call for the spill, with both pages, LRU first
        assert [k for k, _ in seen] == ["enter", "exit", "store"]
        assert seen[0] == ("enter", 2) and len(seen[2][1]) == 2
        assert cache.spill(0) == 0 and len(seen) == 3   # nothing to walk


class TestEngineEquivalence:
    """Engine-level acceptance: token identity on/off, COW, multi-turn,
    eviction under pressure, no retraces."""

    def test_hit_skips_prefill_and_stays_token_identical(self):
        model = tiny_gpt()
        p = np.arange(1, 21, dtype=np.int64) % 90
        want = oracle_greedy(model, p, 8)
        eng = ServingEngine(model, num_slots=2, max_len=64,
                            page_size=8, chunk_len=8)
        r1 = eng.add_request(p, SamplingParams(max_new_tokens=8))
        eng.run()
        chunks_cold = eng.metrics.prefill_chunks
        r2 = eng.add_request(p, SamplingParams(max_new_tokens=8))
        eng.run()
        np.testing.assert_array_equal(np.asarray(r1.output_tokens), want)
        np.testing.assert_array_equal(np.asarray(r2.output_tokens), want)
        assert r1.cached_tokens == 0
        assert r2.cached_tokens == 19           # 2 full pages + COW 3
        # 20 tokens cold = 3 chunks; warm = 1 chunk for the 1 real token
        assert chunks_cold == 3
        assert eng.metrics.prefill_chunks - chunks_cold == 1
        assert eng.prefix_cache.cow_copies_total == 1
        assert accounting_closes(eng)

    def test_shared_prefix_trace_on_off_token_identical(self):
        """The acceptance A/B: same shared-prefix + disjoint trace
        through cache-on and cache-off engines — token streams match
        each other and the solo oracle."""
        model = tiny_gpt()
        sysp = (np.arange(1, 19, dtype=np.int64) * 3) % 90
        prompts = [
            np.concatenate([sysp, [5, 6]]),
            np.concatenate([sysp, [7]]),
            np.array([42, 17, 3], np.int64),          # disjoint
            np.concatenate([sysp, [5, 6]]),           # exact repeat
            np.array([9, 9, 9, 9, 9], np.int64),      # disjoint
        ]
        want = [oracle_greedy(model, p, 6) for p in prompts]
        outs = {}
        for flag in (True, False):
            eng = ServingEngine(model, num_slots=2, max_len=64,
                                page_size=8, chunk_len=8,
                                prefix_cache=flag)
            reqs = [eng.add_request(p, SamplingParams(max_new_tokens=6))
                    for p in prompts]
            eng.run()
            outs[flag] = [list(r.output_tokens) for r in reqs]
            if flag:
                assert any(r.cached_tokens > 0 for r in reqs)
                assert accounting_closes(eng)
            else:
                assert eng.prefix_cache is None
                assert eng.pool.free_pages == eng.num_pages - 1
        for i, w in enumerate(want):
            assert outs[True][i] == outs[False][i] == list(w), i

    def test_multi_turn_follow_up_hits_decoded_pages(self):
        """Turn 2 re-sends turn 1's prompt + completion: the decoded
        pages inserted at retirement serve the follow-up."""
        model = tiny_gpt()
        p1 = np.arange(1, 13, dtype=np.int64)
        eng = ServingEngine(model, num_slots=2, max_len=96,
                            page_size=8, chunk_len=8)
        r1 = eng.add_request(p1, SamplingParams(max_new_tokens=8))
        eng.run()
        p2 = np.concatenate([p1, np.asarray(r1.output_tokens, np.int64),
                             np.array([33, 34], np.int64)])
        want2 = oracle_greedy(model, p2, 6)
        r2 = eng.add_request(p2, SamplingParams(max_new_tokens=6))
        eng.run()
        np.testing.assert_array_equal(np.asarray(r2.output_tokens),
                                      want2)
        # the whole first turn (prompt + 8 decoded) is cached history
        assert r2.cached_tokens >= p1.size + 8 - eng.page_size
        assert accounting_closes(eng)

    def test_eviction_under_pressure_stays_token_identical(self):
        """Pool far too small to cache every retiree: disjoint waves
        force leaf-to-root eviction, outputs stay exact, accounting
        closes."""
        model = tiny_gpt()
        rng = np.random.RandomState(3)
        prompts = [rng.randint(0, 97, size=rng.randint(2, 12))
                   .astype(np.int64) for _ in range(8)]
        want = [oracle_greedy(model, p, 6) for p in prompts]
        eng = ServingEngine(model, num_slots=2, max_len=32,
                            page_size=8, num_pages=7, chunk_len=8)
        reqs = [eng.add_request(p, SamplingParams(max_new_tokens=6))
                for p in prompts]
        eng.run()
        for r, w in zip(reqs, want):
            np.testing.assert_array_equal(np.asarray(r.output_tokens), w)
        assert eng.prefix_cache.evicted_pages_total > 0
        assert accounting_closes(eng)

    def test_no_retrace_across_hit_miss_eviction(self):
        """The compiled step, the COW copy and the swap programs stay
        ONE program each across hits, misses, COW admissions and
        evictions."""
        model = tiny_gpt()
        eng = ServingEngine(model, num_slots=3, max_len=32,
                            page_size=8, num_pages=9, chunk_len=16)
        base = np.arange(1, 10, dtype=np.int64)
        rng = np.random.RandomState(0)
        for i in range(6):
            eng.add_request(base, SamplingParams(max_new_tokens=4),
                            request_id=f"hit-{i}")
            eng.add_request(rng.randint(0, 97, size=rng.randint(1, 12))
                            .astype(np.int64),
                            SamplingParams(max_new_tokens=4),
                            request_id=f"miss-{i}")
            eng.run()
        assert eng.prefix_cache.hits > 0
        # page pressure fired: since the host tier (PR 9) parked pages
        # SPILL to host RAM before anything is dropped, pressure shows
        # up as spills first and evictions only once the tier is full
        assert (eng.prefix_cache.evicted_pages_total
                + eng.prefix_cache.spilled_pages_total) > 0
        assert eng._unified_fn._cache_size() == 1
        if eng._copy_page_fn is not None:
            assert eng._copy_page_fn._cache_size() == 1
        if eng._swap_out_fn is not None:    # spill traffic happened
            assert eng._swap_out_fn._cache_size() == len(SPILL_WIDTHS)
        if eng._swap_in_fn is not None:     # restore traffic happened
            assert eng._swap_in_fn._cache_size() == 1
        assert accounting_closes(eng)

    def test_cancel_while_holding_shared_pages(self):
        """Satellite edge case: cancelling a resident that shares tree
        pages releases its references without freeing the tree — later
        identical prompts still hit and match the oracle."""
        model = tiny_gpt()
        p = np.arange(1, 21, dtype=np.int64) % 90
        want = oracle_greedy(model, p, 8)
        eng = ServingEngine(model, num_slots=2, max_len=64,
                            page_size=8, chunk_len=8)
        eng.add_request(p, SamplingParams(max_new_tokens=8))
        eng.run()                                   # seeds the tree
        b = eng.add_request(p, SamplingParams(max_new_tokens=8))
        eng.step()
        eng.step()
        assert b.cached_tokens > 0 and b.state is RequestState.DECODE
        shared = b.pages[:2]
        assert all(eng.pool.refcount(pg) == 1 for pg in shared)
        eng.cancel(b.request_id)
        eng.run()
        assert b.finish_reason == "cancelled"
        assert all(eng.pool.refcount(pg) == 0 for pg in shared)
        assert all(eng.pool.is_cached(pg) for pg in shared)
        c = eng.add_request(p, SamplingParams(max_new_tokens=8))
        eng.run()
        assert c.cached_tokens > 0
        np.testing.assert_array_equal(np.asarray(c.output_tokens), want)
        assert accounting_closes(eng)

    def test_eviction_racing_admission_same_boundary(self):
        """Two admissions in one step boundary where the second's
        eviction runs while the first holds freshly matched pages: the
        first's match is refcount-protected, both outputs exact."""
        model = tiny_gpt()
        pa = np.arange(1, 9, dtype=np.int64)        # 8 tokens, 1 page
        pb = np.array([90, 91, 92, 93, 94, 95, 96, 1], np.int64)
        want_a = oracle_greedy(model, pa, 7)
        want_b = oracle_greedy(model, pb, 7)
        # 6 allocatable pages, page_size 8: each request needs 2
        eng = ServingEngine(model, num_slots=2, max_len=16,
                            page_size=8, num_pages=7, chunk_len=8)
        seed_a = eng.add_request(pa, SamplingParams(max_new_tokens=7))
        seed_b = eng.add_request(pb, SamplingParams(max_new_tokens=7))
        eng.run()          # tree: both prompts' pages resident
        assert eng.pool.cached_pages == 4
        # both admitted at the SAME boundary: a hits its cached page,
        # b's fresh allocation must evict — but never a's protected match
        ra = eng.add_request(pa, SamplingParams(max_new_tokens=7))
        rb = eng.add_request(pb, SamplingParams(max_new_tokens=7))
        eng.run()
        np.testing.assert_array_equal(np.asarray(ra.output_tokens),
                                      want_a)
        np.testing.assert_array_equal(np.asarray(rb.output_tokens),
                                      want_b)
        assert ra.cached_tokens > 0
        assert accounting_closes(eng)
        np.testing.assert_array_equal(
            np.asarray(seed_a.output_tokens), want_a)
        np.testing.assert_array_equal(
            np.asarray(seed_b.output_tokens), want_b)

    def test_flag_gating_env_and_ctor(self, monkeypatch):
        model = tiny_gpt()
        monkeypatch.setenv("PADDLE_TPU_PREFIX_CACHE", "off")
        eng = ServingEngine(model, num_slots=1, max_len=32)
        assert eng.prefix_cache is None
        eng = ServingEngine(model, num_slots=1, max_len=32,
                            prefix_cache=True)    # ctor overrides env
        assert eng.prefix_cache is not None
        monkeypatch.setenv("PADDLE_TPU_PREFIX_CACHE", "on")
        eng = ServingEngine(model, num_slots=1, max_len=32)
        assert eng.prefix_cache is not None
        assert resolve_prefix_cache_flag("off") is False
        with pytest.raises(ValueError, match="on\\|off"):
            resolve_prefix_cache_flag("sometimes")

    def test_metrics_and_usage_surface_hits(self):
        model = tiny_gpt()
        eng = ServingEngine(model, num_slots=2, max_len=64,
                            page_size=8, chunk_len=8)
        p = np.arange(1, 18, dtype=np.int64)
        eng.add_request(p, SamplingParams(max_new_tokens=4))
        eng.run()
        r2 = eng.add_request(p, SamplingParams(max_new_tokens=4))
        eng.run()
        snap = eng.metrics.snapshot()
        pf = snap["prefix"]
        assert pf["lookups"] == 2 and pf["hits"] == 1
        assert pf["hit_rate"] == 0.5
        assert pf["cached_tokens"] == r2.cached_tokens > 0
        assert pf["resident_pages"] == eng.pool.cached_pages > 0
        assert snap["pool"]["pages_cached"] == eng.pool.cached_pages
        assert pf["cached_tokens_per_request"]["count"] == 2
        out = r2.output()
        assert out.cached_tokens == r2.cached_tokens
