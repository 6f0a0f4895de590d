"""Fleet KV fabric: page transfer, radix persist/restore, affinity.

The load-bearing properties (ISSUE acceptance):

- Pages transferred between replicas are EXACT: a decode specialist
  continuing a stream off grafted pages is token-identical to cold
  recompute (quantized pages are codes, not approximations), and the
  fabric-off path stays bit-token-identical to fabric absent.
- Wire frames are versioned and geometry-checked — int8 ships
  codes+scales at >= 2x fewer bytes than f32 pages, fp8 at exactly
  4x fewer (the acceptance ratios, pinned below).
- `RadixPrefixCache.snapshot()/load()` move the whole tree (host
  tier included) across engines: a re-added replica answers its
  first prompt with a warm hit.
- `Router.remove_replica` no longer leaks breaker/avoided/summary
  state for gracefully removed names (S2 regression).
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.nlp import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import (FabricConfig, HostPagePool, PagePool,
                                RadixPrefixCache, SamplingParams,
                                ServingEngine, decode_frame,
                                encode_frame, frame_header,
                                parse_fabric_spec, prometheus_render,
                                prompt_fingerprints, resolve_fabric)
from paddle_tpu.serving.fabric import FABRIC_ENV, fp_seed, fp_step
from paddle_tpu.serving.http import EngineDriver, Router

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


def make_engine(**kw):
    opts = dict(num_slots=4, max_len=64, page_size=4, chunk_len=16,
                prefix_cache=True, kv_dtype="int8")
    opts.update(kw)
    return ServingEngine(tiny_gpt(), **opts)


def oracle_greedy(model, prompt, n_new):
    out = model.generate(paddle.to_tensor(np.asarray(prompt)[None]),
                         max_new_tokens=n_new).numpy()
    return out[0, len(prompt):].tolist()


def run_engine(eng, prompt, n_new):
    eng.add_request(list(prompt), SamplingParams(max_new_tokens=n_new))
    toks = []
    while eng.has_work:
        for o in eng.step():
            toks.extend(o.token_ids)
    return toks


PROMPT = [int(t) for t in
          np.random.default_rng(0).integers(1, 96, size=13)]


# -- gate -------------------------------------------------------------------
class TestGate:
    def test_spec_off_on(self):
        assert parse_fabric_spec("off") is None
        assert parse_fabric_spec("0") is None
        assert parse_fabric_spec("on") == FabricConfig()
        cfg = parse_fabric_spec("min_pages=3,summary=64,restore=off")
        assert cfg.handoff_min_pages == 3
        assert cfg.summary_limit == 64
        assert cfg.restore_on_add is False

    def test_spec_errors(self):
        with pytest.raises(ValueError, match="k=v"):
            parse_fabric_spec("min_pages")
        with pytest.raises(ValueError, match="unknown key"):
            parse_fabric_spec("bogus=1")

    def test_resolve_override_and_env(self, monkeypatch):
        monkeypatch.delenv(FABRIC_ENV, raising=False)
        assert resolve_fabric() is None          # default OFF
        assert resolve_fabric(True) == FabricConfig()
        assert resolve_fabric(False) is None
        cfg = FabricConfig(roles={"a": "prefill"})
        assert resolve_fabric(cfg) is cfg
        monkeypatch.setenv(FABRIC_ENV, "on")
        assert resolve_fabric() == FabricConfig()
        assert resolve_fabric("off") is None     # override beats env


# -- fingerprints -----------------------------------------------------------
class TestFingerprints:
    def test_chain_extends_prefix(self):
        """fps of a longer prompt start with the shorter prompt's fps
        — the chain property the affinity walk depends on."""
        a = prompt_fingerprints(list(range(20)), 4)
        b = prompt_fingerprints(list(range(30)), 4)
        assert b[:len(a)] == a

    def test_adapter_seeds_disjoint(self):
        a = prompt_fingerprints(list(range(12)), 4, adapter_id=0)
        b = prompt_fingerprints(list(range(12)), 4, adapter_id=1)
        assert not {fp for _, fp in a} & {fp for _, fp in b}

    def test_capped_below_whole_prompt(self):
        """An exactly-page-aligned prompt can never match whole (one
        token must prefill), so its deepest page is not fingerprinted."""
        fps = prompt_fingerprints(list(range(8)), 4)
        assert [d for d, _ in fps] == [1]

    def test_tree_summary_matches_prompt_walk(self):
        """RadixPrefixCache.fingerprints computes the SAME chain the
        router-side prompt walk does — the whole affinity contract."""
        pool = PagePool(16)
        cache = RadixPrefixCache(pool, 4)
        seq = np.arange(100, 112)                      # 3 full pages
        pages = pool.alloc(3)
        cache.insert(seq, pages, 12)
        tree = cache.fingerprints()
        want = {fp for _, fp in prompt_fingerprints(
            list(seq) + [0], 4)}                       # +1: uncapped
        assert want <= tree and len(tree) == 3

    def test_summary_limit_keeps_shallow(self):
        pool = PagePool(32)
        cache = RadixPrefixCache(pool, 4)
        for base in (0, 200, 400):
            seq = np.arange(base, base + 12)
            cache.insert(seq, pool.alloc(3), 12)
        capped = cache.fingerprints(limit=3)
        depth1 = {fp_step(fp_seed(0), np.arange(b, b + 4))
                  for b in (0, 200, 400)}
        assert capped == depth1                        # BFS: shallow


# -- wire frame -------------------------------------------------------------
def _int8_payloads(n_pages, shape, scale_shape, rng):
    return [(rng.integers(-127, 127, size=shape).astype(np.int8),
             rng.random(scale_shape, dtype=np.float32))
            for _ in range(n_pages)]


class TestFrameCodec:
    GEO = dict(page_size=4, n_layers=2, n_kv=2, head_dim=8)
    SHAPE = (2, 2, 4, 2, 8)          # [n_layers, 2, ps, n_kv, D]
    SCALES = (2, 2, 4, 2)

    def test_int8_roundtrip_exact(self):
        rng = np.random.default_rng(1)
        pays = _int8_payloads(3, self.SHAPE, self.SCALES, rng)
        toks = np.arange(12, dtype=np.int64)
        frame = encode_frame(kv_dtype="int8", tokens=toks,
                             payloads=pays, valid=12, adapter_id=5,
                             **self.GEO)
        hdr, out_toks, out = decode_frame(frame)
        assert hdr["kv_dtype"] == "int8" and hdr["adapter_id"] == 5
        assert np.array_equal(out_toks, toks)
        for (c0, s0), (c1, s1) in zip(pays, out):
            assert np.array_equal(c0, c1)
            assert np.array_equal(s0, s1)

    def test_fp_roundtrip_exact(self):
        rng = np.random.default_rng(2)
        pays = [rng.random(self.SHAPE, dtype=np.float32)
                for _ in range(2)]
        toks = np.arange(9, dtype=np.int64)
        frame = encode_frame(kv_dtype="fp", tokens=toks,
                             payloads=pays, valid=8, **self.GEO)
        hdr, out_toks, out = decode_frame(frame, fp_dtype=np.float32)
        assert hdr["valid"] == 8
        for a, b in zip(pays, out):
            assert np.array_equal(a, b)

    def test_wire_ratio_acceptance(self):
        """THE acceptance ratio: per-page wire bytes — int8
        (codes+scales) cuts >= 2x vs f32 pages, fp8 exactly 4x."""
        rng = np.random.default_rng(3)
        n_elem = int(np.prod(self.SHAPE))

        def payload_bytes(kv_dtype, pays, itemsize=None):
            f = encode_frame(kv_dtype=kv_dtype,
                             tokens=np.arange(4, dtype=np.int64),
                             payloads=pays, valid=4,
                             fp_itemsize=itemsize, **self.GEO)
            return frame_header(f)["payload_bytes"]

        f32 = payload_bytes(
            "fp", [rng.random(self.SHAPE, dtype=np.float32)])
        i8 = payload_bytes(
            "int8", _int8_payloads(1, self.SHAPE, self.SCALES, rng))
        fp8 = payload_bytes(
            "fp8", [rng.integers(0, 255, size=self.SHAPE)
                    .astype(np.uint8)], itemsize=1)
        assert f32 == 4 * n_elem
        assert fp8 == n_elem and f32 / fp8 == 4.0
        assert f32 / i8 >= 2.0

    def test_header_validation(self):
        frame = encode_frame(kv_dtype="fp", tokens=[1, 2, 3, 4],
                             payloads=[np.zeros(self.SHAPE,
                                                np.float32)],
                             valid=4, **self.GEO)
        with pytest.raises(ValueError, match="bad magic"):
            frame_header(b"XXXX" + frame[4:])
        with pytest.raises(ValueError, match="truncated"):
            frame_header(frame[:-3])
        # same-length in-place corruption (the header is plain JSON)
        future = frame.replace(b'"version":1', b'"version":9')
        with pytest.raises(ValueError, match="version"):
            frame_header(future)
        assert frame_header(frame)["n_pages"] == 1

    def test_fp_dtype_width_mismatch_rejected(self):
        frame = encode_frame(kv_dtype="fp", tokens=[1, 2, 3, 4],
                             payloads=[np.zeros(self.SHAPE,
                                                np.float32)],
                             valid=4, **self.GEO)
        with pytest.raises(ValueError, match="element width"):
            decode_frame(frame, fp_dtype=np.float16)

    def test_encode_valid_bounds(self):
        with pytest.raises(ValueError, match="exceeds tokens"):
            encode_frame(kv_dtype="fp", tokens=[1, 2], payloads=[],
                         valid=3, **self.GEO)
        with pytest.raises(ValueError, match="page capacity"):
            encode_frame(kv_dtype="fp", tokens=list(range(9)),
                         payloads=[np.zeros(self.SHAPE, np.float32)],
                         valid=9, **self.GEO)


# -- tree fabric mechanics (bare pool, no engine) ---------------------------
class TestTreeFabricUnit:
    PS = 4

    def make(self, num_pages=16):
        pool = PagePool(num_pages)
        cache = RadixPrefixCache(pool, self.PS)
        store = {}

        def alloc_restore(payload):
            pages = pool.alloc(1)
            if pages is None:
                return None
            store[pages[0]] = np.array(payload)
            pool.release(pages)
            pool.park(pages)
            return pages[0]

        return pool, cache, store, alloc_restore

    def insert_seq(self, pool, cache, tokens):
        tokens = np.asarray(tokens, np.int64)
        n = -(-tokens.size // self.PS)
        pages = pool.alloc(n)
        cache.insert(tokens, pages, tokens.size)
        return pages

    def test_collect_chain_walks_and_stops(self):
        pool, cache, _, _ = self.make()
        seq = np.arange(100, 112)
        pages = self.insert_seq(pool, cache, seq)
        depth, refs = cache.collect_chain(seq)
        assert depth == 12
        assert refs == [("page", p) for p in pages]
        # diverging tail: chain stops at the miss
        other = np.concatenate([seq[:4], [7, 7, 7, 7]])
        depth, refs = cache.collect_chain(other)
        assert depth == 4 and refs == [("page", pages[0])]

    def test_graft_then_acquire_hits(self):
        pool, cache, store, ar = self.make()
        toks = np.arange(50, 62)                   # 3 pages
        pays = [np.full(4, i) for i in range(3)]
        assert cache.graft(toks, pays, 12, alloc_restore=ar) == 3
        assert pool.cached_pages == 3
        grant = cache.acquire(np.concatenate([toks, [1, 2]]),
                              max_new_tokens=2)
        assert grant.cached_len == 12
        assert [store[p].tolist() for p in grant.pages[:3]] == \
            [[0] * 4, [1] * 4, [2] * 4]
        cache.release(grant.pages)

    def test_regraft_dedups_for_free(self):
        pool, cache, _, ar = self.make()
        toks = np.arange(20, 28)
        pays = [np.zeros(4), np.ones(4)]
        assert cache.graft(toks, pays, 8, alloc_restore=ar) == 2
        before = pool.free_pages
        assert cache.graft(toks, pays, 8, alloc_restore=ar) == 0
        assert pool.free_pages == before           # no page spent

    def test_graft_partial_tail_and_alloc_failure(self):
        pool, cache, _, ar = self.make(num_pages=4)   # 3 usable
        toks = np.arange(0, 11)                    # 2 full + tail 3
        pays = [np.zeros(4), np.ones(4), np.full(4, 2)]
        got = cache.graft(toks, pays, 11, alloc_restore=ar)
        assert got == 3                            # 2 full + partial
        pool2, cache2, _, ar2 = self.make(num_pages=3)  # 2 usable
        got2 = cache2.graft(toks, pays, 11, alloc_restore=ar2)
        assert got2 == 2                           # tail page denied
        assert cache2.tree_pages == 2

    def test_snapshot_load_roundtrip_with_spilled_node(self):
        pool, cache, store, ar = self.make()
        host = HostPagePool(8)
        cache.set_host_tier(
            store=lambda pages: [host.store(np.array(store[p]))
                                 for p in pages],
            load=lambda slot: ar(host.load(slot)),
            drop=host.free)
        toks = np.arange(30, 42)
        pays = [np.full(4, i + 7) for i in range(3)]
        cache.graft(toks, pays, 12, alloc_restore=ar)
        assert cache.spill(1) == 1                 # LRU page -> host
        assert cache.stats()["spilled_nodes"] == 1
        snap = cache.snapshot(lambda p: np.array(store[p]),
                              host.load)
        assert len(snap["nodes"]) == 3             # spilled INCLUDED
        pool2, cache2, store2, ar2 = self.make()
        assert cache2.load(snap, alloc_restore=ar2) == 3
        grant = cache2.acquire(np.concatenate([toks, [1]]),
                               max_new_tokens=1)
        assert grant.cached_len == 12
        assert [store2[p].tolist() for p in grant.pages[:3]] == \
            [[7] * 4, [8] * 4, [9] * 4]
        cache2.release(grant.pages)

    def test_snapshot_skips_dropped_host_subtree(self):
        """A spilled node whose host payload is GONE cannot ship —
        and neither can its children (a chain with a hole is not a
        prefix)."""
        pool, cache, store, ar = self.make()
        host = HostPagePool(8)
        cache.set_host_tier(
            store=lambda pages: [host.store(np.array(store[p]))
                                 for p in pages],
            load=lambda slot: ar(host.load(slot)),
            drop=host.free)
        toks = np.arange(60, 72)
        cache.graft(toks, [np.zeros(4), np.ones(4), np.full(4, 2)],
                    12, alloc_restore=ar)
        assert cache.spill(1) == 1     # root-most page (LRU) -> host
        snap = cache.snapshot(lambda p: np.array(store[p]),
                              lambda slot: None)   # tier dropped it
        assert snap["nodes"] == []                 # whole chain gone

    def test_load_rejects_version_and_page_size(self):
        _, cache, _, ar = self.make()
        with pytest.raises(ValueError, match="version"):
            cache.load({"version": 2, "page_size": 4, "nodes": []},
                       alloc_restore=ar)
        with pytest.raises(ValueError, match="page_size"):
            cache.load({"version": 1, "page_size": 8, "nodes": []},
                       alloc_restore=ar)


# -- engine-level transfer + restore (e2e) ----------------------------------
class TestEngineFabric:
    def test_transfer_token_identity_int8(self):
        """THE transfer acceptance: prefill on A, export the chain,
        graft on B — B's continued stream is token-identical to cold
        recompute (the oracle)."""
        ea, eb = make_engine(), make_engine()
        run_engine(ea, PROMPT, 4)
        frame = ea.export_prefix_frame(
            np.asarray(PROMPT, dtype=np.int64))
        assert frame is not None
        hdr = frame_header(frame)
        assert hdr["kv_dtype"] == "int8" and hdr["n_pages"] >= 3
        assert ea.metrics.snapshot()["fabric"]["pages_sent"] == \
            hdr["n_pages"]
        grafted = eb.import_prefix_frame(frame)
        assert grafted == hdr["n_pages"]
        toks = run_engine(eb, PROMPT, 6)
        assert toks == oracle_greedy(tiny_gpt(), PROMPT, 6)
        st = eb.prefix_cache.stats()
        assert st["hits"] == 1 and st["cached_tokens"] >= 12
        # byte accounting made it into the cost census
        census = eb.cost_census()
        assert census["fabric"]["bytes_recv"] == len(frame)
        assert census["fabric"]["pages_recv"] == grafted

    def test_geometry_mismatch_rejected_whole(self):
        ea = make_engine()
        run_engine(ea, PROMPT, 2)
        frame = ea.export_prefix_frame(
            np.asarray(PROMPT, dtype=np.int64))
        eb = make_engine(page_size=8)
        with pytest.raises(ValueError, match="page_size"):
            eb.import_prefix_frame(frame)
        assert eb.prefix_cache.tree_pages == 0     # nothing grafted

    def test_snapshot_restore_warm_engine(self):
        ea = make_engine()
        run_engine(ea, PROMPT, 4)
        snap = ea.export_prefix_state()
        assert snap["nodes"] and snap["geometry"] == \
            ea.fabric_geometry
        eb = make_engine()
        restored = eb.import_prefix_state(snap)
        assert restored == len(snap["nodes"])
        assert eb.metrics.snapshot()["fabric"]["restored_pages"] == \
            restored
        toks = run_engine(eb, PROMPT, 6)
        assert toks == oracle_greedy(tiny_gpt(), PROMPT, 6)
        assert eb.prefix_cache.stats()["hits"] == 1

    def test_flight_notes_and_exposition(self):
        ea, eb = make_engine(), make_engine()
        run_engine(ea, PROMPT, 2)
        frame = ea.export_prefix_frame(
            np.asarray(PROMPT, dtype=np.int64))
        eb.import_prefix_frame(frame)
        notes_a = [e for e in ea.obs.flight.snapshot()["steps"]
                   if e.get("note") == "fabric:send"]
        notes_b = [e for e in eb.obs.flight.snapshot()["steps"]
                   if e.get("note") == "fabric:recv"]
        assert notes_a and notes_b
        text = prometheus_render({"r0": eb.metrics.snapshot()})
        for needle in ("fabric_pages_recv_total", "fabric_bytes_recv_total",
                       "prefix_tree_pages", "prefix_spilled_nodes"):
            assert needle in text, needle


# -- router-level: disaggregation + warm restart + S2 -----------------------
class TestRouterFabric:
    def test_disaggregated_handoff_token_identity(self):
        """Prefill specialist runs the prompt at a 1-token budget,
        pages transfer, the decode specialist continues — the client
        sees ONE stream, token-identical to the solo oracle."""
        d1 = EngineDriver(make_engine(), name="pre0")
        d2 = EngineDriver(make_engine(), name="dec0")
        r = Router([d1, d2], fabric=FabricConfig(
            handoff_min_pages=2,
            roles={"pre0": "prefill", "dec0": "decode"})).start()
        try:
            t = r.submit(PROMPT, SamplingParams(max_new_tokens=8))
            toks = [v for k, v in t.events() if k == "token"]
            assert t.error is None
            assert toks == oracle_greedy(tiny_gpt(), PROMPT, 8)
            fab = r.stats()["fabric"]
            assert fab["handoffs_total"] == 1
            assert fab["pages_moved_total"] >= 2
            assert fab["transfer_failures_total"] == 0
            # the decode engine really decoded off grafted pages
            assert d2.engine.prefix_cache.stats()["hits"] >= 1
            plan_notes = [
                e for e in
                d1.engine.obs.flight.snapshot()["steps"]
                if e.get("note") == "fabric:plan"]
            assert plan_notes
        finally:
            r.drain(timeout=30)

    def test_short_prompt_skips_handoff(self):
        d1 = EngineDriver(make_engine(), name="pre0")
        d2 = EngineDriver(make_engine(), name="dec0")
        r = Router([d1, d2], fabric=FabricConfig(
            handoff_min_pages=8,           # prompt is only 3 pages
            roles={"pre0": "prefill", "dec0": "decode"})).start()
        try:
            t = r.submit(PROMPT, SamplingParams(max_new_tokens=4))
            toks = [v for k, v in t.events() if k == "token"]
            assert toks == oracle_greedy(tiny_gpt(), PROMPT, 4)
            assert r.stats()["fabric"]["handoffs_total"] == 0
        finally:
            r.drain(timeout=30)

    def test_affinity_ranks_warm_replica_first(self):
        """The SECOND replica holds the prefix: placement must pick
        it over the equally-idle first (which plain load-order would
        choose) — prefix affinity is doing the ranking."""
        e1, e2 = make_engine(), make_engine()
        run_engine(e2, PROMPT, 2)              # warm r1's tree only
        d1 = EngineDriver(e1, name="r0")
        d2 = EngineDriver(e2, name="r1")
        r = Router([d1, d2], fabric=FabricConfig()).start()
        try:
            r.refresh_fabric_summaries()
            assert len(r._fabric_fps["r1"]) >= 2
            t = r.submit(PROMPT, SamplingParams(max_new_tokens=2))
            toks = [v for k, v in t.events() if k == "token"]
            assert t.driver.name == "r1"       # affinity beat order
            assert toks == oracle_greedy(tiny_gpt(), PROMPT, 2)
        finally:
            r.drain(timeout=30)

    def test_warm_restart_and_s2_breaker_regression(self):
        d1 = EngineDriver(make_engine(), name="r0")
        d2 = EngineDriver(make_engine(), name="r1")
        r = Router([d1, d2], fabric=FabricConfig()).start()
        try:
            t = r.submit(PROMPT, SamplingParams(max_new_tokens=2))
            list(t.events())
            victim = t.driver.name
            # trip the victim's breaker so removal has state to leak
            for _ in range(8):
                r._breaker_for(victim).record_failure(r._clock())
            r._avoided_by[victim] = 3
            r.remove_replica(victim, wait=True)
            # S2: graceful removal reaps EVERY per-name structure —
            # a fresh replica must not inherit the dead one's verdict
            assert victim not in r.breakers
            assert victim not in r._avoided_by
            assert victim not in r._fabric_fps
            # ...and the drain stashed the tree for the next arrival
            assert r._fabric_snapshot is not None
            assert r._fabric_snapshot["nodes"]
            d3 = r.add_replica(make_engine())
            assert d3.engine.prefix_cache.stats()["tree_pages"] >= 2
            toks = [v for k, v in
                    r.submit(PROMPT,
                             SamplingParams(max_new_tokens=4)
                             ).events() if k == "token"]
            assert toks == oracle_greedy(tiny_gpt(), PROMPT, 4)
        finally:
            r.drain(timeout=30)

    def test_fabric_off_is_fabric_absent(self):
        """Default-off acceptance: no fabric structures, identical
        placement behavior, stats block explicitly None."""
        d1 = EngineDriver(make_engine(), name="r0")
        r = Router([d1]).start()
        try:
            assert r.fabric is None
            assert r.stats()["fabric"] is None
            t = r.submit(PROMPT, SamplingParams(max_new_tokens=4))
            toks = [v for k, v in t.events() if k == "token"]
            assert toks == oracle_greedy(tiny_gpt(), PROMPT, 4)
        finally:
            r.drain(timeout=30)

    def test_fleet_snapshot_carries_prefix_and_fabric(self):
        d1 = EngineDriver(make_engine(), name="r0")
        r = Router([d1], fabric=FabricConfig()).start()
        try:
            t = r.submit(PROMPT, SamplingParams(max_new_tokens=2))
            list(t.events())
            snap = r.fleet_snapshot()
            entry = snap["replicas"]["r0"]
            assert entry["prefix"]["tree_pages"] >= 2
            assert set(entry["fabric"]) == {
                "pages_sent", "bytes_sent", "pages_recv",
                "bytes_recv", "restored_pages"}
        finally:
            r.drain(timeout=30)
