"""A spill's pages leave the pools in one batch: a compiled gather a width
of `SPILL_WIDTHS`, copies to host RAM that are set off at the next
step's launch and collected after its fetch, slots handed out at once.

What must hold whatever the timing: the host tier ends with the bytes
the page-at-a-time synchronous read gives, read BEFORE anything rewrote
the freed device pages; a pending slot can be loaded; a full tier
gathers nothing it cannot take; nothing stays in flight once the engine
is idle, closed or snapshotted; and the gather compiles once a width.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.nlp import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import (HostPagePool, SamplingParams,
                                ServingEngine)
from paddle_tpu.serving.engine import (SPILL_IN_FLIGHT_PAGES,
                                       SPILL_WIDTHS)

_MODELS = {}


def tiny_gpt():
    m = _MODELS.get("gpt")
    if m is None:
        paddle.seed(7)
        m = _MODELS["gpt"] = GPTForCausalLM(GPTConfig(
            vocab_size=97, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=64,
            max_position_embeddings=128, hidden_dropout_prob=0.0,
            attention_probs_dropout_prob=0.0))
        m.eval()
    return m


def engine(**kw):
    kw = {"num_slots": 2, "max_len": 64, "page_size": 8, "chunk_len": 8,
          **kw}
    return ServingEngine(tiny_gpt(), **kw)


def fill_pools(eng, seed=0):
    """Every page of every pool gets bytes of its own."""
    import jax.numpy as jnp
    rng = np.random.RandomState(seed)

    def rand(a):
        if a is None:
            return None
        if np.dtype(a.dtype) == np.int8:
            return jnp.asarray(rng.randint(-127, 128, size=a.shape)
                               .astype(np.int8))
        return jnp.asarray(rng.standard_normal(a.shape)
                           .astype(np.float32)).astype(a.dtype)
    eng._ct = tuple(tuple(rand(a) for a in layer) for layer in eng._ct)


def pool_read(eng, page):
    """The page's payload cut out of the pools on the host, with no
    program of the engine's: [n_layers, 2, page_size, H, D] (and the
    scale block on the int8 pool)."""
    codes = np.stack([np.stack((np.asarray(k)[page], np.asarray(v)[page]))
                      for k, v, _, _ in eng._ct])
    if eng.kv_dtype != "int8":
        return codes
    return codes, np.stack([np.stack((np.asarray(ks)[page],
                                      np.asarray(vs)[page]))
                            for _, _, ks, vs in eng._ct])


def same_payload(a, b):
    if isinstance(a, tuple):
        return len(a) == len(b) and all(same_payload(x, y)
                                        for x, y in zip(a, b))
    a, b = np.asarray(a), np.asarray(b)
    return (a.dtype == b.dtype and a.shape == b.shape
            and a.tobytes() == b.tobytes())


def counters(eng):
    """The host-phase counters, the round's unflushed share included."""
    snap = eng.metrics.snapshot()
    return {k: snap[k] + eng._host_phases.get(k, 0)
            for k in ("kv_spill_pages_total", "kv_spill_batches_total",
                      "kv_spill_s_total", "kv_spill_wait_s_total")}


# -- (a) the bytes -----------------------------------------------------------
@pytest.mark.parametrize("n", [1, 3, 8, 9, SPILL_IN_FLIGHT_PAGES + 2])
@pytest.mark.parametrize("kv_dtype", ["fp", "int8", "fp8"])
def test_batched_spill_leaves_the_synchronous_reads_bytes(kv_dtype, n):
    eng = engine(kv_dtype=kv_dtype, num_pages=SPILL_IN_FLIGHT_PAGES + 8)
    fill_pools(eng)
    pages = list(np.random.RandomState(n).permutation(
        np.arange(1, eng.num_pages))[:n])
    one_by_one = [eng._extract_page(p) for p in pages]
    slots = eng._host_store_pages(pages)
    assert len(slots) == n == len(set(slots))
    # never more in flight than the cap, whatever the spill's size
    assert 0 < eng.host_pool.pending_pages <= SPILL_IN_FLIGHT_PAGES
    eng.host_pool.collect_pending()
    assert eng.host_pool.pending_pages == 0
    for page, slot, want in zip(pages, slots, one_by_one):
        got = eng.host_pool.load(slot)
        assert same_payload(got, want), (page, slot)
        assert same_payload(got, pool_read(eng, page)), (page, slot)
    # the count went out in pieces of the fixed widths, largest first
    pieces, left = 0, n
    for w in SPILL_WIDTHS:
        pieces += left // w
        left %= w
    assert counters(eng)["kv_spill_batches_total"] == n + pieces
    assert counters(eng)["kv_spill_pages_total"] == 2 * n


def test_payloads_are_buffers_of_their_own():
    """Freeing one slot frees its bytes: no payload shares memory with
    the neighbours it was gathered with."""
    eng = engine(num_pages=16)
    fill_pools(eng)
    slots = eng._host_store_pages([3, 4, 5, 6])
    eng.host_pool.collect_pending()
    got = [eng.host_pool.load(slot) for slot in slots]
    assert not any(np.shares_memory(a, b)
                   for i, a in enumerate(got) for b in got[i + 1:])


# -- (b) read before overwrite -----------------------------------------------
def test_copy_holds_what_the_pages_held_before_steps_rewrote_them():
    """Spill, let steps rewrite the freed device pages, THEN collect:
    the gather was read before the steps were dispatched (the pools are
    donated to them and rewritten in place), so the payloads are the old
    contents."""
    eng = engine(num_pages=9)
    eng.add_request(np.arange(1, 41, dtype=np.int64) % 97,
                    SamplingParams(max_new_tokens=4))
    eng.run()
    parked = sorted(eng.prefix_cache._owner)
    assert len(parked) >= 5
    before = {p: pool_read(eng, p) for p in parked}
    collect = eng.host_pool.collect_pending
    eng.host_pool.collect_pending = lambda: None    # stays in flight
    seen = {}
    store = eng._host_store_pages
    eng.prefix_cache._host_store = lambda pages: seen.setdefault(
        "pairs", list(zip(pages, store(pages)))) and \
        [s for _, s in seen["pairs"]]
    eng.add_request(np.arange(50, 90, dtype=np.int64) % 97,
                    SamplingParams(max_new_tokens=4))
    for _ in range(4):
        eng.step()
    assert seen["pairs"] and eng.host_pool.pending_pages > 0
    rewritten = [p for p, _ in seen["pairs"]
                 if not same_payload(pool_read(eng, p), before[p])]
    assert rewritten, "no spilled page was reused by the new request"
    collect()
    for page, slot in seen["pairs"]:
        assert same_payload(eng.host_pool.load(slot), before[page])


# -- (c) a load that finds its copy in flight ---------------------------------
def test_load_of_a_pending_slot_collects_it():
    eng = engine(num_pages=16)
    fill_pools(eng)
    want = pool_read(eng, 7)
    s5, s7, s9 = eng._host_store_pages([5, 7, 9])
    assert eng.host_pool.pending_pages == 3
    assert same_payload(eng.host_pool.load(s7), want)
    # 3 pages were pieces of 2 and 1: the load took in s5's and s7's
    assert eng.host_pool.pending_pages == 1
    eng.host_pool.free(s9)          # freed in flight: never materialised
    eng.host_pool.collect_pending()
    assert eng.host_pool.pending_pages == 0
    assert eng.host_pool.used_pages == 2
    with pytest.raises(ValueError, match="dead host page"):
        eng.host_pool.load(s9)
    assert same_payload(eng.host_pool.load(s5), pool_read(eng, 5))


def test_slot_reused_while_its_first_copy_is_in_flight():
    host = HostPagePool(2)
    started = []
    a, = host.store_pending(1, lambda: started.append("first"),
                            lambda: ["first"])
    host.free(a)
    b, = host.store_pending(1, lambda: started.append("second"),
                            lambda: ["second"])
    assert a == b
    host.start_pending()
    host.start_pending()                    # each copy is set off once
    assert started == ["first", "second"]
    host.collect_pending()
    assert host.load(b) == "second" and host.pending_pages == 0
    with pytest.raises(ValueError, match="free host slots"):
        host.store_pending(2, lambda: None, lambda: [None, None])


def test_restore_in_the_round_of_the_spill_matches_an_unbounded_pool():
    """Two admissions in one round: the first pushes the other's cached
    prefix out to the host tier, the second restores it while that copy
    is still in flight. Tokens are those of a pool nothing spills from."""
    shared = np.arange(1, 33, dtype=np.int64) % 97         # 4 pages
    other = np.arange(40, 72, dtype=np.int64) % 97
    prompts = [shared, other,
               np.concatenate([other, [3, 4, 5]]),
               np.concatenate([shared, [9, 8, 7]])]

    def serve(num_pages, spy=None):
        eng = engine(num_pages=num_pages, max_len=64)
        if spy is not None:
            load = eng.host_pool.load
            eng.host_pool.load = lambda slot: spy.append(
                type(eng.host_pool._data[slot]).__name__) or load(slot)
        out = []
        for p in prompts[:2]:
            out.append(eng.add_request(p, SamplingParams(
                max_new_tokens=6)))
            eng.run()
        out += [eng.add_request(p, SamplingParams(max_new_tokens=6))
                for p in prompts[2:]]
        eng.run()
        assert eng.host_pool.pending_pages == 0
        return eng, [list(r.output_tokens) for r in out]

    spy = []
    small, got = serve(12, spy)
    big, want = serve(64)
    assert got == want
    assert big.prefix_cache.spilled_pages_total == 0
    assert small.prefix_cache.restored_pages_total > 0
    assert "_PendingCopy" in spy, spy       # a load met a copy in flight


# -- (d) a full host tier ----------------------------------------------------
def test_full_host_tier_gathers_nothing_it_cannot_take():
    eng = engine(num_pages=12, host_pages=2)
    eng.add_request(np.arange(1, 49, dtype=np.int64) % 97,
                    SamplingParams(max_new_tokens=2))
    eng.run()
    cache = eng.prefix_cache
    assert eng.pool.cached_pages >= 5
    base = counters(eng)
    assert cache.spill(5) == 2                   # the tier's room
    now = counters(eng)
    assert now["kv_spill_pages_total"] - base["kv_spill_pages_total"] == 2
    assert eng.host_pool.used_pages == 2 and cache.spilled_nodes == 2
    assert cache.spill(3) == 0                   # full: nothing gathered
    assert counters(eng)["kv_spill_batches_total"] == \
        now["kv_spill_batches_total"]
    evicted = cache.evicted_pages_total
    assert cache.evict(3) == 3                   # evict takes the rest
    assert cache.evicted_pages_total == evicted + 3
    eng.host_pool.collect_pending()
    eng.pool.assert_quiesced()


# -- (f) nothing stays in flight ---------------------------------------------
def test_no_copy_survives_an_idle_engine_a_snapshot_or_a_close():
    eng = engine(num_pages=9)
    for lo in (1, 30, 60):
        eng.add_request(np.arange(lo, lo + 40, dtype=np.int64) % 97,
                        SamplingParams(max_new_tokens=3))
        eng.run()
        assert eng.host_pool.pending_pages == 0          # idle
    assert eng.prefix_cache.spilled_pages_total > 0
    # a spill outside any round (the fabric's graft does this)
    assert eng.prefix_cache.spill(2) == 2
    assert eng.host_pool.pending_pages == 2
    snap = eng.export_prefix_state()                     # snapshot
    assert eng.host_pool.pending_pages == 0
    assert all(isinstance(n["payload"], np.ndarray) for n in snap["nodes"])
    fresh = engine(num_pages=9)
    assert fresh.import_prefix_state(snap) > 0
    assert fresh.host_pool.pending_pages == 0
    assert eng.prefix_cache.spill(1) == 1
    eng.abort_all()                                      # close
    assert eng.host_pool.pending_pages == 0
    assert fresh.prefix_cache.spill(1) == 1
    fresh.drain()
    assert fresh.host_pool.pending_pages == 0


# -- (g) one program a width, whatever was spilled ---------------------------
def test_gather_compiles_once_a_width():
    eng = engine(num_pages=SPILL_IN_FLIGHT_PAGES + 40)
    fill_pools(eng)
    assert eng._swap_out_fn is None
    rng = np.random.RandomState(5)
    for n in (1, 2, 7, 33, 64, 100, 129, 5, 31):
        pages = rng.permutation(np.arange(1, eng.num_pages))[:n]
        slots = eng._host_store_pages(pages)
        assert len(slots) == n
        assert eng._swap_out_fn._cache_size() == len(SPILL_WIDTHS)
        for slot in slots:
            eng.host_pool.free(slot)
        eng.host_pool.collect_pending()
    eng._extract_page(3)
    assert eng._swap_out_fn._cache_size() == len(SPILL_WIDTHS)


# -- same results, not fewer -------------------------------------------------
def test_tree_and_host_tier_end_as_the_page_at_a_time_spill_left_them():
    """A fixed list of requests under page pressure (spills, restores, a
    host tier that fills, evictions, COW): the numbers are what the tree
    before this change (commit ba12ee1) gives for the same list."""
    eng = ServingEngine(tiny_gpt(), num_slots=3, max_len=96, page_size=8,
                        num_pages=25, host_pages=14, chunk_len=16)
    rng = np.random.RandomState(3)
    base = [rng.randint(0, 97, size=40).astype(np.int64)
            for _ in range(3)]
    reqs = []
    for i in range(14):
        b = base[i % 3]
        tail = rng.randint(0, 97, size=rng.randint(3, 30)).astype(np.int64)
        p = np.concatenate([b[:rng.randint(8, 41)], tail]) if i % 4 \
            else tail
        reqs.append(eng.add_request(p, SamplingParams(
            max_new_tokens=int(rng.randint(2, 9))), request_id=f"r{i}"))
        if i % 3 == 2:
            eng.run()
    eng.run()
    stats = eng.prefix_cache.stats()
    stats.pop("hit_rate")
    assert stats == {
        "lookups": 14, "hits": 7, "cached_tokens": 137,
        "evicted_pages": 10, "cow_copies": 4, "inserted_pages": 63,
        "spilled_pages": 34, "restored_pages": 5, "spilled_nodes": 7,
        "pinned_pages": 0, "tree_pages": 24, "resident_pages": 24}
    assert eng.host_pool.used_pages == 7
    assert eng.host_pool.pending_pages == 0
    assert [list(map(int, r.output_tokens)) for r in reqs] == [
        [26, 26, 26, 26], [71, 71, 71, 71, 71, 71], [60, 60],
        [56, 56, 23, 23, 23, 23, 23, 23], [46, 46, 46, 46],
        [86, 86, 86, 86, 86, 86, 86, 86], [50, 50], [23, 23, 23, 23],
        [60, 60, 60, 60, 60, 60, 60], [71, 71, 71, 71, 71],
        [29, 29, 8, 8], [68, 68, 68, 68, 68, 68, 68, 68], [21, 74],
        [36, 36, 36, 36, 36, 36, 36]]
    # batched: fewer gathers than pages
    c = counters(eng)
    assert 0 < c["kv_spill_batches_total"] < c["kv_spill_pages_total"]
