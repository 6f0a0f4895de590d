"""MiMo-V2-Flash through `ServingEngine`: chunked prefill and then decode
through the engine's pools of split widths (full layers on the shared
paged pool, window layers on their per-slot rings, each layer at its own
kv heads, key and value widths) against the plain reference's full
forward pass, logits compared; the pools' geometry and byte gauges; the
walk and router counters; the refusals. Model and reference as in
tests/test_mimo_v2.py."""
import warnings

import numpy as np
import pytest

from paddle_tpu.ops.pallas import moe
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.serving import SamplingParams, ServingEngine
from paddle_tpu.serving.metrics import (SPLIT_COUNTERS, STEP_WORK_COUNTERS,
                                        prometheus_render)

import ref_mimo_v2 as ref
from test_mimo_v2 import TINY, tiny_mimo


@pytest.fixture(autouse=True)
def _small_blocks(monkeypatch):
    monkeypatch.setattr(ref, "QUERY_BLOCK", 8)
    monkeypatch.setattr(ref, "EXPERT_BLOCK", 3)
    monkeypatch.setattr(ref, "TOKEN_BLOCK", 16)


def engine(model, **kw):
    kw = dict(dict(num_slots=2, max_len=64, page_size=4, chunk_len=16), **kw)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        return ServingEngine(model, **kw)


def serve_and_collect(eng, prompts, n_new):
    """Runs the requests to their end; returns for each (tokens, {p:
    the logits the engine held for position p's successor})."""
    reqs = [eng.add_request(np.asarray(p), SamplingParams(
        max_new_tokens=n_new)) for p in prompts]
    held = [{} for _ in reqs]
    while eng.has_work:
        eng.step()
        pos = np.asarray(eng._pos)
        logits = np.asarray(eng._last_logits)
        for slot, req in eng.scheduler.running.items():
            i = reqs.index(req)
            if len(prompts[i]) <= pos[slot] < len(prompts[i]) + n_new:
                held[i][int(pos[slot]) - 1] = logits[slot].copy()
    return [(list(r.output_tokens), h) for r, h in zip(reqs, held)]


def check_against_reference(model, prompts, results, atol):
    w = ref.mimo_weights(model)
    for prompt, (tokens, held) in zip(prompts, results):
        seq = list(prompt) + tokens
        want, _ = ref.mimo_logits(w, TINY, np.asarray(seq))
        want = np.asarray(want)
        assert sorted(held) == list(range(len(prompt) - 1, len(seq) - 1))
        for p, got in held.items():
            np.testing.assert_allclose(got, want[p], atol=atol,
                                       err_msg=f"position {p}")
            assert int(got.argmax()) == seq[p + 1]


@pytest.mark.parametrize("impl", ["kernel", "jnp"])
def test_chunked_prefill_then_decode_matches_reference(impl, monkeypatch):
    """Prompts of 40 and 23 tokens in chunks of 16, two rows in one
    step, 12 tokens decoded past a window of 8 that wraps the ring: the
    page walk (in interpret mode, with the expert kernel) and the jnp
    forms; both kinds of layer in one model."""
    asked = []
    if impl == "kernel":
        monkeypatch.setattr(pa, "_INTERPRET", True)
        monkeypatch.setattr(moe, "_INTERPRET", True)
        walk = pa._ragged_attention_kernel

        def recording(*args, **kw):
            asked.append((kw.get("window"), kw.get("split_heads"),
                          kw.get("sink") is not None))
            return walk(*args, **kw)
        monkeypatch.setattr(pa, "_ragged_attention_kernel", recording)
    model = tiny_mimo()
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 97, size=n).tolist() for n in (40, 23)]
    eng = engine(model)
    check_against_reference(model, prompts,
                            serve_and_collect(eng, prompts, 12), atol=3e-4)
    if impl == "kernel":
        # every layer asked the walk for its window, kv heads and sink
        assert asked[:5] == [(None, 2, False), (8, 4, True), (8, 4, True),
                             (8, 4, True), (None, 2, False)]


def test_pools_at_each_layers_geometry_and_page_bytes():
    model = tiny_mimo()
    eng = engine(model, max_len=128)
    assert eng.kv_split and sorted(eng.kv_windows) == [1, 2, 3]
    assert eng.ring_pages == (8 + 16) // 4 + 1 == 7
    for i, (k, v, ks, vs) in enumerate(eng._ct):
        if i in (1, 2, 3):
            assert k.shape == (2 * 7 + 1, 4, 4 * 48)
            assert v.shape == (2 * 7 + 1, 4, 4 * 32)
        else:
            assert k.shape == (2 * 32 + 1, 4, 2 * 48)
            assert v.shape == (2 * 32 + 1, 4, 2 * 32)
        assert ks is None and vs is None
    # a page of the shared pool: the two full layers at their geometry
    assert eng.page_bytes == 2 * 4 * 2 * (48 + 32) * 4
    assert eng.metrics.pool_bytes_per_page == eng.page_bytes
    # the full layers' walk: 8 query heads over 2 kv heads
    assert eng._walk_rep == 4


def test_walk_and_router_counters():
    model = tiny_mimo()
    eng = engine(model)
    eng.add_request(np.arange(1, 20), SamplingParams(max_new_tokens=4))
    eng.step()                              # 16 prompt tokens
    eng.step()                              # 3 prompt tokens
    before = eng.metrics.snapshot()
    eng.step()                              # one decode row
    eng.step()
    after = eng.metrics.snapshot()
    delta = {k: after[k] - before[k] for k in STEP_WORK_COUNTERS}
    # the chunks: 16 then 3 queries, two full layers and three window
    # layers of 8
    full16 = pa.count_walk_pairs([0], [16])
    full3 = pa.count_walk_pairs([16], [3])
    win16 = pa.count_walk_pairs([0], [16], 8)
    win3 = pa.count_walk_pairs([16], [3], 8)
    assert before["split_walk_pairs_total"] == 2 * (full16[0] + full3[0])
    assert before["split_walk_keys_total"] == 2 * (16 + 19)
    assert before["split_walk_rows_total"] == 2 * 2
    assert before["sink_walk_pairs_total"] == 3 * (win16[0] + win3[0])
    assert before["sink_walk_keys_total"] == 3 * (win16[1] + win3[1])
    # two decode rows, at positions 19 and 20
    assert delta["split_walk_pairs_total"] == 2 * (20 + 21)
    assert delta["sink_walk_pairs_total"] == 3 * 2 * 8
    assert delta["sink_walk_rows_total"] == 3 * 2
    while eng.has_work:
        eng.step()
    snap = eng.metrics.snapshot()
    # the router (its counts ride a step's fetch): four expert layers a
    # step, top 4 of every token fed (two chunks, then one a step); the
    # bias moved some of them
    steps = snap["unified_steps"]
    assert snap["moe_layer_steps_total"] == 4 * steps
    assert snap["moe_assignments_total"] == 4 * 4 * (19 + steps - 2)
    assert 0 < snap["moe_bias_reranked_total"] \
        < snap["moe_assignments_total"]
    text = prometheus_render({"0": snap})
    for name in SPLIT_COUNTERS + ("moe_bias_reranked_total",):
        assert name in STEP_WORK_COUNTERS
        assert f"paddle_serving_{name}{{" in text


def test_split_kind_switches_reuse_off_and_says_so():
    model = tiny_mimo()
    with pytest.warns(UserWarning, match="switched off"):
        eng = ServingEngine(model, num_slots=2, max_len=64, page_size=4,
                            chunk_len=16)
    assert eng.prefix_cache is None and not eng.preempt
    assert eng.host_pages == 0 and not eng.grouped


@pytest.mark.parametrize("name,value", [
    ("prefix_cache", True), ("preempt", True), ("host_pages", 8),
    ("kv_dtype", "int8"), ("megakernel", True), ("spec", "ngram")])
def test_split_kind_refuses(name, value):
    with pytest.raises(ValueError, match="cannot be had"):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            ServingEngine(tiny_mimo(), num_slots=2, max_len=64, page_size=4,
                          chunk_len=16, **{name: value})


def test_malformed_split_spec_is_refused():
    spec = tiny_mimo()._decode_cache_spec()
    with pytest.raises(ValueError, match="split cache_spec"):
        ServingEngine(tiny_mimo(), spec[:5] + (spec[5][:4],), num_slots=2,
                      max_len=64, page_size=4, chunk_len=16)


def test_slot_refill_recomputes_and_agrees():
    """A second request in a slot a first one left: the ring and the
    pool pages it reuses are written from position 0 before they are
    read, and its logits agree with the reference."""
    model = tiny_mimo()
    eng = engine(model, num_slots=1)
    rng = np.random.default_rng(9)
    for n in (30, 17):
        prompt = rng.integers(0, 97, size=n).tolist()
        check_against_reference(model, [prompt],
                                serve_and_collect(eng, [prompt], 5),
                                atol=3e-4)
