"""Laguna through `ServingEngine`: chunked prefill and then decode
through the engine's cache (full layers on the shared paged pool,
window layers on their per-slot ring) against the plain reference's
full forward pass, logits compared; the ring's bound; the counters.
Model and reference as in tests/test_laguna.py."""
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.ops.pallas import moe
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.serving import SamplingParams, ServingEngine
from paddle_tpu.serving.metrics import (STEP_WORK_COUNTERS,
                                        prometheus_render)

import ref_laguna as ref
from test_laguna import TINY, tiny_laguna


@pytest.fixture(autouse=True)
def _small_blocks(monkeypatch):
    monkeypatch.setattr(ref, "QUERY_BLOCK", 8)
    monkeypatch.setattr(ref, "EXPERT_BLOCK", 3)


def engine(model, **kw):
    kw = dict(dict(num_slots=2, max_len=64, page_size=4, chunk_len=16), **kw)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        return ServingEngine(model, **kw)


def serve_and_collect(eng, prompts, n_new):
    """Runs the requests to their end; returns for each (tokens, {p:
    the logits the engine held for position p's successor}). A round
    commits the step before the one it launched, so a request is still
    running when the step that fed its last token has run: the logits
    past its last token are left out."""
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
    w = ref.laguna_weights(model)
    for prompt, (tokens, held) in zip(prompts, results):
        seq = list(prompt) + tokens
        want, _ = ref.laguna_logits(w, TINY, np.asarray(seq))
        want = np.asarray(want)
        # the last token's successor is never computed
        assert sorted(held) == list(range(len(prompt) - 1, len(seq) - 1))
        for p, got in held.items():
            np.testing.assert_allclose(got, want[p], atol=atol,
                                       err_msg=f"position {p}")
            assert int(got.argmax()) == seq[p + 1]


@pytest.mark.parametrize("impl", ["kernel", "gather"])
def test_chunked_prefill_then_decode_matches_reference(impl, monkeypatch):
    """Prompts of 40 and 23 tokens in chunks of 16, two rows in one
    step, 6 tokens decoded: the page walk (in interpret mode, with the
    expert kernel) and the gather fallback."""
    asked = []
    if impl == "kernel":
        monkeypatch.setattr(pa, "_INTERPRET", True)
        monkeypatch.setattr(moe, "_INTERPRET", True)
        walk = pa._ragged_attention_kernel

        def recording(*args, **kw):
            asked.append(kw.get("window"))
            return walk(*args, **kw)
        monkeypatch.setattr(pa, "_ragged_attention_kernel", recording)
    model = tiny_laguna()
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 97, size=n).tolist() for n in (40, 23)]
    eng = engine(model, attn_impl=impl)
    assert eng.attn_impl == impl
    check_against_reference(model, prompts,
                            serve_and_collect(eng, prompts, 6), atol=3e-4)
    if impl == "kernel":
        # every layer asked the walk for its window
        assert asked[:5] == [None, 8, 8, 8, None]


def test_window_ring_stays_at_its_bound_while_the_context_grows():
    """A context of 4 x the window and more: the window layers' pools
    hold ring_pages a slot whatever max_len is, and the logits agree
    with the reference all the way."""
    model = tiny_laguna()
    eng = engine(model, max_len=128)
    # (window + chunk) / page + 1 pages a slot, plus the trash page
    assert eng.ring_pages == (8 + 16) // 4 + 1 == 7
    big = engine(model, max_len=1024)
    assert big.ring_pages == 7 and big.max_pages == 256
    for i, (k, v, _, _) in enumerate(eng._ct):
        want = 2 * 7 + 1 if i in (1, 2, 3) else 2 * 32 + 1
        assert k.shape == v.shape == (want, 4, 2, 16)
    assert sorted(eng.kv_windows) == [1, 2, 3]
    ring = np.asarray(eng._pt_ring)
    assert ring.shape == (2, 32) and ring.min() == 1 and ring.max() == 14
    assert (ring[0, :7] == ring[0, 7:14]).all()
    assert set(ring[0]) & set(ring[1]) == set()
    prompt = np.random.default_rng(6).integers(0, 97, size=30).tolist()
    results = serve_and_collect(eng, [prompt], 40)
    assert len(results[0][0]) == 40         # 70 positions, window 8
    check_against_reference(model, [prompt], results, atol=3e-4)
    snap = eng.metrics.snapshot()
    assert snap["kv_window_pages_skipped_total"] > 0
    assert snap["kv_window_pages_walked_total"] > 0
    # at 60+ positions a window layer walks 3 pages of 16
    walked_last = pa.count_window_page_reads([68], [1], page_size=4,
                                             window=8)
    assert walked_last == (3, 18)


def test_decode_only_step_hits_fewer_experts_than_it_holds():
    model = tiny_laguna()
    eng = engine(model)
    eng.add_request(np.arange(1, 20), SamplingParams(max_new_tokens=4))
    # what the device counts rides a step's fetch, one round after its
    # launch
    eng.step()                              # 16 prompt tokens
    eng.step()                              # 3 prompt tokens
    eng.step()                              # one decode row
    before = eng.metrics.snapshot()
    eng.step()                              # the decode row fetched
    after = eng.metrics.snapshot()
    delta = {k: after[k] - before[k] for k in STEP_WORK_COUNTERS}
    assert delta["moe_layer_steps_total"] == 4
    assert delta["moe_assignments_total"] == 4 * 4      # 1 token, top 4
    assert 0 < delta["moe_assignments_here_total"] <= 16
    # 8 experts held a layer; one token can hit 4 of them at most
    assert 0 < delta["moe_experts_hit_total"] <= 4 * 4 < 4 * 8
    assert delta["moe_experts_hit_total"] <= \
        delta["moe_assignments_here_total"]
    # the prefill steps counted every prompt token once a layer
    assert before["moe_assignments_total"] == 19 * 4 * 4
    assert before["moe_layer_steps_total"] == 8
    while eng.has_work:
        eng.step()
    text = prometheus_render({"0": eng.metrics.snapshot()})
    for name in STEP_WORK_COUNTERS:
        assert f"paddle_serving_{name}{{" in text


def test_window_layers_switch_reuse_off_and_say_so():
    model = tiny_laguna()
    with pytest.warns(UserWarning, match="switched off"):
        eng = ServingEngine(model, num_slots=2, max_len=64, page_size=4,
                            chunk_len=16)
    assert eng.prefix_cache is None and not eng.preempt
    assert eng.host_pages == 0 and not eng.grouped
    with pytest.raises(ValueError, match="windows"):
        ServingEngine(model, cache_spec=(5, 2, 16, (None, 8)))


@pytest.mark.parametrize("name,value", [
    ("prefix_cache", True), ("preempt", True), ("host_pages", 4),
    ("kv_dtype", "int8"), ("megakernel", True), ("mesh", "dp1mp2"),
    ("adapters", True), ("spec", "ngram")])
def test_window_layers_refuse(name, value):
    """Each feature the engine cannot give a model with window layers
    is refused by name when asked for."""
    with pytest.raises(ValueError,
                       match=rf"sliding-window.*'{name}'"):
        ServingEngine(tiny_laguna(), num_slots=2, max_len=64,
                      page_size=4, chunk_len=16, **{name: value})


def test_same_prompt_twice_recomputes_and_agrees():
    """With the prefix cache off a repeated prompt is prefilled again,
    through a ring another request has used: same tokens."""
    model = tiny_laguna()
    eng = engine(model, num_slots=1)
    prompt = np.random.default_rng(8).integers(0, 97, size=21).tolist()
    first = serve_and_collect(eng, [prompt], 5)[0][0]
    other = serve_and_collect(eng, [prompt[::-1] + prompt], 5)
    again = serve_and_collect(eng, [prompt], 5)[0][0]
    assert first == again and len(other[0][0]) == 5
    assert eng.prefix_cache is None
