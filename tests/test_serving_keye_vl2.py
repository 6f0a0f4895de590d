"""Keye-VL-2.0's language model through `ServingEngine`: chunked prefill
and then decode through the engine's cache (keys, values AND indexer
rows in three paged pools a layer under the slot's one page table, the
selection on the pools) against the plain reference's full forward pass
(the published form), logits compared; the cache's shape; the counters;
what is switched off. Model and reference as in tests/test_keye_vl2.py."""
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.ops.pallas import moe, paged_attention as pa, sparse as sp
from paddle_tpu.serving import SamplingParams, ServingEngine
from paddle_tpu.serving.metrics import (SPARSE_COUNTERS, STEP_WORK_COUNTERS,
                                        prometheus_render)

import ref_keye_vl2 as ref
from test_keye_vl2 import TINY, tiny_keye
from test_serving_laguna import serve_and_collect


@pytest.fixture(autouse=True)
def _small_blocks(monkeypatch):
    monkeypatch.setattr(ref, "QUERY_BLOCK", 8)
    monkeypatch.setattr(ref, "EXPERT_BLOCK", 3)


def engine(model, **kw):
    kw = dict(dict(num_slots=2, max_len=64, page_size=4, chunk_len=16), **kw)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        return ServingEngine(model, **kw)


def check_against_reference(model, prompts, results, atol):
    w = ref.keye_weights(model)
    for prompt, (tokens, held) in zip(prompts, results):
        seq = list(prompt) + tokens
        want = np.asarray(ref.keye_logits(w, TINY, np.asarray(seq))[0])
        # the last token's successor is never computed
        assert sorted(held) == list(range(len(prompt) - 1, len(seq) - 1))
        for p, got in held.items():
            np.testing.assert_allclose(got, want[p], atol=atol,
                                       err_msg=f"position {p}")
            assert int(got.argmax()) == seq[p + 1]


@pytest.mark.parametrize("impl", ["kernel", "fallback"])
def test_chunked_prefill_then_decode_matches_reference(impl, monkeypatch):
    """Prompts of 40 and 23 tokens in chunks of 16 (topk 12: the first
    chunk's early queries see fewer keys than that, every later query
    more; a chunk's selection spans its own new keys), two rows in one
    step, 6 tokens decoded, over pages of 4 and key blocks of 32 and 16:
    the three kernels and the expert kernel in interpret mode, and the
    dense jnp fallback."""
    asked = []
    if impl == "kernel":
        for mod in (sp, pa, moe):
            monkeypatch.setattr(mod, "_INTERPRET", True)
        monkeypatch.setattr(sp, "INDEX_K_BLOCK", 32)
        monkeypatch.setattr(pa, "K_BLOCK", 16)
        walk = sp.sparse_walk

        def recording(q, *args, **kw):
            asked.append(tuple(q.shape))
            return walk(q, *args, **kw)
        monkeypatch.setattr(sp, "sparse_walk", recording)
    model = tiny_keye()
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 97, size=n).tolist() for n in (40, 23)]
    eng = engine(model)
    check_against_reference(model, prompts,
                            serve_and_collect(eng, prompts, 6), atol=4e-4)
    if impl == "kernel":
        # ONE walk a layer for chunk rows and decoding rows alike, traced
        # once a step program: the three kernels are one jit of their own
        assert asked == [(2, 16, 4, 16)]


def test_cache_path_equals_the_published_eager_form():
    model = tiny_keye()
    prompt = np.random.default_rng(6).integers(0, 97, size=30).tolist()
    tokens, held = serve_and_collect(engine(model), [prompt], 10)[0]
    seq = np.asarray(prompt + tokens)
    with paddle.no_grad():
        want = np.asarray(model(paddle.to_tensor(seq[None]))._value)[0]
    for p, got in held.items():
        np.testing.assert_allclose(got, want[p], atol=1e-4)


def test_three_pools_a_layer_under_one_page_table():
    eng = engine(tiny_keye())
    assert eng.kv_sparse and not eng.kv_latent and not eng.kv_windows
    assert (eng.n_kv, eng.head_dim, eng.index_row, eng.sparse_topk) \
        == (2, 16, 128, 12)
    for k, v, ks, vs, rows in eng._ct:
        assert k.shape == v.shape == (2 * 16 + 1, 4, 2, 16)
        assert rows.shape == (2 * 16 + 1, 4, 128)
        assert ks is None and vs is None
    # 3 layers x 4 positions x (2 x 2 x 16 + 128) float32 values
    assert eng.page_bytes == 3 * 4 * (2 * 2 * 16 + 128) * 4


def test_sparse_counters_and_moe_counters():
    eng = engine(tiny_keye())
    eng.add_request(np.arange(1, 20), SamplingParams(max_new_tokens=4))
    eng.step()                              # 16 prompt tokens
    eng.step()                              # 3 prompt tokens
    before = eng.metrics.snapshot()
    eng.step()                              # one decode row at position 19
    after = eng.metrics.snapshot()
    # the host counts a step at its plan, the device's counts ride its
    # fetch, one round after its launch
    eng.step()
    fetched = eng.metrics.snapshot()
    delta = {k: (fetched if k.startswith("moe_") else after)[k] - (
        after if k.startswith("moe_") else before)[k]
        for k in STEP_WORK_COUNTERS}
    assert delta["sparse_rows_total"] == 3
    assert delta["sparse_pairs_visible_total"] == 3 * 20
    assert delta["sparse_pairs_selected_total"] == 3 * 12
    assert delta["sparse_keys_floor_total"] == 3 * 12
    assert delta["sparse_keys_context_total"] == 3 * 20
    # the two chunks: queries 0..18 see 1..19 keys, keep 12 at most
    assert before["sparse_rows_total"] == 3 * 19
    assert before["sparse_pairs_visible_total"] == 3 * (19 * 20 // 2)
    assert before["sparse_pairs_selected_total"] \
        == 3 * (12 * 13 // 2 + 7 * 12)
    assert before["sparse_keys_context_total"] == 3 * (16 + 19)
    assert before["sparse_keys_floor_total"] == 3 * (16 + 19)
    # the masked walk's grid is counted as every full-attention walk's
    assert delta["walk_grid_steps_total"] == 1
    assert delta["mla_rows_total"] == 0
    assert delta["moe_layer_steps_total"] == 3
    assert delta["moe_assignments_total"] == 3 * 3      # 1 token, top 3
    assert delta["moe_assignments_here_total"] <= 9
    while eng.has_work:
        eng.step()
    text = prometheus_render({"0": eng.metrics.snapshot()})
    for name in STEP_WORK_COUNTERS:
        assert f"paddle_serving_{name}{{" in text
    assert set(SPARSE_COUNTERS) <= set(STEP_WORK_COUNTERS)


def test_indexer_rows_switch_reuse_off_and_say_so():
    model = tiny_keye()
    with warnings.catch_warnings(record=True) as said:
        warnings.simplefilter("always")
        eng = ServingEngine(model, num_slots=2, max_len=64, page_size=4,
                            chunk_len=16)
    ours = [w for w in said if "switched off" in str(w.message)]
    assert len(ours) == 1 and "indexer rows" in str(ours[0].message)
    assert "the prefix cache, the host page tier, preemption and the " \
        "grouped walk are switched off for this model" in str(ours[0].message)
    assert eng.prefix_cache is None and not eng.preempt
    assert eng.host_pages == 0 and not eng.grouped
    with pytest.raises(ValueError, match="sparse cache_spec"):
        ServingEngine(model, cache_spec=(3, 2, 16, (None,) * 3, "sparse"))
    with pytest.raises(ValueError, match="sparse cache_spec"):
        ServingEngine(model, cache_spec=(3, 2, 16, (None, 8, None), "sparse",
                                         (128, 12)))


@pytest.mark.parametrize("name,value", [
    ("prefix_cache", True), ("preempt", True), ("host_pages", 4),
    ("kv_dtype", "int8"), ("kv_dtype", "fp8"), ("megakernel", True),
    ("mesh", "dp1mp2"), ("adapters", True), ("spec", "ngram")])
def test_indexer_rows_refuse(name, value):
    """Each feature the engine cannot give a model of the sparse kind
    is refused by name when asked for, in the words the window and
    latent kinds are refused in."""
    with pytest.raises(ValueError, match=rf"indexer rows.*'{name}'.*"
                       r"cannot be had with them yet"):
        ServingEngine(tiny_keye(), num_slots=2, max_len=64, page_size=4,
                      chunk_len=16, **{name: value})


def test_slot_refill_recomputes_and_agrees():
    """With the prefix cache off a repeated prompt is prefilled again,
    in a slot and over pages another request has used: same tokens."""
    model = tiny_keye()
    eng = engine(model, num_slots=1)
    prompt = np.random.default_rng(8).integers(0, 97, size=21).tolist()
    first = serve_and_collect(eng, [prompt], 5)[0][0]
    other = serve_and_collect(eng, [prompt[::-1] + prompt], 5)
    again = serve_and_collect(eng, [prompt], 5)[0][0]
    assert first == again and len(other[0][0]) == 5
    assert eng.prefix_cache is None


def test_cancellation_frees_the_slot_and_its_pages():
    model = tiny_keye()
    eng = engine(model, num_slots=1)
    rng = np.random.default_rng(9)
    gone = eng.add_request(rng.integers(0, 97, size=40),
                           SamplingParams(max_new_tokens=8))
    eng.step()                              # one chunk of 16 in
    free_before = eng.pool.free_pages
    assert eng.cancel(gone.request_id)
    while eng.has_work:
        eng.step()
    assert eng.pool.free_pages > free_before
    assert not eng.scheduler.running
    prompts = [rng.integers(0, 97, size=19).tolist()]
    check_against_reference(model, prompts,
                            serve_and_collect(eng, prompts, 4), atol=4e-4)


def test_sparse_cache_refuses_what_it_is_not_served_by():
    from paddle_tpu.nlp.generation import (DecodeCache,
                                           update_and_attend_sparse)
    z = paddle.to_tensor(np.zeros((1, 1, 2, 4), np.float32))
    pool = paddle.to_tensor(np.zeros((3, 2, 2, 4), np.float32))
    dense = DecodeCache(pool, pool,
                        paddle.to_tensor(np.zeros((1,), np.int32)))
    with pytest.raises(NotImplementedError, match="unified ragged step"):
        update_and_attend_sparse(z, z, z, z, z[..., 0], z[:, :, 0], dense,
                                 topk=2)
