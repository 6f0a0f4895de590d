"""DeepSeek-V2 through `ServingEngine`: chunked prefill and then decode
through the engine's cache (latent rows in ONE paged pool a layer under
the slot's page table, the absorbed form) against the plain reference's
full forward pass (expanded), logits compared; the cache's shape; the
counters; what is switched off. Model and reference as in
tests/test_deepseek_v2.py."""
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.ops.pallas import mla, moe
from paddle_tpu.serving import SamplingParams, ServingEngine
from paddle_tpu.serving.metrics import (LATENT_COUNTERS, STEP_WORK_COUNTERS,
                                        prometheus_render)

import ref_deepseek_v2 as ref
from test_deepseek_v2 import TINY, tiny_dsv2
from test_serving_laguna import serve_and_collect


@pytest.fixture(autouse=True)
def _small_blocks(monkeypatch):
    monkeypatch.setattr(ref, "QUERY_BLOCK", 8)
    monkeypatch.setattr(ref, "EXPERT_BLOCK", 3)
    monkeypatch.setattr(ref, "HEAD_BLOCK", 3)


def engine(model, **kw):
    kw = dict(dict(num_slots=2, max_len=64, page_size=4, chunk_len=16), **kw)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        return ServingEngine(model, **kw)


def check_against_reference(model, prompts, results, atol):
    w = ref.dsv2_weights(model)
    for prompt, (tokens, held) in zip(prompts, results):
        seq = list(prompt) + tokens
        want = np.asarray(ref.dsv2_logits(w, TINY, np.asarray(seq))[0])
        # the last token's successor is never computed
        assert sorted(held) == list(range(len(prompt) - 1, len(seq) - 1))
        for p, got in held.items():
            np.testing.assert_allclose(got, want[p], atol=atol,
                                       err_msg=f"position {p}")
            assert int(got.argmax()) == seq[p + 1]


@pytest.mark.parametrize("impl", ["kernel", "fallback"])
def test_chunked_prefill_then_decode_matches_reference(impl, monkeypatch):
    """Prompts of 40 and 23 tokens in chunks of 16, two rows in one
    step, 6 tokens decoded: the walk and the expert kernel in interpret
    mode, and the dense jnp fallback."""
    asked = []
    if impl == "kernel":
        monkeypatch.setattr(mla, "_INTERPRET", True)
        monkeypatch.setattr(moe, "_INTERPRET", True)
        walk = mla.mla_walk

        def recording(q, *args, **kw):
            asked.append(tuple(q.shape))
            return walk(q, *args, **kw)
        monkeypatch.setattr(mla, "mla_walk", recording)
    model = tiny_dsv2()
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 97, size=n).tolist() for n in (40, 23)]
    eng = engine(model)
    check_against_reference(model, prompts,
                            serve_and_collect(eng, prompts, 6), atol=4e-4)
    if impl == "kernel":
        # a layer walks twice: the chunk rows at a block of 8, the
        # decoding rows at a block of one; 8 heads over rows of 32 + 8,
        # whole tiles of 128 lanes in the cache
        assert asked[:2] == [(2, 16, 8, 128), (2, 1, 8, 128)]
        assert len(asked) == 2 * 5


def test_absorbed_cache_path_equals_the_expanded_eager_form():
    model = tiny_dsv2()
    prompt = np.random.default_rng(6).integers(0, 97, size=30).tolist()
    tokens, held = serve_and_collect(engine(model), [prompt], 10)[0]
    seq = np.asarray(prompt + tokens)
    with paddle.no_grad():
        want = np.asarray(model(paddle.to_tensor(seq[None]))._value)[0]
    for p, got in held.items():
        np.testing.assert_allclose(got, want[p], atol=1e-4)


def test_latent_pool_is_one_lane_without_a_head_axis():
    model = tiny_dsv2()
    eng = engine(model)
    # a latent row of 32 + 8 values fills 128 lanes in the cache
    assert eng.kv_latent and not eng.kv_windows
    assert eng.n_kv == 1 and eng.head_dim == 128
    for rows, v, ks, vs in eng._ct:
        assert rows.shape == (2 * 16 + 1, 4, 128)
        assert v is None and ks is None and vs is None
    # 5 layers x 4 positions x 128 float32 values, one pool a layer
    assert eng.page_bytes == 5 * 4 * 128 * 4


def test_latent_counters_and_moe_counters():
    model = tiny_dsv2()
    eng = engine(model)
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
    delta = {k: after[k] - before[k] for k in LATENT_COUNTERS}
    delta.update({k: fetched[k] - after[k] for k in STEP_WORK_COUNTERS
                  if k.startswith("moe_")})
    assert delta["mla_rows_total"] == 5
    assert delta["mla_pairs_total"] == 5 * 20
    assert delta["mla_keys_distinct_total"] == 5 * 20
    # the two chunks: 16 and 19 keys seen at their ends
    assert before["mla_keys_distinct_total"] == 5 * (16 + 19)
    assert before["mla_rows_total"] == 5 * 19
    assert before["mla_pairs_total"] == 5 * (19 * 20 // 2)
    assert delta["moe_layer_steps_total"] == 4
    assert delta["moe_assignments_total"] == 4 * 3      # 1 token, top 3
    assert delta["moe_assignments_here_total"] <= 12
    while eng.has_work:
        eng.step()
    text = prometheus_render({"0": eng.metrics.snapshot()})
    for name in STEP_WORK_COUNTERS:
        assert f"paddle_serving_{name}{{" in text
    assert set(LATENT_COUNTERS) <= set(STEP_WORK_COUNTERS)


def test_latent_rows_switch_reuse_off_and_say_so():
    model = tiny_dsv2()
    with warnings.catch_warnings(record=True) as said:
        warnings.simplefilter("always")
        eng = ServingEngine(model, num_slots=2, max_len=64, page_size=4,
                            chunk_len=16)
    ours = [w for w in said if "switched off" in str(w.message)]
    assert len(ours) == 1 and "latent rows" in str(ours[0].message)
    assert eng.prefix_cache is None and not eng.preempt
    assert eng.host_pages == 0 and not eng.grouped
    with pytest.raises(ValueError, match="latent cache_spec"):
        ServingEngine(model, cache_spec=(5, 2, 128, (None,) * 5, "latent"))
    with pytest.raises(ValueError, match="latent cache_spec"):
        ServingEngine(model, cache_spec=(5, 1, 128, (None,) * 5, "rows"))
    with pytest.raises(ValueError, match="latent cache_spec"):
        ServingEngine(model, cache_spec=(5, 1, 128, (None, 8) + (None,) * 3,
                                         "latent"))


@pytest.mark.parametrize("name,value", [
    ("prefix_cache", True), ("preempt", True), ("host_pages", 4),
    ("kv_dtype", "int8"), ("kv_dtype", "fp8"), ("megakernel", True),
    ("mesh", "dp1mp2"), ("adapters", True), ("spec", "ngram")])
def test_latent_rows_refuse(name, value):
    """Each feature the engine cannot give a model of the latent kind
    is refused by name when asked for."""
    with pytest.raises(ValueError, match=rf"latent rows.*'{name}'"):
        ServingEngine(tiny_dsv2(), num_slots=2, max_len=64, page_size=4,
                      chunk_len=16, **{name: value})


def test_slot_refill_recomputes_and_agrees():
    """With the prefix cache off a repeated prompt is prefilled again,
    in a slot and over pages another request has used: same tokens."""
    model = tiny_dsv2()
    eng = engine(model, num_slots=1)
    prompt = np.random.default_rng(8).integers(0, 97, size=21).tolist()
    first = serve_and_collect(eng, [prompt], 5)[0][0]
    other = serve_and_collect(eng, [prompt[::-1] + prompt], 5)
    again = serve_and_collect(eng, [prompt], 5)[0][0]
    assert first == again and len(other[0][0]) == 5
    assert eng.prefix_cache is None


def test_cancellation_frees_the_slot_and_its_pages():
    """A request cancelled in mid-prefill gives its slot and pages
    back, and the next request through that slot reads the reference's
    logits."""
    model = tiny_dsv2()
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


def test_latent_cache_refuses_what_it_is_not_served_by():
    from paddle_tpu.nlp.generation import (DecodeCache,
                                           update_and_attend_latent)
    z = paddle.to_tensor(np.zeros((1, 1, 2, 4), np.float32))
    pool = paddle.to_tensor(np.zeros((3, 2, 4), np.float32))
    dense = DecodeCache(pool, None,
                        paddle.to_tensor(np.zeros((1,), np.int32)))
    with pytest.raises(NotImplementedError, match="unified ragged step"):
        update_and_attend_latent(z, z[:, :, 0], dense, d_v=2, scale=1.0)
