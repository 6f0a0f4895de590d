"""The KV pools are DONATED to the programs that return them (the unified
step, the COW page copy, the swap-in): each writes its rows or pages into
the pools' own buffers, and the engine takes back what it returns. What
must hold: the arrays handed over are gone (nothing can read them again),
the compiled step aliases every pool byte, the embed epilogue (a pure
read) leaves the pools alive, and the host tier, whose spill gathers
read pages that a donating program may rewrite in place the same round,
still hands every request the tokens of a solo run."""
import warnings

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.nlp import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import SamplingParams, ServingEngine
from paddle_tpu.serving.metrics import prometheus_render

from test_deepseek_v2 import tiny_dsv2
from test_keye_vl2 import tiny_keye
from test_laguna import tiny_laguna

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


# one model a cache kind: (n_kv, head_dim) pools, window rings beside
# them, one latent row a token, K / V with an indexer's row pool
KINDS = {"paged": tiny_gpt, "window": tiny_laguna, "latent": tiny_dsv2,
         "sparse": tiny_keye}


def engine(kind, **kw):
    kw = dict(dict(num_slots=2, max_len=64, page_size=4, chunk_len=16), **kw)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        return ServingEngine(KINDS[kind](), **kw)


def pools(eng):
    return jax.tree_util.tree_leaves(eng._ct)


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_pools_are_handed_over_and_aliased_whole(kind):
    eng = engine(kind)
    handed = pools(eng)
    eng.add_request(np.arange(1, 24), SamplingParams(max_new_tokens=3))
    eng.step()                                  # the step's first launch
    assert all(a.is_deleted() for a in handed)
    assert not any(a.is_deleted() for a in pools(eng))
    # the compiled step writes every pool byte in place
    snap = eng.metrics.snapshot()
    assert snap["kv_pool_bytes"] == sum(a.nbytes for a in pools(eng)) > 0
    assert snap["kv_pool_aliased_bytes"] >= snap["kv_pool_bytes"]
    text = prometheus_render({"r0": snap})
    assert f'paddle_serving_kv_pool_bytes{{replica="r0"}} ' \
        f'{snap["kv_pool_bytes"]}' in text
    assert f'paddle_serving_kv_pool_aliased_bytes{{replica="r0"}} ' \
        f'{snap["kv_pool_aliased_bytes"]}' in text
    eng.run()
    # the COW copy: one program for every kind, the page copied in place
    # (read a page through JAX: a numpy view of a whole pool on the CPU
    # is a reference the runtime will not donate past)
    src = [np.asarray(a[3]) for a in pools(eng)]
    handed = pools(eng)
    eng._copy_page(3, 5)
    assert all(a.is_deleted() for a in handed)
    for want, a in zip(src, pools(eng)):
        np.testing.assert_array_equal(np.asarray(a[5]), want)
    assert eng._copy_page_fn._cache_size() == 1


def test_restore_and_embed_epilogue():
    """The swap-in writes a page in place; the embed epilogue reads the
    pools and leaves them alive (it is not handed them)."""
    eng = engine("paged")
    eng.add_request(np.arange(1, 24), SamplingParams(max_new_tokens=3))
    eng.run()
    payload = eng._extract_page(2)
    handed = pools(eng)
    eng._restore_page(payload, 6)
    assert all(a.is_deleted() for a in handed)
    np.testing.assert_array_equal(eng._extract_page(6), payload)
    req = eng.add_request(np.arange(5, 17), SamplingParams(
        max_new_tokens=1, embed=True))
    eng.run()
    assert req.embedding is not None
    # the epilogue again, on its own: the pools it read stay alive
    live = pools(eng)
    S = eng.num_slots
    np.asarray(eng._embed_fn(
        eng._ct, eng._dev(np.zeros((S,), np.int32)),
        eng._dev(np.zeros((S, eng.max_pages), np.int32)),
        eng._dev(np.zeros((S, 1), np.int32))))
    assert not any(a.is_deleted() for a in live)
    assert all(a is b for a, b in zip(live, pools(eng)))


def test_every_gathered_piece_is_read_before_the_gather_returns(
        monkeypatch):
    """The guard the host tier rests on: `_gather_pages` hands back only
    pieces it has waited for, so no donating program dispatched after it
    (the step, a COW copy, a restore into a page it just freed) can
    overwrite a page before it was read, whatever the runtime's order."""
    eng = engine("paged", num_pages=17)
    waited = []
    real = jax.block_until_ready

    def spy(x):
        waited.extend(jax.tree_util.tree_leaves(x))
        return real(x)
    monkeypatch.setattr(jax, "block_until_ready", spy)
    pieces = eng._gather_pages([3, 4, 5, 9, 11])
    leaves = jax.tree_util.tree_leaves(pieces)
    assert len(pieces) == 2 and leaves
    assert all(any(x is w for w in waited) for x in leaves)


def oracle_greedy(model, prompt, n_new):
    out = model.generate(paddle.to_tensor(np.asarray(prompt)[None]),
                         max_new_tokens=n_new).numpy()
    return out[0, len(prompt):].tolist()


def test_host_tier_under_donation_matches_solo_runs():
    """Prefix cache, host tier and preemption on, and too few pages:
    spills, preemptions and restores on most rounds, every page program
    donating. Greedy tokens are the solo CompiledGenerator's."""
    model = tiny_gpt()
    eng = ServingEngine(model, num_slots=3, max_len=64, page_size=4,
                        num_pages=19, chunk_len=8)
    rounds = {"gather": set(), "restore": set(), "cow": set()}
    for name, attr in (("gather", "_gather_pages"),
                       ("restore", "_restore_page"), ("cow", "_copy_page")):
        real = getattr(eng, attr)

        def spy(*a, _real=real, _name=name):
            rounds[_name].add(eng._step_idx)
            return _real(*a)
        setattr(eng, attr, spy)
    rng = np.random.RandomState(11)
    stems = [rng.randint(0, 97, size=16) for _ in range(4)]
    want, reqs, steps = [], [], 0
    for i in range(40):
        # a request a round: one in three outranks the residents
        stem = stems[i % 4][:rng.randint(4, 17)]
        tail = rng.randint(0, 97, size=rng.randint(2, 10))
        prompt = np.concatenate([stem, tail])
        n_new = int(rng.randint(2, 6))
        want.append(oracle_greedy(model, prompt, n_new))
        reqs.append(eng.add_request(prompt, SamplingParams(
            max_new_tokens=n_new, priority=0 if i % 3 == 0 else 5)))
        eng.step()
        steps += 1
    while eng.has_work:
        eng.step()
        steps += 1
    assert [list(map(int, r.output_tokens)) for r in reqs] == want
    assert eng.metrics.preemptions > 0
    assert eng.prefix_cache.spilled_pages_total > 0
    assert eng.prefix_cache.restored_pages_total > 0
    assert rounds["cow"]
    busy = rounds["gather"] | rounds["restore"]
    assert len(busy) > steps / 2, (len(busy), steps)
    assert eng.host_pool.pending_pages == 0
