"""Laguna (nlp/laguna.py) against the plain float32 reference
(tests/ref_laguna.py, a copy of benchmark/ref_laguna.py), at a small
size on the CPU with every mechanism present: 5 layers F, W, W, W, F;
9 and 6 query heads a KV head; 16 experts, top 4, 8 held here; window
8 under contexts of 40; YaRN on half of each head in the full layers,
plain rope on the whole head in the window layers.

Engine-side tests are in tests/test_serving_laguna.py.
"""
import filecmp
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.nlp import LagunaConfig, LagunaForCausalLM
from paddle_tpu.nlp import laguna as laguna_mod
from paddle_tpu.ops.pallas import moe
from paddle_tpu.ops.pallas import paged_attention as pa

import ref_laguna as ref

HERE = os.path.dirname(os.path.abspath(__file__))

TINY = dict(
    vocab_size=97, hidden_size=64, intermediate_size=128,
    num_hidden_layers=5, num_key_value_heads=2, head_dim=16,
    num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, sliding_window=8,
    layer_types=["full_attention"] + ["sliding_attention"] * 3
    + ["full_attention"],
    mlp_layer_types=["dense"] + ["sparse"] * 4,
    num_attention_heads_per_layer=[12, 18, 18, 18, 12],
    norm_topk_prob=True, moe_routed_scaling_factor=2.5, rms_norm_eps=1e-6,
    rope_parameters={
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 8,
            "original_max_position_embeddings": 16, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.2,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    ep_size=2, ep_rank=0)

_MODELS = {}


def tiny_laguna(ep_rank=0, ep_size=2):
    """Same seed whatever the share: ranks differ in which experts they
    hold, and their other weights are equal draw for draw only where
    the parameter shapes are, so the share test builds its own."""
    key = (ep_rank, ep_size)
    if key not in _MODELS:
        paddle.seed(3)
        cfg = LagunaConfig(initializer_range=0.2,
                           **dict(TINY, ep_rank=ep_rank, ep_size=ep_size))
        m = _MODELS[key] = LagunaForCausalLM(cfg)
        m.eval()
    return _MODELS[key]


@pytest.fixture(autouse=True)
def _small_blocks(monkeypatch):
    monkeypatch.setattr(ref, "QUERY_BLOCK", 8)
    monkeypatch.setattr(ref, "EXPERT_BLOCK", 3)


def test_reference_copies_are_one_file():
    assert filecmp.cmp(os.path.join(HERE, "ref_laguna.py"),
                       os.path.join(HERE, "..", "benchmark",
                                    "ref_laguna.py"), shallow=False)


def test_reference_shares_no_code_with_the_program():
    with open(os.path.join(HERE, "ref_laguna.py")) as f:
        src = f.read()
    assert "import paddle_tpu" not in src and "from paddle_tpu" not in src


def test_eager_forward_matches_reference():
    m = tiny_laguna()
    ids = np.random.default_rng(0).integers(0, 97, size=(2, 40))
    with paddle.no_grad():
        got = np.asarray(m(paddle.to_tensor(ids))._value)
    w = ref.laguna_weights(m)
    for b in range(2):
        want, margin = ref.laguna_logits(w, TINY, ids[b])
        assert np.abs(np.asarray(want)).max() > 1.0
        np.testing.assert_allclose(got[b], np.asarray(want), atol=2e-4)
        assert margin.shape == (40,) and float(margin.min()) > 0


@pytest.mark.parametrize("kind,rot", [("full_attention", 8),
                                      ("sliding_attention", 16)])
def test_rotary_tables_match_reference(kind, rot):
    rope = TINY["rope_parameters"][kind]
    inv, factor = laguna_mod.rotary_frequencies(rope, rot)
    cos, sin = ref.rope_tables(rope, 16, 40)
    ang = np.arange(40)[:, None] * inv[None, :]
    np.testing.assert_allclose(np.cos(ang) * factor, cos, atol=1e-6)
    np.testing.assert_allclose(np.sin(ang) * factor, sin, atol=1e-6)
    if kind == "full_attention":
        # YaRN moved the low frequencies and left the highest alone
        plain = 500000.0 ** (-np.arange(0, rot, 2) / rot)
        assert inv[0] == pytest.approx(plain[0])
        assert inv[-1] < plain[-1]


def test_share_parts_add_up_to_the_uncut_layer():
    """The share test: the routed parts that ep_rank 0 and 1 compute,
    plus the shared expert counted once, equal the uncut reference's
    layer (ep_size 1 over all 16 experts)."""
    whole = tiny_laguna(0, 1)
    layer = whole.laguna.layers[2]
    wname = "laguna.layers.2."
    w = {n[len(wname):]: v for n, v in ref.laguna_weights(whole).items()
         if n.startswith(wname)}
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(24, 64)), jnp.float32)
    want, _ = ref.sparse_ffn(x, w, dict(TINY, ep_size=1))
    a = np.asarray(ref._rms(x, w["post_attention_layernorm.weight"], 1e-6))
    parts = np.zeros((24, 64), np.float32)
    for rank in (0, 1):
        held = slice(rank * 8, rank * 8 + 8)
        out, stats = moe.routed_experts(
            jnp.asarray(a), jnp.ones((24,), bool), w["mlp.router.weight"],
            w["mlp.experts_gate"][held], w["mlp.experts_up"][held],
            w["mlp.experts_down"][held], top_k=4, scale=2.5,
            norm_topk=True, first=rank * 8)
        parts += np.asarray(out)
        # and the reference, given the same share, gives the same part
        w_rank = dict(w, **{k: w[k][held] for k in (
            "mlp.experts_gate", "mlp.experts_up", "mlp.experts_down")})
        part_ref, _ = ref.sparse_ffn(x, w_rank, TINY, share=(2, rank),
                                     shared_expert=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(part_ref),
                                   atol=2e-4)
        assert int(stats[0]) == 24 * 4 and 0 < int(stats[1]) < 24 * 4
    with paddle.no_grad():
        shared = np.asarray(layer.mlp.shared_expert(
            paddle.to_tensor(a))._value)
    np.testing.assert_allclose(np.asarray(x) + parts + shared,
                               np.asarray(want), atol=3e-4)


@pytest.mark.parametrize("impl", ["kernel", "ragged_dot"])
def test_routed_experts_skip_dead_tokens_and_count(impl, monkeypatch):
    """Both forms of the expert product (the Pallas kernel in interpret
    mode, `jax.lax.ragged_dot` as the CPU gets it) agree with a
    token-by-token loop; a token marked dead is routed nowhere and
    counted nowhere."""
    monkeypatch.setattr(moe, "_INTERPRET", impl == "kernel")
    rng = np.random.default_rng(1)
    t, h, f, n_exp, held, k = 24, 32, 16, 16, 8, 4
    x = jnp.asarray(rng.normal(size=(t, h)), jnp.float32)
    wr = jnp.asarray(rng.normal(size=(h, n_exp)), jnp.float32)
    wg, wu = (jnp.asarray(rng.normal(size=(held, h, f)) * 0.2, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(held, f, h)) * 0.2, jnp.float32)
    valid = np.asarray(rng.random(t) < 0.6)
    score = jax.nn.softmax(x @ wr, -1)
    top_v, top_i = jax.lax.top_k(score, k)
    weight = 2.5 * top_v / top_v.sum(-1, keepdims=True)
    want = np.zeros((t, h), np.float32)
    hit, here = set(), 0
    for tok in np.nonzero(valid)[0]:
        for j in range(k):
            e = int(top_i[tok, j]) - 8
            if 0 <= e < held:
                act = jax.nn.silu(x[tok] @ wg[e]) * (x[tok] @ wu[e])
                want[tok] += float(weight[tok, j]) * np.asarray(act @ wd[e])
                hit.add(e)
                here += 1
    out, stats = moe.routed_experts(x, jnp.asarray(valid), wr, wg, wu, wd,
                                    top_k=k, scale=2.5, norm_topk=True,
                                    first=8)
    np.testing.assert_allclose(np.asarray(out), want, atol=1e-5)
    assert np.asarray(out)[~valid].max() == 0.0
    assert [int(v) for v in stats] == [int(valid.sum()) * k, here, len(hit)]


def test_routed_experts_tile_only_the_experts_hit(monkeypatch):
    """Fixed shapes whatever the routing, and a grid no longer than the
    tiles the routing filled: 3 live tokens of 64 touch at most 12
    experts, so at most 12 tiles of the 10 + 8 the shapes allow."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    wr = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
    valid = jnp.arange(64) < 3
    route = moe.moe_route(x, wr, valid, top_k=4, scale=1.0, norm_topk=True,
                          first=0, n_local=8, tile_rows=32)
    assert route["tile_expert"].shape == (64 * 4 // 32 + 8,)
    assert route["src"].shape == ((64 * 4 // 32 + 8) * 32,)
    n_hit = int(route["stats"][2])
    assert int(route["n_tiles"]) == n_hit <= 8
    assert int((route["group_sizes"] > 0).sum()) == n_hit
    # every tile in use belongs to an expert that was hit
    used = np.asarray(route["tile_expert"])[:n_hit]
    assert (np.asarray(route["group_sizes"])[used] > 0).all()


@pytest.mark.parametrize("pos,q_len", [([37, 0, 20], [1, 16, 9]),
                                       ([5, 63, 0], [3, 1, 0])])
def test_window_walk_kernel_matches_reference_over_a_ring(pos, q_len,
                                                          monkeypatch):
    """The page walk with a window, in interpret mode, over a page table
    that is a ring of fewer physical pages than it has columns: equal
    to the gather reference, which masks what lies below the window."""
    monkeypatch.setattr(pa, "_INTERPRET", True)
    rng = np.random.default_rng(0)
    b, w, h, hkv, d, ps, window, mp = 3, 16, 6, 2, 16, 4, 8, 20
    ring = (window + w + ps - 1) // ps + 1
    pools = [jnp.asarray(rng.normal(size=(b * ring + 1, ps, hkv, d)),
                         jnp.float32) for _ in range(2)]
    tab = jnp.asarray(1 + np.arange(b)[:, None] * ring
                      + np.arange(mp)[None, :] % ring, jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, w, h, d)), jnp.float32)
    pos, q_len = jnp.asarray(pos, jnp.int32), jnp.asarray(q_len, jnp.int32)
    want = pa.ragged_attention_reference(q, *pools, tab, pos, q_len, None,
                                         window)
    got = pa.ragged_paged_attention(q, *pools, tab, pos, q_len,
                                    window=window)
    unwindowed = pa.ragged_attention_reference(q, *pools, tab, pos, q_len)
    for row in range(b):
        n = int(q_len[row])
        np.testing.assert_allclose(got[row, :n], want[row, :n], atol=2e-6)
        # the q-block axis is a dynamic bound: dead queries read zero
        assert not np.asarray(got[row, n:]).any()
        if int(pos[row]) + n > window:
            assert np.abs(np.asarray(want[row, :n])
                          - np.asarray(unwindowed[row, :n])).max() > 1e-3


def test_window_walk_grid_is_the_window_not_the_context(monkeypatch):
    # Laguna's window layers: 9 query heads a kv head, so q-blocks of 16,
    # over key blocks of 256: 4 of the 32 a context of 8192 has
    assert pa._query_blocks(128, 9) == (16, 8)
    assert pa._window_blocks(512, 16, 256) == 4
    assert pa._window_blocks(8, 8, 4) == 5
    # and the grid the wrapper asks for has that many whatever the rows'
    # positions: 8 + 16 - 1 positions lie on 4 key blocks of 8 at most
    monkeypatch.setattr(pa, "_INTERPRET", True)
    monkeypatch.setattr(pa, "K_BLOCK", 8)
    grids = []
    real = pa.pl.pallas_call

    def spy(kernel, **kw):
        grids.append(tuple(int(g) for g in kw["grid_spec"].grid))
        return real(kernel, **kw)

    monkeypatch.setattr(pa.pl, "pallas_call", spy)
    pools = [jnp.zeros((41, 4, 2, 16), jnp.float32)] * 2
    tab = jnp.asarray(1 + np.arange(40).reshape(2, 20), jnp.int32)
    q = jnp.zeros((2, 16, 6, 16), jnp.float32)
    with jax.disable_jit():               # the bounds as numbers
        for pos in ([0, 3], [40, 64]):
            pa.ragged_paged_attention(
                q, *pools, tab, jnp.asarray(pos, jnp.int32),
                jnp.asarray([16, 1], jnp.int32), window=8)
    assert grids == [(2, 4), (2, 4)]
    walked, unwindowed = pa.count_window_page_reads(
        [8000, 0, 100], [1, 128, 0], page_size=16, window=512)
    assert (walked, unwindowed) == (33 + 8, 501 + 8)


def test_window_refused_where_no_path_has_one():
    from paddle_tpu.nlp.generation import DecodeCache, update_and_attend
    z = paddle.to_tensor(np.zeros((1, 1, 2, 4), np.float32))
    pool = paddle.to_tensor(np.zeros((3, 2, 2, 4), np.int8))
    scale = paddle.to_tensor(np.zeros((3, 2, 2), np.float32))
    cache = DecodeCache(pool, pool, paddle.to_tensor(np.zeros((1,), np.int32)),
                        scale, scale,
                        page_table=paddle.to_tensor(np.ones((1, 2), np.int32)))
    with pytest.raises(NotImplementedError, match="sliding-window"):
        update_and_attend(z, z, z, cache, window=4)


def test_config_checks():
    with pytest.raises(ValueError, match="ep_size"):
        LagunaConfig(**dict(TINY, ep_size=3))
    with pytest.raises(ValueError, match="not built"):
        LagunaConfig(**dict(TINY, moe_router_logit_softcapping=30))
    with pytest.raises(ValueError, match="entries"):
        LagunaConfig(**dict(TINY, layer_types=["full_attention"]))
    cfg = LagunaConfig()        # the source's own sizes
    assert cfg.num_local_experts == 256 and cfg.window_of(1) == 512
    assert cfg.window_of(0) is None and cfg.mlp_layer_types[0] == "dense"
    assert tiny_laguna()._decode_cache_spec() == (5, 2, 16,
                                                  (None, 8, 8, 8, None))
