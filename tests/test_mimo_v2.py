"""MiMo-V2-Flash (nlp/mimo_v2.py) against the plain float32 reference
(tests/ref_mimo_v2.py, a copy of benchmark/ref_mimo_v2.py), at a small
size on the CPU with every mechanism present: 5 layers F, W, W, W, F;
full layers 8 query heads over 2 kv heads, window layers 8 over 4; keys
48 wide and values 32; rotary on the first 16 dims, two thetas; a sink a
query head in the window layers; window 8 under contexts of 40; the
first layer dense, then 16 sigmoid-routed experts with a selection bias,
top 4, 8 held here.

The walk over pools of split widths and the router's sigmoid scoring
are tested here too; engine-side tests are in
tests/test_serving_mimo_v2.py.
"""
import filecmp
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.nlp import MiMoV2Config, MiMoV2ForCausalLM
from paddle_tpu.ops.pallas import moe
from paddle_tpu.ops.pallas import paged_attention as pa

import ref_mimo_v2 as ref

HERE = os.path.dirname(os.path.abspath(__file__))

TINY = dict(
    vocab_size=97, hidden_size=64, intermediate_size=128,
    num_hidden_layers=5, num_attention_heads=8, num_key_value_heads=2,
    head_dim=48, v_head_dim=32, swa_num_attention_heads=8,
    swa_num_key_value_heads=4, swa_head_dim=48, swa_v_head_dim=32,
    layernorm_epsilon=1e-5, rope_theta=5000000, swa_rope_theta=10000,
    partial_rotary_factor=0.334, sliding_window=8,
    hybrid_layer_pattern=[0, 1, 1, 1, 0], moe_layer_freq=[0, 1, 1, 1, 1],
    add_swa_attention_sink_bias=True, add_full_attention_sink_bias=False,
    attention_value_scale=0.707, moe_intermediate_size=32,
    n_routed_experts=16, num_experts_per_tok=4, norm_topk_prob=True,
    scoring_func="sigmoid", topk_method="noaux_tc",
    routed_scaling_factor=None, n_shared_experts=None, ep_size=2, ep_rank=0,
    sink_init=[4.0, 1.0], correction_bias_std=0.1)

_MODELS = {}


def tiny_mimo(ep_rank=0, ep_size=2):
    key = (ep_rank, ep_size)
    if key not in _MODELS:
        paddle.seed(3)
        cfg = MiMoV2Config(initializer_range=0.2,
                           **dict(TINY, ep_rank=ep_rank, ep_size=ep_size))
        m = _MODELS[key] = MiMoV2ForCausalLM(cfg)
        m.eval()
    return _MODELS[key]


@pytest.fixture(autouse=True)
def _small_blocks(monkeypatch):
    monkeypatch.setattr(ref, "QUERY_BLOCK", 8)
    monkeypatch.setattr(ref, "EXPERT_BLOCK", 3)
    monkeypatch.setattr(ref, "TOKEN_BLOCK", 16)
    monkeypatch.setattr(ref, "WIDTH_STEP", 16)


def test_reference_copies_are_one_file():
    assert filecmp.cmp(os.path.join(HERE, "ref_mimo_v2.py"),
                       os.path.join(HERE, "..", "benchmark",
                                    "ref_mimo_v2.py"), shallow=False)


def test_reference_shares_no_code_with_the_program():
    with open(os.path.join(HERE, "ref_mimo_v2.py")) as f:
        src = f.read()
    assert "import paddle_tpu" not in src and "from paddle_tpu" not in src


def test_eager_forward_matches_reference():
    m = tiny_mimo()
    ids = np.random.default_rng(0).integers(0, 97, size=(2, 40))
    with paddle.no_grad():
        got = np.asarray(m(paddle.to_tensor(ids))._value)
    w = ref.mimo_weights(m)
    for b in range(2):
        want, margin = ref.mimo_logits(w, TINY, ids[b])
        assert np.abs(np.asarray(want)).max() > 1.0
        np.testing.assert_allclose(got[b], np.asarray(want), atol=3e-4)
        assert margin.shape == (40,) and float(margin.min()) >= 0


@pytest.mark.parametrize("left_out", ["sinks", "bias"])
def test_leaving_a_mechanism_out_is_another_model(left_out):
    """The sink and the selection bias each move the logits by far more
    than rounding at these seeds: a program that left either out would
    not pass for this one."""
    m = tiny_mimo()
    ids = np.random.default_rng(1).integers(0, 97, size=40)
    w = ref.mimo_weights(m)
    want, _ = ref.mimo_logits(w, TINY, ids)
    other, _ = ref.mimo_logits(w, TINY, ids, **{left_out: False})
    assert np.abs(np.asarray(want) - np.asarray(other)).max() > 0.1


def _dense_attention(q, k, v, pos, q_len, window, sink):
    """Row by row, query by query, in numpy float64: the published
    softmax with the sink in its denominator."""
    b, lq, h, dk = q.shape
    hkv = k.shape[2]
    rep = h // hkv
    out = np.zeros((b, lq, h, v.shape[-1]))
    for r in range(b):
        for i in range(q_len[r]):
            t = pos[r] + i
            lo = 0 if window is None else max(0, t - window + 1)
            for hh in range(h):
                g = hh // rep
                s = k[r, lo:t + 1, g] @ q[r, i, hh] / np.sqrt(dk)
                m = s.max() if sink is None else max(s.max(), sink[hh])
                p = np.exp(s - m)
                den = p.sum() + (0.0 if sink is None
                                 else np.exp(sink[hh] - m))
                out[r, i, hh] = (p / den) @ v[r, lo:t + 1, g]
    return out


@pytest.mark.parametrize("interpret", [True, False])
@pytest.mark.parametrize("widths", [(192, 128), (48, 32)],
                         ids=["in_place", "views"])
@pytest.mark.parametrize("window,sink", [(8, True), (None, False),
                                         (None, True)],
                         ids=["window_sink", "full", "full_sink"])
def test_split_walk_matches_dense_softmax(interpret, widths, window, sink,
                                          monkeypatch):
    """The walk over pools of split widths (keys of Dk, values of Dv,
    kv heads side by side) against a dense softmax: chunk rows and
    decoding rows in one call, a row at the window's edge, a dead row
    whose output is zero, a sink a query head. In interpret mode the
    kernel (heads of 192 and 128: pages read in place; of 48 and 32: the
    rows' gathered views), off it the jnp form."""
    monkeypatch.setattr(pa, "_INTERPRET", interpret)
    monkeypatch.setattr(pa, "K_BLOCK", 8)
    rng = np.random.default_rng(7)
    dk, dv = widths
    b, lq, h, hkv, ps, mp = 4, 8, 8, 2, 4, 8
    pos = np.asarray([0, 13, 9, 20], np.int32)
    q_len = np.asarray([8, 1, 0, 3], np.int32)
    kd = rng.normal(size=(b, mp * ps, hkv, dk)).astype(np.float32)
    vd = rng.normal(size=(b, mp * ps, hkv, dv)).astype(np.float32)
    q = rng.normal(size=(b, lq, h, dk)).astype(np.float32)
    sinks = rng.normal(1.0, 1.0, size=h).astype(np.float32) if sink else None
    # the rows' pages, shuffled over a pool with a trash page 0
    perm = 1 + rng.permutation(b * mp)
    table = perm.reshape(b, mp).astype(np.int32)
    k_pool = np.zeros((b * mp + 1, ps, hkv * dk), np.float32)
    v_pool = np.zeros((b * mp + 1, ps, hkv * dv), np.float32)
    k_pool[table.reshape(-1)] = kd.reshape(b * mp, ps, hkv * dk)
    v_pool[table.reshape(-1)] = vd.reshape(b * mp, ps, hkv * dv)
    got = pa.ragged_paged_attention_split(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(table), jnp.asarray(pos), jnp.asarray(q_len),
        None if sinks is None else jnp.asarray(sinks), heads=hkv,
        window=window)
    assert got.shape == (b, lq, h, dv)
    want = _dense_attention(q, kd, vd, pos, q_len, window, sinks)
    for r in range(b):
        n = int(q_len[r])
        np.testing.assert_allclose(np.asarray(got[r, :n]), want[r, :n],
                                   atol=2e-5)
        if interpret:
            # the dead queries, and the dead row, read zero
            assert not np.asarray(got[r, n:]).any()


def test_sink_walk_weighs_one_key_against_the_sink(monkeypatch):
    """The sink is folded in at the item's end, as one more term of the
    denominator that adds no value: a query that sees one key (a window
    of 1) weighs it by exp(s) / (exp(s) + exp(b)) and takes the rest of
    its mass from nothing."""
    monkeypatch.setattr(pa, "_INTERPRET", True)
    rng = np.random.default_rng(3)
    hkv, dk, dv, ps = 2, 192, 128, 4
    k_pool = jnp.asarray(rng.normal(size=(3, ps, hkv * dk)), jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=(3, ps, hkv * dv)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(1, 1, 4, dk)), jnp.float32)
    sink = jnp.asarray([0.0, 1.0, -1.0, 2.0], jnp.float32)
    table = jnp.asarray([[1, 2]], jnp.int32)
    got = pa.ragged_paged_attention_split(
        q, k_pool, v_pool, table, jnp.asarray([0], jnp.int32),
        jnp.asarray([1], jnp.int32), sink, heads=hkv, window=1)
    k0 = np.asarray(k_pool[1, 0]).reshape(hkv, dk)
    v0 = np.asarray(v_pool[1, 0]).reshape(hkv, dv)
    for hh in range(4):
        s = float(np.asarray(q[0, 0, hh]) @ k0[hh // 2]) / np.sqrt(dk)
        w = 1.0 / (1.0 + np.exp(float(sink[hh]) - s))
        np.testing.assert_allclose(np.asarray(got[0, 0, hh]),
                                   w * v0[hh // 2], atol=2e-5)


def test_walk_pair_counts():
    # a full layer: query i of a row at pos p sees p + i + 1 keys
    pairs, keys, rows = pa.count_walk_pairs([10, 0, 5], [3, 0, 1])
    assert pairs == 11 + 12 + 13 + 6 and keys == 13 + 6 and rows == 2
    # a window of 4: at most 4 a query; the keys of a chunk's window
    pairs, keys, rows = pa.count_walk_pairs([10, 1, 0], [3, 1, 5], 4)
    assert pairs == 3 * 4 + 2 + (1 + 2 + 3 + 4 + 4)
    assert keys == (4 - 1 + 3) + 2 + 5 and rows == 3


def test_sigmoid_routing_bias_moves_the_selection_not_the_weights():
    """moe_route's sigmoid scoring against a plain top_k: the experts
    are the top-k of sigmoid + bias, their weights the sigmoids alone
    renormalised; the fourth count is the assignments the bias moved."""
    rng = np.random.default_rng(2)
    t, h, n_exp, k = 24, 32, 16, 4
    x = jnp.asarray(rng.normal(size=(t, h)), jnp.float32)
    wr = jnp.asarray(rng.normal(size=(h, n_exp)) * 0.2, jnp.float32)
    bias = jnp.asarray(rng.normal(size=n_exp) * 0.2, jnp.float32)
    valid = jnp.asarray(np.arange(t) < 20)
    route = moe.moe_route(x, wr, valid, top_k=k, scale=1.0, norm_topk=True,
                          first=0, n_local=n_exp, scoring="sigmoid",
                          bias=bias)
    sig = np.asarray(jax.nn.sigmoid(x @ wr))
    chosen = np.asarray(jax.lax.top_k(sig + np.asarray(bias), k)[1])
    plain = np.asarray(jax.lax.top_k(sig, k)[1])
    # every assignment is computed here (n_local = all): the rows of
    # the tiled layout hold the chosen experts with their weights
    got = np.zeros((t, n_exp))
    tiles = np.asarray(route["tile_expert"])
    src = np.asarray(route["src"])
    rw = np.asarray(route["row_weight"])[:, 0]
    for i in range(int(route["n_tiles"]) * moe.TILE_ROWS):
        if rw[i]:
            got[src[i], tiles[i // moe.TILE_ROWS]] += rw[i]
    for tok in range(20):
        want = np.zeros(n_exp)
        want[chosen[tok]] = sig[tok, chosen[tok]] / sig[tok, chosen[tok]].sum()
        np.testing.assert_allclose(got[tok], want, atol=1e-6)
    moved = sum(len(set(chosen[tok]) - set(plain[tok])) for tok in range(20))
    assert 0 < moved
    assert [int(v) for v in route["stats"]] == [20 * k, 20 * k,
                                                int((got[:20] > 0).any(0)
                                                    .sum()), moved]


def test_softmax_route_has_no_fourth_count():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
    wr = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
    route = moe.moe_route(x, wr, jnp.ones((8,), bool), top_k=2, scale=1.0,
                          norm_topk=True, first=0, n_local=8)
    assert route["stats"].shape == (3,)
    with pytest.raises(ValueError, match="scoring"):
        moe.moe_route(x, wr, jnp.ones((8,), bool), top_k=2, scale=1.0,
                      norm_topk=True, first=0, n_local=8, scoring="tanh")


def test_share_parts_add_up_to_the_uncut_layer():
    """The share test: the routed parts that ep_rank 0 and 1 compute
    equal the uncut reference's routed part (ep_size 1 over all 16
    experts), and the reference given a share gives the program's part;
    the residual is added once."""
    whole = tiny_mimo(0, 1)
    wname = "model.layers.2."
    w = {n[len(wname):]: v for n, v in ref.mimo_weights(whole).items()
         if n.startswith(wname)}
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(24, 64)), jnp.float32)
    want, _ = ref.sparse_ffn(x, w, dict(TINY, ep_size=1))
    a = np.asarray(ref._rms(x, w["post_attention_layernorm.weight"], 1e-5))
    parts = np.zeros((24, 64), np.float32)
    for rank in (0, 1):
        held = slice(rank * 8, rank * 8 + 8)
        out, stats = moe.routed_experts(
            jnp.asarray(a), jnp.ones((24,), bool), w["mlp.gate.weight"],
            w["mlp.experts_gate"][held], w["mlp.experts_up"][held],
            w["mlp.experts_down"][held], top_k=4, scale=1.0,
            norm_topk=True, first=rank * 8, scoring="sigmoid",
            bias=w["mlp.e_score_correction_bias"])
        parts += np.asarray(out)
        w_rank = dict(w, **{k: w[k][held] for k in (
            "mlp.experts_gate", "mlp.experts_up", "mlp.experts_down")})
        part_ref, _ = ref.sparse_ffn(x, w_rank, TINY, share=(2, rank),
                                     residual=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(part_ref),
                                   atol=2e-4)
        assert int(stats[0]) == 24 * 4 and 0 < int(stats[1]) < 24 * 4
        assert stats.shape == (4,)
    np.testing.assert_allclose(np.asarray(x) + parts, np.asarray(want),
                               atol=3e-4)


def test_config_checks():
    with pytest.raises(ValueError, match="ep_size"):
        MiMoV2Config(**dict(TINY, ep_size=3))
    with pytest.raises(ValueError, match="not built"):
        MiMoV2Config(**dict(TINY, n_shared_experts=1))
    with pytest.raises(ValueError, match="not built"):
        MiMoV2Config(**dict(TINY, scoring_func="softmax"))
    with pytest.raises(ValueError, match="entries"):
        MiMoV2Config(**dict(TINY, hybrid_layer_pattern=[0, 1]))
    with pytest.raises(ValueError, match="sliding_window_size"):
        MiMoV2Config(**dict(TINY, sliding_window_size=16))
    cfg = MiMoV2Config()        # the source's own sizes
    assert cfg.num_local_experts == 256 and cfg.window_of(1) == 128
    assert cfg.window_of(0) is None and cfg.moe_layer_freq[0] == 0
    assert cfg.geometry(0) == (64, 4, 192, 128, 5e6, False)
    assert cfg.geometry(1) == (64, 8, 192, 128, 1e4, True)
    assert int(192 * cfg.partial_rotary_factor) == 64
    assert tiny_mimo()._decode_cache_spec() == (
        5, 2, 48, (None, 8, 8, 8, None), "split",
        ((2, 48, 32, False),) + ((4, 48, 32, True),) * 3
        + ((2, 48, 32, False),))
