"""DeepSeek-V2 (nlp/deepseek_v2.py) against the plain float32 reference
(tests/ref_deepseek_v2.py, a copy of benchmark/ref_deepseek_v2.py), at a
small size on the CPU with every mechanism present: 5 layers (one dense,
four with experts), 8 heads over a latent of 32 + a rope part of 8, a
query latent of 48, 16 experts in 4 groups (2 kept) top 3 with 4 held
here, not renormalised, two shared experts, YaRN on.

Engine-side tests are in tests/test_serving_deepseek_v2.py.
"""
import filecmp
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.nlp import DeepseekV2Config, DeepseekV2ForCausalLM
from paddle_tpu.nlp import deepseek_v2 as dsv2_mod
from paddle_tpu.ops.pallas import mla, moe

import ref_deepseek_v2 as ref

HERE = os.path.dirname(os.path.abspath(__file__))

TINY = dict(
    vocab_size=97, hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_hidden_layers=5, num_attention_heads=8,
    num_key_value_heads=8, q_lora_rank=48, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    n_routed_experts=16, n_shared_experts=2, num_experts_per_tok=3,
    n_group=4, topk_group=2, first_k_dense_replace=1,
    norm_topk_prob=False, routed_scaling_factor=4.0, rms_norm_eps=1e-6,
    rope_theta=10000,
    rope_scaling={"type": "yarn", "factor": 4, "beta_fast": 32,
                  "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707,
                  "original_max_position_embeddings": 16},
    ep_size=4, ep_rank=0)

_MODELS = {}


def tiny_dsv2(ep_rank=0, ep_size=4, dtype=None):
    key = (ep_rank, ep_size, dtype)
    if key not in _MODELS:
        paddle.seed(3)
        cfg = DeepseekV2Config(initializer_range=0.2, dtype=dtype, **dict(
            TINY, ep_rank=ep_rank, ep_size=ep_size))
        m = _MODELS[key] = DeepseekV2ForCausalLM(cfg)
        m.eval()
    return _MODELS[key]


@pytest.fixture(autouse=True)
def _small_blocks(monkeypatch):
    monkeypatch.setattr(ref, "QUERY_BLOCK", 8)
    monkeypatch.setattr(ref, "EXPERT_BLOCK", 3)
    monkeypatch.setattr(ref, "HEAD_BLOCK", 3)


def test_reference_copies_are_one_file():
    assert filecmp.cmp(
        os.path.join(HERE, "ref_deepseek_v2.py"),
        os.path.join(HERE, "..", "benchmark", "ref_deepseek_v2.py"),
        shallow=False)


def test_reference_shares_no_code_with_the_program():
    with open(os.path.join(HERE, "ref_deepseek_v2.py")) as f:
        src = f.read()
    assert "import paddle_tpu" not in src and "from paddle_tpu" not in src


def test_eager_forward_matches_reference():
    m = tiny_dsv2()
    ids = np.random.default_rng(0).integers(0, 97, size=(2, 40))
    with paddle.no_grad():
        got = np.asarray(m(paddle.to_tensor(ids))._value)
    w = ref.dsv2_weights(m)
    for b in range(2):
        want, margin = ref.dsv2_logits(w, TINY, ids[b])
        assert np.abs(np.asarray(want)).max() > 1.0
        np.testing.assert_allclose(got[b], np.asarray(want), atol=3e-4)
        assert margin.shape == (40,) and float(margin.min()) > 0


def test_eager_forward_in_bfloat16_stays_near_the_reference():
    """The same model built sublayer by sublayer in bfloat16: its
    logits lie within bf16 rounding of the float32 reference over the
    SAME (bf16) weights, far inside the logits' own spread."""
    m = tiny_dsv2(dtype="bfloat16")
    assert all(p._value.dtype == jnp.bfloat16 for p in m.parameters())
    ids = np.random.default_rng(1).integers(0, 97, size=(1, 40))
    with paddle.no_grad():
        got = np.asarray(m(paddle.to_tensor(ids))._value.astype(jnp.float32))
    want, margin = (np.asarray(a) for a in ref.dsv2_logits(
        ref.dsv2_weights(m), TINY, ids[0]))
    err = np.abs(got[0] - want)
    assert want.std() > 0.5
    assert err.mean() < 0.03 * want.std()
    # where no router choice is a near tie the largest error is
    # rounding's too; a near tie may swap an expert (ref: NEAR TIES)
    assert err[margin > 0.05].max() < 0.15 * want.std()
    assert err.max() < 0.5 * want.std()


def test_rotary_tables_and_scale_match_reference():
    cfg = tiny_dsv2().config
    inv, factor = cfg.rope_frequencies()
    cos, sin = ref.rope_tables(TINY, 40)
    ang = np.arange(40)[:, None] * inv[None, :]
    np.testing.assert_allclose(np.cos(ang) * factor, cos, atol=1e-6)
    np.testing.assert_allclose(np.sin(ang) * factor, sin, atol=1e-6)
    # mscale == mscale_all_dim: cos and sin are scaled by 1
    assert factor == 1.0
    m = 0.1 * 0.707 * np.log(4.0) + 1.0
    assert cfg.softmax_scale() == pytest.approx(24 ** -0.5 * m * m)
    assert ref.softmax_scale(TINY) == pytest.approx(cfg.softmax_scale())
    # the source's: 192^-0.5 * (0.1 * 0.707 * ln 40 + 1)^2
    src = DeepseekV2Config(rope_scaling=dict(
        TINY["rope_scaling"], factor=40,
        original_max_position_embeddings=4096))
    assert src.softmax_scale() == pytest.approx(
        192 ** -0.5 * 1.2608 ** 2, rel=1e-4)
    # without YaRN: plain rope, plain scale
    plain = DeepseekV2Config()
    assert plain.softmax_scale() == pytest.approx(192 ** -0.5)
    assert plain.rope_frequencies()[1] == 1.0
    # pairs are (2i, 2i + 1): a pair's norm survives the rotation
    x = jnp.asarray(np.random.default_rng(1).normal(size=(1, 5, 3, 8)),
                    jnp.float32)
    y = dsv2_mod._rope_pairs_fwd(x, jnp.asarray(7), jnp.asarray(
        inv, jnp.float32), 1.0)
    np.testing.assert_allclose(
        np.square(np.asarray(y)).reshape(1, 5, 3, 4, 2).sum(-1),
        np.square(np.asarray(x)).reshape(1, 5, 3, 4, 2).sum(-1), rtol=1e-5)


def _paged(view, page_size, seed=0):
    """view [B, N, D] -> (pool [P, page_size, D], page_table [B, N /
    page_size]): each row's pages dealt over a pool in a shuffled order,
    with pages no row owns in between."""
    b, n, d = view.shape
    per = n // page_size
    table = np.random.default_rng(seed).permutation(b * per + 5)[:b * per] \
        .reshape(b, per).astype(np.int32)
    pool = np.full((b * per + 5, page_size, d), 7.0, np.float32)
    pool[table.reshape(-1)] = np.asarray(view).reshape(b * per, page_size, d)
    return jnp.asarray(pool, view.dtype), jnp.asarray(table)


@pytest.mark.parametrize("key_block", [None, 32],
                         ids=["one_key_block", "key_blocks_of_32"])
@pytest.mark.parametrize("pos,q_len", [([0, 20, 37], [16, 1, 5]),
                                       ([30, 0, 47], [16, 16, 1]),
                                       ([3, 40, 0], [1, 1, 0])])
def test_walk_kernel_matches_the_dense_form(pos, q_len, key_block,
                                            monkeypatch):
    """`mla_walk` in interpret mode, the pool's pages read in place from
    a shuffled pool, against `latent_attend_reference` over the rows'
    views; chunk rows and decoding rows in one step; dead queries read
    zero. With key blocks of 32 a row's walk takes several, each
    block's pages set off while the block before computes, across work
    items too."""
    monkeypatch.setattr(mla, "_INTERPRET", True)
    if key_block:
        monkeypatch.setattr(mla, "K_BLOCK", key_block)
    rng = np.random.default_rng(0)
    b, l, h, d, dv, n = 3, 16, 4, 40, 32, 64
    q, rows = (jnp.asarray(rng.normal(size=s), jnp.float32)
               for s in ((b, l, h, d), (b, n, d)))
    pos, q_len = jnp.asarray(pos, jnp.int32), jnp.asarray(q_len, jnp.int32)
    kw = dict(d_v=dv, scale=0.3)
    pool, table = _paged(rows, 16)
    want = mla.latent_attend_reference(q, rows, pos, q_len, **kw)
    got = mla.latent_attend(q, pool, table, pos, q_len, **kw)
    np.testing.assert_allclose(got, want, atol=2e-6)
    for row in range(b):
        assert not np.asarray(got[row, int(q_len[row]):]).any()


def test_walk_fallback_gathers_the_views():
    """Off the chip and out of interpret mode `latent_attend` is the
    dense form over the views its page table names."""
    rng = np.random.default_rng(3)
    b, l, h, d, dv, n = 2, 4, 2, 24, 16, 32
    q, rows = (jnp.asarray(rng.normal(size=s), jnp.float32)
               for s in ((b, l, h, d), (b, n, d)))
    pos, q_len = jnp.asarray([9, 0], jnp.int32), jnp.asarray([4, 3], jnp.int32)
    pool, table = _paged(rows, 8)
    np.testing.assert_array_equal(
        np.asarray(mla.gather_view(pool, table)), np.asarray(rows))
    got = mla.latent_attend(q, pool, table, pos, q_len, d_v=dv, scale=0.3)
    want = mla.latent_attend_reference(q, rows, pos, q_len, d_v=dv,
                                       scale=0.3)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_latent_key_counts():
    # 3 rows: a chunk from 0, a chunk at 6, one decoding row
    pairs, distinct, rows = mla.count_latent_keys([0, 6, 100], [4, 4, 1])
    assert rows == 9
    assert pairs == (1 + 2 + 3 + 4) + (7 + 8 + 9 + 10) + 101
    # what must be read once: each row's last context
    assert distinct == 4 + 10 + 101
    assert mla.count_latent_keys([5], [0]) == (0, 0, 0)


def _plain_choice(score, top_k, n_group, topk_group):
    """The group-limited choice by plain sorts in numpy: a group scores
    as its best expert, the best groups by a stable descending sort, the
    top-k of what is left by another."""
    t, e = score.shape
    best = score.reshape(t, n_group, e // n_group).max(-1)
    kept = np.argsort(-best, -1, kind="stable")[:, :topk_group]
    left = np.zeros_like(score)
    for i in range(t):
        for g in kept[i]:
            sl = slice(g * (e // n_group), (g + 1) * (e // n_group))
            left[i, sl] = score[i, sl]
    idx = np.argsort(-left, -1, kind="stable")[:, :top_k]
    return np.take_along_axis(left, idx, -1), idx, kept


@pytest.mark.parametrize("ties", [False, True], ids=["distinct", "ties"])
def test_group_limited_choice_is_the_sort_based_one(ties):
    """`moe._top_experts` under the group limit against plain sorts;
    with scores drawn from four values, groups and experts tie all
    over, and ties go to the lower index in both."""
    rng = np.random.default_rng(3)
    if ties:
        score = rng.integers(1, 5, size=(200, 16)).astype(np.float32) / 8
    else:
        score = np.asarray(jax.nn.softmax(jnp.asarray(
            rng.normal(size=(200, 16)), jnp.float32), -1))
    val, idx = moe._top_experts(jnp.asarray(score), 3, 4, 2)
    want_v, want_i, kept = _plain_choice(score, 3, 4, 2)
    np.testing.assert_array_equal(np.asarray(idx), want_i)
    np.testing.assert_array_equal(np.asarray(val), want_v)
    for t in range(200):
        assert set(np.asarray(idx[t]) // 4) <= set(kept[t])
    # the reference's own choice agrees, and without the limit some
    # token leaves its two best groups
    ref_i, ref_v, _ = ref.choose_experts(jnp.asarray(score), 3, 4, 2)
    if not ties:
        np.testing.assert_array_equal(np.asarray(ref_i), want_i)
        np.testing.assert_array_equal(np.asarray(ref_v), want_v)
        _, free = moe._top_experts(jnp.asarray(score), 3, 1, 1)
        assert any(not set(np.asarray(free[t]) // 4) <= set(kept[t])
                   for t in range(200))


def test_lagunas_routing_is_the_parents():
    """Laguna's call of the widened `moe_route` (no group limit)
    computes what the parent's computed, bit for bit: the parent's three
    lines here; and its lowered text holds no trace of the limit."""
    rng = np.random.default_rng(29)
    x = jnp.asarray(rng.normal(size=(48, 32)), jnp.float32)
    wr = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
    route = moe.moe_route(x, wr, jnp.ones((48,), bool), top_k=4, scale=2.5,
                          norm_topk=True, first=0, n_local=8)
    logits = jnp.dot(x, wr, precision=jax.lax.Precision.HIGHEST)
    top_v, top_i = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), 4)
    top_v = top_v / jnp.sum(top_v, axis=-1, keepdims=True)
    weight = np.asarray((top_v * jnp.float32(2.5)).T.reshape(-1))
    expert = np.asarray(top_i.T.reshape(-1))
    order = np.asarray(route["order"])
    np.testing.assert_array_equal(np.asarray(route["weight_sorted"]),
                                  weight[order])
    np.testing.assert_array_equal(np.asarray(route["here"]), expert < 8)
    eid = np.where(expert < 8, expert, 8)
    assert (np.diff(eid[order]) >= 0).all()

    def parent(x, w):
        """The parent's selection, word for word."""
        score = jax.nn.softmax(jnp.dot(
            x.astype(jnp.float32), w.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST), axis=-1)
        return jax.lax.top_k(score, 4)

    def now(x, w):
        return moe._top_experts(jax.nn.softmax(jnp.dot(
            x.astype(jnp.float32), w.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST), axis=-1), 4, 1, 1)
    texts = [jax.jit(f).lower(x, wr).as_text().replace(f.__name__, "f")
             for f in (parent, now)]
    assert texts[0] == texts[1]


def test_share_parts_add_up_to_the_uncut_layer():
    """The share test: the routed parts that ep_rank 0..3 compute (a
    routing group each), plus the shared experts counted once, equal
    the uncut reference's layer (ep_size 1 over all 16 experts)."""
    whole = tiny_dsv2(0, 1)
    layer = whole.model.layers[2]
    w = ref.layer_weights(ref.dsv2_weights(whole), 2)
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(24, 64)), jnp.float32)
    want, _ = ref.sparse_ffn(x, w, dict(TINY, ep_size=1))
    a = np.asarray(ref._rms(x, w["post_attention_layernorm.weight"], 1e-6))
    parts = np.zeros((24, 64), np.float32)
    here = 0
    for rank in range(4):
        held = slice(rank * 4, rank * 4 + 4)
        out, stats = moe.routed_experts(
            jnp.asarray(a), jnp.ones((24,), bool), w["mlp.router.weight"],
            w["mlp.experts_gate"][held], w["mlp.experts_up"][held],
            w["mlp.experts_down"][held], top_k=3, scale=4.0,
            norm_topk=False, first=rank * 4, n_group=4, topk_group=2)
        parts += np.asarray(out)
        # and the reference, given the same share, gives the same part
        w_rank = dict(w, **{k: w[k][held] for k in (
            "mlp.experts_gate", "mlp.experts_up", "mlp.experts_down")})
        part_ref, _ = ref.sparse_ffn(x, w_rank, TINY, share=(4, rank),
                                     shared_experts=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(part_ref),
                                   atol=2e-4)
        assert int(stats[0]) == 24 * 3
        here += int(stats[1])
    assert here == 24 * 3           # every assignment has one home
    with paddle.no_grad():
        shared = np.asarray(layer.mlp.shared_experts(
            paddle.to_tensor(a))._value)
    np.testing.assert_allclose(np.asarray(x) + parts + shared,
                               np.asarray(want), atol=3e-4)


def test_work_counts_for_the_roofline():
    cfg = dict(kv_lora_rank=512, qk_rope_head_dim=64, qk_nope_head_dim=128,
               v_head_dim=128, num_attention_heads=128)
    assert ref.mla_step_bytes(10, cfg) == 10 * 1152
    # the expanded form's price a pair, the least any form pays
    assert ref.mla_step_flops(10, cfg) == 10 * 128 * (192 + 128) * 2


def test_judge_choices_tells_ties_apart():
    lg = np.asarray([[0.0, 1.0, 3.0], [2.0, 0.0, 1.5], [0.0, 5.0, 1.0]],
                    np.float32)
    margin = np.asarray([0.5, 0.001, 0.5], np.float32)
    got = ref.judge_choices([(lg, margin)], [[2, 2, 2]], tie_margin=0.01)
    assert got["tokens"] == 3 and got["match"] == pytest.approx(1 / 3)
    assert got["gap"] == 4.0 and got["tie_gap"] == 0.5
    assert got["tie_share"] == pytest.approx(1 / 3)
    assert got["min_margin"] == pytest.approx(0.001)
    assert got["each"]["gap"].tolist() == [0.0, 0.5, 4.0]
    # over ALL tokens, the near-tied one too
    assert got["mean_gap"] == pytest.approx(1.5)
    chk = dict(mean_gap=1.6, min_match=0.3)
    assert ref.passes(got, chk)
    for key, worse in (("mean_gap", 1.4), ("min_match", 0.5)):
        assert not ref.passes(got, dict(chk, **{key: worse}))
    # a largest gap, on either side of the margin, is reported and not
    # limited; a gap that is no number is not correct
    assert ref.passes(dict(got, gap=99.0, tie_gap=99.0), chk)
    assert not ref.passes(dict(got, mean_gap=float("nan")), chk)


def test_config_checks():
    with pytest.raises(ValueError, match="ep_size"):
        DeepseekV2Config(**dict(TINY, ep_size=3))
    with pytest.raises(ValueError, match="not built"):
        DeepseekV2Config(**dict(TINY, scoring_func="sigmoid"))
    with pytest.raises(ValueError, match="not built"):
        DeepseekV2Config(**dict(TINY, topk_method="greedy"))
    with pytest.raises(ValueError, match="n_group"):
        DeepseekV2Config(**dict(TINY, n_group=5))
    with pytest.raises(ValueError, match="only yarn"):
        DeepseekV2Config(**dict(TINY, rope_scaling={"type": "linear",
                                                    "factor": 2}))
    cfg = DeepseekV2Config()          # the source's own sizes
    assert cfg.num_local_experts == 160 and cfg.latent_row == 576
    # in the cache a row is whole tiles of 128 lanes, zeros behind
    assert cfg.cache_row == 640
    assert DeepseekV2Config(ep_size=8).num_local_experts == 20
    assert tiny_dsv2()._decode_cache_spec() == (
        5, 1, 128, (None,) * 5, "latent")
    # kept, not read
    assert DeepseekV2Config(model_type="deepseek_v2", seq_aux=True) \
        .source_keys == {"model_type": "deepseek_v2", "seq_aux": True}
