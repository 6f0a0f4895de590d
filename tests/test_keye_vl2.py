"""Keye-VL-2.0's language model (nlp/keye_vl2.py) against the plain
reference (tests/ref_keye_vl2.py, a copy of benchmark/ref_keye_vl2.py):
the published form of the sparse attention, the pieces of
ops/pallas/sparse.py in interpret mode against their jnp forms, the
share arithmetic, the counters' arithmetic, the configuration checks."""
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.nlp import KeyeVL2Config, KeyeVL2ForCausalLM
from paddle_tpu.ops.pallas import moe
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.ops.pallas import sparse as sp

import ref_keye_vl2 as ref

HERE = os.path.dirname(os.path.abspath(__file__))

# every mechanism of the source at a small size: 2 query heads a kv
# head, an indexer of 3 heads x 8 with one shared key, topk 12 (contexts
# on both sides of it), 16 experts top-3 renormalised, two of four
# shares' worth held here
TINY = dict(vocab_size=97, hidden_size=32, intermediate_size=64,
            moe_intermediate_size=16, num_hidden_layers=3,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            num_experts=16, num_experts_per_tok=3, norm_topk_prob=True,
            rms_norm_eps=1e-6, rope_theta=10000.0,
            sa_config={"indexer_head_dim": 8, "indexer_num_heads": 3,
                       "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                       "q_chunk_size": 512, "topk": 12},
            ep_size=2, ep_rank=1)


def tiny_keye(seed=0, **over):
    paddle.seed(seed)
    model = KeyeVL2ForCausalLM(KeyeVL2Config(**dict(TINY, **over)))
    model.eval()
    # norms' vectors off their initial 1 and 0, so that a norm left out,
    # or its bias, shows
    rng = np.random.default_rng(seed + 1)
    for name, p in model.named_parameters():
        if "norm" in name:
            p._value = p._value + jnp.asarray(
                0.1 * rng.standard_normal(p.shape), p._value.dtype)
    return model


@pytest.fixture(autouse=True)
def _small_blocks(monkeypatch):
    monkeypatch.setattr(ref, "QUERY_BLOCK", 8)
    monkeypatch.setattr(ref, "EXPERT_BLOCK", 3)


def test_reference_copies_are_one_file():
    with open(os.path.join(HERE, "ref_keye_vl2.py")) as a, open(os.path.join(
            HERE, "..", "benchmark", "ref_keye_vl2.py")) as b:
        assert a.read() == b.read()


def test_reference_shares_no_code_with_the_program():
    with open(os.path.join(HERE, "ref_keye_vl2.py")) as f:
        src = f.read()
    assert "import paddle" not in src and "from paddle" not in src


@pytest.mark.parametrize("n", [9, 40])
def test_eager_forward_matches_reference(n):
    """Sequences under and over topk 12."""
    model = tiny_keye()
    ids = np.random.default_rng(3).integers(0, 97, size=n)
    with paddle.no_grad():
        got = np.asarray(model(paddle.to_tensor(ids[None]))._value)[0]
    want, margin, sel = ref.keye_logits(ref.keye_weights(model), TINY, ids)
    np.testing.assert_allclose(got, np.asarray(want), atol=2e-5)
    assert np.isfinite(np.asarray(margin)).all()
    # a query that sees no more than topk positions has no selection
    assert np.isinf(np.asarray(sel)[:12]).all()
    assert n <= 12 or np.isfinite(np.asarray(sel)[12:]).all()


def test_leaving_the_selection_out_is_another_model():
    """The control of the cell's check: every visible key attended
    differs from the reference by far more than the tests' tolerance,
    and only where a query sees more than topk positions."""
    model = tiny_keye()
    ids = np.random.default_rng(3).integers(0, 97, size=40)
    w = ref.keye_weights(model)
    want = np.asarray(ref.keye_logits(w, TINY, ids)[0])
    dense = np.asarray(ref.keye_logits(w, TINY, ids, select=False)[0])
    np.testing.assert_allclose(dense[:12], want[:12], atol=2e-5)
    assert np.abs(dense[12:] - want[12:]).max() > 1e-2


def test_eager_forward_in_bfloat16_stays_near_the_reference():
    model = tiny_keye(dtype="bfloat16")
    ids = np.random.default_rng(3).integers(0, 97, size=24)
    with paddle.no_grad():
        got = np.asarray(model(paddle.to_tensor(ids[None]))._value
                         .astype(jnp.float32))[0]
    want = np.asarray(ref.keye_logits(ref.keye_weights(model), TINY, ids)[0])
    assert np.abs(got - want).mean() < 0.02


# -- ops/pallas/sparse.py ------------------------------------------------

def _operands(rng, b, l, *, heads=16, hkv=2, d=128, hi=4, di=64, ps=8,
              mp=8, tie_pages=()):
    bf = jnp.bfloat16
    pages = b * mp + 1
    pt = rng.permutation(np.arange(1, pages))[:b * mp].reshape(b, mp)
    q = jnp.asarray(rng.normal(size=(b, l, heads, d)), bf)
    q_idx = np.zeros((b, l, hi, sp.LANES), np.float32)
    q_idx[..., :di] = rng.normal(size=(b, l, hi, di))
    w_idx = jnp.asarray(rng.normal(size=(b, l, hi)), bf)
    k_pool, v_pool = (jnp.asarray(rng.normal(size=(pages, ps, hkv, d)), bf)
                      for _ in range(2))
    rows = np.zeros((pages, ps, sp.LANES), np.float32)
    rows[..., :di] = rng.normal(size=(pages, ps, di))
    for a, c in tie_pages:        # equal indexer keys: equal scores
        rows[pt.reshape(-1)[a]] = rows[pt.reshape(-1)[c]]
    return (q, jnp.asarray(q_idx, bf), w_idx, k_pool, v_pool,
            jnp.asarray(rows, bf), jnp.asarray(pt, jnp.int32))


CASES = {
    # a chunk whose selection spans its own new keys, a decoding row
    # over 38 keys, a dead row, a chunk that sees fewer keys than topk
    "mixed": ([20, 37, 0, 3], [16, 1, 0, 11]),
    # decoding rows only, contexts on both sides of topk
    "decode": ([5, 40, 63, 11], [1, 1, 1, 1]),
    # full chunks from position 0 and across page and key-block edges
    "chunks": ([0, 31, 48, 16], [16, 16, 16, 9]),
}


@pytest.fixture
def interpret(monkeypatch):
    for mod in (sp, pa):
        monkeypatch.setattr(mod, "_INTERPRET", True)


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("blocks", [(32, 16), (64, 64)],
                         ids=["key-blocks-32-16", "one-key-block"])
def test_kernels_match_their_jnp_forms(case, blocks, interpret, monkeypatch):
    """Each of the three kernels in interpret mode against its jnp form
    on the same operands, ties between indexer keys included, and the
    selection against a stable sort."""
    monkeypatch.setattr(sp, "INDEX_K_BLOCK", blocks[0])
    monkeypatch.setattr(pa, "K_BLOCK", blocks[1])
    pos, q_len = (jnp.asarray(a, jnp.int32) for a in CASES[case])
    topk = 12
    q, q_idx, w_idx, k_pool, v_pool, rows, pt = _operands(
        np.random.default_rng(7), 4, 16, tie_pages=[(1, 2), (9, 10)])
    keys = sp.sparse_index(q_idx, w_idx, rows, pt, pos, q_len)
    want = sp.index_reference(q_idx, w_idx, sp._view(rows, pt))
    kb = keys.shape[3]
    got_f, want_f = (np.asarray(sp.ordered_key(a))
                     for a in (keys, sp.blocked(want, kb)))
    for b in range(4):
        n, p = int(q_len[b]), int(pos[b])
        if n:
            live = (slice(b, b + 1), slice(0, (p + n - 1) // kb + 1),
                    slice(0, 8 if n <= 8 else 16))
            np.testing.assert_allclose(got_f[live], want_f[live], atol=2e-5)
    tau, tie = sp.sparse_select(sp.blocked(want, kb), pos, q_len, topk=topk)
    rtau, rtie = sp.select_reference(want, pos, q_len, topk=topk)
    flat = np.asarray(want)
    for b in range(4):
        for t in range(int(q_len[b])):
            seen = int(pos[b]) + t + 1
            k = flat[b, t, :seen]
            at = np.arange(seen)
            mine, theirs = (
                (k > np.asarray(x)[b, t, 0]) | (
                    (k == np.asarray(x)[b, t, 0])
                    & (at <= np.asarray(y)[b, t, 0]))
                for x, y in ((tau, tie), (rtau, rtie)))
            order = np.argsort(-k.astype(np.int64), kind="stable")[:topk]
            assert set(np.nonzero(mine)[0]) == set(order), (b, t)
            assert (mine == theirs).all(), (b, t)
    out = sp.sparse_walk(q, k_pool, v_pool, pt, pos, q_len,
                         sp.blocked(want, kb), rtau, rtie)
    dense = sp.walk_reference(q, sp._view(k_pool, pt), sp._view(v_pool, pt),
                              pos, q_len, want, rtau, rtie)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(dense, np.float32), atol=2e-2)
    whole = sp.sparse_attend(q, q_idx, w_idx, k_pool, v_pool, rows, pt, pos,
                             q_len, topk=topk)
    np.testing.assert_allclose(np.asarray(whole, np.float32),
                               np.asarray(dense, np.float32), atol=2e-2)
    dead = np.asarray(q_len) == 0
    assert not np.asarray(whole, np.float32)[dead].any()


def test_ties_go_to_the_lower_position(interpret, monkeypatch):
    """All indexer keys equal: every score ties, and a query keeps the
    topk LOWEST positions it sees."""
    monkeypatch.setattr(sp, "INDEX_K_BLOCK", 32)
    keys = jnp.zeros((1, 2, 8, 32), jnp.int32) + 5
    pos, q_len = jnp.asarray([40], jnp.int32), jnp.asarray([3], jnp.int32)
    tau, tie = sp.sparse_select(keys, pos, q_len, topk=12)
    assert np.asarray(tau)[0, :3, 0].tolist() == [5, 5, 5]
    assert np.asarray(tie)[0, :3, 0].tolist() == [11, 11, 11]
    rtau, rtie = sp.select_reference(jnp.zeros((1, 8, 64), jnp.int32) + 5,
                                     pos, q_len, topk=12)
    assert np.asarray(rtie)[0, :3, 0].tolist() == [11, 11, 11]


def test_ordered_key_orders_as_the_floats_do():
    x = jnp.asarray([-np.inf, -3.5, -1e-30, 0.0, 1e-30, 2.0, np.inf],
                    jnp.float32)
    k = np.asarray(sp.ordered_key(x))
    assert (np.diff(k) > 0).all()
    np.testing.assert_array_equal(np.asarray(sp.ordered_key(jnp.asarray(k))),
                                  np.asarray(x))


def test_off_tpu_the_jnp_forms_serve(monkeypatch):
    """Not interpret mode and no TPU: `sparse_attend` is the three jnp
    forms over gathered views."""
    for name in ("sparse_index", "sparse_select", "sparse_walk"):
        monkeypatch.setattr(sp, name, None)
    pos, q_len = (jnp.asarray(a, jnp.int32) for a in CASES["mixed"])
    q, q_idx, w_idx, k_pool, v_pool, rows, pt = _operands(
        np.random.default_rng(7), 4, 16)
    out = sp.sparse_attend(q, q_idx, w_idx, k_pool, v_pool, rows, pt, pos,
                           q_len, topk=12)
    assert out.shape == q.shape and np.isfinite(
        np.asarray(out, np.float32)).all()


def test_sparse_work_counts():
    pos, q_len = np.array([20, 37, 0, 3]), np.array([16, 1, 0, 11])
    visible, selected, floor, context, rows = sp.count_sparse_work(
        pos, q_len, 12)
    brute = [(p + 1 + i) for p, n in zip(pos, q_len) for i in range(n)]
    assert visible == sum(brute)
    assert selected == sum(min(v, 12) for v in brute)
    assert context == 36 + 38 + 14 and rows == 28
    assert floor == min(36, 16 * 12) + min(38, 12) + min(14, 11 * 12)


def test_work_counts_for_the_roofline():
    cfg = dict(num_attention_heads=32, num_key_value_heads=4, head_dim=128,
               sa_config={"indexer_head_dim": 64, "indexer_num_heads": 16})
    assert ref.sparse_step_flops(cfg, selected=10) == 10 * 32 * 256 * 2
    assert ref.sparse_step_flops(cfg, scored=10) == 10 * 16 * 64 * 2
    assert ref.sparse_step_bytes(cfg, floor_keys=3) == 3 * 2048
    assert ref.sparse_step_bytes(cfg, context_keys=3) == 3 * 128


# -- the share, the routing, the judge, the configuration ----------------

def test_share_parts_add_up_to_the_uncut_layer():
    """The routed parts that the ep_size shares compute add up to the
    routed part of the uncut layer; the attention, which every chip
    computes alike, is counted once."""
    uncut = tiny_keye(ep_size=1, ep_rank=0)
    w_all = ref.keye_weights(uncut)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(24, 32)),
                    jnp.float32)
    cfg1 = dict(TINY, ep_size=1, ep_rank=0)
    lw = ref.layer_weights(w_all, 1)
    whole, _ = ref.sparse_ffn(x, lw, cfg1, residual=False)
    parts = 0.0
    for rank in range(4):
        held = dict(lw, **{f"mlp.experts_{n}": lw[f"mlp.experts_{n}"][
            rank * 4:rank * 4 + 4] for n in ("gate", "up", "down")})
        part, _ = ref.sparse_ffn(x, held, dict(TINY, ep_size=4, ep_rank=rank),
                                 residual=False)
        parts = parts + part
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               atol=1e-5)
    # and the program's share is the reference's
    for rank in range(2):
        model = tiny_keye(ep_size=2, ep_rank=rank)
        ids = np.random.default_rng(3).integers(0, 97, size=20)
        with paddle.no_grad():
            got = np.asarray(model(paddle.to_tensor(ids[None]))._value)[0]
        want = ref.keye_logits(ref.keye_weights(model),
                               dict(TINY, ep_rank=rank), ids)[0]
        np.testing.assert_allclose(got, np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("width", [768, 640, 384])
def test_expert_kernel_covers_a_width_its_block_does_not_divide(
        width, monkeypatch):
    """The source's experts are 768 wide, the kernel's block 512: the
    block is the widest of whole lanes that DIVIDES the width (384), not
    512 with the last 256 columns left out (my chip run, PR 36). The
    kernel in interpret mode against `jax.lax.ragged_dot`."""
    rng = np.random.default_rng(1)
    t, h, n_exp, held, k = 40, 128, 8, 4, 3
    x = jnp.asarray(rng.normal(size=(t, h)), jnp.float32)
    wr = jnp.asarray(rng.normal(size=(h, n_exp)), jnp.float32)
    wg, wu = (jnp.asarray(rng.normal(size=(held, h, width)) * 0.1,
                          jnp.float32) for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(held, width, h)) * 0.1, jnp.float32)
    valid = jnp.ones((t,), bool)
    kw = dict(top_k=k, scale=1.0, norm_topk=True, first=0)
    monkeypatch.setattr(moe, "_INTERPRET", False)
    want, _ = moe.routed_experts(x, valid, wr, wg, wu, wd, **kw)
    monkeypatch.setattr(moe, "_INTERPRET", True)
    got, _ = moe.routed_experts(x, valid, wr, wg, wu, wd, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)
    if width > 600:
        with pytest.raises(ValueError, match="multiple of 128"):
            moe.routed_experts(x, valid, wr, wg[..., :600], wu[..., :600],
                               wd[:, :600], **kw)


def test_judge_choices_and_passes():
    lg = np.zeros((4, 5), np.float32)
    lg[np.arange(4), [1, 2, 3, 4]] = 1.0
    reference = [(lg, np.asarray([0.5, 0.001, 0.5, 0.5], np.float32),
                  np.asarray([np.inf, 0.2, 0.01, 0.3], np.float32))]
    got = ref.judge_choices(reference, [[1, 0, 3, 4]], tie_margin=0.05)
    assert got["tokens"] == 4 and got["match"] == 0.75
    assert got["tie_gap"] == 1.0 and got["gap"] == 0.0
    assert got["mean_gap"] == 0.25
    assert got["min_sel_margin"] == pytest.approx(0.01)
    assert ref.passes(got, {"mean_gap": 0.3, "min_match": 0.7})
    assert not ref.passes(got, {"mean_gap": 0.2, "min_match": 0.7})
    assert not ref.passes(got, {"mean_gap": 0.3, "min_match": 0.8})


def test_config_checks():
    KeyeVL2Config(model_type="KeyeVL2", num_local_experts=128,
                  max_window_layers=48, sliding_window=None,
                  rope_scaling={"mrope_section": [16, 24, 24],
                                "rope_type": "default", "type": "default"})
    cfg = KeyeVL2Config(**TINY)
    assert cfg.experts_here == 8 and cfg.index_row == 128 and cfg.topk == 12
    for bad in (dict(mlp_only_layers=[0]), dict(decoder_sparse_step=2),
                dict(hidden_act="gelu"), dict(attention_bias=True),
                dict(tie_word_embeddings=True), dict(use_sliding_window=True),
                dict(rope_scaling={"rope_type": "yarn"}),
                dict(sa_config=dict(TINY["sa_config"],
                                    indexer_num_kv_heads=2))):
        with pytest.raises(ValueError, match="not built"):
            KeyeVL2Config(**dict(TINY, **bad))
    with pytest.raises(ValueError, match="ep_size"):
        KeyeVL2Config(**dict(TINY, ep_size=3))
    model = tiny_keye()
    assert model._decode_cache_spec() == (3, 2, 16, (None,) * 3, "sparse",
                                          (128, 12))
