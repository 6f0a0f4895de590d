"""Ragged paged-attention decode kernel (ops/pallas/paged_attention).

Contracts:
- kernel (interpret mode on CPU) matches the pure-JAX reference across
  page sizes, GQA ratios, partial tail pages, trash-page rows and user
  attention masks;
- through `update_and_attend`, the kernel impl is BIT-IDENTICAL to the
  gather impl on CPU (the reference mirrors the gather path's math by
  construction), and a full ServingEngine run emits identical greedy
  tokens under both `PADDLE_TPU_PAGED_ATTN` settings;
- the dense decode GQA path (`gqa_decode_attend`) is bit-exact against
  the old repeat_interleave + SDPA materialization it replaced;
- a user attn_mask sized for the dense max_len against a paged cache
  raises a clear page-geometry error, not a shape crash.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM
from paddle_tpu.nlp.generation import (DecodeCache, init_decode_caches,
                                       resolve_paged_attn_impl,
                                       update_and_attend)
from paddle_tpu.nn import functional as F
from paddle_tpu.ops import manipulation
from paddle_tpu.ops._helpers import apply_op
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.serving import SamplingParams, ServingEngine


def build_paged(rng, batch, max_pages, page_size, n_kv, head_dim,
                pos=None):
    """Random pools + per-row page tables whose live prefix covers
    pos[b]+1 positions; everything past it (and whole free rows) points
    at the trash page 0."""
    n_pages = batch * max_pages + 1
    kp = rng.randn(n_pages, page_size, n_kv, head_dim).astype(np.float32)
    vp = rng.randn(n_pages, page_size, n_kv, head_dim).astype(np.float32)
    if pos is None:
        pos = rng.randint(0, max_pages * page_size, size=batch)
    pos = np.asarray(pos, np.int32)
    pt = np.zeros((batch, max_pages), np.int32)
    page = 1
    for b in range(batch):
        for i in range(pos[b] // page_size + 1):
            pt[b, i] = page
            page += 1
    return kp, vp, pt, pos


class TestKernelVsReference:
    """The Pallas kernel (interpret mode) against the pure-JAX
    reference — the reference itself is pinned to the gather path by
    TestKernelVsGatherImpl below."""

    @pytest.fixture(autouse=True)
    def _interpret(self, monkeypatch):
        monkeypatch.setattr(pa, "_INTERPRET", True)

    @pytest.mark.parametrize("page_size", [8, 16])
    @pytest.mark.parametrize("rep", [1, 4])
    def test_matches_reference(self, page_size, rep):
        rng = np.random.RandomState(page_size * 10 + rep)
        batch, mp, hkv, d = 4, 5, 2, 16
        h = hkv * rep
        # partial tail page, exact page boundary, single token, full
        pos = np.array([3, page_size - 1, 2 * page_size + 5,
                        mp * page_size - 1], np.int32)
        kp, vp, pt, pos = build_paged(rng, batch, mp, page_size, hkv, d,
                                      pos)
        q = jnp.asarray(rng.randn(batch, 1, h, d).astype(np.float32))
        ref = pa.paged_attention_reference(
            q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(pt),
            jnp.asarray(pos))
        out = pa.paged_decode_attention(          # _INTERPRET -> kernel
            q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(pt),
            jnp.asarray(pos))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-6)

    def test_user_mask_composes_in_kernel(self):
        rng = np.random.RandomState(3)
        batch, mp, page_size, hkv, rep, d = 3, 4, 8, 2, 2, 16
        h = hkv * rep
        kp, vp, pt, pos = build_paged(rng, batch, mp, page_size, hkv, d,
                                      pos=[5, 9, 20])
        q = jnp.asarray(rng.randn(batch, 1, h, d).astype(np.float32))
        mask4 = rng.randn(batch, h, 1, mp * page_size).astype(np.float32)
        args = (q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(pt),
                jnp.asarray(pos))
        madd = pa._mask_to_additive(jnp.asarray(mask4), batch, h,
                                    mp * page_size)
        ref = pa.paged_attention_reference(*args, madd)
        out = pa.paged_decode_attention(*args, jnp.asarray(mask4))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-6)
        # and the mask actually bites: masking everything but position
        # 0 reduces every row to attending a single key
        hard = np.zeros((batch, h, 1, mp * page_size), np.float32)
        hard[:, :, :, 1:] = -1e30
        only0 = pa.paged_decode_attention(*args, jnp.asarray(hard))
        assert not np.allclose(np.asarray(only0), np.asarray(out))

    def test_trash_rows_are_isolated_and_finite(self):
        """A free slot (all-trash page table, pos 0) yields finite
        garbage, and foreign pages never leak into other rows."""
        rng = np.random.RandomState(4)
        batch, mp, page_size, hkv, d = 3, 4, 8, 2, 16
        kp, vp, pt, pos = build_paged(rng, batch, mp, page_size, hkv, d,
                                      pos=[page_size + 2, 0, 5])
        pt[1, :] = 0                                   # trash row
        q = jnp.asarray(rng.randn(batch, 1, hkv, d).astype(np.float32))
        run = lambda pool: np.asarray(pa.paged_decode_attention(
            q, jnp.asarray(pool), jnp.asarray(vp), jnp.asarray(pt),
            jnp.asarray(pos)))
        base = run(kp)
        assert np.isfinite(base).all()
        poisoned = kp.copy()
        poisoned[pt[2, 0]] = 1e6                       # row 2's page
        got = run(poisoned)
        np.testing.assert_array_equal(base[0], got[0])
        np.testing.assert_array_equal(base[1], got[1])
        assert not np.array_equal(base[2], got[2])


def build_ragged(rng, q_len, max_pages, page_size, n_kv, head_dim,
                 pos=None):
    """Random pools + page tables whose live prefix covers each row's
    pos[b] + q_len[b] positions (the chunk being written included);
    everything past it points at the trash page 0."""
    q_len = np.asarray(q_len, np.int32)
    batch = q_len.size
    if pos is None:
        pos = rng.randint(0, max_pages * page_size // 2, size=batch)
    pos = np.asarray(pos, np.int32)
    n_pages = batch * max_pages + 1
    kp = rng.randn(n_pages, page_size, n_kv, head_dim).astype(np.float32)
    vp = rng.randn(n_pages, page_size, n_kv, head_dim).astype(np.float32)
    pt = np.zeros((batch, max_pages), np.int32)
    page = 1
    for b in range(batch):
        live = -(-(int(pos[b]) + max(int(q_len[b]), 1)) // page_size)
        for i in range(min(live, max_pages)):
            pt[b, i] = page
            page += 1
    return kp, vp, pt, pos, q_len


class TestRaggedKernelVsReference:
    """The RAGGED kernel (per-row q_len, interpret mode) against the
    pure-JAX ragged reference and a dense SDPA oracle: mixed batches of
    decode rows (q_len 1) and mid-prefill rows (q_len up to
    page_size + 1), partial tail pages, a chunk spanning a page
    boundary, trash-page rows and user masks on l > 1 rows."""

    @pytest.fixture(autouse=True)
    def _interpret(self, monkeypatch):
        monkeypatch.setattr(pa, "_INTERPRET", True)

    def _dense_oracle(self, q, kp, vp, pt, pos, q_len, mask=None):
        """Row-by-row repeat_interleave + softmax over the gathered
        dense view under the ragged causal window."""
        b, lq, h, d = q.shape
        ps, hkv = kp.shape[1], kp.shape[2]
        mp = pt.shape[1]
        lmax = mp * ps
        rep = h // hkv
        out = np.zeros((b, lq, h, d), np.float32)
        for bi in range(b):
            kf = kp[pt[bi]].reshape(lmax, hkv, d)
            vf = vp[pt[bi]].reshape(lmax, hkv, d)
            for i in range(int(q_len[bi])):
                for hh in range(h):
                    g = hh // rep
                    s = (q[bi, i, hh] @ kf[:, g].T) / np.sqrt(d)
                    s = s.astype(np.float64)
                    if mask is not None:
                        s += mask[bi, hh, i]
                    s[np.arange(lmax) > int(pos[bi]) + i] = -np.inf
                    a = np.exp(s - s.max())
                    a /= a.sum()
                    out[bi, i, hh] = a @ vf[:, g]
        return out

    @pytest.mark.parametrize("page_size", [8, 16])
    @pytest.mark.parametrize("rep", [1, 4])
    def test_mixed_qlen_matches_reference_and_oracle(self, page_size,
                                                     rep):
        rng = np.random.RandomState(page_size * 10 + rep)
        hkv, d, mp = 2, 16, 5
        h = hkv * rep
        # decode row, small chunk, full-page chunk, page_size+1 chunk
        q_len = np.array([1, 3, page_size, page_size + 1], np.int32)
        # pos mixes: fresh row, partial tail page, chunk STARTING
        # mid-page so the q_len=page_size+1 row spans a page boundary
        pos = np.array([7, page_size - 2, 0, page_size // 2], np.int32)
        kp, vp, pt, pos, q_len = build_ragged(
            rng, q_len, mp, page_size, hkv, d, pos)
        lq = int(q_len.max())
        q = rng.randn(len(q_len), lq, h, d).astype(np.float32)
        args = (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                jnp.asarray(pt), jnp.asarray(pos), jnp.asarray(q_len))
        ref = np.asarray(pa.ragged_attention_reference(*args))
        out = np.asarray(pa.ragged_paged_attention(*args))  # kernel
        oracle = self._dense_oracle(q, kp, vp, pt, pos, q_len)
        for b in range(len(q_len)):
            ql = int(q_len[b])
            np.testing.assert_allclose(out[b, :ql], ref[b, :ql],
                                       rtol=2e-5, atol=2e-6)
            np.testing.assert_allclose(out[b, :ql], oracle[b, :ql],
                                       rtol=1e-4, atol=1e-5)
        assert np.isfinite(out).all()   # dead queries: finite garbage

    def test_trash_rows_dead_rows_and_isolation(self):
        """q_len == 0 rows and all-trash page tables yield finite
        garbage; other rows' pages never leak across rows."""
        rng = np.random.RandomState(5)
        page_size, mp, hkv, d = 8, 4, 2, 16
        q_len = np.array([4, 0, 6], np.int32)
        kp, vp, pt, pos, q_len = build_ragged(
            rng, q_len, mp, page_size, hkv, d, pos=[3, 0, 9])
        pt[1, :] = 0                                  # trash row
        lq = int(q_len.max())
        q = rng.randn(3, lq, hkv, d).astype(np.float32)
        run = lambda pool: np.asarray(pa.ragged_paged_attention(
            jnp.asarray(q), jnp.asarray(pool), jnp.asarray(vp),
            jnp.asarray(pt), jnp.asarray(pos), jnp.asarray(q_len)))
        base = run(kp)
        assert np.isfinite(base).all()
        poisoned = kp.copy()
        poisoned[pt[2, 0]] = 1e6                      # row 2's page
        got = run(poisoned)
        np.testing.assert_array_equal(base[0], got[0])
        assert not np.array_equal(base[2, :6], got[2, :6])

    def test_user_mask_composes_on_multi_token_rows(self):
        """A per-head additive user mask composes with the ragged
        causal window in-kernel on l > 1 rows."""
        rng = np.random.RandomState(6)
        page_size, mp, hkv, rep, d = 8, 4, 2, 2, 16
        h = hkv * rep
        q_len = np.array([1, 5, page_size + 1], np.int32)
        kp, vp, pt, pos, q_len = build_ragged(
            rng, q_len, mp, page_size, hkv, d, pos=[2, 6, 3])
        lq = int(q_len.max())
        q = rng.randn(3, lq, h, d).astype(np.float32)
        mask = rng.randn(3, h, lq, mp * page_size).astype(np.float32)
        args = (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                jnp.asarray(pt), jnp.asarray(pos), jnp.asarray(q_len))
        ref = np.asarray(pa.ragged_attention_reference(
            *args, jnp.asarray(mask)))
        out = np.asarray(pa.ragged_paged_attention(
            *args, jnp.asarray(mask)))
        oracle = self._dense_oracle(q, kp, vp, pt, pos, q_len, mask)
        for b in range(3):
            ql = int(q_len[b])
            np.testing.assert_allclose(out[b, :ql], ref[b, :ql],
                                       rtol=2e-5, atol=2e-6)
            np.testing.assert_allclose(out[b, :ql], oracle[b, :ql],
                                       rtol=1e-4, atol=1e-5)
        # and the mask bites: a hard mask changes the output
        hard = np.zeros((3, h, lq, mp * page_size), np.float32)
        hard[:, :, :, 1:] = -1e30
        only0 = np.asarray(pa.ragged_paged_attention(
            *args, jnp.asarray(hard)))
        assert not np.allclose(only0, out)

    def test_l1_rows_bit_identical_to_single_token_reference(self):
        """An all-decode ragged batch (every q_len 1) on the CPU
        reference is BIT-identical to paged_attention_reference — the
        contract that keeps unified-step decode rows on the proven
        gather-path math."""
        rng = np.random.RandomState(7)
        page_size, mp, hkv, d = 8, 4, 2, 16
        q_len = np.ones(3, np.int32)
        kp, vp, pt, pos, q_len = build_ragged(
            rng, q_len, mp, page_size, hkv, d, pos=[3, 9, 17])
        q = rng.randn(3, 1, hkv * 2, d).astype(np.float32)
        args = (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                jnp.asarray(pt), jnp.asarray(pos))
        ragged = pa.ragged_attention_reference(*args,
                                               jnp.asarray(q_len))
        single = pa.paged_attention_reference(*args)
        np.testing.assert_array_equal(np.asarray(ragged),
                                      np.asarray(single))


class TestKernelVsGatherImpl:
    """update_and_attend dispatch: the kernel impl (pure-JAX reference
    on CPU) is bit-identical to the gather impl, with and without a
    user mask."""

    def _caches(self, rng, batch, mp, page_size, hkv, d, pos):
        kp, vp, pt, pos = build_paged(rng, batch, mp, page_size, hkv, d,
                                      pos)
        def mk(impl):
            return DecodeCache(
                Tensor(jnp.asarray(kp)), Tensor(jnp.asarray(vp)),
                Tensor(jnp.asarray(pos)),
                page_table=Tensor(jnp.asarray(pt)), attn_impl=impl)
        return mk

    @pytest.mark.parametrize("page_size,rep", [(8, 1), (16, 4)])
    def test_bit_identical_no_mask(self, page_size, rep):
        rng = np.random.RandomState(7)
        batch, mp, hkv, d = 3, 4, 2, 16
        h = hkv * rep
        mk = self._caches(rng, batch, mp, page_size, hkv, d,
                          [3, page_size, 2 * page_size + 1])
        q = Tensor(jnp.asarray(rng.randn(batch, 1, h, d)
                               .astype(np.float32)))
        kn = Tensor(jnp.asarray(rng.randn(batch, 1, hkv, d)
                                .astype(np.float32)))
        vn = Tensor(jnp.asarray(rng.randn(batch, 1, hkv, d)
                                .astype(np.float32)))
        outs = {}
        for impl in ("kernel", "gather"):
            o, nc = update_and_attend(q, kn, vn, mk(impl))
            assert nc.attn_impl == impl          # impl rides the cache
            outs[impl] = o.numpy()
        np.testing.assert_array_equal(outs["kernel"], outs["gather"])

    def test_bit_identical_with_user_mask(self):
        rng = np.random.RandomState(8)
        batch, mp, page_size, hkv, rep, d = 3, 4, 8, 2, 2, 16
        h = hkv * rep
        mk = self._caches(rng, batch, mp, page_size, hkv, d, [5, 9, 20])
        q = Tensor(jnp.asarray(rng.randn(batch, 1, h, d)
                               .astype(np.float32)))
        kn = Tensor(jnp.asarray(rng.randn(batch, 1, hkv, d)
                                .astype(np.float32)))
        vn = Tensor(jnp.asarray(rng.randn(batch, 1, hkv, d)
                                .astype(np.float32)))
        m = Tensor(jnp.asarray(
            rng.randn(batch, h, 1, mp * page_size).astype(np.float32)))
        outs = {}
        for impl in ("kernel", "gather"):
            o, _ = update_and_attend(q, kn, vn, mk(impl), attn_mask=m)
            outs[impl] = o.numpy()
        np.testing.assert_array_equal(outs["kernel"], outs["gather"])

    def test_dense_mask_width_raises_page_geometry_error(self):
        """Bugfix: a mask whose last dim was sized for the dense
        max_len (not the page-aligned logical view) gets a clear error
        naming the page geometry."""
        rng = np.random.RandomState(9)
        page_size, mp, hkv, d = 16, 4, 2, 16   # logical view = 64
        mk = self._caches(rng, 2, mp, page_size, hkv, d, [3, 7])
        q = Tensor(jnp.asarray(rng.randn(2, 1, hkv, d)
                               .astype(np.float32)))
        kn = vn = Tensor(jnp.asarray(rng.randn(2, 1, hkv, d)
                                     .astype(np.float32)))
        dense_mask = Tensor(jnp.ones((2, 1, 1, 50), jnp.bool_))  # 50!=64
        for impl in ("kernel", "gather"):
            with pytest.raises(ValueError) as ei:
                update_and_attend(q, kn, vn, mk(impl),
                                  attn_mask=dense_mask)
            msg = str(ei.value)
            assert "PAGED" in msg and "page_size" in msg
            assert "page-aligned" in msg

    def test_impl_resolution_env_and_override(self, monkeypatch):
        assert resolve_paged_attn_impl() == "kernel"       # default
        monkeypatch.setenv("PADDLE_TPU_PAGED_ATTN", "gather")
        assert resolve_paged_attn_impl() == "gather"
        assert resolve_paged_attn_impl("kernel") == "kernel"  # override
        monkeypatch.setenv("PADDLE_TPU_PAGED_ATTN", "dense")
        with pytest.raises(ValueError):
            resolve_paged_attn_impl()
        with pytest.raises(ValueError):
            ServingEngine(object(), cache_spec=(1, 2, 8),
                          attn_impl="nope")


class TestDenseGQAGrouped:
    def test_grouped_decode_bit_exact_vs_repeat_interleave(self):
        """The gqa_decode_attend path must reproduce the old
        repeat_interleave + SDPA materialization BIT-EXACTLY (each
        per-group dot keeps the shapes XLA saw before)."""
        rng = np.random.RandomState(11)
        batch, lmax, hkv, rep, d = 3, 24, 2, 4, 8
        h = hkv * rep
        cache = init_decode_caches(1, batch, lmax, hkv, d,
                                   dtype=np.float32)[0]
        qp = Tensor(jnp.asarray(rng.randn(batch, 7, h, d)
                                .astype(np.float32)))
        kvp = Tensor(jnp.asarray(rng.randn(batch, 7, hkv, d)
                                 .astype(np.float32)))
        _, cache = update_and_attend(qp, kvp, kvp, cache)
        q = Tensor(jnp.asarray(rng.randn(batch, 1, h, d)
                               .astype(np.float32)))
        kn = Tensor(jnp.asarray(rng.randn(batch, 1, hkv, d)
                                .astype(np.float32)))
        vn = Tensor(jnp.asarray(rng.randn(batch, 1, hkv, d)
                                .astype(np.float32)))
        out_new, _ = update_and_attend(q, kn, vn, cache)

        # the OLD path, reconstructed: scatter + window mask + H-fold
        # repeat of the cache + dense SDPA
        k_buf = apply_op("kv_cache_update", cache.k, kn, cache.pos)
        v_buf = apply_op("kv_cache_update", cache.v, vn, cache.pos)
        mask = apply_op("window_causal_mask", cache.pos,
                        attrs=dict(l=1, lmax=lmax))
        kf = manipulation.repeat_interleave(k_buf, rep, axis=2)
        vf = manipulation.repeat_interleave(v_buf, rep, axis=2)
        out_old = F.scaled_dot_product_attention(
            q, kf, vf, attn_mask=mask, dropout_p=0.0, is_causal=False,
            training=False)
        np.testing.assert_array_equal(out_new.numpy(), out_old.numpy())

    def test_grouped_decode_per_head_mask(self):
        """Per-head additive masks slice correctly through the grouped
        unroll (head h = g*rep + r)."""
        rng = np.random.RandomState(12)
        batch, lmax, hkv, rep, d = 2, 16, 2, 2, 8
        h = hkv * rep
        cache = init_decode_caches(1, batch, lmax, hkv, d,
                                   dtype=np.float32)[0]
        qp = Tensor(jnp.asarray(rng.randn(batch, 5, h, d)
                                .astype(np.float32)))
        kvp = Tensor(jnp.asarray(rng.randn(batch, 5, hkv, d)
                                 .astype(np.float32)))
        _, cache = update_and_attend(qp, kvp, kvp, cache)
        q = Tensor(jnp.asarray(rng.randn(batch, 1, h, d)
                               .astype(np.float32)))
        kn = Tensor(jnp.asarray(rng.randn(batch, 1, hkv, d)
                                .astype(np.float32)))
        m = Tensor(jnp.asarray(rng.randn(batch, h, 1, lmax)
                               .astype(np.float32)))
        out_new, _ = update_and_attend(q, kn, kn, cache, attn_mask=m)
        k_buf = apply_op("kv_cache_update", cache.k, kn, cache.pos)
        mask = apply_op("window_causal_mask", cache.pos,
                        attrs=dict(l=1, lmax=lmax))
        mask = apply_op("decode_merge_mask", mask, m)
        kf = manipulation.repeat_interleave(k_buf, rep, axis=2)
        out_old = F.scaled_dot_product_attention(
            q, kf, kf, attn_mask=mask, dropout_p=0.0, is_causal=False,
            training=False)
        np.testing.assert_array_equal(out_new.numpy(), out_old.numpy())


# (pos, q_len) of three rows over 20 pages of 4 positions (7 key blocks
# of 3 pages), 16 query positions (2 q-blocks of 8): what the grid's
# dynamic bounds come to, in live (row, q-block) items and key blocks
DECODE_ONLY = ([37, 20, 0], [1, 1, 1])         # 3 items, 4 key blocks
WITH_A_CHUNK = ([14, 60, 5], [3, 16, 0])       # 1 + 2 items, 7 key blocks
ALL_DEAD = ([13, 20, 0], [0, 0, 0])            # 1 item (dead), 1 key block


class TestDynamicGridBounds:
    """Every walk's grid is as long as the step's rows ask
    (`pa.walk_grid_bounds`), not as long as the step's shape allows:
    plain, under a user mask, on the int8 lane and grouped (both
    phases). Live queries equal the reference, which knows no grid;
    dead queries, whose q-blocks the grid may never write, read zero."""

    B, W, H, HKV, D, PS, MP = 3, 16, 6, 2, 128, 4, 20  # pools read in place

    @pytest.fixture(autouse=True)
    def _interpret(self, monkeypatch):
        monkeypatch.setattr(pa, "_INTERPRET", True)
        # blocks small enough for these rows to have several: key blocks
        # of 3 pages (12 keys: `_grouped`'s shared span ends on a block's
        # edge) and q-blocks of 8 queries (24 rows a kv head of three)
        monkeypatch.setattr(pa, "K_BLOCK", 12)
        monkeypatch.setattr(pa, "_Q_ROWS", 24)

    def _operands(self, pos, q_len, seed=3):
        rng = np.random.default_rng(seed)
        b, mp = self.B, self.MP
        shape = (b * mp + 1, self.PS, self.HKV, self.D)
        pools = [jnp.asarray(rng.normal(size=shape), jnp.float32)
                 for _ in range(2)]
        tab = 1 + np.arange(b * mp).reshape(b, mp)
        q = jnp.asarray(rng.normal(size=(b, self.W, self.H, self.D)),
                        jnp.float32)
        return (rng, q, pools, tab, jnp.asarray(pos, jnp.int32),
                jnp.asarray(q_len, jnp.int32))

    def _check(self, got, want, q_len, exact=False):
        for row in range(self.B):
            n = int(q_len[row])
            if exact:
                np.testing.assert_array_equal(got[row, :n], want[row, :n])
            else:
                np.testing.assert_allclose(got[row, :n], want[row, :n],
                                           atol=2e-6)
            assert not np.asarray(got[row, n:]).any()

    @pytest.mark.parametrize("pos,q_len", [
        ([37, 0, 20], [1, 1, 1]), ([5, 63, 0], [3, 16, 0]),
        ([0, 0, 0], [0, 0, 0]), DECODE_ONLY, WITH_A_CHUNK])
    def test_plain_walk_matches_the_reference(self, pos, q_len):
        _, q, pools, tab, pos, q_len = self._operands(pos, q_len)
        tab = jnp.asarray(tab, jnp.int32)
        want = pa.ragged_attention_reference(q, *pools, tab, pos, q_len)
        got = pa.ragged_paged_attention(q, *pools, tab, pos, q_len)
        self._check(got, want, q_len)

    @pytest.mark.parametrize("pos,q_len",
                             [DECODE_ONLY, WITH_A_CHUNK, ALL_DEAD])
    def test_masked_walk_matches_the_reference(self, pos, q_len):
        rng, q, pools, tab, pos, q_len = self._operands(pos, q_len)
        tab = jnp.asarray(tab, jnp.int32)
        mask = jnp.asarray(rng.normal(
            size=(self.B, self.H, self.W, self.MP * self.PS)), jnp.float32)
        want = pa.ragged_attention_reference(q, *pools, tab, pos, q_len,
                                             mask)
        got = pa.ragged_paged_attention(q, *pools, tab, pos, q_len, mask)
        self._check(got, want, q_len)
        plain = pa.ragged_paged_attention(q, *pools, tab, pos, q_len)
        if int(q_len.max()):
            assert np.abs(np.asarray(got) - np.asarray(plain)).max() > 1e-3

    @pytest.mark.parametrize("pos,q_len",
                             [DECODE_ONLY, WITH_A_CHUNK, ALL_DEAD])
    def test_int8_walk_matches_the_reference(self, pos, q_len):
        rng, q, pools, tab, pos, q_len = self._operands(pos, q_len)
        tab = jnp.asarray(tab, jnp.int32)
        shape = pools[0].shape
        codes = [jnp.asarray(rng.integers(-127, 128, size=shape), jnp.int8)
                 for _ in range(2)]
        scales = [jnp.asarray(np.abs(rng.normal(size=shape[:3])) / 127,
                              jnp.float32) for _ in range(2)]
        want = pa.ragged_attention_reference_q8(q, *codes, *scales, tab,
                                                pos, q_len)
        got = pa.ragged_paged_attention_q8(q, *codes, *scales, tab, pos,
                                           q_len)
        self._check(got, want, q_len)

    def _grouped(self, tab):
        """Rows 0 and 1 share their first 3 pages; row 2 is alone."""
        tab = tab.copy()
        tab[1, :3] = tab[0, :3]
        group = [jnp.asarray(v, jnp.int32) for v in
                 ([0, 0, 1], [0, 2, 0], [3, 0, 0])]   # id, leader, count
        return jnp.asarray(tab, jnp.int32), group

    @pytest.mark.parametrize("pos,q_len",
                             [DECODE_ONLY, WITH_A_CHUNK, ALL_DEAD])
    def test_grouped_walk_matches_the_ungrouped(self, pos, q_len):
        _, q, pools, tab, pos, q_len = self._operands(pos, q_len)
        tab, group = self._grouped(tab)
        want = pa.ragged_attention_reference(q, *pools, tab, pos, q_len)
        plain = pa.ragged_paged_attention(q, *pools, tab, pos, q_len)
        got = pa.ragged_paged_attention_grouped(q, *pools, tab, pos, q_len,
                                                *group)
        self._check(got, want, q_len)
        self._check(got, plain, q_len, exact=True)

    @pytest.mark.parametrize("pos,q_len,bounds", [
        (*DECODE_ONLY, (3, 4, 1)), (*WITH_A_CHUNK, (3, 7, 2)),
        (*ALL_DEAD, (1, 1, 1)), ([0, 79, 3], [16, 16, 16], (6, 7, 2))])
    def test_host_count_is_the_grid_the_wrapper_asks_for(
            self, pos, q_len, bounds, monkeypatch):
        """`count_walk_grid_steps`, which the engine counts a step's
        grid with, against the grids the traced wrapper hands to
        `pallas_call`, for both phases of the grouped walk and for the
        ungrouped one."""
        _, q, pools, tab, pos, q_len = self._operands(pos, q_len)
        tab, group = self._grouped(tab)
        seen = []
        real = pa.pl.pallas_call

        def spy(kernel, **kw):
            seen.append((kw["name"],
                         tuple(int(g) for g in kw["grid_spec"].grid)))
            return real(kernel, **kw)

        monkeypatch.setattr(pa.pl, "pallas_call", spy)
        with jax.disable_jit():           # the bounds as numbers
            pa.ragged_paged_attention_grouped(q, *pools, tab, pos, q_len,
                                              *group)
            pa.ragged_paged_attention(q, *pools, tab, pos, q_len)
        # live items, key blocks of the longest context; phase 1: the
        # one sharing group a bounded q-block, over its span's one block
        n_items, n_kblk, n_qblk = bounds
        walk = (n_items, n_kblk)
        assert seen == [("grouped_phase1", (n_qblk, 1)),
                        ("ragged_walk", walk), ("ragged_walk", walk)]
        assert pa.count_walk_grid_steps(
            np.asarray(pos), np.asarray(q_len), lq=self.W,
            rep=self.H // self.HKV, page_size=self.PS,
            max_pages=self.MP) == (n_items * n_kblk, self.B * 2 * 7)

    def test_engine_counts_the_grid_of_every_step(self, monkeypatch):
        """`walk_grid_steps_total` and `walk_grid_steps_full_total`
        hold, a unified step, what `count_walk_grid_steps` says of the
        step's `pos` and `q_len`; `snapshot()` and `/metrics` carry
        them."""
        from paddle_tpu.serving import engine as engine_mod
        from paddle_tpu.serving.metrics import prometheus_render
        # the counters are the host's: the engine may run the reference
        monkeypatch.setattr(pa, "_INTERPRET", False)
        seen = []

        def spy(pos, q_len, **kw):
            seen.append(pa.count_walk_grid_steps(pos, q_len, **kw))
            assert kw == dict(lq=8, rep=2, page_size=8, max_pages=8)
            return seen[-1]

        monkeypatch.setattr(engine_mod, "count_walk_grid_steps", spy)
        paddle.seed(21)
        model = LlamaForCausalLM(LlamaConfig(
            vocab_size=89, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2,
            intermediate_size=48, max_position_embeddings=128))
        model.eval()
        eng = ServingEngine(model, num_slots=2, max_len=64, page_size=8,
                            chunk_len=8)
        eng.generate([np.arange(1, 20, dtype=np.int64),
                      np.array([26, 5, 35], np.int64)],
                     SamplingParams(max_new_tokens=6))
        snap = eng.metrics.snapshot()
        assert len(seen) == snap["unified_steps"] > 0
        steps, full = (sum(col) for col in zip(*seen))
        assert snap["walk_grid_steps_total"] == steps
        assert snap["walk_grid_steps_full_total"] == full \
            == len(seen) * 2 * 1 * 8
        assert 0 < steps < full
        text = prometheus_render({"r0": snap})
        for name, n in (("walk_grid_steps_total", steps),
                        ("walk_grid_steps_full_total", full)):
            assert f"# TYPE paddle_serving_{name} counter" in text
            assert f'paddle_serving_{name}{{replica="r0"}} {n}' in text


# The boundaries of the walk's blocks (a query block as wide as a chunk
# over key blocks of several pages, `_walk_paged`): rows of 4 slots over
# 24 pages of 8 keys, key blocks of 4 pages (32 keys), 128 query
# positions in q-blocks sized by `_query_blocks` from the heads a kv head
# serves. Each case: (pos, q_len) a row, then what differs from the plain
# float lane.
WIDE = {
    # a chunk beside decoding rows and a dead row in one step
    "chunk_decode_dead": dict(pos=[40, 63, 9, 100], q_len=[128, 1, 0, 1]),
    # contexts whose last key lies one before, on and one after the first
    # key of a key block (a decoding row and a chunk each)
    "ends_before_edge": dict(pos=[30, 62, 0, 0], q_len=[1, 1, 31, 63]),
    "ends_on_edge": dict(pos=[31, 63, 0, 0], q_len=[1, 1, 32, 64]),
    "ends_after_edge": dict(pos=[32, 64, 0, 0], q_len=[1, 1, 33, 65]),
    "q_len_1": dict(pos=[5, 50, 100, 150], q_len=[1, 1, 1, 1]),
    "q_len_2": dict(pos=[5, 50, 100, 150], q_len=[2, 2, 2, 2]),
    "q_len_8": dict(pos=[5, 50, 100, 150], q_len=[8, 8, 8, 1]),
    "q_len_127": dict(pos=[0, 50, 31, 60], q_len=[127, 127, 1, 127]),
    "q_len_128": dict(pos=[0, 50, 31, 64], q_len=[128, 128, 128, 128]),
    # query heads a kv head: q-blocks of 128, 32 and 16 queries
    "rep_1": dict(pos=[40, 63, 9, 100], q_len=[128, 1, 17, 2], rep=1),
    "rep_6": dict(pos=[40, 63, 9, 100], q_len=[128, 1, 33, 2], rep=6),
    "rep_9": dict(pos=[40, 63, 9, 100], q_len=[128, 1, 17, 2], rep=9),
    # rows 0-2 share 5 pages: the span ends inside the second key block
    "shared_span_inside_a_block": dict(
        pos=[40, 63, 47, 100], q_len=[128, 1, 2, 1],
        group=([0, 0, 0, 1], [0, 3, 0, 0], [5, 0, 0, 0])),
    # a window whose lower edge falls inside a key block, and one whose
    # keys lie on three
    "window_edge_inside_a_block": dict(
        pos=[40, 63, 9, 100], q_len=[128, 1, 0, 16], window=24, rep=9),
    "window_spans_three_blocks": dict(
        pos=[40, 75, 9, 100], q_len=[128, 1, 0, 16], window=70, rep=6),
    # the tensor-parallel shard's one local kv head
    "local_hkv_1": dict(pos=[40, 63, 9, 100], q_len=[128, 1, 0, 1], hkv=1,
                        rep=2),
    "int8_pool": dict(pos=[40, 63, 9, 100], q_len=[128, 1, 0, 2],
                      lane="int8"),
    "int8_pool_shared_span": dict(
        pos=[40, 63, 47, 100], q_len=[128, 1, 2, 1], lane="int8",
        group=([0, 0, 0, 1], [0, 3, 0, 0], [5, 0, 0, 0])),
    "fp8_pool": dict(pos=[40, 63, 9, 100], q_len=[128, 1, 0, 2],
                     lane="fp8"),
    # heads narrower than the 128 lanes Mosaic cuts HBM by: K and V come
    # as the rows' gathered views, as the int8 lane's scales always do
    "heads_of_64": dict(pos=[40, 63, 9, 100], q_len=[128, 1, 0, 2], d=64),
    "heads_of_64_shared_span": dict(
        pos=[40, 63, 47, 100], q_len=[128, 1, 2, 1], d=64,
        group=([0, 0, 0, 1], [0, 3, 0, 0], [5, 0, 0, 0])),
}


@pytest.mark.parametrize("case", sorted(WIDE))
def test_wide_blocks_match_the_reference(case, monkeypatch):
    """The walk at its real query blocks, over key blocks of several
    pages, against `ragged_attention_reference`, which knows neither:
    live queries equal it, dead ones read zero."""
    c = {**dict(rep=1, hkv=2, lane="fp", window=None, group=None, d=128),
         **WIDE[case]}
    monkeypatch.setattr(pa, "_INTERPRET", True)
    monkeypatch.setattr(pa, "K_BLOCK", 32)
    rng = np.random.default_rng(sorted(WIDE).index(case))
    b, lq, d, ps, mp = 4, 128, c["d"], 8, 24  # heads of 128: read in place
    hkv, h = c["hkv"], c["hkv"] * c["rep"]
    shape = (b * mp + 1, ps, hkv, d)
    tab = 1 + np.arange(b * mp).reshape(b, mp)
    if c["group"]:
        gid, gld, gcnt = c["group"]
        for row in range(b):
            n = gcnt[gid[row]]
            tab[row, :n] = tab[gld[gid[row]], :n]
    tab = jnp.asarray(tab, jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, lq, h, d)), jnp.float32)
    pos = jnp.asarray(c["pos"], jnp.int32)
    q_len = jnp.asarray(c["q_len"], jnp.int32)
    group = [jnp.asarray(g, jnp.int32) for g in c["group"] or ()]
    if c["lane"] == "int8":
        pools = [jnp.asarray(rng.integers(-127, 128, size=shape), jnp.int8)
                 for _ in range(2)]
        pools += [jnp.asarray(np.abs(rng.normal(size=shape[:3])) / 127,
                              jnp.float32) for _ in range(2)]
        want = pa.ragged_attention_reference_q8(q, *pools, tab, pos, q_len)
        op = (pa.ragged_paged_attention_grouped_q8 if group
              else pa.ragged_paged_attention_q8)
        got = op(q, *pools, tab, pos, q_len, *group)
    else:
        dt = pa.FP8_DTYPE if c["lane"] == "fp8" else jnp.float32
        pools = [jnp.asarray(rng.normal(size=shape), jnp.float32).astype(dt)
                 for _ in range(2)]
        want = pa.ragged_attention_reference(q, *pools, tab, pos, q_len,
                                             None, c["window"])
        if group:
            got = pa.ragged_paged_attention_grouped(q, *pools, tab, pos,
                                                    q_len, *group)
        else:
            got = pa.ragged_paged_attention(q, *pools, tab, pos, q_len,
                                            window=c["window"])
    for row in range(b):
        n = int(q_len[row])
        np.testing.assert_allclose(got[row, :n], want[row, :n], atol=5e-6,
                                   rtol=2e-5)
        assert not np.asarray(got[row, n:]).any()


class TestServingEngineAB:
    """E2E acceptance: identical greedy tokens under both
    PADDLE_TPU_PAGED_ATTN settings, through GQA, chunked prefill,
    partial tail pages and page reuse."""

    def _model(self):
        paddle.seed(21)
        cfg = LlamaConfig(vocab_size=89, hidden_size=32,
                          num_hidden_layers=2, num_attention_heads=4,
                          num_key_value_heads=2, intermediate_size=48,
                          max_position_embeddings=128)
        m = LlamaForCausalLM(cfg)
        m.eval()
        return m

    def test_tokens_identical_across_impls(self, monkeypatch):
        model = self._model()
        prompts = [np.array([3, 14, 15, 9, 2, 6, 5], np.int64),
                   np.array([26, 5, 35], np.int64),
                   np.array([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], np.int64)]
        toks = {}
        for impl, via_env in (("kernel", False), ("gather", True)):
            if via_env:   # the env-var spelling of the switch
                monkeypatch.setenv("PADDLE_TPU_PAGED_ATTN", impl)
                eng = ServingEngine(model, num_slots=2, max_len=64,
                                    page_size=8, chunk_len=8)
            else:
                monkeypatch.delenv("PADDLE_TPU_PAGED_ATTN",
                                   raising=False)
                eng = ServingEngine(model, num_slots=2, max_len=64,
                                    page_size=8, chunk_len=8,
                                    attn_impl=impl)
            assert eng.attn_impl == impl
            assert eng.metrics.attn_impl == impl
            outs = eng.generate(
                prompts, SamplingParams(max_new_tokens=8))
            toks[impl] = [list(o.token_ids) for o in outs]
            snap = eng.metrics.snapshot()
            assert snap["attn_impl"] == impl
            assert snap["decode_step_s"]["count"] > 0
        assert toks["kernel"] == toks["gather"]
        # and both equal the solo compiled-generator oracle
        for p, got in zip(prompts, toks["kernel"]):
            want = model.generate(paddle.to_tensor(p[None]),
                                  max_new_tokens=8).numpy()
            np.testing.assert_array_equal(np.asarray(got),
                                          want[0, p.size:])
