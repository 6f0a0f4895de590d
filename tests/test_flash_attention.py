"""Pallas flash-attention kernel parity (interpret mode on CPU; the
same kernels compile under Mosaic on TPU).

Covers review r2 item 3: additive bias masks, key-padding vector
masks (the BERT path), and in-kernel dropout — forward AND backward —
against a plain-jnp oracle that shares the kernel's position-hash keep
mask (reference semantics: fused_attention_op.cu / fmha_ref.h
softmax-then-dropout)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu  # noqa: F401  (device/x64 init)
import paddle_tpu as paddle
from paddle_tpu.ops.pallas import flash_attention as fa


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    monkeypatch.setattr(fa, "_INTERPRET", True)


def _rand(shape, seed):
    return jnp.asarray(np.random.RandomState(seed)
                       .randn(*shape).astype("float32")) * 0.5


def _keep_full(seeds, BH, Lq, Lk, p):
    thresh = fa._drop_thresh(p)
    qpos = jnp.broadcast_to(jnp.arange(Lq, dtype=jnp.int32)[:, None],
                            (Lq, Lk))
    kpos = jnp.broadcast_to(jnp.arange(Lk, dtype=jnp.int32)[None, :],
                            (Lq, Lk))
    return jnp.stack([fa.dropout_keep(seeds[0], seeds[1], bh,
                                      qpos, kpos, thresh)
                      for bh in range(BH)])


def _oracle(q, k, v, bias=None, kvec=None, causal=False, scale=None,
            dropout_p=0.0, seeds=None):
    """[B, L, H, D] oracle sharing the kernel's keep-mask hash."""
    B, Lq, H, D = q.shape
    Lk = k.shape[1]
    if scale is None:
        scale = 1.0 / np.sqrt(D)
    logits = jnp.einsum("blhd,bmhd->bhlm", q, k).astype(jnp.float32) \
        * scale
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    if kvec is not None:
        logits = logits + kvec.astype(jnp.float32)[:, None, None, :]
    if causal:
        cm = jnp.tril(jnp.ones((Lq, Lk), dtype=bool), Lk - Lq)
        logits = jnp.where(cm, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    if dropout_p > 0.0:
        keep = _keep_full(seeds, B * H, Lq, Lk, dropout_p) \
            .reshape(B, H, Lq, Lk)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
    return jnp.einsum("bhlm,bmhd->blhd", probs.astype(q.dtype), v)


def _check(kern_fn, ref_fn, q, k, v, rtol=2e-3, atol=2e-3):
    out = kern_fn(q, k, v)
    ref = ref_fn(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=rtol, atol=atol)
    w = _rand(out.shape, 99)
    gk = jax.grad(lambda q_, k_, v_: jnp.sum(kern_fn(q_, k_, v_) * w),
                  argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q_, k_, v_: jnp.sum(ref_fn(q_, k_, v_) * w),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b, nm in zip(gk, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=rtol, atol=atol,
                                   err_msg=f"d{nm}")


class TestFlashKernelMasks:
    B, H, L, D = 2, 2, 256, 64

    def _qkv(self, lk=None):
        lk = lk or self.L
        return (_rand((self.B, self.L, self.H, self.D), 0),
                _rand((self.B, lk, self.H, self.D), 1),
                _rand((self.B, lk, self.H, self.D), 2))

    @pytest.mark.parametrize("causal", [False, True])
    def test_plain(self, causal):
        q, k, v = self._qkv()
        _check(lambda q_, k_, v_: fa.flash_attention_blhd(
                   q_, k_, v_, causal=causal),
               lambda q_, k_, v_: _oracle(q_, k_, v_, causal=causal),
               q, k, v)

    @pytest.mark.parametrize("causal", [False, True])
    def test_key_padding_vector(self, causal):
        """The BERT shape: additive [B, Lk] from a [B,1,1,Lk] mask."""
        q, k, v = self._qkv()
        pad = np.zeros((self.B, self.L), "float32")
        pad[0, 200:] = -1e30
        pad[1, 150:] = -1e30
        kvec = jnp.asarray(pad)
        _check(lambda q_, k_, v_: fa.flash_attention_blhd(
                   q_, k_, v_, kvec=kvec, causal=causal),
               lambda q_, k_, v_: _oracle(q_, k_, v_, kvec=kvec,
                                          causal=causal),
               q, k, v)

    @pytest.mark.parametrize("bshape", [(2, 2), (1, 1), (2, 1)])
    def test_full_bias(self, bshape):
        q, k, v = self._qkv()
        bias = _rand((bshape[0], bshape[1], self.L, self.L), 5)
        _check(lambda q_, k_, v_: fa.flash_attention_blhd(
                   q_, k_, v_, bias=bias),
               lambda q_, k_, v_: _oracle(q_, k_, v_, bias=bias),
               q, k, v)

    def test_ragged_length_with_kvec(self):
        q, k, v = self._qkv(lk=200)
        q = q[:, :200]
        pad = np.zeros((self.B, 200), "float32")
        pad[:, 180:] = -1e30
        kvec = jnp.asarray(pad)
        _check(lambda q_, k_, v_: fa.flash_attention_blhd(
                   q_, k_, v_, kvec=kvec),
               lambda q_, k_, v_: _oracle(q_, k_, v_, kvec=kvec),
               q, k, v)

    @pytest.mark.parametrize("causal", [False, True])
    def test_dropout(self, causal):
        """Kernel dropout == oracle with the SAME hash keep-mask, fwd
        and bwd (the position-keyed hash makes the mask reproducible
        across the three kernels)."""
        q, k, v = self._qkv()
        seeds = jnp.asarray([12345, 67890], jnp.int32)
        p = 0.3
        _check(lambda q_, k_, v_: fa.flash_attention_blhd(
                   q_, k_, v_, seeds=seeds, causal=causal, dropout_p=p),
               lambda q_, k_, v_: _oracle(q_, k_, v_, causal=causal,
                                          dropout_p=p, seeds=seeds),
               q, k, v)

    def test_dropout_rate_and_determinism(self):
        keep = _keep_full(jnp.asarray([1, 2], jnp.int32), 4, 256, 256,
                          0.3)
        rate = float(jnp.mean(keep.astype(jnp.float32)))
        assert abs(rate - 0.7) < 0.01
        keep2 = _keep_full(jnp.asarray([1, 2], jnp.int32), 4, 256, 256,
                           0.3)
        assert bool(jnp.all(keep == keep2))
        keep3 = _keep_full(jnp.asarray([3, 2], jnp.int32), 4, 256, 256,
                           0.3)
        assert not bool(jnp.all(keep == keep3))

    def test_dropout_with_kvec_mask(self):
        q, k, v = self._qkv()
        pad = np.zeros((self.B, self.L), "float32")
        pad[:, 220:] = -1e30
        kvec = jnp.asarray(pad)
        seeds = jnp.asarray([7, 11], jnp.int32)
        p = 0.2
        _check(lambda q_, k_, v_: fa.flash_attention_blhd(
                   q_, k_, v_, kvec=kvec, seeds=seeds, dropout_p=p),
               lambda q_, k_, v_: _oracle(q_, k_, v_, kvec=kvec,
                                          dropout_p=p, seeds=seeds),
               q, k, v)


class TestSdpaRouting:
    def test_mask_mapping(self):
        from paddle_tpu.nn.functional.attention import (
            _mask_to_kernel_operands)
        B, H, Lq, Lk = 4, 8, 128, 128
        pad = jnp.ones((B, 1, 1, Lk), bool)
        kind, kv = _mask_to_kernel_operands(pad, B, H, Lq, Lk)
        assert kind == "kvec" and kv.shape == (B, Lk)
        full = jnp.zeros((B, H, Lq, Lk), jnp.float32)
        kind, b = _mask_to_kernel_operands(full, B, H, Lq, Lk)
        assert kind == "bias"
        bcast = jnp.zeros((1, 1, Lq, Lk), jnp.float32)
        kind, b = _mask_to_kernel_operands(bcast, B, H, Lq, Lk)
        assert kind == "bias" and b.shape == (1, 1, Lq, Lk)
        bad = jnp.zeros((B, H, 7, Lk), jnp.float32)
        assert _mask_to_kernel_operands(bad, B, H, Lq, Lk) is None
        # per-head key mask [B, H, 1, Lk]: a singleton Lq would be
        # zero-padded (not broadcast) by the bias streamer -> fallback
        perhead = jnp.zeros((B, H, 1, Lk), jnp.float32)
        assert _mask_to_kernel_operands(perhead, B, H, Lq, Lk) is None

    def test_return_softmax_is_real(self):
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F
        rng = np.random.RandomState(0)
        q = paddle.to_tensor(rng.randn(2, 16, 2, 8).astype("float32"))
        k = paddle.to_tensor(rng.randn(2, 16, 2, 8).astype("float32"))
        v = paddle.to_tensor(rng.randn(2, 16, 2, 8).astype("float32"))
        out, sm = F.flash_attention(q, k, v, causal=True,
                                    return_softmax=True)
        assert sm is not None and sm.shape == [2, 2, 16, 16]
        np.testing.assert_allclose(
            np.asarray(sm.numpy().sum(-1)), 1.0, rtol=1e-5)


class TestSparseAttention:
    """paddle.nn.functional.sparse_attention (reference:
    python/paddle/nn/functional/sparse_attention.py — CSR-pattern
    block-sparse attention, the CUDA 11.3 kernel's API)."""

    def _csr_causal(self, B, H, L):
        """Causal pattern as fixed-width CSR (every (b,h) same nnz)."""
        rows = [i for i in range(L) for _ in range(i + 1)]
        cols = [j for i in range(L) for j in range(i + 1)]
        counts = [i + 1 for i in range(L)]
        offset = np.concatenate([[0], np.cumsum(counts)]).astype("int32")
        off = np.broadcast_to(offset, (B, H, L + 1)).copy()
        col = np.broadcast_to(np.asarray(cols, "int32"),
                              (B, H, len(cols))).copy()
        return off, col, np.asarray(rows), np.asarray(cols)

    def test_matches_dense_causal_softmax(self):
        import paddle_tpu.nn.functional as F
        rs = np.random.RandomState(0)
        B, H, L, D = 2, 3, 6, 8
        q = rs.randn(B, H, L, D).astype("float32")
        k = rs.randn(B, H, L, D).astype("float32")
        v = rs.randn(B, H, L, D).astype("float32")
        off, col, rows, cols = self._csr_causal(B, H, L)
        out = F.sparse_attention(
            paddle.to_tensor(q), paddle.to_tensor(k),
            paddle.to_tensor(v), paddle.to_tensor(off),
            paddle.to_tensor(col)).numpy()
        logits = np.einsum("bhld,bhmd->bhlm", q, k) / np.sqrt(D)
        mask = np.tril(np.ones((L, L), bool))
        logits = np.where(mask, logits, -np.inf)
        p = np.exp(logits - logits.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        want = np.einsum("bhlm,bhmd->bhld", p, v)
        np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-5)

    def test_key_padding_mask(self):
        import paddle_tpu.nn.functional as F
        rs = np.random.RandomState(1)
        B, H, L, D = 1, 2, 4, 4
        q = rs.randn(B, H, L, D).astype("float32")
        k = rs.randn(B, H, L, D).astype("float32")
        v = rs.randn(B, H, L, D).astype("float32")
        off, col, _, _ = self._csr_causal(B, H, L)
        kpm = np.zeros((B, L), "float32")
        kpm[:, 3] = -1e30  # key 3 masked out
        out = F.sparse_attention(
            paddle.to_tensor(q), paddle.to_tensor(k),
            paddle.to_tensor(v), paddle.to_tensor(off),
            paddle.to_tensor(col),
            key_padding_mask=paddle.to_tensor(kpm)).numpy()
        logits = np.einsum("bhld,bhmd->bhlm", q, k) / np.sqrt(D)
        mask = np.tril(np.ones((L, L), bool))
        logits = np.where(mask, logits, -np.inf)
        logits[..., 3] = np.where(mask[:, 3], -1e30,
                                  -np.inf)[None, None]
        # row 3's only unmasked key... all keys up to 3 valid except 3
        logits2 = np.einsum("bhld,bhmd->bhlm", q, k) / np.sqrt(D)
        logits2 = np.where(mask, logits2, -np.inf) + kpm[:, None, None, :]
        p = np.exp(logits2 - logits2.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        want = np.einsum("bhlm,bhmd->bhld", p, v)
        np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-5)

    def test_gradients_flow(self):
        import paddle_tpu.nn.functional as F
        rs = np.random.RandomState(2)
        B, H, L, D = 1, 1, 4, 4
        q = paddle.to_tensor(rs.randn(B, H, L, D).astype("float32"),
                             stop_gradient=False)
        k = paddle.to_tensor(rs.randn(B, H, L, D).astype("float32"),
                             stop_gradient=False)
        v = paddle.to_tensor(rs.randn(B, H, L, D).astype("float32"),
                             stop_gradient=False)
        off, col, _, _ = self._csr_causal(B, H, L)
        out = F.sparse_attention(q, k, v, paddle.to_tensor(off),
                                 paddle.to_tensor(col))
        out.sum().backward()
        for t in (q, k, v):
            assert t.grad is not None
            assert np.isfinite(t.grad.numpy()).all()


class TestKernelAutotune:
    """incubate.autotune.set_config kernel tuning (reference:
    python/paddle/incubate/autotune.py:24 over
    phi/kernels/autotune/switch_autotune.cc) — per-signature
    (block_q, block_k) sweep for the Pallas flash kernel."""

    def test_config_roundtrip_and_cache(self):
        from paddle_tpu.incubate import autotune as at
        at.set_config({"kernel": {"enable": True,
                                  "tuning_range": [1, 2]}})
        cfg = at.get_config()
        assert cfg["kernel"]["enable"] is True
        calls = []

        def measure(bq, bk):
            calls.append((bq, bk))
            return 0.01 if (bq, bk) == (256, 512) else 0.02

        sig = (2, 1024, 1024, 4, 64, "bfloat16", True)
        best = at.kernel_blocks_for(sig, measure)
        assert best == (256, 512)
        n = len(calls)
        # cached: no re-measurement
        assert at.kernel_blocks_for(sig, measure) == (256, 512)
        assert len(calls) == n
        # disabled -> None
        at.set_config({"kernel": {"enable": False}})
        assert at.kernel_blocks_for(sig, measure) is None

    def test_sdpa_path_with_explicit_blocks_matches_default(self):
        """block attrs thread through the sdpa ops without changing
        numerics (CPU falls back to the reference path regardless)."""
        import paddle_tpu.nn.functional as F
        from paddle_tpu.ops._helpers import apply_op, as_tensor
        rs = np.random.RandomState(0)
        q = rs.randn(1, 8, 2, 16).astype("float32")
        want = F.scaled_dot_product_attention(
            paddle.to_tensor(q), paddle.to_tensor(q),
            paddle.to_tensor(q), is_causal=True,
            training=False).numpy()
        got = apply_op("sdpa", as_tensor(paddle.to_tensor(q)),
                       as_tensor(paddle.to_tensor(q)),
                       as_tensor(paddle.to_tensor(q)),
                       attrs=dict(causal=True, scale=0.25,
                                  dropout_p=0.0, block_q=256,
                                  block_k=512)).numpy()
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
