"""Decode MEGAKERNEL (PADDLE_TPU_MEGAKERNEL, default off): the unified
step's per-layer op soup — paged LoRA gather, KV quantize-then-scatter,
ragged attend — fused into ONE dispatched op per layer, with greedy
argmax + spec acceptance as epilogue ops over the logits tile.

The acceptance matrix this file pins:

- gate-off serving is bit-token-identical to HEAD (the flag defaults
  off and the unfused path is untouched);
- gate-on greedy/int8-off serving is bit-identical to the CPU
  reference oracle — by CONSTRUCTION (every fused stage's off-TPU
  forward IS the unfused op's shared forward), asserted end-to-end;
- the lossy lanes (int8, fp8 pure-convert) hold the same pinned drift
  fused as unfused — gate-on tokens equal gate-off tokens exactly;
- interpret-mode Pallas kernels (in-place aliased scatter, paged LoRA
  delta with scalar-prefetch page chase, argmax epilogue) are
  bit-identical to their pure-jnp references;
- the REFEREES move: the launch-count probe shows strictly fewer
  registered-op dispatches per traced unified step gate-on, and
  `count_page_block_reads(fused=)` models strictly fewer bytes/token
  (pinned numbers, including the PR 11 --prefix-share 0.8 shape);
- the one-trace discipline survives: gate-on engines still compile
  exactly one unified program (retrace probe cache_size 1).
"""
import numpy as np
import pytest
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.ops.pallas.paged_attention as pa
from paddle_tpu.nlp import (GPTConfig, GPTForCausalLM, LlamaConfig,
                            LlamaForCausalLM)
from paddle_tpu.serving import SamplingParams, ServingEngine


_MODELS = {}   # engines never mutate the model: share per module


def tiny_gpt():
    m = _MODELS.get("gpt")
    if m is None:
        paddle.seed(7)
        cfg = GPTConfig(vocab_size=97, hidden_size=32,
                        num_hidden_layers=2, num_attention_heads=4,
                        intermediate_size=64,
                        max_position_embeddings=128,
                        hidden_dropout_prob=0.0,
                        attention_probs_dropout_prob=0.0)
        m = _MODELS["gpt"] = GPTForCausalLM(cfg)
        m.eval()
    return m


def tiny_llama():
    m = _MODELS.get("llama")
    if m is None:
        paddle.seed(11)
        cfg = LlamaConfig(vocab_size=89, hidden_size=32,
                          num_hidden_layers=2, num_attention_heads=4,
                          num_key_value_heads=2, intermediate_size=48,
                          max_position_embeddings=128)
        m = _MODELS["llama"] = LlamaForCausalLM(cfg)
        m.eval()
    return m


def build_decode(rng, b, mp, ps, h, d, w=1):
    """Pools + page tables + fresh K/V for a packed decode step: each
    row's live prefix covers pos[b] positions and its table has room
    for the w new tokens the step writes."""
    pos = np.asarray(
        rng.randint(ps, (mp - 1) * ps - w, size=b), np.int32)
    n_pages = b * mp + 1
    kp = rng.randn(n_pages, ps, h, d).astype(np.float32)
    vp = rng.randn(n_pages, ps, h, d).astype(np.float32)
    pt = np.zeros((b, mp), np.int32)
    page = 1
    for r in range(b):
        for i in range((pos[r] + w - 1) // ps + 1):
            pt[r, i] = page
            page += 1
    q = rng.randn(b, w, h, d).astype(np.float32)
    kn = rng.randn(b, w, h, d).astype(np.float32)
    vn = rng.randn(b, w, h, d).astype(np.float32)
    ql = np.full(b, w, np.int32)
    return (jnp.asarray(q), jnp.asarray(kn), jnp.asarray(vn),
            jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(pt),
            jnp.asarray(pos), jnp.asarray(ql))


def lora_operands(rng, b, w, h, d, pools=3, r=4):
    """Full A/B adapter pools + per-row page/scale operands; page 0 is
    the reserved all-zero base page."""
    cin, cout = h * d, h * d
    aq = rng.randn(pools, cin, r).astype(np.float32) * 0.1
    bq = rng.randn(pools, r, cout).astype(np.float32) * 0.1
    aq[0] = 0.0
    bq[0] = 0.0
    x = rng.randn(b, w, cin).astype(np.float32)
    apage = np.asarray(rng.randint(0, pools, size=b), np.int32)
    ascale = rng.rand(b).astype(np.float32)
    return (jnp.asarray(x), jnp.asarray(aq), jnp.asarray(bq),
            jnp.asarray(apage), jnp.asarray(ascale))


class TestFlagResolution:
    def test_default_off(self, monkeypatch):
        monkeypatch.delenv(pa.MEGAKERNEL_ENV, raising=False)
        assert pa.resolve_megakernel_flag() is False

    def test_env_on(self, monkeypatch):
        for v in ("1", "on", "true"):
            monkeypatch.setenv(pa.MEGAKERNEL_ENV, v)
            assert pa.resolve_megakernel_flag() is True
        for v in ("0", "off", "no"):
            monkeypatch.setenv(pa.MEGAKERNEL_ENV, v)
            assert pa.resolve_megakernel_flag() is False
        monkeypatch.setenv(pa.MEGAKERNEL_ENV, "sideways")
        with pytest.raises(ValueError):
            pa.resolve_megakernel_flag()

    def test_override_wins(self, monkeypatch):
        monkeypatch.setenv(pa.MEGAKERNEL_ENV, "1")
        assert pa.resolve_megakernel_flag(False) is False
        monkeypatch.delenv(pa.MEGAKERNEL_ENV, raising=False)
        assert pa.resolve_megakernel_flag(True) is True


class TestFusedOpBitIdentity:
    """megakernel_decode[_q8] vs the unfused op composition it
    replaces — bit-equality on every lane (shared forwards)."""

    def test_fp_flat(self):
        rng = np.random.RandomState(0)
        q, kn, vn, kp, vp, pt, pos, ql = build_decode(
            rng, 4, 5, 8, 2, 16)
        out, k2, v2 = pa.megakernel_decode(q, kn, vn, kp, vp, pt,
                                           pos, ql)
        ke = pa.paged_scatter(kp, kn, pos, pt)
        ve = pa.paged_scatter(vp, vn, pos, pt)
        ref = pa.ragged_paged_attention(q, ke, ve, pt, pos, ql)
        assert np.array_equal(np.asarray(out), np.asarray(ref))
        assert np.array_equal(np.asarray(k2), np.asarray(ke))
        assert np.array_equal(np.asarray(v2), np.asarray(ve))

    def test_q8_flat(self):
        rng = np.random.RandomState(1)
        q, kn, vn, kp, vp, pt, pos, ql = build_decode(
            rng, 3, 4, 8, 2, 16)
        kc = jnp.asarray(
            rng.randint(-127, 128, kp.shape).astype(np.int8))
        vc = jnp.asarray(
            rng.randint(-127, 128, vp.shape).astype(np.int8))
        ks = jnp.abs(jnp.asarray(
            rng.randn(*kp.shape[:3]).astype(np.float32))) / 127.0
        vs = jnp.abs(jnp.asarray(
            rng.randn(*vp.shape[:3]).astype(np.float32))) / 127.0
        out, k2, v2, ks2, vs2 = pa.megakernel_decode_q8(
            q, kn, vn, kc, vc, ks, vs, pt, pos, ql)
        ke, kse = pa.paged_scatter_q8(kc, ks, kn, pos, pt)
        ve, vse = pa.paged_scatter_q8(vc, vs, vn, pos, pt)
        ref = pa.ragged_paged_attention_q8(q, ke, ve, kse, vse, pt,
                                           pos, ql)
        assert np.array_equal(np.asarray(out), np.asarray(ref))
        assert np.array_equal(np.asarray(k2), np.asarray(ke))
        assert np.array_equal(np.asarray(ks2), np.asarray(kse))
        assert np.array_equal(np.asarray(vs2), np.asarray(vse))

    def test_grouped(self):
        rng = np.random.RandomState(2)
        b, mp, ps, h, d = 4, 6, 8, 2, 16
        # rows 0-2 share a 2-page physical prefix, row 3 is private
        pt = np.zeros((b, mp), np.int32)
        nxt = 3
        for r in range(b):
            start = 0
            if r < 3:
                pt[r, :2] = [1, 2]
                start = 2
            for i in range(start, mp):
                pt[r, i] = nxt
                nxt += 1
        pos = np.asarray([2 * ps + 3, 2 * ps + 1, 3 * ps,
                          ps + 2], np.int32)
        n_pages = int(pt.max()) + 1
        kp = jnp.asarray(rng.randn(n_pages, ps, h, d)
                         .astype(np.float32))
        vp = jnp.asarray(rng.randn(n_pages, ps, h, d)
                         .astype(np.float32))
        q = jnp.asarray(rng.randn(b, 1, h, d).astype(np.float32))
        kn = jnp.asarray(rng.randn(b, 1, h, d).astype(np.float32))
        vn = jnp.asarray(rng.randn(b, 1, h, d).astype(np.float32))
        ql = jnp.asarray(np.ones(b, np.int32))
        gid = jnp.asarray(np.asarray([0, 0, 0, 1], np.int32))
        gld = jnp.asarray(np.asarray([0, 3, 0, 0], np.int32))
        gcn = jnp.asarray(np.asarray([3, 0, 0, 0], np.int32))
        pos_j, pt_j = jnp.asarray(pos), jnp.asarray(pt)
        out, k2, v2 = pa.megakernel_decode(
            q, kn, vn, kp, vp, pt_j, pos_j, ql, gid, gld, gcn,
            grouped=True)
        ke = pa.paged_scatter(kp, kn, pos_j, pt_j)
        ve = pa.paged_scatter(vp, vn, pos_j, pt_j)
        ref = pa.ragged_paged_attention_grouped(
            q, ke, ve, pt_j, pos_j, ql, gid, gld, gcn)
        assert np.array_equal(np.asarray(out), np.asarray(ref))

    def test_lora_prologue(self):
        """lora=True == adding the paged deltas to q/k_new/v_new
        before the plain fused op (the prologue is exactly the
        delta-add the unfused model path performs)."""
        rng = np.random.RandomState(3)
        b, h, d = 3, 2, 16
        q, kn, vn, kp, vp, pt, pos, ql = build_decode(
            rng, b, 4, 8, h, d)
        x, a, bw, apage, ascale = lora_operands(rng, b, 1, h, d)
        rest = (x, a, bw, a, bw, a, bw, apage, ascale)
        out, k2, v2 = pa.megakernel_decode(
            q, kn, vn, kp, vp, pt, pos, ql, *rest, lora=True)
        dq = pa.lora_delta_paged(x, a, bw, apage, ascale)
        q_e = q + dq.reshape(q.shape)
        kn_e = kn + dq.reshape(kn.shape)
        vn_e = vn + dq.reshape(vn.shape)
        ref, ke, ve = pa.megakernel_decode(q_e, kn_e, vn_e, kp, vp,
                                           pt, pos, ql)
        assert np.array_equal(np.asarray(out), np.asarray(ref))
        assert np.array_equal(np.asarray(k2), np.asarray(ke))

    def test_base_page_is_exact_zero(self):
        """apage 0 (the all-zero base page) contributes exactly 0:
        lora=True with every row on page 0 is bit-identical to
        lora=False."""
        rng = np.random.RandomState(4)
        b, h, d = 3, 2, 16
        q, kn, vn, kp, vp, pt, pos, ql = build_decode(
            rng, b, 4, 8, h, d)
        x, a, bw, _, _ = lora_operands(rng, b, 1, h, d)
        zero_pg = jnp.zeros(b, jnp.int32)
        zero_sc = jnp.zeros(b, jnp.float32)
        rest = (x, a, bw, a, bw, a, bw, zero_pg, zero_sc)
        out, _, _ = pa.megakernel_decode(
            q, kn, vn, kp, vp, pt, pos, ql, *rest, lora=True)
        ref, _, _ = pa.megakernel_decode(q, kn, vn, kp, vp, pt, pos,
                                         ql)
        assert np.array_equal(np.asarray(out), np.asarray(ref))


class TestInterpretKernels:
    """The Pallas stages (interpret mode on CPU) against their
    pure-jnp references — bit-equality, including the in-place
    aliased scatter and the scalar-prefetch LoRA page chase."""

    @pytest.fixture(autouse=True)
    def _interpret(self, monkeypatch):
        monkeypatch.setattr(pa, "_INTERPRET", True)

    def test_scatter_fp(self):
        rng = np.random.RandomState(5)
        _, kn, vn, kp, _, pt, pos, _ = build_decode(
            rng, 4, 5, 8, 2, 16, w=2)
        ker = pa._paged_scatter_kernel(kp, kn, pos, pt)
        ref = pa.paged_scatter(kp, kn, pos, pt)
        assert np.array_equal(np.asarray(ker), np.asarray(ref))

    def test_scatter_fp8(self):
        rng = np.random.RandomState(6)
        _, kn, _, kp, _, pt, pos, _ = build_decode(
            rng, 3, 4, 8, 2, 16)
        kp8 = (kp / 8.0).astype(pa.FP8_DTYPE)
        ker = pa._paged_scatter_kernel(kp8, kn, pos, pt)
        ref = pa.paged_scatter(kp8, kn, pos, pt)
        assert ker.dtype == pa.FP8_DTYPE
        assert np.array_equal(np.asarray(ker).astype(np.float32),
                              np.asarray(ref).astype(np.float32))

    def test_scatter_q8(self):
        rng = np.random.RandomState(7)
        _, kn, _, kp, _, pt, pos, _ = build_decode(
            rng, 4, 5, 8, 2, 16, w=2)
        kc = jnp.asarray(
            rng.randint(-127, 128, kp.shape).astype(np.int8))
        ks = jnp.abs(jnp.asarray(
            rng.randn(*kp.shape[:3]).astype(np.float32))) / 127.0
        cker, sker = pa._paged_scatter_q8_kernel(kc, ks, kn, pos, pt)
        cref, sref = pa.paged_scatter_q8(kc, ks, kn, pos, pt)
        assert np.array_equal(np.asarray(cker), np.asarray(cref))
        assert np.array_equal(np.asarray(sker), np.asarray(sref))

    def test_lora_delta_paged(self):
        rng = np.random.RandomState(8)
        b, h, d = 4, 2, 16
        x, a, bw, apage, ascale = lora_operands(rng, b, 1, h, d)
        ker = pa.lora_delta_paged(x, a, bw, apage, ascale)
        ref = pa.lora_delta(x, jnp.take(a, apage, axis=0),
                            jnp.take(bw, apage, axis=0),
                            ascale.astype(jnp.float32))
        assert np.array_equal(np.asarray(ker), np.asarray(ref))

    def test_greedy_argmax_with_tie(self):
        rng = np.random.RandomState(9)
        lg = rng.randn(5, 97).astype(np.float32)
        lg[2, 10] = lg[2, 40] = lg[2].max() + 1.0   # tie: first wins
        out = pa.decode_greedy_argmax(jnp.asarray(lg))
        ref = jnp.argmax(jnp.asarray(lg), axis=-1).astype(jnp.int32)
        assert out.dtype == jnp.int32
        assert np.array_equal(np.asarray(out), np.asarray(ref))
        assert int(out[2]) == 10

    def test_spec_verify_accept(self):
        rng = np.random.RandomState(10)
        b, w, v = 4, 5, 33
        lg = jnp.asarray(rng.randn(b, w, v).astype(np.float32))
        preds = np.asarray(jnp.argmax(lg, axis=-1))
        toks = np.asarray(rng.randint(0, v, size=(b, w)), np.int32)
        # row 0: drafts match the argmax chain -> full acceptance
        toks[0, 1:] = preds[0, :-1]
        q_len = jnp.asarray(np.asarray([5, 3, 1, 0], np.int32))
        is_dec = jnp.asarray(
            np.asarray([True, True, True, False]))
        acc = pa.spec_verify_accept(lg, jnp.asarray(toks), q_len,
                                    is_dec)
        match = (toks[:, 1:] == preds[:, :-1])
        valid = (np.arange(w - 1)[None, :]
                 < (np.asarray(q_len) - 1)[:, None])
        ref = np.cumsum(
            np.cumprod(np.where(match & valid, 1, 0), axis=1),
            axis=1)[:, -1].astype(np.int32)
        ref = np.where(np.asarray(is_dec), ref, 0)
        assert np.array_equal(np.asarray(acc), ref)
        assert int(acc[0]) == 4 and int(acc[3]) == 0

    def test_megakernel_full_fused_interpret(self):
        """The whole fused op with every Pallas stage live (interpret:
        kernel scatter + kernel LoRA chase + kernel walk) vs the
        UNFUSED op composition on the same backend — bit-equal, so
        fusing moves no floats on the lowered path either. (The walk
        kernel itself is allclose-not-bitwise vs the pure-jnp
        reference — flash accumulation order — which the paged-
        attention suite already pins; here both sides ride it.)"""
        rng = np.random.RandomState(11)
        b, h, d = 3, 2, 16
        q, kn, vn, kp, vp, pt, pos, ql = build_decode(
            rng, b, 4, 8, h, d)
        x, a, bw, apage, ascale = lora_operands(rng, b, 1, h, d)
        rest = (x, a, bw, a, bw, a, bw, apage, ascale)
        out_i, k_i, v_i = pa.megakernel_decode(
            q, kn, vn, kp, vp, pt, pos, ql, *rest, lora=True)
        dq = pa.lora_delta_paged(x, a, bw, apage, ascale)
        q_e = q + dq.reshape(q.shape)
        ke = pa.paged_scatter(kp, kn + dq.reshape(kn.shape), pos, pt)
        ve = pa.paged_scatter(vp, vn + dq.reshape(vn.shape), pos, pt)
        ref = pa.ragged_paged_attention(q_e, ke, ve, pt, pos, ql)
        assert np.array_equal(np.asarray(out_i), np.asarray(ref))
        assert np.array_equal(np.asarray(k_i), np.asarray(ke))
        assert np.array_equal(np.asarray(v_i), np.asarray(ve))


class TestFusedByteModel:
    """count_page_block_reads(fused=): the modeled DMA bytes of the
    unfused vs fused step — pinned numbers, strict drop."""

    # the grouped fixture of test_grouped_attention's model test:
    # rows 0,1 share 2 pages; 4/3/2 live pages; row 3 idle
    def _fixture(self):
        pt = np.zeros((4, 8), np.int32)
        pos = np.array([25, 20, 10, 5], np.int32)
        q_len = np.array([1, 4, 1, 0], np.int32)
        gid = np.array([0, 0, 1, 2], np.int32)
        gcnt = np.array([2, 0, 0, 0], np.int32)
        return pt, pos, q_len, gid, gcnt

    def test_pinned_grouped_int8_lora(self):
        pt, pos, q_len, gid, gcnt = self._fixture()
        flat, grouped, sizes, wb = pa.count_page_block_reads(
            pt, pos, q_len, gid, gcnt, page_size=8,
            fused=dict(head_dim=64, kv_elt=1, scale_elt=4,
                       lora_bytes=1000))
        assert (flat, grouped, sizes) == (9, 7, [2])
        # attn = 7 blocks * 8 slots * (64*1 + 4) * 2 sides = 7616
        # write = 6 new tokens * (64*1 + 4) * 2 = 816
        # stage (unfused only) = 6 * 64 * 4 * 2 = 3072
        # lora: 3 * 1000 unfused (per projection), 1000 fused
        assert wb == {"unfused": 14504, "fused": 9432}

    def test_pinned_flat_fp(self):
        pt, pos, q_len, _, _ = self._fixture()
        flat, grouped, sizes, wb = pa.count_page_block_reads(
            pt, pos, q_len, page_size=8,
            fused=dict(head_dim=64, kv_elt=4, scale_elt=0,
                       lora_bytes=0))
        assert (flat, grouped, sizes) == (9, 9, [])
        assert wb == {"unfused": 43008, "fused": 39936}

    def test_pinned_prefix_share_08(self):
        """The PR 11 --prefix-share 0.8 shape: 10 decode rows, 8 of
        them sharing a 4-page physical prefix, bf16 pools."""
        ps, rows = 16, 10
        pt = np.zeros((rows, 8), np.int32)
        nxt = 5
        for r in range(rows):
            start = 0
            if r < 8:
                pt[r, :4] = [1, 2, 3, 4]
                start = 4
            for i in range(start, 8):
                pt[r, i] = nxt
                nxt += 1
        pos = np.full(rows, 4 * ps + 7, np.int32)
        q_len = np.ones(rows, np.int32)
        gid = np.array([0] * 8 + [1, 2], np.int32)
        gcnt = np.zeros(rows, np.int32)
        gcnt[0] = 4  # shared PAGE count (4-page prefix), not members
        fused = dict(head_dim=64, kv_elt=2, scale_elt=0, lora_bytes=0)
        flat, grouped, sizes, wb = pa.count_page_block_reads(
            pt, pos, q_len, gid, gcnt, page_size=ps, fused=fused)
        assert (flat, grouped, sizes) == (50, 22, [8])
        assert wb == {"unfused": 97792, "fused": 92672}
        # the flat walk prices the same fused savings (stage traffic)
        f2, g2, s2, wb2 = pa.count_page_block_reads(
            pt, pos, q_len, page_size=ps, fused=fused)
        assert (f2, g2, s2) == (50, 50, [])
        assert wb2 == {"unfused": 212480, "fused": 207360}

    def test_strict_drop_and_compat(self):
        pt, pos, q_len, gid, gcnt = self._fixture()
        for kv_elt, scale_elt, lora in ((4, 0, 0), (1, 4, 0),
                                        (1, 1, 0), (2, 0, 512)):
            *_, wb = pa.count_page_block_reads(
                pt, pos, q_len, gid, gcnt, page_size=8,
                fused=dict(head_dim=32, kv_elt=kv_elt,
                           scale_elt=scale_elt, lora_bytes=lora))
            assert wb["fused"] < wb["unfused"], (kv_elt, wb)
        # without fused= the model keeps its 3-tuple contract
        out = pa.count_page_block_reads(pt, pos, q_len, gid, gcnt,
                                        page_size=8)
        assert len(out) == 3


class TestEngineMegakernel:
    """ServingEngine(megakernel=...) — gate resolution, end-to-end
    token identity on every lane, and the launch/byte referees."""

    def _run(self, model, prompts, sp, megak, **kw):
        eng = ServingEngine(model, num_slots=3, max_len=64,
                            page_size=8, chunk_len=16,
                            megakernel=megak, **kw)
        outs = eng.generate(prompts, sp)
        return [o.token_ids for o in outs], eng

    def test_gate_resolution(self, monkeypatch):
        m = tiny_gpt()
        eng = ServingEngine(m, num_slots=2, max_len=64)
        assert eng.megakernel is False          # default OFF
        eng = ServingEngine(m, num_slots=2, max_len=64,
                            megakernel=True)
        assert eng.megakernel is True
        assert eng.metrics.megakernel is True
        # silent downgrade off the fused-capable path: the gather
        # impl has no fused form
        eng = ServingEngine(m, num_slots=2, max_len=64,
                            megakernel=True, attn_impl="gather")
        assert eng.megakernel is False
        monkeypatch.setenv(pa.MEGAKERNEL_ENV, "1")
        eng = ServingEngine(m, num_slots=2, max_len=64)
        assert eng.megakernel is True

    def test_gpt_greedy_identity_and_referees(self):
        """Gate-on greedy tokens == gate-off (HEAD behavior, and the
        CPU reference oracle by the serving suite's own pin); the
        launch-count probe and the fused-byte census both DROP; one
        trace either way."""
        m = tiny_gpt()
        prompts = [np.array([2, 4, 6, 8], np.int64),
                   np.array([1, 3, 5], np.int64)]
        sp = SamplingParams(max_new_tokens=8, eos_token_id=96)
        t_off, e_off = self._run(m, prompts, sp, False)
        t_on, e_on = self._run(m, prompts, sp, True)
        assert t_on == t_off
        assert e_off.megakernel is False and e_on.megakernel is True
        c_off, c_on = e_off.cost_census(), e_on.cost_census()
        d_off = c_off["unified_dispatch"]
        d_on = c_on["unified_dispatch"]
        assert d_on["total"] < d_off["total"], (d_off, d_on)
        assert "megakernel_decode" in d_on["ops"]
        assert "decode_greedy_argmax" in d_on["ops"]
        assert "spec_verify_accept" in d_on["ops"]
        assert "kv_cache_update_paged" not in d_on["ops"]
        assert "kv_cache_update_paged" in d_off["ops"]
        w_off = c_off["page_walk"]["modeled_bytes_per_token"]
        w_on = c_on["page_walk"]["modeled_bytes_per_token"]
        assert w_on["fused"] < w_off["unfused"]
        assert c_on["page_walk"]["megakernel"] is True
        # snapshot + exposition carry the tag and the gauge
        snap = e_on.metrics.snapshot()
        assert snap["megakernel"] is True
        assert snap["unified_dispatch_ops"] == d_on["total"]
        # ONE compiled unified program either way (retrace probe)
        assert e_on._unified_fn._cache_size() == 1
        assert e_off._unified_fn._cache_size() == 1

    def test_int8_spec_identity(self):
        """int8 lane through the fused quantize-on-write + the fused
        acceptance epilogue under speculative decoding: gate-on ==
        gate-off bit-token-identically (same lossy math, fused)."""
        m = tiny_gpt()
        tpl = np.array([5, 9, 13], np.int64)
        prompts = [np.concatenate([np.array([3], np.int64),
                                   np.tile(tpl, 4)])] * 3
        sp = SamplingParams(max_new_tokens=10, eos_token_id=96)
        t_off, e_off = self._run(m, prompts, sp, False,
                                 kv_dtype="int8", spec="ngram")
        t_on, e_on = self._run(m, prompts, sp, True,
                               kv_dtype="int8", spec="ngram")
        assert t_on == t_off
        d = e_on.cost_census()["unified_dispatch"]["ops"]
        assert "megakernel_decode_q8" in d
        assert "kv_cache_update_paged_q8" not in d
        # speculation really ran through the fused acceptance
        assert e_on.metrics.spec_accepted_tokens > 0
        assert (e_on.metrics.spec_accepted_tokens
                == e_off.metrics.spec_accepted_tokens)

    def test_model_spec_identity(self):
        """The MODEL drafter tier (PR 20) through the fused acceptance
        epilogue: drafts come from the resident draft model's own
        compiled program, the target verifies via `spec_verify_accept`
        — gate-on tokens bit-identical to gate-off with the same
        accepted-draft accounting, the fused ops really dispatched,
        and BOTH engines' draft pools quiesce. The draft program never
        fuses (it has no epilogue to fuse — its argmax IS the
        output), so the megakernel gate leaves it untouched."""
        m = tiny_gpt()
        tpl = np.array([5, 9, 13], np.int64)
        prompts = [np.concatenate([np.array([3], np.int64),
                                   np.tile(tpl, 4)])] * 3
        sp = SamplingParams(max_new_tokens=10, eos_token_id=96)
        t_off, e_off = self._run(m, prompts, sp, False,
                                 spec="model:4")
        t_on, e_on = self._run(m, prompts, sp, True,
                               spec="model:4")
        assert t_on == t_off
        d = e_on.cost_census()["unified_dispatch"]["ops"]
        assert "spec_verify_accept" in d
        assert "megakernel_decode" in d
        assert e_on.metrics.spec_accepted_tokens > 0
        assert (e_on.metrics.spec_accepted_tokens
                == e_off.metrics.spec_accepted_tokens)
        # still exactly TWO compiled programs per engine
        assert e_on._unified_fn._cache_size() == 1
        assert e_on._draft._fn._cache_size() == 1
        for e in (e_on, e_off):
            e.drain()
            e._draft.assert_quiesced()

    def test_fp8_fused_quantize_on_write(self):
        """fp8 pure-convert lane through the fused write: gate-on ==
        gate-off exactly, and the lane keeps the pinned drift vs fp
        pools (lossy, but bounded — e4m3's ~6% per read)."""
        m = tiny_gpt()
        prompts = [np.array([2, 4, 6, 8, 10, 12], np.int64)]
        sp = SamplingParams(max_new_tokens=8, eos_token_id=96)
        t_off, _ = self._run(m, prompts, sp, False, kv_dtype="fp8")
        t_on, e_on = self._run(m, prompts, sp, True, kv_dtype="fp8")
        assert t_on == t_off
        assert e_on.kv_dtype == "fp8" and e_on.megakernel is True
        # drift probe: one decode step's held logits, fp8 vs fp pools,
        # both gate-on — lossy (nonzero) but pinned
        t_fp, e_fp = self._run(m, prompts, sp, True)
        lg8 = np.asarray(e_on._last_logits[0])
        lgf = np.asarray(e_fp._last_logits[0])
        drift = float(np.max(np.abs(lg8 - lgf)))
        assert drift > 0.0
        assert drift <= 0.5, drift

    def test_adapters_identity(self):
        """Multi-tenant LoRA through the fused prologue (GPT bundles
        q/k/v into the megakernel; o rides lora_delta_paged): gate-on
        == gate-off for mixed tenant/base batches."""
        import sys, os
        sys.path.insert(0, os.path.dirname(__file__))
        from test_serving_adapters import gpt_adapters
        m = tiny_gpt()
        ws = gpt_adapters(2)
        prompt = np.array([3, 14, 15, 9, 22], np.int64)
        toks = {}
        engs = {}
        for megak in (False, True):
            eng = ServingEngine(m, num_slots=3, max_len=64,
                                adapters=True, adapter_pages=3,
                                megakernel=megak)
            ids = [eng.adapters.register(f"t{i}", w)
                   for i, w in enumerate(ws)]
            sp = lambda aid: SamplingParams(  # noqa: E731
                max_new_tokens=6, adapter_id=aid)
            outs = eng.generate([prompt] * 3,
                                [sp(ids[0]), sp(ids[1]), sp(0)])
            toks[megak] = [o.token_ids for o in outs]
            engs[megak] = eng
            eng.drain()
        assert toks[True] == toks[False]
        d = engs[True].cost_census()["unified_dispatch"]["ops"]
        assert "lora_delta_paged" in d     # the o-projection delta
        assert "lora_delta" not in d       # gathered path retired
        assert "lora_delta" in \
            engs[False].cost_census()["unified_dispatch"]["ops"]

    def test_llama_identity(self):
        """Llama (rope between LoRA delta and attend, GQA heads):
        gate-on == gate-off under speculation."""
        m = tiny_llama()
        prompts = [np.array([2, 4, 6, 2, 4, 6, 2, 4, 6], np.int64)] * 2
        sp = SamplingParams(max_new_tokens=8, eos_token_id=88)
        t_off, _ = self._run(m, prompts, sp, False, spec="ngram")
        t_on, e_on = self._run(m, prompts, sp, True, spec="ngram")
        assert t_on == t_off
        assert "megakernel_decode" in \
            e_on.cost_census()["unified_dispatch"]["ops"]
