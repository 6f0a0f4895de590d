"""Quantized serving end-to-end: int8 paged KV through the unified
ragged kernel (PADDLE_TPU_KV_DTYPE / ServingEngine(kv_dtype="int8")).

The tentpole contracts:
- the quantized paged scatter/gather ROUNDTRIP is bit-exact against
  the dense rowwise-int8 reference (quantize_kv_rowwise applied
  densely, then dequantized) — paging moves codes+scales, it never
  re-quantizes;
- the ragged kernel's int8 lane is bit-identical to the quantized
  gather path through `update_and_attend` on CPU (both dequantize
  through the SAME `dequantize_paged_q8` expression), and the
  interpret-mode kernel matches the q8 reference;
- an int8 engine is DETERMINISTIC and feature-on/off token-identical
  across the whole serving feature matrix — prefix-cache COW,
  preemption swap-out/in, speculative decoding, mid-stream migration
  — because every whole-page move (COW copy, host swap, spill)
  carries code AND scale pages together;
- int8 vs fp output drift is bounded (token agreement + a one-step
  logit-drift probe), not zero: quantization is lossy by design;
- retrace discipline survives the dtype: ONE unified program, one
  trace per COW/swap program (cache_size probes);
- the float-only guard that used to block the paged int8 path is
  GONE, replaced by real dispatch — the only remaining ValueError is
  the genuinely unsupported dense-scales-on-a-paged-pool mix.
"""
import numpy as np
import pytest
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.nlp import GPTConfig, GPTForCausalLM
from paddle_tpu.nlp.generation import (DecodeCache, quantize_kv_rowwise,
                                       update_and_attend)
from paddle_tpu.ops._helpers import apply_op
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.serving import (SamplingParams, ServingEngine,
                                prometheus_render, resolve_kv_dtype)
from paddle_tpu.serving.engine import SPILL_WIDTHS
from paddle_tpu.serving.http.driver import EngineDriver
from paddle_tpu.serving.http.protocol import completion_body
from paddle_tpu.serving.http.router import Router

_MODELS = {}


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


def run_engine(model, prompts, max_new, *, kv_dtype="int8",
               sampling=None, **kw):
    """One batch through a fresh engine; returns (token streams in
    submission order, engine)."""
    eng = ServingEngine(model, kv_dtype=kv_dtype, **kw)
    if sampling is None:
        sampling = SamplingParams(max_new_tokens=max_new)
    outs = eng.generate(prompts, sampling)
    return [o.token_ids for o in outs], eng


# -- the quantized paged ops -------------------------------------------------
class TestQuantizedPagedOps:
    def _pool(self, b, mp, ps, h, d):
        n_pages = b * mp + 1
        pool = jnp.zeros((n_pages, ps, h, d), jnp.int8)
        spool = jnp.zeros((n_pages, ps, h), jnp.float32)
        pt = jnp.asarray(np.arange(1, n_pages, dtype=np.int32)
                         .reshape(b, mp))
        return pool, spool, pt

    def test_scatter_gather_roundtrip_bit_exact_vs_dense_reference(self):
        """Quantize-then-scatter + dequantizing gather == the dense
        rowwise-int8 reference (quantize densely, dequantize densely)
        — BIT-exact, at every written position, across page
        boundaries and per-row offsets."""
        rng = np.random.RandomState(0)
        b, l, h, d, ps, mp = 3, 7, 2, 8, 4, 4
        pool, spool, pt = self._pool(b, mp, ps, h, d)
        upd = jnp.asarray(rng.randn(b, l, h, d).astype(np.float32))
        pos = jnp.asarray([0, 3, 8], jnp.int32)   # mid-page offsets
        npool, nspool = apply_op(
            "kv_cache_update_paged_q8", Tensor(pool), Tensor(spool),
            Tensor(upd), Tensor(pos), Tensor(pt))
        view = apply_op("paged_kv_gather_q8", npool, nspool,
                        Tensor(pt)).numpy()       # [B, mp*ps, H, D]
        codes, scales = quantize_kv_rowwise(upd)
        dense = np.asarray(codes.astype(jnp.float32)
                           * scales[..., None])
        for bi in range(b):
            for t in range(l):
                got = view[bi, int(pos[bi]) + t]
                assert (got == dense[bi, t]).all(), (bi, t)

    def test_scatter_out_of_window_lands_in_trash_page(self):
        """Positions past a row's addressable window redirect codes
        AND scales into page 0 — live pages (and their scales) are
        never clobbered by chunk padding."""
        rng = np.random.RandomState(1)
        b, h, d, ps, mp = 1, 2, 8, 4, 2
        pool, spool, pt = self._pool(b, mp, ps, h, d)
        # pre-fill the live pages with a sentinel write
        first = jnp.asarray(rng.randn(b, 4, h, d).astype(np.float32))
        npool, nspool = apply_op(
            "kv_cache_update_paged_q8", Tensor(pool), Tensor(spool),
            Tensor(first), Tensor(jnp.zeros((1,), jnp.int32)),
            Tensor(pt))
        before = npool.numpy().copy(), nspool.numpy().copy()
        # a write starting past the 2-page window (addressable = 8)
        over = jnp.asarray(rng.randn(b, 3, h, d).astype(np.float32))
        npool2, nspool2 = apply_op(
            "kv_cache_update_paged_q8", npool, nspool, Tensor(over),
            Tensor(jnp.asarray([mp * ps], jnp.int32)), Tensor(pt))
        after = npool2.numpy(), nspool2.numpy()
        # live pages untouched, trash page (0) took the codes+scales
        assert (after[0][1:] == before[0][1:]).all()
        assert (after[1][1:] == before[1][1:]).all()
        assert (after[0][0] != before[0][0]).any()
        assert (after[1][0] != before[1][0]).any()

    def test_resolve_kv_dtype_validates(self):
        assert resolve_kv_dtype() == "fp"
        assert resolve_kv_dtype("int8") == "int8"
        with pytest.raises(ValueError, match="kv_dtype must be one"):
            resolve_kv_dtype("int4")
        with pytest.raises(ValueError, match="PADDLE_TPU_KV_DTYPE"):
            resolve_kv_dtype("fp16")


# -- the kernel's int8 lane --------------------------------------------------
class TestQ8KernelVsReference:
    """Interpret-mode Pallas q8 kernel against the pure-JAX q8
    reference (which itself is pinned to the quantized-gather path
    below)."""

    @pytest.fixture(autouse=True)
    def _interpret(self, monkeypatch):
        monkeypatch.setattr(pa, "_INTERPRET", True)

    @pytest.mark.parametrize("page_size", [8, 16])
    @pytest.mark.parametrize("rep", [1, 4])
    def test_matches_reference_mixed_qlen(self, page_size, rep):
        rng = np.random.RandomState(page_size + rep)
        b, mp, hkv, d = 4, 4, 2, 16
        h = hkv * rep
        lq = 6
        n_pages = b * mp + 1
        kp = jnp.asarray(rng.randint(-127, 128, size=(
            n_pages, page_size, hkv, d)).astype(np.int8))
        vp = jnp.asarray(rng.randint(-127, 128, size=(
            n_pages, page_size, hkv, d)).astype(np.int8))
        ks = jnp.asarray(np.abs(rng.randn(
            n_pages, page_size, hkv)).astype(np.float32) * 0.02)
        vs = jnp.asarray(np.abs(rng.randn(
            n_pages, page_size, hkv)).astype(np.float32) * 0.02)
        pt = jnp.asarray(np.arange(1, n_pages, dtype=np.int32)
                         .reshape(b, mp))
        # decode row, mid-prefill rows, page-boundary pos, dead row
        pos = jnp.asarray([5, 0, page_size - 1, 9], jnp.int32)
        qlen = jnp.asarray([1, lq, lq, 0], jnp.int32)
        q = jnp.asarray(rng.randn(b, lq, h, d).astype(np.float32))
        ref = pa.ragged_attention_reference_q8(
            q, kp, vp, ks, vs, pt, pos, qlen)
        out = pa.ragged_paged_attention_q8(
            q, kp, vp, ks, vs, pt, pos, qlen)
        ref, out = np.asarray(ref), np.asarray(out)
        for bi in range(b):
            for i in range(int(qlen[bi])):       # live queries only
                np.testing.assert_allclose(
                    out[bi, i], ref[bi, i], rtol=2e-5, atol=2e-6,
                    err_msg=f"row {bi} query {i}")


class TestQ8KernelVsGatherBitIdentity:
    """Through update_and_attend on CPU, the int8 kernel lane (the q8
    reference) and the quantized-gather impl must be BIT-identical —
    both dequantize through the shared dequantize_paged_q8
    expression."""

    def _paged_int8_cache(self, b, mp, ps, hkv, d, pos, impl,
                          rng, q_len=None):
        n_pages = b * mp + 1
        kp = jnp.zeros((n_pages, ps, hkv, d), jnp.int8)
        sp = jnp.zeros((n_pages, ps, hkv), jnp.float32)
        pt = np.arange(1, n_pages, dtype=np.int32).reshape(b, mp)
        # scatter a real history below pos so reads cross pages
        hist_len = int(max(pos)) if len(pos) else 0
        cache = DecodeCache(
            Tensor(kp), Tensor(kp), Tensor(jnp.zeros((b,), jnp.int32)),
            Tensor(sp), Tensor(sp), page_table=Tensor(jnp.asarray(pt)),
            attn_impl=impl)
        if hist_len:
            hist = jnp.asarray(
                rng.randn(b, hist_len, hkv, d).astype(np.float32))
            k_buf, k_sc = apply_op(
                "kv_cache_update_paged_q8", cache.k, cache.k_scale,
                Tensor(hist), cache.pos, cache.page_table)
            v_buf, v_sc = apply_op(
                "kv_cache_update_paged_q8", cache.v, cache.v_scale,
                Tensor(hist), cache.pos, cache.page_table)
            cache = DecodeCache(
                k_buf, v_buf, Tensor(jnp.asarray(pos, jnp.int32)),
                k_sc, v_sc, page_table=cache.page_table,
                attn_impl=impl,
                q_len=(None if q_len is None
                       else Tensor(jnp.asarray(q_len, jnp.int32))))
        return cache

    @pytest.mark.parametrize("rep", [1, 2])
    def test_decode_step_bit_identical(self, rep):
        rng = np.random.RandomState(11 + rep)
        b, mp, ps, hkv, d = 3, 3, 8, 2, 16
        h = hkv * rep
        pos = [5, 11, 2]
        q = Tensor(jnp.asarray(
            rng.randn(b, 1, h, d).astype(np.float32)))
        kn = Tensor(jnp.asarray(
            rng.randn(b, 1, hkv, d).astype(np.float32)))
        vn = Tensor(jnp.asarray(
            rng.randn(b, 1, hkv, d).astype(np.float32)))
        outs = {}
        for impl in ("kernel", "gather"):
            cache = self._paged_int8_cache(b, mp, ps, hkv, d, pos,
                                           impl, np.random.RandomState(5))
            out, _ = update_and_attend(q, kn, vn, cache)
            outs[impl] = out.numpy()
        assert (outs["kernel"] == outs["gather"]).all()

    def test_ragged_rows_match_across_impls(self):
        """Mixed q_len rows (the unified step's shape): kernel lane vs
        gather impl agree on every LIVE query (gather's dead-query
        outputs are unspecified, like the kernel's)."""
        rng = np.random.RandomState(21)
        b, mp, ps, hkv, d, lq = 3, 3, 8, 2, 16, 4
        pos = [5, 0, 9]
        q_len = [1, 4, 3]
        q = Tensor(jnp.asarray(
            rng.randn(b, lq, hkv * 2, d).astype(np.float32)))
        kn = Tensor(jnp.asarray(
            rng.randn(b, lq, hkv, d).astype(np.float32)))
        vn = Tensor(jnp.asarray(
            rng.randn(b, lq, hkv, d).astype(np.float32)))
        outs = {}
        for impl in ("kernel", "gather"):
            cache = self._paged_int8_cache(
                b, mp, ps, hkv, d, pos, impl,
                np.random.RandomState(6), q_len=q_len)
            out, _ = update_and_attend(q, kn, vn, cache)
            outs[impl] = out.numpy()
        for bi in range(b):
            for i in range(q_len[bi]):
                np.testing.assert_allclose(
                    outs["kernel"][bi, i], outs["gather"][bi, i],
                    rtol=2e-5, atol=2e-6, err_msg=f"row {bi} q {i}")


# -- dispatch: the float-only guard is gone ---------------------------------
class TestDispatchErrors:
    def test_paged_pool_with_dense_scales_raises(self):
        """The one genuinely unsupported combo: per-head calibrated
        CONSTANT scales (the dense int8 mode) on a paged pool."""
        rng = np.random.RandomState(2)
        kp = Tensor(jnp.zeros((5, 4, 2, 8), jnp.int8))
        sc = Tensor(jnp.ones((2,), jnp.float32))     # dense-mode shape
        cache = DecodeCache(
            kp, kp, Tensor(jnp.zeros((1,), jnp.int32)), sc, sc,
            page_table=Tensor(jnp.zeros((1, 2), jnp.int32)))
        q = Tensor(jnp.asarray(rng.randn(1, 1, 2, 8), jnp.float32))
        kn = Tensor(jnp.asarray(rng.randn(1, 1, 2, 8), jnp.float32))
        with pytest.raises(ValueError,
                           match="dense int8 mode and the paged pool "
                                 "cannot mix"):
            update_and_attend(q, kn, kn, cache)

    def test_paged_int8_no_longer_future_work(self):
        """The replaced guard: a well-formed int8 paged cache WORKS —
        multi-token chunked writes included (the dense int8 cache
        still rejects those; the paged pool is the fix)."""
        rng = np.random.RandomState(3)
        kp = Tensor(jnp.zeros((5, 4, 2, 8), jnp.int8))
        sp = Tensor(jnp.zeros((5, 4, 2), jnp.float32))
        cache = DecodeCache(
            kp, kp, Tensor(jnp.zeros((1,), jnp.int32)), sp, sp,
            page_table=Tensor(jnp.asarray([[1, 2]], jnp.int32)))
        q = Tensor(jnp.asarray(rng.randn(1, 6, 2, 8), jnp.float32))
        kn = Tensor(jnp.asarray(rng.randn(1, 6, 2, 8), jnp.float32))
        out, new_cache = update_and_attend(q, kn, kn, cache)
        assert out.numpy().shape == (1, 6, 2, 8)
        assert np.isfinite(out.numpy()).all()
        assert new_cache.k_scale is not None

    def test_dense_int8_perrow_multitoken_still_guarded(self):
        """The dense-cache limitation keeps its own clear message (and
        now points at the paged pool as the fix)."""
        rng = np.random.RandomState(4)
        k8 = Tensor(jnp.zeros((2, 2, 16, 8), jnp.int8))
        sc = Tensor(jnp.ones((2,), jnp.float32))
        cache = DecodeCache(
            k8, k8, Tensor(jnp.zeros((2,), jnp.int32)), sc, sc)
        q = Tensor(jnp.asarray(rng.randn(2, 4, 2, 8), jnp.float32))
        kn = Tensor(jnp.asarray(rng.randn(2, 4, 2, 8), jnp.float32))
        with pytest.raises(NotImplementedError,
                           match="int8 PAGED pool"):
            update_and_attend(q, kn, kn, cache)


# -- engine end-to-end -------------------------------------------------------
class TestInt8Engine:
    def _prompts(self, rng, n=4):
        return [rng.randint(0, 97, size=int(rng.randint(3, 20)))
                .astype(np.int64) for _ in range(n)]

    def test_kernel_vs_gather_identity_and_fp_drift_bounded(self):
        """One trace, three arms: int8-kernel == int8-gather
        BIT-token-identical (the kernel lane and the quantized gather
        dequantize through the same expression), and int8 vs fp
        agreement stays high — quantization is lossy but bounded; a
        broken scale path collapses agreement to noise."""
        model = tiny_gpt()
        prompts = self._prompts(np.random.RandomState(0), n=5)
        kern, _ = run_engine(model, prompts, 8, num_slots=3,
                             max_len=64, page_size=8, chunk_len=16,
                             attn_impl="kernel")
        gath, _ = run_engine(model, prompts, 8, num_slots=3,
                             max_len=64, page_size=8, chunk_len=16,
                             attn_impl="gather")
        assert kern == gath
        fp, _ = run_engine(model, prompts, 8, kv_dtype="fp",
                           num_slots=3, max_len=64, page_size=8,
                           chunk_len=16)
        flat_q8 = [t for s in kern for t in s]
        flat_fp = [t for s in fp for t in s]
        assert len(flat_q8) == len(flat_fp)
        agree = sum(a == b for a, b in zip(flat_q8, flat_fp))
        assert agree / len(flat_fp) >= 0.8, (kern, fp)


# -- the serving feature matrix at int8 -------------------------------------
class TestInt8FeatureMatrix:
    def test_prefix_cache_cow_token_identity(self):
        """Mid-page prefix matches force COW copies; with int8 the
        copy must carry the SCALE page too — cache on vs off stays
        token-identical (a dropped scale page poisons the dequant and
        this assert catches it)."""
        model = tiny_gpt()
        rng = np.random.RandomState(5)
        shared = rng.randint(0, 97, size=11).astype(np.int64)  # !%8==0
        prompts = [np.concatenate([
            shared, rng.randint(0, 97, size=4).astype(np.int64)])
            for _ in range(4)]

        def run(prefix):
            eng = ServingEngine(model, num_slots=2, max_len=64,
                                page_size=8, chunk_len=16,
                                kv_dtype="int8", prefix_cache=prefix)
            outs = []
            for p in prompts:   # sequential: follow-ups hit the tree
                outs.extend(o.token_ids for o in eng.generate(
                    [p], SamplingParams(max_new_tokens=6)))
            return outs, eng

        on, eng = run(True)
        off, _ = run(False)
        assert on == off
        snap = eng.metrics.snapshot()
        assert snap["prefix"]["hits"] > 0
        assert snap["prefix"]["cow_copies"] > 0    # scale copy proven

    def test_preemption_swap_token_identity(self):
        """Preempt-swap-resume at int8: the host tier holds
        (codes, scales) page pairs; the resumed stream must be
        bit-token-identical to the never-preempted int8 run."""
        model = tiny_gpt()

        def run(preempt):
            vt = [0.0]
            eng = ServingEngine(model, num_slots=2, max_len=64,
                                page_size=8, chunk_len=16,
                                kv_dtype="int8", preempt=preempt,
                                clock=lambda: vt[0])
            rng = np.random.RandomState(6)
            lows = [eng.add_request(
                rng.randint(0, 97, size=6).astype(np.int64),
                SamplingParams(max_new_tokens=20, priority=5))
                for _ in range(2)]
            for _ in range(3):
                eng.step()
                vt[0] += 0.01
            hi = eng.add_request(
                rng.randint(0, 97, size=6).astype(np.int64),
                SamplingParams(max_new_tokens=4, priority=0))
            while eng.has_work:
                eng.step()
                vt[0] += 0.01
            eng.drain()
            return [r.output_tokens for r in lows + [hi]], eng

        on, eng = run(True)
        off, _ = run(False)
        assert on == off
        assert eng.metrics.snapshot()["preemptions"] >= 1
        assert eng.metrics.snapshot()["swapped_out_pages"] >= 1
        eng.pool.assert_quiesced()

    def test_spec_decode_token_identity(self):
        """Draft-then-verify over the int8 pool: rejected drafts'
        transient quantized writes roll back exactly like fp padding
        columns — spec on == spec off, and drafts really verified."""
        model = tiny_gpt()
        rng = np.random.RandomState(7)
        tpl = rng.randint(0, 97, size=6).astype(np.int64)
        prompts = [np.concatenate(
            [rng.randint(0, 97, size=2).astype(np.int64),
             np.tile(tpl, 3)]) for _ in range(3)]

        def run(spec):
            eng = ServingEngine(model, num_slots=3, max_len=96,
                                page_size=8, chunk_len=16,
                                kv_dtype="int8", spec=spec)
            outs = eng.generate(prompts,
                                SamplingParams(max_new_tokens=10))
            return ([o.token_ids for o in outs],
                    sum(o.accepted_draft_tokens for o in outs))

        on, accepted = run("ngram:4")
        off, _ = run(False)
        assert on == off
        assert accepted > 0

    def test_prefix_spill_to_host_restores_codes_and_scales(self):
        """Parked prefix pages spill to the host tier under pressure
        as (codes, scales) pairs; a later match restores them and the
        hit decodes exactly what the original run produced."""
        model = tiny_gpt()
        eng = ServingEngine(model, num_slots=1, max_len=32,
                            page_size=8, num_pages=5, chunk_len=8,
                            kv_dtype="int8")
        base = np.arange(1, 10, dtype=np.int64)
        r1 = eng.add_request(base, SamplingParams(max_new_tokens=4))
        eng.run()
        assert eng.pool.cached_pages > 0
        # disjoint request too big for the free pages alone: the
        # parked pages spill (int8: half the host bytes per page too)
        eng.add_request(np.arange(40, 57),
                        SamplingParams(max_new_tokens=4))
        eng.run()
        assert eng.prefix_cache.spilled_pages_total >= 1
        r3 = eng.add_request(base, SamplingParams(max_new_tokens=4))
        eng.run()
        assert eng.prefix_cache.restored_pages_total >= 1
        assert r3.cached_tokens > 0
        assert r3.output_tokens == r1.output_tokens
        eng.drain()

    def test_midstream_migration_token_identity(self):
        """Kill the serving replica after the first streamed token: the
        re-placed int8 continuation on the survivor (fresh quantized
        re-prefill of prompt + banked history) matches the
        never-killed int8 stream."""
        model = tiny_gpt()
        prompt = np.array([3, 14, 15, 9, 26], np.int64)
        solo, _ = run_engine(model, [prompt], 12, num_slots=2,
                             max_len=64, page_size=8, chunk_len=16)
        engines = [ServingEngine(model, num_slots=2, max_len=64,
                                 page_size=8, chunk_len=16,
                                 kv_dtype="int8") for _ in range(2)]
        for e in engines:
            e.generate([np.array([1, 2, 3])],
                       SamplingParams(max_new_tokens=2))
        drivers = [EngineDriver(e, name=f"replica-{i}")
                   for i, e in enumerate(engines)]
        router = Router(drivers).start()
        try:
            t = router.submit(prompt,
                              SamplingParams(max_new_tokens=12))
            victim = t.driver
            tokens = []
            for kind, val in t.events(poll_s=0.01):
                if kind == "token":
                    tokens.append(val)
                    if len(tokens) == 2 and not victim.dead:
                        victim.kill()
                elif kind == "done":
                    done = val
                    break
                elif kind == "error":
                    raise AssertionError(f"stream error: {val}")
            assert done == "length"
            assert tokens == solo[0]
            assert t.output().migrations == 1
        finally:
            router.drain()


# -- retrace discipline ------------------------------------------------------
class TestInt8RetraceDiscipline:
    def test_one_unified_program_and_one_trace_swap_cow(
            self, only_the_unified_step):
        """int8 on changes the POOL DTYPE, not the program count:
        exactly ONE compiled ragged step across every mix, ONE trace
        for each of COW-copy / swap-out / swap-in over traced page
        ids, and the legacy families never built."""
        model = tiny_gpt()
        vt = [0.0]
        eng = ServingEngine(model, num_slots=2, max_len=64,
                            page_size=8, chunk_len=16,
                            kv_dtype="int8", clock=lambda: vt[0])
        rng = np.random.RandomState(9)
        shared = rng.randint(0, 97, size=11).astype(np.int64)
        # prefix traffic (forces COW), then overload (forces swap)
        for _ in range(2):
            eng.generate([np.concatenate(
                [shared, rng.randint(0, 97, size=3).astype(np.int64)])],
                SamplingParams(max_new_tokens=4))
        lows = [eng.add_request(
            rng.randint(0, 97, size=6).astype(np.int64),
            SamplingParams(max_new_tokens=16, priority=5))
            for _ in range(2)]
        for _ in range(3):
            eng.step()
            vt[0] += 0.01
        eng.add_request(rng.randint(0, 97, size=6).astype(np.int64),
                        SamplingParams(max_new_tokens=4, priority=0))
        while eng.has_work:
            eng.step()
            vt[0] += 0.01
        assert all(r.finished for r in lows)
        assert eng.metrics.snapshot()["preemptions"] >= 1
        only_the_unified_step(eng)
        assert eng._copy_page_fn._cache_size() == 1
        assert eng._swap_out_fn._cache_size() == len(SPILL_WIDTHS)
        assert eng._swap_in_fn._cache_size() == 1


# -- metrics / usage ---------------------------------------------------------
class TestInt8Metrics:
    def test_kv_dtype_tag_and_byte_gauges(self):
        # gauges are set at construction — no compiled step needed
        model = tiny_gpt()
        eng = ServingEngine(model, num_slots=2, max_len=64,
                            page_size=8, chunk_len=16,
                            kv_dtype="int8")
        snap = eng.metrics.snapshot()
        assert snap["kv_dtype"] == "int8"
        assert snap["pool"]["bytes_per_page"] == eng.page_bytes > 0
        assert snap["host_pool"]["bytes_total"] == \
            eng.host_pages * eng.page_bytes
        text = prometheus_render({"r0": snap})
        assert 'kv_dtype="int8"' in text
        assert "pool_bytes_per_page" in text
        assert "host_bytes_total" in text
        # int8 pages really are smaller than the fp ones
        fp_eng = ServingEngine(model, num_slots=2, max_len=64,
                               page_size=8, chunk_len=16,
                               kv_dtype="fp")
        assert eng.page_bytes < fp_eng.page_bytes
        assert 'kv_dtype="fp"' in prometheus_render(
            {"r0": fp_eng.metrics.snapshot()})

    def test_env_gate_resolves_at_construction(self, monkeypatch):
        model = tiny_gpt()
        monkeypatch.setenv("PADDLE_TPU_KV_DTYPE", "int8")
        eng = ServingEngine(model, num_slots=2, max_len=64)
        assert eng.kv_dtype == "int8"
        monkeypatch.setenv("PADDLE_TPU_KV_DTYPE", "int4")
        with pytest.raises(ValueError, match="kv_dtype"):
            ServingEngine(model, num_slots=2, max_len=64)

    def test_openai_usage_shape_unchanged_with_int8(self):
        """The HTTP `usage` block with int8 on has EXACTLY the fp
        keys and the same accounting semantics — quantization is an
        engine-internal economy, not an API change."""
        model = tiny_gpt()
        prompt = np.array([4, 8, 15], np.int64)

        def usage(kv_dtype):
            eng = ServingEngine(model, num_slots=2, max_len=64,
                                page_size=8, chunk_len=16,
                                kv_dtype=kv_dtype)
            out = eng.generate([prompt],
                               SamplingParams(max_new_tokens=5))[0]
            return completion_body("t-0", "tiny", out)["usage"]

        u8, ufp = usage("int8"), usage("fp")
        assert set(u8) == set(ufp)
        assert u8["prompt_tokens"] == ufp["prompt_tokens"] == 3
        assert u8["completion_tokens"] == \
            ufp["completion_tokens"] == 5
        assert u8["total_tokens"] == ufp["total_tokens"] == 8


# -- bench A/B ---------------------------------------------------------------
def _bench_mod():
    import importlib.util
    import os
    script = os.path.join(os.path.dirname(__file__), os.pardir,
                          "scripts", "serving_bench.py")
    spec = importlib.util.spec_from_file_location(
        "serving_bench_quant", script)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_quant_trace_ab_contract():
    """The --quant-ab core (quant_trace called directly, one attempt
    per arm — the cheap tier-1 pin; the full `serving_bench --smoke
    --quant-ab` path rides the slow marker): under the SAME HBM
    page-byte budget int8 admits >= 1.5x residents at peak, one-step
    logit drift stays under the pinned epsilon, trace throughput does
    not regress, and both arms serve the whole burst."""
    mod = _bench_mod()
    model, cfg = mod.build_model(False)
    qt = mod.quant_trace(model, cfg, slots=8, seed=4, on_tpu=False,
                         repeats=1)
    assert qt["fp"]["completed"] == qt["int8"]["completed"] \
        == qt["requests"]
    assert qt["residents_ratio"] >= 1.5, qt
    assert qt["max_logit_drift"] <= qt["drift_epsilon"], qt
    assert qt["int8"]["pool_bytes"] <= qt["hbm_budget_bytes"]
    assert qt["int8"]["num_pages"] > qt["fp"]["num_pages"]
    assert 0.0 <= qt["token_agreement"] <= 1.0


@pytest.mark.slow
def test_serving_bench_quant_ab_smoke(tmp_path, monkeypatch):
    """`serving_bench.py --smoke --quant-ab` end-to-end (ISSUE
    acceptance): the report's "quant" section lands in
    BENCH_serving.json (schema v9) and the script's own asserts —
    residents ratio, drift epsilon, tokens/s no-regression — pass."""
    import json
    import sys
    mod = _bench_mod()
    out = str(tmp_path / "BENCH_serving.json")
    monkeypatch.setattr(sys, "argv",
                        ["serving_bench.py", "--smoke", "--requests",
                         "3", "--quant-ab", "--out", out])
    mod.main()
    with open(out) as f:
        report = json.load(f)
    assert report["schema_version"] == 19
    qt = report["quant"]
    assert set(qt) >= {"fp", "int8", "residents_ratio",
                       "tokens_per_sec_ratio", "max_logit_drift",
                       "hbm_budget_bytes", "token_agreement"}
    assert qt["residents_ratio"] >= 1.5
    assert qt["tokens_per_sec_ratio"] >= 1.0
