"""fp8 KV lane (PADDLE_TPU_KV_DTYPE=fp8 / ServingEngine(kv_dtype=...)).

PURE-CONVERT f8_e4m3 paged KV — no scale pages at all: the e4m3 value
IS the number (saturating round-to-nearest on write, plain upconvert
on read), one byte per element. Contracts:

- the paged scatter writes f8_e4m3 pools and the dequantizing gather
  (`paged_kv_gather` on an fp8 pool) returns the f32 view — the same
  upconvert the kernel lane fuses in VMEM; out-of-range values
  SATURATE (e4m3fn has no inf), so pools stay finite;
- an fp8 engine is DETERMINISTIC (same tokens across runs) and
  feature-on/off token-identical at fp8 — prefix cache, the grouped
  walk, preemption swap (whole fp8 pages move through COW/swap
  unchanged: there is nothing to keep paired);
- fp8 vs fp drift is BOUNDED (~6% relative per read, e4m3's 3-bit
  mantissa) — the one-step logit-drift probe pins it, the same
  epsilon discipline as int8's;
- page economics: an fp8 page costs 1 byte/element with ZERO scale
  overhead — strictly fewer bytes than int8's codes+scales;
- the kv_dtype gate accepts fp8 and the tag rides engine_info.
"""
import numpy as np
import pytest
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.nlp import GPTConfig, GPTForCausalLM
from paddle_tpu.nlp.generation import DecodeCache, FP8_DTYPE
from paddle_tpu.ops._helpers import apply_op
from paddle_tpu.serving import (SamplingParams, ServingEngine,
                                prometheus_render, resolve_kv_dtype)

_MODELS = {}


def tiny_gpt():
    m = _MODELS.get("gpt")
    if m is None:
        paddle.seed(13)
        cfg = GPTConfig(vocab_size=97, hidden_size=32,
                        num_hidden_layers=2, num_attention_heads=4,
                        intermediate_size=64,
                        max_position_embeddings=128,
                        hidden_dropout_prob=0.0,
                        attention_probs_dropout_prob=0.0)
        m = _MODELS["gpt"] = GPTForCausalLM(cfg)
        m.eval()
    return m


def run_engine(model, prompts, max_new, **kw):
    eng = ServingEngine(model, **kw)
    outs = eng.generate(prompts, SamplingParams(max_new_tokens=max_new))
    return [list(o.token_ids) for o in outs], eng


class TestFp8PagedOps:
    def test_scatter_writes_fp8_and_gather_upcasts(self):
        rng = np.random.RandomState(0)
        b, l, h, d, ps, mp = 2, 5, 2, 8, 4, 3
        n_pages = b * mp + 1
        pool = jnp.zeros((n_pages, ps, h, d), FP8_DTYPE)
        pt = Tensor(jnp.asarray(np.arange(1, n_pages, dtype=np.int32)
                                .reshape(b, mp)))
        upd = rng.randn(b, l, h, d).astype(np.float32)
        npool = apply_op("kv_cache_update_paged", Tensor(pool),
                         Tensor(jnp.asarray(upd)),
                         Tensor(jnp.asarray([0, 2], jnp.int32)), pt)
        assert npool._value.dtype == jnp.dtype(FP8_DTYPE)
        view = apply_op("paged_kv_gather", npool, pt)
        assert view._value.dtype == jnp.float32      # pure convert
        # the roundtrip is the e4m3 quantization of the update: row 0
        # wrote positions 0..4 of its logical view
        got = view.numpy()[0, :l]
        want = np.asarray(jnp.asarray(upd[0]).astype(FP8_DTYPE)
                          .astype(jnp.float32))
        np.testing.assert_array_equal(got, want)
        # e4m3's ~6% relative error, not garbage
        assert np.max(np.abs(got - upd[0])) < 0.2

    def test_out_of_range_saturates_finite_through_the_scatter(self):
        """XLA's raw f32->e4m3 convert NaNs past the format range;
        the paged scatter clips to +-448 first, so a pathological
        activation can never poison the pool."""
        pool = jnp.zeros((3, 4, 1, 4), FP8_DTYPE)
        pt = Tensor(jnp.asarray([[1, 2]], jnp.int32))
        upd = Tensor(jnp.asarray(
            [[[[1e6, -1e6, 448.0, -448.0]]]], jnp.float32))
        npool = apply_op("kv_cache_update_paged", Tensor(pool), upd,
                         Tensor(jnp.zeros((1,), jnp.int32)), pt)
        got = np.asarray(npool._value.astype(jnp.float32))
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got[1, 0, 0],
                                      [448.0, -448.0, 448.0, -448.0])

    def test_resolve_kv_dtype_accepts_fp8(self, monkeypatch):
        assert resolve_kv_dtype("fp8") == "fp8"
        monkeypatch.setenv("PADDLE_TPU_KV_DTYPE", "fp8")
        assert resolve_kv_dtype() == "fp8"
        with pytest.raises(ValueError, match="kv_dtype must be one"):
            resolve_kv_dtype("e5m2")


class TestFp8Engine:
    def _prompts(self, rng, n=3):
        return [rng.randint(0, 97, size=4 + 3 * i).astype(np.int64)
                for i in range(n)]

    def test_pools_are_fp8_and_pages_cost_one_byte(self):
        model = tiny_gpt()
        eng = ServingEngine(model, num_slots=2, max_len=32,
                            page_size=8, chunk_len=8, kv_dtype="fp8")
        k, v, ks, vs = eng._ct[0]
        assert k.dtype == jnp.dtype(FP8_DTYPE)
        assert v.dtype == jnp.dtype(FP8_DTYPE)
        assert ks is None and vs is None            # NO scale pages
        n_layers, n_kv, head_dim = model._decode_cache_spec()
        assert eng.page_bytes == n_layers * 2 * 8 * n_kv * head_dim
        # strictly below int8 (codes + f32 scales) and fp (f32)
        q8 = ServingEngine(model, num_slots=2, max_len=32,
                           page_size=8, chunk_len=8, kv_dtype="int8")
        fp = ServingEngine(model, num_slots=2, max_len=32,
                           page_size=8, chunk_len=8)
        assert eng.page_bytes < q8.page_bytes < fp.page_bytes
        assert eng.metrics.kv_dtype == "fp8"
        text = prometheus_render({"r0": eng.metrics.snapshot()})
        assert 'kv_dtype="fp8"' in text

    def test_deterministic_across_runs(self):
        model = tiny_gpt()
        rng = np.random.RandomState(1)
        prompts = self._prompts(rng)
        runs = [run_engine(model, prompts, 8, num_slots=2, max_len=64,
                           page_size=8, chunk_len=16,
                           kv_dtype="fp8")[0] for _ in range(2)]
        assert runs[0] == runs[1]

    def test_feature_gates_token_identical_at_fp8(self):
        """Prefix cache on/off (and with it the grouped or the flat
        walk) changes page ids and HBM walks, never tokens — the same
        oracle pattern as int8's, now on the fp8 lane."""
        model = tiny_gpt()
        rng = np.random.RandomState(2)
        sys_p = rng.randint(0, 97, size=16).astype(np.int64)
        prompts = [np.concatenate(
            [sys_p, rng.randint(0, 97, size=n).astype(np.int64)])
            for n in (3, 5)]
        base = None
        for pc in (True, False):
            toks, eng = run_engine(
                model, prompts, 6, num_slots=2, max_len=64,
                page_size=8, chunk_len=16, kv_dtype="fp8",
                prefix_cache=pc)
            assert eng.kv_dtype == "fp8" and eng.grouped is pc
            if base is None:
                base = toks
            assert toks == base

    def test_preemption_swap_roundtrip_moves_fp8_pages_whole(self):
        """A page extracted to the host tier and restored into a
        different device page lands BIT-identical — fp8 pages move as
        opaque payloads through the one-trace swap programs."""
        model = tiny_gpt()
        rng = np.random.RandomState(3)
        eng = ServingEngine(model, num_slots=2, max_len=64,
                            page_size=8, chunk_len=16, kv_dtype="fp8")
        eng.generate([rng.randint(0, 97, size=10).astype(np.int64)],
                     SamplingParams(max_new_tokens=4))
        src = 1                       # a written page
        payload = eng._extract_page(src)
        dst = eng.num_pages - 1       # an untouched page
        eng._restore_page(payload, dst)
        for k, v, _, _ in eng._ct:
            np.testing.assert_array_equal(
                np.asarray(k[src].astype(jnp.float32)),
                np.asarray(k[dst].astype(jnp.float32)))
            np.testing.assert_array_equal(
                np.asarray(v[src].astype(jnp.float32)),
                np.asarray(v[dst].astype(jnp.float32)))

    def test_drift_vs_fp_bounded_and_one_trace(self):
        """One-step logit drift of an fp8 paged prefill vs fp stays
        under the pinned epsilon (e4m3's ~6% relative read error; a
        broken convert drifts by O(logit magnitude)) — and the fp8
        engine still compiles ONE unified program."""
        model = tiny_gpt()
        rng = np.random.RandomState(4)
        prompt = rng.randint(0, 97, size=12).astype(np.int64)
        toks = {}
        engines = {}
        for dt in ("fp", "fp8"):
            toks[dt], engines[dt] = run_engine(
                model, [prompt], 6, num_slots=2, max_len=64,
                page_size=8, chunk_len=16, kv_dtype=dt)
        assert engines["fp8"]._unified_fn._cache_size() == 1
        # logit drift probe: one prefill through paged fp vs fp8 caches
        n_layers, n_kv, head_dim = model._decode_cache_spec()
        mp = 2
        pt = Tensor(jnp.asarray(np.arange(1, mp + 1, dtype=np.int32)
                                .reshape(1, mp)))
        logits = {}
        for dt in ("fp", "fp8"):
            pool_dt = jnp.float32 if dt == "fp" else FP8_DTYPE
            caches = [DecodeCache(
                Tensor(jnp.zeros((2 * mp + 1, 8, n_kv, head_dim),
                                 pool_dt)),
                Tensor(jnp.zeros((2 * mp + 1, 8, n_kv, head_dim),
                                 pool_dt)),
                Tensor(jnp.zeros((1,), jnp.int32)), page_table=pt)
                for _ in range(n_layers)]
            lg, _ = model(Tensor(jnp.asarray(prompt[None, :],
                                             jnp.int32)),
                          caches=caches)
            logits[dt] = np.asarray(
                lg._value[:, -1, :].astype(jnp.float32))
        drift = float(np.max(np.abs(logits["fp"] - logits["fp8"])))
        assert drift > 0.0                 # it IS lossy
        assert drift <= 0.5, drift         # ~50x headroom over ~1e-2
