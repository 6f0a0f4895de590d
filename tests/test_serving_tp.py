"""Multi-chip tensor-parallel serving replica (serving/tp.py).

The load-bearing property (ISSUE 13 acceptance): an engine spanning a
(dp, mp) mesh of the conftest's 8 virtual CPU devices emits tokens
BIT-IDENTICAL to the single-device (mp=1) oracle — through prefix
cache on/off, int8/fp8 pools, grouped attention, COW, preemption swap
and speculative decoding — while compiling ONE unified trace whose
only collectives are bit-exact output all-gathers (one per layer,
ZERO all-reduces: fp math is never reassociated, which is why the
identity is provable rather than pinned-drift).

Non-slow tests stay lean (a handful of tiny-model engine compiles,
mp=2); the mp=4 x {int8, fp8, prefix, spec, preempt} matrix rides the
`slow` marker.
"""
import os
import re

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.nlp import (GPTConfig, GPTForCausalLM, LlamaConfig,
                            LlamaForCausalLM)
from paddle_tpu.ops.pallas.paged_attention import \
    count_page_block_reads
from paddle_tpu.serving import (SamplingParams, ServingEngine,
                                ServingTP, collective_counts,
                                parse_mesh_spec, prometheus_render,
                                resolve_serving_mesh,
                                shared_prefix_groups)
from paddle_tpu.serving.engine import SPILL_WIDTHS

_MODELS = {}   # engines never mutate the model: share per module


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


def _prompts(vocab, sizes, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, vocab, size=n).astype(np.int64)
            for n in sizes]


def _serve(eng, prompts, max_new=8, **sp):
    outs = eng.generate(
        prompts, [SamplingParams(max_new_tokens=max_new, **sp)
                  for _ in prompts])
    return [list(o.token_ids) for o in outs]


def _engine(model, mesh=None, **kw):
    kw.setdefault("num_slots", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("page_size", 8)
    kw.setdefault("chunk_len", 8)
    return ServingEngine(model, mesh=mesh, **kw)


# module-scoped engine pair: most non-slow tests drive traffic through
# these two (requests retire cleanly, so reuse is free — and reuse is
# itself a retrace check: the one trace must survive every batch)
@pytest.fixture(scope="module")
def mp1_eng():
    return _engine(tiny_llama())


@pytest.fixture(scope="module")
def mp2_eng():
    return _engine(tiny_llama(), mesh="dp1mp2")


class TestMeshResolution:
    def test_parse_specs(self):
        assert parse_mesh_spec("dp2mp4") == (2, 4)
        assert parse_mesh_spec("dp1xmp2") == (1, 2)
        assert parse_mesh_spec(" DP2MP2 ") == (2, 2)
        for bad in ("mp2", "dp2", "dp0mp2", "2x4", "dp2mp"):
            with pytest.raises(ValueError):
                parse_mesh_spec(bad)

    def test_env_gate(self, monkeypatch):
        monkeypatch.delenv("PADDLE_TPU_MESH", raising=False)
        assert resolve_serving_mesh(None) is None       # default off
        monkeypatch.setenv("PADDLE_TPU_MESH", "off")
        assert resolve_serving_mesh(None) is None
        monkeypatch.setenv("PADDLE_TPU_MESH", "dp1mp2")
        tp = resolve_serving_mesh(None)
        assert tp.shape == "dp1xmp2" and (tp.dp, tp.mp) == (1, 2)
        # an explicit False wins over the env (the oracle arm's knob)
        assert resolve_serving_mesh(False) is None
        monkeypatch.setenv("PADDLE_TPU_MESH", "nonsense")
        with pytest.raises(ValueError, match="dp2mp4"):
            resolve_serving_mesh(None)

    def test_overrides(self):
        assert resolve_serving_mesh((2, 2)).shape == "dp2xmp2"
        tp = ServingTP(1, 2)
        assert resolve_serving_mesh(tp) is tp
        # a jax Mesh / ProcessMesh with dp+mp axes passes through
        from paddle_tpu.distributed.mesh import ProcessMesh
        pm = ProcessMesh(shape=[2, 2], dim_names=["dp", "mp"])
        got = resolve_serving_mesh(pm)
        assert (got.dp, got.mp) == (2, 2)
        with pytest.raises(ValueError, match="mp"):
            resolve_serving_mesh(
                ProcessMesh(shape=[2], dim_names=["dp"]))
        with pytest.raises(ValueError, match="tuple"):
            resolve_serving_mesh(3.5)

    def test_too_many_devices(self):
        with pytest.raises(ValueError, match="devices"):
            ServingTP(4, 4)    # 16 > the conftest's 8


class TestGeometryValidation:
    def test_kv_head_mismatch_names_dims_and_legal_values(self):
        # llama tiny: H_kv=2, H=4, hidden=32 — mp=4 cannot split the
        # kv heads; the error must name the dims and the legal mps
        with pytest.raises(ValueError) as ei:
            _engine(tiny_llama(), mesh="dp1mp4")
        msg = str(ei.value)
        assert "H_kv=2" in msg and "mp=4" in msg
        assert "H=4" in msg and "hidden=32" in msg
        assert "Legal mp values" in msg and "[1, 2]" in msg

    def test_validation_happens_at_construction(self):
        # no engine state, no compiled program, no sharded array —
        # the raise precedes all of it (no silent mis-shard)
        try:
            _engine(tiny_llama(), mesh="dp2mp4")
        except ValueError as exc:
            assert "H_kv=2" in str(exc)
        else:
            pytest.fail("geometry error not raised")

    def test_legal_mp_passes(self):
        eng = _engine(tiny_llama(), mesh="dp1mp2")
        assert (eng.mp, eng.dp) == (2, 1)
        assert eng.tp.shape == "dp1xmp2"
        # per-chip page cost is 1/mp of the full page
        assert eng.page_bytes_per_chip * 2 == eng.page_bytes


class TestTokenIdentity:
    """mp>1 must be BIT-token-identical to the mp=1 oracle."""

    def test_mp2_matches_mp1_and_solo_oracle(self, mp1_eng, mp2_eng):
        m = tiny_llama()
        prompts = _prompts(89, (5, 9, 17, 3, 12, 7), seed=1)
        t1 = _serve(mp1_eng, prompts)
        t2 = _serve(mp2_eng, prompts)
        assert t1 == t2
        # one solo CompiledGenerator cross-check anchors the pair to
        # the offline oracle (same-length prompts share one compile)
        solo = m.generate(paddle.to_tensor(prompts[0][None]),
                          max_new_tokens=8).numpy()[0, prompts[0].size:]
        assert t2[0] == list(solo)

    @pytest.mark.slow
    def test_mp2_dp2_full_mesh(self, mp1_eng):
        # dp replicates (control and data plane): a dp2xmp2 mesh must
        # still be bit-token-identical to the single-device oracle
        prompts = _prompts(89, (4, 11, 6), seed=2)
        eng = _engine(tiny_llama(), mesh=(2, 2))
        assert _serve(eng, prompts) == _serve(mp1_eng, prompts)

    def test_mp2_prefix_cache_off(self, mp1_eng):
        # the mp1 arm rides the module fixture (prefix ON): cache
        # on/off is token-identical by PR 5's proven gate, so the
        # sharded prefix-OFF engine must match it bit-for-bit too
        prompts = _prompts(89, (6, 13, 8), seed=3)
        e2 = _engine(tiny_llama(), mesh="dp1mp2", prefix_cache=False)
        assert _serve(e2, prompts) == _serve(mp1_eng, prompts)

    def test_mp2_int8_pool(self):
        # int8 is lossy vs fp but DETERMINISTIC: the sharded int8
        # engine must match the single-device int8 engine bit-for-bit
        # (quantize-on-write and fused dequant both ride the sharded
        # head axis; scales shard alongside their codes)
        prompts = _prompts(89, (5, 14, 9, 3), seed=4)
        e1 = _engine(tiny_llama(), kv_dtype="int8")
        e2 = _engine(tiny_llama(), mesh="dp1mp2", kv_dtype="int8")
        assert _serve(e1, prompts) == _serve(e2, prompts)


class TestOneTrace:
    """The mesh must not cost a single extra trace: ONE unified
    program, one-trace COW and swap programs."""

    def test_retrace_probe(self, mp2_eng, only_the_unified_step):
        # the fixture already served several batches with different
        # membership/page mixes across tests; serve one more and
        # assert the ONE-trace discipline held throughout
        prompts = _prompts(89, (7, 15, 4), seed=5)
        _serve(mp2_eng, prompts)
        only_the_unified_step(mp2_eng)

    def test_cow_and_swap_one_trace_on_sharded_pool(self):
        m = tiny_llama()
        # COW: finish a request mid-page, then two follow-ups sharing
        # the partial page force two copy-on-writes over different
        # (src, dst) pairs — ONE compiled copy program serves both,
        # moving every shard's page slice together
        eng = _engine(m, mesh="dp1mp2", num_slots=2, num_pages=17)
        base = _prompts(89, (13,), seed=6)[0]
        _serve(eng, [base], max_new=3)
        for seed in (7, 8):
            tail = _prompts(89, (5,), seed=seed)[0]
            _serve(eng, [np.concatenate([base[:13], tail])], max_new=3)
        assert eng._copy_page_fn is not None
        assert eng._copy_page_fn._cache_size() == 1
        # preemption swap: fill the pool with low-priority residents,
        # admit a high-priority head — the victim's pages swap out
        # whole-page (codes+slices of every shard together) and later
        # restore, each through ONE compiled program
        lo = [eng.add_request(p, SamplingParams(max_new_tokens=10,
                                                priority=5))
              for p in _prompts(89, (9, 12), seed=9)]
        for _ in range(4):
            eng.step()
        hi = eng.add_request(_prompts(89, (8,), seed=10)[0],
                             SamplingParams(max_new_tokens=6,
                                            priority=0))
        eng.run()
        assert all(r.finished for r in [*lo, hi])
        assert sum(r.preemptions for r in [*lo, hi]) >= 1
        assert eng._swap_out_fn._cache_size() == len(SPILL_WIDTHS)
        assert eng._swap_in_fn._cache_size() == 1
        assert eng._unified_fn._cache_size() == 1


class TestCollectives:
    """The sharded step's collective contract: zero all-reduces
    (never reassociate fp math), exactly ONE output all-gather per
    layer per step."""

    def test_compiled_hlo_census(self, mp2_eng):
        prompts = _prompts(89, (5, 8), seed=11)
        _serve(mp2_eng, prompts)
        counts = mp2_eng.collective_counts()
        assert counts["all_reduce"] == 0
        assert counts["reduce_scatter"] == 0
        assert counts["all_gather"] == mp2_eng.n_layers
        # helper sanity: the census comes from real HLO text
        assert collective_counts("x = all-gather(y)\n"
                                 "z = all-reduce(w)") == {
            "all_reduce": 1, "all_gather": 1, "reduce_scatter": 0,
            "all_to_all": 0, "collective_permute": 0}

    def test_collective_counts_needs_mesh_and_a_step(self, mp1_eng):
        with pytest.raises(ValueError, match="mesh"):
            mp1_eng.collective_counts()
        fresh = _engine(tiny_llama(), mesh="dp1mp2")
        with pytest.raises(ValueError, match="no unified step"):
            fresh.collective_counts()

    def test_flight_record_carries_per_step_collectives(self, mp2_eng,
                                                        mp1_eng):
        _serve(mp2_eng, _prompts(89, (6,), seed=12))
        rec = mp2_eng.obs.flight.snapshot()["steps"][-1]
        # the modeled per-step count: one output all-gather per layer
        assert rec["collectives"] == mp2_eng.n_layers
        _serve(mp1_eng, _prompts(89, (6,), seed=12))
        rec1 = mp1_eng.obs.flight.snapshot()["steps"][-1]
        assert rec1["collectives"] == 0


class TestGroupedShardingInterplay:
    """Grouped attention x sharding: the group operands are
    replicated scalars, the grouped walk on a SHARDED pool stays
    token-identical to flat, and the DMA model counts per-shard."""

    def test_grouped_walk_on_sharded_pool_token_identical(
            self, mp1_eng, mp2_eng):
        # both fixtures run the grouped walk (they have a prefix
        # cache); a
        # shared-prefix trace forms real groups over the SHARDED pool
        # and the tokens must still match the single-device engine
        # bit-for-bit (test_grouped_attention.py holds grouped == flat
        # on one device, so this chains to flat). Zero extra engine
        # compiles.
        sysp = _prompts(89, (21,), seed=30)[0]
        prompts = [np.concatenate([sysp, t])
                   for t in _prompts(89, (3, 5, 2), seed=31)]
        before = mp2_eng.metrics.snapshot(
        )["shared_page_reads_saved_total"]
        t1 = _serve(mp1_eng, [sysp], max_new=2)
        t2 = _serve(mp2_eng, [sysp], max_new=2)
        assert t1 == t2
        assert _serve(mp1_eng, prompts, max_new=6) == \
            _serve(mp2_eng, prompts, max_new=6)
        after = mp2_eng.metrics.snapshot(
        )["shared_page_reads_saved_total"]
        assert after > before        # groups really formed + saved

    @pytest.mark.slow
    def test_grouped_vs_flat_on_sharded_pool(self):
        m = tiny_llama()
        sysp = _prompts(89, (21,), seed=13)[0]
        prompts = [np.concatenate([sysp, t])
                   for t in _prompts(89, (3, 5, 2, 9), seed=14)]
        runs = {}
        # the flat walk is what an engine without a prefix cache
        # compiles: nothing can put a page into two rows' tables
        for grouped in (True, False):
            eng = _engine(m, mesh="dp1mp2", prefix_cache=grouped)
            assert eng.grouped is grouped
            _serve(eng, [sysp], max_new=2)     # warm the radix tree
            runs[grouped] = (_serve(eng, prompts, max_new=6), eng)
        assert runs[True][0] == runs[False][0]
        # groups really formed on the sharded pool (reads saved > 0)
        snap = runs[True][1].metrics.snapshot()
        assert snap["shared_page_reads_saved_total"] > 0
        assert runs[True][1]._unified_fn._cache_size() == 1

    def test_group_operands_ride_replicated(self):
        # the grouped-walk operands are [S] host scalars; on the mesh
        # they enter the step fully replicated — operand data, never
        # sharded state
        pt = np.array([[1, 2, 0], [1, 2, 0], [3, 0, 0]], np.int32)
        gid, gld, gcn = shared_prefix_groups(pt, np.array([1, 1, 1]))
        tp = ServingTP(1, 2)
        for arr in (gid, gld, gcn):
            dev = tp.replicate(np.asarray(arr))
            assert dev.sharding.is_fully_replicated

    def test_per_shard_read_model_scales_with_mp(self):
        # one shared span of 2 pages across 3 rows + a private tail
        pt = np.array([[1, 2, 4, 0], [1, 2, 5, 0], [1, 2, 6, 7]],
                      np.int32)
        pos = np.array([20, 20, 28])
        q_len = np.array([1, 1, 1])
        gid, gld, gcn = shared_prefix_groups(pt, q_len)
        base_flat, base_grp, sizes = count_page_block_reads(
            pt, pos, q_len, gid, gcn, page_size=8)
        assert base_grp < base_flat and sizes == [3]
        # n_kv=4: per-chip reads drop with mp (each chip walks
        # n_kv/mp local heads over 1/mp page slices)
        per_chip = {}
        for mp in (1, 2, 4):
            f, g, _ = count_page_block_reads(
                pt, pos, q_len, gid, gcn, page_size=8, n_kv=4, mp=mp)
            per_chip[mp] = (f, g)
        assert per_chip[1] == (4 * base_flat, 4 * base_grp)
        assert per_chip[2] == (2 * base_flat, 2 * base_grp)
        assert per_chip[4] == (base_flat, base_grp)
        # per-chip reads SAVED by grouping scale the same way
        saved = {mp: f - g for mp, (f, g) in per_chip.items()}
        assert saved[1] == 2 * saved[2] == 4 * saved[4] > 0


class TestObservability:
    def test_metrics_and_debug_state_tags(self, mp2_eng):
        snap = mp2_eng.metrics.snapshot()
        assert snap["mesh"] == "dp1xmp2"
        assert (snap["mp"], snap["dp"]) == (2, 1)
        assert snap["pool"]["shard_bytes_per_page"] * 2 == \
            snap["pool"]["bytes_per_page"]
        st = mp2_eng.debug_state()
        assert st["config"]["mesh"] == "dp1xmp2"
        assert (st["config"]["mp"], st["config"]["dp"]) == (2, 1)

    def test_prometheus_render_mesh_labels_valid(self, mp2_eng):
        text = prometheus_render({"r0": mp2_eng.metrics.snapshot()})
        info = [ln for ln in text.splitlines()
                if ln.startswith("paddle_serving_engine_info")]
        assert len(info) == 1
        assert 'mesh="dp1xmp2"' in info[0]
        assert 'mp="2"' in info[0] and 'dp="1"' in info[0]
        shard = [ln for ln in text.splitlines()
                 if ln.startswith("paddle_serving_pool_shard_bytes_per_page")]
        assert len(shard) == 1 and shard[0].split()[-1] != "0"
        # every line is exposition-shaped (the strict cross-field
        # checks live in test_serving_obs's format suite)
        rx = re.compile(
            r'^[A-Za-z_:][A-Za-z0-9_:]*'
            r'(\{[A-Za-z0-9_]+="[^"]*"(,[A-Za-z0-9_]+="[^"]*")*\})?'
            r' -?[0-9.eE+\-]+(inf|nan)?$')
        for ln in text.splitlines():
            if not ln or ln.startswith("#"):
                continue
            assert rx.match(ln), ln


@pytest.mark.slow
class TestMp4Matrix:
    """The deep matrix on the full 8-device budget: GPT (H_kv=4)
    shards at mp=4; every serving feature stays bit-token-identical
    to its single-device twin."""

    def _pair(self, **kw):
        m = tiny_gpt()
        prompts = _prompts(97, (5, 9, 17, 3, 12, 7), seed=20)
        e1 = _engine(m, **kw)
        e2 = _engine(m, mesh="dp1mp4", **kw)
        return _serve(e1, prompts), _serve(e2, prompts), e2

    def test_mp4_fp(self):
        t1, t2, eng = self._pair()
        assert t1 == t2
        counts = eng.collective_counts()
        assert counts["all_reduce"] == 0
        assert counts["all_gather"] == eng.n_layers

    def test_mp4_int8(self):
        t1, t2, _ = self._pair(kv_dtype="int8")
        assert t1 == t2

    def test_mp4_fp8(self):
        t1, t2, _ = self._pair(kv_dtype="fp8")
        assert t1 == t2

    def test_mp4_prefix_off(self):
        t1, t2, _ = self._pair(prefix_cache=False)
        assert t1 == t2

    def test_mp4_spec(self):
        t1, t2, _ = self._pair(spec="ngram:3")
        assert t1 == t2

    def test_mp4_preempt_swap(self):
        m = tiny_gpt()
        outs = {}
        for mesh in (None, "dp2mp4"):          # all 8 devices
            eng = _engine(m, mesh=mesh, num_slots=2, num_pages=17)
            lo = [eng.add_request(p, SamplingParams(
                max_new_tokens=10, priority=5))
                for p in _prompts(97, (9, 12), seed=21)]
            for _ in range(4):
                eng.step()
            hi = eng.add_request(
                _prompts(97, (8,), seed=22)[0],
                SamplingParams(max_new_tokens=6, priority=0))
            eng.run()
            assert sum(r.preemptions for r in [*lo, hi]) >= 1
            outs[mesh] = [list(r.output_tokens) for r in [*lo, hi]]
        assert outs[None] == outs["dp2mp4"]


@pytest.mark.slow
def test_serving_bench_tp_ab_smoke(tmp_path, monkeypatch):
    """The --tp-ab bench end to end: schema v12, token identity,
    residents-per-chip win and the pinned collective census all
    asserted by the script itself."""
    import importlib.util
    import json
    import sys

    spec = importlib.util.spec_from_file_location(
        "serving_bench", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "scripts", "serving_bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = str(tmp_path / "BENCH_serving.json")
    monkeypatch.setattr(sys, "argv",
                        ["serving_bench.py", "--smoke", "--requests",
                         "3", "--tp-ab", "--out", out])
    mod.main()
    with open(out) as f:
        report = json.load(f)
    assert report["schema_version"] == 19
    tp = report["tp"]
    assert tp["token_identical"] is True
    assert tp["residents_ratio"] >= 1.5
    assert tp["collectives"]["all_reduce"] == 0
    assert tp["output_collectives_per_layer_step"] == 1.0
    assert tp["mp2"]["page_bytes_per_chip"] * 2 == \
        tp["mp2"]["page_bytes"]
