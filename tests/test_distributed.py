"""Distributed tests on the 8-device virtual CPU mesh (conftest forces
xla_force_host_platform_device_count=8 — the SURVEY.md §4 'fake one-chip
mesh backend' strategy)."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
import paddle_tpu.optimizer as opt
import paddle_tpu.distributed as dist
from paddle_tpu.distributed import fleet


@pytest.fixture()
def hcg():
    # function-scoped: conftest's autouse reset tears fleet down after
    # every test, so each test re-inits (cheap — no process groups).
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 2,
                               "pp_degree": 1, "sharding_degree": 1,
                               "sep_degree": 2}
    fleet.init(is_collective=True, strategy=strategy)
    return fleet.get_hybrid_communicate_group()


def _randn(*shape):
    return np.random.RandomState(sum(shape)).randn(*shape).astype("float32")


class TestTopology:
    def test_axes(self, hcg):
        assert hcg.get_data_parallel_world_size() == 2
        assert hcg.get_model_parallel_world_size() == 2
        assert hcg.get_sep_parallel_world_size() == 2
        assert hcg.get_pipe_parallel_world_size() == 1

    def test_comm_topology_ranks(self):
        from paddle_tpu.distributed.fleet.topology import \
            CommunicateTopology
        topo = CommunicateTopology(["data", "model"], [2, 4])
        assert topo.world_size() == 8
        assert topo.get_rank(data=1, model=2) == 6
        comm = topo.get_comm_list("model")
        assert comm == [[0, 1, 2, 3], [4, 5, 6, 7]]


class TestTensorParallel:
    def test_column_row_roundtrip(self, hcg):
        col = fleet.ColumnParallelLinear(16, 32, has_bias=True,
                                         gather_output=False)
        row = fleet.RowParallelLinear(32, 16, input_is_parallel=True)
        x = paddle.to_tensor(_randn(8, 16), stop_gradient=False)
        y = row(col(x))
        assert y.shape == [8, 16]
        y.mean().backward()
        assert col.weight.grad is not None
        assert row.weight.grad is not None

    def test_matches_dense(self, hcg):
        # TP result must equal plain linear with the same weights
        col = fleet.ColumnParallelLinear(8, 12, has_bias=True,
                                         gather_output=True)
        x = paddle.to_tensor(_randn(4, 8))
        got = col(x).numpy()
        want = x.numpy() @ col.weight.numpy() + col.bias.numpy()
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_vocab_parallel_embedding(self, hcg):
        emb = fleet.VocabParallelEmbedding(64, 16)
        ids = paddle.to_tensor(np.array([[1, 63], [0, 32]]))
        out = emb(ids)
        np.testing.assert_allclose(
            out.numpy(), emb.weight.numpy()[ids.numpy()], rtol=1e-6)

    def test_parallel_cross_entropy(self, hcg):
        ce = fleet.ParallelCrossEntropy()
        logits = paddle.to_tensor(_randn(4, 32), stop_gradient=False)
        label = paddle.to_tensor(np.array([1, 5, 31, 0]))
        loss = ce(logits, label)
        assert loss.shape == [4, 1]
        loss.mean().backward()
        assert logits.grad is not None


class TestRingAttention:
    def test_matches_flash_reference(self, hcg):
        qn = _randn(2, 8, 2, 16)
        q = paddle.to_tensor(qn, stop_gradient=False)
        out = dist.ring_attention(q, q, q, causal=True)
        qq = paddle.to_tensor(qn)
        ref = F.scaled_dot_product_attention(qq, qq, qq, is_causal=True)
        np.testing.assert_allclose(out.numpy(), ref.numpy(), rtol=2e-2,
                                   atol=2e-3)

    def test_noncausal_and_grad(self, hcg):
        qn, kn, vn = _randn(1, 8, 2, 8), _randn(1, 8, 2, 8), \
            _randn(1, 8, 2, 8)
        q = paddle.to_tensor(qn, stop_gradient=False)
        k = paddle.to_tensor(kn, stop_gradient=False)
        v = paddle.to_tensor(vn, stop_gradient=False)
        out = dist.ring_attention(q, k, v, causal=False)
        ref = F.scaled_dot_product_attention(
            paddle.to_tensor(qn), paddle.to_tensor(kn),
            paddle.to_tensor(vn))
        np.testing.assert_allclose(out.numpy(), ref.numpy(), rtol=2e-2,
                                   atol=2e-3)
        out.sum().backward()
        assert q.grad is not None and k.grad is not None


class TestCollectives:
    def test_all_reduce_sum(self, hcg):
        g = dist.new_group(axis_name="mp")
        t = paddle.to_tensor(np.ones(4, "float32"))
        dist.all_reduce(t, group=g)
        np.testing.assert_allclose(t.numpy(), 2 * np.ones(4))

    def test_all_gather(self, hcg):
        g = dist.new_group(axis_name="dp")
        out = []
        dist.all_gather(out, paddle.to_tensor(np.arange(3)), group=g)
        assert len(out) == 2

    def test_reduce_scatter(self, hcg):
        g = dist.new_group(axis_name="mp")
        t = paddle.to_tensor(np.zeros(2, "float32"))
        parts = [paddle.to_tensor(np.full(2, 3.0, "float32")),
                 paddle.to_tensor(np.full(2, 3.0, "float32"))]
        dist.reduce_scatter(t, parts, group=g)
        np.testing.assert_allclose(t.numpy(), [6.0, 6.0])

    def test_in_program_collectives(self, hcg):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from paddle_tpu.distributed import shard_ops
        mesh = dist.get_mesh().jax_mesh

        def f(x):
            return shard_ops.psum(x, "mp")

        g = jax.shard_map(f, mesh=mesh, in_specs=P("mp"),
                          out_specs=P("mp"))
        x = jnp.arange(8.0)
        out = g(x)
        assert out.shape == (8,)


class TestMoE:
    def test_forward_backward(self, hcg):
        moe = dist.MoELayer(16, experts=[nn.Linear(16, 16)
                                         for _ in range(4)],
                            gate={"type": "gshard", "top_k": 2})
        x = paddle.to_tensor(_randn(2, 6, 16), stop_gradient=False)
        y = moe(x)
        assert y.shape == [2, 6, 16]
        (y.mean() + moe.aux_loss * 0.01).backward()
        assert moe.gate.gate.weight.grad is not None

    def test_capacity_covers_tokens(self, hcg):
        # with generous capacity every token is routed: outputs nonzero
        moe = dist.MoELayer(8, experts=[nn.Identity() for _ in range(2)],
                            gate={"type": "naive", "top_k": 1},
                            capacity_factor=4.0)
        x = paddle.to_tensor(np.abs(_randn(1, 4, 8)) + 0.5)
        y = moe(x)
        assert float(np.abs(y.numpy()).sum()) > 0


class TestShardedTraining:
    def test_group_sharded_levels(self, hcg):
        for level in ("os", "os_g", "p_g_os"):
            model = nn.Sequential(nn.Linear(16, 32), nn.ReLU(),
                                  nn.Linear(32, 16))
            o = opt.Adam(1e-3, parameters=model.parameters())
            model, o = dist.group_sharded_parallel(model, o, level=level)
            x = paddle.to_tensor(_randn(8, 16))
            model(x).mean().backward()
            o.step()
            o.clear_grad()

    def test_recompute_matches_plain(self, hcg):
        from paddle_tpu.distributed.fleet.utils import recompute
        lin = nn.Linear(8, 8)
        x = paddle.to_tensor(_randn(4, 8), stop_gradient=False)
        y1 = recompute(lambda v: F.relu(lin(v)), x)
        y2 = F.relu(lin(paddle.to_tensor(x.numpy())))
        np.testing.assert_allclose(y1.numpy(), y2.numpy(), rtol=1e-5)
        y1.mean().backward()
        assert lin.weight.grad is not None

    def test_dp_batch_sharding(self, hcg):
        model = paddle.DataParallel(nn.Linear(16, 4))
        x = dist.shard_batch(paddle.to_tensor(_randn(8, 16)))
        y = model(x)
        assert y.shape == [8, 4]


class TestPipeline:
    def test_pipeline_layer_segmentation(self):
        from paddle_tpu.distributed.fleet.meta_parallel import (
            LayerDesc, PipelineLayer)
        descs = [LayerDesc(nn.Linear, 8, 8) for _ in range(6)]
        pp = PipelineLayer(descs, num_stages=2,
                           loss_fn=nn.CrossEntropyLoss())
        assert pp.segment_parts == [0, 3, 6]
        x = paddle.to_tensor(_randn(2, 8))
        assert pp(x).shape == [2, 8]

    def test_pipeline_parallel_train_batch(self, hcg):
        from paddle_tpu.distributed.fleet.meta_parallel import (
            LayerDesc, PipelineLayer, PipelineParallel)
        import paddle_tpu.optimizer as popt
        descs = [LayerDesc(nn.Linear, 8, 8), LayerDesc(nn.ReLU),
                 LayerDesc(nn.Linear, 8, 4)]
        pp = PipelineLayer(descs, num_stages=1,
                           loss_fn=nn.CrossEntropyLoss())
        strategy = fleet.DistributedStrategy()
        strategy.pipeline_configs = {"accumulate_steps": 2}
        runner = PipelineParallel(pp, strategy=strategy)
        o = popt.SGD(0.01, parameters=pp.parameters())
        x = paddle.to_tensor(_randn(4, 8))
        y = paddle.to_tensor(np.array([0, 1, 2, 3]))
        loss = runner.train_batch((x, y), o)
        assert np.isfinite(float(loss))


class _ResBlock(nn.Layer):
    """Shape-preserving homogeneous block for pipeline stacking tests."""

    def __init__(self, d):
        super().__init__()
        self.fc1 = nn.Linear(d, 2 * d)
        self.fc2 = nn.Linear(2 * d, d)

    def forward(self, x):
        return self.fc2(F.gelu(self.fc1(x))) + x


def _pp_fixture(pp_degree, dp_degree=1):
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": dp_degree, "mp_degree": 1,
                               "pp_degree": pp_degree,
                               "sharding_degree": 1, "sep_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    return fleet.get_hybrid_communicate_group()


class TestCompiledPipeline:
    """The GPipe schedule compiled over the pp mesh axis: loss parity
    with sequential execution + stage ownership of parameters
    (review round-1 item 3)."""

    def _build(self, n_blocks, num_stages, d=16, seed=7):
        from paddle_tpu.distributed.fleet.meta_parallel import (
            PipelineLayer)
        paddle.seed(seed)
        blocks = [_ResBlock(d) for _ in range(n_blocks)]
        pre = nn.Linear(d, d)
        post = nn.Linear(d, d)
        pp = PipelineLayer([pre] + blocks + [post],
                           num_stages=num_stages)
        return pp, pre, blocks, post

    def _ref_forward(self, pre, blocks, post, x):
        h = pre(x)
        for b in blocks:
            h = b(h)
        return post(h)

    @pytest.mark.parametrize("pp_degree", [2, 4])
    def test_loss_and_grad_parity(self, pp_degree):
        _pp_fixture(pp_degree, dp_degree=1)
        pp, pre, blocks, post = self._build(4, pp_degree)
        assert pp._pipelined
        x_np = _randn(8, 16)
        y_np = _randn(8, 16)

        x = paddle.to_tensor(x_np, stop_gradient=False)
        out = pp(x, num_microbatches=4)
        loss = F.mse_loss(out, paddle.to_tensor(y_np))
        loss.backward()
        stacked_grads = [np.asarray(sp.grad.numpy())
                         for sp in pp._stacked]
        loss_pipe = float(loss)
        for p in pp.parameters():
            p.clear_gradient()

        x2 = paddle.to_tensor(x_np, stop_gradient=False)
        ref = self._ref_forward(pre, blocks, post, x2)
        loss_ref = F.mse_loss(ref, paddle.to_tensor(y_np))
        loss_ref.backward()

        np.testing.assert_allclose(loss_pipe, float(loss_ref),
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(out.numpy(), ref.numpy(),
                                   rtol=2e-4, atol=2e-5)
        # stacked grad slice i == block i's grad (same name order)
        names = pp._stack_names
        for k, name in enumerate(names):
            for i, b in enumerate(blocks):
                want = dict(b.named_parameters())[name].grad.numpy()
                np.testing.assert_allclose(
                    stacked_grads[k][i], want, rtol=2e-3, atol=2e-4,
                    err_msg=f"{name} block {i}")

    def test_stage_owns_param_shard(self):
        _pp_fixture(4)
        pp, *_ = self._build(8, 4)
        import jax
        from jax.sharding import NamedSharding
        for sp in pp._stacked:
            sh = sp._value.sharding
            assert isinstance(sh, NamedSharding)
            assert sh.spec[0] == "pp"
            local = sp._value.addressable_shards[0].data.shape
            assert local[0] == 8 // 4  # 1/num_stages of the layer stack

    def test_microbatch_counts_agree(self):
        _pp_fixture(2)
        pp, *_ = self._build(4, 2)
        x = paddle.to_tensor(_randn(8, 16))
        o1 = pp(x, num_microbatches=2).numpy()
        o2 = pp(x, num_microbatches=4).numpy()
        np.testing.assert_allclose(o1, o2, rtol=2e-4, atol=2e-5)

    def test_train_batch_compiled_path(self):
        from paddle_tpu.distributed.fleet.meta_parallel import (
            PipelineParallel)
        import paddle_tpu.optimizer as popt
        _pp_fixture(2, dp_degree=2)
        pp, *_ = self._build(4, 2)
        pp._loss_fn = nn.MSELoss()
        strategy = fleet.DistributedStrategy()
        strategy.pipeline_configs = {"accumulate_steps": 4}
        runner = PipelineParallel(pp, strategy=strategy)
        o = popt.SGD(0.05, parameters=pp.parameters())
        x = paddle.to_tensor(_randn(8, 16))
        y = paddle.to_tensor(_randn(8, 16))
        losses = [float(runner.train_batch((x, y), o)) for _ in range(4)]
        assert losses[-1] < losses[0]

    def test_gpt_pipe_matches_dense(self):
        from paddle_tpu.nlp import (GPTConfig, GPTForCausalLM,
                                    GPTForCausalLMPipe)
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 2,
                                   "pp_degree": 2, "sharding_degree": 1,
                                   "sep_degree": 1}
        fleet.init(is_collective=True, strategy=strategy)
        cfg = GPTConfig(vocab_size=128, hidden_size=32,
                        num_hidden_layers=4, num_attention_heads=4,
                        max_position_embeddings=16,
                        hidden_dropout_prob=0.0,
                        attention_probs_dropout_prob=0.0)
        paddle.seed(0)
        pipe = GPTForCausalLMPipe(cfg)
        paddle.seed(0)
        ref = GPTForCausalLM(cfg)
        pipe.eval()
        ref.eval()
        ids = paddle.to_tensor(
            np.random.RandomState(0).randint(0, 128, (4, 8)))
        np.testing.assert_allclose(pipe(ids).numpy(), ref(ids).numpy(),
                                   rtol=2e-4, atol=2e-4)

    def test_no_mesh_fallback_scan(self):
        pp, pre, blocks, post = self._build(4, 2)
        # no fleet.init: stacked params exist but run via plain scan
        x_np = _randn(4, 16)
        out = pp(paddle.to_tensor(x_np))
        ref = self._ref_forward(pre, blocks, post,
                                paddle.to_tensor(x_np))
        np.testing.assert_allclose(out.numpy(), ref.numpy(),
                                   rtol=2e-4, atol=2e-5)


class TestPipelineSchedules:
    """1F1B and interleaved virtual-pipeline schedules (review r2
    item 2; reference fleet/meta_parallel/pipeline_parallel.py:119
    1F1B, :463 interleave)."""

    def _build(self, n_blocks, num_stages, d=16, seed=7, vpp=None):
        from paddle_tpu.distributed.fleet.meta_parallel import (
            PipelineLayer)
        paddle.seed(seed)
        blocks = [_ResBlock(d) for _ in range(n_blocks)]
        pre = nn.Linear(d, d)
        post = nn.Linear(d, d)
        pp = PipelineLayer([pre] + blocks + [post],
                           num_stages=num_stages,
                           loss_fn=nn.MSELoss(),
                           num_virtual_pipeline_stages=vpp)
        return pp, pre, blocks, post

    @pytest.mark.parametrize("pp_degree,dp_degree",
                             [(2, 1), (4, 1), (2, 2)])
    def test_1f1b_matches_gpipe(self, pp_degree, dp_degree):
        """Same loss and same grads (stacked AND hetero pre/post) as
        the AD-transposed GPipe schedule, M >= S microbatches."""
        _pp_fixture(pp_degree, dp_degree)
        pp, pre, blocks, post = self._build(4, pp_degree)
        assert pp._pipelined
        x_np, y_np = _randn(8, 16), _randn(8, 16)

        out = pp(paddle.to_tensor(x_np), num_microbatches=4)
        loss_g = F.mse_loss(out, paddle.to_tensor(y_np))
        loss_g.backward()
        g_stack = [sp.grad.numpy().copy() for sp in pp._stacked]
        g_het = [p.grad.numpy().copy() for p in pp._hetero_params]
        for p in pp.parameters():
            p.clear_gradient()

        loss_f = pp.train_step_1f1b(paddle.to_tensor(x_np),
                                    paddle.to_tensor(y_np),
                                    num_microbatches=4)
        np.testing.assert_allclose(float(loss_f), float(loss_g),
                                   rtol=2e-4, atol=2e-5)
        for sp, want in zip(pp._stacked, g_stack):
            np.testing.assert_allclose(sp.grad.numpy(), want,
                                       rtol=2e-3, atol=2e-4)
        for p, want in zip(pp._hetero_params, g_het):
            np.testing.assert_allclose(p.grad.numpy(), want,
                                       rtol=2e-3, atol=2e-4)

    def test_1f1b_more_microbatches_than_stages(self):
        _pp_fixture(2)
        pp, *_ = self._build(4, 2)
        x_np, y_np = _randn(8, 16), _randn(8, 16)
        out = pp(paddle.to_tensor(x_np), num_microbatches=8)
        loss_g = float(F.mse_loss(out, paddle.to_tensor(y_np)))
        loss_f = float(pp.train_step_1f1b(paddle.to_tensor(x_np),
                                          paddle.to_tensor(y_np),
                                          num_microbatches=8))
        np.testing.assert_allclose(loss_f, loss_g, rtol=2e-4, atol=2e-5)

    def test_train_batch_1f1b_schedule(self):
        from paddle_tpu.distributed.fleet.meta_parallel import (
            PipelineParallel)
        import paddle_tpu.optimizer as popt
        _pp_fixture(2, dp_degree=2)
        pp, *_ = self._build(4, 2)
        strategy = fleet.DistributedStrategy()
        strategy.pipeline_configs = {"accumulate_steps": 4,
                                     "schedule_mode": "1F1B"}
        runner = PipelineParallel(pp, strategy=strategy)
        o = popt.SGD(0.05, parameters=pp.parameters())
        x = paddle.to_tensor(_randn(8, 16))
        y = paddle.to_tensor(_randn(8, 16))
        losses = [float(runner.train_batch((x, y), o)) for _ in range(4)]
        assert losses[-1] < losses[0]

    @pytest.mark.parametrize("vpp", [2, 4])
    def test_interleaved_forward_parity(self, vpp):
        _pp_fixture(2)
        pp, pre, blocks, post = self._build(8, 2, vpp=vpp)
        assert pp._vpp == vpp
        x_np = _randn(8, 16)
        out = pp(paddle.to_tensor(x_np), num_microbatches=4)
        h = pre(paddle.to_tensor(x_np))
        for b in blocks:
            h = b(h)
        ref = post(h)
        np.testing.assert_allclose(out.numpy(), ref.numpy(),
                                   rtol=2e-4, atol=2e-5)

    def test_interleaved_backward_parity(self):
        _pp_fixture(2)
        pp, pre, blocks, post = self._build(8, 2, vpp=2)
        x_np, y_np = _randn(8, 16), _randn(8, 16)
        out = pp(paddle.to_tensor(x_np), num_microbatches=4)
        loss = F.mse_loss(out, paddle.to_tensor(y_np))
        loss.backward()
        stacked_grads = [sp.grad.numpy().copy() for sp in pp._stacked]
        for p in pp.parameters():
            p.clear_gradient()
        # stacked slice j holds block _stack_order[j]'s grad
        x2 = paddle.to_tensor(x_np)
        h = pre(x2)
        for b in blocks:
            h = b(h)
        ref_loss = F.mse_loss(post(h), paddle.to_tensor(y_np))
        ref_loss.backward()
        for k, name in enumerate(pp._stack_names):
            got = stacked_grads[k]
            for j, bi in enumerate(pp._stack_order):
                want = dict(blocks[bi].named_parameters())[name] \
                    .grad.numpy()
                np.testing.assert_allclose(got[j], want, rtol=2e-3,
                                           atol=2e-4,
                                           err_msg=f"{name} slot {j}")

    def test_gpt_1f1b_matches_dense_train(self):
        """Hetero first/last stages for real: embedding inside stage 0,
        tied LM head + CrossEntropy inside stage S-1."""
        from paddle_tpu.nlp import (GPTConfig, GPTForCausalLM,
                                    GPTForCausalLMPipe)
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1,
                                   "pp_degree": 2, "sharding_degree": 1,
                                   "sep_degree": 1}
        fleet.init(is_collective=True, strategy=strategy)
        cfg = GPTConfig(vocab_size=128, hidden_size=32,
                        num_hidden_layers=4, num_attention_heads=4,
                        max_position_embeddings=16,
                        hidden_dropout_prob=0.0,
                        attention_probs_dropout_prob=0.0)
        paddle.seed(0)
        pipe = GPTForCausalLMPipe(cfg)
        paddle.seed(0)
        ref = GPTForCausalLM(cfg)
        rng = np.random.RandomState(0)
        ids = paddle.to_tensor(rng.randint(0, 128, (4, 8)))
        labels = paddle.to_tensor(rng.randint(0, 128, (4, 8)))

        loss_f = pipe.pipeline.train_step_1f1b(ids, labels,
                                               num_microbatches=2)
        loss_r = ref(ids, labels=labels)
        loss_r.backward()
        np.testing.assert_allclose(float(loss_f), float(loss_r),
                                   rtol=2e-4, atol=2e-4)
        # tied word-embedding grad (stage-0 embed + stage-1 head psum)
        emb_p = next(p for p in pipe.pipeline._hetero_params
                     if "embedding" in p.name.lower()
                     or p.shape == [128, 32])
        want = ref.gpt.embeddings.word_embeddings.weight.grad
        np.testing.assert_allclose(emb_p.grad.numpy(), want.numpy(),
                                   rtol=2e-3, atol=2e-4)

    def test_vpp_layout_mismatch_is_loud(self):
        """A checkpoint saved with a different vpp rebinds the layout
        buffer; the next forward must raise, not silently permute."""
        _pp_fixture(2)
        pp_v2, *_ = self._build(8, 2, vpp=2)
        sd = {k: v.numpy() for k, v in pp_v2.state_dict().items()}
        _pp_fixture(2)
        pp_v1, *_ = self._build(8, 2, vpp=None)
        pp_v1.set_state_dict(sd)
        with pytest.raises(ValueError, match="virtual_pipeline"):
            pp_v1(paddle.to_tensor(_randn(4, 16)))

    def test_1f1b_trains_closure_params(self):
        """A bare-callable pipeline entry referencing a Layer through
        its closure must still get grads under 1F1B."""
        from paddle_tpu.distributed.fleet.meta_parallel import (
            PipelineLayer)
        _pp_fixture(2)
        paddle.seed(3)
        proj = nn.Linear(16, 16)

        def head(x):
            return proj(x)

        blocks = [_ResBlock(16) for _ in range(4)]
        pp = PipelineLayer([nn.Linear(16, 16)] + blocks + [head],
                           num_stages=2, loss_fn=nn.MSELoss())
        assert any(p is proj.weight for p in pp._hetero_params)
        pp.train_step_1f1b(paddle.to_tensor(_randn(4, 16)),
                           paddle.to_tensor(_randn(4, 16)),
                           num_microbatches=2)
        assert proj.weight.grad is not None
        assert float(proj.weight.grad.abs().sum()) > 0

    def test_sequential_fallback_warns(self):
        from paddle_tpu.distributed.fleet.meta_parallel import (
            PipelineLayer)
        _pp_fixture(2)
        # heterogeneous: alternating widths -> no stackable run
        layers = [nn.Linear(16, 32), nn.Linear(32, 16),
                  nn.Linear(16, 8), nn.Linear(8, 16)]
        with pytest.warns(UserWarning, match="SEQUENTIALLY"):
            pp = PipelineLayer(layers, num_stages=2)
        assert not pp._pipelined
        x = paddle.to_tensor(_randn(4, 16))
        assert pp(x).shape == [4, 16]


class TestRNGTracker:
    def test_streams_differ(self):
        from paddle_tpu.distributed.fleet.utils import RNGStatesTracker
        tr = RNGStatesTracker()
        tr.add("a", 100)
        tr.add("b", 200)
        with tr.rng_state("a"):
            x1 = paddle.rand([4])
        with tr.rng_state("b"):
            x2 = paddle.rand([4])
        assert not np.allclose(x1.numpy(), x2.numpy())


class TestMeshLifecycle:
    def test_fleet_shutdown_resets_mesh(self):
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 2,
                                   "pp_degree": 1, "sharding_degree": 1,
                                   "sep_degree": 2}
        fleet.init(is_collective=True, strategy=strategy)
        assert dist.get_mesh() is not None
        fleet.shutdown()
        assert dist.get_mesh() is None

    def test_train_after_fleet_session(self):
        # the round-1 suite-order failure: a model trained after an
        # earlier fleet session must not see mixed device placements
        from paddle_tpu import jit as pjit
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 8, "mp_degree": 1,
                                   "pp_degree": 1, "sharding_degree": 1,
                                   "sep_degree": 1}
        fleet.init(is_collective=True, strategy=strategy)
        m_dist = nn.Linear(4, 4)
        fleet.distributed_model(m_dist)  # placed on the 8-dev mesh
        fleet.shutdown()
        model = nn.Linear(4, 4)
        o = opt.SGD(learning_rate=0.1, parameters=model.parameters())
        step = pjit.compile_train_step(
            lambda x, y: ((model(x) - y) ** 2).mean(), model, o)
        x = paddle.to_tensor(_randn(2, 4))
        y = paddle.to_tensor(_randn(2, 4))
        loss = step(x, y)
        assert np.isfinite(float(loss))

    def test_trainer_harmonizes_stale_mesh_params(self, hcg):
        # model built under an active mesh, trained while mesh active,
        # with a straggler param created... (placement mix): params were
        # placed by distributed_model; a later-added param lives on one
        # device until CompiledTrainStep harmonizes it.
        from paddle_tpu import jit as pjit
        model = nn.Linear(4, 4)
        fleet.distributed_model(model)
        # new param created fresh (single-device committed)
        import paddle_tpu
        model.extra = paddle_tpu.core.tensor.Parameter(
            __import__("jax.numpy", fromlist=["x"]).zeros((4,)))
        o = opt.SGD(learning_rate=0.1, parameters=model.parameters())
        step = pjit.compile_train_step(
            lambda x, y: ((model(x) + model.extra - y) ** 2).mean(),
            model, o)
        x = paddle.to_tensor(_randn(2, 4))
        y = paddle.to_tensor(_randn(2, 4))
        assert np.isfinite(float(step(x, y)))

    def test_gshard_aux_loss_has_gradient(self, hcg):
        moe = dist.MoELayer(8, experts=[nn.Linear(8, 8) for _ in range(4)],
                            gate={"type": "gshard", "top_k": 2})
        x = paddle.to_tensor(_randn(2, 8, 8), stop_gradient=False)
        moe(x)
        aux = moe.aux_loss
        aux.backward()
        g = moe.gate.gate.weight.grad
        assert g is not None
        assert float(np.abs(g.numpy()).max()) > 0.0


@pytest.fixture()
def ep_hcg():
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "ep_degree": 4}
    fleet.init(is_collective=True, strategy=strategy)
    return fleet.get_hybrid_communicate_group()


def _deterministic_experts(n, d, hidden):
    rs = np.random.RandomState(7)
    experts = []
    for _ in range(n):
        mlp = nn.Sequential(nn.Linear(d, hidden), nn.GELU(),
                            nn.Linear(hidden, d))
        for p in mlp.parameters():
            p.set_value(paddle.to_tensor(
                rs.randn(*p.shape).astype("float32") * 0.1))
        experts.append(mlp)
    return experts


class TestExpertParallel:
    """review round-1 item 5: physical expert parallelism — stacked
    expert weights live sharded over the ep axis, each device owns
    E/ep_degree experts."""

    def test_topology_has_ep_axis(self, ep_hcg):
        assert ep_hcg.get_expert_parallel_world_size() == 4
        assert "ep" in ep_hcg.mesh.dim_names

    def test_stacked_params_sharded_over_ep(self, ep_hcg):
        moe = dist.MoELayer(16, experts=_deterministic_experts(8, 16, 32),
                            gate={"type": "gshard", "top_k": 2})
        assert moe._stacked_names, "experts should stack"
        for name in moe._stacked_names:
            p = getattr(moe, name)
            assert p.shape[0] == 8
            spec = p._value.sharding.spec
            assert spec and spec[0] == "ep", f"{name}: {spec}"
            # physical ownership: every device shard holds E/ep experts
            for s in p._value.addressable_shards:
                assert s.data.shape[0] == 2
        # stacked params are what the optimizer sees; per-expert templates
        # are only initializers
        names = [n for n, _ in moe.named_parameters()]
        assert sum(n.startswith("expert__") for n in names) == \
            len(moe._stacked_names)

    def test_ep_matches_replicated(self, ep_hcg):
        # same weights, same tokens: GSPMD expert-parallel execution must
        # be numerically identical to the single-device run
        experts = _deterministic_experts(8, 16, 32)
        paddle.seed(11)
        moe = dist.MoELayer(16, experts=experts,
                            gate={"type": "naive", "top_k": 2},
                            capacity_factor=8.0)
        moe.eval()
        x = paddle.to_tensor(_randn(4, 6, 16))
        y = moe(x).numpy()

        fleet.shutdown()
        experts2 = _deterministic_experts(8, 16, 32)
        paddle.seed(11)
        moe2 = dist.MoELayer(16, experts=experts2,
                             gate={"type": "naive", "top_k": 2},
                             capacity_factor=8.0)
        moe2.eval()
        y2 = moe2(x).numpy()
        np.testing.assert_allclose(y, y2, rtol=2e-5, atol=2e-5)

    def test_backward_reaches_stacked_experts(self, ep_hcg):
        moe = dist.MoELayer(16, experts=_deterministic_experts(4, 16, 32),
                            gate={"type": "gshard", "top_k": 2})
        x = paddle.to_tensor(_randn(2, 8, 16), stop_gradient=False)
        y = moe(x)
        (y.mean() + moe.aux_loss * 0.01).backward()
        for name in moe._stacked_names:
            g = getattr(moe, name).grad
            assert g is not None
            assert np.isfinite(g.numpy()).all()


class TestGates:
    """Gate algorithm unit tests vs the reference semantics
    (moe/gate/{gshard,switch}_gate.py)."""

    def _dispatch(self, probs_logits, key, **attrs):
        import jax
        from paddle_tpu.distributed.moe import _moe_dispatch_fwd
        T, E = probs_logits.shape
        x = np.ones((T, 4), dtype="float32")
        defaults = dict(n_expert=E, topk=2, capacity=T,
                        second_policy="all", jitter_eps=0.0, training=True)
        defaults.update(attrs)
        import jax.numpy as jnp
        return _moe_dispatch_fwd(jnp.asarray(x), jnp.asarray(probs_logits),
                                 key, **defaults)

    def test_aux_loss_uniform_is_one(self):
        import jax.numpy as jnp
        from paddle_tpu.distributed.moe import _gshard_aux
        T, E = 32, 4
        probs = jnp.full((T, E), 1.0 / E)
        onehot = jnp.zeros((T, 2, E)).at[:, 0, 0].set(1.0)
        onehot = onehot.at[:, 1, 1].set(1.0)
        # me uniform (1/E), all top-1 on expert 0 -> aux = E * (1/E * 1) = 1
        assert abs(float(_gshard_aux(probs, onehot)) - 1.0) < 1e-6

    def test_aux_loss_collapsed_is_E(self):
        import jax.numpy as jnp
        from paddle_tpu.distributed.moe import _gshard_aux
        T, E = 32, 4
        probs = jnp.zeros((T, E)).at[:, 0].set(1.0)
        onehot = jnp.zeros((T, 2, E)).at[:, 0, 0].set(1.0)
        assert abs(float(_gshard_aux(probs, onehot)) - E) < 1e-6

    def test_gshard_random_routing_drops_weak_second(self):
        import jax
        # expert 0 dominant: p2 ~ 0 -> second expert essentially never
        # kept; tokens land only in expert 0's buffer
        logits = np.zeros((16, 4), dtype="float32")
        logits[:, 0] = 20.0
        expert_in, combine, _ = self._dispatch(
            logits, jax.random.PRNGKey(0), second_policy="random")
        assert float(np.abs(np.asarray(expert_in)[1:]).sum()) < 1e-5

    def test_gshard_random_routing_keeps_strong_second(self):
        import jax
        # two equal experts: p2 = 0.5, 2*p2 = 1.0 > uniform -> always kept
        logits = np.zeros((16, 4), dtype="float32")
        logits[:, 0] = 5.0
        logits[:, 1] = 5.0
        expert_in, combine, _ = self._dispatch(
            logits, jax.random.PRNGKey(0), second_policy="random")
        assert float(np.abs(np.asarray(expert_in)[1]).sum()) > 1.0

    def test_capacity_drops_overflow(self):
        import jax
        # all 8 tokens want expert 0, capacity 2 -> only 2 dispatched
        logits = np.zeros((8, 4), dtype="float32")
        logits[:, 0] = 20.0
        expert_in, combine, _ = self._dispatch(
            logits, jax.random.PRNGKey(0), topk=1, capacity=2)
        buf0 = np.asarray(expert_in)[0]
        assert float(np.abs(buf0[:2]).sum()) > 0
        assert float(np.abs(np.asarray(combine)).sum()) <= 2 * 1.0 + 1e-5

    def test_switch_gate_is_top1_with_jitter(self, hcg):
        moe = dist.MoELayer(8, experts=[nn.Linear(8, 8) for _ in range(4)],
                            gate={"type": "switch"})
        assert moe.topk == 1
        assert moe.gate.jitter_eps > 0
        x = paddle.to_tensor(_randn(2, 4, 8))
        y = moe(x)
        assert y.shape == [2, 4, 8]
        # eval mode: jitter off, deterministic
        moe.eval()
        y1 = moe(x).numpy()
        y2 = moe(x).numpy()
        np.testing.assert_allclose(y1, y2, rtol=1e-6)


@pytest.fixture()
def shard8_hcg():
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"sharding_degree": 8}
    fleet.init(is_collective=True, strategy=strategy)
    return fleet.get_hybrid_communicate_group()


def _per_device_nbytes(arr):
    shards = arr.addressable_shards
    sizes = {s.data.nbytes for s in shards}
    assert len(sizes) == 1, "uneven shards"
    return sizes.pop()


class TestZeroMemoryScaling:
    """review round-1 item 10: measure per-device live bytes across
    ZeRO stages on the 8-device mesh and assert the ~1/n scaling the
    reference achieves by explicit partitioning
    (group_sharded_optimizer_stage2.py:53, stage3.py:61)."""

    def _train_once(self, level):
        model = nn.Sequential(nn.Linear(64, 128), nn.ReLU(),
                              nn.Linear(128, 64))
        o = opt.Adam(learning_rate=1e-3,
                     parameters=model.parameters())
        out = dist.group_sharded_parallel(model, o, level)
        model, o = out[0], out[1]
        x = paddle.to_tensor(_randn(8, 64))
        y = paddle.to_tensor(_randn(8, 64))
        loss = ((model(x) - y) ** 2).mean()
        loss.backward()
        o.step()
        return model, o, float(loss)

    def test_stage1_optimizer_states_one_eighth(self, shard8_hcg):
        model, o, loss = self._train_once("os")
        assert np.isfinite(loss)
        checked = 0
        for st in o._accumulators.values():
            for name, arr in st.items():
                if arr.size < 8:
                    continue  # beta-pow scalars stay replicated
                assert _per_device_nbytes(arr) == arr.nbytes // 8, name
                checked += 1
        assert checked >= 4  # both moments for both weight matrices
        # params NOT sharded at stage 1
        for p in model.parameters():
            assert _per_device_nbytes(p._value) == p._value.nbytes

    def test_stage2_grads_one_eighth(self, shard8_hcg):
        model, o, _ = self._train_once("os_g")
        checked = 0
        for p in model.parameters():
            g = p.grad._value
            if g.size < 8:
                continue
            spec = g.sharding.spec
            assert any(ax == "sharding" for ax in spec if ax), spec
            assert _per_device_nbytes(g) == g.nbytes // 8
            checked += 1
        assert checked >= 2

    def test_stage3_params_one_eighth(self, shard8_hcg):
        model, o, _ = self._train_once("p_g_os")
        checked = 0
        for p in model.parameters():
            if p._value.size < 8:
                continue
            assert _per_device_nbytes(p._value) == p._value.nbytes // 8
            checked += 1
        assert checked >= 2

    def test_per_device_total_shrinks_with_stage(self, shard8_hcg):
        def total(level):
            model, o, _ = self._train_once(level)
            n = 0
            for p in model.parameters():
                n += _per_device_nbytes(p._value)
                if p.grad is not None:
                    n += _per_device_nbytes(p.grad._value)
            for st in o._accumulators.values():
                for arr in st.values():
                    n += _per_device_nbytes(arr)
            return n

        t1, t2, t3 = total("os"), total("os_g"), total("p_g_os")
        assert t2 < t1 * 0.8, (t1, t2)     # grads now 1/8
        assert t3 < t2 * 0.7, (t2, t3)     # params too

    def test_stage_parity_with_dense(self, shard8_hcg):
        # numerics must not change with sharding level
        losses = {}
        for level in ("os", "os_g", "p_g_os"):
            paddle.seed(3)
            _, _, losses[level] = self._train_once(level)
        assert abs(losses["os"] - losses["os_g"]) < 1e-5
        assert abs(losses["os"] - losses["p_g_os"]) < 1e-5


class TestUlyssesAttention:
    """DeepSpeed-Ulysses style all-to-all sequence parallelism — the
    second SP mode next to ring attention."""

    def _qkv(self, b=2, l=16, h=8, d=16):
        rs = np.random.RandomState(0)
        mk = lambda: paddle.to_tensor(
            rs.randn(b, l, h, d).astype("float32") * 0.3,
            stop_gradient=False)
        return mk(), mk(), mk()

    def _dense(self, q, k, v, causal):
        import paddle_tpu.nn.functional as F
        return F.scaled_dot_product_attention(
            paddle.to_tensor(q.numpy()), paddle.to_tensor(k.numpy()),
            paddle.to_tensor(v.numpy()), is_causal=causal)

    def test_matches_dense(self, hcg):
        for causal in (False, True):
            q, k, v = self._qkv()
            out = dist.ulysses_attention(q, k, v, causal=causal)
            want = self._dense(q, k, v, causal)
            np.testing.assert_allclose(out.numpy(), want.numpy(),
                                       rtol=2e-3, atol=2e-3)

    def test_backward(self, hcg):
        q, k, v = self._qkv()
        out = dist.ulysses_attention(q, k, v, causal=True)
        out.mean().backward()
        for t in (q, k, v):
            g = t.grad
            assert g is not None and np.isfinite(g.numpy()).all()
        assert float(np.abs(q.grad.numpy()).sum()) > 0

    def test_head_divisibility_error(self, hcg):
        rs = np.random.RandomState(1)
        mk = lambda h: paddle.to_tensor(
            rs.randn(1, 8, h, 8).astype("float32"))
        with pytest.raises(Exception, match="divisible|ring"):
            dist.ulysses_attention(mk(3), mk(3), mk(3))

    def test_fallback_without_sep(self):
        # no mesh: plain SDPA path
        q, k, v = self._qkv(h=4)
        out = dist.ulysses_attention(q, k, v, causal=True)
        want = self._dense(q, k, v, True)
        np.testing.assert_allclose(out.numpy(), want.numpy(),
                                   rtol=2e-3, atol=2e-3)


class TestZeroOffload:
    """review round-2 item 9: group_sharded_parallel(offload=True).
    pinned_host memory kinds need a TPU/GPU backend (the CPU PJRT
    backend aborts on host-kind executable inputs), so on the CPU mesh
    the call must degrade gracefully — sharding still applies, a warning
    fires, training proceeds. scripts/offload_check.py measures the
    device-memory drop on the real chip (recorded in BASELINE.md)."""

    def test_offload_graceful_on_cpu_and_training_works(self, shard8_hcg):
        import warnings as _w
        model = nn.Sequential(nn.Linear(64, 128), nn.ReLU(),
                              nn.Linear(128, 64))
        o = opt.Adam(learning_rate=1e-3, parameters=model.parameters())
        with _w.catch_warnings(record=True) as rec:
            _w.simplefilter("always")
            model, o = dist.group_sharded_parallel(model, o, "os",
                                                   offload=True)
        assert any("offload" in str(r.message) for r in rec)
        x = paddle.to_tensor(_randn(8, 64))
        y = paddle.to_tensor(_randn(8, 64))
        losses = []
        for _ in range(3):
            loss = ((model(x) - y) ** 2).mean()
            loss.backward()
            o.step()
            o.clear_grad()
            losses.append(float(loss))
        assert losses[-1] < losses[0]
        # states still sharded 1/8 despite the offload fallback
        checked = 0
        for st in o._accumulators.values():
            for name, arr in st.items():
                if arr.size < 8:
                    continue
                assert _per_device_nbytes(arr) == arr.nbytes // 8
                checked += 1
        assert checked >= 4

    @pytest.mark.skipif(
        __import__("jax").devices()[0].platform not in ("tpu", "gpu"),
        reason="pinned_host memory kind needs TPU/GPU PJRT")
    def test_offload_states_in_host_memory(self):
        model = nn.Sequential(nn.Linear(32, 64), nn.ReLU(),
                              nn.Linear(64, 32))
        o = opt.Adam(learning_rate=1e-3, parameters=model.parameters())
        model, o = dist.group_sharded_parallel(model, o, "os",
                                               offload=True)
        x = paddle.to_tensor(_randn(4, 32))
        loss = (model(x) ** 2).mean()
        loss.backward()
        o.step()
        kinds = {getattr(v.sharding, "memory_kind", None)
                 for s in o._accumulators.values() for v in s.values()}
        assert kinds == {"pinned_host"}


class TestGradientMergeLocalSGD:
    """DistributedStrategy gradient_merge + localsgd knobs (reference
    distributed_strategy.proto:81-104, localsgd_optimizer.py)."""

    def test_gradient_merge_matches_full_batch(self):
        import paddle_tpu.nn as nn
        import paddle_tpu.nn.functional as F
        import paddle_tpu.optimizer as opt
        from paddle_tpu import jit

        def build():
            paddle.seed(7)
            m = nn.Sequential(nn.Linear(6, 16), nn.Tanh(),
                              nn.Linear(16, 3))
            o = opt.SGD(learning_rate=0.1, parameters=m.parameters())
            return m, o

        rng = np.random.RandomState(3)
        x = rng.randn(8, 6).astype(np.float32)
        y = rng.randint(0, 3, (8,))

        m1, o1 = build()
        s1 = jit.compile_train_step(
            lambda a, b: F.cross_entropy(m1(a), b), m1, o1)
        s1(paddle.to_tensor(x), paddle.to_tensor(y))

        m2, o2 = build()
        s2 = jit.compile_train_step(
            lambda a, b: F.cross_entropy(m2(a), b), m2, o2,
            accumulate_steps=4)
        s2(paddle.to_tensor(x), paddle.to_tensor(y))

        # mean-reduction loss: average of 4 micro-grads == full-batch
        # grad, so one merged update must equal one full-batch update
        for p1, p2 in zip(m1.parameters(), m2.parameters()):
            np.testing.assert_allclose(p1.numpy(), p2.numpy(),
                                       rtol=2e-5, atol=2e-6)

    def test_gradient_merge_via_fleet_strategy(self):
        import paddle_tpu.nn as nn
        import paddle_tpu.optimizer as opt
        import paddle_tpu.distributed.fleet as fleet

        strategy = fleet.DistributedStrategy()
        strategy.gradient_merge = True
        strategy.gradient_merge_configs = {"k_steps": 2, "avg": True}
        fleet.init(is_collective=True, strategy=strategy)
        try:
            m = nn.Linear(4, 2)
            o = opt.SGD(learning_rate=0.1, parameters=m.parameters())
            o = fleet.distributed_optimizer(o)
            assert getattr(o, "_gradient_merge_k", None) == 2
            from paddle_tpu.jit.trainer import CompiledTrainStep
            import paddle_tpu.nn.functional as F
            step = CompiledTrainStep(
                lambda a, b: F.mse_loss(m(a), b), m, o)
            assert step.accumulate_steps == 2
        finally:
            fleet.shutdown()

    def test_localsgd_wrapper_counts_and_syncs(self):
        import paddle_tpu.nn as nn
        import paddle_tpu.optimizer as opt
        import paddle_tpu.distributed.fleet as fleet

        strategy = fleet.DistributedStrategy()
        strategy.localsgd = True
        strategy.localsgd_configs = {"k_steps": 3}
        fleet.init(is_collective=True, strategy=strategy)
        try:
            m = nn.Linear(4, 2)
            o = opt.SGD(learning_rate=0.1, parameters=m.parameters())
            wrapped = fleet.distributed_optimizer(o)
            assert isinstance(wrapped, fleet.LocalSGDOptimizer)
            syncs = []
            wrapped.sync_params = lambda: syncs.append(
                wrapped._local_steps)
            x = paddle.to_tensor(
                np.random.RandomState(0).randn(4, 4).astype("float32"))
            import paddle_tpu.nn.functional as F
            for _ in range(7):
                loss = F.mse_loss(m(x), x[:, :2])
                loss.backward()
                wrapped.step()
                wrapped.clear_grad()
            assert syncs == [3, 6]
            # single-process world: real sync_params is an exact no-op
            del wrapped.__dict__["sync_params"]
            before = [p.numpy().copy() for p in m.parameters()]
            wrapped.sync_params()
            for b, p in zip(before, m.parameters()):
                np.testing.assert_array_equal(b, p.numpy())
        finally:
            fleet.shutdown()

    def test_gradient_merge_sum_semantics(self):
        """avg=False keeps the reference's sum semantics: the SGD update
        is k x the averaged one."""
        import paddle_tpu.nn as nn
        import paddle_tpu.nn.functional as F
        import paddle_tpu.optimizer as opt
        from paddle_tpu import jit

        rng = np.random.RandomState(4)
        x = rng.randn(8, 4).astype(np.float32)
        y = rng.randn(8, 2).astype(np.float32)

        def build(avg):
            paddle.seed(9)
            m = nn.Linear(4, 2)
            o = opt.SGD(learning_rate=0.1, parameters=m.parameters())
            o._gradient_merge_k = 4
            o._gradient_merge_avg = avg
            w0 = m.weight.numpy().copy()
            s = jit.compile_train_step(
                lambda a, b: F.mse_loss(m(a), b), m, o)
            s(paddle.to_tensor(x), paddle.to_tensor(y))
            return w0, m.weight.numpy()

        w0a, wa = build(True)
        w0s, ws = build(False)
        np.testing.assert_allclose(ws - w0s, (wa - w0a) * 4,
                                   rtol=2e-4, atol=1e-6)

    def test_lars_strategy_swaps_optimizer(self):
        import paddle_tpu.nn as nn
        import paddle_tpu.optimizer as opt
        import paddle_tpu.distributed.fleet as fleet
        strategy = fleet.DistributedStrategy()
        strategy.lars = True
        strategy.lars_configs = {"lars_coeff": 0.002}
        fleet.init(is_collective=True, strategy=strategy)
        try:
            m = nn.Linear(4, 2)
            o = fleet.distributed_optimizer(
                opt.Momentum(learning_rate=0.1, momentum=0.8,
                             parameters=m.parameters()))
            assert isinstance(o, opt.LarsMomentum)
            assert o._coeff == 0.002 and o._momentum == 0.8
        finally:
            fleet.shutdown()
