"""Launcher / multi-host bootstrap tests (review round-1 item 8).

Strategy mirrors the reference's TestDistBase (python/paddle/fluid/tests/
unittests/test_dist_base.py:900): spawn real OS processes on one box,
run the same model distributed vs single-process, compare numerics.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestLauncher:
    def test_dp2_step_matches_single_process(self, tmp_path):
        """2-process dp=2 SGD step == single-process step on the union
        batch (the reference's dist-vs-local loss-closeness check)."""
        out = str(tmp_path / "out.npz")
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node", "2", "--log_dir", str(tmp_path),
             "tests/launch_payload_dp.py", out],
            cwd=REPO, capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, (proc.stdout[-3000:],
                                      proc.stderr[-3000:])
        got = np.load(out)

        # single-process reference on the full 8-sample batch: the
        # distributed run's global batch is ranks' shards interleaved —
        # the same 8 samples, and mean-loss is order-invariant
        import paddle_tpu as paddle
        import paddle_tpu.nn as nn
        import paddle_tpu.optimizer as opt
        xs = (np.arange(32, dtype="float32").reshape(8, 4) / 10.0) - 1.0
        ys = (xs.sum(1, keepdims=True) * 0.5 + 0.25).astype("float32")
        paddle.seed(0)
        model = nn.Linear(4, 1)
        optimizer = opt.SGD(learning_rate=0.1,
                            parameters=model.parameters())
        loss = ((model(paddle.to_tensor(xs)) - paddle.to_tensor(ys)) ** 2
                ).mean()
        loss.backward()
        optimizer.step()

        np.testing.assert_allclose(got["loss"], float(loss), rtol=1e-5)
        np.testing.assert_allclose(got["w"], model.weight.numpy(),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(got["b"], model.bias.numpy(),
                                   rtol=1e-5, atol=1e-6)

    def test_eager_collectives_divergent_values(self, tmp_path):
        """Every eager collective primitive with DIVERGENT per-rank
        tensors must match numpy (review r2 item 1; reference
        semantics: distributed/collective.py:174, ProcessGroup.h:52).
        Assertions live in the payload; both ranks verify."""
        out = str(tmp_path / "ok.npz")
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node", "2", "--log_dir", str(tmp_path),
             "tests/launch_payload_collectives.py", out],
            cwd=REPO, capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, (proc.stdout[-3000:],
                                      proc.stderr[-3000:])
        assert np.load(out)["ok"] == 1

    def test_launcher_propagates_failure(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text("import sys; sys.exit(3)\n")
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node", "2", str(bad)],
            cwd=REPO, capture_output=True, text=True, timeout=120)
        assert proc.returncode == 3

    def test_spawn_two_processes(self, tmp_path):
        sys.path.insert(0, os.path.join(REPO, "tests"))
        try:
            from spawn_payload import worker
            from paddle_tpu.distributed.launch import spawn
            spawn(worker, args=(str(tmp_path),), nprocs=2,
                  envs={"PADDLE_TPU_FORCE_CPU_DEVICES": "1",
                        "XLA_FLAGS": ""})
        finally:
            sys.path.pop(0)
        r0 = (tmp_path / "rank0.txt").read_text().split(",")
        r1 = (tmp_path / "rank1.txt").read_text().split(",")
        assert r0 == ["0", "2", "2", "2"]
        assert r1 == ["1", "2", "2", "2"]

    def test_elastic_relaunch_after_rank_sigkill(self, tmp_path):
        """Fault injection (review r2 weak 7): SIGKILL a rank of a
        LIVE 2-process collective job mid-run; the elastic wrapper
        relaunches the pod with fresh rendezvous and the retry
        completes on both ranks."""
        from paddle_tpu.distributed.fleet.elastic import launch_elastic
        rc, mgr = launch_elastic(
            "tests/launch_payload_faulty.py",
            script_args=[str(tmp_path)], nproc_per_node=2,
            max_restarts=2, log_dir=str(tmp_path / "logs"),
            envs={"PYTHONPATH": REPO})
        assert rc == 0
        assert mgr.restarts == 1  # exactly one fault -> one relaunch
        # the SUCCESSFUL attempt is attempt 1, with both ranks done
        assert (tmp_path / "done_rank0_a1").exists()
        assert (tmp_path / "done_rank1_a1").exists()
        # attempt 0 died before completing
        assert not (tmp_path / "done_rank1_a0").exists()


def test_two_node_simulated_launch(tmp_path):
    """nnodes=2 simulated on one box: two launcher invocations
    (node_rank 0/1) sharing one --master, 2 procs each -> a dp=4 world.
    Asserts the master/node_rank plumbing end-to-end and numeric parity
    with a single-process step on the union batch (reference pattern:
    test_dist_base.py:900)."""
    from paddle_tpu.distributed.launch import find_free_port
    out = str(tmp_path / "out.npz")
    master = f"127.0.0.1:{find_free_port()}"
    nodes = []
    for node_rank in range(2):
        nodes.append(subprocess.Popen(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nnodes", "2", "--node_rank", str(node_rank),
             "--master", master, "--nproc_per_node", "2",
             "--log_dir", str(tmp_path / f"node{node_rank}"),
             "tests/launch_payload_dp4.py", out],
            cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True))
    outs = []
    for p in nodes:
        stdout, _ = p.communicate(timeout=300)
        outs.append(stdout)
        assert p.returncode == 0, stdout[-3000:]

    got = np.load(out)
    # single-process reference on the full 16-sample batch
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as opt
    xs = (np.arange(64, dtype="float32").reshape(16, 4) / 20.0) - 1.0
    ys = (xs.sum(1, keepdims=True) * 0.5 + 0.25).astype("float32")
    paddle.seed(0)
    model = nn.Linear(4, 1)
    optimizer = opt.SGD(learning_rate=0.1,
                        parameters=model.parameters())
    loss = ((model(paddle.to_tensor(xs)) - paddle.to_tensor(ys)) ** 2
            ).mean()
    loss.backward()
    optimizer.step()
    np.testing.assert_allclose(got["loss"], float(loss), rtol=1e-5)
    np.testing.assert_allclose(got["w"], model.weight.numpy(),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got["b"], model.bias.numpy(),
                               rtol=1e-5, atol=1e-6)
