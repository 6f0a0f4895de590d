"""CPU rehearsal of chip_smoke.py: its serve and train phase functions
at a tiny size (kernels in interpret mode), and its failure contract —
no TPU, or a failed phase, means a non-zero exit and no result line."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

REFUSED = ("PADDLE_TPU_PALLAS_INTERPRET", "PADDLE_TPU_FORCE_CPU_DEVICES")

TINY = dict(vocab_size=256, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, max_position_embeddings=128)


def test_serve_phase_tiny_cpu(monkeypatch):
    from paddle_tpu.ops.pallas import paged_attention as pa
    monkeypatch.setattr(pa, "_INTERPRET", True)   # the Pallas walk runs
    cfg = chip_smoke.ServeConfig(
        model=dict(TINY), dtype="float32", num_slots=4, max_len=128,
        page_size=8, chunk_len=16,
        requests=((5, False), (40, False), (70, True)), max_tokens=8,
        on_chip=False)
    res = chip_smoke.serve_phase(cfg)
    assert [len(o) for o in res["outputs"]] == [8, 8, 8]
    # float32 on the CPU: the engine's tokens ARE the dense argmax
    assert res["gap"] <= 1e-4 and res["match"] == 1.0


def test_multichip_phase_tiny_cpu(monkeypatch):
    """The four-chip path on four of conftest's virtual CPU devices,
    kernels in interpret mode — so the per-device (`shard_map`) form of
    the page walk and of the fused LayerNorm runs, which the jnp
    references of the other mesh tests never reach."""
    from paddle_tpu.nn.functional import norm as fnorm
    from paddle_tpu.ops.pallas import layer_norm as pln
    from paddle_tpu.ops.pallas import paged_attention as pa
    monkeypatch.setattr(pa, "_INTERPRET", True)
    monkeypatch.setattr(pln, "_INTERPRET", True)
    monkeypatch.setattr(fnorm, "_use_pallas_ln", lambda: True)
    cfg = chip_smoke.MultiChipConfig(
        model=dict(TINY, hidden_size=128), dtype="float32",
        meshes=("dp1mp4", "dp2mp2"), num_slots=4, max_len=128,
        page_size=8, chunk_len=16, requests=((5, False), (40, False)),
        max_tokens=6,
        tolerance=1e-3, min_match=1.0, on_chip=False)
    res = chip_smoke.multichip_phase(cfg)
    assert res["tokens"]["dp1mp4"] == res["tokens"][None]
    assert res["tokens"]["dp2mp2"] == res["tokens"][None]


def test_train_phase_tiny_cpu():
    cfg = chip_smoke.TrainConfig(model=dict(TINY), batch=2, seqlen=32,
                                 on_chip=False)
    res = chip_smoke.train_phase(cfg)
    assert len(res["losses"]) == 5
    assert res["losses"][-1] < res["losses"][0]


def test_script_fails_at_device_check_on_cpu():
    # (test_pallas_layer_norm.py sets the interpret variable at import,
    # in whichever worker collects it: start from a clean request)
    env = {k: v for k, v in os.environ.items() if k not in REFUSED}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout


@pytest.mark.parametrize("var", REFUSED)
def test_device_check_refuses_cpu_and_interpret_requests(monkeypatch,
                                                         var):
    for other in REFUSED:
        monkeypatch.delenv(other, raising=False)
    monkeypatch.setenv(var, "1")
    with pytest.raises(RuntimeError, match=var):
        chip_smoke.check_device()


def test_failed_phase_is_a_failed_run(monkeypatch, capsys):
    fake = {"platform": "tpu", "kind": "fake", "count": 1}
    monkeypatch.setattr(chip_smoke, "check_device", lambda n=1: fake)
    monkeypatch.setattr(chip_smoke, "serve_phase", lambda cfg: 1 / 0)
    with pytest.raises(ZeroDivisionError):   # uncaught -> exit code 1
        chip_smoke.main([])
    assert '"ok"' not in capsys.readouterr().out
