"""Rehearsals of what the MiMo-V2-Flash configuration added to the
yardstick, on the CPU:

    python -m pytest benchmark/rehearsal/test_mimo_cell.py -q

The tiny MiMo-V2-Flash cell end to end (kind serve_http_mimo,
ref_mimo_v2's check, the split walks' and the router's counters through
their readers), the three controls through the cell's own comparison,
the new roofline reader on a synthetic observation, and the real
configuration's file against the model's and the reference's readings
of it.
"""
import json
import os
import subprocess
import sys

import pytest

from benchmark.readers import split_roofline
from benchmark.rehearsal.test_rehearsal import ROOT, _run


def _line(proc):
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("trace_flag, expected", [
    ("0", {"setup_s", "ttft_p50_ms", "itl_p95_ms"}),
    ("1", {"step.wall_ms.steady",
           "where.x.moe.experts_hit_per_layer_step.code",
           "where.x.moe.here_share.code",
           "where.x.kv_window.skipped_share.code",
           "where.zzzz.split_walk.keys_per_row.long",
           "where.y.walk_grid.share.steady",
           "where.engine.plan_ms_per_step.steady",
           "rehearsal.requests_per_s"}),
])
def test_tiny_mimo_cell_end_to_end(trace_flag, expected):
    """Shares of the device's trace and of its peak are left out on the
    CPU; the counters' metrics are read."""
    proc = _run("tiny.mimo", "--trace", trace_flag)
    line = _line(proc)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == expected
    assert "compilations inside the window: 0" in proc.stdout
    assert "switched off for this model" in proc.stdout
    if trace_flag == "1":
        m = {k: v["value"] for k, v in line["metrics"].items()}
        assert 0 < m["where.x.moe.experts_hit_per_layer_step.code"] <= 4
        # prompts of 12-100 tokens and answers of 2-24: a row's full
        # layers see tens of keys
        assert 12 < m["where.zzzz.split_walk.keys_per_row.long"] < 125
        # the full layers' walk is bounded by walk_grid_bounds at their
        # own rep, so the counted grid share is part of the whole grid
        assert 0 < m["where.y.walk_grid.share.steady"] <= 100


def test_controls_go_through_the_cells_own_comparison():
    """scripts/mimo_controls_reading.py: one whole run of the tiny cell
    through run.py's `main`, then the reference without the sinks,
    without the selection bias and over fp8 weights, each judged on the
    run's own sample by `ref_mimo_v2.judge_choices` and `passes`: the
    engine comes out correct, no control does."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("PADDLE_TPU_PALLAS_INTERPRET",
                        "PADDLE_TPU_FORCE_CPU_DEVICES")}
    proc = subprocess.run(
        [sys.executable, "scripts/mimo_controls_reading.py", "--workload",
         "tiny.mimo", "--seed", "3000000011", "--seconds", "3"], cwd=ROOT,
        env=dict(env, JAX_PLATFORMS="cpu"), capture_output=True, text=True,
        timeout=900)
    assert _line(proc)["correct"] is True
    for name in ("no_sinks", "no_bias", "all_matrices_fp8"):
        assert f"control {name}: correct false" in proc.stdout, name


class _Chip:
    with open(os.path.join(ROOT, "benchmark/configs/"
                           "z.mimo-v2-flash-serve-ep16.json")) as f:
        config = json.load(f)
    rehearsal = False

    def peak(self, what):
        return {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}[what]


def test_split_roofline_reader_on_a_synthetic_observation():
    """While the trace ran: the full layers' walk scored 1e9 (query, key)
    pairs over 2e7 distinct keys in 1 s, the window layers' 2e8 pairs
    over 4e6 keys in 0.5 s. A pair costs 2 x 64 x (192 + 128) operations
    in both kinds; a key 4 x 320 x 2 bytes in a full layer, 8 x 320 x 2
    in a window layer."""
    obs = {"trace": {"ops": {"split.1": 1.0, "sink.1": 0.5, "fusion": 2.0},
                     "text": {"split.1": "x ptk:split_walk y",
                              "sink.1": "x ptk:sink_walk z",
                              "fusion": "%fusion.1"},
                     "busy_s": 3.5, "chips": 1},
           "engine_traced": {"split_walk_pairs_total": 1e9,
                             "split_walk_keys_total": 2e7,
                             "sink_walk_pairs_total": 2e8,
                             "sink_walk_keys_total": 4e6}}
    split = dict(kernels=["ptk:split_walk"], pairs="split_walk_pairs_total",
                 keys="split_walk_keys_total", window=False)
    sink = dict(kernels=["ptk:sink_walk"], pairs="sink_walk_pairs_total",
                keys="sink_walk_keys_total", window=True)
    flops = 1e9 * 2 * 64 * 320 / 197e12         # 0.208 s
    bytes_ = 2e7 * 4 * 320 * 2 / 819e9          # 0.0625 s
    assert split_roofline.read(obs, _Chip(), **split) == pytest.approx(
        100 * max(flops, bytes_) / 1.0)
    flops = 2e8 * 2 * 64 * 320 / 197e12
    bytes_ = 4e6 * 8 * 320 * 2 / 819e9
    assert split_roofline.read(obs, _Chip(), **sink) == pytest.approx(
        100 * max(flops, bytes_) / 0.5)
    # the metric files' arguments are these
    for name, args in (("split_walk", split), ("sink_walk", sink)):
        with open(os.path.join(ROOT, "benchmark/metrics/where.zzzz." + name
                               + ".roofline_share.long.json")) as f:
            spec = json.load(f)
        assert spec["reader"] == "split_roofline" and spec["args"] == args
    # nothing to read: the parent's line, an untraced run, the CPU
    for bad in (dict(obs, engine_traced={}), dict(obs, engine_traced=None),
                dict(obs, trace=None)):
        assert split_roofline.read(bad, _Chip(), **split) is None
    no_kernel = dict(obs, trace=dict(obs["trace"], text={
        k: "other" for k in obs["trace"]["text"]}))
    assert split_roofline.read(no_kernel, _Chip(), **split) is None


def test_configuration_is_the_catalogs_cut_as_it_says():
    """The file's source keys are the published ones except what
    `reduced` names; the model reads it into the published widths and
    the router's whole width; the reference reads the same geometry."""
    from benchmark import ref_mimo_v2
    from benchmark.kinds import serve_http_mimo
    from paddle_tpu.nlp import MiMoV2Config
    cfg = _Chip.config
    assert cfg["published"] == {"num_hidden_layers": 48,
                                "n_routed_experts": 256,
                                "vocab_size": 152576}
    kw = serve_http_mimo.model_config(cfg)
    model = MiMoV2Config(**kw)
    assert model.n_routed_experts == 256 and model.num_local_experts == 16
    assert [model.window_of(i) for i in range(7)] == [None] + [128] * 5 \
        + [None]
    assert model.geometry(0)[:4] == (64, 4, 192, 128)
    assert model.geometry(1)[:4] == (64, 8, 192, 128)
    rcfg = serve_http_mimo.reference_config(cfg)
    assert ref_mimo_v2.geometry(rcfg, 6)[:4] == (64, 4, 192, 128)
    assert ref_mimo_v2.walk_step_bytes(rcfg, 1, True) == 8 * 320 * 2
    assert ref_mimo_v2.walk_step_bytes(rcfg, 1, False) == 4 * 320 * 2
    assert ref_mimo_v2.walk_step_flops(rcfg, 1, True) == 2 * 64 * 320
