"""Checks of the yardstick itself, run by hand on the CPU:

    python -m pytest benchmark/rehearsal -q

The trace reduction against a synthetic trace with known intervals; the
manifest against the files; the generators' determinism; and the tiny
cells end to end, which are added to the benchmark exactly as a later PR
adds a cell: files only (a configuration, a mix, a cell, a metric).
"""
import json
import os
import re
import subprocess
import sys

import pytest

from benchmark import manifest, stats, trace
from benchmark.traffic import open_loop_poisson

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# one chip, three operations, times in ps from the line's 1000 ns:
#   fusion.1       [1000, 3000) ns
#   custom-call.7  [2500, 4000) ns   overlaps fusion.1 by 500 ns
#   fusion.1       [9000, 10000) ns
# busy = [1000, 4000) + [9000, 10000) = 4000 ns; idle gap [4000, 9000);
# the host thread spends [3600, 8400) in "np.asarray" inside a longer
# "step" [500, 10500) that is over half the window and so not a name.
SYNTHETIC = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1500000 duration_ps: 1500000 }
    events { metadata_id: 1 offset_ps: 8000000 duration_ps: 1000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 9000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "custom-call.7" } }
  event_metadata { key: 3 value { id: 3 name: "jit_step" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 7 name: "engine" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 3600000 duration_ps: 4800000 }
  }
  event_metadata { key: 1 value { id: 1 name: "step" } }
  event_metadata { key: 2 value { id: 2 name: "np.asarray" } }
}
"""


def _synthetic():
    from jax.profiler import ProfileData
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(SYNTHETIC))


def test_trace_reduction_on_known_intervals(monkeypatch):
    monkeypatch.setattr(trace, "MIN_GAP_NS", 100)
    red = trace.reduce_xspace(_synthetic())
    assert red["chips"] == 1
    assert red["busy_s"] == pytest.approx(4000e-9)
    assert red["window_s"] == pytest.approx(10000e-9)   # 500 .. 10500 ns
    assert red["ops"]["fusion.1"] == pytest.approx(3000e-9)
    assert red["ops"]["custom-call.7"] == pytest.approx(1500e-9)
    # the long gap is the fetch's; the 500 ns at either end nobody's
    assert red["gaps"]["np.asarray"] == pytest.approx(5000e-9)
    assert sum(red["gaps"].values()) == pytest.approx(6000e-9)
    # shares are of summed operation time over busy time: overlapping
    # operations can pass 100 together, one alone cannot
    assert trace.share_of_busy(red, ["custom-call"]) == pytest.approx(37.5)
    assert trace.share_of_busy(red, ["no such kernel"]) is None
    top = trace.breakdown(red)
    assert top["device_ops"][0][0] == "fusion.1"
    assert top["idle_gaps"][0] == ["np.asarray", pytest.approx(5000e-9)]


def test_trace_without_device_operations_reduces_to_nothing():
    from jax.profiler import ProfileData
    host_only = SYNTHETIC[SYNTHETIC.index('planes { id: 2'):]
    pd = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(host_only))
    assert trace.reduce_xspace(pd) is None


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 90) == 90
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile([5.0], 90) == 5.0
    assert stats.stat({"a": {"x": []}}, "a", "x", "median") is None
    assert stats.stat({"a": {"x": [1, 3]}}, "a", "x", "median") == 2


def test_mfu_is_step_rate_times_operations_over_peak():
    """GPT-2 124M at 16 x 1024 and 146.65 ms a step on a 197 TFLOP/s chip:
    111.7k tokens/s x 860.1M operations a token = 48.8% (the reader is
    silent on the CPU, so its arithmetic is checked here)."""
    from benchmark import ref
    from benchmark.readers import mfu

    class Chip:
        cell = {"chips": 1}

        def peak(self, what):
            return 197e12
    cfg = {"vocab_size": 50304, "hidden_size": 768, "num_hidden_layers": 12,
           "num_attention_heads": 12, "max_position_embeddings": 1024}
    assert ref.n_params(cfg) == 124475904
    obs = {"train": {"tokens_per_step": 16 * 1024, "step_s": [0.14665] * 3,
                     "flops_per_token": ref.train_flops_per_token(cfg, 1024)}}
    assert mfu.read(obs, Chip()) == pytest.approx(48.78, abs=0.01)


def test_manifest_matches_the_files_and_the_contract():
    manifest.main(["--check"])
    b = manifest.build()
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    e2e = {m["name"]: m for m in b["end_to_end"]}
    cells = {w["name"] for w in b["workloads"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in b["end_to_end"] + b["per_layer"]:
        assert name.match(m["name"]) and re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$",
                                                  m["unit"])
        assert set(m.get("workloads", cells)) <= cells
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
    for c in cells:
        mine = [m for m in b["end_to_end"] if c in m.get("workloads", cells)]
        assert len(mine) >= 2
        assert any(c in m["workloads"] for m in b["per_layer"])
    for w in b["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)


def test_open_loop_schedule_is_the_mix_not_the_seed():
    with open(os.path.join(ROOT, "benchmark/traffic/chat_steady.json")) as f:
        mix = dict(json.load(f), rate_rps=2.0)
    long, short = (open_loop_poisson.schedule(mix, s) for s in (51, 20))
    assert long[:len(short)] == short           # a shorter run is a prefix
    assert long == open_loop_poisson.schedule(mix, 51)
    n = len(long) / (mix["ramp_s"] + 51)
    assert 1.5 < n < 2.5
    assert all(8 <= p <= 1024 and 4 <= m <= 64 for _, p, m in long)
    # the same sizes in the same order at another rate, only faster
    fast = open_loop_poisson.schedule(dict(mix, rate_rps=4.0), 20)
    assert [x[1:] for x in fast[:len(short)]] == [x[1:] for x in short]


def _run(cell, *extra, cpu=True):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PADDLE_TPU_PALLAS_INTERPRET",
                        "PADDLE_TPU_FORCE_CPU_DEVICES", "JAX_PLATFORMS")}
    if cpu:
        env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         "3000000011", "--seconds", "3", *extra], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=900)


@pytest.mark.parametrize("cell, trace_flag, expected", [
    ("tiny.chat", "0", {"setup_s", "ttft_p50_ms", "itl_p95_ms"}),
    ("tiny.chat", "1", {"front.ttft_overhead_ms.steady",
                        "front.ttft_p90_ms.steady",
                        "sched.queue_wait_p90_ms.steady",
                        "engine.host_ms_per_step.steady",
                        "step.wall_ms.steady", "rehearsal.requests_per_s"}),
    ("tiny.backlog", "0", {"setup_s", "serve_tok_s"}),
    ("tiny.pretrain", "0", {"setup_s", "train_tok_s"}),
    ("tiny.pretrain", "1", {"trainer.step_ms.train"}),
])
def test_tiny_cells_end_to_end(cell, trace_flag, expected):
    """What only a chip can supply (a share of the device's trace, a
    share of its peak) is left out of the line on the CPU: a reader
    that finds nothing returns nothing."""
    proc = _run(cell, "--trace", trace_flag)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == expected
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert "compilations inside the window: 0" in proc.stdout


def test_a_real_cell_measures_nothing_without_a_tpu():
    proc = _run("gpt2-124m.pretrain")
    assert proc.returncode != 0 and "no TPU" in proc.stderr
    assert '"correct"' not in proc.stdout


def test_a_rehearsal_cell_runs_only_where_the_cpu_was_asked_for():
    proc = _run("tiny.pretrain", cpu=False)
    assert proc.returncode != 0 and "rehearsal cell" in proc.stderr
    assert '"correct"' not in proc.stdout
