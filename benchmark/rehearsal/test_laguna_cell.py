"""Rehearsals of what PR 29 added to the yardstick, on the CPU:

    python -m pytest benchmark/rehearsal -q

The tiny Laguna cell end to end (kind serve_http_laguna, ref_laguna's
check, the new counters through the new readers), the `sessions`
generator (same schedule from the same `shape_seed`, Zipf shares, every
system prompt sent in the ramp) and its tiny cell, and the new readers
on synthetic observations.
"""
import json
import os

import numpy as np
import pytest

from benchmark import ref_laguna
from benchmark.readers import moe_roofline, share_of_sum
from benchmark.rehearsal.test_rehearsal import ROOT, _run
from benchmark.traffic import sessions


def _line(proc):
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("trace_flag, expected", [
    ("0", {"setup_s", "ttft_p50_ms", "itl_p95_ms"}),
    ("1", {"step.wall_ms.steady",
           "where.x.moe.experts_hit_per_layer_step.code",
           "where.x.moe.here_share.code",
           "where.x.kv_window.skipped_share.code",
           "where.engine.plan_ms_per_step.steady",
           "rehearsal.requests_per_s"}),
])
def test_tiny_laguna_cell_end_to_end(trace_flag, expected):
    """Shares of the device's trace and of its peak are left out on the
    CPU; the counters' metrics are read."""
    proc = _run("tiny.laguna", "--trace", trace_flag)
    line = _line(proc)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == expected
    assert "compilations inside the window: 0" in proc.stdout
    assert "switched off for this model" in proc.stdout
    if trace_flag == "1":
        m = {k: v["value"] for k, v in line["metrics"].items()}
        assert 0 < m["where.x.moe.experts_hit_per_layer_step.code"] <= 8
        assert 30 < m["where.x.moe.here_share.code"] < 70
        assert 0 < m["where.x.kv_window.skipped_share.code"] < 100


def test_tiny_sessions_cell_shares_prefixes():
    proc = _run("tiny.sessions", "--trace", "1")
    line = _line(proc)
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"]["where.walk_group.engaged_share.steady"][
        "value"] > 0
    assert "compilations inside the window: 0" in proc.stdout


def _mix():
    with open(os.path.join(ROOT,
                           "benchmark/traffic/prefix_sessions.json")) as f:
        return dict(json.load(f), rate_rps=3.0)


def test_sessions_schedule_is_the_mix_not_the_seed():
    mix = _mix()
    long, short = (sessions.schedule(mix, s) for s in (51, 20))
    assert long[:len(short)] == short           # a shorter run is a prefix
    assert long == sessions.schedule(mix, 51)
    assert 2.2 < len(long) / (mix["ramp_s"] + 51) < 3.8
    assert all(0 <= w < 8 and 32 <= n <= 256 and 4 <= m <= 64
               for _, w, n, m in long)
    fast = sessions.schedule(dict(mix, rate_rps=6.0), 20)
    assert [x[1:] for x in fast[:len(short)]] == [x[1:] for x in short]
    heads = sessions.system_prompts(mix, 50304)
    assert heads.shape == (8, 1024)
    assert (heads == sessions.system_prompts(mix, 50304)).all()


def test_sessions_zipf_shares():
    shares = sessions.zipf_shares(8, 1.1)
    assert shares.sum() == pytest.approx(1.0)
    assert shares[0] / shares[1] == pytest.approx(2 ** 1.1)
    mix = dict(_mix(), rate_rps=200.0)
    which = np.array([w for _, w, _, _ in sessions.schedule(mix, 51)])
    seen = np.bincount(which, minlength=8) / which.size
    assert np.abs(seen - shares).max() < 0.02


def test_sessions_sends_every_system_prompt_in_the_ramp():
    """Against a recording `send`: the eight residents go out, and
    return, before the window opens, every prompt is a system prompt
    plus a tail, and only what is due inside the window is counted."""
    mix = dict(_mix(), rate_rps=20.0, ramp_s=0.3, drain_s=2)
    sent, opened = [], []

    def send(prompt, max_tokens, stream):
        sent.append((len(opened), prompt, max_tokens, stream))
        return {"status": 200, "tokens": [1] * max_tokens,
                "t_tokens": [0.0] * max_tokens, "error": None,
                "t_done": 0.0, "finish": "length"}
    res = sessions.drive(mix, 7, 0.5, 50304, send, lambda: None,
                         lambda: opened.append(1), lambda: opened.append(2))
    heads = sessions.system_prompts(mix, 50304).tolist()
    first = [p for before, p, m, stream in sent
             if m == 1 and not stream and not before]
    assert sorted(p[:1024] for p in first) == sorted(heads)
    assert 0 <= res["residents_done_s"] < mix["ramp_s"]
    for _, p, _, _ in sent:
        assert p[:1024] in heads and 1056 <= len(p) <= 1280
    plan = sessions.schedule(mix, 0.5)
    assert len(res["records"]) == sum(d >= 0.3 for d, *_ in plan)
    assert res["offered"] == len(plan)
    assert all(r["prompt"][:1024] == heads[r["system_prompt"]]
               for r in res["records"])
    again = sessions.drive(mix, 7, 0.5, 50304, send, lambda: None,
                           lambda: None, lambda: None)
    assert [r["prompt"] for r in again["records"]] == \
        [r["prompt"] for r in res["records"]]


class _Chip:
    config = {"hidden_size": 3072, "moe_intermediate_size": 1024}
    rehearsal = False

    def peak(self, what):
        return {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}[what]


def test_moe_roofline_reader_on_a_synthetic_observation():
    """60 experts hit and 160 assignments a layer-step, 400 layer-steps
    while the trace ran, the kernel busy for 0.8 s: 60 x 18.9 MB of
    weights at 819 GB/s is 1.385 ms of the 2 ms a layer-step."""
    obs = {"trace": {"ops": {"moe_experts custom-call": 0.8,
                             "fusion": 2.0},
                     "text": {"moe_experts custom-call":
                              "x kernel_metadata ptk:moe_experts y",
                              "fusion": "%fusion.1"},
                     "busy_s": 3.5, "chips": 1},
           "engine_traced": {"moe_experts_hit_total": 60 * 400,
                             "moe_assignments_here_total": 160 * 400}}
    args = dict(kernels=["ptk:moe_experts"], hit="moe_experts_hit_total",
                here="moe_assignments_here_total")
    byts = ref_laguna.expert_step_bytes(60, 160, 3072, 1024)
    assert byts == 60 * 3 * 3072 * 1024 * 2 + 160 * 2 * 3072 * 2
    assert ref_laguna.expert_step_flops(160, 3072, 1024) == \
        160 * 6 * 3072 * 1024
    got = moe_roofline.read(obs, _Chip(), **args)
    assert got == pytest.approx(100 * (byts / 819e9) / 2e-3, rel=1e-9)
    assert 69 < got < 70
    # compute-bound where many rows share an expert's weights
    dense = dict(obs, engine_traced={"moe_experts_hit_total": 128,
                                     "moe_assignments_here_total": 200000})
    assert moe_roofline.read(dense, _Chip(), **args) == pytest.approx(
        100 * (200000 * 6 * 3072 * 1024 / 197e12) / 0.8)
    # nothing to read: the parent's line, an untraced run, the CPU
    assert moe_roofline.read(dict(obs, engine_traced={}), _Chip(),
                             **args) is None
    assert moe_roofline.read(dict(obs, trace=None), _Chip(), **args) is None
    no_kernel = dict(obs, trace=dict(obs["trace"], text={
        "moe_experts custom-call": "other", "fusion": "%fusion.1"}))
    assert moe_roofline.read(no_kernel, _Chip(), **args) is None


def test_share_of_sum_reader():
    obs = {"engine": {"a": 30, "b": 10}}
    assert share_of_sum.read(obs, None, "engine", ["a"], ["a", "b"],
                             scale=100.0) == 75.0
    assert share_of_sum.read(obs, None, "engine", ["a"], ["a", "c"]) is None
    assert share_of_sum.read({"engine": {"a": 0, "b": 0}}, None, "engine",
                             ["a"], ["a", "b"]) is None
