"""Rehearsals of what PR 34 added to the yardstick, on the CPU:

    python -m pytest benchmark/rehearsal -q

The tiny DeepSeek-V2 cell end to end (kind serve_http_dsv2,
ref_deepseek_v2's check, the latent counters through their readers),
the `open_loop_bursts` generator (same schedule from the same
`shape_seed`, mean rate `rate_rps`, no arrival in an off phase, `drive`
word for word the steady generator's) and its tiny cell, the fp8
control through the cell's own comparison, and the new roofline reader
on a synthetic observation.
"""
import json
import os
import subprocess
import sys

import pytest

from benchmark.readers import latent_roofline
from benchmark.rehearsal.test_rehearsal import ROOT, _run
from benchmark.traffic import open_loop_bursts, open_loop_poisson


def _line(proc):
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("trace_flag, expected", [
    ("0", {"setup_s", "ttft_p50_ms", "itl_p95_ms"}),
    ("1", {"step.wall_ms.steady",
           "where.x.moe.experts_hit_per_layer_step.code",
           "where.x.moe.here_share.code",
           "where.z.mla_walk.keys_per_row.docs",
           "where.engine.plan_ms_per_step.steady",
           "rehearsal.requests_per_s"}),
])
def test_tiny_dsv2_cell_end_to_end(trace_flag, expected):
    """Shares of the device's trace and of its peak are left out on the
    CPU; the counters' metrics are read."""
    proc = _run("tiny.dsv2", "--trace", trace_flag)
    line = _line(proc)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == expected
    assert "compilations inside the window: 0" in proc.stdout
    assert "switched off for this model" in proc.stdout
    if trace_flag == "1":
        m = {k: v["value"] for k, v in line["metrics"].items()}
        assert 0 < m["where.x.moe.experts_hit_per_layer_step.code"] <= 4
        assert 10 < m["where.x.moe.here_share.code"] < 45
        # prompts of 12-100 tokens: a query row sees some tens of keys
        assert 5 < m["where.z.mla_walk.keys_per_row.docs"] < 100


def test_tiny_surge_cell_end_to_end():
    proc = _run("tiny.surge", "--trace", "0")
    line = _line(proc)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "itl_p95_ms"}
    assert "compilations inside the window: 0" in proc.stdout


def _mix():
    with open(os.path.join(ROOT, "benchmark/traffic/chat_surge.json")) as f:
        return dict(json.load(f), rate_rps=1.0)


def test_bursts_schedule_is_the_mix_not_the_seed():
    mix = _mix()
    long, short = (open_loop_bursts.schedule(mix, s) for s in (51, 20))
    assert long[:len(short)] == short           # a shorter run is a prefix
    assert long == open_loop_bursts.schedule(mix, 51)
    assert all(8 <= p <= 1024 and 4 <= m <= 64 for _, p, m in long)
    # the same sizes in the same order as the steady mix would offer
    steady = open_loop_poisson.schedule(dict(mix, rate_rps=3.0), 51)
    assert [x[1:] for x in long] == [x[1:] for x in steady[:len(long)]]
    other = open_loop_bursts.schedule(dict(mix, shape_seed=33), 51)
    assert [d for d, _, _ in other] != [d for d, _, _ in long]


def test_bursts_mean_rate_and_silent_off_phases():
    mix = dict(_mix(), rate_rps=40.0)
    on_s, period, phase = open_loop_bursts.cycle(mix)
    assert (on_s, period) == (2, 6) and 0 <= phase < 6
    plan = open_loop_bursts.schedule(mix, 595)          # 100 cycles
    assert len(plan) / 600 == pytest.approx(40.0, rel=0.05)
    into = [(d + phase) % period for d, _, _ in plan]
    assert max(into) < on_s                 # no arrival in an off phase
    # inside a burst the rate is three times the mean
    assert len(plan) / (600 * on_s / period) == pytest.approx(120, rel=0.05)
    with pytest.raises(ValueError, match="on_factor"):
        open_loop_bursts.schedule(dict(mix, burst={
            "on_s": 2, "off_s": 4, "on_factor": 2}), 10)


def test_bursts_drive_replays_its_own_schedule():
    mix = dict(_mix(), rate_rps=30.0, ramp_s=0.2, drain_s=2)
    sent = []

    def send(prompt, max_tokens, stream):
        sent.append(len(prompt))
        return {"status": 200, "tokens": [1] * max_tokens,
                "t_tokens": [0.0] * max_tokens, "error": None,
                "t_done": 0.0, "finish": "length"}
    res = open_loop_bursts.drive(mix, 7, 0.6, 50304, send, lambda: None,
                                 lambda: None, lambda: None)
    plan = open_loop_bursts.schedule(mix, 0.6)
    assert res["offered"] == len(plan) == len(sent)
    assert sorted(sent) == sorted(p for _, p, _ in plan)
    assert len(res["records"]) == sum(d >= 0.2 for d, _, _ in plan)


def test_bursts_drive_is_the_steady_generators_word_for_word():
    """`open_loop_bursts.drive` is a copy (the steady module's finds its
    schedule as a module global): the two texts stay equal."""
    import inspect
    assert inspect.getsource(open_loop_bursts.drive) \
        == inspect.getsource(open_loop_poisson.drive)


def test_fp8_control_goes_through_the_cells_own_comparison():
    """scripts/dsv2_precision_reading.py: one whole run of the tiny
    cell through run.py's `main`, then the reference over fp8 weights
    judged on the run's own sample by `ref_deepseek_v2.judge_choices`
    and `passes`: the engine comes out correct, fp8 weights do not."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("PADDLE_TPU_PALLAS_INTERPRET",
                        "PADDLE_TPU_FORCE_CPU_DEVICES")}
    proc = subprocess.run(
        [sys.executable, "scripts/dsv2_precision_reading.py", "--workload",
         "tiny.dsv2", "--seed", "3000000011", "--seconds", "3"], cwd=ROOT,
        env=dict(env, JAX_PLATFORMS="cpu"), capture_output=True, text=True,
        timeout=900)
    assert _line(proc)["correct"] is True
    assert "control all_matrices_fp8: correct false" in proc.stdout


class _Chip:
    config = {"kv_lora_rank": 512, "qk_rope_head_dim": 64,
              "qk_nope_head_dim": 128, "v_head_dim": 128,
              "num_attention_heads": 128}
    rehearsal = False

    def peak(self, what):
        return {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}[what]


def test_latent_roofline_reader_on_a_synthetic_observation():
    """2e9 (query, key) pairs while the trace ran, the walk busy for
    4 s. A pair costs 128 x 320 x 2 operations (81.9 kFLOP, the expanded
    form's price), a distinct key's row 1152 bytes: where chunks share
    their keys (1e8 distinct rows) the walk is bound by operations,
    where every row decodes (distinct = pairs) by bytes, 71 operations
    a byte under a ridge of 240."""
    obs = {"trace": {"ops": {"mla_walk.1": 3.0, "mla_walk.2": 1.0,
                             "fusion": 2.0},
                     "text": {"mla_walk.1": "x ptk:mla_walk y",
                              "mla_walk.2": "x ptk:mla_walk z",
                              "fusion": "%fusion.1"},
                     "busy_s": 7.0, "chips": 1},
           "engine_traced": {"mla_pairs_total": 2e9,
                             "mla_keys_distinct_total": 1e8}}
    walk = dict(kernels=["ptk:mla_walk"], pairs="mla_pairs_total",
                distinct="mla_keys_distinct_total")
    got = latent_roofline.read(obs, _Chip(), **walk)
    assert got == pytest.approx(100 * (2e9 * 128 * 320 * 2 / 197e12) / 4.0)
    assert 20 < got < 21
    decoding = dict(obs, engine_traced=dict(
        obs["engine_traced"], mla_keys_distinct_total=2e9))
    assert latent_roofline.read(decoding, _Chip(), **walk) == pytest.approx(
        100 * (2e9 * 1152 / 819e9) / 4.0)
    # nothing to read: the parent's line, an untraced run, the CPU
    assert latent_roofline.read(dict(obs, engine_traced={}), _Chip(),
                                **walk) is None
    assert latent_roofline.read(dict(obs, trace=None), _Chip(),
                                **walk) is None
    no_kernel = dict(obs, trace=dict(obs["trace"], text={
        k: "other" for k in obs["trace"]["text"]}))
    assert latent_roofline.read(no_kernel, _Chip(), **walk) is None
