"""Rehearsals of what PR 36 added to the yardstick, on the CPU:

    python -m pytest benchmark/rehearsal -q

The tiny Keye-VL-2.0 cell end to end (kind serve_http_keye,
ref_keye_vl2's check, the sparse counters through their readers), the
`open_loop_grouped` generator (the steady mix's instants and multiset
of requests, re-dealt by length; `drive` word for word the steady
generator's) and its tiny cell over the Laguna kind, the two controls
through the cell's own comparison, and the new roofline reader on a
synthetic observation.
"""
import inspect
import json
import os
import subprocess
import sys

import pytest

from benchmark.readers import sparse_roofline
from benchmark.rehearsal.test_rehearsal import ROOT, _run
from benchmark.traffic import open_loop_grouped, open_loop_poisson


def _line(proc):
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("trace_flag, expected", [
    ("0", {"setup_s", "ttft_p50_ms", "itl_p95_ms"}),
    ("1", {"step.wall_ms.steady",
           "where.x.moe.experts_hit_per_layer_step.code",
           "where.x.moe.here_share.code",
           "where.z.sparse.selected_share.long",
           "where.z.sparse.keys_per_row.long",
           "where.engine.plan_ms_per_step.steady",
           "rehearsal.requests_per_s"}),
])
def test_tiny_keye_cell_end_to_end(trace_flag, expected):
    """Shares of the device's trace and of its peak are left out on the
    CPU; the counters' metrics are read."""
    proc = _run("tiny.keye", "--trace", trace_flag)
    line = _line(proc)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == expected
    assert "compilations inside the window: 0" in proc.stdout
    assert "switched off for this model" in proc.stdout
    if trace_flag == "1":
        m = {k: v["value"] for k, v in line["metrics"].items()}
        assert 0 < m["where.x.moe.experts_hit_per_layer_step.code"] <= 4
        # prompts of 12-100 tokens, topk 16: a query row sees some tens
        # of keys and keeps 16 of them
        assert 16 < m["where.z.sparse.keys_per_row.long"] < 100
        assert 15 < m["where.z.sparse.selected_share.long"] < 80


def test_tiny_by_length_cell_end_to_end():
    proc = _run("tiny.by_length", "--trace", "0")
    line = _line(proc)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "ttft_p50_ms", "itl_p95_ms"}
    assert "compilations inside the window: 0" in proc.stdout


def _mixes():
    out = []
    for name in ("code_mixed", "code_by_length"):
        with open(os.path.join(ROOT, f"benchmark/traffic/{name}.json")) as f:
            out.append(dict(json.load(f), rate_rps=0.48))
    return out


def test_grouped_cell_offers_code_mixeds_requests_at_its_instants():
    """The cell's mix against `code_mixed`'s, both at the cells' rate
    and window: the same arrival instants, the same multiset of (prompt
    length, max_tokens) pairs; only which pair arrives when differs."""
    steady, grouped = _mixes()
    assert {k: v for k, v in grouped.items()
            if k not in ("generator", "group", "what")} \
        == {k: v for k, v in steady.items()
            if k not in ("generator", "what")}
    a = open_loop_poisson.schedule(steady, 51)
    b = open_loop_grouped.schedule(grouped, 51)
    assert [d for d, _, _ in a] == [d for d, _, _ in b]
    assert sorted(x[1:] for x in a) == sorted(x[1:] for x in b)
    assert [x[1:] for x in a] != [x[1:] for x in b]
    assert b == open_loop_grouped.schedule(grouped, 51)


def test_groups_hold_neighbours_by_length():
    """Consecutive runs of `group` requests are neighbours in the order
    of prompt lengths, each run sorted; the runs' order comes from
    shape_seed."""
    _, grouped = _mixes()
    plan = open_loop_grouped.schedule(grouped, 51)
    lens = [p for _, p, _ in plan]
    ranked = sorted(lens)
    left = [ranked[i:i + 6] for i in range(0, len(ranked), 6)]
    firsts, at = [], 0
    while at < len(lens):
        run = next(g for g in left if lens[at:at + len(g)] == g)
        left.remove(run)
        firsts.append(run[0])
        at += len(run)
    assert not left and firsts != sorted(firsts)
    other = open_loop_grouped.schedule(dict(grouped, shape_seed=30), 51)
    assert [p for _, p, _ in other] != lens


def test_grouped_drive_replays_its_own_schedule():
    _, grouped = _mixes()
    mix = dict(grouped, rate_rps=30.0, ramp_s=0.2, drain_s=2)
    sent = []

    def send(prompt, max_tokens, stream):
        sent.append(len(prompt))
        return {"status": 200, "tokens": [1] * max_tokens,
                "t_tokens": [0.0] * max_tokens, "error": None,
                "t_done": 0.0, "finish": "length"}
    res = open_loop_grouped.drive(mix, 7, 0.6, 50176, send, lambda: None,
                                  lambda: None, lambda: None)
    plan = open_loop_grouped.schedule(mix, 0.6)
    assert res["offered"] == len(plan) == len(sent)
    assert sent == [p for _, p, _ in plan]


def test_grouped_drive_is_the_steady_generators_word_for_word():
    """`open_loop_grouped.drive` is a copy (the steady module's finds
    its schedule as a module global): the two texts stay equal."""
    assert inspect.getsource(open_loop_grouped.drive) \
        == inspect.getsource(open_loop_poisson.drive)


def test_controls_go_through_the_cells_own_comparison():
    """scripts/keye_controls_reading.py: one whole run of the tiny cell
    through run.py's `main`, then the reference without the selection
    and over fp8 weights, each judged on the run's own sample by
    `ref_keye_vl2.judge_choices` and `passes`: the engine comes out
    correct, neither control does."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("PADDLE_TPU_PALLAS_INTERPRET",
                        "PADDLE_TPU_FORCE_CPU_DEVICES")}
    proc = subprocess.run(
        [sys.executable, "scripts/keye_controls_reading.py", "--workload",
         "tiny.keye", "--seed", "3000000011", "--seconds", "3"], cwd=ROOT,
        env=dict(env, JAX_PLATFORMS="cpu"), capture_output=True, text=True,
        timeout=900)
    assert _line(proc)["correct"] is True
    assert "control no_selection: correct false" in proc.stdout
    assert "control all_matrices_fp8: correct false" in proc.stdout


class _Chip:
    config = {"num_attention_heads": 32, "num_key_value_heads": 4,
              "head_dim": 128,
              "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16}}
    rehearsal = False

    def peak(self, what):
        return {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}[what]


def test_sparse_roofline_reader_on_a_synthetic_observation():
    """While the trace ran: 4e9 visible and 1e9 selected (query, key)
    pairs, 2e7 keys of context, 1.5e7 of them the attention's floor; the
    walk busy for 2 s, the indexer for 0.5 s. A selected pair costs
    32 x 256 x 2 operations and a floor key 2048 bytes: the walk is
    bound by operations (83 ms against 37.5 ms of bytes); a visible pair
    costs the indexer 16 x 64 x 2 and a context key 128 bytes: bound by
    operations too (41.6 ms against 3.1 ms)."""
    obs = {"trace": {"ops": {"sparse_walk.1": 2.0, "sparse_index.1": 0.5,
                             "fusion": 2.0},
                     "text": {"sparse_walk.1": "x ptk:sparse_walk y",
                              "sparse_index.1": "x ptk:sparse_index z",
                              "fusion": "%fusion.1"},
                     "busy_s": 4.5, "chips": 1},
           "engine_traced": {"sparse_pairs_visible_total": 4e9,
                             "sparse_pairs_selected_total": 1e9,
                             "sparse_keys_floor_total": 1.5e7,
                             "sparse_keys_context_total": 2e7}}
    walk = dict(kernels=["ptk:sparse_walk"],
                flops={"selected": "sparse_pairs_selected_total"},
                bytes={"floor_keys": "sparse_keys_floor_total"})
    index = dict(kernels=["ptk:sparse_index"],
                 flops={"scored": "sparse_pairs_visible_total"},
                 bytes={"context_keys": "sparse_keys_context_total"})
    assert sparse_roofline.read(obs, _Chip(), **walk) == pytest.approx(
        100 * (1e9 * 32 * 256 * 2 / 197e12) / 2.0)
    assert sparse_roofline.read(obs, _Chip(), **index) == pytest.approx(
        100 * (4e9 * 16 * 64 * 2 / 197e12) / 0.5)
    # decoding rows alone: few pairs a key, bound by bytes
    few = dict(obs, engine_traced=dict(obs["engine_traced"],
                                       sparse_pairs_selected_total=2e7))
    assert sparse_roofline.read(few, _Chip(), **walk) == pytest.approx(
        100 * (1.5e7 * 2048 / 819e9) / 2.0)
    # the metric files' arguments are these
    for name, args in (("sparse_walk", walk), ("sparse_index", index)):
        with open(os.path.join(ROOT, "benchmark/metrics/where.z." + name
                               + ".roofline_share.long.json")) as f:
            spec = json.load(f)
        assert spec["reader"] == "sparse_roofline" and spec["args"] == args
    # nothing to read: the parent's line, an untraced run, the CPU
    assert sparse_roofline.read(dict(obs, engine_traced={}), _Chip(),
                                **walk) is None
    assert sparse_roofline.read(dict(obs, engine_traced=None), _Chip(),
                                **walk) is None
    assert sparse_roofline.read(dict(obs, trace=None), _Chip(),
                                **walk) is None
    no_kernel = dict(obs, trace=dict(obs["trace"], text={
        k: "other" for k in obs["trace"]["text"]}))
    assert sparse_roofline.read(no_kernel, _Chip(), **walk) is None
