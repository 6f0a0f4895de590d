"""The program's own clocks: host spans that reach a JAX profiler trace,
and the host-phase counters fed from the same clock reads.

`profiler.RecordEvent` is the one span type. Inside a JAX profiler
session it is also a `jax.profiler.TraceAnnotation`, so the engine's
phases sit in the `/host:` plane of the same `.xplane.pb` as the
device's operations; `ServingMetrics.host_phases` holds the same
seconds as cumulative counters (`HOST_PHASE_COUNTERS`).
"""
import ast
import glob
import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.jit import trainer
from paddle_tpu.nlp import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import SamplingParams, ServingEngine
from paddle_tpu.serving import engine as engine_mod
from paddle_tpu.serving.http import driver as driver_mod
from paddle_tpu.serving.metrics import (HOST_PHASE_COUNTERS,
                                        prometheus_render)

_MODELS = {}


def tiny_gpt():
    m = _MODELS.get("gpt")
    if m is None:
        paddle.seed(7)
        m = _MODELS["gpt"] = GPTForCausalLM(GPTConfig(
            vocab_size=97, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=64,
            max_position_embeddings=128, hidden_dropout_prob=0.0,
            attention_probs_dropout_prob=0.0))
        m.eval()
    return m


def _host_events(trace_dir):
    """{name: [stats dict, ...]} over every `/host:` plane's events."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1, files
    out = {}
    for plane in ProfileData.from_file(files[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if "::" in ev.name:
                    out.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.duration_ns, dict(ev.stats)))
    return out


def test_record_event_reaches_the_jax_trace_with_its_arguments(tmp_path):
    import jax
    outside = profiler.RecordEvent("test::outside", page=1)
    with outside:
        pass                            # no session: inert, still timed
    assert outside.elapsed_s >= 0.0
    jax.profiler.start_trace(str(tmp_path))
    try:
        with profiler.RecordEvent("test::outer", step=7, page=3) as ev:
            with profiler.RecordEvent("test::inner"):
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    assert ev.elapsed_s >= 0.002
    host = _host_events(str(tmp_path))
    assert "test::outside" not in host
    (o_start, o_dur, o_args), = host["test::outer"]
    (i_start, i_dur, _), = host["test::inner"]
    assert o_args["step"] == 7 and o_args["page"] == 3
    assert o_start <= i_start and i_start + i_dur <= o_start + o_dur
    # the span's own two clock reads bound the annotation
    assert o_dur / 1e9 == pytest.approx(ev.elapsed_s, abs=2e-3)


def test_engine_rounds_reach_the_jax_trace(tmp_path):
    """What an operator gets from `jax.profiler.start_trace` against a
    running engine: every round with its step index, and its phases."""
    import jax
    eng = ServingEngine(tiny_gpt(), num_slots=2, max_len=48)
    eng.add_request(np.array([1, 2, 3], np.int64),
                    SamplingParams(max_new_tokens=2))
    eng.step()                          # compiles outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run()
    finally:
        jax.profiler.stop_trace()
    host = _host_events(str(tmp_path))
    steps = [args["step"] for _, _, args in host["serving::admit"]]
    assert steps == list(range(2, eng._step_idx + 1))
    # a round fetches and commits the step the round before launched,
    # after it launched its own; the last round launches none
    for name in ("plan", "fetch", "commit", "report"):
        assert len(host[f"serving::{name}"]) == len(steps), name
    # one decoding row a step, as the host planned it
    assert [args["tokens"] for _, _, args in host["serving::launch"]] \
        == [1] * (len(steps) - 1)
    assert "serving::round" not in host
    assert "serving::unified_step" not in host


SPAN_USERS = (engine_mod, driver_mod, trainer)


@pytest.mark.parametrize("mod", SPAN_USERS,
                         ids=[m.__name__.rsplit(".", 1)[-1]
                              for m in SPAN_USERS])
def test_span_names_are_module_constants(mod):
    """No span name is built per call: every `RecordEvent(...)` and
    `self._phase(...)` takes a module-level `SPAN_*` string, ids go
    into keyword arguments."""
    with open(mod.__file__) as f:
        tree = ast.parse(f.read())
    # `_phase` itself opens the span it was given: not a call site
    inside_phase = {id(n) for f in ast.walk(tree)
                    if isinstance(f, ast.FunctionDef)
                    and f.name == "_phase" for n in ast.walk(f)}
    calls = [n for n in ast.walk(tree) if isinstance(n, ast.Call)
             and id(n) not in inside_phase
             and ((isinstance(n.func, ast.Name)
                   and n.func.id == "RecordEvent")
                  or (isinstance(n.func, ast.Attribute)
                      and n.func.attr == "_phase"))]
    assert calls
    for call in calls:
        name = call.args[0]
        assert isinstance(name, ast.Name) and name.id.startswith("SPAN_"), \
            ast.dump(name)
        value = getattr(mod, name.id)
        assert isinstance(value, str) and "::" in value
        assert "[" not in value and "{" not in value


def test_host_phase_counters_cover_the_round():
    """After N rounds the six phase counters add up to the wall time of
    `step()` (within 10%: what lies between the spans is a few clock
    reads), and the page counters count pages."""
    eng = ServingEngine(tiny_gpt(), num_slots=2, max_len=64, chunk_len=8)
    for i in range(3):
        eng.add_request(np.arange(1, 12 + i, dtype=np.int64),
                        SamplingParams(max_new_tokens=6))
    eng.step()                          # the compiling round, left out
    before = dict(eng.metrics.snapshot())
    wall, rounds = 0.0, 0
    while eng.has_work:
        t0 = time.perf_counter()
        eng.step()
        wall += time.perf_counter() - t0
        rounds += 1
    snap = eng.metrics.snapshot()
    assert rounds >= 6
    assert snap["unified_steps"] - before["unified_steps"] == rounds
    phases = ("step_plan_s_total", "step_launch_s_total",
              "step_fetch_s_total", "step_commit_s_total",
              "round_admit_s_total", "round_report_s_total")
    spent = {k: snap[k] - before[k] for k in phases}
    assert all(v > 0 for v in spent.values()), spent
    assert sum(spent.values()) == pytest.approx(wall, rel=0.10)
    # flat, numeric, top-level, cumulative: what the benchmark's window
    # difference and a Prometheus scrape both need
    for name in HOST_PHASE_COUNTERS:
        assert isinstance(snap[name], (int, float)), name
    text = prometheus_render({"0": snap})
    for name in HOST_PHASE_COUNTERS:
        assert f"# TYPE paddle_serving_{name} counter" in text
        assert f'paddle_serving_{name}{{replica="0"}} ' in text


def test_spills_are_counted_with_their_seconds():
    """A pool smaller than the traffic: parked prefix pages spill to the
    host tier, and the counters say how many pages, in how many gathers,
    and how long: the tree walk and the dispatch inside `serving::admit`
    or `serving::plan`, the copies set off after the step's launch and
    collected after its fetch, all under `serving::spill`; the last two
    also inside a `serving::spill` of the round's own."""
    eng = ServingEngine(tiny_gpt(), num_slots=2, max_len=64,
                        page_size=8, num_pages=13, chunk_len=8)
    wall = 0.0
    with profiler.Profiler(targets=[profiler.ProfilerTarget.CPU]) as p:
        for i in range(6):
            eng.add_request(np.arange(1 + 7 * i, 30 + 7 * i,
                                      dtype=np.int64) % 97,
                            SamplingParams(max_new_tokens=2))
            t0 = time.perf_counter()
            eng.run()
            wall += time.perf_counter() - t0
    snap = eng.metrics.snapshot()
    pages = snap["kv_spill_pages_total"]
    assert pages > 0 and pages == snap["prefix"]["spilled_pages"]
    assert 0 < snap["kv_spill_batches_total"] <= pages
    assert 0 <= snap["kv_spill_wait_s_total"] <= snap["kv_spill_s_total"]
    # no spill second lies under another phase than admit or plan, or
    # between a launch and its fetch
    assert 0 < snap["kv_spill_s_total"] <= wall - sum(
        snap[k] for k in ("step_launch_s_total", "step_fetch_s_total",
                          "step_commit_s_total", "round_report_s_total"))
    # the copies set off and collected between the round's other
    # leaves, one span around each such call: the leaves tile the rounds
    assert snap["round_spill_s_total"] > 0
    assert sum(snap[k] for k in (
        "round_admit_s_total", "step_plan_s_total", "step_launch_s_total",
        "round_spill_s_total", "step_fetch_s_total", "step_commit_s_total",
        "round_report_s_total")) == pytest.approx(wall, rel=0.10)
    spans = p.aggregate()["serving::spill"]
    # a spill's tree walk and its dispatch; a gather's copies set off
    # and collected; the round's calls that set them off and collect
    # them, around those
    batches = snap["kv_spill_batches_total"]
    assert 2 * batches < spans["calls"] <= 6 * batches
    assert spans["total"] / 1e9 == pytest.approx(
        snap["kv_spill_s_total"] + snap["round_spill_s_total"], rel=0.05)


def test_submit_wait_is_counted_by_the_pump_thread():
    """Every request submitted through an EngineDriver is serviced once,
    and its inbox wait (handler thread's put -> pump thread's
    add_request) lands in `submit_wait_s_total`; `http::submit` is the
    handler thread's span around it."""
    eng = ServingEngine(tiny_gpt(), num_slots=2, max_len=48)
    drv = driver_mod.EngineDriver(eng).start()
    try:
        with profiler.Profiler(
                targets=[profiler.ProfilerTarget.CPU]) as p:
            reqs = [drv.submit(np.array([1, 2, 3 + i], np.int64),
                               SamplingParams(max_new_tokens=2))
                    for i in range(5)]
            assert all(r.wait(timeout=60.0) for r in reqs)
    finally:
        assert drv.drain(timeout=60.0)
    snap = eng.metrics.snapshot()
    assert snap["submits_serviced_total"] == 5
    assert snap["submit_wait_s_total"] > 0
    spans = p.aggregate()["http::submit"]
    assert spans["calls"] == 5
    # the handler's span holds the wait and the reply's way back
    assert spans["total"] / 1e9 >= snap["submit_wait_s_total"]


# the leaves of the engine's thread and the counter each feeds
LEAVES = {"serving::inbox": "inbox_s_total",
          "serving::wait": "engine_wait_s_total",
          "serving::admit": "round_admit_s_total",
          "serving::plan": "step_plan_s_total",
          "serving::launch": "step_launch_s_total",
          "serving::fetch": "step_fetch_s_total",
          "serving::commit": "step_commit_s_total",
          "serving::report": "round_report_s_total",
          "serving::spill": "round_spill_s_total"}


def test_leaves_tile_the_pump_thread(tmp_path):
    """Under an EngineDriver the engine's thread is a row of disjoint
    leaf spans, busy rounds and idle stretches alike, with no span
    around a round or a step; their counters add up to the pump loop's
    own seconds."""
    eng = ServingEngine(tiny_gpt(), num_slots=2, max_len=48)
    eng.generate([np.array([1, 2, 3], np.int64)],
                 SamplingParams(max_new_tokens=2))   # compiles first
    drv = driver_mod.EngineDriver(eng).start()
    try:
        time.sleep(0.1)                  # idle: the account is flushed
        before = eng.metrics.snapshot()
        with profiler.Profiler(
                targets=[profiler.ProfilerTarget.CPU]) as p:
            for i in range(3):
                reqs = [drv.submit(np.array([1, 2, 3 + i + j], np.int64),
                                   SamplingParams(max_new_tokens=3 + j))
                        for j in range(2)]
                assert all(r.wait(timeout=60.0) for r in reqs)
                time.sleep(0.5)          # an idle stretch between them
        time.sleep(0.1)
        snap = eng.metrics.snapshot()
    finally:
        assert drv.drain(timeout=60.0)
    path = p.export(str(tmp_path / "pump.json"))
    events = profiler.load_profiler_result(path)["traceEvents"]
    names = {e["name"] for e in events}
    assert not names & {"serving::round", "serving::unified_step"}
    pump = sorted((e["ts"], e["ts"] + e["dur"], e["name"], e.get("args"))
                  for e in events if e["tid"] == drv._thread.ident
                  and e["name"] in LEAVES)
    # a spill inside admit or plan is no leaf: none happens here
    assert "serving::spill" not in names
    for (_, end, a, _), (start, _, b, _) in zip(pump, pump[1:]):
        assert start >= end - 1e-3, (a, b)          # us, as exported
    covered = sum(e - s for s, e, _, _ in pump)
    assert covered >= 0.99 * (pump[-1][1] - pump[0][0])
    assert {n for _, _, n, _ in pump} >= set(LEAVES) - {"serving::spill"}
    # an idle stretch is cut into spans of WAIT_SPAN_S, not one a poll
    waits = [e - s for s, e, n, _ in pump if n == "serving::wait"]
    assert len(waits) >= 3 * 3
    assert max(waits) <= 1e6 * (driver_mod.WAIT_SPAN_S + 0.05)
    assert all(isinstance(a["step"], int)
               for _, _, n, a in pump if n == "serving::admit")
    assert all(a["tokens"] >= 1
               for _, _, n, a in pump if n == "serving::launch")
    spent = {k: snap[k] - before[k] for k in
             set(LEAVES.values()) | {"pump_s_total"}}
    leaves = sum(v for k, v in spent.items() if k != "pump_s_total")
    assert 0.99 * spent["pump_s_total"] <= leaves <= spent["pump_s_total"]


def test_train_step_spans(tmp_path):
    import paddle_tpu.optimizer as opt
    from paddle_tpu import jit
    paddle.seed(3)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=97, hidden_size=32, num_hidden_layers=1,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=16, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0))
    step = jit.compile_train_step(
        lambda ids, labels: model(ids, labels=labels), model,
        opt.AdamW(learning_rate=1e-3, parameters=model.parameters()))
    ids = paddle.to_tensor(np.arange(32).reshape(2, 16) % 97)
    with profiler.Profiler(targets=[profiler.ProfilerTarget.CPU]) as p:
        for _ in range(3):
            float(step(ids, ids))
    path = p.export(str(tmp_path / "train.json"))
    events = profiler.load_profiler_result(path)["traceEvents"]
    assert sum(e["name"] == "train::step" for e in events) == 3
