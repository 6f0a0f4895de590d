"""Checks of the readers that read the program's own clocks, by hand on
the CPU beside test_rehearsal.py:

    python -m pytest benchmark/rehearsal -q

`per_unit` on made-up counters; `span_idle` on a synthetic trace with
known device and span intervals; the kernels' `ptk:` names through
`trace_share` on a synthetic trace whose events carry the compiled
instruction's text; and a tiny cell that names the new metrics, end to
end.
"""
import json

import pytest

from benchmark import trace
from benchmark.readers import per_unit, span_idle, trace_share
from benchmark.rehearsal.test_rehearsal import _run

# One chip, busy [1000, 3000) and [9000, 10000) ns, so idle [3000, 9000)
# (and 500 ns at either end of the window [500, 10500), which the host's
# long "serving::round" sets). On the engine's thread:
#   serving::admit  [2500, 6000)   idle part [3000, 6000) = 3000 ns
#   serving::spill  [3500, 4500)   inside admit, idle all of its 1000 ns
#   serving::spill  [5000, 5500)   inside admit, idle all of its 500 ns
#   serving::fetch  [8000, 9500)   idle part [8000, 9000) = 1000 ns
#   np.asarray      [8100, 9400)   JAX's own event: not a program span
# On a handler thread:
#   http::submit    [4000, 7000)   idle all of its 3000 ns
# admit without its spills: 3000 - 1500 = 1500 ns.
SYNTHETIC = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 8000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%ragged_walk.3 = bf16[8,16]{1,0} custom-call(%a), custom_call_target=\\"tpu_custom_call\\", frontend_attributes={kernel_metadata={\\n\\"fn\\":\\"_ragged_kernel\\",\\n\\"kernel\\":\\"ptk:ragged_walk\\"\\n}}" } }
  event_metadata { key: 2 value { id: 2 name: "%layer_norm_fwd.5 = bf16[8,16]{1,0} custom-call(%b), custom_call_target=\\"tpu_custom_call\\", frontend_attributes={kernel_metadata={\\n\\"fn\\":\\"_ln_fwd_kernel\\",\\n\\"kernel\\":\\"ptk:layer_norm_fwd\\"\\n}}" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 7 name: "engine" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 2500000 duration_ps: 3500000 }
    events { metadata_id: 3 offset_ps: 3500000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 500000 }
    events { metadata_id: 4 offset_ps: 8000000 duration_ps: 1500000 }
    events { metadata_id: 5 offset_ps: 8100000 duration_ps: 1300000 }
  }
  lines { id: 8 name: "handler" timestamp_ns: 0
    events { metadata_id: 6 offset_ps: 4000000 duration_ps: 3000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "serving::round" } }
  event_metadata { key: 2 value { id: 2 name: "serving::admit" } }
  event_metadata { key: 3 value { id: 3 name: "serving::spill" } }
  event_metadata { key: 4 value { id: 4 name: "serving::fetch" } }
  event_metadata { key: 5 value { id: 5 name: "np.asarray(jax.Array)" } }
  event_metadata { key: 6 value { id: 6 name: "http::submit" } }
}
"""


def _pd(text=SYNTHETIC):
    from jax.profiler import ProfileData
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))


@pytest.fixture
def parsed(monkeypatch):
    monkeypatch.setattr(trace, "MIN_GAP_NS", 100)
    return span_idle.parse(_pd())


@pytest.mark.parametrize("spans, excluding, idle_ns", [
    (["serving::admit"], [], 3000),
    (["serving::spill"], [], 1500),
    (["serving::admit"], ["serving::spill"], 1500),
    (["serving::fetch"], [], 1000),
    (["http::submit"], [], 3000),
    # a union, not a sum: submit overlaps admit on [4000, 6000)
    (["serving::admit", "http::submit"], [], 4000),
    (["serving::round"], [], 7000),         # every gap, the ends too
])
def test_span_idle_on_known_intervals(parsed, spans, excluding, idle_ns):
    assert parsed["window"] == (500, 10500)
    assert parsed["idle"] == [(500, 1000), (3000, 9000), (10000, 10500)]
    assert "np.asarray(jax.Array)" not in parsed["spans"]
    assert span_idle.share(parsed, spans, excluding) == \
        pytest.approx(100.0 * idle_ns / 10000)


def test_span_idle_is_silent_where_there_is_nothing_to_read(parsed):
    # a program without these spans (the parent of the PR that added
    # them), a run without a trace, a trace without a device
    assert span_idle.share(parsed, ["serving::launch"]) is None
    assert span_idle.read({"trace": None}, None, ["serving::fetch"]) is None
    host_only = SYNTHETIC[SYNTHETIC.index('planes { id: 2'):]
    assert span_idle.parse(_pd(host_only)) is None


def test_span_idle_ignores_pauses_under_the_traces_own_limit():
    # short pauses are launch latency, as in trace._gaps: at the real
    # MIN_GAP_NS (20 us) the synthetic chip never idles
    assert span_idle.parse(_pd())["idle"] == []


def test_span_idle_reads_the_file_once_a_run(tmp_path, monkeypatch):
    monkeypatch.setattr(trace, "MIN_GAP_NS", 100)
    from jax.profiler import ProfileData
    d = tmp_path / ".bench_trace" / "some.cell" / "plugins" / "profile"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(SYNTHETIC))

    class Ctx:
        root, cell_name, logged = str(tmp_path), "some.cell", []

        def log(self, msg):
            self.logged.append(msg)
    obs = {"trace": {"window_s": 1e-5}}
    ctx = Ctx()
    assert span_idle.read(obs, ctx, ["serving::fetch"]) == \
        pytest.approx(10.0)
    assert span_idle.read(obs, ctx, ["serving::admit"],
                          ["serving::spill"]) == pytest.approx(15.0)
    assert len(ctx.logged) == 1 and "serving::spill" in ctx.logged[0]


def test_kernel_names_through_trace_share(monkeypatch):
    """The compiled instruction's text carries `ptk:<name>`; two kernels
    with one result shape stay apart because `name=` reaches the
    instruction's name, which `trace.short_name` keeps."""
    monkeypatch.setattr(trace, "MIN_GAP_NS", 100)
    red = trace.reduce_xspace(_pd())
    assert set(red["ops"]) == {"ragged_walk custom-call bf16[8,16]",
                               "layer_norm_fwd custom-call bf16[8,16]"}
    obs = {"trace": red}
    assert trace_share.read(obs, None, ["ptk:ragged_walk"]) == \
        pytest.approx(100.0 * 2000 / 3000)
    assert trace_share.read(obs, None, ["ptk:layer_norm_"]) == \
        pytest.approx(100.0 * 1000 / 3000)
    assert trace_share.read(obs, None, ["tpu_custom_call"]) == \
        pytest.approx(100.0)
    assert trace_share.read(obs, None, ["ptk:grouped_phase1"]) is None


def test_per_unit():
    obs = {"engine": {"step_plan_s_total": 0.5, "round_admit_s_total": 1.0,
                      "round_report_s_total": 0.25, "unified_steps": 250,
                      "submits_serviced_total": 0, "submit_wait_s_total": 0}}
    assert per_unit.read(obs, None, "engine", ["step_plan_s_total"],
                         "unified_steps", 1000.0) == pytest.approx(2.0)
    assert per_unit.read(obs, None, "engine",
                         ["round_admit_s_total", "round_report_s_total"],
                         "unified_steps", 1000.0) == pytest.approx(5.0)
    # nothing counted, or a program without the counter: nothing reported
    assert per_unit.read(obs, None, "engine", ["submit_wait_s_total"],
                         "submits_serviced_total") is None
    assert per_unit.read(obs, None, "engine", ["kv_spill_s_total"],
                         "unified_steps") is None
    assert per_unit.read({}, None, "engine", ["step_plan_s_total"],
                         "unified_steps") is None


def test_tiny_cell_reads_the_programs_counters():
    """A cell that names the new metrics, end to end on the CPU: the
    harness picks the counters up with no file edited; what needs a
    device trace (idle and busy shares) is left out of the line."""
    proc = _run("tiny.phases", "--trace", "1")
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    got = {n: m["value"] for n, m in line["metrics"].items()}
    assert set(got) == {"where.engine.plan_ms_per_step.steady",
                        "where.engine.launch_ms_per_step.steady",
                        "where.engine.fetch_ms_per_step.steady",
                        "where.engine.commit_ms_per_step.steady",
                        "where.engine.between_steps_ms.steady",
                        "where.front.submit_wait_ms.steady",
                        "where.kv.spill_pages_per_step.backlog"}
    assert all(v > 0 for n, v in got.items() if "spill" not in n)
    assert got["where.kv.spill_pages_per_step.backlog"] >= 0
