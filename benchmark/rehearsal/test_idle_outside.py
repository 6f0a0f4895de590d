"""`idle_outside` and `leaf_idle` on a synthetic trace with known device
and span intervals, beside test_readers.py's checks of `span_idle`:

    python -m pytest benchmark/rehearsal/test_idle_outside.py -q
"""
import pytest

from benchmark import trace
from benchmark.readers import idle_outside, leaf_idle, span_idle

# One chip, busy [1000, 3000) and [9000, 10000) ns, so idle [500, 1000),
# [3000, 9000) and [10000, 10500) in the window [500, 10500) (7000 ns).
# On the engine's thread, leaves and what nests in them:
#   np.asarray      [500, 600)     JAX's own event: not a program span
#   serving::admit  [2500, 4000)
#   serving::spill  [3500, 3800)   inside admit
#   serving::plan   [4000, 5000)
#                   [5000, 6000)   under no span: idle all of it
#   serving::fetch  [6000, 9500)
#   serving::wait   [9500, 10500)
# Idle outside every leaf: [500, 1000) and [5000, 6000), 1500 ns.
SYNTHETIC = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 8000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = bf16[8,16]{1,0} fusion(%a)" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 7 name: "engine" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 100000 }
    events { metadata_id: 2 offset_ps: 2500000 duration_ps: 1500000 }
    events { metadata_id: 3 offset_ps: 3500000 duration_ps: 300000 }
    events { metadata_id: 4 offset_ps: 4000000 duration_ps: 1000000 }
    events { metadata_id: 5 offset_ps: 6000000 duration_ps: 3500000 }
    events { metadata_id: 6 offset_ps: 9500000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "np.asarray(jax.Array)" } }
  event_metadata { key: 2 value { id: 2 name: "serving::admit" } }
  event_metadata { key: 3 value { id: 3 name: "serving::spill" } }
  event_metadata { key: 4 value { id: 4 name: "serving::plan" } }
  event_metadata { key: 5 value { id: 5 name: "serving::fetch" } }
  event_metadata { key: 6 value { id: 6 name: "serving::wait" } }
}
"""
LEAVES = ["serving::wait", "serving::inbox", "serving::admit",
          "serving::plan", "serving::launch", "serving::fetch",
          "serving::commit", "serving::report", "serving::spill"]


def _xspace(text=SYNTHETIC):
    from jax.profiler import ProfileData
    return ProfileData.text_proto_to_serialized_xspace(text)


@pytest.fixture
def parsed(monkeypatch):
    from jax.profiler import ProfileData
    monkeypatch.setattr(trace, "MIN_GAP_NS", 100)
    return span_idle.parse(ProfileData.from_serialized_xspace(_xspace()))


@pytest.mark.parametrize("spans, idle_ns", [
    (LEAVES, 1500),                             # the gap and the edge
    (["serving::admit"], 6000),                 # admit holds 1000 of it
    (["serving::spill"], 6700),                 # 300 under the spill
    (["serving::fetch", "serving::wait"], 3500),
    ([], 7000),                                 # no span: every gap
])
def test_idle_outside_on_known_intervals(parsed, spans, idle_ns):
    assert parsed["window"] == (500, 10500)
    assert parsed["idle"] == [(500, 1000), (3000, 9000), (10000, 10500)]
    assert idle_outside.outside(parsed, spans) == \
        pytest.approx(100.0 * idle_ns / 10000)


def test_idle_outside_and_span_idle_add_up_to_the_idle(parsed):
    inside = span_idle.share(parsed, LEAVES)
    assert inside + idle_outside.outside(parsed, LEAVES) == \
        pytest.approx(70.0)


class Ctx:
    def __init__(self, root):
        self.root, self.cell_name, self.logged = root, "some.cell", []

    def log(self, msg):
        self.logged.append(msg)


def _on_disk(tmp_path, text=SYNTHETIC):
    d = tmp_path / ".bench_trace" / "some.cell" / "plugins" / "profile"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(_xspace(text))
    return Ctx(str(tmp_path))


def test_idle_outside_reads_the_runs_trace(tmp_path, monkeypatch):
    monkeypatch.setattr(trace, "MIN_GAP_NS", 100)
    ctx = _on_disk(tmp_path)
    obs = {"trace": {"window_s": 1e-5}}
    assert idle_outside.read(obs, ctx, LEAVES) == pytest.approx(15.0)
    # the parse is span_idle's, once a run
    assert span_idle.read(obs, ctx, ["serving::plan"]) == \
        pytest.approx(10.0)
    assert len(ctx.logged) == 1
    # a program without any of these spans (the parent of the PR that
    # added them) and a run without a trace: nothing to read
    assert idle_outside.read(obs, ctx, ["serving::inbox"]) is None
    assert idle_outside.read({"trace": None}, ctx, LEAVES) is None


def test_leaf_idle_reads_0_where_the_program_has_the_leaf(tmp_path,
                                                          monkeypatch):
    monkeypatch.setattr(trace, "MIN_GAP_NS", 100)
    ctx = _on_disk(tmp_path, SYNTHETIC.replace("serving::wait",
                                               "serving::report"))
    args = dict(spans=["serving::wait"], source="engine",
                counter="engine_wait_s_total")
    # the engine never waited inside the trace, and counts its waits
    obs = {"trace": {"window_s": 1e-5},
           "engine": {"engine_wait_s_total": 0.0}}
    assert leaf_idle.read(obs, ctx, **args) == 0.0
    # a program without the counter (the parent): nothing to read
    assert leaf_idle.read({"trace": obs["trace"], "engine": {}}, ctx,
                          **args) is None
    assert leaf_idle.read({"trace": None, "engine": obs["engine"]}, ctx,
                          **args) is None
    # where the trace holds the leaf, span_idle's reading
    obs = {"trace": {"window_s": 1e-5},
           "engine": {"engine_wait_s_total": 1.0}}
    assert leaf_idle.read(obs, ctx, ["serving::fetch"], "engine",
                          "engine_wait_s_total") == pytest.approx(30.0)
