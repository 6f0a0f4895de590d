"""The two per-layer metrics of the batched spill, as files under
`benchmark/metrics/`, read by the readers that exist: a number from a
window that holds the counters, nothing (and no exception) from one
that lacks them, as the parent of the PR that added them does."""
import importlib
import json
import os

import pytest

from paddle_tpu.serving.metrics import HOST_PHASE_COUNTERS

METRICS = os.path.join(os.path.dirname(__file__), os.pardir, "benchmark",
                       "metrics")
WITH = {"window_s": 51.0, "engine": {
    "unified_steps": 340, "kv_spill_s_total": 1.7,
    "kv_spill_pages_total": 11220, "kv_spill_batches_total": 1020,
    "kv_spill_wait_s_total": 0.51}}
PARENT = {"window_s": 51.0, "engine": {
    "unified_steps": 231, "kv_spill_s_total": 19.3,
    "kv_spill_pages_total": 7715}}


def _read(name, obs):
    with open(os.path.join(METRICS, name + ".json")) as f:
        spec = json.load(f)
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    return spec, reader.read(obs, None, **spec["args"])


@pytest.mark.parametrize("name, reader, want", [
    ("where.z.kv_spill.pages_per_batch.backlog", "ratio", 11.0),
    ("where.z.kv_spill.wait_ms_per_step.backlog", "per_unit", 1.5)])
def test_metric_reads_the_new_counters_and_is_silent_without(name, reader,
                                                             want):
    spec, value = _read(name, WITH)
    assert spec["reader"] == reader
    assert value == pytest.approx(want)
    assert _read(name, PARENT)[1] is None
    assert _read(name, {"window_s": 51.0, "engine": {}})[1] is None
    assert spec["cells"] == ["gpt3-1.3b.docs_backlog"]
    assert spec["moves"] == "serve_tok_s"
    assert spec["source"] == "program_counter"
    with open(os.path.join(METRICS,
                           "where.kv.spill_ms_per_step.backlog.json")) as f:
        assert spec["layer"] == json.load(f)["layer"]
    for counter in spec["args"]["over"] + [spec["args"]["by"]]:
        assert counter in HOST_PHASE_COUNTERS + ("unified_steps",)


def test_manifest_lists_them_last():
    """Last when PR 32 added them (the driver takes new entries only at
    the end of a list); what later PRs add comes after, in name order."""
    with open(os.path.join(METRICS, os.pardir, os.pardir,
                           "BENCHMARK.json")) as f:
        names = [m["name"] for m in json.load(f)["per_layer"]]
    at = names.index("where.z.kv_spill.pages_per_batch.backlog")
    assert names[at + 1] == "where.z.kv_spill.wait_ms_per_step.backlog"
    assert names[:at + 2] == sorted(names[:at + 2])
    assert all(n > names[at + 1] for n in names[at + 2:])
