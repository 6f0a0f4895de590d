"""The names the yardstick reads exist in the program.

`benchmark/metrics/*.json` name counters and histograms of
`ServingMetrics` and host spans of the engine. A PR that drops one
learns it on the chip, as a `null` under `per_layer`; this file says so
on the CPU. It reads the metric files and edits nothing there.

- a metric whose arguments have `"source": "engine"` (at the top or in
  an operand of a difference): every counter it names under `over`,
  `by`, `of` is a numeric key of the metrics' `_snapshot_locked()`, and
  every `series` a histogram attribute with the `count` and `_recent`
  that `benchmark/kinds/serve_http.py` `EngineWindow` takes;
- `moe_roofline`: its `hit` and `here` counters likewise;
  `latent_roofline`: its `pairs` and `distinct` counters;
  `sparse_roofline`: the counters its `flops` and `bytes` name;
- `span_idle`: every span under `spans` and `excluding` is a `SPAN_*`
  constant that the engine or the HTTP driver opens; `idle_outside`
  and `leaf_idle`: every span under `spans` likewise, and
  `leaf_idle`'s `counter` a counter as above.
"""
import glob
import json
import os
import re

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.nlp import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import SamplingParams, ServingEngine
from paddle_tpu.serving import engine as engine_mod
from paddle_tpu.serving.http import driver as driver_mod

METRICS_DIR = os.path.join(os.path.dirname(__file__), os.pardir,
                           "benchmark", "metrics")


def _listed(x):
    return [x] if isinstance(x, str) else list(x or ())


def engine_names(args):
    """(counters, series) that `args` reads from the engine."""
    counters, series = [], []
    if args.get("source") == "engine":
        for key in ("over", "by", "of"):
            counters += _listed(args.get(key))
        series += _listed(args.get("series"))
    for operand in args.values():
        if isinstance(operand, dict):
            c, s = engine_names(operand)
            counters += c
            series += s
    return counters, series


def _metric_files():
    out = []
    for path in sorted(glob.glob(os.path.join(METRICS_DIR, "*.json"))):
        with open(path) as f:
            spec = json.load(f)
        counters, series = engine_names(spec["args"])
        if spec["reader"] == "moe_roofline":
            counters += [spec["args"]["hit"], spec["args"]["here"]]
        if spec["reader"] == "latent_roofline":
            counters += [spec["args"]["pairs"], spec["args"]["distinct"]]
        if spec["reader"] == "leaf_idle":
            counters.append(spec["args"]["counter"])
        if spec["reader"] == "sparse_roofline":
            counters += [*spec["args"]["flops"].values(),
                         *spec["args"]["bytes"].values()]
        if spec["reader"] == "split_roofline":
            counters += [spec["args"]["pairs"], spec["args"]["keys"]]
        spans = (_listed(spec["args"].get("spans"))
                 + _listed(spec["args"].get("excluding"))
                 if spec["reader"] in ("span_idle", "idle_outside",
                                       "leaf_idle")
                 else [])
        if counters or series or spans:
            out.append(pytest.param(
                counters, series, spans,
                id=os.path.basename(path)[:-len(".json")]))
    return out


@pytest.fixture(scope="module")
def metrics():
    """The metrics object of a tiny engine after one short run."""
    paddle.seed(3)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=97, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=64, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0))
    model.eval()
    eng = ServingEngine(model, num_slots=2, max_len=48, page_size=8,
                        chunk_len=8)
    eng.generate([np.arange(1, 12, dtype=np.int64)],
                 SamplingParams(max_new_tokens=3))
    return eng.metrics


def opened_spans():
    """Values of the `SPAN_*` constants that their module passes to a
    `RecordEvent` or to the engine's `_phase`."""
    out = set()
    for mod in (engine_mod, driver_mod):
        with open(mod.__file__) as f:
            src = f.read()
        for name, value in vars(mod).items():
            if name.startswith("SPAN_") and re.search(
                    rf"(RecordEvent|_phase)\(\s*{name}\b", src):
                out.add(value)
    return out


def test_the_yardstick_names_something():
    """35 metrics read the engine's counters and histograms at the top
    of their arguments, 2 more in an operand, 6 through the roofline
    readers, 1 through `leaf_idle`; 14 read spans, 2 of them through
    `idle_outside` and 1 through `leaf_idle`."""
    cases = [p.values for p in _metric_files()]
    assert sum(1 for c, s, sp in cases if c or s) == 44
    assert sum(1 for c, s, sp in cases if sp) == 14


@pytest.mark.parametrize("counters,series,spans", _metric_files())
def test_metric_reads_names_the_program_has(metrics, counters, series,
                                            spans):
    with metrics._lock:
        snap = metrics._snapshot_locked()
    for name in counters:
        assert isinstance(snap.get(name), (int, float)) \
            and not isinstance(snap[name], bool), name
    for name in series:
        hist = getattr(metrics, name)
        assert hasattr(hist, "count") and hasattr(hist, "_recent"), name
    assert set(spans) <= opened_spans(), spans
