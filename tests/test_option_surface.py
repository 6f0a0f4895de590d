"""The serving path's option surface, pinned: a new keyword of
`ServingEngine.__init__` or a new `PADDLE_TPU_*` gate needs an edit
here, which a reviewer sees (ROADMAP D1: every independent option
doubles what the tests and the cells must cover)."""
import glob
import inspect
import os
import re

import pytest

from paddle_tpu.serving import ServingEngine

ROOT = os.path.join(os.path.dirname(__file__), os.pardir, "paddle_tpu")

KEYWORDS = {
    "num_slots", "max_len", "page_size", "num_pages", "chunk_len",
    "scheduler", "metrics", "max_queue", "clock", "attn_impl",
    "prefix_cache", "token_budget", "spec", "preempt", "host_pages",
    "kv_dtype", "obs", "flight_steps", "mesh", "adapters",
    "adapter_pages", "adapter_ranks", "slo", "cost_census", "grammar",
    "megakernel", "session_ttl_s", "draft_pages"}

GATES = {"PADDLE_TPU_" + name for name in (
    "MESH", "SPEC_DECODE", "PREFIX_CACHE", "PAGED_ATTN", "KV_DTYPE",
    "DEBUG", "ADAPTERS", "PREEMPT", "KV_FABRIC", "FAULTS",
    "CONTROLPLANE", "GRAMMAR", "COST_CENSUS", "SLO", "OBS",
    "FLIGHT_STEPS", "MEGAKERNEL")}


def test_engine_keywords():
    params = inspect.signature(ServingEngine.__init__).parameters
    got = {p.name for p in params.values() if p.kind is p.KEYWORD_ONLY}
    assert got == KEYWORDS and len(KEYWORDS) == 28


def test_environment_gates_of_the_serving_path():
    """Every `PADDLE_TPU_*` name in the source of `serving/`,
    `nlp/generation.py` and `ops/pallas/paged_attention.py`, aside from
    the Pallas interpreter's switch."""
    files = glob.glob(os.path.join(ROOT, "serving", "**", "*.py"),
                      recursive=True)
    files += [os.path.join(ROOT, "nlp", "generation.py"),
              os.path.join(ROOT, "ops", "pallas", "paged_attention.py")]
    got = set()
    for path in files:
        with open(path) as f:
            got |= set(re.findall(r"PADDLE_TPU_[A-Z0-9_]+", f.read()))
    assert got - {"PADDLE_TPU_PALLAS_INTERPRET"} == GATES
    assert len(GATES) == 17


@pytest.mark.parametrize("gone", ["unified", "grouped"])
def test_decided_switches_are_gone(gone):
    """The ledger decided both forks (PR 31): the engine has one step
    program and derives the grouped walk."""
    with pytest.raises(TypeError):
        ServingEngine(None, cache_spec=(1, 1, 8), **{gone: False})
