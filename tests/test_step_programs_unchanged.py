"""The unified step of the models the benchmark already measures is the
PARENT's program, byte for byte: a small GPT, Laguna, DeepSeek-V2 and
Keye-VL-2.0 engine each serve one request on the CPU (the jnp forms: the
text holds every operation of the step, its operands' shapes and the
pytrees' arity), the lowered step's text is hashed, and the hashes are
those of `tests/step_digests.json`, which was written by THIS file run
on the parent commit's tree (a new model adds nothing to what the
accepted cells trace and lower). The router's softmax path, which every
accepted expert model runs, is hashed the same way, lowered alone, and so
is the training step of a small GPT-2 built as the pretraining cell
builds its own (bf16 weights, AdamW, `jit.compile_train_step`).

A PR that changes one of these steps on purpose writes the file anew on
its own tree and says so:

    STEP_DIGESTS_WRITE=1 JAX_PLATFORMS=cpu python -m pytest \\
        tests/test_step_programs_unchanged.py -q
"""
import hashlib
import json
import os
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.nlp import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import SamplingParams, ServingEngine

from test_deepseek_v2 import tiny_dsv2
from test_keye_vl2 import tiny_keye
from test_laguna import tiny_laguna



@pytest.fixture(autouse=True)
def jnp_forms(monkeypatch):
    """Not interpret mode, whatever an earlier test file of this worker
    asked for at its import (as tests/test_tpu_compile.py): on the CPU
    the step then holds the jnp forms, which is what was hashed."""
    from paddle_tpu.ops.pallas import (flash_attention, layer_norm, mla,
                                       moe, paged_attention)
    for mod in (flash_attention, layer_norm, mla, moe, paged_attention):
        monkeypatch.setattr(mod, "_INTERPRET", False)


DIGESTS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "step_digests.json")


def _tiny_gpt():
    paddle.seed(3)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=97, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=64, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0))
    model.eval()
    return model


MODELS = {"gpt": _tiny_gpt, "laguna": tiny_laguna, "deepseek_v2": tiny_dsv2,
          "keye": tiny_keye}


def step_text(name):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        eng = ServingEngine(MODELS[name](), num_slots=2, max_len=64,
                            page_size=4, chunk_len=16)
    eng.generate([np.arange(1, 24, dtype=np.int64)],
                 SamplingParams(max_new_tokens=3))
    return eng.lowered_unified_step().as_text()


def route_text(top_k, n_group, topk_group):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import moe
    f = jax.jit(lambda x, w, v: moe.moe_route(
        x, w, v, top_k=top_k, scale=2.5, norm_topk=True, first=8,
        n_local=8, n_group=n_group, topk_group=topk_group))
    return f.lower(jax.ShapeDtypeStruct((64, 32), jnp.float32),
                   jax.ShapeDtypeStruct((32, 16), jnp.float32),
                   jax.ShapeDtypeStruct((64,), jnp.bool_)).as_text()


ROUTES = {"moe_route_softmax.k2g1": (2, 1, 1),
          "moe_route_softmax.k6g4": (6, 4, 2)}


def train_text():
    import jax
    import paddle_tpu.optimizer as opt
    from paddle_tpu import jit
    before = jax.config.jax_default_matmul_precision
    paddle.set_matmul_precision("default")
    try:
        model = _tiny_gpt()
        model.to(dtype="bfloat16")
        optimizer = opt.AdamW(learning_rate=1e-4,
                              parameters=model.parameters(),
                              weight_decay=0.01)
        step = jit.compile_train_step(
            lambda ids, labels: model(ids, labels=labels), model,
            optimizer)
        ids = paddle.to_tensor(np.arange(32, dtype=np.int64)
                               .reshape(2, 16) % 97)
        return step.compile_info(ids, ids).as_text()
    finally:
        paddle.set_matmul_precision(before)


@pytest.mark.parametrize("name", sorted(MODELS) + sorted(ROUTES)
                         + ["gpt2_train"])
def test_lowered_step_is_the_parents(name):
    if name in MODELS:
        text = step_text(name)
    elif name in ROUTES:
        text = route_text(*ROUTES[name])
    else:
        text = train_text()
    digest = hashlib.sha256(text.encode()).hexdigest()
    if os.environ.get("STEP_DIGESTS_WRITE"):
        have = {}
        if os.path.exists(DIGESTS):
            with open(DIGESTS) as f:
                have = json.load(f)
        have[name] = digest
        with open(DIGESTS, "w") as f:
            json.dump(have, f, indent=1, sort_keys=True)
            f.write("\n")
        return
    with open(DIGESTS) as f:
        assert json.load(f)[name] == digest
