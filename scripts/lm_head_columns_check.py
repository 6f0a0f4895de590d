"""Does the LM head over the kept columns give, bit for bit, the logits
the head over every column gives there?

The unified serving step runs its causal-LM wrapper with `columns=`: one
column a row (the one whose logits the row keeps), gathered from the
final hidden states before the vocabulary-wide matmul. This builds a
served configuration from its benchmark file (weights from `--seed`),
feeds one batch shaped like the step ([num_slots, chunk_len] tokens) to
`model(ids)` and to `model(ids, columns=cols)` under `jax.jit`, and
compares the narrow logits with the full ones at the same columns.
Prints one JSON line: the largest absolute difference in float32,
whether every row's argmax agrees, and the device.

    python scripts/lm_head_columns_check.py \\
        --config benchmark/configs/gpt3-1.3b-serve.json
    python scripts/lm_head_columns_check.py \\
        --config benchmark/configs/laguna-s-2.1-serve-ep2.json
    # rehearsal on the CPU, at the rehearsal's tiny sizes
    JAX_PLATFORMS=cpu python scripts/lm_head_columns_check.py \\
        --config benchmark/rehearsal/configs/tiny-laguna-serve.json
"""
import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def build(cfg, seed):
    """The model a cell of this configuration serves."""
    kind = cfg["kind"]
    if kind == "serve_http":
        from benchmark.kinds.serve_http import build_gpt
        return build_gpt(cfg["model"], cfg["dtype"], seed)
    if kind == "serve_http_laguna":
        from benchmark.kinds.serve_http_laguna import build_laguna
        return build_laguna(cfg, seed)
    raise SystemExit(f"no builder here for kind {kind!r}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True,
                    help="a serving configuration file of the benchmark")
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.nlp.generation import _restore_state, _swap_state

    with open(args.config) as f:
        cfg = json.load(f)
    model = build(cfg, args.seed)
    state = (list(model.parameters())
             + [b for _, b in model.named_buffers()])
    eng = cfg["engine"]
    slots, width = eng["num_slots"], eng["chunk_len"]
    vocab = (cfg["model"] if "model" in cfg else cfg)["vocab_size"]
    rng = np.random.default_rng([args.seed, 42])
    ids = jnp.asarray(rng.integers(0, vocab, (slots, width)), jnp.int32)
    # a decoding row's column 0, a prefill row's last real column
    cols = jnp.asarray(np.where(np.arange(slots) % 2 == 0, 0,
                                rng.integers(0, width, slots))[:, None],
                       jnp.int32)

    def run(vals, ids, cols):
        originals = _swap_state(state, vals)
        try:
            c = None if cols is None else Tensor(cols)
            return model(Tensor(ids), columns=c)._value.astype(jnp.float32)
        finally:
            _restore_state(state, originals)

    # the weights are the programs' argument, as in the engine's step
    vals = [t._value for t in state]
    full = jax.jit(lambda v, i: run(v, i, None))(vals, ids)
    full = np.asarray(jnp.take_along_axis(full, cols[:, :, None],
                                          axis=1))
    narrow = np.asarray(jax.jit(run)(vals, ids, cols))
    diff = np.abs(narrow - full)
    dev = jax.devices()[0]
    print(json.dumps({
        "config": os.path.basename(args.config),
        "rows": slots, "width": width, "vocab": int(full.shape[-1]),
        "bit_identical": bool(np.array_equal(narrow, full)),
        "max_abs_diff": float(diff.max()),
        "argmax_agree": bool((narrow.argmax(-1) == full.argmax(-1)).all()),
        "device": {"platform": dev.platform, "kind": dev.device_kind}}))


if __name__ == "__main__":
    main()
