"""The second reading behind the tolerances of
benchmark/configs/laguna-s-2.1-serve-ep2.json (`check`): what the plain
reference gives when computed in the nearest precision below the one
the configuration states. The configuration states bfloat16; below it
is an 8-bit float, so the reference is run once more over the same
weights rounded to float8_e4m3fn (the routed experts alone, then every
matrix), and its choices are judged as the engine's are: teacher-forced
over one random sequence of the cell's check width, for each position
gap = best float32-reference logit - float32-reference logit of the
token the low-precision pass chose (`ref_laguna.laguna_gaps`' rule,
near ties apart). It has to come out as NOT correct by one of the
cell's limits.

    chiprun -- python scripts/laguna_precision_reading.py --seed 7
"""
import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--config", default="laguna-s-2.1-serve-ep2")
    ap.add_argument("--mix", default="code_mixed")
    ap.add_argument("--positions", type=int, default=512)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from paddle_tpu.utils.compile_cache import use_compile_cache
    from benchmark import ref_laguna as ref
    from benchmark.kinds import serve_http_laguna as kind
    use_compile_cache()
    with open(os.path.join(ROOT, "benchmark/configs", args.config + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "benchmark/traffic", args.mix + ".json")) as f:
        width = ref.check_width(json.load(f))
    chk, rcfg = cfg["check"], kind.reference_config(cfg)
    model = kind.build_laguna(cfg, args.seed)
    weights = ref.laguna_weights(model)
    ids = np.random.default_rng([args.seed, 9]).integers(
        0, cfg["vocab_size"], size=width)
    pos = np.arange(width - args.positions, width)
    want, margin = (np.asarray(v) for v in ref.laguna_logits(
        weights, rcfg, ids, pos))
    out = {"device": jax.devices()[0].device_kind, "width": width,
           "positions": len(pos), "check": chk}

    def fp8(v):
        return v.astype(jnp.float8_e4m3fn).astype(v.dtype)
    # one after the other and IN PLACE, parameter by parameter: two
    # copies of 11 GB of weights do not fit the chip (the second
    # variant therefore includes the first)
    variants = {
        "experts_fp8": lambda n: ".mlp.experts_" in n,
        "all_matrices_fp8": lambda n: "norm" not in n
        and ".mlp.experts_" not in n,
    }
    del weights
    for name, which in variants.items():
        for n, p in model.named_parameters():
            if which(n):
                p._value = fp8(p._value)
        got, _ = ref.laguna_logits(ref.laguna_weights(model), rcfg, ids, pos)
        got = np.asarray(got)
        chosen = got.argmax(-1)
        gap = want.max(-1) - want[np.arange(len(pos)), chosen]
        tie = margin < chk["tie_margin"]
        row = {"gap": float(gap[~tie].max()) if (~tie).any() else 0.0,
               "tie_gap": float(gap[tie].max()) if tie.any() else 0.0,
               "tie_share": float(tie.mean()),
               "match": float((chosen == want.argmax(-1)).mean()),
               "logit_abs_diff_max": float(np.abs(got - want).max()),
               "gap_p50": float(np.median(gap)),
               "gap_p99": float(np.quantile(gap, 0.99))}
        row["correct"] = bool(row["gap"] <= chk["tolerance"]
                              and row["tie_gap"] <= chk["tie_tolerance"]
                              and row["tie_share"] <= chk["max_tie_share"]
                              and row["match"] >= chk["min_match"])
        out[name] = row
    out["logit_spread"] = float(want.max(-1).mean() - want.mean())
    out["margin_quantiles"] = {q: float(np.quantile(margin, q))
                               for q in (0.01, 0.05, 0.1, 0.25, 0.5)}
    print(json.dumps(out))


if __name__ == "__main__":
    main()
