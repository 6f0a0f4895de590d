"""The second readings behind the limits of
benchmark/configs/z.mimo-v2-flash-serve-ep16.json (`check`), taken THROUGH
the harness: one whole run of the cell (`benchmark/run.py`'s own `main`:
the window, the result line, `correct` for the engine), after which the
kind puts a variant of the plain reference through the same comparison
on the same sampled requests (`kinds/serve_http_mimo.run(ctx,
controls=...)`, `ref_mimo_v2.judge_choices`, `passes`):

- `no_sinks`: the same weights with the window layers' sinks LEFT OUT
  of every softmax (what a walk that dropped the sink would compute);
- `no_bias`: the same weights with the selection bias LEFT OUT of every
  router's choice (what a router that ignored `noaux_tc` would compute);
- `all_matrices_fp8`: the reference computed in the nearest precision
  below the one the configuration states (bfloat16 -> every weight
  matrix rounded to float8_e4m3fn).

Each is judged as the engine is: at every emitted token's position the
variant's own argmax over the engine's context, gap = best float32
reference logit - float32 reference logit of that token. Each has to come
out as NOT correct; the log's `control <name>:` line says by which
limit. On one chip:

    python scripts/mimo_controls_reading.py \\
        --workload z.mimo-v2-flash.long_prompts --seed 7 --seconds 51

(`--trace 1` takes the run's trace too: the controls' readings do not
depend on it.)
"""
import argparse
import functools
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="z.mimo-v2-flash.long_prompts")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--controls", default="no_sinks,no_bias,all_matrices_fp8")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    import run as harness
    # (the harness imports the kind itself, after it has found its
    # device: this import must not come first)
    harness_context = harness.context

    def context(a):
        ctx = harness_context(a)
        from benchmark.kinds import serve_http_mimo as kind
        kind.run = functools.partial(kind.run,
                                     controls=args.controls.split(","))
        return ctx
    harness.context = context
    return harness.main(["--workload", args.workload, "--seed",
                         str(args.seed), "--seconds", str(args.seconds),
                         "--trace", str(args.trace)])


if __name__ == "__main__":
    sys.exit(main())
