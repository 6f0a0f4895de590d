"""The routed experts of one layer, alone, on the chip: the Pallas
kernel (`ops/pallas/moe.py` `moe_experts`) against `jax.lax.ragged_dot`
over the same sorted assignments, at the serving step's shape (2048
token rows of which `--valid` are live), with Laguna-S-2.1's widths and
this chip's share of the experts. Prints ms a call by the host clock
around `block_until_ready`, and, with `--trace`, the device time of the
events whose text holds each `ptk:` name.

    chiprun -- python scripts/moe_experts_bench.py --valid 16,1024
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--valid", default="16,1024")
    ap.add_argument("--rows", type=int, default=2048)
    ap.add_argument("--hidden", type=int, default=3072)
    ap.add_argument("--width", type=int, default=1024)
    ap.add_argument("--experts", type=int, default=256)
    ap.add_argument("--local", type=int, default=128)
    ap.add_argument("--top-k", type=int, default=10)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--trace", type=int, default=1)
    ap.add_argument("--dump", type=int, default=0,
                    help="print what each traced run's trace holds "
                         "(benchmark/trace.py's listing)")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import paddle_tpu  # noqa: F401  (x64 mode as the program runs)
    from paddle_tpu.ops.pallas import moe
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform}))
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    t, h, f = args.rows, args.hidden, args.width
    x = jax.random.normal(k[0], (t, h), jnp.bfloat16)
    wr = (jax.random.normal(k[1], (h, args.experts), jnp.float32)
          * 0.02).astype(jnp.bfloat16)
    wg, wu = (jax.random.normal(kk, (args.local, h, f), jnp.bfloat16) * 0.02
              for kk in k[2:4])
    wd = jax.random.normal(k[4], (args.local, f, h), jnp.bfloat16) * 0.02
    kw = dict(top_k=args.top_k, scale=2.5, norm_topk=True, first=0)
    out = {}
    for nv in (int(v) for v in args.valid.split(",")):
        valid = jnp.arange(t) < nv
        for impl in ("ragged_dot", "kernel"):
            name = f"{impl}.valid{nv}"

            # routed_experts takes the kernel on a TPU: the other form is
            # asked for the way the CPU gets it
            moe._use_kernel = lambda impl=impl: impl == "kernel"

            def body(x, valid, wr, wg, wu, wd):
                return moe.routed_experts(x, valid, wr, wg, wu, wd, **kw)
            fn = jax.jit(body)
            try:
                t0 = time.perf_counter()
                o, st = jax.block_until_ready(fn(x, valid, wr, wg, wu, wd))
                comp = time.perf_counter() - t0
                t0 = time.perf_counter()
                for _ in range(args.iters):
                    o, st = fn(x, valid, wr, wg, wu, wd)
                jax.block_until_ready(o)
                ms = 1e3 * (time.perf_counter() - t0) / args.iters
                row = {"ms": ms, "compile_s": comp,
                       "stats": [int(v) for v in st],
                       "abs_mean": float(jnp.abs(o.astype(jnp.float32)).mean())}
                if args.trace:
                    row.update(_traced(fn, (x, valid, wr, wg, wu, wd), name,
                                       args.dump))
            except Exception as e:      # a candidate the compiler refuses
                row = {"error": f"{type(e).__name__}: {str(e)[:400]}"}
            out[name] = row
            print(json.dumps({name: row}), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/moe_experts_bench.json", "w") as fh:
        json.dump(out, fh, indent=1)


def _traced(fn, fn_args, name, dump=0):
    """Device seconds a call under each `ptk:` name, 5 calls traced."""
    import glob
    import shutil
    import jax
    from jax.profiler import ProfileData
    from benchmark import trace
    d = os.path.join("chiprun_out", "moe_trace", name)
    shutil.rmtree(d, ignore_errors=True)
    jax.profiler.start_trace(d)
    for _ in range(5):
        o = fn(*fn_args)
    jax.block_until_ready(o)
    jax.profiler.stop_trace()
    files = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    red = trace.reduce_xspace(ProfileData.from_file(files[0]))
    if dump:
        trace.main([files[0]])
    shutil.rmtree(d, ignore_errors=True)
    if not red:
        return {}
    res = {"busy_ms": 1e3 * red["busy_s"] / 5}
    for needle in ("ptk:moe_experts", "ptk:moe_route"):
        sec = sum(s for n, s in red["ops"].items()
                  if needle in red["text"][n])
        res[needle + "_ms"] = 1e3 * sec / 5
    top = sorted(red["ops"].items(), key=lambda kv: -kv[1])[:6]
    res["top"] = [[n, round(1e3 * s / 5, 3),
                   [p for p in ("ptk:moe_experts", "ptk:moe_route")
                    if p in red["text"][n]]] for n, s in top]
    return res


if __name__ == "__main__":
    main()
