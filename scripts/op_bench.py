"""Op micro-benchmark harness (reference:
/root/reference/paddle/fluid/operators/benchmark/op_tester.cc:1 +
tools/ci_op_benchmark.sh:1 — config-driven single-op timing feeding a
CI regression gate; see scripts/op_bench_check.py for the gate).

For each op: `host_us` (eager dispatch cost, async — the Python->
device-queue path that SURVEY §3.1 flags) and `wall_us` (pipelined
wall time per op incl. device execution, measured over a chained loop
with one host sync at the end). Writes a JSON report and prints one
summary line.

Usage:
  python scripts/op_bench.py [--out op_bench.json] [--iters 200]
  python scripts/op_bench_check.py old.json new.json   # the gate
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _cases():
    """(name, build() -> (fn, args)) for the hot ops. Shapes sized so
    device work is measurable but dispatch still dominates on CPU."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu import ops
    import paddle_tpu.nlp.generation  # noqa: F401  (paged decode ops)
    from paddle_tpu.ops._helpers import apply_op

    rng = np.random.RandomState(0)

    def t(*shape, dtype="float32"):
        if dtype == "int64":
            return paddle.to_tensor(
                rng.randint(0, 100, shape).astype(np.int64))
        if dtype == "bool":
            return paddle.to_tensor(rng.rand(*shape) > 0.5)
        return paddle.to_tensor(rng.randn(*shape).astype(dtype))

    M = (256, 256)
    cases = {
        "add": lambda: (paddle.add, (t(*M), t(*M))),
        "multiply": lambda: (paddle.multiply, (t(*M), t(*M))),
        "scale": lambda: (lambda x: paddle.scale(x, 1.01), (t(*M),)),
        "exp": lambda: (paddle.exp, (t(*M),)),
        "tanh": lambda: (paddle.tanh, (t(*M),)),
        "relu": lambda: (F.relu, (t(*M),)),
        "gelu": lambda: (F.gelu, (t(*M),)),
        "sigmoid": lambda: (F.sigmoid, (t(*M),)),
        "sqrt": lambda: (paddle.sqrt, (t(*M) * 0 + 2.0,)),
        "pow": lambda: (lambda x: paddle.pow(x, 2.0), (t(*M),)),
        "maximum": lambda: (paddle.maximum, (t(*M), t(*M))),
        "where": lambda: (paddle.where,
                          (t(*M, dtype="bool"), t(*M), t(*M))),
        "cast": lambda: (lambda x: x.astype("bfloat16"), (t(*M),)),
        "matmul": lambda: (paddle.matmul, (t(256, 256), t(256, 256))),
        "matmul_batched": lambda: (paddle.matmul,
                                   (t(8, 128, 64), t(8, 64, 128))),
        "conv2d": lambda: (
            lambda x, w: F.conv2d(x, w, padding=1),
            (t(8, 16, 32, 32), t(32, 16, 3, 3))),
        "softmax": lambda: (F.softmax, (t(64, 1024),)),
        "log_softmax": lambda: (F.log_softmax, (t(64, 1024),)),
        "cross_entropy": lambda: (
            F.cross_entropy, (t(64, 100), t(64, dtype="int64") % 100)),
        "layer_norm": lambda: (
            lambda x, w, b: F.layer_norm(x, 256, w, b),
            (t(64, 256), t(256), t(256))),
        "batch_norm_infer": lambda: (
            lambda x, m, v, w, b: F.batch_norm(x, m, v, w, b),
            (t(8, 16, 32, 32), t(16), t(16) * 0 + 1.0, t(16), t(16))),
        "dropout_eval": lambda: (
            lambda x: F.dropout(x, 0.5, training=False), (t(*M),)),
        "reduce_sum": lambda: (paddle.sum, (t(*M),)),
        "reduce_mean_axis": lambda: (
            lambda x: paddle.mean(x, axis=1), (t(*M),)),
        "argmax": lambda: (lambda x: paddle.argmax(x, -1), (t(*M),)),
        "cumsum": lambda: (lambda x: paddle.cumsum(x, -1), (t(*M),)),
        "topk": lambda: (lambda x: paddle.topk(x, 8), (t(64, 1024),)),
        "sort": lambda: (lambda x: paddle.sort(x, -1), (t(64, 256),)),
        "transpose": lambda: (
            lambda x: paddle.transpose(x, [1, 0]), (t(*M),)),
        "reshape": lambda: (
            lambda x: paddle.reshape(x, [64, 1024]), (t(*M),)),
        "concat": lambda: (
            lambda a, b: paddle.concat([a, b], axis=0),
            (t(*M), t(*M))),
        "split": lambda: (
            lambda x: paddle.split(x, 2, axis=1), (t(*M),)),
        "gather": lambda: (
            lambda x, i: paddle.gather(x, i),
            (t(*M), t(64, dtype="int64") % 256)),
        "index_select": lambda: (
            lambda x, i: paddle.index_select(x, i),
            (t(*M), t(64, dtype="int64") % 256)),
        "embedding": lambda: (
            lambda i, w: F.embedding(i, w),
            (t(64, 32, dtype="int64") % 1000, t(1000, 64))),
        "one_hot": lambda: (
            lambda i: F.one_hot(i % 64, 64),
            (t(64, dtype="int64"),)),
        "clip": lambda: (
            lambda x: paddle.clip(x, -1.0, 1.0), (t(*M),)),
        "tril": lambda: (paddle.tril, (t(*M),)),
        "masked_fill": lambda: (
            lambda x, m: paddle.masked_fill(x, m, 0.0),
            (t(*M), t(*M, dtype="bool"))),
        "squeeze_unsqueeze": lambda: (
            lambda x: paddle.unsqueeze(paddle.squeeze(x, 0), 0),
            (t(1, *M),)),
        # ragged paged-attention decode: 8 slots x 8 pages of 16 over
        # 8 kv heads served to 8 query heads (the serving hot path; on
        # CPU this times the pure-JAX reference, on TPU the kernel)
        "paged_decode_attention": lambda: (
            lambda q, kp, vp, pt, pos: apply_op(
                "paged_decode_attention", q, kp, vp, pt, pos),
            (t(8, 1, 8, 64), t(65, 16, 8, 64), t(65, 16, 8, 64),
             paddle.to_tensor(np.arange(1, 65, dtype=np.int32)
                              .reshape(8, 8)),
             paddle.to_tensor(np.full((8,), 100, np.int32)))),
        # ragged generalization (the serving engine's UNIFIED step):
        # the same pools, but a mixed batch — decode rows (q_len 1)
        # next to mid-prefill rows (q_len up to the step width 16)
        # through one invocation
        "ragged_paged_attention": lambda: (
            lambda q, kp, vp, pt, pos, ql: apply_op(
                "ragged_paged_attention", q, kp, vp, pt, pos, ql),
            (t(8, 16, 8, 64), t(65, 16, 8, 64), t(65, 16, 8, 64),
             paddle.to_tensor(np.arange(1, 65, dtype=np.int32)
                              .reshape(8, 8)),
             paddle.to_tensor(np.asarray(
                 [100, 96, 88, 100, 40, 16, 0, 64], np.int32)),
             paddle.to_tensor(np.asarray(
                 [1, 1, 1, 1, 16, 16, 8, 3], np.int32)))),
        # speculative decoding's VERIFY shape through the same ragged
        # op: decode rows carrying 1 sampled + k drafts (q_len 1+k,
        # k=4 here) next to plain q_len-1 decode rows — the per-step
        # hot mix `ServingEngine(spec=...)` runs, tracked so the
        # verify pass keeps a perf number of its own
        # int8 lane of the ragged op over the SAME mixed batch: code
        # pools + rowwise scale pools, dequant fused in-kernel — the
        # serving hot path with PADDLE_TPU_KV_DTYPE=int8 (on CPU this
        # times the q8 reference; the HBM halving shows on the chip)
        "ragged_paged_attention_q8": lambda: (
            lambda q, kp, vp, ks, vs, pt, pos, ql: apply_op(
                "ragged_paged_attention_q8", q, kp, vp, ks, vs, pt,
                pos, ql),
            (t(8, 16, 8, 64),
             paddle.to_tensor((np.random.RandomState(7)
                               .randint(-127, 128, size=(65, 16, 8,
                                                         64)))
                              .astype(np.int8)),
             paddle.to_tensor((np.random.RandomState(8)
                               .randint(-127, 128, size=(65, 16, 8,
                                                         64)))
                              .astype(np.int8)),
             paddle.to_tensor(np.abs(np.random.RandomState(9)
                                     .randn(65, 16, 8))
                              .astype(np.float32) / 127.0),
             paddle.to_tensor(np.abs(np.random.RandomState(10)
                                     .randn(65, 16, 8))
                              .astype(np.float32) / 127.0),
             paddle.to_tensor(np.arange(1, 65, dtype=np.int32)
                              .reshape(8, 8)),
             paddle.to_tensor(np.asarray(
                 [100, 96, 88, 100, 40, 16, 0, 64], np.int32)),
             paddle.to_tensor(np.asarray(
                 [1, 1, 1, 1, 16, 16, 8, 3], np.int32)))),
        "ragged_paged_attention_verify": lambda: (
            lambda q, kp, vp, pt, pos, ql: apply_op(
                "ragged_paged_attention", q, kp, vp, pt, pos, ql),
            (t(8, 16, 8, 64), t(65, 16, 8, 64), t(65, 16, 8, 64),
             paddle.to_tensor(np.arange(1, 65, dtype=np.int32)
                              .reshape(8, 8)),
             paddle.to_tensor(np.asarray(
                 [100, 96, 88, 75, 40, 16, 9, 64], np.int32)),
             paddle.to_tensor(np.asarray(
                 [5, 5, 5, 5, 1, 1, 5, 3], np.int32)))),
        # prefix-sharing-aware GROUPED walk over the same pools: the
        # first four decode rows share a 4-page physical prefix (one
        # group — the system-prompt shape), the rest walk privately.
        # On the chip the shared pages stream once per group; on CPU
        # this times the reference — the entry exists so the grouped
        # op keeps a tracked perf number next to the flat ragged one.
        "ragged_paged_attention_grouped": lambda: (
            lambda q, kp, vp, pt, pos, ql, gid, gld, gcn: apply_op(
                "ragged_paged_attention_grouped", q, kp, vp, pt, pos,
                ql, gid, gld, gcn),
            (t(8, 16, 8, 64), t(65, 16, 8, 64), t(65, 16, 8, 64),
             paddle.to_tensor(_grouped_page_table()),
             paddle.to_tensor(np.asarray(
                 [100, 96, 88, 100, 40, 16, 0, 64], np.int32)),
             paddle.to_tensor(np.asarray(
                 [1, 1, 1, 1, 16, 16, 8, 3], np.int32)),
             paddle.to_tensor(np.asarray(
                 [0, 0, 0, 0, 1, 2, 3, 4], np.int32)),
             paddle.to_tensor(np.asarray(
                 [0, 4, 5, 6, 7, 0, 0, 0], np.int32)),
             paddle.to_tensor(np.asarray(
                 [4, 0, 0, 0, 0, 0, 0, 0], np.int32)))),
        # ...and its int8 lane: code + rowwise scale pages chase the
        # same grouped stream (the quantized shared-prefix hot path)
        "ragged_paged_attention_grouped_q8": lambda: (
            lambda q, kp, vp, ks, vs, pt, pos, ql, gid, gld, gcn:
            apply_op(
                "ragged_paged_attention_grouped_q8", q, kp, vp, ks,
                vs, pt, pos, ql, gid, gld, gcn),
            (t(8, 16, 8, 64),
             paddle.to_tensor((np.random.RandomState(17)
                               .randint(-127, 128, size=(65, 16, 8,
                                                         64)))
                              .astype(np.int8)),
             paddle.to_tensor((np.random.RandomState(18)
                               .randint(-127, 128, size=(65, 16, 8,
                                                         64)))
                              .astype(np.int8)),
             paddle.to_tensor(np.abs(np.random.RandomState(19)
                                     .randn(65, 16, 8))
                              .astype(np.float32) / 127.0),
             paddle.to_tensor(np.abs(np.random.RandomState(20)
                                     .randn(65, 16, 8))
                              .astype(np.float32) / 127.0),
             paddle.to_tensor(_grouped_page_table()),
             paddle.to_tensor(np.asarray(
                 [100, 96, 88, 100, 40, 16, 0, 64], np.int32)),
             paddle.to_tensor(np.asarray(
                 [1, 1, 1, 1, 16, 16, 8, 3], np.int32)),
             paddle.to_tensor(np.asarray(
                 [0, 0, 0, 0, 1, 2, 3, 4], np.int32)),
             paddle.to_tensor(np.asarray(
                 [0, 4, 5, 6, 7, 0, 0, 0], np.int32)),
             paddle.to_tensor(np.asarray(
                 [4, 0, 0, 0, 0, 0, 0, 0], np.int32)))),
        # decode MEGAKERNEL, greedy-epilogue variant: the fused
        # scatter+attend over 8 decode rows (q_len 1) immediately
        # followed by the decode_greedy_argmax epilogue over a held
        # [S, V] logits tile — the gate-on hot pair the unified step
        # dispatches per decode layer + once per step
        "megakernel_decode_greedy": lambda: (
            lambda q, kn, vn, kp, vp, pt, pos, ql, lg: (
                apply_op("megakernel_decode", q, kn, vn, kp, vp, pt,
                         pos, ql),
                apply_op("decode_greedy_argmax", lg)),
            (t(8, 1, 8, 64), t(8, 1, 8, 64), t(8, 1, 8, 64),
             t(65, 16, 8, 64), t(65, 16, 8, 64),
             paddle.to_tensor(np.arange(1, 65, dtype=np.int32)
                              .reshape(8, 8)),
             paddle.to_tensor(np.full((8,), 100, np.int32)),
             paddle.to_tensor(np.ones((8,), np.int32)),
             t(8, 4096))),
        # ...and its LoRA-prologue variant: the same fused decode
        # walk with 9 extra operands — per-row hidden states, full
        # A/B adapter pools for q/k/v and the page/scale row operands
        # — so the per-row low-rank deltas ride the kernel prologue
        # (the multi-tenant gate-on shape)
        "megakernel_decode_lora": lambda: (
            lambda q, kn, vn, kp, vp, pt, pos, ql, x, aq, bq, ak, bk,
            av, bv, ap, asc: apply_op(
                "megakernel_decode", q, kn, vn, kp, vp, pt, pos, ql,
                x, aq, bq, ak, bk, av, bv, ap, asc,
                attrs=dict(lora=True)),
            (t(8, 1, 8, 64), t(8, 1, 8, 64), t(8, 1, 8, 64),
             t(65, 16, 8, 64), t(65, 16, 8, 64),
             paddle.to_tensor(np.arange(1, 65, dtype=np.int32)
                              .reshape(8, 8)),
             paddle.to_tensor(np.full((8,), 100, np.int32)),
             paddle.to_tensor(np.ones((8,), np.int32)),
             t(8, 1, 256),
             t(3, 256, 4), t(3, 4, 512), t(3, 256, 4), t(3, 4, 512),
             t(3, 256, 4), t(3, 4, 512),
             paddle.to_tensor(np.asarray(
                 [0, 1, 2, 0, 1, 2, 0, 0], np.int32)),
             paddle.to_tensor(np.full((8,), 0.5, np.float32)))),
    }
    return cases


def _grouped_page_table():
    """Page table for the grouped op-bench entries: rows 0-3 share a
    4-page physical prefix (one group), every row owns a private
    tail — the operand contract of the grouped walk."""
    pt = np.zeros((8, 8), np.int32)
    nxt = 5
    for r in range(8):
        start = 0
        if r < 4:
            pt[r, :4] = [1, 2, 3, 4]
            start = 4
        for i in range(start, 8):
            pt[r, i] = nxt
            nxt += 1
    return pt


def _sync(v):
    out = v
    while isinstance(out, (tuple, list)):
        out = out[0]
    np.asarray(out.numpy()).ravel()[:1]


def bench_op(fn, args, iters, repeats=5):
    """Best-of-`repeats` for both metrics: a single loop of host
    timings is polluted by multi-ms scheduling spikes on a shared host
    (the builders' earlier runs differed 5-10x per op without this; the
    MIN is the stable statistic)."""
    out = fn(*args)  # warm (jit compile)
    _sync(out)
    host_us = wall_us = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        host_us = min(host_us,
                      (time.perf_counter() - t0) / iters * 1e6)
        _sync(out)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        _sync(out)
        wall_us = min(wall_us,
                      (time.perf_counter() - t0) / iters * 1e6)
    return round(host_us, 2), round(wall_us, 2)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--iters", type=int, default=200)
    ap.add_argument("--ops", default=None,
                    help="comma-separated subset of op names")
    args = ap.parse_args()

    import paddle_tpu  # noqa: F401  (applies device config before jax init)
    import jax
    platform = jax.devices()[0].platform
    cases = _cases()
    if args.ops:
        want = set(args.ops.split(","))
        cases = {k: v for k, v in cases.items() if k in want}

    report = {"platform": platform, "iters": args.iters, "ops": {}}
    for name, build in cases.items():
        fn, fargs = build()
        host_us, wall_us = bench_op(fn, fargs, args.iters)
        report["ops"][name] = {"host_us": host_us, "wall_us": wall_us}
        print(f"{name:22s} host {host_us:8.1f} us  wall "
              f"{wall_us:8.1f} us", file=sys.stderr)

    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
    med = float(np.median([v["host_us"]
                           for v in report["ops"].values()]))
    print(json.dumps({
        "metric": "op_dispatch_median_us",
        "value": round(med, 2),
        "unit": f"us/op ({platform}, {len(report['ops'])} ops, "
                "eager host dispatch)",
        "vs_baseline": 0.0,
    }))


if __name__ == "__main__":
    main()
