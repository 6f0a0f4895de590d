"""Eager (dygraph) dispatch latency + eager train throughput.

SURVEY §3.1 names the per-op Python->device transition as the #1 perf
risk of an eager runtime; the reference pays it in the pybind layer
(paddle/fluid/pybind/eager_method.cc), we pay it in `apply_op` (cached
jit lookup + Tensor wrap + tape bookkeeping). This bench puts numbers on
it:

  - dispatch_us: host-side cost of one eager binary op (1k chained adds,
    async dispatch — no device sync inside the loop)
  - tape_us: same with autograd recording (requires_grad inputs)
  - eager LeNet train step/s: full dygraph fwd+bwd+SGD step, no
    compile_train_step — the reference's dygraph MNIST shape

Prints one JSON line per metric.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import jax
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F
    import paddle_tpu.optimizer as opt

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"

    # -- per-op dispatch cost (no grad) ---------------------------------
    x = paddle.to_tensor(np.ones((256, 256), np.float32))
    y = paddle.to_tensor(np.ones((256, 256), np.float32))
    x.stop_gradient = True
    y.stop_gradient = True
    z = x + y  # warm the jit cache
    float(z.sum())
    # min-of-batches: single 1000-op windows absorb host scheduling
    # spikes of 2-10x (BASELINE.md op-bench caveat)
    N, BATCHES = 200, 8
    dispatch_us = float("inf")
    for _ in range(BATCHES):
        z = x
        t0 = time.perf_counter()
        for _ in range(N):
            z = z + y
        dispatch_us = min(dispatch_us,
                          (time.perf_counter() - t0) / N * 1e6)
        float(z.sum()[0] if z.sum().ndim else z.sum())

    # -- per-op dispatch cost with tape recording -----------------------
    xg = paddle.to_tensor(np.ones((256, 256), np.float32))
    xg.stop_gradient = False
    z = xg + y
    float(z.sum())
    tape_us = float("inf")
    for _ in range(BATCHES):
        z = xg
        t0 = time.perf_counter()
        for _ in range(N):
            z = z + y
        tape_us = min(tape_us, (time.perf_counter() - t0) / N * 1e6)
        loss = z.sum()
        loss.backward()
        float(xg.grad.sum())
        xg.clear_grad()

    # -- eager LeNet train loop (BASELINE config #1 shape) --------------
    paddle.seed(0)
    model = nn.Sequential(
        nn.Conv2D(1, 6, 5, padding=2), nn.ReLU(), nn.MaxPool2D(2, 2),
        nn.Conv2D(6, 16, 5), nn.ReLU(), nn.MaxPool2D(2, 2),
        nn.Flatten(), nn.Linear(400, 120), nn.ReLU(),
        nn.Linear(120, 84), nn.ReLU(), nn.Linear(84, 10))
    sgd = opt.SGD(learning_rate=0.01, parameters=model.parameters())
    rng = np.random.RandomState(0)
    bs = 64
    xb = paddle.to_tensor(rng.randn(bs, 1, 28, 28).astype(np.float32))
    yb = paddle.to_tensor(rng.randint(0, 10, (bs,)))

    def one_step():
        loss = F.cross_entropy(model(xb), yb)
        loss.backward()
        sgd.step()
        sgd.clear_grad()
        return loss

    for _ in range(3):
        loss = one_step()
    float(loss)
    iters = 30 if on_tpu else 10
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = one_step()
    float(loss)
    steps_per_s = iters / (time.perf_counter() - t0)

    where = "tpu" if on_tpu else "cpu"
    print(json.dumps({
        "metric": "eager_dispatch_us_per_op", "value": round(dispatch_us, 1),
        "unit": f"us ({where}, async host cost, 256x256 add x{N})",
        "vs_baseline": 0.0}))
    print(json.dumps({
        "metric": "eager_dispatch_us_per_op_taped", "value": round(tape_us, 1),
        "unit": f"us ({where}, with autograd tape)", "vs_baseline": 0.0}))
    print(json.dumps({
        "metric": "eager_lenet_train_steps_per_sec",
        "value": round(steps_per_s, 2),
        "unit": f"steps/s ({where}, bs{bs}, full dygraph fwd+bwd+SGD)",
        "vs_baseline": 0.0}))


if __name__ == "__main__":
    main()
