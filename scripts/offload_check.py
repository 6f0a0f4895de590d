"""ZeRO offload verification on the real chip.

Trains the same model twice — optimizer states in device HBM vs
offloaded to pinned host memory (group_sharded_parallel(offload=True)) —
and reports per-step device-memory occupancy. The reference analogue:
group_sharded_stage3.py:61 offload=True (states on CPU).
Prints one JSON line with both numbers and the drop.
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run(offload):
    import jax
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as opt
    import paddle_tpu.distributed as dist
    from paddle_tpu.device import cuda as dmem

    paddle.seed(0)
    model = nn.Sequential(
        nn.Linear(1024, 4096), nn.GELU(),
        nn.Linear(4096, 4096), nn.GELU(),
        nn.Linear(4096, 1024))
    adam = opt.Adam(learning_rate=1e-3, parameters=model.parameters())
    model, adam = dist.group_sharded_parallel(model, adam, "os",
                                              offload=offload)
    x = paddle.to_tensor(
        np.random.RandomState(0).randn(32, 1024).astype("float32"))
    for _ in range(3):
        loss = (model(x) ** 2).mean()
        loss.backward()
        adam.step()
        adam.clear_grad()
    float(loss)  # sync
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    import jax
    # None on a backend without allocator stats (the CPU); a failing
    # call raises. The optimizer-state buffers' actual placement is
    # measured either way.
    stats = jax.devices()[0].memory_stats()
    dev_bytes = host_bytes = 0
    host_states = 0
    for s in adam._accumulators.values():
        for v in s.values():
            kind = getattr(getattr(v, "sharding", None), "memory_kind",
                           "device")
            if kind == "pinned_host":
                host_bytes += v.nbytes
                host_states += 1
            else:
                dev_bytes += v.nbytes
    used = (stats or {}).get("bytes_in_use", dev_bytes)
    return used, n_params, host_states, float(loss)


def main():
    if len(sys.argv) > 1:  # child: one clean-process measurement
        used, n_params, host_states, loss = run(sys.argv[1] == "offload")
        print(json.dumps({"used": used, "params": n_params,
                          "host_states": host_states, "loss": loss}))
        return
    import subprocess
    out = {}
    for mode in ("offload", "resident"):
        r = subprocess.run([sys.executable, os.path.abspath(__file__),
                            mode], capture_output=True, text=True)
        line = [ln for ln in r.stdout.splitlines()
                if ln.startswith("{")][-1]
        out[mode] = json.loads(line)
    print(json.dumps({
        "metric": "zero_offload_device_bytes",
        "device_bytes_offload": out["offload"]["used"],
        "device_bytes_resident": out["resident"]["used"],
        "drop_bytes": out["resident"]["used"] - out["offload"]["used"],
        "params": out["offload"]["params"],
        "host_placed_state_tensors": out["offload"]["host_states"],
        "loss_offload": out["offload"]["loss"],
        "loss_resident": out["resident"]["loss"],
    }))


if __name__ == "__main__":
    main()
