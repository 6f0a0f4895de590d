"""Launcher payload: every eager collective primitive exercised with
DIVERGENT per-rank values, results checked against numpy on both ranks
(review r2 item 1 — reference semantics:
python/paddle/distributed/collective.py:174, ProcessGroup.h:52)."""
import os
import re
import sys

os.environ["XLA_FLAGS"] = re.sub(
    r"--xla_force_host_platform_device_count=\d+", "",
    os.environ.get("XLA_FLAGS", "")).strip()
os.environ["PADDLE_TPU_FORCE_CPU_DEVICES"] = "1"

import numpy as np  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
import paddle_tpu.distributed as dist  # noqa: E402

out_path = sys.argv[1]

env = dist.init_parallel_env()
r, n = env.rank, env.world_size
assert n == 2

# divergent per-rank data: rank r holds r+1, r+2, ...
base = np.arange(4, dtype="float32") + (r + 1)
per_rank = [np.arange(4, dtype="float32") + (j + 1) for j in range(n)]

# all_reduce SUM / MAX / PROD
t = paddle.to_tensor(base.copy())
dist.all_reduce(t)
np.testing.assert_allclose(t.numpy(), sum(per_rank))
t = paddle.to_tensor(base.copy())
dist.all_reduce(t, op=dist.ReduceOp.MAX)
np.testing.assert_allclose(t.numpy(), np.maximum(*per_rank))
t = paddle.to_tensor(base.copy())
dist.all_reduce(t, op=dist.ReduceOp.PROD)
np.testing.assert_allclose(t.numpy(), per_rank[0] * per_rank[1])

# all_gather
out = []
dist.all_gather(out, paddle.to_tensor(base.copy()))
assert len(out) == n
for j in range(n):
    np.testing.assert_allclose(out[j].numpy(), per_rank[j])

# broadcast from rank 1
t = paddle.to_tensor(base.copy())
dist.broadcast(t, src=1)
np.testing.assert_allclose(t.numpy(), per_rank[1])

# reduce to dst=1: only rank 1 must hold the sum
t = paddle.to_tensor(base.copy())
dist.reduce(t, dst=1)
np.testing.assert_allclose(t.numpy(),
                           sum(per_rank) if r == 1 else per_rank[r])

# scatter from rank 0: rank j receives src's list[j]
src_parts = [paddle.to_tensor(np.full(3, 10.0 + j, "float32"))
             for j in range(n)]
t = paddle.to_tensor(np.zeros(3, "float32"))
dist.scatter(t, src_parts if r == 0 else None, src=0)
np.testing.assert_allclose(t.numpy(), np.full(3, 10.0 + r))

# alltoall: out[j] = rank j's in[r]
ins = [paddle.to_tensor(np.full(2, 100.0 * r + j, "float32"))
       for j in range(n)]
outs = dist.alltoall(ins)
for j in range(n):
    np.testing.assert_allclose(outs[j].numpy(), np.full(2, 100.0 * j + r))

# reduce_scatter: result = sum_j rank j's chunk r
parts = [paddle.to_tensor(np.full(2, float(r + 1) * (j + 1), "float32"))
         for j in range(n)]
t = paddle.to_tensor(np.zeros(2, "float32"))
dist.reduce_scatter(t, parts)
expect = sum((j + 1) * (r + 1) for j in range(n))
np.testing.assert_allclose(t.numpy(), np.full(2, float(expect)))

# alltoall_single
flat = paddle.to_tensor(
    (np.arange(4, dtype="float32") + 10 * r).reshape(4, 1))
got = dist.alltoall_single(flat)
expect = np.concatenate([(np.arange(4).reshape(4, 1)[2 * r:2 * r + 2]
                          + 10 * j) for j in range(n)]).astype("float32")
np.testing.assert_allclose(got.numpy(), expect)

# send/recv p2p: 0 -> 1 then 1 -> 0 (different payloads)
if r == 0:
    dist.send(paddle.to_tensor(np.full(3, 7.0, "float32")), dst=1)
    t = paddle.to_tensor(np.zeros(3, "float32"))
    dist.recv(t, src=1)
    np.testing.assert_allclose(t.numpy(), np.full(3, 9.0))
else:
    t = paddle.to_tensor(np.zeros(3, "float32"))
    dist.recv(t, src=0)
    np.testing.assert_allclose(t.numpy(), np.full(3, 7.0))
    dist.send(paddle.to_tensor(np.full(3, 9.0, "float32")), dst=0)

# subgroup with non-trivial global->group rank mapping: ranks=[1,0]
g2 = dist.new_group(ranks=[1, 0])
assert g2.rank == (1 if r == 0 else 0)
t = paddle.to_tensor(base.copy())
dist.broadcast(t, src=1, group=g2)  # src is a GLOBAL rank
np.testing.assert_allclose(t.numpy(), per_rank[1])
t = paddle.to_tensor(base.copy())
dist.all_reduce(t, group=g2)
np.testing.assert_allclose(t.numpy(), sum(per_rank))

# non-member no-op: rank 0 is outside ranks=[1]
g3 = dist.new_group(ranks=[1])
t = paddle.to_tensor(base.copy())
dist.all_reduce(t, group=g3)
np.testing.assert_allclose(t.numpy(), per_rank[r])  # unchanged either way

# objects + barrier + true group rank
objs = []
dist.all_gather_object(objs, {"rank": r, "tag": "x" * (r + 1)})
assert [o["rank"] for o in objs] == list(range(n))
olist = [None]
if r == 0:
    olist = [{"cfg": 42}]
dist.broadcast_object_list(olist, src=0)
assert olist[0] == {"cfg": 42}
g = dist.get_group(0)
assert g.rank == r and g.nranks == n
dist.barrier()

if r == 0:
    np.savez(out_path, ok=np.array(1))
print(f"rank {r}: all eager collectives verified", flush=True)
