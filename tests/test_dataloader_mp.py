"""Multiprocess DataLoader workers (shared-memory ndarray passing).

Reference: python/paddle/fluid/reader.py:312 +
fluid/dataloader/worker.py — worker subprocesses feeding batches through
shared memory so GIL-bound Python decode/augment pipelines scale.
"""
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.io import DataLoader, Dataset, get_worker_info


class ArrayDataset(Dataset):
    def __init__(self, n=64, shape=(3, 32, 32)):
        self.x = np.arange(n * int(np.prod(shape)),
                           dtype=np.float32).reshape((n,) + shape)
        self.y = np.arange(n, dtype=np.int64)

    def __len__(self):
        return len(self.y)

    def __getitem__(self, i):
        return self.x[i], self.y[i]


class HeavyTransformDataset(Dataset):
    """Pure-Python (GIL-bound) per-sample work — the ImageFolder decode/
    augment profile the reference's shm workers exist for."""

    def __init__(self, n=48, work=150_000):
        self.n = n
        self.work = work

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        acc = 0
        for j in range(self.work):  # deliberately holds the GIL
            acc += (i + j) % 7
        # the sample, its index, and the process it was made in
        return (np.full((64,), float(acc % 97), np.float32), i,
                os.getpid())


class WorkerIdDataset(Dataset):
    def __len__(self):
        return 16

    def __getitem__(self, i):
        info = get_worker_info()
        wid = -1 if info is None else info.id
        return np.asarray([i, wid], np.int64)


class TestMultiprocessCorrectness:
    def test_batches_match_serial(self):
        ds = ArrayDataset(40)
        serial = [(x.numpy(), y.numpy()) for x, y in
                  DataLoader(ds, batch_size=8, num_workers=0)]
        mp = [(x.numpy(), y.numpy()) for x, y in
              DataLoader(ds, batch_size=8, num_workers=3)]
        assert len(serial) == len(mp) == 5
        for (xs, ys), (xm, ym) in zip(serial, mp):
            np.testing.assert_array_equal(xs, xm)
            np.testing.assert_array_equal(ys, ym)

    def test_shuffle_drop_last_and_reuse(self):
        ds = ArrayDataset(37)
        dl = DataLoader(ds, batch_size=8, num_workers=2, shuffle=True,
                        drop_last=True)
        for _ in range(2):  # loader is re-iterable
            seen = []
            for x, y in dl:
                assert x.shape == [8, 3, 32, 32]
                seen.extend(y.numpy().tolist())
            assert len(seen) == 32 and len(set(seen)) == 32

    def test_worker_exception_propagates(self):
        class Boom(Dataset):
            def __len__(self):
                return 8

            def __getitem__(self, i):
                if i == 5:
                    raise ValueError("bad sample 5")
                return np.zeros(4, np.float32)

        dl = DataLoader(Boom(), batch_size=4, num_workers=2)
        with pytest.raises(RuntimeError, match="bad sample 5"):
            list(dl)

    def test_worker_info_in_subprocess(self):
        dl = DataLoader(WorkerIdDataset(), batch_size=4, num_workers=2)
        wids = set()
        for b in dl:
            arr = b.numpy()
            wids.update(arr[:, 1].tolist())
        assert wids <= {0, 1} and len(wids) >= 1
        assert -1 not in wids  # info WAS set in the worker

    def test_user_collate_runs_in_parent(self):
        ds = ArrayDataset(16)
        marker = []

        def collate(samples):
            marker.append(len(samples))  # parent-side mutation visible
            xs = np.stack([s[0] for s in samples])
            return paddle.to_tensor(xs.sum(axis=(1, 2, 3)))

        out = list(DataLoader(ds, batch_size=4, num_workers=2,
                              collate_fn=collate))
        assert marker == [4, 4, 4, 4]  # ran in THIS process
        assert out[0].shape == [4]

    def test_thread_fallback_flag(self):
        ds = ArrayDataset(16)
        out = list(DataLoader(ds, batch_size=4, num_workers=2,
                              use_shared_memory=False))
        assert len(out) == 4


class TestMultiprocessThroughput:
    def test_gil_bound_pipeline_faster_than_threads(self):
        """What lets a Python-transform pipeline scale past the GIL is
        WHERE `__getitem__` runs: with shared memory on, in worker
        processes (several of them, none the parent); with it off, on
        the thread pool inside the parent. Both yield the same batches.
        (The behaviour, not a time: two CPU timings of half a second
        proved nothing and failed at random, ROADMAP D11.)"""
        ds = HeavyTransformDataset()

        def run(use_shm):
            dl = DataLoader(ds, batch_size=4, num_workers=4,
                            use_shared_memory=use_shm)
            batches = [(x.numpy(), i.numpy(), pid.numpy())
                       for x, i, pid in dl]
            pids = {int(p) for _, _, pid in batches for p in pid}
            return [(x, i) for x, i, _ in batches], pids

        proc, proc_pids = run(True)
        thread, thread_pids = run(False)
        assert len(proc) == len(thread) == 12
        for (x1, i1), (x2, i2) in zip(proc, thread):
            np.testing.assert_array_equal(i1, i2)
            np.testing.assert_array_equal(x1, x2)
        assert len(proc_pids) >= 2 and os.getpid() not in proc_pids
        assert thread_pids == {os.getpid()}


class TestMultiprocessRobustness:
    def test_dead_worker_raises_not_hangs(self):
        class Killer(Dataset):
            def __len__(self):
                return 8

            def __getitem__(self, i):
                if i == 5:
                    os._exit(13)  # simulate OOM-kill / native crash
                return np.zeros(4, np.float32)

        dl = DataLoader(Killer(), batch_size=4, num_workers=2)
        with pytest.raises(RuntimeError, match="exited abnormally"):
            list(dl)

    def test_tensor_dataset_routes_to_threads(self):
        """Samples holding jax-backed Tensors must not cross fork (the
        inherited PJRT client is not fork-safe)."""
        from paddle_tpu.io import TensorDataset
        xs = paddle.to_tensor(np.arange(32, dtype=np.float32)
                              .reshape(8, 4))
        ys = paddle.to_tensor(np.arange(8, dtype=np.int64))
        dl = DataLoader(TensorDataset([xs, ys]), batch_size=4,
                        num_workers=2)
        out = [(x.numpy(), y.numpy()) for x, y in dl]
        assert len(out) == 2
        np.testing.assert_array_equal(out[0][1], [0, 1, 2, 3])
