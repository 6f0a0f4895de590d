"""Multi-process launcher: `python -m paddle_tpu.distributed.launch`.

TPU-native replacement for paddle.distributed.launch (reference:
python/paddle/distributed/launch/main.py:18, controllers/controller.py:66
Controller.run building Job/Pod/Containers, controllers/collective.py:32
per-rank env injection, rendezvous via the master KV at
controllers/master.py and TCPStore paddle/fluid/distributed/store/
tcp_store.h:117).

TPU model: one process PER HOST (not per device) — inside a process,
GSPMD drives all local devices; across processes, JAX's distributed
runtime (coordinator service at PADDLE_MASTER) plays the TCPStore role.
The launcher spawns the local processes, injects the rank/rendezvous
env, streams logs, and tears the pod down on first failure exactly like
the reference's watcher loop.
"""
from __future__ import annotations

import os
import signal
import socket
import subprocess
import sys
import time

__all__ = ["launch", "spawn", "find_free_port"]


def find_free_port():
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _refuse_if_holding_chip(what):
    """A chip belongs to one process: a parent that has touched JAX on
    an accelerator holds it, and a child that needs it then fails or
    hangs. Start workers from a process that has not run anything on
    the device yet (importing paddle_tpu does not). Reads jax's backend
    registry (`jax._src.xla_bridge`, checked against jax 0.9.0): there
    is no public way to ask without initializing the backend."""
    import jax
    from jax._src import xla_bridge
    if xla_bridge.backends_are_initialized() and \
            jax.default_backend() != "cpu":
        raise RuntimeError(
            f"{what}: this process already holds the "
            f"{jax.default_backend()} device(s); worker processes could "
            f"not open them. Launch before the first device query or "
            f"computation in the parent.")


def _rank_env(master, nnodes, nproc_per_node, node_rank, local_rank,
              extra=None):
    """Only the vars the launcher injects (merged over os.environ by the
    caller)."""
    world = nnodes * nproc_per_node
    rank = node_rank * nproc_per_node + local_rank
    env = {
        "PADDLE_MASTER": master,
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_TRAINERS_NUM": str(world),
        "PADDLE_LOCAL_RANK": str(local_rank),
        "PADDLE_NNODES": str(nnodes),
        "PADDLE_NODE_RANK": str(node_rank),
        # reference-compat endpoint list (synthetic host-local ports)
        "PADDLE_TRAINER_ENDPOINTS": ",".join(
            f"127.0.0.1:{61000 + i}" for i in range(world)),
        "PADDLE_CURRENT_ENDPOINT": f"127.0.0.1:{61000 + rank}",
        # children resolve imports relative to the launch directory (the
        # script's own dir replaces it in sys.path otherwise)
        "PYTHONPATH": os.pathsep.join(
            p for p in (os.getcwd(),
                        os.environ.get("PYTHONPATH")) if p),
    }
    if extra:
        env.update(extra)
    return env


def launch(script, script_args=(), nproc_per_node=1, nnodes=1,
           node_rank=0, master=None, log_dir=None, envs=None,
           poll_interval=0.5):
    """Spawn `nproc_per_node` local worker processes running `script`
    and watch them; on any failure terminate the pod (reference:
    controller.py:66 run/watch). Returns the first nonzero exit code, or
    0."""
    _refuse_if_holding_chip("launch")
    if master is None:
        if nnodes > 1:
            # each node inventing its own local coordinator can never
            # rendezvous — fail fast instead of hanging every worker
            raise ValueError(
                "--master host:port is required when nnodes > 1")
        master = f"127.0.0.1:{find_free_port()}"
    procs = []
    logs = []
    if log_dir:
        os.makedirs(log_dir, exist_ok=True)
    for lr in range(nproc_per_node):
        env = dict(os.environ)
        env.update(_rank_env(master, nnodes, nproc_per_node, node_rank,
                             lr, envs))
        cmd = [sys.executable, script, *script_args]
        if log_dir and lr > 0:
            f = open(os.path.join(log_dir, f"workerlog.{lr}"), "w")
            logs.append(f)
            out = f
        else:
            out = None  # rank 0 (or no log_dir): inherit stdio
        procs.append(subprocess.Popen(cmd, env=env, stdout=out,
                                      stderr=subprocess.STDOUT
                                      if out else None))
    rc = 0
    try:
        while procs:
            alive = []
            for p in procs:
                r = p.poll()
                if r is None:
                    alive.append(p)
                elif r != 0 and rc == 0:
                    rc = r
            procs = alive
            if rc != 0:
                break
            time.sleep(poll_interval)
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
        deadline = time.time() + 10
        for p in procs:
            try:
                p.wait(timeout=max(deadline - time.time(), 0.1))
            except subprocess.TimeoutExpired:
                p.kill()
        for f in logs:
            f.close()
    return rc


def _spawn_target(fn, args):
    # rendezvous env was injected by the parent before start() (it must
    # be visible when the child imports paddle_tpu to unpickle this)
    fn(*args)


def spawn(func, args=(), nprocs=-1, join=True, daemon=False,
          **options):
    """paddle.distributed.spawn parity (reference: distributed/spawn.py):
    run `func(*args)` in `nprocs` freshly-spawned processes with the
    rendezvous env set. nprocs=-1 -> one per local device group (1 on a
    single host)."""
    import multiprocessing as mp
    _refuse_if_holding_chip("spawn")
    if nprocs <= 0:
        nprocs = int(os.getenv("PADDLE_NPROCS", "1"))
    master = f"127.0.0.1:{find_free_port()}"
    ctx = mp.get_context("spawn")
    procs = []
    for r in range(nprocs):
        p = ctx.Process(target=_spawn_target, args=(func, args),
                        daemon=daemon)
        # the child inherits os.environ at start(); the rendezvous vars
        # must be visible BEFORE its paddle_tpu import (package-import
        # bootstrap), not just when the target runs
        child_env = _rank_env(master, 1, nprocs, 0, r,
                              options.get("envs"))
        saved = {k: os.environ.get(k) for k in child_env}
        os.environ.update(child_env)
        try:
            p.start()
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        procs.append(p)
    if not join:
        return procs
    rc = 0
    for p in procs:
        p.join()
        if p.exitcode and rc == 0:
            rc = p.exitcode
    if rc:
        raise RuntimeError(f"spawned process failed with exit code {rc}")
    return procs
