"""Stateful RNG facade over JAX's stateless threefry keys.

TPU-native replacement for phi::Generator (reference:
paddle/phi/core/generator.h:23, paddle/fluid/framework/generator.h:40).
Paddle keeps a mutable Philox state per device; here a Generator holds a
threefry key and splits off a fresh subkey per draw, which keeps every op
pure (a requirement for jit/pjit tracing) while preserving the
`paddle.seed(...)` API. TP/parallel RNG (RNGStatesTracker,
fleet/layers/mpu/random.py:34) is layered on top via named generator states.
"""
from __future__ import annotations

import threading

import numpy as np
import jax

__all__ = ["Generator", "default_generator", "seed", "get_rng_state",
           "set_rng_state", "next_key", "manual_seed"]


def _threefry2x32(k0, k1, x0, x1):
    """Host-side threefry-2x32 (bit-identical to jax._src.prng).

    Lets the stateful Generator mint per-step keys on the host, with no
    eager device op (and no device-to-host fetch) on the
    compiled-train-step dispatch path.
    """
    rot = (13, 15, 26, 6, 17, 29, 16, 24)
    M = 0xFFFFFFFF

    def rotl(x, r):
        return ((x << r) | (x >> (32 - r))) & M

    k0, k1, x0, x1 = int(k0), int(k1), int(x0), int(x1)
    ks = (k0, k1, k0 ^ k1 ^ 0x1BD11BDA)
    x0 = (x0 + ks[0]) & M
    x1 = (x1 + ks[1]) & M
    for r in range(5):
        for j in range(4):
            x0 = (x0 + x1) & M
            x1 = rotl(x1, rot[(0 if r % 2 == 0 else 4) + j])
            x1 = x0 ^ x1
        x0 = (x0 + ks[(r + 1) % 3]) & M
        x1 = (x1 + ks[(r + 2) % 3] + r + 1) & M
    return np.uint32(x0), np.uint32(x1)


def _host_fold_in(k0, k1, i):
    """numpy twin of jax.random.fold_in on a threefry key (key ⊕ i)."""
    return _threefry2x32(k0, k1, np.uint32(0), np.uint32(i))


class Generator:
    """A splittable RNG stream with Paddle's stateful facade."""

    def __init__(self, seed: int = 0):
        self._seed = int(seed)
        self._count = 0
        self._lock = threading.Lock()

    def manual_seed(self, seed: int):
        with self._lock:
            self._seed = int(seed)
            self._count = 0
        return self

    def initial_seed(self) -> int:
        return self._seed

    def next_key_host(self):
        """A fresh key as a host numpy uint32[2]; bit-identical to
        jax.random.fold_in(PRNGKey(seed), i) but with zero device work —
        for callers that feed the key straight into a jitted program
        (PRNGKey(s) packs to [s>>32, s&0xffffffff])."""
        with self._lock:
            i = self._count
            self._count += 1
        k0, k1 = (self._seed >> 32) & 0xFFFFFFFF, self._seed & 0xFFFFFFFF
        return np.asarray(_host_fold_in(k0, k1, i), dtype=np.uint32)

    def next_key(self):
        """A fresh threefry key on device; deterministic given
        (seed, draw index). One host->device transfer — the fold itself
        happens host-side (see next_key_host)."""
        return jax.numpy.asarray(self.next_key_host())

    def get_state(self):
        return (self._seed, self._count)

    def set_state(self, state):
        self._seed, self._count = int(state[0]), int(state[1])
        return self

    # Paddle compat
    @property
    def state(self):
        return self.get_state()


class _TraceRng(threading.local):
    """Trace-time RNG: while jit.to_static traces a program, random draws
    derive from a traced key input (fold_in per draw), so compiled programs
    get fresh randomness per call instead of baked-in constants."""

    def __init__(self):
        self.stack = []
        self.counters = []


_trace_rng = _TraceRng()


def push_trace_key(key):
    _trace_rng.stack.append(key)
    _trace_rng.counters.append(0)


def pop_trace_key():
    _trace_rng.stack.pop()
    _trace_rng.counters.pop()


def in_trace():
    return bool(_trace_rng.stack)


default_generator = Generator(0)
_named: dict[str, Generator] = {}


def get_generator(name: str | None = None) -> Generator:
    if name is None:
        return default_generator
    if name not in _named:
        _named[name] = Generator(hash(name) & 0x7FFFFFFF)
    return _named[name]


def seed(s: int):
    """paddle.seed parity (python/paddle/framework/random.py)."""
    default_generator.manual_seed(s)
    for g in _named.values():
        g.manual_seed(s)
    return default_generator


manual_seed = seed


def next_key():
    if _trace_rng.stack:
        i = _trace_rng.counters[-1]
        _trace_rng.counters[-1] += 1
        return jax.random.fold_in(_trace_rng.stack[-1], i)
    return default_generator.next_key()


def next_key_host():
    """Host-side key mint for compiled-step callers (no device op)."""
    if _trace_rng.stack:
        i = _trace_rng.counters[-1]
        _trace_rng.counters[-1] += 1
        return jax.random.fold_in(_trace_rng.stack[-1], i)
    return default_generator.next_key_host()


def get_rng_state():
    return [default_generator.get_state()] + [g.get_state() for g in _named.values()]


def set_rng_state(states):
    gens = [default_generator] + list(_named.values())
    for g, s in zip(gens, states):
        g.set_state(s)
