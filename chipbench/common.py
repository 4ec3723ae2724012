"""What every driver shares: host spans, the compile counter, the bitwise
checksum of device state, and per-leaf norms."""
from __future__ import annotations

import contextlib
import time

import jax
import jax.numpy as jnp

_COMPILE = "/jax/core/compile/backend_compile_duration"
_HIT = "/jax/compilation_cache/cache_hits"


class Spans:
    """Host spans (name, start, end) on ``time.perf_counter``. Each is also a
    ``TraceAnnotation``, so a profiler trace shows what the host was doing."""

    def __init__(self):
        self.items = []

    @contextlib.contextmanager
    def __call__(self, name):
        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.items.append((name, t0, time.perf_counter()))

    def durations(self, name, t0=float("-inf"), t1=float("inf")):
        return [e - s for n, s, e in self.items
                if n == name and s >= t0 and e <= t1]


class CompileCounter:
    """Counts backend compiles and persistent-cache hits from JAX's
    monitoring events; a compile that is not a hit was a real compile."""

    def __init__(self):
        self.compiles = 0
        self.hits = 0
        self.names = []           # the function of each backend compile
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, fun_name=None, **_):
        if event == _COMPILE:
            self.compiles += 1
            self.names.append(fun_name)

    def _event(self, event, **_):
        if event == _HIT:
            self.hits += 1

    def snapshot(self):
        return self.compiles, self.hits, len(self.names)

    def since(self, snap):
        return {"backend_compiles": self.compiles - snap[0],
                "cache_hits": self.hits - snap[1],
                "compiles": (self.compiles - snap[0]) - (self.hits - snap[1]),
                "functions": self.names[snap[2]:]}


_UINT = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}


def _leaf_sum(a):
    bits = jax.lax.bitcast_convert_type(a, _UINT[a.dtype.itemsize])
    bits = bits.reshape(-1).astype(jnp.uint32)
    idx = jax.lax.iota(jnp.uint32, bits.shape[0])
    return jnp.sum(bits * (idx * jnp.uint32(2654435761) + jnp.uint32(1)),
                   dtype=jnp.uint32)


@jax.jit
def checksum(tree):
    """A position-weighted sum of every leaf's bits: one uint32 per leaf.
    Equal state gives equal sums; a changed bit changes its leaf's sum."""
    return jnp.stack([_leaf_sum(a) for a in jax.tree.leaves(tree)])


def leaf_norms(named: dict, stacked) -> dict:
    """Traceable: the L2 norm of each named weight, per layer for the names
    in ``stacked`` ("name[3]"), whose leading axis is the layer. ``named``
    maps names to arrays."""
    out = {}
    for k, a in named.items():
        a = a.astype(jnp.float32)
        if k in stacked:
            n = jnp.sqrt(jnp.sum(jnp.square(a), axis=tuple(range(1, a.ndim))))
            out.update({f"{k}[{i}]": n[i] for i in range(a.shape[0])})
        else:
            out[k] = jnp.sqrt(jnp.sum(jnp.square(a)))
    return out
