"""Frozen copies of ``chip_smoke.py``'s checks: the normwise backward
error and the clock of JAX's compile events."""
from __future__ import annotations

import collections

import numpy as np

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_MISS = "/jax/compilation_cache/cache_misses"


def backward_error(a: np.ndarray, x: np.ndarray, b: np.ndarray,
                   shift: float = 0.0) -> float:
    """Largest normwise backward error over the columns,
    ``||b - A x|| / (||A|| ||x|| + ||b||)`` in the infinity norm, with
    ``A = a + shift * I``; ``a`` is read in f64 row blocks so no f64 copy
    of it is made."""
    x = x.reshape(x.shape[0], -1)
    b = b.reshape(b.shape[0], -1)
    r = np.empty_like(b)
    a_norm = 0.0
    step = 1024
    for r0 in range(0, a.shape[0], step):
        blk = np.asarray(a[r0:r0 + step], dtype=np.float64)
        rows = np.arange(blk.shape[0])
        diag = blk[rows, r0 + rows]
        r[r0:r0 + step] = (b[r0:r0 + step] - blk @ x
                           - shift * x[r0:r0 + step])
        sums = np.abs(blk).sum(axis=1) - np.abs(diag) + np.abs(diag + shift)
        a_norm = max(a_norm, float(sums.max()))
    err = np.abs(r).max(axis=0) / (a_norm * np.abs(x).max(axis=0)
                                   + np.abs(b).max(axis=0))
    return float(err.max())


class CompileClock:
    """Sums the seconds of every JAX monitoring duration event by name and
    counts the backend compiles (a persistent-cache load counts as one:
    JAX times it under the same event) and the cache misses."""

    def __init__(self):
        import jax
        self.seconds = collections.Counter()
        self.counts = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        self.seconds[event] += duration
        self.counts[event] += 1

    def _on_event(self, event, **_):
        self.counts[event] += 1

    def snapshot(self) -> tuple[dict, dict]:
        return dict(self.seconds), dict(self.counts)

    def compiles_since(self, snap: tuple[dict, dict]) -> int:
        return self.counts[BACKEND_COMPILE] - snap[1].get(BACKEND_COMPILE, 0)
