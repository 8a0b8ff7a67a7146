"""Plain references for the controls: a blocked right-looking Cholesky
and a dense substitution, independent of the program under test.

``cholesky(a, tb, dot)`` factors in f32 on the default device with every
trailing update through ``dot``.  ``dot_high`` is the three-pass
bfloat16 product (``Precision.HIGH`` on a TPU: a = hi + lo in bfloat16,
hi*hi + hi*lo + lo*hi summed in f32), written out so that it computes
the same on any backend.  The diagonal blocks and panels go through
JAX's own ``cholesky`` and ``solve_triangular``.
"""
from __future__ import annotations

import numpy as np


def _split(x):
    import jax.numpy as jnp
    hi = x.astype(jnp.bfloat16)
    lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def dot_high(x, y):
    """``x @ y`` in three bfloat16 passes with f32 accumulation."""
    import jax.numpy as jnp
    xh, xl = _split(x)
    yh, yl = _split(y)

    def mm(p, q):
        return jnp.matmul(p, q, preferred_element_type=jnp.float32)

    return mm(xh, yh) + (mm(xh, yl) + mm(xl, yh))


def cholesky(a: np.ndarray, tb: int, dot) -> np.ndarray:
    """Lower Cholesky factor of ``a`` in f32, returned to the host as f64."""
    import jax
    import jax.numpy as jnp
    n = a.shape[0]

    @jax.jit
    def factor(m):
        for k in range(0, n, tb):
            e = k + tb
            lkk = jnp.linalg.cholesky(m[k:e, k:e])
            m = m.at[k:e, k:e].set(lkk)
            if e == n:
                break
            panel = jax.scipy.linalg.solve_triangular(
                lkk, m[e:, k:e].T, lower=True).T
            m = m.at[e:, k:e].set(panel)
            m = m.at[e:, e:].add(-dot(panel, panel.T))
        return jnp.tril(m)

    dev = jax.device_put(np.asarray(a, dtype=np.float32))
    return np.asarray(factor(dev), dtype=np.float64)


def cho_solve(l: np.ndarray, b: np.ndarray) -> np.ndarray:
    """``x`` with ``l l^T x = b`` by two dense f64 triangular solves."""
    import scipy.linalg as sla
    z = sla.solve_triangular(l, b, lower=True)
    return sla.solve_triangular(l, z, lower=True, trans="T")


class Solver:
    """The reference in the program's place: ``factor``, ``logdet`` and
    ``solve`` as ``OOCSolver`` has them, on a dense factor made by
    ``cholesky(a, tb, dot)``."""

    def __init__(self, tb: int, dot):
        self.tb, self.dot, self.l = tb, dot, None

    def factor(self, a: np.ndarray, materialize: bool = True):
        self.l = cholesky(a, self.tb, self.dot)
        return self.l if materialize else None

    def logdet(self) -> float:
        d = np.diagonal(self.l)
        if not (np.isfinite(d).all() and (d > 0).all()):
            raise ValueError("the factor's diagonal is not positive")
        return float(2.0 * np.log(d).sum())

    def solve(self, b: np.ndarray) -> np.ndarray:
        return cho_solve(self.l, b)
