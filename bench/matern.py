"""Matérn test matrices, a frozen copy of ``repro.geo.matern``.

The yardstick keeps its own copy so that a change to the program cannot
change the benchmark's input.  ``bench/tests/test_matern.py`` checks that
the two agree value for value.  This copy builds row blocks on a thread
pool: NumPy releases the interpreter lock inside each array operation, so
the blocks run in parallel and every entry is computed by the same
operations as in the original.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

# entries per row block: 2 MiB of f64, so a block's temporaries stay in
# a core's cache
_BLOCK_ELEMS = 1 << 18


def _morton_key(pts: np.ndarray, bits: int = 16) -> np.ndarray:
    """Z-order key per point: ExaGeoStat orders locations this way, so
    covariance tiles are spatial blocks whose norms decay off the
    diagonal."""
    q = np.clip((pts * (2**bits - 1)).astype(np.uint64), 0, 2**bits - 1)

    def spread(x):
        x = x.astype(np.uint64)
        x = (x | (x << np.uint64(16))) & np.uint64(0x0000FFFF0000FFFF)
        x = (x | (x << np.uint64(8))) & np.uint64(0x00FF00FF00FF00FF)
        x = (x | (x << np.uint64(4))) & np.uint64(0x0F0F0F0F0F0F0F0F)
        x = (x | (x << np.uint64(2))) & np.uint64(0x3333333333333333)
        x = (x | (x << np.uint64(1))) & np.uint64(0x5555555555555555)
        return x

    return spread(q[:, 0]) | (spread(q[:, 1]) << np.uint64(1))


def generate_locations(n: int, seed: int) -> np.ndarray:
    """Jittered grid on the unit square, ``n`` points drawn from it by the
    seed, in Morton order."""
    rng = np.random.default_rng(seed)
    side = int(np.ceil(np.sqrt(n)))
    xs, ys = np.meshgrid(np.arange(side), np.arange(side))
    pts = np.stack([xs.ravel(), ys.ravel()], axis=1).astype(np.float64)
    pts += rng.uniform(-0.4, 0.4, size=pts.shape)
    pts = (pts - pts.min(0)) / (pts.max(0) - pts.min(0))
    idx = rng.permutation(pts.shape[0])[:n]
    pts = pts[idx]
    order = np.argsort(_morton_key(pts))
    return pts[order]


def _kernel(h: np.ndarray, nu: float) -> np.ndarray:
    """Unit-variance Matérn correlation at scaled distances ``h``."""
    if nu == 0.5:
        return np.exp(-h)
    if nu == 1.5:
        s = np.sqrt(3.0) * h
        return (1.0 + s) * np.exp(-s)
    if nu == 2.5:
        s = np.sqrt(5.0) * h
        return (1.0 + s + s * s / 3.0) * np.exp(-s)
    raise ValueError(f"nu={nu}: only the closed forms 0.5, 1.5, 2.5")


def matern_covariance(locs: np.ndarray, sigma2: float, beta: float,
                      nu: float, nugget: float,
                      threads: int | None = None) -> np.ndarray:
    """Dense f64 Matérn covariance plus ``nugget * sigma2`` on the
    diagonal, built in row blocks on ``threads`` threads."""
    n = locs.shape[0]
    cov = np.empty((n, n), dtype=np.float64)
    rows = max(1, _BLOCK_ELEMS // max(n, 1))

    x, y = locs[:, 0], locs[:, 1]

    def block(r0):
        # dx*dx + dy*dy is what ((p - q) ** 2).sum(-1) computes for 2-D
        # points, term for term, without the (rows, n, 2) temporary
        dx = x[r0:r0 + rows, None] - x[None, :]
        dy = y[r0:r0 + rows, None] - y[None, :]
        dx *= dx
        dy *= dy
        dx += dy
        np.sqrt(dx, out=dx)
        dx /= beta
        cov[r0:r0 + rows] = sigma2 * _kernel(dx, nu)

    workers = threads or min(16, os.cpu_count() or 1)
    with ThreadPoolExecutor(workers) as pool:
        # list() reads every future, so a failed block raises here
        list(pool.map(block, range(0, n, rows)))
    cov[np.diag_indices_from(cov)] += nugget * sigma2
    return cov
