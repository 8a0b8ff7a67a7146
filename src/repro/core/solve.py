"""Blocked triangular substitution against the factored tile store.

After the OOC factorization the host store holds the lower Cholesky
factor tile-by-tile (``tiles[i, j]`` with ``i >= j``; strictly-upper
tiles are untouched input and never read here).  These routines turn the
factorization into an actual linear solver without ever materializing the
dense n x n factor: the right-hand side is partitioned into ``Nt`` blocks
of ``tb`` rows and streamed through the same tiles the schedule produced.

    forward:   L z = b      z_i = L_ii^-1 (b_i - sum_{j<i} L_ij z_j)
    backward:  L^T x = z    x_i = L_ii^-T (z_i - sum_{j>i} L_ji^T x_j)

``cho_solve_tiles`` chains both, matching ``scipy.linalg.cho_solve`` on
the dense factor to fp64 round-off.  The per-block GEMM/TRSM structure is
the transfer-volume-optimal access pattern for an out-of-core factor: each
tile of L is read exactly once per substitution sweep.

Multi-RHS (0.7): every routine accepts ``k`` stacked right-hand sides as
an ``(n, k)`` matrix and solves them in **one** sweep over the tile
store — the per-block update becomes a ``(tb, tb) @ (tb, k)`` GEMM, so
the factor-read traffic (the OOC bottleneck) is amortized ``k``-fold.
This is the substrate :mod:`repro.serve`'s batcher stands on: concurrent
single-RHS solves against the same factor coalesce into one stacked
call.  For very wide stacks ``rhs_block`` tiles the sweep over RHS
*column panels* of at most that many columns, bounding the live
workspace to ``n * rhs_block`` doubles while keeping the per-panel GEMM
shape; each column's arithmetic is independent, so panel width only
affects scheduling, not the mathematical result.
"""
from __future__ import annotations

from typing import Optional

import numpy as np
import scipy.linalg as sla


def _blocks(tiles: np.ndarray, b: np.ndarray):
    """Validate shapes and view b as [Nt, tb, k] blocks (k may be 1)."""
    nt, nt2, tb, tb2 = tiles.shape
    if nt != nt2 or tb != tb2:
        raise ValueError(f"malformed tile store {tiles.shape}")
    n = nt * tb
    b = np.asarray(b, dtype=np.float64)
    if b.ndim not in (1, 2):
        raise ValueError(f"rhs must be (n,) or stacked (n, k), "
                         f"got shape {b.shape}")
    squeeze = b.ndim == 1
    if squeeze:
        b = b[:, None]
    if b.shape[1] == 0:
        raise ValueError("rhs has 0 columns; nothing to solve")
    if b.shape[0] != n:
        raise ValueError(f"rhs has {b.shape[0]} rows, factor is {n}x{n}")
    return b.reshape(nt, tb, b.shape[1]), squeeze


def _panels(k: int, rhs_block: Optional[int]):
    """Column-panel slices tiling ``k`` RHS columns (one slice if unset)."""
    if rhs_block is not None and rhs_block < 1:
        raise ValueError(f"rhs_block must be >= 1, got {rhs_block}")
    step = k if rhs_block is None else min(rhs_block, k)
    return [slice(c, min(c + step, k)) for c in range(0, k, step)]


def solve_lower_tiles(tiles: np.ndarray, b: np.ndarray,
                      rhs_block: Optional[int] = None) -> np.ndarray:
    """Solve ``L z = b`` with L in the [Nt, Nt, tb, tb] tile store.

    ``b`` may be ``(n,)`` or ``(n, k)`` stacked columns; ``rhs_block``
    optionally tiles the sweep over RHS column panels of that width.
    """
    blocks, squeeze = _blocks(tiles, b)
    nt = tiles.shape[0]
    z = np.empty_like(blocks)
    for cols in _panels(blocks.shape[2], rhs_block):
        for i in range(nt):
            rhs = blocks[i, :, cols].copy()
            for j in range(i):
                rhs -= tiles[i, j] @ z[j, :, cols]
            z[i, :, cols] = sla.solve_triangular(tiles[i, i], rhs,
                                                 lower=True)
    out = z.reshape(-1, blocks.shape[2])
    return out[:, 0] if squeeze else out


def solve_lower_t_tiles(tiles: np.ndarray, b: np.ndarray,
                        rhs_block: Optional[int] = None) -> np.ndarray:
    """Solve ``L^T x = b`` with L in the [Nt, Nt, tb, tb] tile store."""
    blocks, squeeze = _blocks(tiles, b)
    nt = tiles.shape[0]
    x = np.empty_like(blocks)
    for cols in _panels(blocks.shape[2], rhs_block):
        for i in range(nt - 1, -1, -1):
            rhs = blocks[i, :, cols].copy()
            for j in range(i + 1, nt):
                rhs -= tiles[j, i].T @ x[j, :, cols]
            x[i, :, cols] = sla.solve_triangular(tiles[i, i], rhs,
                                                 lower=True, trans="T")
    out = x.reshape(-1, blocks.shape[2])
    return out[:, 0] if squeeze else out


def cho_solve_tiles(tiles: np.ndarray, b: np.ndarray,
                    rhs_block: Optional[int] = None) -> np.ndarray:
    """Solve ``A x = b`` given ``A = L L^T`` in the tile store."""
    return solve_lower_t_tiles(tiles,
                               solve_lower_tiles(tiles, b, rhs_block),
                               rhs_block)


def logdet_tiles(tiles: np.ndarray) -> float:
    """``log|A| = 2 sum_i log L_ii`` from the diagonal tiles.

    A valid Cholesky factor has strictly positive diagonal entries; a
    non-positive entry means the factorization failed upstream (loss of
    positive definiteness, e.g. under an MxP ladder too aggressive for
    the matrix) and ``log`` would silently produce NaN/-inf.  Each
    diagonal is widened to f64 before the ``log``, so a store kept in a
    narrower compute dtype gives the sum its f64 store would.
    """
    nt = tiles.shape[0]
    acc = 0.0
    for i in range(nt):
        d = np.diag(tiles[i, i]).astype(np.float64, copy=False)
        if not np.all(d > 0.0):
            bad = np.flatnonzero(~(d > 0.0))
            raise ValueError(
                f"logdet: diagonal tile ({i}, {i}) has non-positive "
                f"diagonal entries at local indices {bad.tolist()} "
                f"(min value {d.min()!r}); the factor is not a valid "
                "Cholesky factor — the factorization lost positive "
                "definiteness (e.g. precision ladder too aggressive)")
        acc += float(np.sum(np.log(d)))
    return 2.0 * acc
