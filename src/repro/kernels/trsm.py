"""Pallas TPU kernel: triangular solve X @ L^T = C (TRSM, right/lower-T).

One grid cell per C-row-panel: L (tb x tb) is broadcast to every cell, the
C panel streams through VMEM in ``bm``-row blocks so arbitrarily tall C
panels (the paper's column block of TRSMs, Fig. 3c) stay within the VMEM
budget.  Columns are produced in place by forward substitution
(:func:`trsm_in_place`); each step is one masked matvec over the
already-solved panel (VPU), with the column read and written through iota
masks (Mosaic has no dynamic lane slice).
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_interpret


def trsm_in_place(l_ref, buf):
    """Overwrite ``buf`` (holding C, ``[m, n]``) with X, ``X L^T = C``,
    for the lower-triangular ``[n, n]`` factor in ref ``l_ref``.

    At step ``j`` columns ``< j`` of ``buf`` hold X and columns ``>= j``
    still hold C; only ``L[j, :j+1]`` is read, so the strict upper part
    of ``l_ref`` may hold anything.
    """
    n = l_ref.shape[0]
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)

    def col(j, _):
        t = buf[...]
        lj = l_ref[pl.ds(j, 1), :].astype(t.dtype)                 # L[j, :]
        ljj = jnp.sum(jnp.where(cols == j, lj, 0.0), axis=1, keepdims=True)
        lrow = jnp.where(cols < j, lj, 0.0)
        # X[:, j] = (C[:, j] - X[:, :j] @ L[j, :j]^T) / L[j, j]
        v = jnp.sum(jnp.where(cols == j, t, 0.0) - t * lrow, axis=1,
                    keepdims=True) / ljj
        buf[...] = jnp.where(cols == j, v, t)

    # int32 bounds: under x64 a Python-int index would be int64, which
    # Mosaic cannot lower
    jax.lax.fori_loop(jnp.int32(0), jnp.int32(n), col, None)


def _trsm_kernel(l_ref, c_ref, o_ref, lbuf, buf):
    lbuf[...] = l_ref[...].astype(jnp.float32)
    buf[...] = c_ref[...].astype(jnp.float32)
    trsm_in_place(lbuf, buf)
    o_ref[...] = buf[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def trsm(l: jax.Array, c: jax.Array, bm: int | None = None,
         interpret: bool | None = None) -> jax.Array:
    """Solve X L^T = C.  l: [n, n] lower-triangular; c: [m, n]."""
    m, n = c.shape
    bm = bm or m
    assert m % bm == 0, (m, bm)
    z = np.int32(0)     # int32 block indices, also under x64 (Mosaic has no i64)
    return pl.pallas_call(
        _trsm_kernel,
        grid=(m // bm,),
        out_shape=jax.ShapeDtypeStruct((m, n), c.dtype),
        in_specs=[
            pl.BlockSpec((n, n), lambda i: (z, z)),      # L broadcast
            pl.BlockSpec((bm, n), lambda i: (i, z)),     # C row panel
        ],
        out_specs=pl.BlockSpec((bm, n), lambda i: (i, z)),
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32),
                        pltpu.VMEM((bm, n), jnp.float32)],
        interpret=pallas_interpret(interpret),
    )(l, c)
