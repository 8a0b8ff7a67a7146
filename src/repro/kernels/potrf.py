"""Pallas TPU kernel: single-tile Cholesky factorization (POTRF).

The whole tile lives in VMEM (one grid cell — a 512x512 f32 tile is
1 MiB, far under the VMEM budget).  The kernel runs the column-recursive
algorithm in place (:func:`chol_in_place`): column ``j`` is formed with
one masked matvec against the already-factored panel, which the Mosaic
compiler maps to VPU lanes; the O(n^2) matvec per column is dominated by
the O(n^3) SYRK/GEMM traffic that surrounds POTRF in the factorization
(surface-to-volume, paper §I), so MXU-blocking the interior of POTRF is
deliberately not done.

Mosaic has no dynamic lane slice, so column ``j`` is never sliced out or
scattered by index: it is read with an iota mask and a lane reduction,
and written back with a masked select over the tile.

dtypes: f32/bf16 storage, f32 compute.  (f64 tiles take the stock XLA path
— the TPU has no native f64 MXU; see :mod:`repro.kernels.ops`.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_interpret


def chol_in_place(buf):
    """Overwrite the symmetric tile in ref ``buf`` with its lower
    Cholesky factor (zeros above the diagonal).

    At step ``j`` columns ``< j`` of ``buf`` hold the factor and columns
    ``>= j`` still hold the input, so row ``j`` of the factor is row ``j``
    of ``buf`` masked to the columns ``< j``.
    """
    n = buf.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)

    def col(j, _):
        t = buf[...]
        lrow = jnp.where(cols < j, buf[pl.ds(j, 1), :], 0.0)     # L[j, :j]
        # v = A[:, j] - L[:, :j] @ L[j, :j]^T
        v = jnp.sum(jnp.where(cols == j, t, 0.0) - t * lrow, axis=1,
                    keepdims=True)
        d = jnp.sum(jnp.where(rows == j, v, 0.0), axis=0, keepdims=True)
        colv = jnp.where(rows >= j, v / jnp.sqrt(d), 0.0)
        buf[...] = jnp.where(cols == j, colv, t)

    # int32 bounds: under x64 a Python-int index would be int64, which
    # Mosaic cannot lower
    jax.lax.fori_loop(jnp.int32(0), jnp.int32(n), col, None)


def _potrf_kernel(a_ref, o_ref, buf):
    a = a_ref[...].astype(jnp.float32)
    buf[...] = 0.5 * (a + a.T)
    chol_in_place(buf)
    o_ref[...] = buf[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def potrf(a: jax.Array, interpret: bool | None = None) -> jax.Array:
    n = a.shape[0]
    return pl.pallas_call(
        _potrf_kernel,
        out_shape=jax.ShapeDtypeStruct((n, n), a.dtype),
        in_specs=[pl.BlockSpec((n, n), lambda: (0, 0))],
        out_specs=pl.BlockSpec((n, n), lambda: (0, 0)),
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        interpret=pallas_interpret(interpret),
    )(a)
