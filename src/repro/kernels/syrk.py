"""Pallas TPU kernel: symmetric rank-k update  C <- C - A @ A^T  (SYRK).

Dedicated kernel rather than GEMM-with-B=A so the grid can skip the
strictly-upper blocks: only blocks with i >= j are computed (the factor is
lower-triangular; the paper stores/moves only the lower triangle — Fig. 8).
The upper blocks are filled with the mirrored transpose afterwards by the
wrapper when a full tile is required.

Grid (M/bm, M/bm, K/bk), K innermost, VMEM f32 scratch accumulator.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_interpret
from .mxp_gemm import _precision


def _syrk_kernel(a_ref, a2_ref, c_ref, o_ref, acc_ref, *, k_steps):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = c_ref[...].astype(jnp.float32)

    @pl.when(i >= j)
    def _update():
        acc_ref[...] -= jax.lax.dot_general(
            a_ref[...], a2_ref[...], (((1,), (1,)), ((), ())),
            precision=_precision(a_ref.dtype),
            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "interpret"))
def syrk_update(c: jax.Array, a: jax.Array, bm: int = 128, bk: int = 128,
                interpret: bool | None = None) -> jax.Array:
    """Lower-triangle C - A @ A^T; upper blocks of C pass through untouched
    in the block-skip region (callers that need symmetry mirror afterwards)."""
    m, k = a.shape
    assert c.shape == (m, m)
    bm, bk = min(bm, m), min(bk, k)
    assert m % bm == 0 and k % bk == 0
    k_steps = k // bk
    kernel = functools.partial(_syrk_kernel, k_steps=k_steps)
    return pl.pallas_call(
        kernel,
        grid=(m // bm, m // bm, k_steps),
        out_shape=jax.ShapeDtypeStruct((m, m), c.dtype),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),   # A row block
            pl.BlockSpec((bm, bk), lambda i, j, kk: (j, kk)),   # A col block
            pl.BlockSpec((bm, bm), lambda i, j, kk: (i, j)),    # C in
        ],
        out_specs=pl.BlockSpec((bm, bm), lambda i, j, kk: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bm), jnp.float32)],
        interpret=pallas_interpret(interpret),
    )(a, a, c)
