"""Pallas TPU kernel: mixed-precision GEMM update  C <- C - A @ B^T.

This is the hot kernel of the factorization (GEMM is ~n^3/3 of the work)
and the place where the paper's four-precision scheme meets the hardware:
A and B keep their *storage* precision (fp8-e4m3 / bf16 / f32) so the MXU
runs at the narrow-operand rate, while the accumulator is always f32.

Tiling: grid (M/bm, N/bn, K/bk) with the K dimension innermost; a VMEM
scratch accumulator carries partial sums across the K steps (standard TPU
matmul pattern — the HBM->VMEM traffic per operand block is amortized over
the whole K loop).  Block sizes default to 128 to match the 128x128 MXU
systolic array; both operands are [rows, K]-major so the B block is
transposed inside VMEM (free — feeds the MXU's stationary side).

SYRK (C - A A^T) reuses this kernel with B = A.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_interpret


def _precision(dtype):
    """f32 operands contract at full f32 (HIGHEST); narrower storage
    operands keep the MXU's native single pass."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _mxp_gemm_kernel(a_ref, b_ref, c_ref, o_ref, acc_ref, *, k_steps):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = c_ref[...].astype(jnp.float32)

    a = a_ref[...]
    b = b_ref[...]
    acc_ref[...] -= jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), precision=_precision(a.dtype),
        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def mxp_gemm_update(c: jax.Array, a: jax.Array, b: jax.Array,
                    bm: int = 128, bn: int = 128, bk: int = 128,
                    interpret: bool | None = None) -> jax.Array:
    """C - A @ B^T with f32 accumulation.  a: [M,K], b: [N,K], c: [M,N]."""
    m, k = a.shape
    n, kb = b.shape
    assert k == kb and c.shape == (m, n), (a.shape, b.shape, c.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    k_steps = k // bk
    kernel = functools.partial(_mxp_gemm_kernel, k_steps=k_steps)
    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, k_steps),
        out_shape=jax.ShapeDtypeStruct((m, n), c.dtype),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),   # A
            pl.BlockSpec((bn, bk), lambda i, j, kk: (j, kk)),   # B
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),    # C in
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=pallas_interpret(interpret),
    )(a, b, c)
