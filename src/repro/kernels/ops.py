"""jit'd public wrappers over the Pallas kernels.

Dispatch rule: f64 tiles take the stock XLA path (the TPU has no native
f64 MXU, and Mosaic no f64 type); f32/bf16/fp8 tiles take the Pallas
kernels.  ``interpret=None`` follows the backend
(:func:`repro.kernels.pallas_interpret`): on the CPU every kernel runs in
interpret mode, which executes the kernel body through XLA and validates
the BlockSpec pipeline end to end; on a TPU it compiles.
"""
from __future__ import annotations

import jax.numpy as jnp

from . import ref as _ref
from .potrf import potrf as _potrf_pallas
from .trsm import trsm as _trsm_pallas
from .syrk import syrk_update as _syrk_pallas
from .mxp_gemm import mxp_gemm_update as _gemm_pallas
# fused column-step megakernel (CholeskyConfig.fuse_columns) + the
# launch accounting shared by fused and unfused dispatch
from .fused_column import (fused_column_step, launch_counts,  # noqa: F401
                           reset_launch_counts)

_F64 = (jnp.float64,)


def _is_f64(*xs) -> bool:
    return any(x.dtype in _F64 for x in xs)


def potrf(a, interpret: bool | None = None):
    if _is_f64(a):
        return _ref.potrf_ref(a)
    return _potrf_pallas(a, interpret=interpret)


def trsm(l, c, interpret: bool | None = None):
    if _is_f64(l, c):
        return _ref.trsm_ref(l, c)
    return _trsm_pallas(l, c, interpret=interpret)


def syrk_update(c, a, interpret: bool | None = None):
    if _is_f64(c, a):
        return _ref.syrk_update_ref(c, a)
    out = _syrk_pallas(c, a, interpret=interpret)
    # mirror the lower triangle (kernel skips strictly-upper blocks)
    return jnp.tril(out) + jnp.tril(out, -1).T


def gemm_update(c, a, b, interpret: bool | None = None):
    if _is_f64(c, a, b):
        return _ref.gemm_update_ref(c, a, b)
    return _gemm_pallas(c, a, b, interpret=interpret)


mxp_gemm_update = gemm_update
