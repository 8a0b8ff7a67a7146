"""Pallas TPU megakernel: one launch per fused column step.

The unfused executor dispatches one kernel per tile op — for column ``k``
of a left-looking tile Cholesky that is ``k`` SYRKs + 1 POTRF on the
diagonal and, per owned row ``m > k``, ``k`` GEMMs + 1 TRSM: ``O(nt * k)``
launches whose HBM->VMEM traffic re-reads the same panel-history tiles
over and over.  This kernel runs the whole column step in a *single*
``pallas_call``:

* grid ``(R, K)`` — ``R`` output tiles (row 0 is the diagonal when
  ``with_diag``), ``K`` accumulation steps.  The TPU grid executes
  sequentially row-major, so row 0 (the POTRF) completes before any TRSM
  row consumes its factor from VMEM scratch.
* same-shape tile GEMMs are batched across rows: step ``(r, kk)`` is
  ``acc_r -= hist[r, kk] @ bhist[kk]^T`` with the B operand (the diagonal
  row's history) broadcast across the ``r`` axis — for ``r = 0`` and
  ``hist[0] = bhist`` that is exactly the SYRK.
* the tile being updated stays resident in a VMEM accumulator across all
  ``K`` steps; the triangular solve / factorization runs in the same
  launch on the final step (``pl.when``), against the VMEM-resident
  factor — no HBM round-trip between the update wave and the solve.
* the per-tile precision down-cast runs *in the epilogue*: each output
  row carries a class id, and scaled-FP8 rows additionally track their
  amax at store time and fold the power-of-two scale into the cast
  (see ``repro.core.precision.fp8_scale`` and docs/kernels.md).

Launch accounting: the executors and benchmarks count kernel dispatches
through :func:`launch_counts` — every call here bumps ``fused_column``
(one per column step), every wrapper in :mod:`repro.kernels.ops` bumps
``tile_op`` (one per unfused tile op).
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_interpret
from .potrf import chol_in_place
from .trsm import trsm_in_place

_JNP_DTYPES = {
    "f64": jnp.float64,
    "f32": jnp.float32,
    "f16": jnp.float16,
    "bf16": jnp.bfloat16,
    "f8e4m3": jnp.float8_e4m3fn,
    "f8e4m3s": jnp.float8_e4m3fn,
}

_VMEM_LIMIT = 64 * 1024 * 1024     # of v5e's 128 MiB

# trace-time kernel dispatch counters (see launch_counts)
_LAUNCHES = {"fused_column": 0, "tile_op": 0}


def launch_counts() -> dict:
    """Kernel dispatches since the last reset: ``fused_column`` counts
    fused column-step launches, ``tile_op`` unfused per-tile-op launches
    (incremented by the :mod:`repro.kernels.ops` wrappers)."""
    return dict(_LAUNCHES)


def reset_launch_counts() -> None:
    for k in _LAUNCHES:
        _LAUNCHES[k] = 0


def count_tile_op() -> None:
    _LAUNCHES["tile_op"] += 1


def _fp8_scale_of(amax, dtype):
    """Power-of-two scale for a scaled-FP8 tile from its amax: largest
    ``2^e`` with ``amax * 2^e <= 448``.  Computed via frexp so jax and
    numpy agree bitwise (a log2/floor boundary could differ by one ulp
    and shift the scale a whole octave)."""
    m, e = jnp.frexp(amax)
    exp = (8 - e) + (m <= 0.875).astype(e.dtype)   # int32 also under x64
    s = jnp.exp2(exp.astype(dtype))
    ok = jnp.isfinite(amax) & (amax > 0)
    return jnp.where(ok, s, jnp.asarray(1.0, dtype))


def _round_class(x, name: str):
    """Round-trip one tile through a storage class inside the kernel
    epilogue (the executors' ``_jx_round`` semantics; the scaled-FP8
    class applies its store-time amax scale before the cast and inverts
    it after).  The f64 class is the identity: an f64 tile is already
    exact, and an f32 tile survives the f32 -> f64 -> f32 round trip
    unchanged (nor does Mosaic have an f64 type to cast through)."""
    if name == "f64" or _JNP_DTYPES[name] == x.dtype:
        return x
    if name == "f8e4m3s":
        # amax stays a (1, 1) vector: Mosaic bitcasts (frexp) vectors only
        s = _fp8_scale_of(jnp.max(jnp.abs(x), keepdims=True), x.dtype)
        return ((x * s).astype(jnp.float8_e4m3fn).astype(x.dtype)) / s
    return x.astype(_JNP_DTYPES[name]).astype(x.dtype)


def _epilogue(x, cls_id, ladder):
    """Class-indexed epilogue cast: ``cls_id`` selects which storage
    class the result is rounded through (-1 = leave unrounded; the
    executor's own STORE will round it)."""
    out = x
    for idx, name in enumerate(ladder):
        out = jnp.where(cls_id == idx, _round_class(x, name), out)
    return out


def _fused_kernel(cls_ref, c_ref, h_ref, b_ref, l_ref, o_ref, acc_ref,
                  l_scr, *, k_steps, with_diag, ladder):
    r = pl.program_id(0)
    kk = pl.program_id(1)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = c_ref[0].astype(acc_ref.dtype)

    a = h_ref[0, 0].astype(acc_ref.dtype)
    b = b_ref[0].astype(acc_ref.dtype)
    acc_ref[...] -= jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=acc_ref.dtype)

    @pl.when(kk == k_steps - 1)
    def _final():
        cls_id = cls_ref[r]
        if with_diag:
            @pl.when(r == 0)
            def _diag():
                # the epilogue-rounded factor goes to scratch too: the
                # row TRSMs must solve against the *stored* (class-
                # rounded) diagonal, exactly as the unfused trace reads
                # it back after its STORE
                c = acc_ref[...]
                acc_ref[...] = 0.5 * (c + c.T)
                chol_in_place(acc_ref)
                l = _epilogue(acc_ref[...], cls_id, ladder)
                l_scr[...] = l
                o_ref[0] = l.astype(o_ref.dtype)

            @pl.when(r > 0)
            def _row():
                trsm_in_place(l_scr, acc_ref)
                o_ref[0] = _epilogue(acc_ref[...], cls_id,
                                     ladder).astype(o_ref.dtype)
        else:
            trsm_in_place(l_ref, acc_ref)
            o_ref[0] = _epilogue(acc_ref[...], cls_id,
                                 ladder).astype(o_ref.dtype)


def fused_column_step(c_stack, hist, bhist, l_kk, cls_ids, *,
                      ladder, with_diag: bool, interpret: bool | None = None):
    """One fused column step: trailing update + solve, one launch.

    Args:
      c_stack: ``[R, tb, tb]`` tiles to update.  With ``with_diag`` row 0
        is the diagonal tile (SYRK wave + POTRF); every later row gets
        the GEMM wave + TRSM against the in-launch factor.  Without
        ``with_diag`` every row is a panel row solved against ``l_kk``.
      hist: ``[R, K, tb, tb]`` A-operand history (``A[m, j]`` for
        ``j < k``).  ``K = 0`` is allowed (column 0: pure solve).
      bhist: ``[K, tb, tb]`` B-operand history — the diagonal row's
        panel tiles ``A[k, j]``; with ``with_diag``, ``hist[0] == bhist``.
      l_kk: ``[tb, tb]`` pre-factored diagonal (ignored with
        ``with_diag`` — pass zeros).
      cls_ids: ``[R]`` int32 storage-class index per output row for the
        epilogue cast (-1 leaves a row unrounded); scalar-prefetched
        into SMEM.
      ladder: the precision-plan ladder naming the class indices.
      with_diag: statically selects the POTRF-in-launch variant.
      interpret: run the Pallas interpreter; ``None`` chooses from the
        backend (:func:`repro.kernels.pallas_interpret`).  A compiled
        launch refuses f64 tiles: Mosaic has no f64 type.

    Returns ``[R, tb, tb]``: the factored diagonal (row 0, with_diag)
    and solved panel rows, epilogue-cast per class.
    """
    interpret = pallas_interpret(interpret)
    r_tiles, tb, _ = c_stack.shape
    if c_stack.dtype == jnp.float64 and not interpret:
        raise ValueError(
            "fused_column_step cannot compile f64 tiles: Mosaic has no f64 "
            "type; use an f32 compute dtype or fuse_columns=False")
    k_hist = hist.shape[1]
    if k_hist == 0:     # pure-solve column: accumulate an exact zero
        hist = jnp.zeros((r_tiles, 1, tb, tb), dtype=c_stack.dtype)
        bhist = jnp.zeros((1, tb, tb), dtype=c_stack.dtype)
        k_hist = 1
    acc_dtype = (jnp.float64 if c_stack.dtype == jnp.float64
                 else jnp.float32)
    cls_arr = jnp.asarray(cls_ids, dtype=jnp.int32).reshape(r_tiles)
    _LAUNCHES["fused_column"] += 1
    kernel = functools.partial(_fused_kernel, k_steps=k_hist,
                               with_diag=with_diag, ladder=tuple(ladder))
    z = np.int32(0)     # int32 block indices, also under x64 (Mosaic has no i64)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,      # cls_ids
        grid=(r_tiles, k_hist),
        in_specs=[
            pl.BlockSpec((1, tb, tb), lambda r, kk, cls: (r, z, z)),   # C
            pl.BlockSpec((1, 1, tb, tb),
                         lambda r, kk, cls: (r, kk, z, z)),            # A
            pl.BlockSpec((1, tb, tb), lambda r, kk, cls: (kk, z, z)),  # B
            pl.BlockSpec((tb, tb), lambda r, kk, cls: (z, z)),         # L in
        ],
        out_specs=pl.BlockSpec((1, tb, tb), lambda r, kk, cls: (r, z, z)),
        scratch_shapes=[pltpu.VMEM((tb, tb), acc_dtype),
                        pltpu.VMEM((tb, tb), acc_dtype)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r_tiles, tb, tb), c_stack.dtype),
        # ten double-buffered tile blocks, two scratch tiles and the
        # f32 (HIGHEST) matmul's temporaries outgrow the default 16 MiB
        # scoped VMEM at tb=512
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(cls_arr, c_stack, hist, bhist, l_kk)
