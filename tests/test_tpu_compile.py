"""Ahead-of-time compiles for a described TPU v5e, without a chip.

The TPU compiler ships with jax, and it compiles for a topology that is
described rather than attached.  These tests compile the Pallas tile
kernels at the tile sizes the solver runs on the chip, and the unrolled
single-device executor, and check that a Mosaic kernel
(``tpu_custom_call``) sits wherever one should.  What Mosaic refuses
(dynamic lane slices, i64 indices, f64 scratch, VMEM overruns) fails
here instead of on the chip.  Nothing runs: a compile that passes says
nothing about results or times.

The topology is described inside a module fixture, never at import, so
every test worker collects the same tests and only the worker that runs
this file loads the TPU library.
"""
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.cholesky import make_jax_executor
from repro.core.precision import uniform_plan
from repro.core.schedule import build_schedule
from repro.kernels import ops
from repro.kernels.fused_column import fused_column_step

TILES = (256, 512)
KERNEL = "tpu_custom_call"


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")    # else libtpu logs to /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but can never be read back without one: keep it off
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            cc.reset_cache()


def _hlo(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _tile(sharding, *shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("tb", TILES)
@pytest.mark.parametrize("kernel", ["potrf", "trsm", "syrk_update",
                                    "gemm_update"])
def test_tile_kernel_compiles(one_chip, kernel, tb):
    fn = getattr(ops, kernel)
    nargs = {"potrf": 1, "trsm": 2, "syrk_update": 2, "gemm_update": 3}
    args = [_tile(one_chip, tb, tb)] * nargs[kernel]
    assert KERNEL in _hlo(lambda *a: fn(*a, interpret=False), *args)


@pytest.mark.parametrize("tb", TILES)
@pytest.mark.parametrize("with_diag, ladder", [
    (True, ("f64", "f32", "bf16", "f8e4m3")),       # the tpu ladder
    (False, ("f64", "f32", "bf16", "f8e4m3s")),     # tpu-scaled
])
def test_fused_column_step_compiles(one_chip, tb, with_diag, ladder):
    r, k = 3, 2

    def step(c, hist, bhist, l_kk, cls_ids):
        return fused_column_step(c, hist, bhist, l_kk, cls_ids,
                                 ladder=ladder, with_diag=with_diag,
                                 interpret=False)

    hlo = _hlo(step, _tile(one_chip, r, tb, tb), _tile(one_chip, r, k, tb, tb),
               _tile(one_chip, k, tb, tb), _tile(one_chip, tb, tb),
               _tile(one_chip, r, dtype=jnp.int32))
    assert hlo.count(KERNEL) == 1       # the whole column step, one launch


@pytest.mark.parametrize("n, tb, use_pallas, fuse", [
    (4096, 1024, False, False),     # the default path: stock XLA only
    (2048, 512, True, False),
    (2048, 512, False, True),
])
def test_jax_executor_compiles(one_chip, n, tb, use_pallas, fuse):
    nt = n // tb
    sched = build_schedule(nt, tb, "v3", plan=uniform_plan(nt, "f32"))
    run = make_jax_executor(sched, jnp.float32, use_pallas=use_pallas,
                            interpret=False, fuse_columns=fuse)
    hlo = _hlo(run, _tile(one_chip, nt, nt, tb, tb))
    assert (KERNEL in hlo) == (use_pallas or fuse)
