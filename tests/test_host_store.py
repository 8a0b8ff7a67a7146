"""The factored tile store on the host.

* on the single-device jit path the store keeps the executor's compute
  dtype: the matrix is tiled and cast in one copy, and the factor comes
  back from the device with no widening;
* ``logdet()`` reads that store as its f64 widening would read;
* the first ``solve``/``solve_lower`` after a factor widens the store to
  f64 once, and its answers are those of the f64 store, bit for bit;
* every other path keeps an f64 store, and a materialized ``L`` is f64.
"""
import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

import repro
from repro import CholeskyConfig, obs
from repro.core.api import _writable
from repro.core.solve import cho_solve_tiles, logdet_tiles, solve_lower_tiles
from repro.core.tiling import from_tiles, random_spd, to_tiles
from repro.obs.timed import FACTOR

_N, _TB = 256, 64
_WIDEN = "repro.solve.widen_bytes"


def _counter(name: str) -> int:
    return obs.snapshot()["counters"].get(name, 0)


_F32 = CholeskyConfig(tb=_TB, policy="v3", backend="jax",
                      compute_dtype=jnp.float32)


def _f32_solver():
    return repro.plan(_N, _F32).compile()


def test_jit_store_keeps_the_compute_dtype():
    solver = _f32_solver()
    solver.factor(random_spd(_N, seed=1), materialize=False)  # compile
    before = {p: _counter(f"{FACTOR}.{p}_bytes")
              for p in ("tile", "cast", "h2d", "d2h", "widen")}
    solver.factor(random_spd(_N, seed=2), materialize=False)
    store = solver._tiles
    assert store.dtype == np.float32
    assert store.shape == (_N // _TB, _N // _TB, _TB, _TB)
    assert store.flags.c_contiguous and store.flags.writeable
    moved = {p: _counter(f"{FACTOR}.{p}_bytes") - b
             for p, b in before.items()}
    assert moved == {"tile": 4 * _N * _N, "cast": 0, "h2d": 4 * _N * _N,
                     "d2h": 4 * _N * _N, "widen": 0}


def test_logdet_reads_the_store_as_its_f64_widening():
    solver = _f32_solver()
    a = random_spd(_N, seed=3)
    solver.factor(a, materialize=False)
    widened = solver._tiles.astype(np.float64)
    assert solver.logdet() == logdet_tiles(widened)
    assert solver._tiles.dtype == np.float32        # logdet widens nothing
    assert abs(solver.logdet() - np.linalg.slogdet(a)[1]) < 1e-3


def test_first_solve_widens_once_per_factor():
    solver = _f32_solver()
    a = random_spd(_N, seed=4)
    b = np.random.default_rng(4).standard_normal((_N, 3))
    solver.factor(a, materialize=False)
    widened = solver._tiles.astype(np.float64)
    before = _counter(_WIDEN)
    x = solver.solve(b)
    assert _counter(_WIDEN) - before == 8 * _N * _N
    z = solver.solve_lower(b)
    assert _counter(_WIDEN) - before == 8 * _N * _N   # no second widening
    assert solver._tiles.dtype == np.float64
    assert np.array_equal(x, cho_solve_tiles(widened, b))
    assert np.array_equal(z, solve_lower_tiles(widened, b))

    solver.factor(a, materialize=False)             # re-arms the widening
    assert solver._tiles.dtype == np.float32
    before = _counter(_WIDEN)
    assert np.array_equal(solver.solve_lower(b), z)
    assert _counter(_WIDEN) - before == 8 * _N * _N


@pytest.mark.parametrize("src", [np.float64, np.float32])
@pytest.mark.parametrize("dst", [np.float32, np.float64, None])
@pytest.mark.parametrize("tb", [_TB, _N])
def test_to_tiles_casts_in_the_one_copy(src, dst, tb):
    a = random_spd(_N, seed=7).astype(src)
    got = to_tiles(a, tb, dst)
    want = np.asarray(to_tiles(a, tb).astype(np.float64),
                      dtype=a.dtype if dst is None else dst)
    assert got.dtype == want.dtype and got.flags.c_contiguous
    assert np.array_equal(got, want)
    assert not np.shares_memory(got, a)         # a fresh store, even nt=1


@pytest.mark.parametrize("cfg, traced", [
    (CholeskyConfig(tb=_TB, policy="v3", backend="numpy"), False),
    (CholeskyConfig(tb=_TB, policy="v3", backend="numpy", host_slots=4),
     False),
    (CholeskyConfig(tb=_TB, policy="v3", backend="jax", host_slots=4,
                    compute_dtype=jnp.float32), False),
    (CholeskyConfig(tb=_TB, policy="v3", ndev=2, backend="numpy"), False),
    (CholeskyConfig(tb=_TB, policy="v3", ndev=2, host_slots=5), False),
    (_F32, True),
], ids=["numpy", "numpy-spill", "jax-spill", "multidevice",
        "multidevice-spill", "traced"])
def test_other_paths_keep_f64_stores(cfg, traced):
    solver = repro.plan(_N, cfg).compile()
    solver.factor(random_spd(_N, seed=8), materialize=False,
                  trace=obs.TraceRecorder() if traced else None)
    assert solver._tiles.dtype == np.float64
    before = _counter(_WIDEN)
    solver.solve(np.ones(_N))
    assert _counter(_WIDEN) == before


def test_materialized_factor_is_f64():
    solver = _f32_solver()
    a = random_spd(_N, seed=10)
    l = solver.factor(a)
    assert l.dtype == np.float64
    assert np.array_equal(
        l, np.tril(from_tiles(solver._tiles.astype(np.float64))))
    assert np.abs(l - np.linalg.cholesky(a)).max() < 1e-5


@pytest.mark.parametrize("dtype", [np.float32, ml_dtypes.bfloat16])
def test_writable_keeps_the_memory_and_the_dtype(dtype):
    host = np.asarray(jax.device_put(np.arange(12, dtype=dtype)
                                     .reshape(3, 4)))
    assert not host.flags.writeable
    own = _writable(host)
    assert own.dtype == host.dtype and own.shape == host.shape
    assert np.shares_memory(own, host) and own.flags.writeable
    own[0, 0] = 7
    assert host[0, 0] == 7
    assert _writable(own) is own
