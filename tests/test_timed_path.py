"""Spans and scopes on the timed path (``repro.obs.timed``).

* one untraced ``factor()`` on the single-device jit path writes a
  ``repro.factor`` span into the ``jax.profiler`` trace, with its five
  phases as children in the order they run, each carrying the bytes of
  the array it made (none for the hand-over to the store, which
  converts nothing);
* every tile op of the executor lowers inside a ``<kind>.<class>`` named
  scope, each rounding through a narrower class inside ``round.<class>``,
  and each fused column step inside ``column.<k>``;
* the scopes are metadata only: the program's ops, ``jit_traces`` and
  the factor are what they were.
"""
import contextlib
import re

import jax
import numpy as np
import pytest

import repro
from repro import CholeskyConfig, obs
from repro.core import cholesky
from repro.core.cholesky import run_schedule_numpy
from repro.core.precision import LADDERS, PrecisionPlan
from repro.core.tiling import from_tiles, random_spd, to_tiles
from repro.obs.timed import FACTOR, parse_scope, scope, span

_N, _TB = 256, 64
_SCOPE_RE = re.compile(r"/((?:%s)\.[A-Za-z0-9_]+)"
                       % "|".join(obs.timed.SCOPE_KINDS))


def _mxp_plan(nt):
    """f64 tiles with the bottom-left ones in bf16 and one in f8."""
    cls = np.zeros((nt, nt), np.int8)
    cls[nt - 1, 0] = 3
    cls[nt - 2, 0] = cls[nt - 1, 1] = 2
    return PrecisionPlan(cls, LADDERS["tpu"], 1e-4)


def _scopes(solver) -> set:
    ex = solver._executor
    nt = solver.n // solver.config.tb
    tb = solver.config.tb
    shape = jax.ShapeDtypeStruct((nt, nt, tb, tb), np.float64)
    text = ex.fn.lower(shape).as_text(debug_info=True)
    return set(_SCOPE_RE.findall(text))


def test_factor_phase_spans_nest_in_order(tmp_path):
    a = random_spd(_N, seed=3)
    solver = repro.plan(_N, CholeskyConfig(tb=_TB, policy="v3",
                                           backend="jax")).compile()
    solver.factor(a, materialize=False)          # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    solver.factor(a, materialize=False)
    jax.profiler.stop_trace()

    from jax.profiler import ProfileData
    path = next(tmp_path.rglob("*.xplane.pb"))
    spans = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name,
                    dict(e.stats))
                   for p in ProfileData.from_file(str(path)).planes
                   if p.name.startswith("/host:")
                   for line in p.lines for e in line.events
                   if e.name.startswith(FACTOR))
    outer = [s for s in spans if s[2] == FACTOR]
    assert len(outer) == 1
    lo, hi, _, args = outer[0]
    assert args["call"] == 2                     # the second call
    phases = [s for s in spans if s[2] != FACTOR]
    assert [s[2] for s in phases] == [
        f"{FACTOR}.{p}" for p in ("tile", "h2d", "run", "d2h", "widen")]
    for (s, e, _, _), (s2, _, _, _) in zip(phases, phases[1:]):
        assert lo <= s <= e <= s2                # in order, not overlapping
    assert phases[-1][1] <= hi
    store = _N * _N * np.dtype(solver._executor.dtype).itemsize
    want = {"tile": store, "h2d": store, "d2h": store, "widen": 0}
    got = {n[len(FACTOR) + 1:]: st.get("bytes") for _, _, n, st in phases}
    assert got == {**want, "run": None}


def test_executor_scopes_name_every_tile_op():
    nt = 4
    cfg = CholeskyConfig(tb=16, policy="v3", backend="jax",
                         plan=_mxp_plan(nt))
    found = _scopes(repro.plan(16 * nt, cfg).compile())
    kinds = {s.split(".")[0] for s in found}
    assert {"potrf", "trsm", "syrk", "gemm", "load", "store",
            "round"} <= kinds
    assert {"round.bf16", "round.f8e4m3", "load.bf16",
            "store.f64"} <= found
    # an f64 tile in an f64 program never converts
    assert "round.f64" not in found


def test_fused_column_steps_are_scoped():
    cfg = CholeskyConfig(tb=16, policy="v3", backend="jax",
                         fuse_columns=True)
    found = _scopes(repro.plan(64, cfg).compile())
    assert {f"column.{k}" for k in range(4)} <= found


def test_scopes_leave_the_program_unchanged(monkeypatch):
    nt = 4
    cfg = CholeskyConfig(tb=16, policy="v3", backend="jax",
                         plan=_mxp_plan(nt))
    shape = jax.ShapeDtypeStruct((nt, nt, 16, 16), np.float64)

    def program():
        repro.clear_plan_cache()
        ex = repro.plan(16 * nt, cfg).compile()._executor
        return ex.fn.lower(shape).as_text()

    scoped = program()
    monkeypatch.setattr(cholesky.jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    assert program() == scoped


def test_split_staging_keeps_the_factor():
    a = random_spd(_N, seed=5)
    solver = repro.plan(_N, CholeskyConfig(tb=_TB, policy="v3",
                                           backend="jax")).compile()
    l1 = solver.factor(a)
    l2 = solver.factor(a)
    assert np.array_equal(l1, l2)
    l_np = np.tril(from_tiles(run_schedule_numpy(
        to_tiles(a, _TB), solver._plan.single_schedule())))
    assert np.abs(l1 - l_np).max() < 1e-13
    assert np.abs(l1 - np.linalg.cholesky(a)).max() < 1e-10
    assert solver.stats["jit_traces"] == 1


def test_host_cast_equals_the_fused_staging_it_replaced():
    """An f32 store cast on the host gives the factor bit for bit that
    ``jnp.asarray(tiles, dtype)`` in, ``np.asarray(out, float64)`` out
    gave before the staging was split into phases."""
    import jax.numpy as jnp
    a = random_spd(_N, seed=6)
    solver = repro.plan(_N, CholeskyConfig(
        tb=_TB, policy="v3", backend="jax",
        compute_dtype=jnp.float32)).compile()
    got = solver.factor(a)
    ex = solver._executor
    out = np.asarray(ex.fn(jnp.asarray(to_tiles(a, _TB), dtype=ex.dtype)),
                     dtype=np.float64)
    assert np.array_equal(got, np.tril(from_tiles(out)))
    assert solver.stats["jit_traces"] == 1


def test_span_counts_bytes_only_once_the_phase_ran():
    name = "repro.test.phase"
    before = obs.snapshot()["counters"].get(name + "_bytes", 0)
    with span(name, nbytes=1234, call=1):
        pass
    with pytest.raises(RuntimeError):
        with span(name, nbytes=99):
            raise RuntimeError("phase failed")
    with span(name):
        pass
    assert obs.snapshot()["counters"][name + "_bytes"] == before + 1234


def test_scope_names():
    assert scope("gemm", "f32") == "gemm.f32"
    assert scope("column", 3) == "column.3"
    with pytest.raises(ValueError):
        scope("bcast", "f32")
    with pytest.raises(ValueError):
        scope("gemm", "f/32")
    assert parse_scope("jit(f)/jit(main)/store.bf16/round.bf16/"
                       "convert_element_type") == "round.bf16"
    assert parse_scope("jit(f)/jit(main)/gemm.f32/dot_general") == "gemm.f32"
    assert parse_scope("jit(f)/jit(main)/broadcast_in_dim") is None
    assert parse_scope("jit(f)/gemm.f32.x/dot_general") is None
