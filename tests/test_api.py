"""Planner/executor API: config validation, plan caching, executor reuse
(schedule built + jitted exactly once per plan), blocked solve accuracy,
and the deprecated ooc_cholesky shim's equivalence + unified return type."""
import numpy as np
import pytest
import scipy.linalg as sla

import repro
from repro.core import api
from repro.core.schedule import MultiDeviceSchedule, OpKind
from repro.core.tiling import random_spd, to_tiles


# ---------------------------------------------------------------------------
# CholeskyConfig eager validation

@pytest.mark.parametrize("kwargs, match", [
    # tb=0 is now the autotune sentinel (see test_tune.py); negatives
    # remain invalid
    (dict(tb=-1), "tb"),
    (dict(tb=32, policy="bogus"), "policy"),
    (dict(tb=32, backend="torch"), "backend"),
    (dict(tb=32, ladder="cuda"), "ladder"),
    (dict(tb=32, eps_target=0.0), "eps_target"),
    (dict(tb=32, cache_slots=-1), "cache_slots"),
    (dict(tb=32, ndev=0), "ndev"),
    (dict(tb=32, block=(2,)), "block"),
    (dict(tb=32, policy="v3", block=(2, 2)), "only meaningful for"),
    (dict(tb=32, policy="v4", cache_slots=5), "slots"),
    (dict(tb=32, use_pallas=True, backend="numpy"), "use_pallas"),
    (dict(tb=32, compute_dtype=np.float32, backend="numpy"),
     "compute_dtype"),
    (dict(tb=32, eps_target=1e-6, plan=repro.uniform_plan(4)), "not both"),
])
def test_config_validation(kwargs, match):
    with pytest.raises(ValueError, match=match):
        repro.CholeskyConfig(**kwargs)


# auto-backend resolution (and with it use_pallas/compute_dtype
# validation) is device-count-dependent by design; these guards make the
# expectations explicit instead of assuming a single-device process
import jax as _jax

_NDEVICES = len(_jax.devices())
_single_device = pytest.mark.skipif(
    _NDEVICES > 1, reason="needs a process where jax sees one device "
    "(auto resolves ndev=2 to the jax executor here)")


@pytest.mark.parametrize("kwargs, match", [
    # kwargs invalid for multi-device schedules on any device count
    (dict(use_pallas=True, backend="numpy"), "use_pallas"),
    (dict(compute_dtype=np.float64, backend="numpy"), "compute_dtype"),
    (dict(policy="async"), "sync/v1/v2/v3"),
    (dict(policy="v4"), "sync/v1/v2/v3"),
])
def test_config_multidevice_rejects_unsupported(kwargs, match):
    with pytest.raises(ValueError, match=match):
        repro.CholeskyConfig(tb=32, ndev=2, **kwargs)


@_single_device
@pytest.mark.parametrize("kwargs, match", [
    # with one visible device, auto resolves ndev=2 to the numpy replay,
    # which supports neither of these
    (dict(use_pallas=True), "use_pallas"),
    (dict(compute_dtype=np.float64), "compute_dtype"),
])
def test_config_multidevice_auto_numpy_rejects(kwargs, match):
    with pytest.raises(ValueError, match=match):
        repro.CholeskyConfig(tb=32, ndev=2, **kwargs)


@_single_device
def test_multidevice_jax_backend_requires_devices():
    """0.3: backend='jax' with ndev > 1 is a *valid config* (the
    per-device executor); with too few visible devices it fails at
    compile() with an actionable error instead of at construction."""
    cfg = repro.CholeskyConfig(tb=16, policy="v3", ndev=2, backend="jax")
    assert cfg.resolved_backend() == "jax"
    with pytest.raises(RuntimeError,
                       match="needs 2 devices.*host_platform_device_count"):
        repro.plan(64, cfg).compile()
    # the shim inherits the same behaviour (pre-0.2 it silently fell back
    # to the NumPy replay; 0.2 rejected the config outright)
    a = random_spd(64, seed=0)
    with pytest.warns(DeprecationWarning):
        with pytest.raises(RuntimeError, match="needs 2 devices"):
            repro.ooc_cholesky(a, 16, ndev=2, backend="jax")


def test_config_backend_resolution_and_hash():
    c1 = repro.CholeskyConfig(tb=32)
    assert c1.resolved_backend() == "jax"
    # multi-device auto resolution follows the visible device count
    expect = "jax" if _NDEVICES >= 2 else "numpy"
    assert repro.CholeskyConfig(tb=32, ndev=2).resolved_backend() == expect
    # value semantics: equal configs hash equal (keys one plan cache slot)
    assert c1 == repro.CholeskyConfig(tb=32) and hash(c1) == hash(
        repro.CholeskyConfig(tb=32))
    p = repro.uniform_plan(4)
    c2 = repro.CholeskyConfig(tb=32, plan=p)
    c3 = repro.CholeskyConfig(tb=32, plan=repro.uniform_plan(4))
    assert c2 == c3 and hash(c2) == hash(c3) and c2 != c1


def _steer_platform(monkeypatch, platform, count):
    """Make JAX report ``count`` devices of ``platform`` (the backend
    choice is read from jax.devices() / jax.default_backend())."""
    import types

    import jax
    fake = [types.SimpleNamespace(platform=platform, id=i)
            for i in range(count)]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: fake)
    monkeypatch.setattr(jax, "default_backend", lambda: platform)


@pytest.mark.parametrize("count, kwargs, match", [
    (1, dict(), "needs 2 devices, found 1"),
    (4, dict(host_slots=4), "host_slots > 0 with ndev > 1"),
])
def test_auto_backend_never_leaves_an_accelerator(monkeypatch, count,
                                                  kwargs, match):
    """backend='auto' falls back to the NumPy replay only on the CPU: on
    a TPU with too few devices (or a spill schedule only the replay
    runs) it raises instead of quietly running on the host."""
    cfg = repro.CholeskyConfig(tb=32, ndev=2, **kwargs)
    _steer_platform(monkeypatch, "tpu", count)
    with pytest.raises(RuntimeError, match=match):
        cfg.resolved_backend()
    _steer_platform(monkeypatch, "cpu", count)
    assert cfg.resolved_backend() == "numpy"


def test_auto_backend_resolves_jax_on_enough_accelerators(monkeypatch):
    _steer_platform(monkeypatch, "tpu", 4)
    assert repro.CholeskyConfig(tb=32, ndev=4).resolved_backend() == "jax"
    assert repro.CholeskyConfig(tb=32).resolved_backend() == "jax"


def test_fused_f64_refused_before_compiling_off_cpu(monkeypatch):
    """fuse_columns with an f64 compute dtype cannot compile (Mosaic has
    no f64): off the CPU it is refused when the executor is built, before
    anything is traced."""
    cfg = repro.CholeskyConfig(tb=16, policy="v3", fuse_columns=True,
                               compute_dtype=np.float64)
    p = repro.plan(64, cfg)
    _steer_platform(monkeypatch, "tpu", 1)
    with pytest.raises(ValueError, match="no f64"):
        p.compile()


# ---------------------------------------------------------------------------
# plan() caching + executor reuse

def test_plan_cache_returns_same_object():
    api.clear_plan_cache()
    p1 = repro.plan(96, tb=32, policy="v2")
    p2 = repro.plan(96, repro.CholeskyConfig(tb=32, policy="v2"))
    assert p1 is p2
    # solvers are fresh per compile() (per-call-site factored state)...
    s1, s2 = p1.compile(), p2.compile()
    assert s1 is not s2
    # ...but share the plan's one compiled executor
    assert s1._executor is s2._executor
    api.clear_plan_cache()
    assert repro.plan(96, tb=32, policy="v2") is not p1


def test_solvers_do_not_share_factored_state():
    """Two call sites holding solvers for the same (n, config) must not
    observe each other's factors (regression: the solver used to be
    cached globally, so factor() at site B silently re-pointed site A's
    solve())."""
    n = 96
    a1, a2 = random_spd(n, seed=1), random_spd(n, seed=2)
    s_a = repro.plan(n, tb=32, policy="v3").compile()
    s_b = repro.plan(n, tb=32, policy="v3").compile()
    s_a.factor(a1)
    s_b.factor(a2)
    b = np.ones(n)
    assert np.abs(a1 @ s_a.solve(b) - b).max() < 1e-8
    assert np.abs(a2 @ s_b.solve(b) - b).max() < 1e-8
    # a fresh solver never inherits another call site's factor
    with pytest.raises(RuntimeError, match="factor"):
        repro.plan(n, tb=32, policy="v3").compile().solve(b)


def test_executor_reuse_builds_and_jits_once():
    """The amortization contract: K same-shape factorizations through one
    OOCSolver build the schedule once and trace the jit once."""
    api.clear_plan_cache()
    n, k = 128, 4
    before = api.schedule_build_count()
    solver = repro.plan(n, tb=32, policy="v3").compile()
    ls = [solver.factor(random_spd(n, seed=s)) for s in range(k)]
    assert api.schedule_build_count() - before == 1
    assert solver.stats["jit_traces"] == 1
    assert solver.stats["factor_calls"] == k
    # replay is deterministic: same matrix -> bitwise same factor
    assert np.array_equal(ls[0], solver.factor(random_spd(n, seed=0)))
    # re-planning + recompiling the same (n, config) neither rebuilds the
    # schedule nor retraces: the fresh solver rides the cached executor
    other = repro.plan(n, tb=32, policy="v3").compile()
    other.factor(random_spd(n, seed=0))
    assert api.schedule_build_count() - before == 1
    assert other.stats["jit_traces"] == 1


def test_plan_default_plan_carries_config_ladder():
    """Regression: the f64 default plan used to hardcode ladder='tpu',
    misreporting the schedule metadata for ladder='gpu' configs."""
    pl = repro.plan(64, tb=32, policy="v3", ladder="gpu")
    assert pl.schedule.plan.ladder == repro.LADDERS["gpu"]
    assert repro.plan(64, tb=32, policy="v3").schedule.plan.ladder == \
        repro.LADDERS["tpu"]


def test_factor_materialize_false_keeps_tile_store_only():
    n = 96
    a = random_spd(n, seed=6)
    solver = repro.plan(n, tb=32, policy="v3").compile()
    assert solver.factor(a, materialize=False) is None
    b = np.ones(n)
    assert np.abs(a @ solver.solve(b) - b).max() < 1e-8
    assert solver.logdet() == pytest.approx(
        2.0 * np.sum(np.log(np.diag(np.linalg.cholesky(a)))), rel=1e-12)
    assert solver.stats["factor_calls"] == 1
    assert solver.stats["solve_calls"] == 1


def test_plan_rejects_matrix_dependent_eps():
    with pytest.raises(ValueError, match="specialize"):
        repro.plan(128, tb=32, eps_target=1e-6)


def test_specialize_freezes_plan():
    a = random_spd(128, seed=3)
    cfg = repro.CholeskyConfig(tb=32, policy="v3", eps_target=1e-6)
    frozen = cfg.specialize(a)
    assert frozen.eps_target is None and frozen.plan is not None
    expect = repro.plan_for_matrix(to_tiles(a, 32), 1e-6)
    assert frozen.plan == expect
    # already-static configs pass through untouched
    assert frozen.specialize(a) is frozen
    l = repro.plan(128, frozen).compile().factor(a)
    assert np.abs(l @ l.T - a).max() < 1e-5


# ---------------------------------------------------------------------------
# solve(): blocked triangular substitution against the tile store

@pytest.mark.parametrize("nrhs", [None, 3])
def test_solve_matches_scipy_cho_solve(nrhs):
    n, tb = 192, 48
    a = random_spd(n, seed=7)
    rng = np.random.default_rng(1)
    b = rng.standard_normal(n if nrhs is None else (n, nrhs))
    solver = repro.plan(n, tb=tb, policy="v3").compile()
    solver.factor(a)
    x = solver.solve(b)
    assert x.shape == b.shape
    ref = sla.cho_solve((np.linalg.cholesky(a), True), b)
    assert np.abs(x - ref).max() < 1e-10


def test_solve_multidevice_and_logdet():
    n = 128
    a = random_spd(n, seed=9)
    solver = repro.plan(n, tb=16, policy="v3", ndev=2).compile()
    solver.factor(a)
    b = np.ones(n)
    assert np.abs(a @ solver.solve(b) - b).max() < 1e-8
    assert solver.logdet() == pytest.approx(
        2.0 * np.sum(np.log(np.diag(np.linalg.cholesky(a)))), rel=1e-12)


def test_solve_before_factor_raises():
    api.clear_plan_cache()
    solver = repro.plan(64, tb=32, policy="v1").compile()
    with pytest.raises(RuntimeError, match="factor"):
        solver.solve(np.ones(64))


def test_factor_shape_mismatch_raises():
    solver = repro.plan(64, tb=32, policy="v3").compile()
    with pytest.raises(ValueError, match="n=64"):
        solver.factor(random_spd(96, seed=0))


def test_gaussian_loglik_solver_path_matches_dense():
    from repro.geo.likelihood import gaussian_loglik
    n = 128
    a = random_spd(n, seed=2)
    y = np.random.default_rng(0).standard_normal(n)
    solver = repro.plan(n, tb=32, policy="v3").compile()
    l = solver.factor(a)
    assert gaussian_loglik(solver, y) == pytest.approx(
        gaussian_loglik(l, y), rel=1e-12)


# ---------------------------------------------------------------------------
# shim: unified return type + equivalence with the solver path

def test_shim_returns_unified_schedule_and_matches_solver():
    a = random_spd(96, seed=4)
    with pytest.warns(DeprecationWarning):
        l, sched = repro.ooc_cholesky(a, 32, policy="v3")
    assert isinstance(sched, MultiDeviceSchedule) and sched.ndev == 1
    solver = repro.plan(96, tb=32, policy="v3").compile()
    assert np.array_equal(l, solver.factor(a))
    # degenerate schedule feeds the single-device analytics directly
    rep = repro.volume_report(sched)
    assert rep["c2g_bytes"] == sched.loads_bytes()
    r = repro.simulate(sched, repro.HW["gh200"])
    assert r.h2d_bytes == sched.loads_bytes()


def test_degenerate_schedule_round_trip():
    pl = repro.plan(96, tb=32, policy="v3")
    m = pl.schedule
    assert isinstance(m, MultiDeviceSchedule) and m.ndev == 1
    s = m.to_single()
    assert s.ops == m.streams[0]
    assert s.hits == m.hits[0] and s.loads_bytes() == m.loads_bytes()
    assert MultiDeviceSchedule.from_single(s).digest() == m.digest()
    assert m.count(OpKind.BCAST) == 0
    m4 = repro.plan(96, tb=32, policy="v3", ndev=4).schedule
    with pytest.raises(ValueError, match="ndev=4"):
        m4.to_single()
    with pytest.raises(ValueError, match="ndev=4"):
        repro.simulate(m4, repro.HW["gh200"])


def test_plan_volume_and_simulate_dispatch():
    single = repro.plan(96, tb=32, policy="v3")
    multi = repro.plan(96, tb=32, policy="v3", ndev=2)
    assert "per_device" not in single.volume()
    assert len(multi.volume()["per_device"]) == 2
    hw = repro.HW["gh200"]
    assert hasattr(multi.simulate(hw), "compute_efficiency")
    assert hasattr(single.simulate(hw), "tflops")
