"""repro.obs: measured tracing, drift analysis, refine, metrics.

The load-bearing claims:

* a traced ``factor()`` records **exactly one span per executed op, in
  dispatch order**, on every executor — numpy and jax, single- and
  multi-device, spilled and in-core — and the traced result still
  matches dense LAPACK;
* the ``NullRecorder`` default is free: bit-identical output through
  the unchanged jitted path, ``jit_traces`` unmoved;
* ``drift_report`` aligns the measured trace positionally against the
  event simulator (and refuses misaligned or lossy inputs);
* ``tune.calibrate(refine_from=trace)`` returns a measured
  ``HardwareModel`` whose re-simulation predicts the same trace
  strictly better than the base model;
* the process-wide metrics registry absorbs counters and pull sources
  under one ``snapshot()`` / ``render_text()``.
"""
import json

import numpy as np
import pytest

import repro
from repro import CholeskyConfig
from repro.core import api
from repro.core.analytics import HW, simulate, simulate_multi
from repro.obs import (NULL, MODELED_KINDS, MetricsRegistry, NullRecorder,
                       TraceRecorder, chrome_trace_measured, drift_report,
                       total_abs_error, trace_view, write_jsonl)
from repro.tune import refine_from_trace

_N, _TB = 192, 48


def _spd(n=_N, seed=0):
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((n, n))
    return a @ a.T + n * np.eye(n)


def _jax_devices() -> int:
    import jax
    return jax.device_count()


def _factor_traced(cfg, a=None):
    a = _spd() if a is None else a
    plan = api.plan(a.shape[0], cfg)
    rec = TraceRecorder()
    l = plan.compile().factor(a, trace=rec)
    return plan, rec, l


# ---------------------------------------------------------------------------
# span contracts: one span per executed op, every executor
# ---------------------------------------------------------------------------

_NUMPY_VARIANTS = [
    ("numpy-single", CholeskyConfig(tb=_TB, policy="v3", backend="numpy")),
    ("numpy-spill", CholeskyConfig(tb=_TB, policy="v3", backend="numpy",
                                   host_slots=8)),
    ("numpy-ndev2", CholeskyConfig(tb=_TB, policy="v3", backend="numpy",
                                   ndev=2)),
    ("numpy-ndev2-spill", CholeskyConfig(tb=_TB, policy="v3",
                                         backend="numpy", ndev=2,
                                         host_slots=8)),
    ("numpy-ndev2-lookahead", CholeskyConfig(tb=_TB, policy="v3",
                                             backend="numpy", ndev=2,
                                             lookahead=1)),
]


@pytest.mark.parametrize("label,cfg", _NUMPY_VARIANTS,
                         ids=[v[0] for v in _NUMPY_VARIANTS])
def test_numpy_executors_one_span_per_op(label, cfg):
    a = _spd()
    plan, rec, l = _factor_traced(cfg, a)
    ops = (plan.single_schedule().ops if cfg.ndev == 1
           else [op for _, op in plan.schedule.iter_dispatch_order()])
    assert len(rec.spans) == len(ops)
    assert rec.dropped == 0
    # dispatch order, monotone indices, sane clocks
    assert [s.op_index for s in rec.spans] == list(range(len(ops)))
    assert all(s.t_end >= s.t_start for s in rec.spans)
    assert np.abs(l - np.linalg.cholesky(a)).max() < 1e-10


def test_jax_single_device_one_span_per_op():
    a = _spd()
    cfg = CholeskyConfig(tb=_TB, policy="v3", backend="jax")
    plan, rec, l = _factor_traced(cfg, a)
    ops = plan.single_schedule().ops
    assert len(rec.spans) == len(ops)
    assert [s.op_index for s in rec.spans] == list(range(len(ops)))
    # spans carry the op identity the drift report aligns on
    for s, op in zip(rec.spans, ops):
        assert s.kind == op.kind.value
    assert np.abs(l - np.linalg.cholesky(a)).max() < 1e-10
    # run metadata stamped for export/refine
    assert rec.meta["n"] == _N and rec.meta["tb"] == _TB
    assert rec.makespan_s() > 0


def test_jax_spill_one_span_per_op():
    a = _spd()
    cfg = CholeskyConfig(tb=_TB, policy="v3", backend="jax", host_slots=8)
    plan, rec, l = _factor_traced(cfg, a)
    ops = plan.single_schedule().ops
    assert len(rec.spans) == len(ops)
    kinds = {s.kind for s in rec.spans}
    assert "fetch" in kinds and "spill" in kinds
    assert np.abs(l - np.linalg.cholesky(a)).max() < 1e-10


@pytest.mark.skipif("_jax_devices() < 2",
                    reason="needs >= 2 jax devices (forced host devices)")
@pytest.mark.parametrize("lookahead", [0, 1])
def test_jax_multidevice_one_span_per_op(lookahead):
    a = _spd()
    cfg = CholeskyConfig(tb=_TB, policy="v3", backend="jax", ndev=2,
                         lookahead=lookahead or None)
    plan, rec, l = _factor_traced(cfg, a)
    ops = [op for _, op in plan.schedule.iter_dispatch_order()]
    assert len(rec.spans) == len(ops)
    assert rec.meta["ndev"] == 2
    assert rec.meta["lookahead"] == lookahead
    assert {s.device for s in rec.spans} == {0, 1}
    assert np.abs(l - np.linalg.cholesky(a)).max() < 1e-10


def test_null_recorder_is_free_and_bit_identical():
    a = _spd()
    solver = api.plan(_N, CholeskyConfig(tb=_TB, policy="v3",
                                         backend="jax")).compile()
    base = solver.factor(a)
    traces0 = solver.stats["jit_traces"]
    null = NullRecorder()
    again = solver.factor(a, trace=null)
    assert np.array_equal(base, again)          # bit-identical, same path
    assert solver.stats["jit_traces"] == traces0   # no retrace
    assert len(null.spans) == 0 and not null.active
    assert np.array_equal(solver.factor(a, trace=NULL), base)


def test_ring_buffer_overflow_counts_drops():
    a = _spd()
    rec = TraceRecorder(capacity=4)
    plan = api.plan(_N, CholeskyConfig(tb=_TB, policy="v3",
                                       backend="numpy"))
    plan.compile().factor(a, trace=rec)
    assert len(rec.spans) == 4
    assert rec.dropped == len(plan.single_schedule().ops) - 4
    # a lossy trace cannot be drift-analyzed — refuse, don't misalign
    with pytest.raises(ValueError, match="dropped"):
        drift_report(rec, plan.simulate(HW["a100-pcie"],
                                        record_timeline=True))


# ---------------------------------------------------------------------------
# drift: positional alignment against the simulator
# ---------------------------------------------------------------------------

def test_drift_report_aligns_and_summarizes():
    cfg = CholeskyConfig(tb=_TB, policy="v3", backend="jax")
    plan, rec, _ = _factor_traced(cfg)
    predicted = plan.simulate(HW["a100-pcie"], record_timeline=True)
    rep = drift_report(rec, predicted)
    assert rep.nops > 0
    assert set(rep.per_kind) <= MODELED_KINDS
    assert rep.per_kind["gemm"]["count"] > 0
    for stats in rep.per_kind.values():
        assert stats["measured_s"] > 0 and stats["predicted_s"] > 0
        assert stats["ratio"] == pytest.approx(
            stats["measured_s"] / stats["predicted_s"])
    assert rep.total_abs_error > 0
    assert rep.makespan_ratio == pytest.approx(
        rep.measured_makespan / rep.predicted_makespan)
    assert len(rep.top_mispredicted) > 0
    worst = rep.top_mispredicted[0]["abs_error_s"]
    assert all(e["abs_error_s"] <= worst for e in rep.top_mispredicted)
    # fenced per-op execution serializes the overlap by construction
    assert rep.measured_overlap_efficiency == pytest.approx(0.0, abs=0.05)
    assert "drift" in rep.summary()


def test_drift_refuses_misaligned_schedule():
    cfg = CholeskyConfig(tb=_TB, policy="v3", backend="numpy")
    _, rec, _ = _factor_traced(cfg)
    other = api.plan(_N, CholeskyConfig(tb=_TB, policy="sync",
                                        backend="numpy"))
    with pytest.raises(ValueError):
        drift_report(rec, other.simulate(HW["a100-pcie"],
                                         record_timeline=True))


def test_refine_from_trace_reduces_error():
    cfg = CholeskyConfig(tb=_TB, policy="v3", backend="jax")
    plan, rec, _ = _factor_traced(cfg)
    base = HW["a100-pcie"]
    err0 = total_abs_error(rec, plan.simulate(base, record_timeline=True))
    refined = repro.tune.calibrate(refine_from=rec)
    assert refined.source == "measured"
    err1 = total_abs_error(rec, plan.simulate(refined,
                                              record_timeline=True))
    assert err1 < err0
    # explicit base + name are honored
    named = refine_from_trace(rec, base=HW["h100-pcie"], name="this-box")
    assert named.name == "this-box" and named.source == "measured"
    # refuse traces that cannot parameterize a model
    with pytest.raises(ValueError, match="empty"):
        refine_from_trace(TraceRecorder())
    bare = TraceRecorder()
    bare.record(0, "gemm", 0, 0, 10**6, 0)
    with pytest.raises(ValueError, match="tb"):
        refine_from_trace(bare)


# ---------------------------------------------------------------------------
# export: chrome lanes + jsonl
# ---------------------------------------------------------------------------

def test_chrome_trace_measured_single_device(tmp_path):
    cfg = CholeskyConfig(tb=_TB, policy="v3", backend="jax", host_slots=8)
    _, rec, _ = _factor_traced(cfg)
    path = tmp_path / "run.trace.json"
    trace = chrome_trace_measured(rec, path)
    on_disk = json.loads(path.read_text())
    assert on_disk["traceEvents"] == trace["traceEvents"]
    lanes = {e["args"]["name"] for e in trace["traceEvents"]
             if e["ph"] == "M"}
    assert {"h2d", "cmp", "d2h", "dsk"} <= lanes
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert len(xs) == len(rec.spans)
    assert all(e["ts"] >= 0 and e["dur"] >= 0 for e in xs)
    with pytest.raises(ValueError, match="empty"):
        chrome_trace_measured(TraceRecorder())


def test_write_jsonl_round_trips(tmp_path):
    cfg = CholeskyConfig(tb=_TB, policy="v3", backend="numpy")
    _, rec, _ = _factor_traced(cfg)
    path = tmp_path / "run.jsonl"
    n = write_jsonl(rec, path)
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    header, rows = lines[0], lines[1:]
    assert header["event"] == "meta" and header["spans"] == n
    assert len(rows) == n == len(rec.spans)
    assert rows[0]["kind"] == rec.spans[0].kind


def test_trace_view_is_simulator_shaped():
    cfg = CholeskyConfig(tb=_TB, policy="v3", backend="numpy")
    _, rec, _ = _factor_traced(cfg)
    view = trace_view(rec)
    assert view.makespan == pytest.approx(rec.makespan_s())
    engines = {e for e, *_ in view.timeline}
    assert engines == {"h2d", "cmp", "d2h"}
    # rebased to t0, seconds
    assert min(s for _, s, *_ in view.timeline) == pytest.approx(0.0)
    assert view.tflops > 0


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_metrics_registry_counters_and_sources():
    reg = MetricsRegistry()
    reg.inc("x.calls")
    reg.inc("x.calls", 2)
    reg.set_gauge("x.depth", 7)
    reg.register_source("good", lambda: {"a": 1, "b": {"c": 2}})
    reg.register_source("bad", lambda: 1 / 0)
    snap = reg.snapshot()
    assert snap["counters"]["x.calls"] == 3
    assert snap["gauges"]["x.depth"] == 7
    assert snap["sources"]["good"] == {"a": 1, "b": {"c": 2}}
    assert "error" in snap["sources"]["bad"]
    text = reg.render_text()
    assert "x.calls 3" in text and "good.b.c 2" in text
    # fn-matched unregister: a stranger's fn does not evict the source
    reg.unregister_source("good", fn=lambda: None)
    assert "good" in reg.snapshot()["sources"]
    reg.unregister_source("good")
    reg.unregister_source("bad")
    assert reg.snapshot()["sources"] == {}


def test_global_registry_absorbs_solver_counters():
    """The host<->device byte counters count what a factor copied: the
    tile store each way on the jax backend, nothing on the NumPy one."""
    from repro import obs

    def counters():
        c = obs.snapshot()["counters"]
        return [c.get(f"repro.factor.{k}", 0)
                for k in ("calls", "h2d_bytes", "d2h_bytes")]

    calls, h2d, d2h = counters()
    solver = api.plan(_N, CholeskyConfig(tb=_TB, policy="v3",
                                         backend="jax")).compile()
    solver.factor(_spd())
    store = _N * _N * np.dtype(solver._executor.dtype).itemsize
    assert counters() == [calls + 1, h2d + store, d2h + store]
    text = obs.render_text()
    assert f"repro.factor.h2d_bytes {h2d + store:g}\n" in text

    api.plan(_N, CholeskyConfig(tb=_TB, policy="v3",
                                backend="numpy")).compile().factor(_spd())
    assert counters() == [calls + 2, h2d + store, d2h + store]
    snap = obs.snapshot()
    assert "plan_cache" in snap["sources"]
    assert "hits" in snap["sources"]["plan_cache"]
    assert "repro.factor.calls" in obs.render_text()


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_registry_counts_executed_disk_bytes(backend):
    """On a spill plan ``fetch_bytes``/``spill_bytes`` grow by what the
    executor moved, and no host<->device bytes are counted."""
    from repro import obs
    keys = ("fetch_bytes", "spill_bytes", "h2d_bytes", "d2h_bytes")

    def counters():
        c = obs.snapshot()["counters"]
        return [c.get(f"repro.factor.{k}", 0) for k in keys]

    before = counters()
    solver = api.plan(_N, CholeskyConfig(tb=_TB, policy="v3",
                                         backend=backend,
                                         host_slots=8)).compile()
    solver.factor(_spd())
    io = solver.stats["transfers"]
    assert io["fetched_bytes"] > 0 and io["spilled_bytes"] > 0
    assert counters() == [before[0] + io["fetched_bytes"],
                          before[1] + io["spilled_bytes"],
                          before[2], before[3]]


def test_serve_registers_metrics_source():
    from repro import obs
    from repro.serve import SolverService
    with SolverService(workers=1) as svc:
        assert "serve" in obs.snapshot()["sources"]
        snap = svc.metrics.snapshot()
        # empty window: percentiles read as "no data", not zero latency
        assert snap["latency_s"]["p50"] is None
        assert snap["latency_s"]["mean"] is None
    assert "serve" not in obs.snapshot()["sources"]


# ---------------------------------------------------------------------------
# stats unification
# ---------------------------------------------------------------------------

def test_stats_transfers_single_device():
    plan = api.plan(_N, CholeskyConfig(tb=_TB, policy="v3", backend="jax"))
    solver = plan.compile()
    solver.factor(_spd())
    t = solver.stats["transfers"]
    sched = plan.single_schedule()
    assert t["h2d_bytes"] == sched.loads_bytes()
    assert t["d2h_bytes"] == sched.stores_bytes()
    assert t["loads"] > 0 and t["stores"] > 0


def test_stats_transfers_spill_counters():
    cfg = CholeskyConfig(tb=_TB, policy="v3", backend="jax", host_slots=8)
    plan = api.plan(_N, cfg)
    solver = plan.compile()
    solver.factor(_spd())
    t = solver.stats["transfers"]
    assert t["scheduled_fetch_bytes"] == plan.schedule.fetch_bytes()
    assert t["scheduled_spill_bytes"] == plan.schedule.spill_bytes()
    # executed counters folded in from the spill executor
    assert t["fetched_bytes"] == plan.schedule.fetch_bytes()
    assert t["spilled_bytes"] == plan.schedule.spill_bytes()
    assert t["fetch_ops"] > 0 and t["spill_ops"] > 0


def test_stats_transfers_multidevice_numpy_spill():
    cfg = CholeskyConfig(tb=_TB, policy="v3", backend="numpy", ndev=2,
                         host_slots=8)
    plan = api.plan(_N, cfg)
    solver = plan.compile()
    solver.factor(_spd())
    t = solver.stats["transfers"]
    assert t["fetched_bytes"] == plan.schedule.fetch_bytes()
    assert t["spilled_bytes"] == plan.schedule.spill_bytes()
    assert t["bcast_bytes"] == plan.schedule.bcast_bytes()
