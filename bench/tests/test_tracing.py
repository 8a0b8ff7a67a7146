"""The trace reduction, on hand-made intervals and on a trace recorded on
the CPU."""
import time

import pytest

from bench import tracing
from bench.tracing import Trace

W, F = "bench.window", "bench.factor"


def _hand_trace(devices=1):
    ops = {"/device:TPU:0": [(0, 10, "a"), (5, 15, "b"), (20, 30, "a"),
                             (50, 60, "c")]}
    if devices == 2:
        ops["/device:TPU:1"] = [(0, 100, "x")]
    return Trace(ops, [(0, 100, W), (2, 40, F), (45, 70, F)])


def test_union_and_busy_by_hand():
    tr = _hand_trace()
    assert tracing.union(tr.ops["/device:TPU:0"]) == [(0, 15), (20, 30),
                                                      (50, 60)]
    assert tracing.busy_ns(tr, 0, 100) == 35
    assert tracing.busy_ns(tr, 2, 40) == 23
    assert tracing.busy_ns(tr, 45, 70) == 10
    # averaged over the chips used
    assert tracing.busy_ns(_hand_trace(2), 0, 100) == (35 + 100) / 2
    assert tr.window() == (0, 100)
    assert tr.span_list(F) == [(2, 40), (45, 70)]


def test_top_ops_by_hand():
    tr = _hand_trace()
    assert tracing.top_ops(tr, 0, 100) == [["a", 20e-9], ["b", 10e-9],
                                           ["c", 10e-9]]
    assert tracing.top_ops(tr, 0, 100, k=1) == [["a", 20e-9]]


def test_idle_gaps_by_hand():
    assert tracing.idle_gaps(_hand_trace(), 0, 100) == [
        ["window.between_calls", 30e-9],
        ["factor.after_last_op", 10e-9],
        ["factor.after_last_op", 10e-9],
        ["factor.between_ops", 5e-9],
        ["window.between_calls", 5e-9],
        ["factor.before_first_op", 5e-9],
    ]
    tr = Trace({"/device:TPU:0": [(0, 1, "a")]}, [(0, 10, W), (2, 6, F)])
    assert tracing.idle_gaps(tr, 0, 10) == [
        ["factor.no_device_op", 4e-9], ["window.between_calls", 4e-9],
        ["window.between_calls", 1e-9]]


def test_a_trace_recorded_on_the_cpu(tmp_path):
    import jax
    import jax.numpy as jnp

    mm = jax.jit(lambda x: x @ x)
    x = jnp.ones((256, 256))
    mm(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(W):
        for _ in range(3):
            with jax.profiler.TraceAnnotation(F):
                mm(x).block_until_ready()
            time.sleep(0.05)
    jax.profiler.stop_trace()

    path = tracing.latest_xplane(str(tmp_path))
    with pytest.raises(ValueError, match="no XLA Ops"):
        tracing.load(path)
    tr = tracing.load(path, host_ops=True)
    lo, hi = tr.window()
    assert hi - lo >= 0.15e9
    spans = tr.span_list(F)
    assert len(spans) == 3
    dots = [iv for v in tr.ops.values() for iv in v if "dot" in iv[2]]
    assert len(dots) == 3                 # one matmul per call
    for s, e, _ in dots:                  # each inside its call's span
        assert any(a <= s and e <= b for a, b in spans)
    busy = tracing.busy_ns(tr, lo, hi)
    assert 0 < busy == pytest.approx(
        sum(tracing.busy_ns(tr, a, b) for a, b in spans))
    label, seconds = tracing.idle_gaps(tr, lo, hi, k=1)[0]
    assert label == "window.between_calls" and seconds >= 0.05
    assert any("dot" in name for name, _ in tracing.top_ops(tr, lo, hi))


def test_a_traced_run_reports_every_per_layer_metric(monkeypatch, cell_of):
    from bench import run, work
    table = work.peaks("TPU v5 lite")
    monkeypatch.setattr(work, "peaks", lambda kind: table)
    cell = cell_of("matern24k-f32", 1024, 256)
    result = run.run_cell(cell, 12, 0.3, True, require_tpu=False)
    assert result["correct"]
    assert set(result["metrics"]) == {m["name"] for m in cell["per_layer"]}
    assert 0 < result["metrics"]["kernel_roofline.factor"]["value"] <= 100
    assert 0 < result["device"]["busy_s"] < result["device"]["window_s"]
    assert list(result)[-1] == "checks"
    for key in ("device_ops", "idle_gaps"):
        assert 0 < len(result["breakdown"][key]) <= 10
