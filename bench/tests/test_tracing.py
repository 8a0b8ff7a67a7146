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
    assert tracing.busy_ns(tr, 0, 100, 1) == 35
    assert tracing.busy_ns(tr, 2, 40, 1) == 23
    assert tracing.busy_ns(tr, 45, 70, 1) == 10
    # averaged over the chips used
    assert tracing.busy_ns(_hand_trace(2), 0, 100, 2) == (35 + 100) / 2
    assert tr.window() == (0, 100)
    assert tr.span_list(F) == [(2, 40), (45, 70)]


def test_top_ops_by_hand():
    tr = _hand_trace()
    assert tracing.top_ops(tr, 0, 100, 1) == [["a", 20e-9], ["b", 10e-9],
                                              ["c", 10e-9]]
    assert tracing.top_ops(tr, 0, 100, 1, k=1) == [["a", 20e-9]]
    # averaged over the chips used; a plane of an unused device is left out
    assert tracing.top_ops(_hand_trace(2), 0, 100, 2, k=2) == [
        ["x", 50e-9], ["a", 10e-9]]
    assert tracing.top_ops(_hand_trace(2), 0, 100, 1) == tracing.top_ops(
        tr, 0, 100, 1)


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


# -- the per-layer readers of the device trace on one chip and on four ----

LEAST = 7.0e-9      # a least time, in seconds, that the readers are given


def _ctx(monkeypatch, tr, chips):
    from bench import work
    monkeypatch.setattr(work, "least_seconds", lambda *a: LEAST)
    return {"trace": tr, "chips": chips, "config": {"n": 512, "tb": 128},
            "classes": None, "device_kind": "TPU v5 lite"}


def _read(name, ctx):
    from bench import run
    return run.read_metric(name, ctx)


def _chips_trace(planes):
    """A window of 100 ns holding two factors, [10, 50] and [60, 90], and
    the given ``{device: ops}``."""
    return Trace(planes, [(0, 100, W), (10, 50, F), (60, 90, F)])


def test_readers_on_four_chips_count_chip_time(monkeypatch):
    # each chip busy 8 ns in each factor, at its own times
    planes = {f"/device:TPU:{d}": [(12 + 2 * d, 20 + 2 * d, "gemm"),
                                   (62 + 2 * d, 70 + 2 * d, "gemm")]
              for d in range(4)}
    ctx = _ctx(monkeypatch, _chips_trace(planes), 4)
    b = 8e-9                            # one chip's busy seconds a factor
    # least time over the chip-seconds spent: least / (4 b); the reader
    # that took the busy time averaged over chips gave least / b
    assert _read("kernel_roofline.factor", ctx) == pytest.approx(
        100 * LEAST / (4 * b))
    # the host's time is what no chip covers: [12, 26] of [10, 50]
    assert _read("host_s.factor", ctx) == pytest.approx(
        ((40 - 14) + (30 - 14)) / 2 * 1e-9)
    assert _read("device_idle.factor", ctx) == pytest.approx(100 - 16)
    assert tracing.busy_ns(ctx["trace"], 0, 100, 4) == 16


def test_a_used_chip_with_no_op_line_counts_as_idle(monkeypatch):
    planes = {f"/device:TPU:{d}": [(10, 50, "gemm")] for d in range(3)}
    ctx = _ctx(monkeypatch, _chips_trace(planes), 4)
    assert _read("device_idle.factor", ctx) == pytest.approx(100 - 30)
    assert _read("kernel_roofline.factor", ctx) == pytest.approx(
        100 * LEAST * 2 / 120e-9)
    assert tracing.top_ops(ctx["trace"], 0, 100, 4) == [["gemm", 30e-9]]
    # none of the four busy: nothing to read a roofline from
    ctx = _ctx(monkeypatch, _chips_trace({}), 4)
    assert _read("kernel_roofline.factor", ctx) is None
    assert _read("device_idle.factor", ctx) == 100


def test_a_plane_of_an_unused_device_changes_no_reading(monkeypatch):
    one = {"/device:TPU:0": [(12, 20, "gemm"), (62, 75, "trsm")]}
    more = dict(one, **{"/device:TPU:1": [(0, 100, "x")],
                        "/device:TPU:0 SparseCore 0": [(0, 100, "y")]})
    names = ("kernel_roofline.factor", "host_s.factor",
             "device_idle.factor")
    got = [[_read(m, _ctx(monkeypatch, _chips_trace(p), 1)) for m in names]
           for p in (one, more)]
    assert got[0] == got[1]
    assert got[0][1] == pytest.approx(((40 - 8) + (30 - 13)) / 2 * 1e-9)


def _old_busy_ns(tr, lo, hi):
    """The busy time before chips were counted: averaged over the planes
    with an op line."""
    return sum(tracing.covered(tracing.union(v), lo, hi)
               for v in tr.ops.values()) / len(tr.ops)


def test_one_chip_reads_as_before(monkeypatch):
    """On one plane the readers give the floats the readers gave before
    the chip count reached them."""
    import random
    rng = random.Random(5)
    ops, t = [], 0.0
    for _ in range(400):
        t += rng.uniform(0, 3e5) + 0.123
        d = rng.uniform(1, 4e5) + 0.371
        ops.append((t, t + d, rng.choice("abc")))
    spans = sorted((a, a + rng.uniform(2e6, 4e6) + 0.5, F)
                   for a in (1.1e6, 6.3e6, 11.7e6, 17.9e6))
    tr = Trace({"/device:TPU:0": sorted(ops)},
               [(0.25, t + 1e6 + 0.75, W)] + spans)
    ctx = _ctx(monkeypatch, tr, 1)
    lo, hi = tr.window()
    fs = tr.span_list(F)
    old = {
        "kernel_roofline.factor": 100.0 * LEAST * len(fs) / (
            sum(_old_busy_ns(tr, s, e) for s, e in fs) / 1e9),
        "host_s.factor": sum((e - s) - _old_busy_ns(tr, s, e)
                             for s, e in fs) / len(fs) / 1e9,
        "device_idle.factor": 100.0 * (1.0 - _old_busy_ns(tr, lo, hi)
                                       / (hi - lo)),
    }
    assert {m: _read(m, ctx) for m in old} == old
    assert tracing.busy_ns(tr, lo, hi, 1) == _old_busy_ns(tr, lo, hi)


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
    busy = tracing.busy_ns(tr, lo, hi, 1)
    assert 0 < busy == pytest.approx(
        sum(tracing.busy_ns(tr, a, b, 1) for a, b in spans))
    label, seconds = tracing.idle_gaps(tr, lo, hi, k=1)[0]
    assert label == "window.between_calls" and seconds >= 0.05
    assert any("dot" in name for name, _ in tracing.top_ops(tr, lo, hi, 1))


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
    assert (result["device"]["count"], result["device"]["ndev"],
            result["device"]["grid"]) == (1, 1, [1, 1])
    for key in ("device_ops", "idle_gaps"):
        assert 0 < len(result["breakdown"][key]) <= 10
