"""The reader of the program's spans and scopes, and the five metrics
that read it, on hand-made spans and on a trace recorded on the CPU."""
import importlib.util

import numpy as np
import pytest

from bench import spans, tracing
from bench.spans import Spans
from bench.tracing import Trace

W, F = "bench.window", "bench.factor"
R = "repro.factor"
METRICS = ("stage_in_s.factor", "h2d_gbs.factor", "d2h_gbs.factor",
           "stage_out_s.factor", "round_s.factor")


def _metric(name, ctx):
    path = spans.ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"m_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def _factor(t0, call, scale=1):
    """One ``repro.factor`` span at ``t0`` with its six phases (ns)."""
    out, t = [(t0, t0 + 100 * scale, R, {"call": call})], t0
    for ph, ns, nbytes in (("tile", 10, 800), ("cast", 20, 400),
                           ("h2d", 4, 400), ("run", 30, None),
                           ("d2h", 8, 400), ("widen", 25, 800)):
        args = {} if nbytes is None else {"bytes": nbytes}
        out.append((t, t + ns * scale, f"{R}.{ph}", args))
        t += ns * scale
    return out


def _hand(monkeypatch, sp):
    monkeypatch.setattr(spans, "current", lambda: sp)
    return {"trace": Trace({}, [(0, 1000, W)])}


def test_metrics_on_hand_made_spans(monkeypatch):
    ops = {"/device:TPU:0": [(120, 130, "%convert.1 = bf16[2]", "round.bf16"),
                             (125, 140, "%fusion.2 = f32[2]", "store.bf16"),
                             (150, 160, "%copy.3 = f32[2]", None),
                             (320, 340, "%convert.4 = bf16[2]", "round.bf16"),
                             (2000, 2100, "%convert.5", "round.bf16")]}
    sp = Spans(_factor(100, 1) + _factor(300, 2) + _factor(3000, 3),
               ops, True)
    ctx = _hand(monkeypatch, sp)
    got = {m: _metric(m, ctx) for m in METRICS}
    # the third factor lies outside the window
    assert got["stage_in_s.factor"] == pytest.approx(30e-9)
    assert got["stage_out_s.factor"] == pytest.approx(25e-9)
    assert got["h2d_gbs.factor"] == pytest.approx(800 / 8)
    assert got["d2h_gbs.factor"] == pytest.approx(800 / 16)
    # round ops inside the factors: 10 ns and 20 ns over two factors
    assert got["round_s.factor"] == pytest.approx(15e-9)
    # the breakdown cuts overlaps, so the parts add up to the busy time
    parts = spans.scope_breakdown(sp, 0, 1000)
    assert parts == pytest.approx({"round.bf16": 30e-9, "store.bf16": 10e-9,
                                   "unscoped:copy.3": 10e-9})


def test_metrics_read_nothing_without_the_programs_spans(monkeypatch):
    # the parent program: no repro. spans, no scopes
    ops = {"/device:TPU:0": [(120, 130, "%convert.1", None)]}
    ctx = _hand(monkeypatch, Spans([], ops, False))
    assert all(_metric(m, ctx) is None for m in METRICS)
    ctx = _hand(monkeypatch, None)
    assert all(_metric(m, ctx) is None for m in METRICS)
    # spans but no scope on any op: the rounding cannot be told apart
    ctx = _hand(monkeypatch, Spans(_factor(100, 1), ops, False))
    assert _metric("round_s.factor", ctx) is None
    assert _metric("stage_in_s.factor", ctx) == pytest.approx(30e-9)


def test_a_factor_recorded_on_the_cpu(tmp_path):
    import jax
    import repro
    from repro.core.tiling import random_spd

    n, tb = 256, 64
    a = random_spd(n, seed=1)
    solver = repro.plan(n, repro.CholeskyConfig(
        tb=tb, policy="v3", backend="jax")).compile()
    solver.factor(a, materialize=False)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(W):
        for _ in range(2):
            with jax.profiler.TraceAnnotation(F):
                solver.factor(a, materialize=False)
    jax.profiler.stop_trace()

    path = spans.latest_xplane(tmp_path)
    sp = spans.load(path)
    calls = sp.named(R)
    assert [c[2]["call"] for c in calls] == [2, 3]
    store = n * n * np.dtype(solver._executor.dtype).itemsize
    for s, e, _ in calls:
        got = [(n_, a_.get("bytes")) for s_, e_, n_, a_ in sp.spans
               if n_.startswith(R + ".") and s <= s_ and e_ <= e]
        assert got == [(f"{R}.tile", 8 * n * n), (f"{R}.cast", store),
                       (f"{R}.h2d", store), (f"{R}.run", None),
                       (f"{R}.d2h", store), (f"{R}.widen", 8 * n * n)]
    # a CPU trace keeps the scopes in the HLO of /host:metadata
    assert sp.scoped
    parts = spans.scope_breakdown(sp, calls[0][0], calls[-1][1])
    scoped = [k.split(".") for k in parts if not k.startswith("unscoped:")]
    # every tile op kind, all of the one class of a uniform plan
    assert {k for k, _ in scoped} == {"potrf", "trsm", "syrk", "gemm",
                                      "load", "store"}
    assert len({c for _, c in scoped}) == 1
    busy = sum(tracing.covered(tracing.union((s, e) for s, e, _, _ in ops),
                               calls[0][0], calls[-1][1])
               for ops in sp.ops.values()) / len(sp.ops) / 1e9
    assert sum(parts.values()) == pytest.approx(busy)

    # the report: per bench.factor, the phases cover the span, and the
    # split by scope adds up to its busy time
    rep = spans.report(path, host_ops=True)
    assert len(rep["factors"]) == 2
    for f in rep["factors"]:
        assert [p[0] for p in f["phases"]] == ["tile", "cast", "h2d", "run",
                                              "d2h", "widen"]
        assert 0.5 < f["covered"] <= 1
        assert f["by_scope_sum_s"] == pytest.approx(f["busy_s"])

    ctx = {"trace": tracing.load(path, host_ops=True)}
    spans._LOADED.clear()
    spans.TRACES, old = tmp_path, spans.TRACES
    try:
        got = {m: _metric(m, ctx) for m in METRICS}
    finally:
        spans.TRACES = old
        spans._LOADED.clear()
    # uniform f32 tiles never round
    assert got["round_s.factor"] == 0.0
    for m in METRICS[:4]:
        assert got[m] > 0


# -- a TPU-style trace, written by hand: the scope sits in the tf_op stat
# of each op's event metadata on the /device: plane --------------------

def _key(num, wire):
    return _uvarint(num << 3 | wire)


def _uvarint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _msg(*parts):
    """A message of ``(field, value)``: an int as a varint, else bytes."""
    out = b""
    for num, v in parts:
        if isinstance(v, int):
            out += _key(num, 0) + _uvarint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _key(num, 2) + _uvarint(len(v)) + v
    return out


def test_device_scopes_from_event_metadata(tmp_path):
    tf_op, ref = 1, 2                   # stat metadata ids
    path = "jit(traced)/store.bf16/round.bf16/convert_element_type:"
    ops = [  # (metadata id, name, stat of the scope or None, offset ps)
        (10, "%convert.1 = bf16[2] convert(f32[2] %p)", (5, path), 0),
        (11, "%fusion.2 = f32[2] fusion(f32[2] %q)", (7, 3), 5000),
        (12, "%copy.3 = f32[2] copy(f32[2] %r)", None, 9000)]
    metas = []
    for mid, name, stat, _ in ops:
        stats = [] if stat is None else [(5, _msg((1, tf_op), stat))]
        metas.append((4, _msg((1, mid), (2, _msg((1, mid), (2, name),
                                                 *stats)))))
    stat_names = [(5, _msg((1, i), (2, _msg((1, i), (2, n)))))
                  for i, n in ((tf_op, "tf_op"), (ref, "ignored"),
                               (3, "jit(traced)/gemm.f32/dot_general:"))]
    events = [(4, _msg((1, mid), (2, off), (3, 2000)))
              for mid, _, _, off in ops]
    line = _msg((1, 1), (2, "XLA Ops"), (3, 1000), *events)
    plane = _msg((1, 1), (2, "/device:TPU:0"), (3, line), *metas,
                 *stat_names)
    (tmp_path / "t.xplane.pb").write_bytes(_msg((1, plane)))

    sp = spans.load(str(tmp_path / "t.xplane.pb"))
    assert sp.scoped and sp.spans == []
    got = [(s, e, spans.hlo_name(n), scope)
           for s, e, n, scope in sp.ops["/device:TPU:0"]]
    assert got == [(1000, 1002, "convert.1", "round.bf16"),
                   (1005, 1007, "fusion.2", "gemm.f32"),
                   (1009, 1011, "copy.3", None)]
    assert spans.scope_breakdown(sp, 0, 2000) == pytest.approx(
        {"round.bf16": 2e-9, "gemm.f32": 2e-9, "unscoped:copy.3": 2e-9})


def test_hlo_op_names_give_a_hoisted_convert_its_scope():
    """XLA converts the whole store once for several fusions that each
    slice a tile of it, and leaves the convert without an op name: it
    takes the scope of the converts inside the fusions that read it."""
    def inst(name, opcode, iid, operands=(), calls=(), op_name=""):
        parts = [(1, name), (2, opcode), (35, iid)]
        parts += [(36, o) for o in operands] + [(38, c) for c in calls]
        if op_name:
            parts.append((7, _msg((2, op_name))))
        return (2, _msg(*parts))

    rnd = "jit(traced)/load.bf16/round.bf16/convert_element_type"
    fused = _msg((1, "fused"), (5, 10),
                 inst("param.1", "parameter", 1),
                 inst("slice.1", "slice", 2, [1],
                      op_name="jit(traced)/load.bf16/slice"),
                 inst("convert.2", "convert", 3, [2], op_name=rnd))
    entry = _msg((1, "main"), (5, 1),
                 inst("store", "parameter", 1),
                 inst("convert.1", "convert", 2, [1]),
                 inst("fusion.1", "fusion", 3, [2], [10],
                      op_name="jit(traced)/load.bf16/scatter"),
                 inst("fusion.2", "fusion", 4, [2], [10],
                      op_name="jit(traced)/load.bf16/scatter"),
                 inst("copy.1", "copy", 5, [1]),
                 inst("fusion.3", "fusion", 6, [5], [10],
                      op_name="jit(traced)/gemm.f32/dot_general"))
    names = spans.hlo_op_names(
        _msg((1, _msg((1, "jit_traced"), (3, entry), (3, fused)))))
    assert names["convert.1"] == rnd
    assert spans.parse_scope(names["convert.1"]) == "round.bf16"
    # a copy whose users hold no copy keeps no op name
    assert "copy.1" not in names and "store" not in names
    assert names["fusion.3"] == "jit(traced)/gemm.f32/dot_general"


def test_a_traced_mxp_run_reports_its_metrics(monkeypatch, cell_of):
    """A CPU rehearsal of the MxP cell's traced run reports every
    per-layer metric listed for it, the rounding time among them."""
    from bench import run, work
    table = work.peaks("TPU v5 lite")
    monkeypatch.setattr(work, "peaks", lambda kind: table)
    spans._LOADED.clear()
    cell = cell_of("matern24k-mxp", 1024, 128)
    assert {c for row in cell["config"]["precision"]["classes"]
            for c in row} & {"bf16", "f8e4m3"}
    result = run.run_cell(cell, 7, 0.3, True, require_tpu=False)
    assert result["correct"]
    got = result["metrics"]
    assert set(got) == {m["name"] for m in cell["per_layer"]}
    assert set(METRICS) <= set(got)
    assert got["round_s.factor"]["value"] >= 0
    assert got["h2d_gbs.factor"]["value"] > 0
