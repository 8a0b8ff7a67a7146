"""Run one benchmark cell once on the chip it asks for.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is ``<config>.<traffic>`` from ``BENCHMARK.json``.  Set-up builds
the configuration's matrix from the seed, plans and compiles the solver
through ``repro.plan(n, cfg).compile()`` and runs one step of the
traffic as warm-up, at a nugget that no window step uses.  The window
then runs the traffic's steps for ``--seconds`` (``bench/loop.py`` reads
the mix from ``bench/traffic/<mix>.json``).  After the window the last
factor solves ``check_rhs`` right-hand sides from the seed, and the
normwise backward error of those and of every solve of the window,
each against the f64 matrix of the step that made its factor, is held to
the cell's limit (``bench/limits/<cell>.json``).

``--trace 1`` runs the profiler over the window and reports the cell's
per-layer metrics (``bench/metrics/<metric>.py``) instead of its
end-to-end ones.  The last line of standard output is one JSON object;
the last lines of standard error are the numbers compared, each with its
limit.  Without a TPU, with fewer chips than the cell asks for, or with
Pallas in interpret mode, the run exits non-zero and prints no result.
JAX keeps its compile cache where ``JAX_COMPILATION_CACHE_DIR`` says,
else in ``<checkout>/.bench_cache/jax``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import cell as cells  # noqa: E402
from bench import checks, loop, tracing  # noqa: E402

CACHE = ROOT / ".bench_cache"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class NoChip(RuntimeError):
    """JAX sees no TPU, too few of them, or would interpret Pallas."""


def devices_for(chips: int, require_tpu: bool):
    import jax
    from repro.kernels import pallas_interpret
    devs = jax.devices()
    if require_tpu:
        if devs[0].platform != "tpu":
            raise NoChip(f"no TPU: JAX sees {len(devs)} "
                         f"{devs[0].platform} device(s)")
        if len(devs) < chips:
            raise NoChip(f"the cell needs {chips} TPUs, JAX sees "
                         f"{len(devs)}")
        if pallas_interpret():
            raise NoChip("Pallas would run in interpret mode")
    return devs[:chips]


def use_compile_cache() -> None:
    """JAX's persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` where
    it is set, else a fixed directory in the checkout.  Every program is
    kept, and with no size cap: one cell's unrolled factor program is an
    executable of 370 MB, over the 200 MB cap some hosts set."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def read_metric(name: str, ctx: dict):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, t_start: float | None = None) -> dict:
    """One run of ``cell``; returns the result object."""
    import jax
    config, traffic = cell["config"], cell["traffic"]
    loop.validate(traffic)
    t_start = time.perf_counter() if t_start is None else t_start
    jax.config.update("jax_enable_x64", bool(config["x64"]))
    devices = devices_for(cell["chips"], require_tpu)
    clock = checks.CompileClock()

    t0 = time.perf_counter()
    start_s = t0 - t_start
    a = cells.build_matrix(config, seed)
    input_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    solver = cells.make_solver(config)
    plan_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    driver = loop.Driver(solver, a, config, traffic, seed)
    del solver
    warm_ok = driver.warm_up()
    warmup_s = time.perf_counter() - t0
    setup_s = time.perf_counter() - t_start
    secs, counts = clock.snapshot()
    log(f"[setup] {setup_s:.3f} s: start-up {start_s:.3f} s, input "
        f"{input_s:.3f} s, plan "
        f"{plan_s:.3f} s, warm-up step {warmup_s:.3f} s; within it jaxpr "
        f"trace {secs.get('/jax/core/compile/jaxpr_trace_duration', 0):.3f}"
        f" s, lowering "
        f"{secs.get('/jax/core/compile/jaxpr_to_mlir_module_duration', 0):.3f}"
        f" s, backend compile or cache load "
        f"{secs.get(checks.BACKEND_COMPILE, 0):.3f} s "
        f"({counts.get(checks.BACKEND_COMPILE, 0)} programs, "
        f"{counts.get(checks.CACHE_MISS, 0)} cache misses)")

    trace_dir = CACHE / "trace" / cell["name"]
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    snap = clock.snapshot()
    try:
        w = loop.window(driver, seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    compiled = clock.compiles_since(snap)
    log(f"[window] {w['steps']} steps of "
        f"{[c['op'] for c in traffic['step']]} in {w['elapsed']:.3f} s, "
        f"{w['failed']} failed")
    if compiled:
        raise RuntimeError(f"{compiled} programs compiled or loaded inside "
                           f"the window; set-up has to warm up every shape")
    # the CPU of a rehearsal reports no memory_stats
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    if require_tpu and peak <= 0:
        raise RuntimeError("the chip reports no peak_bytes_in_use")

    answers = driver.finish()
    gc.collect()
    err = loop.judge(a, answers)
    limit = cell["limits"]["backward_error"]
    correct = bool(warm_ok and w["failed"] == 0 and np.isfinite(err)
                   and err <= limit)

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    # the check judges the last factor and every solve; a step that gives
    # no finite log-determinant or solve has failed on its own
    result = {"correct": correct, "attempted": w["steps"],
              "failed": max(w["failed"], 0 if correct else 1)}
    if trace:
        tr = tracing.load(tracing.latest_xplane(str(trace_dir)),
                          host_ops=not require_tpu)
        lo, hi = tr.window()
        device["busy_s"] = tracing.busy_ns(tr, lo, hi) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        ctx = {"trace": tr, "config": config,
               "classes": cells.classes_as_run(config),
               "device_kind": devices[0].device_kind}
        values = {m["name"]: read_metric(m["name"], ctx)
                  for m in cell["per_layer"]}
        shown = cell["per_layer"]
    else:
        values = {name: w["stats"][stat]
                  for name, stat in traffic["metrics"].items()}
        values.update(peak_hbm_gb=peak / 1e9, setup_s=setup_s)
        shown = cell["end_to_end"]
        missing = {m["name"] for m in shown} - set(values)
        if missing:
            raise KeyError(f"traffic {cell['name']} gives no {missing}")
    # a reader that finds nothing to read returns None: left out
    result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                     "unit": m["unit"]}
                         for m in shown if values[m["name"]] is not None}
    result["device"] = device
    if trace:
        result["breakdown"] = {"device_ops": tracing.top_ops(tr, lo, hi),
                               "idle_gaps": tracing.idle_gaps(tr, lo, hi)}
    result["checks"] = {"backward_error": {"value": err, "limit": limit}}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        log(f"no system under test: {ROOT / 'src' / 'repro'} is missing")
        return 2
    cell = cells.load(args.workload)
    use_compile_cache()
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          t_start=T_START)
    except NoChip as e:
        log(f"FAIL: {e}")
        return 3
    print(json.dumps(result), flush=True)
    for name, c in result["checks"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
