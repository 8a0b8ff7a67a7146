"""Smoke run of the solver's main path on a TPU, through its public API.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # a four-chip host: the (2, 2) grid only

One chip runs three phases, each checked against a stated bound:

* compiled kernels: ``use_pallas=True`` and ``fuse_columns=True`` at
  n=4096, tb=512 against LAPACK and the stock-XLA factor;
* main path, f32: ``repro.plan(n, cfg).compile()``, ``factor`` (cold, with
  the compile, then warm) and ``solve`` at n=24576, tb=2048, policy v3 —
  a 2.4 GB f32 tile store in HBM;
* mixed precision: a Matérn covariance at the same n, ``eps_target`` on
  the ``tpu`` ladder, ``specialize``, ``factor``, ``solve``.

``--chips 4`` runs only the four-device ``(2, 2)`` grid at n=8192,
tb=2048: solve backward error, executed-vs-scheduled transfers, each
device's HBM, and the factor against LAPACK.

The script runs in one process and starts none.  It exits non-zero, and
prints no result line, when JAX sees no TPU or any check fails.  The last
line of standard output is ``{"ok": true, "device": {...}}``.  JAX keeps
its compile cache where ``JAX_COMPILATION_CACHE_DIR`` says, and otherwise
in ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import repro  # noqa: E402
from repro.core.analytics import crosscheck_executed_volume  # noqa: E402
from repro.core.precision import uniform_plan  # noqa: E402
from repro.geo.matern import (BETA_MEDIUM, generate_locations,  # noqa: E402
                              matern_covariance)
from repro.kernels import pallas_interpret  # noqa: E402
from repro.kernels.fused_column import (launch_counts,  # noqa: E402
                                        reset_launch_counts)

SEED = 0
# The executor unrolls every tile op into one program, and each f32
# (HIGHEST) tile GEMM at tb=2048 costs about a second of compile: nt=16
# (n=32768) compiled in 700 s on an 8-core host, so two such programs
# overrun a 20-minute smoke run.  nt=12 is the largest that fits both f32
# phases (226 s and 197 s of compile on a v5e host).
N_MAIN, TB_MAIN = 24576, 2048
N_KERNELS, TB_KERNELS = 4096, 512
# The four-chip grid compiles one program per dispatch chunk: on an 8-core
# host 75 of them at nt=16 took 1133 s to compile, 15 at nt=4 took 127 s.
N_GRID, TB_GRID = 8192, 2048
NRHS = 4
U32 = float(np.finfo(np.float32).eps) / 2      # f32 unit round-off

# Normwise backward error of an f32 factor + f64 substitution: the
# factorization's round-off grows like sqrt(n) u for random rounding
# errors; 16 sqrt(n) u is 1.5e-4 at n=24576.  One f32 matmul at the TPU's
# default precision (a single bf16 pass) is already off by 2.3e-3.
def f32_backward_bound(n: int) -> float:
    return 16.0 * np.sqrt(n) * U32


# Largest |L - L_lapack| / |L_lapack| of an f32 factor of the test matrix
# (kappa <= 3, see device_spd): the forward error is bounded by kappa
# times the backward error.
def f32_factor_bound(n: int) -> float:
    return 4.0 * f32_backward_bound(n)


# Matérn deployment of the mixed-precision phase: exponential kernel
# (nu=0.5) at the paper's medium range, with a nugget (measurement-noise
# variance) of 0.1 of the sill, which bounds kappa near 1e4 so that an f32
# factor exists at n=24576.
MATERN_NUGGET = 0.1
EPS_MXP = 1e-4

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Sums the backend compile seconds JAX reports while it is active."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.seconds += duration

    def take(self) -> float:
        s, self.seconds = self.seconds, 0.0
        return s


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    print(f"FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def device_spd(n: int, seed: int, device) -> np.ndarray:
    """SPD test matrix ``G G^T / n + 2 I`` (eigenvalues in [2, 6]), built
    on ``device`` from a seed and returned to the host as f32."""
    @jax.jit
    def build(key):
        g = jax.random.normal(key, (n, n), jnp.float32) / np.sqrt(n)
        a = jnp.matmul(g, g.T, precision=jax.lax.Precision.HIGHEST)
        return 0.5 * (a + a.T) + 2.0 * jnp.eye(n, dtype=jnp.float32)

    return np.asarray(build(jax.device_put(jax.random.key(seed), device)))


def rhs(n: int, seed: int) -> np.ndarray:
    return np.random.default_rng(seed).standard_normal((n, NRHS))


def backward_error(a: np.ndarray, x: np.ndarray, b: np.ndarray) -> float:
    """Largest normwise backward error over the columns,
    ``||b - A x|| / (||A|| ||x|| + ||b||)`` in the infinity norm; ``A`` is
    read in f64 row blocks so no f64 copy of it is made."""
    r = np.empty_like(b)
    a_norm = 0.0
    step = 1024
    for r0 in range(0, a.shape[0], step):
        blk = np.asarray(a[r0:r0 + step], dtype=np.float64)
        r[r0:r0 + step] = b[r0:r0 + step] - blk @ x
        a_norm = max(a_norm, float(np.abs(blk).sum(axis=1).max()))
    err = np.abs(r).max(axis=0) / (a_norm * np.abs(x).max(axis=0)
                                   + np.abs(b).max(axis=0))
    return float(err.max())


def factor_twice(solver, a, clock: CompileClock) -> tuple[float, float, float]:
    """Cold then warm ``factor(a, materialize=False)``; returns (cold s,
    compile s within the cold call, warm s)."""
    clock.take()
    t0 = time.perf_counter()
    solver.factor(a, materialize=False)
    cold = time.perf_counter() - t0
    compile_s = clock.take()
    t0 = time.perf_counter()
    solver.factor(a, materialize=False)
    warm = time.perf_counter() - t0
    check(clock.take() == 0.0, "the warm factor() compiled again")
    return cold, compile_s, warm


def hbm(device) -> dict:
    stats = device.memory_stats()
    check(stats is not None, f"{device} reports no memory_stats()")
    return stats


def phase_device(chips: int):
    devices = jax.devices()
    d0 = devices[0]
    log(f"[device] platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devices)} jax={jax.__version__}")
    check(d0.platform == "tpu",
          f"no TPU: JAX sees {len(devices)} {d0.platform} device(s)")
    check(len(devices) >= chips,
          f"--chips {chips} needs {chips} TPUs, JAX sees {len(devices)}")
    check(not pallas_interpret(), "Pallas would run in interpret mode")
    return devices


def phase_main(n: int, tb: int, clock: CompileClock) -> None:
    dev = jax.devices()[0]
    t0 = time.perf_counter()
    a = device_spd(n, SEED, dev)
    log(f"[main] n={n} tb={tb} nt={n // tb} policy=v3 f32: SPD matrix "
        f"built in {time.perf_counter() - t0:.3f} s")
    cfg = repro.CholeskyConfig(tb=tb, policy="v3", backend="jax",
                               plan=uniform_plan(n // tb, "f32"))
    solver = repro.plan(n, cfg).compile()
    cold, compile_s, warm = factor_twice(solver, a, clock)
    b = rhs(n, SEED + 1)
    t0 = time.perf_counter()
    x = solver.solve(b)
    solve_s = time.perf_counter() - t0
    err = backward_error(a, x, b)
    bound = f32_backward_bound(n)
    peak = hbm(dev)["peak_bytes_in_use"]
    log(f"[main] cold factor {cold:.3f} s (compile {compile_s:.3f} s), "
        f"warm factor {warm:.3f} s, solve({NRHS} rhs) {solve_s:.3f} s")
    log(f"[main] backward error {err:.3e} (bound {bound:.3e}), "
        f"peak HBM {peak / 1e9:.3f} GB")
    check(np.isfinite(err) and err <= bound,
          f"main f32 backward error {err:.3e} exceeds {bound:.3e}")


def phase_mixed(n: int, tb: int, clock: CompileClock) -> None:
    t0 = time.perf_counter()
    locs = generate_locations(n, seed=SEED)
    a = matern_covariance(locs, beta=BETA_MEDIUM, nu=0.5,
                          nugget=MATERN_NUGGET)
    built = time.perf_counter() - t0
    t0 = time.perf_counter()
    cfg = repro.CholeskyConfig(tb=tb, policy="v3", backend="jax",
                               eps_target=EPS_MXP, ladder="tpu").specialize(a)
    counts = cfg.plan.histogram()
    log(f"[mixed] n={n} tb={tb} Matern nu=0.5 beta={BETA_MEDIUM} nugget="
        f"{MATERN_NUGGET}: built in {built:.3f} s, planned in "
        f"{time.perf_counter() - t0:.3f} s; eps_target={EPS_MXP:g} tiles "
        f"per class {counts}")
    check(sum(v for k, v in counts.items() if k not in ("f64", "f32")) > 0,
          "the precision plan put no tile below f32")
    solver = repro.plan(n, cfg).compile()
    clock.take()
    t0 = time.perf_counter()
    solver.factor(a, materialize=False)
    cold = time.perf_counter() - t0
    compile_s = clock.take()
    b = rhs(n, SEED + 2)
    x = solver.solve(b)
    err = backward_error(a, x, b)
    log(f"[mixed] cold factor {cold:.3f} s (compile {compile_s:.3f} s); "
        f"backward error {err:.3e} (eps_target {EPS_MXP:g})")
    check(np.isfinite(err) and err <= EPS_MXP,
          f"mixed-precision backward error {err:.3e} exceeds eps_target")


def phase_kernels(n: int, tb: int, clock: CompileClock) -> None:
    a = device_spd(n, SEED + 3, jax.devices()[0])
    l_ref = np.linalg.cholesky(a.astype(np.float64))
    scale = np.abs(l_ref).max()
    bound = f32_factor_bound(n)
    factors = {}
    for name, kw in (("xla", {}), ("pallas", dict(use_pallas=True)),
                     ("fused", dict(fuse_columns=True))):
        cfg = repro.CholeskyConfig(tb=tb, policy="v3", backend="jax",
                                   plan=uniform_plan(n // tb, "f32"), **kw)
        solver = repro.plan(n, cfg).compile()
        reset_launch_counts()
        clock.take()
        t0 = time.perf_counter()
        l = solver.factor(a)
        dt = time.perf_counter() - t0
        launches = launch_counts()
        factors[name] = l
        err = np.abs(l - l_ref).max() / scale
        line = (f"[kernels] {name}: n={n} tb={tb} first factor {dt:.3f} s "
                f"(compile {clock.take():.3f} s), launches {launches}, "
                f"|L - L_lapack|/|L| = {err:.3e}")
        if name != "xla":
            vs = np.abs(l - factors["xla"]).max() / scale
            line += f", |L - L_xla|/|L| = {vs:.3e}"
            check(vs <= bound, f"{name} differs from the XLA factor by "
                               f"{vs:.3e} > {bound:.3e}")
        log(line + f" (bound {bound:.3e})")
        check(np.isfinite(err) and err <= bound,
              f"{name} factor error {err:.3e} exceeds {bound:.3e}")
    check(launch_counts()["fused_column"] > 0, "no fused launch was traced")


def phase_grid(n: int, tb: int, clock: CompileClock) -> None:
    devices = jax.devices()[:4]
    nt = n // tb
    a = device_spd(n, SEED + 4, devices[0])
    cfg = repro.CholeskyConfig(tb=tb, policy="v3", backend="jax", ndev=4,
                               grid=(2, 2), plan=uniform_plan(nt, "f32"))
    solver = repro.plan(n, cfg).compile()
    tag = f"[grid] n={n} tb={tb} ndev=4 grid=(2, 2) f32:"
    cold, compile_s, warm = factor_twice(solver, a, clock)
    b = rhs(n, SEED + 5)
    x = solver.solve(b)
    err = backward_error(a, x, b)
    bound = f32_backward_bound(n)
    log(f"{tag} cold factor {cold:.3f} s (compile {compile_s:.3f} s), "
        f"warm factor {warm:.3f} s; backward error {err:.3e} "
        f"(bound {bound:.3e})")
    check(np.isfinite(err) and err <= bound,
          f"grid backward error {err:.3e} exceeds {bound:.3e}")
    xc = crosscheck_executed_volume(solver.schedule, solver.transfer_stats())
    log(f"{tag} executed transfers {xc['executed']}, match={xc['match']}")
    check(xc["match"], f"executed != scheduled transfers: "
                       f"{xc['mismatches']}")
    # each device holds the tile rows of its grid row
    slab = (nt // 2) * nt * tb * tb * 4
    for d in devices:
        peak = hbm(d)["peak_bytes_in_use"]
        log(f"{tag} device {d.id} peak HBM {peak / 1e9:.3f} GB "
            f"(row slab {slab / 1e9:.3f} GB)")
        check(peak >= slab, f"device {d.id} never held its row slab")
    l = solver.factor(a)
    l_ref = np.linalg.cholesky(a.astype(np.float64))
    err = np.abs(l - l_ref).max() / np.abs(l_ref).max()
    bound = f32_factor_bound(n)
    log(f"{tag} |L - L_lapack|/|L| = {err:.3e} (bound {bound:.3e})")
    check(np.isfinite(err) and err <= bound,
          f"grid factor error {err:.3e} exceeds {bound:.3e}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_enable_x64", False)
    devices = phase_device(args.chips)
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_grid(N_GRID, TB_GRID, clock)
    else:
        # cheapest phase first: a compiled kernel that misbehaves on the
        # chip fails in seconds, not after the long compiles
        phase_kernels(N_KERNELS, TB_KERNELS, clock)
        gc.collect()
        phase_main(N_MAIN, TB_MAIN, clock)
        gc.collect()
        phase_mixed(N_MAIN, TB_MAIN, clock)
    log(f"[done] all phases passed in {time.perf_counter() - t0:.3f} s")
    d0 = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
