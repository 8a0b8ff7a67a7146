"""Hardware calibration: micro-benchmarks -> a *measured* HardwareModel.

The datasheet presets in :data:`repro.core.analytics.HW` carry published
peaks; the simulator is only as predictive as those numbers are honest for
the backend actually running (Le Fèvre et al. make the same point for
A64FX Cholesky: measured kernel rates, not published ones, make a cost
model transferable).  This module times, on the live JAX backend:

  * tb x tb POTRF / TRSM / SYRK / GEMM kernels per precision class
    (the exact kernel fns the executors replay, so the measured rate
    includes the cast-through-class behaviour of the real pipeline);
  * host<->device transfer bandwidth (``jax.device_put`` up, host
    ``np.asarray`` readback down) at several transfer sizes, keeping the
    steady-state large-transfer rate;
  * device-to-device interconnect bandwidth (``link_bw``; measured when
    >= 2 devices are visible) — the default the multi-device broadcast
    model :func:`repro.core.analytics.simulate_multi` rides;
  * jit launch overhead and buffer-allocation overhead;
  * device memory capacity (``memory_stats()`` where the backend exposes
    it, a conservative fallback otherwise);
  * disk sequential read/write bandwidth (tmpfile probe on the spill
    tier's filesystem) and physical host RAM — the lanes/capacity the
    disk-tier simulation and the tuner's ``host_slots`` axis consume;

and returns a frozen :class:`HardwareModel` with ``source="measured"``
and a :func:`hardware_fingerprint` identity hash that keys the tuning
database: re-tuning on the same machine is a dict lookup, moving to a
different machine invalidates the cache automatically.

Everything runs in seconds at the default ``tb=256`` — small enough for
the CPU CI smoke leg, honest enough to rank schedule candidates.
"""
from __future__ import annotations

import hashlib
import os
import tempfile
import time

import numpy as np

from repro.core.analytics import GB, HardwareModel
from repro.core.precision import BYTES, LADDERS
from repro.kernels import pallas_interpret

# classes measured by default: every precision name any ladder can assign
_ALL_CLASSES = ("f64", "f32", "f16", "bf16", "f8e4m3", "f8e4m3s")

# fallback device-memory capacity when the backend reports none (CPU CI):
# deliberately small so OOC feasibility filtering stays exercised.
_FALLBACK_MEM_BYTES = 8 * GB

_TASK_FLOP_COUNT = {
    "gemm": lambda tb: 2 * tb**3,
    "syrk": lambda tb: tb**3,
    "trsm": lambda tb: tb**3,
    "potrf": lambda tb: tb**3 / 3.0,
}


def hardware_fingerprint() -> str:
    """Identity hash of the live backend (tuning-db cache key).

    Folds in everything that changes measured rates or the executor's
    numerics: platform, device kind and count, jax version, and the x64
    flag (with x64 off the f64 class degrades to f32 end to end).
    """
    import jax
    dev = jax.devices()[0]
    ident = "|".join([
        jax.default_backend(),
        getattr(dev, "device_kind", type(dev).__name__),
        str(jax.device_count()),
        jax.__version__,
        f"x64={bool(jax.config.jax_enable_x64)}",
    ])
    return hashlib.sha256(ident.encode()).hexdigest()[:12]


def _best_seconds(fn, repeats: int) -> float:
    """Min-of-repeats wall time of ``fn()`` (result blocked on)."""
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn()
        if hasattr(out, "block_until_ready"):
            out.block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return max(best, 1e-9)


def _class_dtype(cls_name: str):
    """jnp dtype a class's tiles are cast through on the live backend
    (the executor's `_jx_round` semantics: f64 degrades to f32 with x64
    off; every class casts back to the compute dtype for the kernel)."""
    import jax
    from repro.core.cholesky import _JNP_DTYPES
    import jax.numpy as jnp
    if cls_name == "f64" and not jax.config.jax_enable_x64:
        return jnp.float32
    return _JNP_DTYPES[cls_name]


def _measure_kernels(tb: int, classes, repeats: int) -> dict:
    """Time the executor's own kernel fns per (task, class) and return
    ``{task: {class: flop_rate}}``.

    The kernel runs exactly as the executor would: operands round-trip
    through the class dtype, the arithmetic runs in the compute dtype.
    So a "bf16-class GEMM" here is cast-to-bf16 + matmul — the honest
    rate of that class on *this* backend, which is what the simulator
    needs to rank schedules (not the MXU's marketing number).
    """
    import jax
    import jax.numpy as jnp
    from repro.core.cholesky import _make_kernel_fns

    compute_dtype = (jnp.float64 if jax.config.jax_enable_x64
                     else jnp.float32)
    kf = _make_kernel_fns(use_pallas=False, interpret=pallas_interpret())
    rng = np.random.default_rng(0)
    spd = np.eye(tb) * (2.0 * tb)
    spd += rng.standard_normal((tb, tb)) @ rng.standard_normal((tb, tb)).T / tb
    c_host = jnp.asarray(spd, dtype=compute_dtype)
    l_host = jnp.asarray(np.linalg.cholesky(spd), dtype=compute_dtype)
    a_host = jnp.asarray(rng.standard_normal((tb, tb)), dtype=compute_dtype)
    b_host = jnp.asarray(rng.standard_normal((tb, tb)), dtype=compute_dtype)

    from repro.core.cholesky import _jx_round

    rates: dict = {task: {} for task in _TASK_FLOP_COUNT}
    for cls_name in classes:

        def through(x):
            # class round-trip: what LOAD does to every operand tile
            # (the scaled-FP8 class applies its per-tile amax scale
            # around the cast — _jx_round is the executor's own path)
            return _jx_round(x, cls_name, compute_dtype)

        jobs = {
            "gemm": jax.jit(lambda c, a, b: kf["gemm"](
                through(c), through(a), through(b))),
            "syrk": jax.jit(lambda c, a: kf["syrk"](through(c), through(a))),
            "trsm": jax.jit(lambda l, c: kf["trsm"](through(l), through(c))),
            "potrf": jax.jit(lambda c: kf["potrf"](through(c))),
        }
        args = {
            "gemm": (c_host, a_host, b_host),
            "syrk": (c_host, a_host),
            "trsm": (l_host, b_host),
            "potrf": (c_host,),
        }
        for task, fn in jobs.items():
            try:
                fn(*args[task]).block_until_ready()       # compile/warm
                dt = _best_seconds(lambda: fn(*args[task]), repeats)
            except Exception:
                # dtype unsupported by this backend's kernels: fall back
                # to the compute-dtype rate (what execution would do too)
                rates[task][cls_name] = rates[task].get(
                    "f64", _TASK_FLOP_COUNT[task](tb) / 1e-3)
                continue
            rates[task][cls_name] = _TASK_FLOP_COUNT[task](tb) / dt
    return rates


def _measure_fused(tb: int, classes, repeats: int,
                   r_tiles: int = 4, k_hist: int = 2) -> dict:
    """Time the fused column-step megakernel per class and return
    ``{"fused_column": {class: flop_rate}}``.

    One launch runs the whole column step (update wave + POTRF + row
    TRSMs with the epilogue cast fused in), so its rate is directly
    comparable to the sum of the unfused per-op rates — the simulator
    and :mod:`benchmarks.roofline` use exactly this comparison to decide
    whether ``fuse_columns`` wins on the calibrated backend.
    """
    import jax
    import jax.numpy as jnp
    from repro.core.precision import LADDERS as _LADS
    from repro.kernels.fused_column import fused_column_step

    compute_dtype = (jnp.float64 if jax.config.jax_enable_x64
                     else jnp.float32)
    if compute_dtype == jnp.float64 and not pallas_interpret():
        # the compiled megakernel has no f64 (Mosaic), and fuse_columns
        # refuses an f64 compute dtype off the CPU: nothing to time
        return {}
    rng = np.random.default_rng(0)
    spd = np.eye(tb) * (2.0 * tb)
    spd += rng.standard_normal((tb, tb)) @ rng.standard_normal((tb, tb)).T / tb
    c_stack = jnp.asarray(
        np.stack([spd] + [rng.standard_normal((tb, tb))
                          for _ in range(r_tiles - 1)]), dtype=compute_dtype)
    hist = jnp.asarray(rng.standard_normal((r_tiles, k_hist, tb, tb)) / tb,
                       dtype=compute_dtype)
    bhist = hist[0]
    l_kk = jnp.zeros((tb, tb), dtype=compute_dtype)
    # FLOPs of the whole step: R*K tile GEMMs + POTRF + (R-1) TRSMs
    flops = (r_tiles * k_hist * 2 * tb**3 + tb**3 / 3.0
             + (r_tiles - 1) * tb**3)

    rates: dict = {}
    for cls_name in classes:
        # the class's position in whichever ladder carries it (the
        # epilogue is ladder-indexed)
        lad = next((l for l in _LADS.values() if cls_name in l), None)
        if lad is None:
            continue
        cls_ids = jnp.full((r_tiles,), lad.index(cls_name), dtype=jnp.int32)

        def run():
            return fused_column_step(c_stack, hist, bhist, l_kk, cls_ids,
                                     ladder=lad, with_diag=True)
        run().block_until_ready()                          # compile/warm
        rates[cls_name] = flops / _best_seconds(run, repeats)
    return {"fused_column": rates} if rates else {}


def _measure_bandwidth(sizes_mb, repeats: int) -> tuple[float, float]:
    """Steady-state host->device / device->host bytes per second."""
    import jax
    import jax.numpy as jnp
    dev = jax.devices()[0]
    h2d = d2h = 0.0
    for mb in sizes_mb:
        nbytes = int(mb * 1e6)
        host = np.zeros(nbytes // 4, dtype=np.float32)
        dt_up = _best_seconds(lambda: jax.device_put(host, dev), repeats)
        x = jax.device_put(host, dev)
        x.block_until_ready()
        dt_down = _best_seconds(lambda: np.asarray(x), repeats)
        # keep the best (largest-transfer) rate: small transfers are
        # latency-bound and would understate the link
        h2d = max(h2d, nbytes / dt_up)
        d2h = max(d2h, nbytes / dt_down)
    return h2d, d2h


def _measure_link_bandwidth(sizes_mb, repeats: int) -> float:
    """Steady-state device-to-device bytes/s (``jax.device_put`` between
    the first two visible devices) — the interconnect the multi-device
    broadcasts ride.  Returns 0.0 when fewer than two devices are
    visible (``simulate_multi`` then falls back to ``h2d_bw``)."""
    import jax
    devs = jax.devices()
    if len(devs) < 2:
        return 0.0
    best = 0.0
    for mb in sizes_mb:
        nbytes = int(mb * 1e6)
        x = jax.device_put(np.zeros(nbytes // 4, dtype=np.float32), devs[0])
        x.block_until_ready()
        dt = _best_seconds(lambda: jax.device_put(x, devs[1]), repeats)
        best = max(best, nbytes / dt)
    return best


def _measure_overheads(repeats: int) -> tuple[float, float]:
    """(jit launch overhead, buffer alloc overhead) in seconds/event."""
    import jax
    import jax.numpy as jnp
    tiny = jnp.zeros((8, 8))
    f = jax.jit(lambda x: x + 1.0)
    f(tiny).block_until_ready()          # compile
    n = 50
    t0 = time.perf_counter()
    y = tiny
    for _ in range(n):
        y = f(y)
    y.block_until_ready()
    launch = max((time.perf_counter() - t0) / n, 1e-8)
    alloc = _best_seconds(lambda: jnp.zeros((256, 256)), repeats)
    return launch, alloc


def _measure_disk_bandwidth(sizes_mb, repeats: int,
                            directory: str | None = None
                            ) -> tuple[float, float]:
    """Sequential (read_bw, write_bw) bytes/s of the filesystem holding
    the spill tier's tile store.

    Writes fsync to make the number honest for SPILL durability; reads
    go through the page cache (so the measured read rate is the *replay's*
    effective rate — a FETCH of a recently spilled tile is usually warm —
    not the device's cold-read floor).  ``directory`` targets the
    filesystem the :class:`~repro.core.spill.DiskTileStore` will live on
    (default: the system tmpdir)."""
    read_bw = write_bw = 0.0
    with tempfile.TemporaryDirectory(dir=directory) as td:
        path = os.path.join(td, "disk_probe.bin")
        for mb in sizes_mb:
            nbytes = int(mb * 1e6)
            buf = bytes(nbytes)

            def wr():
                with open(path, "wb") as f:
                    f.write(buf)
                    f.flush()
                    os.fsync(f.fileno())

            def rd():
                with open(path, "rb") as f:
                    return f.read()

            write_bw = max(write_bw, nbytes / _best_seconds(wr, repeats))
            read_bw = max(read_bw, nbytes / _best_seconds(rd, repeats))
    return read_bw, write_bw


def _host_mem_bytes() -> float:
    """Physical host RAM (``os.sysconf``); 0.0 where unavailable —
    the search then treats host memory as unbounded."""
    try:
        return float(os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES"))
    except (AttributeError, OSError, ValueError):
        return 0.0


def _device_mem_bytes() -> float:
    """Device memory capacity, from the backend when it reports one."""
    import jax
    try:
        stats = jax.devices()[0].memory_stats()
        if stats and stats.get("bytes_limit", 0) > 0:
            return float(stats["bytes_limit"])
    except Exception:
        pass
    return float(_FALLBACK_MEM_BYTES)


def refine_from_trace(trace, base: HardwareModel | None = None,
                      name: str | None = None) -> HardwareModel:
    """Refit a :class:`HardwareModel` from a *measured* execution trace.

    ``trace`` is a :class:`repro.obs.TraceRecorder` filled by a traced
    ``OOCSolver.factor(a, trace=...)`` (its ``meta`` must carry ``tb``).
    Per-op fenced spans are the honest record of what this machine did
    on the *actual* factorization ops — better calibration data than any
    micro-benchmark, because tile shapes, precision round-trips, and
    dispatch overhead are all the real pipeline's:

    * compute spans refit ``kernel_flops[task][class]`` as
      ``task_flops(tb) / median(duration)``;
    * LOAD/STORE spans refit ``h2d_bw``/``d2h_bw`` as the median of
      ``bytes / duration``; RECV spans refit ``link_bw``; FETCH/SPILL
      spans refit the disk bandwidths;
    * everything the trace did not exercise keeps ``base``'s value
      (default: the ``a100-pcie`` datasheet preset).

    The returned model is the drift feedback loop closed: re-simulating
    the same schedule with it reduces the total predicted-vs-measured
    error of :func:`repro.obs.drift_report` (docs/tuning.md).
    """
    import dataclasses
    import statistics

    from repro.core.analytics import HW

    spans = trace.spans
    if not spans:
        raise ValueError("refine_from trace is empty: run "
                         "factor(..., trace=recorder) first")
    meta = getattr(trace, "meta", {}) or {}
    tb = meta.get("tb")
    if not tb:
        raise ValueError(
            "trace.meta carries no 'tb': refine from a trace recorded by "
            "OOCSolver.factor(a, trace=...) (which stamps run metadata), "
            "or set trace.meta['tb'] yourself")
    if base is None:
        base = HW["a100-pcie"]

    by_task: dict = {}
    bw: dict = {"load": [], "store": [], "recv": [], "fetch": [], "spill": []}
    for s in spans:
        dur = s.duration_s
        if dur <= 0:
            continue
        if s.kind in _TASK_FLOP_COUNT:
            by_task.setdefault((s.kind, s.cls or "f64"), []).append(dur)
        elif s.kind in bw and s.bytes > 0:
            bw[s.kind].append(s.bytes / dur)
    if not by_task and not any(bw.values()):
        raise ValueError("trace contains no compute or transfer spans to "
                         "refine from")

    kernel_flops = {task: dict(per)
                    for task, per in (base.kernel_flops or {}).items()}
    for (task, cls_name), durs in by_task.items():
        rate = _TASK_FLOP_COUNT[task](tb) / statistics.median(durs)
        kernel_flops.setdefault(task, {})[cls_name] = rate
    # class peaks follow the measured GEMM rates (the dominant kernel),
    # exactly as the micro-benchmark calibration does
    flops = dict(base.flops)
    flops.update(kernel_flops.get("gemm", {}))

    def med(rates, fallback):
        return statistics.median(rates) if rates else fallback

    return dataclasses.replace(
        base,
        name=name or f"refined-{base.name}",
        flops=flops,
        kernel_flops=kernel_flops,
        h2d_bw=med(bw["load"], base.h2d_bw),
        d2h_bw=med(bw["store"], base.d2h_bw),
        link_bw=med(bw["recv"], base.link_bw),
        disk_read_bw=med(bw["fetch"], base.disk_read_bw),
        disk_write_bw=med(bw["spill"], base.disk_write_bw),
        source="measured",
        fingerprint=hardware_fingerprint(),
    )


def calibrate(tb: int = 256,
              classes=None,
              repeats: int = 3,
              transfer_sizes_mb=(1, 8, 32),
              mem_bytes: float | None = None,
              name: str | None = None,
              disk_dir: str | None = None,
              refine_from=None,
              base: HardwareModel | None = None) -> HardwareModel:
    """Measure the live backend and return a ``source="measured"`` model.

    The result plugs into everything the datasheet presets do —
    ``simulate``/``simulate_multi``, the tuner's candidate search — but
    with per-kernel, per-class rates measured through the executor's own
    kernel fns, real host-link *and* (whenever at least two devices are
    visible) device-to-device interconnect bandwidth — ``link_bw``,
    which ``simulate_multi`` then uses by default for the multi-device
    broadcasts — and the device's actual memory capacity (``mem_bytes``
    overrides detection, e.g. to model a smaller slot budget than the
    hardware has).

    ``refine_from``: instead of running micro-benchmarks, refit the
    model from a measured execution trace
    (:class:`repro.obs.TraceRecorder`) — see :func:`refine_from_trace`;
    ``base`` seeds the un-exercised fields (default ``a100-pcie``).
    """
    if refine_from is not None:
        return refine_from_trace(refine_from, base=base, name=name)
    import jax
    classes = tuple(classes) if classes is not None else _ALL_CLASSES
    for c in classes:
        if c not in BYTES:
            raise ValueError(f"unknown precision class {c!r}; "
                             f"expected a subset of {_ALL_CLASSES}")
    kernel_flops = _measure_kernels(tb, classes, repeats)
    # the fused column-step megakernel, timed as one launch: rates land
    # under kernel_flops["fused_column"] next to the per-op kernels, so
    # fused-vs-unfused comparisons ride the same measured model
    kernel_flops.update(_measure_fused(tb, classes, repeats))
    h2d_bw, d2h_bw = _measure_bandwidth(transfer_sizes_mb, repeats)
    link_bw = _measure_link_bandwidth(transfer_sizes_mb, repeats)
    disk_read_bw, disk_write_bw = _measure_disk_bandwidth(
        transfer_sizes_mb, repeats, directory=disk_dir)
    launch, alloc = _measure_overheads(repeats)
    fp = hardware_fingerprint()
    dev = jax.devices()[0]
    if name is None:
        kind = getattr(dev, "device_kind", jax.default_backend())
        name = f"measured-{str(kind).lower().replace(' ', '-')}-{fp[:6]}"
    return HardwareModel(
        name=name,
        # class peaks = the measured GEMM rate (the dominant kernel);
        # per-kernel detail rides in kernel_flops for the simulator
        flops={c: kernel_flops["gemm"][c] for c in classes},
        h2d_bw=h2d_bw,
        d2h_bw=d2h_bw,
        link_bw=link_bw,
        alloc_overhead=alloc,
        launch_overhead=launch,
        mem_bytes=float(mem_bytes) if mem_bytes else _device_mem_bytes(),
        source="measured",
        fingerprint=fp,
        kernel_flops=kernel_flops,
        disk_read_bw=disk_read_bw,
        disk_write_bw=disk_write_bw,
        host_mem_bytes=_host_mem_bytes(),
    )


def model_to_dict(hw: HardwareModel) -> dict:
    """JSON-serializable form of a model (see :func:`model_from_dict`)."""
    import dataclasses
    return dataclasses.asdict(hw)


def model_from_dict(d: dict) -> HardwareModel:
    return HardwareModel(**d)
