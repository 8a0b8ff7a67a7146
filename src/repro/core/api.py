"""Two-phase planner/executor API: ``CholeskyConfig`` -> plan -> solve.

The paper's core claim is that the schedule is *static*: built once ahead
of time, replayed for every factorization.  This module makes that the
shape of the public API instead of an implementation detail:

    import repro

    cfg = repro.CholeskyConfig(tb=256, policy="v3")
    solver = repro.plan(n, cfg).compile()   # schedule + jit, built ONCE
    for a in covariance_stream:             # amortized across calls
        l = solver.factor(a)
        x = solver.solve(b)                 # blocked fwd/back substitution

Phases:

* :class:`CholeskyConfig` — frozen, hashable description of everything
  that determines the op stream and the executor: tiling (``tb``), policy,
  precision (``eps_target``/``ladder``/explicit ``plan``), device-memory
  budget (``cache_slots``), and execution (``backend``/``compute_dtype``/
  ``use_pallas``/``block``/``ndev``).  Validation is *eager*: unsupported
  combinations raise at construction, not deep inside an executor (the old
  ``ooc_cholesky`` silently ignored four kwargs when ``ndev > 1``).
* :func:`plan` — builds the static schedule for ``(n, config)`` and caches
  the resulting :class:`CholeskyPlan` (LRU, value-keyed: two configs that
  compare equal share one plan).  The schedule is the unified
  :class:`~repro.core.schedule.MultiDeviceSchedule`; ``ndev=1`` is its
  degenerate single-stream form.
* :meth:`CholeskyPlan.compile` — builds the executor (one ``jax.jit``
  trace for the JAX backend; for ``ndev > 1`` one jitted column-segment
  sequence per device stream with device-to-device panel broadcasts —
  :class:`~repro.core.cholesky.MultiDeviceJaxExecutor`) exactly once per
  plan and returns a :class:`OOCSolver` over it.  The solver is fresh per
  ``compile()`` call — factored state is never shared between call
  sites — but every solver of a plan replays the same compiled executor.
  ``backend="auto"`` resolves multi-device configs to jax whenever the
  process sees at least ``ndev`` devices, else to the NumPy host replay.

Mixed precision: an ``eps_target`` plan depends on the matrix values
(tile norms), so a *reusable* solver needs the plan frozen up front —
``config.specialize(a)`` computes the Higham-Mary plan from a
representative matrix and returns a config with it pinned.  The one-shot
:func:`repro.core.cholesky.ooc_cholesky` shim does this per call.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
import types
from typing import Any, Optional

import numpy as np

from repro.obs.timed import FACTOR, span

from .precision import LADDERS, PrecisionPlan, uniform_plan
from .schedule import (MultiDeviceSchedule, OpKind,
                       build_multidevice_schedule, build_schedule,
                       min_cache_slots)
from .tiling import TileLayout, from_tiles, to_tiles


def _obs_registry():
    """The process-wide obs metrics registry, imported lazily so the
    core planner stays importable without the obs package (and so the
    repro package __init__ never cycles through obs at import time)."""
    try:
        from repro.obs.metrics import REGISTRY
        return REGISTRY
    except Exception:
        return None

_POLICIES = ("sync", "async", "v1", "v2", "v3", "v4", "auto")
_MULTIDEV_POLICIES = ("sync", "v1", "v2", "v3")
_BACKENDS = ("auto", "jax", "numpy")
_DEFAULT_BLOCK = (4, 4)


@dataclasses.dataclass(frozen=True)
class CholeskyConfig:
    """Frozen description of one OOC Cholesky pipeline.

    Hashable by value (including the optional :class:`PrecisionPlan`), so
    it can key the plan cache: equal configs share one schedule and one
    compiled executor.  Fields group into tiling (``tb``), schedule
    policy (``policy``/``cache_slots``/``block``), precision
    (``eps_target``/``ladder``/``plan``), distribution (``ndev``/
    ``grid``), and execution (``backend``/``compute_dtype``/
    ``use_pallas``); see docs/architecture.md for the subsystem map and
    docs/schedule-format.md for what each knob does to the op stream.

    Multi-device (``ndev > 1``): ``grid=(p, q)`` with ``p*q == ndev``
    arranges the devices as a 2D block-cyclic grid (tile ``(i, j)`` is
    owned by device ``(i%p)*q + (j%q)``), which scopes the panel
    broadcast to ``p-1`` receivers and adds a ``q-1``-receiver ownership
    broadcast — strictly less interconnect traffic than 1D for every
    true 2D factorization.  ``grid=None`` means the 1D tile-row layout
    ``(ndev, 1)``, except under the autotuner, which searches every
    factorization of ``ndev`` (docs/multidevice.md).  ``lookahead=L > 0``
    pipelines up to ``L`` panel columns ahead of the trailing update
    (eager peer pushes + rotating panel regions — each depth pins one
    extra cache slot and ``nt`` extra panel slots); ``None`` means 0,
    or a searched dimension when the tuner is engaged.

    Disk tier: ``host_slots=H > 0`` bounds host residency to ``H`` tile
    slabs over a disk-backed store — the builder post-pass interleaves
    explicit ``FETCH``/``SPILL`` ops, executors replay them against a
    :class:`~repro.core.spill.DiskTileStore`, and the factorization can
    exceed host memory (docs/spill.md).  Incompatible with
    ``lookahead > 0``; ``ndev > 1`` spill schedules run on the NumPy
    replay.

    Open dimensions (0.4): ``tb=0`` and/or ``policy="auto"`` leave those
    axes to the autotuner — ``plan()`` resolves them through
    :func:`repro.tune.resolve_config` (exact-simulation search against
    the ``hw`` preset, the process default hardware, or the ``gh200``
    preset) before building the schedule.  With the tuner engaged,
    ``cache_slots=0`` means "search slot budgets" and ``grid=None``
    means "search grids" instead of the builder defaults
    (docs/tuning.md).
    """

    tb: int                                   # tile size (0 = autotune)
    policy: str = "v3"                        # sync/async/v1-v4, or "auto"
    eps_target: Optional[float] = None        # Higham-Mary accuracy level
    ladder: str = "tpu"                       # precision ladder name
    plan: Optional[PrecisionPlan] = None      # explicit per-tile classes
    cache_slots: int = 0                      # 0 = policy default/tuned
    backend: str = "auto"                     # auto -> jax if devices suffice
    compute_dtype: Any = None                 # jax backend compute dtype
    use_pallas: bool = False                  # Pallas tile kernels (jax)
    fuse_columns: bool = False                # fused column-step megakernel
                                              #   (one Pallas launch per
                                              #   column step, jax backend)
    block: tuple = _DEFAULT_BLOCK             # v4 (h, w) update block
    ndev: int = 1                             # block-cyclic devices
    grid: Optional[tuple] = None              # (p, q) device grid; None =
                                              #   1D (ndev, 1), or searched
                                              #   when the tuner is engaged
    hw: Optional[str] = None                  # analytics.HW preset name
    lookahead: Optional[int] = None           # pipelined panels ahead of the
                                              #   trailing update (ndev > 1);
                                              #   None = 0, or searched when
                                              #   the tuner is engaged
    host_slots: int = 0                       # bounded host tier over a disk
                                              #   store (0 = host-resident;
                                              #   > 0 inserts FETCH/SPILL)

    def __post_init__(self):
        object.__setattr__(self, "policy", str(self.policy).lower())
        object.__setattr__(self, "block", tuple(self.block))
        if self.tb < 0:
            raise ValueError(f"tb must be >= 1, or 0 to let the tuner "
                             f"pick it, got {self.tb}")
        if self.policy not in _POLICIES:
            raise ValueError(f"unknown policy {self.policy!r}; "
                             f"expected one of {_POLICIES}")
        if self.backend not in _BACKENDS:
            raise ValueError(f"unknown backend {self.backend!r}; "
                             f"expected one of {_BACKENDS}")
        if self.ladder not in LADDERS:
            raise ValueError(f"unknown ladder {self.ladder!r}; "
                             f"expected one of {tuple(LADDERS)}")
        if self.eps_target is not None and self.eps_target <= 0:
            raise ValueError(f"eps_target must be > 0, got {self.eps_target}")
        if self.eps_target is not None and self.plan is not None:
            raise ValueError("pass either eps_target or an explicit plan, "
                             "not both")
        if self.cache_slots < 0:
            raise ValueError(f"cache_slots must be >= 0 (0 = policy "
                             f"default), got {self.cache_slots}")
        if self.ndev < 1:
            raise ValueError(f"ndev must be >= 1, got {self.ndev}")
        if self.grid is not None:
            object.__setattr__(self, "grid", tuple(self.grid))
            if (len(self.grid) != 2
                    or any(not isinstance(x, int) or x < 1
                           for x in self.grid)):
                raise ValueError(f"grid must be two positive ints (p, q), "
                                 f"got {self.grid!r}")
            if self.grid[0] * self.grid[1] != self.ndev:
                raise ValueError(
                    f"grid={self.grid} does not factor ndev={self.ndev} "
                    f"(need p*q == ndev)")
        if self.lookahead is not None:
            if (isinstance(self.lookahead, bool)
                    or not isinstance(self.lookahead, int)
                    or self.lookahead < 0):
                raise ValueError(f"lookahead must be an int >= 0 (or None "
                                 f"to leave it to the tuner), got "
                                 f"{self.lookahead!r}")
            if self.lookahead > 0 and self.ndev < 2:
                raise ValueError(
                    f"lookahead={self.lookahead} pipelines panels across "
                    f"devices and needs ndev > 1 (got ndev={self.ndev}); "
                    f"the single-device analogue is policy='async'/'v4'")
        if (len(self.block) != 2
                or any(not isinstance(x, int) or x < 1 for x in self.block)):
            raise ValueError(f"block must be two positive ints, "
                             f"got {self.block!r}")
        if self.policy not in ("v4", "auto") and self.block != _DEFAULT_BLOCK:
            raise ValueError(
                f"block={self.block} is only meaningful for policy='v4' "
                f"(got policy={self.policy!r})")
        if self.cache_slots > 0 and self.policy != "auto":
            # eager slot-minimum validation: an unbuildable budget used to
            # surface only as a cache-thrash RuntimeError deep inside
            # schedule construction
            floor = min_cache_slots(self.policy, self.block,
                                    self.lookahead or 0)
            if self.cache_slots < floor:
                raise ValueError(
                    f"policy {self.policy!r}"
                    + (f" with block={self.block}" if self.policy == "v4"
                       else "")
                    + (f" at lookahead={self.lookahead}"
                       if self.lookahead else "")
                    + f" needs >= {floor} cache slots"
                    + (" (h*w + w + 2)" if self.policy == "v4" else
                       " (each lookahead depth pins one extra slot)"
                       if self.lookahead else "")
                    + f", got {self.cache_slots}")
        if self.ndev > 1 and self.policy not in _MULTIDEV_POLICIES \
                and self.policy != "auto":
            raise ValueError(
                f"multi-device schedules support sync/v1/v2/v3, "
                f"got {self.policy!r}")
        if self.host_slots < 0:
            raise ValueError(f"host_slots must be >= 0 (0 = host-resident "
                             f"store, no spill tier), got {self.host_slots}")
        if self.host_slots > 0:
            if (self.lookahead or 0) > 0:
                raise ValueError(
                    "host_slots > 0 (disk spill tier) is incompatible with "
                    "lookahead > 0: the spill post-pass inserts ops into "
                    "each stream, which would invalidate the pipelined "
                    "emitter's dispatch-chunk indices")
            if self.ndev > 1 and self.backend == "jax":
                raise ValueError(
                    "host_slots > 0 with ndev > 1 runs on the NumPy replay "
                    "(the multi-device JAX executor keeps full row slabs "
                    "device-resident); use backend='auto' or 'numpy'")
        if self.hw is not None:
            from .analytics import HW
            if self.hw not in HW:
                raise ValueError(f"unknown hw preset {self.hw!r}; "
                                 f"expected one of {tuple(HW)}")
            mem = HW[self.hw].mem_bytes
            if mem > 0 and self.tb > 0 and self.cache_slots > 0:
                # 8-byte (f64 compute) device tiles; the OOC constraint
                # that used to fail only at executor build time
                need = self.cache_slots * self.tb * self.tb * 8
                if need > mem:
                    raise ValueError(
                        f"cache_slots={self.cache_slots} of "
                        f"{self.tb}x{self.tb} f64 tiles needs "
                        f"{need / 1e9:.1f} GB, but hw={self.hw!r} has "
                        f"mem_bytes={mem / 1e9:.1f} GB")
        if self.use_pallas and self.resolved_backend() != "jax":
            raise ValueError("use_pallas requires the 'jax' backend, "
                             f"got backend={self.backend!r} "
                             f"(resolved {self.resolved_backend()!r})")
        if self.fuse_columns and self.resolved_backend() != "jax":
            raise ValueError("fuse_columns (the fused column-step "
                             "megakernel) requires the 'jax' backend, "
                             f"got backend={self.backend!r} "
                             f"(resolved {self.resolved_backend()!r})")
        if self.compute_dtype is not None and self.resolved_backend() != "jax":
            raise ValueError("compute_dtype is only supported on the 'jax' "
                             f"backend, got backend={self.backend!r} "
                             f"(resolved {self.resolved_backend()!r})")

    @property
    def needs_tuning(self) -> bool:
        """True when an open dimension (``tb=0`` / ``policy="auto"``)
        must be resolved by :func:`repro.tune.resolve_config` before a
        schedule can be built."""
        return self.tb == 0 or self.policy == "auto"

    def resolved_backend(self) -> str:
        """Backend ``'auto'`` actually runs on.

        Single-device resolves to ``'jax'``.  Multi-device resolves to
        ``'jax'`` whenever the process sees at least ``ndev`` JAX devices
        (the per-device executor replays the streams on real devices).
        Only on the CPU backend does it fall back to the ``'numpy'`` host
        replay (too few devices, or a multi-device spill schedule, which
        only the replay runs); on an accelerator those cases raise, so a
        run never leaves the device unnoticed.  An explicit
        ``backend='jax'`` with too few devices raises at ``compile()``.
        """
        if self.backend != "auto":
            return self.backend
        if self.ndev == 1:
            return "jax"
        import jax
        devices = jax.devices()
        if self.host_slots == 0 and len(devices) >= self.ndev:
            return "jax"
        if devices[0].platform == "cpu":
            return "numpy"
        why = ("host_slots > 0 with ndev > 1 runs only on the NumPy replay"
               if self.host_slots > 0 else
               f"ndev={self.ndev} needs {self.ndev} devices, found "
               f"{len(devices)}")
        raise RuntimeError(
            f"backend='auto' will not leave the {devices[0].platform} for "
            f"the NumPy replay: {why}; pass backend='numpy' to run it on "
            f"the host")

    def specialize(self, a: np.ndarray) -> "CholeskyConfig":
        """Freeze the matrix-dependent precision plan into the config.

        With ``eps_target`` set, the Higham-Mary plan is computed from
        ``a``'s tile norms and pinned as ``plan``; the result is fully
        static and can be planned/compiled for reuse.  A config that is
        already static (uniform f64 or explicit plan) is returned as-is.
        """
        if self.eps_target is None:
            return self
        if self.tb == 0:
            raise ValueError(
                "specialize() tiles the matrix with tb, which is still "
                "open (tb=0): resolve the config first — e.g. "
                "repro.tune.tune(n, config, sample=a, eps_target=...) "
                "searches tb and the precision plan together")
        from .cholesky import plan_for_matrix
        a = np.asarray(a, dtype=np.float64)
        if a.ndim != 2 or a.shape[0] != a.shape[1]:
            raise ValueError(f"expected a square matrix, got {a.shape}")
        pplan = plan_for_matrix(to_tiles(a, self.tb), self.eps_target,
                                self.ladder)
        return dataclasses.replace(self, eps_target=None, plan=pplan)


class OOCSolver:
    """Reusable compiled executor for one ``(n, config)`` plan.

    Created via ``repro.plan(n, config).compile()``.  ``factor(a)``
    replays the cached schedule (the JAX executor lives on the shared
    plan and is jitted exactly once across every solver of that plan —
    see ``stats``); ``solve(b)``/``solve_lower(b)``/``logdet()`` run
    blocked substitution against the factored tile store (pass
    ``factor(a, materialize=False)`` to keep the factor tiled — the OOC
    mode); ``simulate(hw)`` / ``volume()`` expose the analytics of the
    underlying plan, and ``transfer_stats()`` the executed interconnect
    counters of a multi-device jax ``factor()``.  The full walkthrough
    lives in docs/architecture.md.

    Each ``compile()`` call returns a *fresh* solver: the expensive
    artifacts (schedule, jitted executor) are shared through the plan
    cache, but the factored tile store is per-solver, so independent
    call sites holding solvers for the same ``(n, config)`` cannot
    observe (or silently consume) each other's factors.
    """

    def __init__(self, plan: "CholeskyPlan", executor: "_CompiledExecutor",
                 default_trace=None):
        self._plan = plan
        self._executor = executor
        # this solver's factored tile store: the compute dtype on the
        # single-device jit path until a solve widens it, else f64
        self._tiles = None
        self._factor_calls = 0
        self._solve_calls = 0
        self._default_trace = default_trace   # from compile(trace=...)
        self._last_io = None        # executed FETCH/SPILL counters

    @property
    def stats(self) -> dict:
        """``jit_traces`` is plan-wide (the amortization contract);
        ``factor_calls``/``solve_calls`` count this solver's own use.

        ``transfers`` is the *unified* movement view across all three
        executor classes: the schedule's static LOAD/STORE volumes
        (which, by the static-schedule claim, are also the executed
        volumes), overlaid — when the last ``factor()`` ran an executor
        that counts at run time — with executed BCAST/RECV counters
        (multi-device jax) and executed FETCH/SPILL counters (spill
        executors and replays)."""
        sched = self._plan.schedule
        transfers = {
            "loads": sched.count(OpKind.LOAD),
            "stores": sched.count(OpKind.STORE),
            "h2d_bytes": sched.loads_bytes(),
            "d2h_bytes": sched.stores_bytes(),
        }
        if self._plan.config.ndev > 1:
            transfers["bcast_bytes"] = sched.bcast_bytes()
            executed = self.transfer_stats()
            if executed is not None:
                transfers.update(executed)
        if sched.host_slots:
            transfers["scheduled_fetch_bytes"] = sched.fetch_bytes()
            transfers["scheduled_spill_bytes"] = sched.spill_bytes()
            if self._last_io is not None:
                transfers.update(self._last_io)
        return {"jit_traces": self._executor.jit_traces,
                "factor_calls": self._factor_calls,
                "solve_calls": self._solve_calls,
                "transfers": transfers}

    # -- two-phase surface -------------------------------------------------
    @property
    def config(self) -> CholeskyConfig:
        return self._plan.config

    @property
    def n(self) -> int:
        return self._plan.n

    @property
    def schedule(self) -> MultiDeviceSchedule:
        return self._plan.schedule

    def simulate(self, hw, link_bw=None, record_timeline: bool = False):
        return self._plan.simulate(hw, link_bw=link_bw,
                                   record_timeline=record_timeline)

    def volume(self) -> dict:
        return self._plan.volume()

    # -- execution ---------------------------------------------------------
    def factor(self, a: np.ndarray, materialize: bool = True,
               trace=None) -> np.ndarray | None:
        """Factor SPD ``a`` through the cached schedule; returns tril L.

        ``materialize=False`` skips assembling the dense n x n factor and
        returns None — the factorization stays in the tile store, where
        ``solve()``/``solve_lower()``/``logdet()`` consume it.  That is
        the out-of-core mode: at OOC scale the dense L is exactly the
        object that does not fit.

        ``trace``: an *active* :class:`repro.obs.TraceRecorder` switches
        every backend to its measured path — eager op-by-op execution
        with a ``block_until_ready`` fence per op, recording exactly one
        span per schedule op (see docs/observability.md; analyze with
        :func:`repro.obs.drift_report`).  ``None`` (or the inactive
        :data:`repro.obs.NULL`) runs the ordinary jitted path unchanged —
        bit-identical results, no extra jit traces.  A default recorder
        can be pinned at :meth:`CholeskyPlan.compile`.

        A solver holds exactly **one** factor: each ``factor()`` call
        *overwrites* the previous tile store, so pending ``solve()``
        calls against the old matrix must complete first.  This
        single-factor statefulness is why :class:`repro.serve`'s service
        pools one solver per session instead of sharing one solver
        across tenants.

        On the single-device jit path the tile store keeps the
        executor's compute dtype (f32 with x64 off): the matrix is tiled
        and cast in one pass, and the factor comes back from the device
        with no widening.  :meth:`logdet` reads it as it is; the first
        :meth:`solve` or :meth:`solve_lower` after the factor widens it
        to f64 once.  Every other path keeps an f64 store.  The dense
        ``L`` that ``materialize=True`` returns is f64 on every path.

        Each call runs inside a ``jax.profiler`` span ``repro.factor``
        (arg ``call``: the call's number on this solver, from 1); on the
        single-device jit path its phases are child spans with their
        bytes (:mod:`repro.obs.timed`, docs/observability.md).
        """
        a = np.asarray(a)
        if a.shape != (self.n, self.n):
            raise ValueError(
                f"matrix shape {a.shape} does not match the plan's "
                f"n={self.n}; build a new plan for a different size")
        with span(FACTOR, call=self._factor_calls + 1):
            return self._factor(a, materialize, trace)

    def _factor(self, a: np.ndarray, materialize: bool, trace):
        cfg = self._plan.config
        ex = self._executor
        if trace is None:
            trace = self._default_trace
        active = trace is not None and getattr(trace, "active", False)
        if active:
            trace.meta.update({
                "n": self.n, "tb": cfg.tb, "nt": self.schedule.nt,
                "ndev": cfg.ndev, "policy": self.schedule.policy,
                "lookahead": cfg.lookahead or 0,
                "host_slots": cfg.host_slots,
                "grid": list(self.schedule.grid),
                "backend": cfg.resolved_backend(),
            })
        io = None       # executed FETCH/SPILL counters of a spill path
        if (cfg.resolved_backend() == "jax" and ex.multidevice is None
                and ex.spill is None and not active):
            out = self._factor_jit(a)
        else:
            # tile first, widen second: an f32 matrix never gets a dense
            # f64 copy next to its f64 tiles
            with span(FACTOR + ".tile", nbytes=8 * self.n * self.n):
                tiles = to_tiles(a, cfg.tb).astype(np.float64, copy=False)
            out, io = self._factor_f64(tiles, trace, active)
        if io is not None:
            self._last_io = io
        self._tiles = out
        self._factor_calls += 1
        reg = _obs_registry()
        if reg is not None:
            reg.inc("repro.factor.calls")
            if io is not None:
                reg.inc("repro.factor.fetch_bytes", io["fetched_bytes"])
                reg.inc("repro.factor.spill_bytes", io["spilled_bytes"])
            reg.set_gauge("repro.factor.jit_traces",
                          self._executor.jit_traces)
        if not materialize:
            return None
        return np.tril(from_tiles(out)).astype(np.float64, copy=False)

    def _factor_jit(self, a: np.ndarray) -> np.ndarray:
        """The single-device jit path: the store is staged, factored and
        kept in the executor's compute dtype, with no f64 copy;
        :meth:`solve` widens it when it first needs f64 tiles."""
        import jax
        ex = self._executor
        dtype = np.dtype(ex.dtype)
        with span(FACTOR + ".tile", nbytes=a.size * dtype.itemsize):
            host = to_tiles(a, self._plan.config.tb, dtype)
        with span(FACTOR + ".h2d", nbytes=host.nbytes):
            dev_tiles = jax.device_put(host).block_until_ready()
        with span(FACTOR + ".run"):
            dev_out = ex.fn(dev_tiles)
            # the host copy is dead once on the device (4.3 GB at
            # n=32768): free it while the program runs
            del host
            dev_out.block_until_ready()
        with span(FACTOR + ".d2h", nbytes=dev_out.nbytes):
            host = np.asarray(dev_out)
        # the hand-over to the store converts nothing
        with span(FACTOR + ".widen", nbytes=0):
            out = _writable(host)
        # the device array caches its host copy: drop both
        del dev_out, host
        return out

    def _factor_f64(self, tiles: np.ndarray, trace, active: bool):
        """Every other path, on an f64 host store: ``(store, io)``, where
        ``io`` holds the executed FETCH/SPILL counters of a spill path."""
        cfg = self._plan.config
        io = None
        if self._executor.multidevice is not None:
            # per-device jitted streams + device-to-device panel broadcast
            # (or, traced, the executor's fenced op-by-op measured path)
            out = self._executor.fn(tiles, trace=trace)
        elif cfg.ndev > 1:
            if cfg.host_slots > 0:
                from .cholesky import run_multidevice_spill
                from .spill import ArrayTileStore
                store = ArrayTileStore(tiles)
                hosts = run_multidevice_spill(store, self._plan.schedule,
                                              trace=trace)
                out = store.to_tiles()
                io = {
                    "fetch_ops": sum(h.fetch_ops for h in hosts),
                    "spill_ops": sum(h.spill_ops for h in hosts),
                    "fetched_bytes": sum(h.fetched_bytes for h in hosts),
                    "spilled_bytes": sum(h.spilled_bytes for h in hosts),
                }
            else:
                from .cholesky import run_multidevice_numpy
                out = run_multidevice_numpy(tiles, self._plan.schedule,
                                            trace=trace)
        elif cfg.resolved_backend() == "numpy":
            if cfg.host_slots > 0:
                from .cholesky import run_schedule_spill
                from .spill import ArrayTileStore
                store = ArrayTileStore(tiles)
                h = run_schedule_spill(store, self._plan.single_schedule(),
                                       trace=trace)
                out = store.to_tiles()
                io = {
                    "fetch_ops": h.fetch_ops, "spill_ops": h.spill_ops,
                    "fetched_bytes": h.fetched_bytes,
                    "spilled_bytes": h.spilled_bytes,
                }
            else:
                from .cholesky import run_schedule_numpy
                out = run_schedule_numpy(tiles, self._plan.single_schedule(),
                                         trace=trace)
        elif self._executor.spill is not None:
            # segmented spill executor: host tiles stay numpy (the
            # bounded slab buffer is the only jax-resident host state)
            out = np.asarray(self._executor.fn(tiles, trace=trace),
                             dtype=np.float64)
            io = self._executor.spill.last_io_stats
        else:
            # traced: per-op spans are unobservable inside the single
            # unrolled jit, so the same op semantics run eagerly
            from .cholesky import run_traced_jax
            out = run_traced_jax(self._plan.single_schedule(), tiles, trace,
                                 compute_dtype=self._executor.dtype,
                                 use_pallas=cfg.use_pallas)
        return out, io

    def _factored_tiles(self) -> np.ndarray:
        if self._tiles is None:
            raise RuntimeError("no factor available: call factor(a) before "
                               "solve()/solve_lower()/logdet()")
        return self._tiles

    def _f64_tiles(self) -> np.ndarray:
        """The store in f64, as the substitution reads it.  A store kept
        in the compute dtype is widened once, at the first solve after
        its factor, and the f64 store replaces it."""
        tiles = self._factored_tiles()
        if tiles.dtype != np.float64:
            with span("repro.solve.widen", nbytes=8 * tiles.size):
                tiles = tiles.astype(np.float64)
            self._tiles = tiles
        return tiles

    def _check_rhs(self, b) -> np.ndarray:
        """Eager rhs validation: reject shape/dtype mismatches with a
        plan-aware error instead of letting them fall through to the
        blocked-substitution internals."""
        b = np.asarray(b)
        if b.dtype.kind not in "fiub":
            raise TypeError(
                f"rhs dtype {b.dtype} is not real-valued; the tiled "
                f"substitution runs in float64")
        if b.ndim not in (1, 2):
            raise ValueError(
                f"rhs must be a vector (n,) or stacked columns (n, k), "
                f"got shape {b.shape}")
        if b.shape[0] != self.n:
            raise ValueError(
                f"rhs has {b.shape[0]} rows but this solver's plan is "
                f"n={self.n}; build a plan for the rhs size or reshape")
        if b.ndim == 2 and b.shape[1] == 0:
            raise ValueError("rhs has 0 columns; nothing to solve")
        return np.asarray(b, dtype=np.float64)

    def solve(self, b: np.ndarray) -> np.ndarray:
        """Solve ``A x = b`` with the last factored ``A = L L^T``.

        ``b`` may be one vector ``(n,)`` or ``k`` stacked columns
        ``(n, k)`` — the blocked substitution sweeps the tile store once
        for the whole stack, which is what the serve batcher exploits
        to coalesce concurrent single-RHS solves.  The result is
        against this solver's *current* factor (see :meth:`factor`).

        The substitution runs in f64: a store kept in the compute dtype
        is widened on the first solve after its factor (span
        ``repro.solve.widen``, counter ``repro.solve.widen_bytes``), and
        later solves against the same factor reuse the f64 store."""
        from .solve import cho_solve_tiles
        x = cho_solve_tiles(self._f64_tiles(), self._check_rhs(b))
        self._solve_calls += 1
        reg = _obs_registry()
        if reg is not None:
            reg.inc("repro.solve.calls")
        return x

    def solve_lower(self, b: np.ndarray) -> np.ndarray:
        """Forward substitution ``L z = b`` (e.g. Gaussian quad forms);
        like :meth:`solve`, accepts one vector or ``(n, k)`` stacked
        columns against the current factor, and widens the store to f64
        as :meth:`solve` does."""
        from .solve import solve_lower_tiles
        z = solve_lower_tiles(self._f64_tiles(), self._check_rhs(b))
        self._solve_calls += 1
        reg = _obs_registry()
        if reg is not None:
            reg.inc("repro.solve.calls")
        return z

    def logdet(self) -> float:
        """``log|A|`` of the last factored matrix, from the diagonal
        tiles of the store in its own dtype, each diagonal widened to
        f64 for the ``log``: no widening of the store."""
        from .solve import logdet_tiles
        return logdet_tiles(self._factored_tiles())

    def transfer_stats(self) -> Optional[dict]:
        """Executed BCAST/RECV op and byte counters of the last
        ``factor()`` on the multi-device JAX backend (None elsewhere);
        cross-check against the static schedule and the event simulator
        with :func:`repro.core.analytics.crosscheck_executed_volume`."""
        mdx = self._executor.multidevice
        return None if mdx is None else mdx.last_transfer_stats


def _writable(host: np.ndarray) -> np.ndarray:
    """A writable array over the memory of ``host``, the read-only host
    copy of a device array that its caller drops at once.  The factor
    store stays its holder's to change, as a store made on the host is,
    without copying it into fresh pages (2.4 GB at n=24576 in f32)."""
    if host.flags.writeable:
        return host
    iface = dict(host.__array_interface__, data=(host.ctypes.data, False))
    own = np.asarray(types.SimpleNamespace(__array_interface__=iface,
                                           base=host))
    return own.view(host.dtype)     # typestr drops extension dtypes


def _resolved_dtype(cfg: CholeskyConfig):
    """Compute dtype the jax executor would use *right now* (None for
    numpy backends).  Read per compile() so a cached plan does not pin a
    float32 executor across a later jax_enable_x64 flip — the pre-0.2
    one-shot API re-read the flag on every call."""
    if cfg.resolved_backend() != "jax":
        return None
    import jax
    import jax.numpy as jnp
    return cfg.compute_dtype or (jnp.float64 if jax.config.jax_enable_x64
                                 else jnp.float32)


class _CompiledExecutor:
    """The per-plan compiled artifact: built once per compute dtype,
    shared by every solver of the plan.  Holds no factored data — only
    the jitted function(s) (JAX backend) and the trace counter.

    For ``ndev > 1`` on the JAX backend this holds a
    :class:`~repro.core.cholesky.MultiDeviceJaxExecutor` — one jitted
    column-segment sequence per device stream, BCAST/RECV edges as
    device-to-device transfers; building it verifies that enough devices
    are visible (RuntimeError otherwise)."""

    def __init__(self, plan: "CholeskyPlan"):
        self._jit_traces = 0
        self.fn = None
        self.multidevice = None    # MultiDeviceJaxExecutor (jax, ndev > 1)
        self.spill = None          # SpillJaxExecutor (jax, host_slots > 0)
        cfg = plan.config
        self.dtype = _resolved_dtype(cfg)
        if cfg.resolved_backend() != "jax":
            return
        import jax
        import jax.numpy as jnp
        from repro.kernels import pallas_interpret
        if (cfg.fuse_columns and jnp.dtype(self.dtype) == jnp.float64
                and not pallas_interpret()):
            raise ValueError(
                f"fuse_columns=True cannot run an f64 compute dtype on "
                f"{jax.default_backend()}: the fused Pallas kernel compiles "
                f"through Mosaic, which has no f64; use "
                f"compute_dtype=jnp.float32 or fuse_columns=False")
        if cfg.ndev > 1:
            from .cholesky import make_multidevice_jax_executor
            self.multidevice = make_multidevice_jax_executor(
                plan.schedule, self.dtype, use_pallas=cfg.use_pallas,
                fuse_columns=cfg.fuse_columns)
            self.fn = self.multidevice
            return
        if cfg.host_slots > 0:
            # segmented executor over the bounded slab buffer; jits one
            # program per device segment, disk I/O driven between them
            from .cholesky import SpillJaxExecutor
            self.spill = SpillJaxExecutor(plan.single_schedule(),
                                          self.dtype,
                                          use_pallas=cfg.use_pallas,
                                          fuse_columns=cfg.fuse_columns)
            self.fn = self.spill
            return
        from .cholesky import make_jax_executor
        raw = make_jax_executor(plan.single_schedule(), self.dtype,
                                use_pallas=cfg.use_pallas,
                                fuse_columns=cfg.fuse_columns)

        def traced(host_tiles):
            # body runs only while tracing: counts jit compilations
            self._jit_traces += 1
            return raw(host_tiles)

        self.fn = jax.jit(traced)

    @property
    def jit_traces(self) -> int:
        if self.multidevice is not None:
            return self.multidevice.jit_traces
        if self.spill is not None:
            return self.spill.jit_traces
        return self._jit_traces


@dataclasses.dataclass
class CholeskyPlan:
    """Cached static schedule for one ``(n, config)``; ``compile()`` hands
    out per-call-site solvers over one shared compiled executor."""

    n: int
    config: CholeskyConfig
    schedule: MultiDeviceSchedule
    _single: Any = None            # single-device Schedule (ndev=1 only)
    _executor: Optional[_CompiledExecutor] = None
    _compile_lock: Any = dataclasses.field(default_factory=threading.Lock,
                                           repr=False, compare=False)

    def single_schedule(self):
        """The flat single-device Schedule backing the ndev=1 degenerate."""
        if self._single is None:
            self._single = self.schedule.to_single()
        return self._single

    def compile(self, trace=None) -> OOCSolver:
        """Return a fresh solver over this plan's one compiled executor.

        The executor (jit) is built on first call and reused afterwards
        (rebuilt only if the jax x64 flag changed the compute dtype in
        the meantime); the solver itself is new each time so factored
        state stays with the call site that produced it (and is freed
        with it — the plan cache never pins a factored matrix).  The
        per-plan lock makes concurrent first compiles (serve workers
        racing for a shared plan) build exactly one executor.

        ``trace``: a :class:`repro.obs.TraceRecorder` pinned as the
        solver's default — every ``factor()`` without an explicit
        ``trace=`` records into it (a per-call ``trace=`` overrides)."""
        with self._compile_lock:
            if (self._executor is None
                    or self._executor.dtype != _resolved_dtype(self.config)):
                self._executor = _CompiledExecutor(self)
            return OOCSolver(self, self._executor, default_trace=trace)

    def simulate(self, hw, link_bw=None, record_timeline: bool = False):
        """Three-engine event model (per-device + shared link for ndev>1)."""
        from . import analytics
        if self.config.ndev > 1:
            return analytics.simulate_multi(self.schedule, hw,
                                            link_bw=link_bw,
                                            record_timeline=record_timeline)
        return analytics.simulate(self.single_schedule(), hw,
                                  record_timeline=record_timeline)

    def volume(self) -> dict:
        """Exact byte-volume report of the static schedule (Fig. 8/12)."""
        from . import analytics
        if self.config.ndev > 1:
            return analytics.volume_report_multi(self.schedule)
        return analytics.volume_report(self.single_schedule())


_PLAN_CACHE: "collections.OrderedDict[tuple, CholeskyPlan]" = \
    collections.OrderedDict()
_PLAN_CACHE_MAX = 32
# One lock for every cache mutation *and* the build of a missing plan:
# concurrent plan() calls from serve workers must neither corrupt the
# OrderedDict (move_to_end/popitem race) nor duplicate a build — with
# the lock held across the miss path, N threads planning the same
# (n, config) produce exactly one schedule and share one CholeskyPlan
# (and therefore one jitted executor).  Reentrant because the tuner
# resolution path may consult planning helpers.
_PLAN_CACHE_LOCK = threading.RLock()
_SCHEDULE_BUILDS = 0     # module-wide build counter (amortization tests)
_PLAN_CACHE_HITS = 0     # served from cache (serve metrics read these)
_PLAN_CACHE_MISSES = 0   # built fresh


def schedule_build_count() -> int:
    return _SCHEDULE_BUILDS


def plan_cache_stats() -> dict:
    """Hit/miss/occupancy counters of the process-wide plan cache.

    ``hits``/``misses`` are cumulative since import (a miss is a fresh
    schedule build); ``size``/``max`` describe current occupancy.  The
    serve metrics layer snapshots this around a traffic window to report
    the cache's contribution to request latency."""
    with _PLAN_CACHE_LOCK:
        return {"hits": _PLAN_CACHE_HITS, "misses": _PLAN_CACHE_MISSES,
                "size": len(_PLAN_CACHE), "max": _PLAN_CACHE_MAX}


def clear_plan_cache() -> None:
    with _PLAN_CACHE_LOCK:
        _PLAN_CACHE.clear()


def plan(n: int, config: CholeskyConfig | None = None,
         **overrides) -> CholeskyPlan:
    """Build (or fetch) the static plan for an ``n x n`` factorization.

    ``plan(n, config)`` or the kwargs shorthand ``plan(n, tb=..., ...)``.
    Plans are cached by ``(n, config)`` value: repeated calls with equal
    configs return the *same* plan object, whose ``compile()`` reuses one
    jitted executor — schedule construction and tracing are amortized
    across every factorization of that shape.

    Configs with open dimensions (``tb=0``, ``policy="auto"``, and —
    given ``ndev > 1`` — ``grid=None`` / ``cache_slots=0``) are resolved
    through the autotuner first (:func:`repro.tune.resolve_config`,
    docs/tuning.md); ``eps_target`` configs must be frozen with
    :meth:`CholeskyConfig.specialize` before planning, because the
    precision plan depends on the matrix values.  See
    docs/architecture.md for the full planner/executor walkthrough.
    """
    global _SCHEDULE_BUILDS, _PLAN_CACHE_HITS, _PLAN_CACHE_MISSES
    if config is None:
        config = CholeskyConfig(**overrides)
    elif overrides:
        config = dataclasses.replace(config, **overrides)
    if config.eps_target is not None:
        raise ValueError(
            "eps_target makes the precision plan matrix-dependent, so it "
            "cannot be planned ahead of the data: freeze it with "
            "config.specialize(a) (or pass plan=plan_for_matrix(...)), or "
            "use the one-shot ooc_cholesky()")
    # the lock spans lookup *and* build: concurrent misses on one key
    # collapse to a single schedule construction (see _PLAN_CACHE_LOCK)
    with _PLAN_CACHE_LOCK:
        auto_key = None
        if config.needs_tuning:
            # open dimensions (tb=0 / policy="auto"): resolve through the
            # autotuner — exact-simulation search against the config's hw
            # preset (or the process default model), memoized in the tuning
            # db.  The plan is cached under the auto key too, so repeat
            # plan() calls with the same auto config skip even the db hit;
            # the key carries the resolving model's identity, so installing
            # a different default hardware model re-resolves instead of
            # serving a plan tuned for the previous one.
            from repro.tune import resolve_config, resolution_token
            auto_key = (n, config, resolution_token(config))
            cached = _PLAN_CACHE.get(auto_key)
            if cached is not None:
                _PLAN_CACHE.move_to_end(auto_key)
                _PLAN_CACHE_HITS += 1
                return cached
            config = resolve_config(n, config)
        if config.grid == (config.ndev, 1):
            # an explicit 1D grid (e.g. a tuner winner) builds the identical
            # schedule as grid=None: canonicalize so both key one cached plan
            # and one jitted executor
            config = dataclasses.replace(config, grid=None)
        if config.lookahead == 0:
            # same canonicalization for an explicit zero lookahead: the
            # emitter's L=0 streams are bit-identical to the default
            config = dataclasses.replace(config, lookahead=None)
        layout = TileLayout(n, config.tb)   # validates n % tb == 0
        key = (n, config)
        cached = _PLAN_CACHE.get(key)
        if cached is not None:
            _PLAN_CACHE.move_to_end(key)
            _PLAN_CACHE_HITS += 1
            if auto_key is not None:
                _PLAN_CACHE[auto_key] = cached
            return cached
        _SCHEDULE_BUILDS += 1
        _PLAN_CACHE_MISSES += 1
        # resolve the default plan here (not in the builders) so the
        # schedule's metadata carries the config's ladder, not a hardcoded
        # one
        pplan = config.plan or uniform_plan(layout.nt, "f64", config.ladder)
        if config.ndev > 1:
            msched = build_multidevice_schedule(
                layout.nt, config.tb, config.ndev, config.policy,
                config.cache_slots, pplan, grid=config.grid,
                lookahead=config.lookahead or 0,
                host_slots=config.host_slots)
            single = None
        else:
            single = build_schedule(layout.nt, config.tb, config.policy,
                                    config.cache_slots, pplan,
                                    block=config.block,
                                    host_slots=config.host_slots)
            msched = MultiDeviceSchedule.from_single(single)
        p = CholeskyPlan(n=n, config=config, schedule=msched, _single=single)
        _PLAN_CACHE[key] = p
        if auto_key is not None:
            _PLAN_CACHE[auto_key] = p
        while len(_PLAN_CACHE) > _PLAN_CACHE_MAX:
            _PLAN_CACHE.popitem(last=False)
        return p
