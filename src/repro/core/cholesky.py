"""Executors for the statically scheduled OOC tile Cholesky.

Three executors over the static op streams:

* ``run_schedule_numpy`` / ``run_multidevice_numpy`` — plain NumPy
  oracles (any size, any policy; one host store shared by all streams).
* ``make_jax_executor``   — the op stream is *unrolled into a single jit*:
  LOAD/STORE become dynamic slices between the host tile store and a bounded
  ``slots`` buffer (the "GPU memory"); compute ops run on slots.  On TPU the
  host store is placed with ``memory_kind='pinned_host'`` so the LOAD/STORE
  slices lower to asynchronous host<->HBM DMAs that XLA overlaps with the
  MXU work — the TPU equivalent of the paper's multi-stream ``async`` engine
  (DESIGN.md §2).  On CPU the same program runs with a device-resident store.
* ``make_multidevice_jax_executor`` — the per-device op streams of a
  :class:`~repro.core.schedule.MultiDeviceSchedule` on real JAX devices:
  one jitted column-segment sequence per device (same unrolled machinery
  and kernel fns as the single-device executor), the BCAST/RECV edges
  lowered to class-precision ``jax.device_put`` transfers into each
  peer's dedicated panel slot (see :class:`MultiDeviceJaxExecutor`).

Mixed precision: LOAD casts host(f64) -> tile class -> compute dtype, i.e.
the interconnect carries class-precision bytes ("on-the-fly down-casting",
paper §IV-C).  STORE rounds the finished tile through its class, and the
rounded value is also written back to the slot so that later consumers see
exactly what the paper's low-precision device tile would contain.

Public API migration (0.2): the one-shot :func:`ooc_cholesky` is a
deprecated shim over the two-phase planner/executor API in
:mod:`repro.core.api` — build a frozen config once, then reuse the
compiled solver across same-shape factorizations::

    solver = repro.plan(n, repro.CholeskyConfig(tb=256, policy="v3")).compile()
    l = solver.factor(a)        # schedule + jit amortized across calls
    x = solver.solve(b)         # blocked triangular substitution

Old kwarg -> new config field: ``tb/policy/eps_target/ladder/cache_slots/
compute_dtype/use_pallas/block/ndev`` map 1:1 onto
:class:`~repro.core.api.CholeskyConfig` fields of the same name;
``backend`` gains an ``"auto"`` default: jax single-device, and for
``ndev > 1`` jax whenever the process sees at least ``ndev`` devices
(the per-device executor) with the NumPy host replay as the fallback.
An explicit ``backend="jax"`` with too few visible devices raises at
``compile()``.
"""
from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
import ml_dtypes

from .schedule import (HOST_IO, MultiDeviceSchedule, Op, OpKind, Schedule,
                       grid_owner)
from .precision import (PrecisionPlan, assign_precision, tile_norms,
                        uniform_plan)
from .precision import tile_amax as _tile_amax
from repro.kernels import pallas_interpret
from repro.obs.timed import scope

_NP_DTYPES = {
    "f64": np.float64,
    "f32": np.float32,
    "f16": np.float16,
    "bf16": ml_dtypes.bfloat16,
    "f8e4m3": ml_dtypes.float8_e4m3fn,
    # the *scaled* FP8 class stores the same e4m3 payload; the per-tile
    # power-of-two scale applied around the cast is what differs
    "f8e4m3s": ml_dtypes.float8_e4m3fn,
}
_JNP_DTYPES = {
    "f64": jnp.float64,
    "f32": jnp.float32,
    "f16": jnp.float16,
    "bf16": jnp.bfloat16,
    "f8e4m3": jnp.float8_e4m3fn,
    "f8e4m3s": jnp.float8_e4m3fn,
}


# --------------------------------------------------------------------------
# NumPy oracle
# --------------------------------------------------------------------------

def _np_fp8_scale(amax: float) -> float:
    """Store-time power-of-two scale of a scaled-FP8 tile (the frexp form
    of :func:`repro.core.precision.fp8_scale` — see the jax twin
    ``fused_column._fp8_scale_of`` for why frexp and not log2/floor)."""
    if not amax > 0.0 or not np.isfinite(amax):
        return 1.0
    m, e = np.frexp(amax)
    return float(2.0 ** (int(8 - e) + (1 if m <= 0.875 else 0)))


def _np_round(x: np.ndarray, cls_name: str) -> np.ndarray:
    if cls_name == "f8e4m3s":
        s = _np_fp8_scale(float(np.max(np.abs(x))))
        return ((x * s).astype(_NP_DTYPES[cls_name]).astype(x.dtype)) / s
    return x.astype(_NP_DTYPES[cls_name]).astype(x.dtype)


def _np_interpret_op(host: np.ndarray, slots: np.ndarray, op: Op,
                     lad: tuple) -> None:
    """Execute one op against the shared host store and a slot buffer.

    The single numerical semantics for both the single-device and the
    multi-device replay (a RECV is a LOAD whose bytes crossed the
    interconnect instead of the host link — the class round-trip is the
    same; BCAST/ALLOC/FREE are bookkeeping-only).  A host-landing RECV
    (``slot_c < 0``, the 2D grid's row-scoped ownership broadcast) moves
    a finalized tile between per-device host slabs; against the replay's
    *shared* host store it is coherence bookkeeping with no effect.

    FETCH/SPILL (the disk tier) delegate to the host store object: a
    spill schedule is replayed against a
    :class:`repro.core.spill.SpilledHostStore` instead of the full
    ``[Nt, Nt, tb, tb]`` array — both support the same ``host[i, j]``
    tile indexing, so every other branch is tier-agnostic."""
    if op.kind is OpKind.FETCH:
        host.fetch(op)
    elif op.kind is OpKind.SPILL:
        host.spill(op)
    elif op.kind is OpKind.LOAD or op.kind is OpKind.RECV:
        if op.slot_c < 0:
            return
        slots[op.slot_c] = _np_round(host[op.i, op.j], lad[op.cls])
    elif op.kind is OpKind.STORE:
        rounded = _np_round(slots[op.slot_c], lad[op.cls])
        slots[op.slot_c] = rounded
        host[op.i, op.j] = rounded
    elif op.kind is OpKind.SYRK:
        a = slots[op.slot_a]
        slots[op.slot_c] = slots[op.slot_c] - a @ a.T
    elif op.kind is OpKind.GEMM:
        slots[op.slot_c] = slots[op.slot_c] - slots[op.slot_a] @ slots[op.slot_b].T
    elif op.kind is OpKind.POTRF:
        slots[op.slot_c] = np.linalg.cholesky(
            0.5 * (slots[op.slot_c] + slots[op.slot_c].T))
    elif op.kind is OpKind.TRSM:
        import scipy.linalg as sla
        l = slots[op.slot_a]
        slots[op.slot_c] = sla.solve_triangular(
            l, slots[op.slot_c].T, lower=True).T


def _device_nslots(ops) -> int:
    return max((max(o.slot_c, o.slot_a, o.slot_b)
                for o in ops if o.kind not in HOST_IO), default=-1) + 1


def run_schedule_numpy(host_tiles: np.ndarray, sched: Schedule,
                       trace=None) -> np.ndarray:
    """Interpret the op stream with NumPy.  Returns the factored tile store.

    A spill schedule (``host_slots > 0``) is replayed through a bounded
    host cache over an in-memory backing store with the disk store's
    interface — convenient for equivalence tests; use
    :func:`run_schedule_spill` to drive a real on-disk
    :class:`~repro.core.spill.DiskTileStore`.

    ``trace``: an active :class:`repro.obs.trace.TraceRecorder` records
    one measured span per op (NumPy is synchronous, so no fencing is
    needed); ``None`` or an inactive recorder leaves the replay loop
    untouched.
    """
    if sched.host_slots > 0:
        from .spill import ArrayTileStore
        store = ArrayTileStore(host_tiles)
        run_schedule_spill(store, sched, trace=trace)
        return store.to_tiles()
    host = host_tiles.astype(np.float64).copy()
    tb = sched.tb
    nslots = _device_nslots(sched.ops)
    slots = np.zeros((nslots, tb, tb), dtype=np.float64)
    lad = sched.plan.ladder
    if trace is not None and getattr(trace, "active", False):
        for idx, op in enumerate(sched.ops):
            t0 = trace.now()
            _np_interpret_op(host, slots, op, lad)
            trace.record(idx, op.kind.value, 0, t0, trace.now(), op.bytes,
                         lad[op.cls], op.i, op.j)
        return host
    for op in sched.ops:
        _np_interpret_op(host, slots, op, lad)
    return host


def run_schedule_spill(store, sched: Schedule, trace=None):
    """Replay a spill schedule against a disk-backed tile store in place.

    ``store`` is a :class:`~repro.core.spill.DiskTileStore` (or anything
    with its tile interface) holding the input matrix tiles; on return it
    holds the factored tiles.  Host memory use is bounded: one
    ``[host_slots, tb, tb]`` slab cache plus the device slot buffer.
    Returns the :class:`~repro.core.spill.SpilledHostStore` (its
    fetched/spilled byte counters crosscheck the schedule).  An active
    ``trace`` recorder gets one measured span per op, disk I/O included.
    """
    from .spill import SpilledHostStore
    if sched.host_slots < 1:
        raise ValueError("run_schedule_spill needs a spill schedule "
                         "(build with host_slots > 0)")
    host = SpilledHostStore(store, sched.host_slots)
    slots = np.zeros((_device_nslots(sched.ops), sched.tb, sched.tb),
                     dtype=np.float64)
    lad = sched.plan.ladder
    if trace is not None and getattr(trace, "active", False):
        for idx, op in enumerate(sched.ops):
            t0 = trace.now()
            _np_interpret_op(host, slots, op, lad)
            trace.record(idx, op.kind.value, 0, t0, trace.now(), op.bytes,
                         lad[op.cls], op.i, op.j)
    else:
        for op in sched.ops:
            _np_interpret_op(host, slots, op, lad)
    store.flush()
    return host


def run_multidevice_numpy(host_tiles: np.ndarray,
                          msched: MultiDeviceSchedule,
                          trace=None) -> np.ndarray:
    """Interpret all per-device op streams against one host tile store.

    Each device gets its own slot buffer; the streams are replayed in
    :meth:`MultiDeviceSchedule.iter_dispatch_order` (column-major with
    the owner first for ``lookahead = 0``, the emitter's pipelined chunk
    order otherwise), so every RECV observes the sender's finalized
    (host-coherent) tile.  An active ``trace`` recorder gets one span per
    op, tagged with its device stream and dispatch phase.
    """
    if msched.host_slots > 0:
        from .spill import ArrayTileStore
        store = ArrayTileStore(host_tiles)
        run_multidevice_spill(store, msched, trace=trace)
        return store.to_tiles()
    host = host_tiles.astype(np.float64).copy()
    tb = msched.tb
    lad = msched.plan.ladder
    slots = [np.zeros((msched.stream_nslots(d), tb, tb), dtype=np.float64)
             for d in range(msched.ndev)]
    if trace is not None and getattr(trace, "active", False):
        for idx, (d, op, phase) in enumerate(
                msched.iter_dispatch_order(with_phase=True)):
            t0 = trace.now()
            _np_interpret_op(host, slots[d], op, lad)
            trace.record(idx, op.kind.value, d, t0, trace.now(), op.bytes,
                         lad[op.cls], op.i, op.j, phase)
        return host
    for d, op in msched.iter_column_order():
        _np_interpret_op(host, slots[d], op, lad)
    return host


def run_multidevice_spill(store, msched: MultiDeviceSchedule, trace=None):
    """Replay a multi-device spill schedule against one shared tile store.

    Each device bounds its own host tier (one
    :class:`~repro.core.spill.SpilledHostStore` per stream) over the
    single shared disk store — per-device host accesses are disjoint or
    replicated-final, so a shared backing tier is coherent.  Unlike the
    plain replay, the shared-host shortcut for broadcasts is gone: a
    BCAST snapshots the sender's resident slab onto a wire keyed
    ``(i, j, k, src)`` (exactly the JAX executor's wire table) and each
    RECV consumes the wire — into a panel slot (class-rounded) or, for
    the row-scoped host-landing RECV, into the receiver's own slab.
    Returns the per-device host stores (fetch/spill counters).
    """
    from .spill import SpilledHostStore
    if msched.host_slots < 1:
        raise ValueError("run_multidevice_spill needs a spill schedule "
                         "(build with host_slots > 0)")
    tb = msched.tb
    lad = msched.plan.ladder
    hosts = [SpilledHostStore(store, msched.host_slots)
             for _ in range(msched.ndev)]
    slots = [np.zeros((msched.stream_nslots(d), tb, tb), dtype=np.float64)
             for d in range(msched.ndev)]
    wires: dict = {}
    recording = trace is not None and getattr(trace, "active", False)
    for idx, (d, op, phase) in enumerate(
            msched.iter_dispatch_order(with_phase=True)):
        t0 = trace.now() if recording else 0
        if op.kind is OpKind.BCAST:
            wires[(op.i, op.j, op.k, op.src)] = np.array(hosts[d][op.i, op.j])
        elif op.kind is OpKind.RECV:
            t = wires[(op.i, op.j, op.k, op.src)]
            if op.slot_c >= 0:
                slots[d][op.slot_c] = _np_round(t, lad[op.cls])
            else:
                hosts[d][op.i, op.j] = t
        else:
            _np_interpret_op(hosts[d], slots[d], op, lad)
        if recording:
            trace.record(idx, op.kind.value, d, t0, trace.now(), op.bytes,
                         lad[op.cls], op.i, op.j, phase)
    store.flush()
    return hosts


# --------------------------------------------------------------------------
# JAX executor (single jit, schedule unrolled)
# --------------------------------------------------------------------------

def _jx_fp8_scale(amax, compute_dtype):
    """Store-time power-of-two scale (jax twin of :func:`_np_fp8_scale`;
    frexp keeps the two bitwise-identical across backends)."""
    m, e = jnp.frexp(amax)
    exp = (8 - e) + jnp.where(m <= 0.875, 1, 0)
    s = jnp.exp2(exp.astype(compute_dtype))
    ok = jnp.isfinite(amax) & (amax > 0)
    return jnp.where(ok, s, jnp.asarray(1.0, compute_dtype))


def _jx_round(x, cls_name, compute_dtype):
    if _JNP_DTYPES[cls_name] == compute_dtype:
        return x
    if cls_name == "f64" and not jax.config.jax_enable_x64:
        return x  # f64 class degrades to compute dtype when x64 is off
    with jax.named_scope(scope("round", cls_name)):
        if cls_name == "f8e4m3s":
            s = _jx_fp8_scale(jnp.max(jnp.abs(x)), compute_dtype)
            return ((x * s).astype(_JNP_DTYPES[cls_name])
                    .astype(compute_dtype)) / s
        return x.astype(_JNP_DTYPES[cls_name]).astype(compute_dtype)


def _trsm_jax(l, c):
    # X L^T = C  =>  L X^T = C^T
    return jax.scipy.linalg.solve_triangular(l, c.T, lower=True).T


def _make_kernel_fns(use_pallas: bool, interpret: bool):
    from repro.kernels.fused_column import count_tile_op

    def counted(fn):
        # trace-time dispatch counter, symmetric with the fused path's
        # launch accounting (repro.kernels.fused_column.launch_counts)
        def wrapped(*args):
            count_tile_op()
            return fn(*args)
        return wrapped

    if not use_pallas:
        # HIGHEST: a TPU's default f32 matmul is one bf16 pass, which
        # would silently factor at bf16 accuracy (XLA's Cholesky and
        # triangular-solve expanders already use HIGHEST)
        hi = jax.lax.Precision.HIGHEST
        fns = {
            "potrf": lambda c: jnp.linalg.cholesky(0.5 * (c + c.T)),
            "trsm": _trsm_jax,
            "syrk": lambda c, a: c - jnp.matmul(a, a.T, precision=hi),
            "gemm": lambda c, a, b: c - jnp.matmul(a, b.T, precision=hi),
        }
    else:
        from repro.kernels import ops as kops
        fns = {
            "potrf": partial(kops.potrf, interpret=interpret),
            "trsm": partial(kops.trsm, interpret=interpret),
            "syrk": partial(kops.syrk_update, interpret=interpret),
            "gemm": partial(kops.gemm_update, interpret=interpret),
        }
    return {name: counted(fn) for name, fn in fns.items()}


_JX_OPS = (OpKind.LOAD, OpKind.STORE, OpKind.SYRK, OpKind.GEMM,
           OpKind.POTRF, OpKind.TRSM)


def _jx_interpret_op(host, slots, op: Op, lad, kf, compute_dtype, lrow):
    """Trace one op against a (host store, slot buffer) pair.

    The single unrolled-op semantics shared by the single-device executor
    and every per-device segment of the multi-device executor; ``lrow``
    maps a global tile row to the host store's row index (identity for a
    full store, ``i // ndev`` for a device's block-cyclic row slab).
    Returns the updated ``(host, slots)``.  Each op traces inside the
    named scope ``<kind>.<class>`` (:func:`repro.obs.timed.scope`).
    """
    if op.kind not in _JX_OPS:
        return host, slots      # the other kinds move nothing here
    with jax.named_scope(scope(op.kind.value, lad[op.cls])):
        return _jx_trace_op(host, slots, op, lad, kf, compute_dtype, lrow)


def _jx_trace_op(host, slots, op: Op, lad, kf, compute_dtype, lrow):
    if op.kind is OpKind.LOAD:
        t = _jx_round(host[lrow(op.i), op.j], lad[op.cls], compute_dtype)
        slots = slots.at[op.slot_c].set(t)
    elif op.kind is OpKind.STORE:
        r = _jx_round(slots[op.slot_c], lad[op.cls], compute_dtype)
        slots = slots.at[op.slot_c].set(r)
        host = host.at[lrow(op.i), op.j].set(r)
    elif op.kind is OpKind.SYRK:
        slots = slots.at[op.slot_c].set(
            kf["syrk"](slots[op.slot_c], slots[op.slot_a]))
    elif op.kind is OpKind.GEMM:
        slots = slots.at[op.slot_c].set(
            kf["gemm"](slots[op.slot_c], slots[op.slot_a], slots[op.slot_b]))
    elif op.kind is OpKind.POTRF:
        slots = slots.at[op.slot_c].set(kf["potrf"](slots[op.slot_c]))
    elif op.kind is OpKind.TRSM:
        slots = slots.at[op.slot_c].set(
            kf["trsm"](slots[op.slot_a], slots[op.slot_c]))
    return host, slots


# --------------------------------------------------------------------------
# Fused column-step tracing (CholeskyConfig.fuse_columns)
# --------------------------------------------------------------------------
#
# The unfused trace dispatches one kernel per tile op.  The fused trace
# groups the compute ops of one column step (same ``op.k``) and replaces
# the whole group — SYRK wave + POTRF on the diagonal, GEMM wave + TRSM
# per row — with a single ``fused_column_step`` pallas launch
# (repro.kernels.fused_column).  LOAD/STORE/ALLOC/FREE are *not* fused:
# the data-movement record (bytes, digests, crosschecks) is the
# schedule's contract and stays op-for-op identical; LOADs execute ahead
# of the group and STOREs are deferred behind it, with explicit hazard
# checks forcing a flush whenever the reordering could be observed.

_FUSABLE = (OpKind.SYRK, OpKind.GEMM, OpKind.POTRF, OpKind.TRSM)


def _parse_column_group(group):
    """Match one column step's pending group against the canonical
    pattern the megakernel implements; ``None`` means run it per-op.

    Expected compute shape: an optional diagonal phase (SYRKs into one
    slot, then POTRF on it) followed by zero or more rows (GEMMs into one
    slot, then TRSM on it against the column's diagonal slot), with a
    uniform history depth and identical B-operand slot sequence across
    rows (the fused grid batches the rows over one shared B stack).
    STOREs riding in the group must be expressible as the launch
    epilogue: at most one per slot, positioned after the slot's last
    compute (the diagonal's directly after its POTRF — the row TRSMs
    then solve against the epilogue-rounded scratch factor).  Anything
    else — advance-update chunks of a lookahead schedule, v4 block
    phases, slot-reuse corner cases, mid-accumulation partial stores —
    falls back to the per-op interpreter.
    """
    ops = [op for op, _s in group if op.kind is not OpKind.STORE]
    last_compute_pos = {}
    for pos, (op, _s) in enumerate(group):
        if op.kind is not OpKind.STORE:
            last_compute_pos[op.slot_c] = pos
    store_of = {}
    for pos, (op, _s) in enumerate(group):
        if op.kind is OpKind.STORE:
            if op.slot_c in store_of:       # two roundings of one slot
                return None
            if pos < last_compute_pos.get(op.slot_c, -1):
                return None                 # mid-accumulation store
            store_of[op.slot_c] = op
    idx, n = 0, len(ops)
    syrks: list = []
    potrf = None
    while idx < n and ops[idx].kind is OpKind.SYRK:
        syrks.append(ops[idx])
        idx += 1
    if idx < n and ops[idx].kind is OpKind.POTRF:
        potrf = ops[idx]
        idx += 1
        if any(o.slot_c != potrf.slot_c for o in syrks):
            return None
    elif syrks:
        return None
    rows = []
    while idx < n:
        gemms: list = []
        while idx < n and ops[idx].kind is OpKind.GEMM:
            gemms.append(ops[idx])
            idx += 1
        if idx >= n or ops[idx].kind is not OpKind.TRSM:
            return None
        trsm = ops[idx]
        idx += 1
        if any(o.slot_c != trsm.slot_c for o in gemms):
            return None
        rows.append((gemms, trsm))
    with_diag = potrf is not None
    if not with_diag and not rows:
        return None
    k_steps = len(syrks) if with_diag else len(rows[0][0])
    bslots = ([o.slot_a for o in syrks] if with_diag
              else [o.slot_b for o in rows[0][0]])
    for gemms, _t in rows:
        if len(gemms) != k_steps or [o.slot_b for o in gemms] != bslots:
            return None
    if with_diag:
        diag_slot = potrf.slot_c
    else:
        diag_slot = rows[0][1].slot_a
    if any(t.slot_a != diag_slot for _g, t in rows):
        return None
    c_slots = ([diag_slot] if with_diag else []) + [t.slot_c for _g, t in rows]
    if len(set(c_slots)) != len(c_slots):
        return None
    if not set(store_of) <= set(c_slots):
        return None     # a store of a tile this launch doesn't produce
    operand_slots = set(bslots)
    for gemms, _t in rows:
        operand_slots.update(o.slot_a for o in gemms)
    if set(c_slots) & operand_slots:
        # an output slot doubling as a history operand: the operand
        # snapshot would be stale by the time the unfused order reads it
        return None
    return {"with_diag": with_diag, "potrf": potrf, "rows": rows,
            "syrks": syrks, "k_steps": k_steps, "bslots": bslots,
            "diag_slot": diag_slot, "c_slots": c_slots,
            "store_of": store_of}


def _flush_group_fused(group, c_init, slots, lad, cdt, kf, interpret):
    """Run one pending group: a single fused launch when it matches the
    column-step pattern, the per-op interpreter otherwise.

    ``group`` is a list of ``(op, snap)`` pairs — compute ops with their
    operand values captured at the op's stream position (see
    :func:`_run_ops_fused`) plus the column's STOREs — and ``c_init``
    maps each touched slot to its value when the group first saw it;
    together they reproduce the unfused read order exactly, no matter
    what LOADs ran in between.  Returns ``(slots, host_writes)`` where
    ``host_writes`` lists ``(store_op, rounded_tile)`` in stream order
    for the caller to apply to its host tier.
    """
    def val(t):
        return local[t[1]] if t[0] == "slot" else t[1]

    parsed = _parse_column_group(group)
    if parsed is None:
        # per-op replay over the snapshots (not the live slot buffer:
        # later hoisted LOADs may have re-used operand slots); STORE
        # roundings apply at their exact stream position
        local = dict(c_init)
        host_writes = []
        for op, snap in group:
            if op.kind is OpKind.STORE:
                r = _jx_round(local[op.slot_c], lad[op.cls], cdt)
                local[op.slot_c] = r
                host_writes.append((op, r))
            elif op.kind is OpKind.SYRK:
                local[op.slot_c] = kf["syrk"](local[op.slot_c],
                                              val(snap["a"]))
            elif op.kind is OpKind.GEMM:
                local[op.slot_c] = kf["gemm"](local[op.slot_c],
                                              val(snap["a"]),
                                              val(snap["b"]))
            elif op.kind is OpKind.POTRF:
                local[op.slot_c] = kf["potrf"](local[op.slot_c])
            elif op.kind is OpKind.TRSM:
                local[op.slot_c] = kf["trsm"](val(snap["l"]),
                                              local[op.slot_c])
        for s, v in local.items():
            slots = slots.at[s].set(v)
        return slots, host_writes

    from repro.kernels.fused_column import fused_column_step
    local = c_init     # markers can only name diag (parse rejects others)
    tb = slots.shape[1]
    snaps = {id(op): snap for op, snap in group}
    rows = parsed["rows"]
    with_diag = parsed["with_diag"]
    k_steps = parsed["k_steps"]
    c_slots = parsed["c_slots"]
    store_of = parsed["store_of"]
    c_stack = jnp.stack([c_init[s] for s in c_slots])
    if k_steps:
        hist_rows = [[val(snaps[id(o)]["a"]) for o in gemms]
                     for gemms, _t in rows]
        if with_diag:
            bhist_tiles = [val(snaps[id(o)]["a"]) for o in parsed["syrks"]]
            hist_rows = [bhist_tiles] + hist_rows
        else:
            bhist_tiles = [val(snaps[id(o)]["b"]) for o in rows[0][0]]
        hist = jnp.stack([jnp.stack(r) for r in hist_rows])
        bhist = jnp.stack(bhist_tiles)
    else:
        hist = jnp.zeros((len(c_slots), 0, tb, tb), dtype=cdt)
        bhist = jnp.zeros((0, tb, tb), dtype=cdt)
    l_kk = (jnp.zeros((tb, tb), dtype=cdt) if with_diag
            else val(snaps[id(rows[0][1])]["l"]))
    cls_ids = [store_of[s].cls if s in store_of else -1 for s in c_slots]
    with jax.named_scope(scope("column", group[0][0].k)):
        out = fused_column_step(c_stack, hist, bhist, l_kk, cls_ids,
                                ladder=lad, with_diag=with_diag,
                                interpret=interpret)
    out = out.astype(cdt)
    slots = slots.at[jnp.asarray(c_slots)].set(out)
    row_of = {s: r for r, s in enumerate(c_slots)}
    host_writes = [(op, out[row_of[op.slot_c]])
                   for op, _s in group if op.kind is OpKind.STORE]
    return slots, host_writes


def _run_ops_fused(ops, host, slots, lad, cdt, kf, interpret,
                   read_host, write_host):
    """Trace an op stream with column-step fusion.

    ``read_host(host, op) -> tile`` / ``write_host(host, op, tile) ->
    host`` abstract the host tier (full store, block-cyclic slab, or
    spill slab buffer — the three executor contexts).  Compute ops of one
    column accumulate into a pending group launched as one megakernel.
    Each op's operands are *snapshotted at its stream position* (a slot
    marker when the operand is itself a pending group output), so LOADs
    that later re-use an operand slot need no flush — the executed read
    order is op-for-op that of the unfused trace.  STOREs are deferred
    behind the launch; the remaining hazards (a LOAD targeting a pending
    output slot or a host tile with a deferred STORE, a compute op
    reading a deferred-STORE slot before its in-place rounding) force a
    flush.  IO ops themselves are never fused — the schedule's
    data-movement record is preserved exactly.  Returns the updated
    ``(host, slots)``.
    """
    group: list = []        # (op, operand snapshots); STOREs ride along
    gwrite: set = set()     # slots the pending group writes (or rounds)
    c_init: dict = {}       # slot -> value at first group touch
    dtiles: set = set()     # host tiles with a pending in-group STORE

    def snap_operand(s):
        if s in gwrite:
            return ("slot", s)
        return ("val", slots[s])

    def flush():
        nonlocal host, slots
        if not group:
            return
        slots, host_writes = _flush_group_fused(group, c_init, slots,
                                                lad, cdt, kf, interpret)
        for o, r in host_writes:
            host = write_host(host, o, r)
        group.clear()
        gwrite.clear()
        c_init.clear()
        dtiles.clear()

    for op in ops:
        if op.kind is OpKind.LOAD:
            if op.slot_c in gwrite or (op.i, op.j) in dtiles:
                # the slot would be clobbered by the group's scatter, or
                # the host tile's STORE hasn't landed yet
                flush()
            t = _jx_round(read_host(host, op), lad[op.cls], cdt)
            slots = slots.at[op.slot_c].set(t)
        elif op.kind is OpKind.STORE:
            if group:
                # ride in the group: the rounding applies at this exact
                # stream position (launch epilogue / fallback replay),
                # the host write lands at flush
                if op.slot_c not in gwrite:
                    c_init[op.slot_c] = slots[op.slot_c]
                    gwrite.add(op.slot_c)
                group.append((op, None))
                dtiles.add((op.i, op.j))
            else:
                r = _jx_round(slots[op.slot_c], lad[op.cls], cdt)
                slots = slots.at[op.slot_c].set(r)
                host = write_host(host, op, r)
        elif op.kind in _FUSABLE:
            if group and op.k != group[0][0].k:
                flush()
            snap = {}
            if op.kind is OpKind.SYRK:
                snap["a"] = snap_operand(op.slot_a)
            elif op.kind is OpKind.GEMM:
                snap["a"] = snap_operand(op.slot_a)
                snap["b"] = snap_operand(op.slot_b)
            elif op.kind is OpKind.TRSM:
                snap["l"] = snap_operand(op.slot_a)
            if op.slot_c not in gwrite:
                c_init[op.slot_c] = slots[op.slot_c]
            group.append((op, snap))
            gwrite.add(op.slot_c)
        # ALLOC/FREE are bookkeeping-only, as in the unfused trace
    flush()
    return host, slots


def _donate_argnums(n: int) -> tuple:
    """Cross-segment buffer donation for the fused executors: the slab /
    slot buffers are dead after each segment call (the caller rebinds
    them to the outputs), so on accelerator backends XLA may reuse their
    HBM for the results.  CPU ignores donation with a warning per jit —
    keep it off there."""
    return () if jax.default_backend() == "cpu" else tuple(range(n))


def make_jax_executor(sched: Schedule, compute_dtype=jnp.float64,
                      use_pallas: bool = False, interpret: bool | None = None,
                      fuse_columns: bool = False):
    """Build a jit-able ``host_tiles -> factored host_tiles`` function.

    The returned function's HLO contains exactly the transfers of the static
    schedule; everything else (overlap, async copies) is XLA's job — the
    deterministic-schedule insight of the paper moved to trace time.
    ``fuse_columns`` swaps the per-op compute trace for the column-step
    megakernels (:func:`_run_ops_fused`); the transfers are unchanged.
    """
    if sched.host_slots > 0:
        raise ValueError(
            "make_jax_executor jits over the full host store; a spill "
            "schedule bounds host residency — use SpillJaxExecutor")
    tb = sched.tb
    lad = sched.plan.ladder
    nslots = _device_nslots(sched.ops)
    interpret = pallas_interpret(interpret)
    kf = _make_kernel_fns(use_pallas, interpret)

    def run(host_tiles):
        host = host_tiles.astype(compute_dtype)
        slots = jnp.zeros((nslots, tb, tb), dtype=compute_dtype)
        if fuse_columns:
            host, _ = _run_ops_fused(
                sched.ops, host, slots, lad, compute_dtype, kf, interpret,
                read_host=lambda h, o: h[o.i, o.j],
                write_host=lambda h, o, r: h.at[o.i, o.j].set(r))
            return host
        for op in sched.ops:
            host, slots = _jx_interpret_op(host, slots, op, lad, kf,
                                           compute_dtype, lambda i: i)
        return host

    return run


def run_traced_jax(sched: Schedule, host_tiles: np.ndarray, trace,
                   compute_dtype=jnp.float64, use_pallas: bool = False,
                   interpret: bool | None = None) -> np.ndarray:
    """Single-device JAX execution in *measured* mode: op-by-op, eager,
    with a ``jax.block_until_ready`` fence after every op so each
    recorded span covers that op's actual execution (under async
    dispatch an unfenced timestamp would measure queue insertion).

    This is what ``OOCSolver.factor(a, trace=rec)`` runs on the jax
    backend instead of the unrolled single-jit program — per-op spans
    are unobservable from inside one jitted computation.  The numerical
    semantics are identical (:func:`_jx_interpret_op` is the same
    interpreter the jit unrolls); the fencing serializes the engines, so
    a traced run is slower than an untraced one by construction.
    Records exactly one span per schedule op (ALLOC/FREE included, as
    zero-width bookkeeping spans) and returns the factored f64 tiles.
    """
    if sched.host_slots > 0:
        raise ValueError("run_traced_jax runs host-resident schedules; "
                         "spill schedules trace through SpillJaxExecutor")
    tb = sched.tb
    lad = sched.plan.ladder
    kf = _make_kernel_fns(use_pallas, pallas_interpret(interpret))
    host = jnp.asarray(np.asarray(host_tiles, dtype=np.float64),
                       dtype=compute_dtype)
    slots = jnp.zeros((max(_device_nslots(sched.ops), 1), tb, tb),
                      dtype=compute_dtype)
    jax.block_until_ready((host, slots))   # setup outside the first span
    ident = lambda i: i  # noqa: E731
    for idx, op in enumerate(sched.ops):
        t0 = trace.now()
        host, slots = _jx_interpret_op(host, slots, op, lad, kf,
                                       compute_dtype, ident)
        jax.block_until_ready((host, slots))
        trace.record(idx, op.kind.value, 0, t0, trace.now(), op.bytes,
                     lad[op.cls], op.i, op.j)
    return np.asarray(host, dtype=np.float64)


class SpillJaxExecutor:
    """JAX executor for single-device spill schedules (bounded host tier).

    The stream is split at its FETCH/SPILL ops into maximal device
    *segments*; each segment is unrolled into one jitted
    ``(slabs, slots) -> (slabs, slots)`` program where LOAD/STORE address
    the bounded ``[host_slots, tb, tb]`` slab buffer at trace-time-static
    slab indices (the tile -> slab map is constant within a segment — it
    only changes at FETCH ops, which run between segments).  The disk
    tier itself is driven from Python between segments: a FETCH reads
    one tile from the :class:`~repro.core.spill.DiskTileStore` into its
    slab, a SPILL writes one slab back.  Device memory never sees more
    than ``host_slots + device slots`` tiles; host memory never holds the
    full store.

    ``jit_traces`` counts segment traces (constant across repeated runs
    on same-shape stores — the plan-cache amortization contract).
    """

    def __init__(self, sched: Schedule, compute_dtype=jnp.float64,
                 use_pallas: bool = False, interpret: bool | None = None,
                 fuse_columns: bool = False):
        if sched.host_slots < 1:
            raise ValueError("SpillJaxExecutor needs a spill schedule "
                             "(build with host_slots > 0)")
        self.sched = sched
        self.compute_dtype = compute_dtype
        self.jit_traces = 0
        self.last_io_stats = None     # executed FETCH/SPILL counters
        self._interpret = pallas_interpret(interpret)
        self._kf = _make_kernel_fns(use_pallas, self._interpret)
        self._fuse = fuse_columns
        self._nslots = _device_nslots(sched.ops)
        self._segments = self._build_segments()

    def _make_segment(self, ops: list[Op]):
        lad, cdt, kf = self.sched.plan.ladder, self.compute_dtype, self._kf
        ops = tuple(ops)
        interpret = self._interpret
        if self._fuse:
            def seg(slabs, slots):
                self.jit_traces += 1    # body runs only while tracing
                return _run_ops_fused(
                    ops, slabs, slots, lad, cdt, kf, interpret,
                    read_host=lambda h, o: h[o.hslot],
                    write_host=lambda h, o, r: h.at[o.hslot].set(r))

            return jax.jit(seg, donate_argnums=_donate_argnums(2))

        def seg(slabs, slots):
            self.jit_traces += 1        # body runs only while tracing
            for op in ops:
                if op.kind is OpKind.LOAD:
                    t = _jx_round(slabs[op.hslot], lad[op.cls], cdt)
                    slots = slots.at[op.slot_c].set(t)
                elif op.kind is OpKind.STORE:
                    r = _jx_round(slots[op.slot_c], lad[op.cls], cdt)
                    slots = slots.at[op.slot_c].set(r)
                    slabs = slabs.at[op.hslot].set(r)
                else:
                    _, slots = _jx_interpret_op(None, slots, op, lad, kf,
                                                cdt, None)
            return slabs, slots

        return jax.jit(seg)

    def _build_segments(self):
        """Cut the stream at host-IO ops; resolve each LOAD/STORE's slab.

        Segments are keyed by their op tuple including the resolved
        ``hslot`` attributes, so the static residency decided by the
        spill post-pass is baked into the traced programs.
        """
        import dataclasses as _dc

        @_dc.dataclass(frozen=True)
        class _SlabOp:
            """An op plus the host slab its tile occupies (segment-local
            static metadata; not part of the schedule vocabulary)."""
            kind: object
            i: int
            j: int
            slot_c: int
            slot_a: int
            slot_b: int
            cls: int
            hslot: int
            k: int = -1     # column step, for fused-trace grouping

        where: dict[tuple[int, int], int] = {}
        segments = []       # list of ("io", op) | ("run", jitted fn)
        pending: list = []

        def close_run():
            if pending:
                segments.append(("run", self._make_segment(pending)))
                pending.clear()

        for op in self.sched.ops:
            if op.kind in HOST_IO:
                if op.kind is OpKind.FETCH:
                    # rebind: drop whatever tile held this slab
                    for t, s in list(where.items()):
                        if s == op.slot_c:
                            del where[t]
                    where[(op.i, op.j)] = op.slot_c
                close_run()
                segments.append(("io", op))
            elif op.kind in (OpKind.LOAD, OpKind.STORE):
                pending.append(_SlabOp(op.kind, op.i, op.j, op.slot_c,
                                       op.slot_a, op.slot_b, op.cls,
                                       where[(op.i, op.j)], op.k))
            elif op.kind in (OpKind.ALLOC, OpKind.FREE):
                continue
            else:
                pending.append(_SlabOp(op.kind, op.i, op.j, op.slot_c,
                                       op.slot_a, op.slot_b, op.cls, -1,
                                       op.k))
        close_run()
        return segments

    def run_store(self, store, trace=None) -> None:
        """Factor the tile store in place (input tiles -> L tiles).

        An active ``trace`` recorder switches to the measured path: the
        full op stream is executed eagerly op-by-op with a
        ``block_until_ready`` fence per op (one span per op, disk I/O
        included) instead of the jitted segments.  Either way,
        ``last_io_stats`` holds the executed FETCH/SPILL counters."""
        if trace is not None and getattr(trace, "active", False):
            return self._run_traced_store(store, trace)
        sched = self.sched
        tb, cdt = sched.tb, self.compute_dtype
        slabs = jnp.zeros((sched.host_slots, tb, tb), dtype=cdt)
        slots = jnp.zeros((max(self._nslots, 1), tb, tb), dtype=cdt)
        io = {"fetch_ops": 0, "spill_ops": 0,
              "fetched_bytes": 0, "spilled_bytes": 0}
        for kind, item in self._segments:
            if kind == "io":
                op = item
                if op.kind is OpKind.FETCH:
                    io["fetch_ops"] += 1
                    io["fetched_bytes"] += op.bytes
                    if op.bytes:
                        slabs = slabs.at[op.slot_c].set(
                            jnp.asarray(store.read_tile(op.i, op.j),
                                        dtype=cdt))
                else:
                    io["spill_ops"] += 1
                    io["spilled_bytes"] += op.bytes
                    store.write_tile(
                        op.i, op.j,
                        np.asarray(slabs[op.slot_c], dtype=np.float64))
            else:
                slabs, slots = item(slabs, slots)
        store.flush()
        self.last_io_stats = io

    def _run_traced_store(self, store, trace) -> None:
        """Measured replay: the stream op-by-op, fenced, one span each.

        Maintains the same tile->slab residency map the segment builder
        bakes into its jitted programs (it changes only at FETCH), so
        LOAD/STORE hit the same slabs and the numerics match the
        segmented path op-for-op."""
        sched = self.sched
        tb, cdt = sched.tb, self.compute_dtype
        lad = sched.plan.ladder
        slabs = jnp.zeros((sched.host_slots, tb, tb), dtype=cdt)
        slots = jnp.zeros((max(self._nslots, 1), tb, tb), dtype=cdt)
        jax.block_until_ready((slabs, slots))
        where: dict[tuple[int, int], int] = {}
        io = {"fetch_ops": 0, "spill_ops": 0,
              "fetched_bytes": 0, "spilled_bytes": 0}
        for idx, op in enumerate(sched.ops):
            t0 = trace.now()
            if op.kind is OpKind.FETCH:
                for t, s in list(where.items()):
                    if s == op.slot_c:
                        del where[t]
                where[(op.i, op.j)] = op.slot_c
                io["fetch_ops"] += 1
                io["fetched_bytes"] += op.bytes
                if op.bytes:
                    slabs = slabs.at[op.slot_c].set(
                        jnp.asarray(store.read_tile(op.i, op.j), dtype=cdt))
                    jax.block_until_ready(slabs)
            elif op.kind is OpKind.SPILL:
                io["spill_ops"] += 1
                io["spilled_bytes"] += op.bytes
                store.write_tile(
                    op.i, op.j,
                    np.asarray(slabs[op.slot_c], dtype=np.float64))
            elif op.kind is OpKind.LOAD:
                t = _jx_round(slabs[where[(op.i, op.j)]], lad[op.cls], cdt)
                slots = slots.at[op.slot_c].set(t)
                jax.block_until_ready(slots)
            elif op.kind is OpKind.STORE:
                r = _jx_round(slots[op.slot_c], lad[op.cls], cdt)
                slots = slots.at[op.slot_c].set(r)
                slabs = slabs.at[where[(op.i, op.j)]].set(r)
                jax.block_until_ready((slabs, slots))
            elif op.kind is OpKind.ALLOC or op.kind is OpKind.FREE:
                pass
            else:
                _, slots = _jx_interpret_op(None, slots, op, lad, self._kf,
                                            cdt, None)
                jax.block_until_ready(slots)
            trace.record(idx, op.kind.value, 0, t0, trace.now(), op.bytes,
                         lad[op.cls], op.i, op.j)
        store.flush()
        self.last_io_stats = io

    def __call__(self, host_tiles: np.ndarray, trace=None) -> np.ndarray:
        """Array-in/array-out convenience: factor a full tile array
        through an in-memory backing store (tests, the solver path when
        the caller holds the matrix anyway)."""
        from .spill import ArrayTileStore
        store = ArrayTileStore(host_tiles)
        self.run_store(store, trace=trace)
        return store.to_tiles()


# --------------------------------------------------------------------------
# Multi-device JAX executor (one jitted column segment per device stream)
# --------------------------------------------------------------------------

def _wire_dtype(cls_name: str, compute_dtype):
    """Dtype a broadcast tile travels in: the tile's precision class (the
    interconnect carries class-precision bytes, paper §IV-C), degraded to
    the compute dtype when the f64 class is unavailable (x64 off)."""
    if cls_name == "f64" and not jax.config.jax_enable_x64:
        return compute_dtype
    return _JNP_DTYPES[cls_name]


def _make_wire(tile, cls_name, compute_dtype):
    """Round a finalized tile onto the interconnect wire.

    Every wire is a ``(payload, scale)`` pair so the pytree structure is
    class-independent: plain classes ship their class-dtype payload with
    ``scale=None`` (an empty pytree leaf — nothing travels), the scaled
    FP8 class ships the e4m3 payload plus its power-of-two scale scalar.
    Byte accounting counts the payload only — the scale is 4 bytes of
    metadata riding the ``[Nt, Nt]`` scale table, not tile traffic.
    """
    if cls_name == "f8e4m3s":
        s = _jx_fp8_scale(jnp.max(jnp.abs(tile)), compute_dtype)
        return ((tile * s).astype(_JNP_DTYPES[cls_name]), s)
    return (tile.astype(_wire_dtype(cls_name, compute_dtype)), None)


def _unwire(wire, compute_dtype):
    """Promote a received wire back to the compute dtype (inverting the
    scaled-FP8 store-time scale when one rode along)."""
    payload, scale = wire
    t = payload.astype(compute_dtype)
    return t if scale is None else t / scale


class MultiDeviceJaxExecutor:
    """Replay a :class:`MultiDeviceSchedule` on ``ndev`` real JAX devices.

    Each device stream is compiled as a sequence of *dispatch-chunk
    segments* (:meth:`MultiDeviceSchedule.dispatch_chunks`) — unrolled
    jitted programs (same op semantics and kernel fns as the
    single-device executor) operating on that device's block-cyclic host
    row slab and its private slot buffer.  The slab holds the tile rows
    of the device's *grid row* (``[ceil(Nt/p), Nt, tb, tb]``; with the 1D
    default grid ``(ndev, 1)`` each device has a private slab, a 2D grid
    replicates each slab across its ``q`` grid-row peers).  The
    ``BCAST``/``RECV`` cross-stream edges are the only points where data
    leaves a device: a segment returns the tiles its BCAST ops publish,
    rounded to their class (wire) dtype, and :func:`jax.device_put`
    moves each tile to its receivers, where the consuming segment writes
    it into its panel slot — or, for the 2D grid's row-scoped ownership
    broadcast (``slot_c < 0``), directly into the receiver's host slab.
    For ``lookahead = 0`` the chunk order is the historical per-column
    wave::

        owner head (diag update + POTRF + panel-row wire tiles)
          -> device_put to each grid-column peer  (the BCAST/RECV edges)
          -> owner tail (its own rows of column k)  |  concurrently
          -> each worker's segment (RECV + rows)    |  (async dispatch)
          -> row-scoped receivers (host-slab RECVs of finalized tiles)

    and for ``lookahead > 0`` the emitter's pipelined chunk list: a
    column's final waves interleave with the next panels' bulk pushes,
    eager panel receives, and advance-update segments (whose partial
    accumulators are stored back to the slab), so the owner's trailing
    update overlaps the in-flight panels exactly as in the static
    schedule's partial order.

    Numerics are op-for-op those of :func:`run_multidevice_numpy`: a RECV
    observes the sender's host-coherent tile rounded through its class, so
    FP64 plans agree with the NumPy replay to BLAS round-off and MxP plans
    perform the identical rounding events.

    Attributes: ``jit_traces`` counts segment traces (amortization
    contract: constant across repeated calls); ``last_transfer_stats``
    holds the executed BCAST/RECV op and byte counters of the most recent
    run, cross-checkable against the schedule and the event simulator via
    :func:`repro.core.analytics.crosscheck_executed_volume`.
    """

    def __init__(self, msched: MultiDeviceSchedule, compute_dtype=jnp.float64,
                 use_pallas: bool = False, interpret: bool | None = None,
                 devices=None, fuse_columns: bool = False):
        if msched.ndev < 2:
            raise ValueError(
                f"MultiDeviceJaxExecutor needs ndev >= 2 (got "
                f"{msched.ndev}); use make_jax_executor for one device")
        if devices is None:
            devices = jax.devices()
        if len(devices) < msched.ndev:
            raise RuntimeError(
                f"multi-device jax executor needs {msched.ndev} devices, "
                f"found {len(devices)} ({devices[0].platform}); on CPU, "
                f"set XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{msched.ndev} before importing jax, or use "
                f"backend='numpy'")
        self.msched = msched
        self.devices = list(devices[:msched.ndev])
        self.compute_dtype = compute_dtype
        self.jit_traces = 0
        self.last_transfer_stats = None
        self._interpret = pallas_interpret(interpret)
        self._kf = _make_kernel_fns(use_pallas, self._interpret)
        self._fuse = fuse_columns
        # device d's host slab holds the rows of its grid row (d // q);
        # tile-level ownership within the slab follows schedule.grid_owner,
        # the same rule the builder and column_device_order use
        p, q = msched.grid
        self._rows = [
            [i for i in range(msched.nt) if i % p == d // q]
            for d in range(msched.ndev)
        ]
        self._local_row = [
            {g: l for l, g in enumerate(rows)} for rows in self._rows
        ]
        self._segments = self._build_segments()

    # -- compile-time: split streams into per-column jitted segments -------
    def _make_segment(self, d: int, ops: list[Op]):
        """Jit one device-column slice of device ``d``'s stream.

        ``seg(host_slab, slots, recv_tiles) -> (host_slab, slots, wires)``
        where ``recv_tiles`` match the slice's RECV ops in order (panel
        RECVs land in their slot, host-landing RECVs in the slab) and
        ``wires`` are the class-dtype tiles its BCAST ops publish.
        """
        msched = self.msched
        lad, cdt = msched.plan.ladder, self.compute_dtype
        recv_ops = tuple(o for o in ops if o.kind is OpKind.RECV)
        bcast_ops = tuple(o for o in ops if o.kind is OpKind.BCAST)
        body = tuple(o for o in ops
                     if o.kind is not OpKind.RECV and o.kind is not OpKind.BCAST)
        lrow = self._local_row[d].__getitem__
        fuse, kf, interpret = self._fuse, self._kf, self._interpret

        def seg(host, slots, recv_tiles):
            self.jit_traces += 1        # body runs only while tracing
            for o, t in zip(recv_ops, recv_tiles):
                t = _unwire(t, cdt)
                if o.slot_c >= 0:
                    slots = slots.at[o.slot_c].set(t)
                else:
                    host = host.at[lrow(o.i), o.j].set(t)
            if fuse:
                host, slots = _run_ops_fused(
                    body, host, slots, lad, cdt, kf, interpret,
                    read_host=lambda h, o: h[lrow(o.i), o.j],
                    write_host=lambda h, o, r: h.at[lrow(o.i), o.j].set(r))
            else:
                for o in body:
                    host, slots = _jx_interpret_op(host, slots, o, lad,
                                                   kf, cdt, lrow)
            wires = tuple(
                _make_wire(host[lrow(o.i), o.j], lad[o.cls], cdt)
                for o in bcast_ops)
            return host, slots, wires

        donate = _donate_argnums(2) if fuse else ()
        return jax.jit(seg, donate_argnums=donate), recv_ops, bcast_ops

    def _build_segments(self):
        """Compile one jitted segment per dispatch chunk.

        The segment waves are :meth:`MultiDeviceSchedule.dispatch_chunks`
        — for ``lookahead = 0`` the historical column-major order (the
        diagonal owner's column ops split at its last panel BCAST into a
        head publishing the panel wires and a tail running its own rows);
        for ``lookahead > 0`` the emitter's interleaved final / advance /
        push chunks, so an in-flight panel's early updates run between a
        column's finalization waves.  Wires are matched to their RECVs by
        ``(i, j, k, src)`` — with eager panel pushes the same tile can be
        on two wires at once (row-scoped now, panel-scoped for a later
        column), so the tile id alone is not a key.  ``self._nrecv``
        records each wire's receiver count (executed-bcast-bytes
        accounting for scoped broadcasts, and wire lifetime).
        """
        msched = self.msched
        nrecv = {}
        for stream in msched.streams:
            for o in stream:
                if o.kind is OpKind.RECV:
                    key = (o.i, o.j, o.k, o.src)
                    nrecv[key] = nrecv.get(key, 0) + 1
        self._nrecv = nrecv
        chunks = [(d, list(msched.streams[d][start:stop]))
                  for d, start, stop, _k, _phase in msched.dispatch_chunks()]
        if self._fuse:
            # PR 3 leftover: segment fusion across adjacent dispatch
            # chunks of the same device (consecutive same-owner columns,
            # owner tail + next head, back-to-back worker waves).  Safe
            # exactly when the absorbed chunk has no RECV ops: cross-
            # device data flows only over wires, so a recv-free chunk
            # cannot depend on anything dispatched between the two — and
            # pulling its BCAST publications earlier only ever helps
            # (wire keys are unique per (i, j, k, src)).
            merged: list = []
            for d, ops in chunks:
                if (merged and merged[-1][0] == d
                        and not any(o.kind is OpKind.RECV for o in ops)):
                    merged[-1][1].extend(ops)
                else:
                    merged.append((d, ops))
            chunks = merged
        return [(d,) + self._make_segment(d, ops) for d, ops in chunks]

    # -- run time ----------------------------------------------------------
    def __call__(self, host_tiles: np.ndarray, trace=None) -> np.ndarray:
        """Factor the [Nt, Nt, tb, tb] host store; returns it in f64.

        An active ``trace`` recorder switches to the measured path
        (:meth:`_run_traced`): the dispatch order op-by-op, eagerly, with
        a fence per op — one span per op across all device streams.  An
        inactive/absent trace runs the jitted segments unchanged."""
        if trace is not None and getattr(trace, "active", False):
            return self._run_traced(host_tiles, trace)
        msched = self.msched
        tb, ndev, cdt = msched.tb, msched.ndev, self.compute_dtype
        host_tiles = np.asarray(host_tiles, dtype=np.float64)
        row_slabs = self._rows
        host_d = [jax.device_put(jnp.asarray(host_tiles[rows], dtype=cdt),
                                 self.devices[d])
                  for d, rows in enumerate(row_slabs)]
        slots_d = [
            jax.device_put(
                jnp.zeros((max(msched.stream_nslots(d), 1), tb, tb),
                          dtype=cdt), self.devices[d])
            for d in range(ndev)
        ]
        stats = {"bcast_ops": 0, "recv_ops": 0,
                 "bcast_bytes": 0, "recv_bytes": 0}
        wire_of = {}
        pending = dict(self._nrecv)     # wire -> receivers still to land
        for d, fn, recv_ops, bcast_ops in self._segments:
            recv_tiles = tuple(
                jax.device_put(wire_of[(o.i, o.j, o.k, o.src)],
                               self.devices[d])
                for o in recv_ops)
            for o in recv_ops:
                key = (o.i, o.j, o.k, o.src)
                pending[key] -= 1
                if pending[key] == 0:   # last receiver landed: free the wire
                    del wire_of[key]
            stats["recv_ops"] += len(recv_tiles)
            stats["recv_bytes"] += sum(t[0].nbytes for t in recv_tiles)
            host_d[d], slots_d[d], wires = fn(host_d[d], slots_d[d],
                                              recv_tiles)
            for o, t in zip(bcast_ops, wires):
                key = (o.i, o.j, o.k, o.src)
                wire_of[key] = t
                stats["bcast_bytes"] += t[0].nbytes * self._nrecv[key]
            stats["bcast_ops"] += len(bcast_ops)
        out = np.empty_like(host_tiles)
        p, q = msched.grid
        for d, rows in enumerate(row_slabs):
            if d % q:                   # grid-row peers hold replica slabs
                continue
            out[rows] = np.asarray(host_d[d], dtype=np.float64)
        if q > 1:
            # slabs are replicated along grid rows and kept coherent by the
            # row-scoped broadcast — except the diagonal tiles, which no
            # later task consumes and which are therefore never shipped:
            # read each one from its own diagonal owner
            for k in range(msched.nt):
                if k % q:
                    dv = grid_owner(k, k, p, q)
                    out[k, k] = np.asarray(
                        host_d[dv][self._local_row[dv][k], k],
                        dtype=np.float64)
        self.last_transfer_stats = stats
        return out

    def _run_traced(self, host_tiles: np.ndarray, trace) -> np.ndarray:
        """Measured replay: every op of every stream in dispatch order,
        eagerly, fenced per op — one recorded span per op.

        The numerics are those of the segmented path (same interpreter,
        same wire table keyed ``(i, j, k, src)``, same class-dtype wire
        rounding); only the execution granularity changes, so per-op
        durations are observable.  ``last_transfer_stats`` is maintained
        exactly as on the jitted path."""
        msched = self.msched
        tb, ndev, cdt = msched.tb, msched.ndev, self.compute_dtype
        lad = msched.plan.ladder
        host_tiles = np.asarray(host_tiles, dtype=np.float64)
        host_d = [jax.device_put(jnp.asarray(host_tiles[rows], dtype=cdt),
                                 self.devices[d])
                  for d, rows in enumerate(self._rows)]
        slots_d = [
            jax.device_put(
                jnp.zeros((max(msched.stream_nslots(d), 1), tb, tb),
                          dtype=cdt), self.devices[d])
            for d in range(ndev)
        ]
        jax.block_until_ready((host_d, slots_d))  # setup outside spans
        stats = {"bcast_ops": 0, "recv_ops": 0,
                 "bcast_bytes": 0, "recv_bytes": 0}
        wire_of = {}
        pending = dict(self._nrecv)
        for idx, (d, op, phase) in enumerate(
                msched.iter_dispatch_order(with_phase=True)):
            t0 = trace.now()
            lrow = self._local_row[d].__getitem__
            if op.kind is OpKind.BCAST:
                key = (op.i, op.j, op.k, op.src)
                w = _make_wire(host_d[d][lrow(op.i), op.j],
                               lad[op.cls], cdt)
                jax.block_until_ready(w)
                wire_of[key] = w
                stats["bcast_ops"] += 1
                stats["bcast_bytes"] += w[0].nbytes * self._nrecv[key]
            elif op.kind is OpKind.RECV:
                key = (op.i, op.j, op.k, op.src)
                wire = jax.device_put(wire_of[key], self.devices[d])
                t = _unwire(wire, cdt)
                if op.slot_c >= 0:
                    slots_d[d] = slots_d[d].at[op.slot_c].set(t)
                    jax.block_until_ready(slots_d[d])
                else:
                    host_d[d] = host_d[d].at[lrow(op.i), op.j].set(t)
                    jax.block_until_ready(host_d[d])
                stats["recv_ops"] += 1
                stats["recv_bytes"] += wire[0].nbytes
                pending[key] -= 1
                if pending[key] == 0:
                    del wire_of[key]
            else:
                host_d[d], slots_d[d] = _jx_interpret_op(
                    host_d[d], slots_d[d], op, lad, self._kf, cdt, lrow)
                jax.block_until_ready((host_d[d], slots_d[d]))
            trace.record(idx, op.kind.value, d, t0, trace.now(), op.bytes,
                         lad[op.cls], op.i, op.j, phase)
        out = np.empty_like(host_tiles)
        p, q = msched.grid
        for d, rows in enumerate(self._rows):
            if d % q:                   # grid-row peers hold replica slabs
                continue
            out[rows] = np.asarray(host_d[d], dtype=np.float64)
        if q > 1:
            for k in range(msched.nt):
                if k % q:
                    dv = grid_owner(k, k, p, q)
                    out[k, k] = np.asarray(
                        host_d[dv][self._local_row[dv][k], k],
                        dtype=np.float64)
        self.last_transfer_stats = stats
        return out


def make_multidevice_jax_executor(msched: MultiDeviceSchedule,
                                  compute_dtype=jnp.float64,
                                  use_pallas: bool = False,
                                  interpret: bool | None = None,
                                  devices=None,
                                  fuse_columns: bool = False,
                                  ) -> MultiDeviceJaxExecutor:
    """Build the per-device JAX executor for a multi-device schedule.

    Returns a callable ``host_tiles -> factored host_tiles`` (f64 NumPy in
    and out) backed by one jitted program sequence per device stream; see
    :class:`MultiDeviceJaxExecutor`.  Raises ``RuntimeError`` when fewer
    than ``msched.ndev`` JAX devices are visible.
    """
    return MultiDeviceJaxExecutor(msched, compute_dtype,
                                  use_pallas=use_pallas, interpret=interpret,
                                  devices=devices, fuse_columns=fuse_columns)


# --------------------------------------------------------------------------
# Public API
# --------------------------------------------------------------------------

def plan_for_matrix(a_tiles: np.ndarray, eps_target: float | None,
                    ladder: str = "tpu") -> PrecisionPlan:
    nt = a_tiles.shape[0]
    if eps_target is None:
        return uniform_plan(nt, "f64", ladder)
    norms, total = tile_norms(a_tiles)
    # amax-aware classification: tiles outside e4m3's representable band
    # no longer qualify for the unscaled FP8 class (the scaled class is
    # unaffected — its per-tile scale recentres the band)
    return assign_precision(norms, total, eps_target, ladder,
                            tile_amax=_tile_amax(a_tiles))


def ooc_cholesky(
    a: np.ndarray,
    tb: int,
    policy: str = "v3",
    eps_target: float | None = None,
    ladder: str = "tpu",
    cache_slots: int = 0,
    backend: str | None = None,
    compute_dtype=None,
    use_pallas: bool = False,
    block: tuple = (4, 4),
    ndev: int = 1,
) -> tuple[np.ndarray, MultiDeviceSchedule]:
    """One-shot out-of-core Cholesky — deprecated shim over the planner API.

    .. deprecated:: 0.2
       Use ``repro.plan(n, CholeskyConfig(...)).compile()`` instead: the
       static schedule and jitted executor are then built once and reused
       across every same-shape factorization.  Kwarg migration:

       ============== ===========================================
       old kwarg      CholeskyConfig field
       ============== ===========================================
       tb             ``tb``
       policy         ``policy``
       eps_target     ``eps_target`` (freeze via ``specialize(a)``)
       ladder         ``ladder``
       cache_slots    ``cache_slots``
       backend        ``backend`` (new default ``"auto"``)
       compute_dtype  ``compute_dtype``
       use_pallas     ``use_pallas``
       block          ``block``
       ndev           ``ndev``
       ============== ===========================================

    Returns ``(L, schedule)`` with L lower-triangular (upper part zeroed)
    and ``schedule`` the unified
    :class:`~repro.core.schedule.MultiDeviceSchedule` (ndev=1 degenerate
    for the single-device path) carrying the exact data-movement record.

    ``ndev > 1`` with ``backend="jax"`` (or ``"auto"`` with enough
    visible devices) runs the per-device JAX executor
    (:class:`MultiDeviceJaxExecutor`); with too few devices an explicit
    ``"jax"`` raises ``RuntimeError`` at compile.  Unsupported
    combinations (``async``/``v4`` multi-device, pallas or compute_dtype
    on a numpy-resolved backend) raise eagerly from config validation.
    """
    import warnings

    from .api import CholeskyConfig, plan as _plan

    warnings.warn(
        "ooc_cholesky() is deprecated: use "
        "repro.plan(n, CholeskyConfig(...)).compile() to amortize the "
        "schedule build and jit across factorizations",
        DeprecationWarning, stacklevel=2)
    a = np.asarray(a, dtype=np.float64)
    cfg = CholeskyConfig(
        tb=tb, policy=policy, eps_target=eps_target, ladder=ladder,
        cache_slots=cache_slots, backend=backend or "auto",
        compute_dtype=compute_dtype, use_pallas=use_pallas, block=block,
        ndev=ndev,
    ).specialize(a)
    solver = _plan(a.shape[0], cfg).compile()
    return solver.factor(a), solver.schedule
