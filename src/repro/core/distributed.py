"""Multi-device left-looking tile Cholesky (paper §IV-D, Fig. 5/9).

TPU-native adaptation of the paper's 1D block-cyclic multi-GPU scheme:

* tile-row ``i`` is owned by device ``i % P`` (block-cyclic, Fig. 5a);
* the left-looking order makes the *panel row broadcast* the only
  communication: at column step ``k`` the owner finalizes the diagonal
  tile locally, then row ``k`` (which is final: columns < k are done)
  is broadcast once (``psum`` of a zero-masked contribution); every
  device then updates/factors its own rows of column ``k`` locally.

This mirrors the paper's claim that the lazy left-looking variant avoids
the right-looking variant's collective storm: exactly one broadcast of at
most Nt tiles per column step, everything else is device-local.

Implementation: ``shard_map`` over one mesh axis; the tile store is
row-cyclically permuted on the host so each device's shard is a dense
``[Nt/P, Nt, tb, tb]`` slab.  The k-loop is a ``lax.fori_loop``; the
update sweep is a single masked einsum (full-width contraction against
the zero-padded broadcast row), trading ≤2x redundant MXU flops for a
scan-free, layout-stable inner step.

Role in 0.3+: this shard_map einsum path is the *reference baseline* for
the multi-device executors.  The production path is the static-schedule
stack — ``schedule.build_multidevice_schedule`` (per-device op streams
with BCAST/RECV edges) replayed on real devices by
``cholesky.make_multidevice_jax_executor`` (one jitted column-segment
sequence per device, device-to-device panel transfers), with
``analytics.simulate_multi`` as its exact event model and
``cholesky.run_multidevice_numpy`` as the host-side oracle.  The
equivalence suite (``tests/test_backend_equivalence.py``) pins all of
them against each other and against LAPACK; :func:`modeled_scaling`
below ties the Fig. 9 scaling argument to the exact op streams the
executor replays.  Keep this path dependency-light and *simple* — its
value is being an independently-derived answer, not being fast.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .tiling import to_tiles, from_tiles


def _cyclic_permute(nt: int, p: int) -> np.ndarray:
    """Global row order so that contiguous shards = block-cyclic ownership.

    Device d gets global rows [d, d+P, d+2P, ...] as its contiguous slab.
    """
    return np.concatenate([np.arange(d, nt, p) for d in range(p)])


def distributed_cholesky(a: np.ndarray, tb: int, mesh: Mesh, axis: str = "model",
                         dtype=jnp.float64) -> np.ndarray:
    """Factor SPD ``a`` across ``mesh[axis]`` devices. Returns L (host)."""
    n = a.shape[0]
    nt = n // tb
    p = mesh.shape[axis]
    if nt % p != 0:
        raise ValueError(f"Nt={nt} must be divisible by device count {p}")
    nt_loc = nt // p

    perm = _cyclic_permute(nt, p)
    inv_perm = np.argsort(perm)

    tiles = to_tiles(np.asarray(a, dtype=np.float64), tb)[perm]  # [Nt, Nt, tb, tb]
    tiles = jnp.asarray(tiles, dtype=dtype)

    @jax.jit
    def factor(tiles_sharded):
        def body(local):   # local: [Nt_loc, Nt, tb, tb]
            dev = jax.lax.axis_index(axis)

            def col_step(k, loc):
                owner = k % p
                rk = k // p                      # local row idx on owner
                # ---- 1) owner updates + factors the diagonal tile ----
                my_row = jax.lax.dynamic_index_in_dim(loc, rk, axis=0,
                                                      keepdims=False)  # [Nt, tb, tb]
                colmask = (jnp.arange(nt) < k).astype(loc.dtype)[:, None, None]
                row_m = my_row * colmask
                # SYRK sweep: A[k,k] -= sum_n<k A[k,n] A[k,n]^T (masked full width)
                delta = jnp.einsum("nab,ncb->ac", row_m, row_m,
                                   preferred_element_type=loc.dtype)
                akk = jax.lax.dynamic_index_in_dim(my_row, k, axis=0,
                                                   keepdims=False) - delta
                lkk = jnp.linalg.cholesky(0.5 * (akk + akk.T))
                # write L[k,k] back into the owner's slab (no-op elsewhere)
                new_row = jax.lax.dynamic_update_index_in_dim(my_row, lkk, k, axis=0)
                is_owner = (dev == owner)
                upd_row = jnp.where(is_owner, new_row, my_row)
                loc = jax.lax.dynamic_update_index_in_dim(loc, upd_row, rk, axis=0)

                # ---- 2) broadcast final row k (masked psum) ----
                contrib = jnp.where(is_owner, upd_row, jnp.zeros_like(upd_row))
                row_k = jax.lax.psum(contrib, axis)          # [Nt, tb, tb]

                # ---- 3) everyone updates its rows of column k ----
                row_k_m = row_k * colmask                    # zero cols >= k
                lkk_b = jax.lax.dynamic_index_in_dim(row_k, k, axis=0,
                                                     keepdims=False)
                # GEMM sweep for all local rows at once (masked full width)
                deltas = jnp.einsum("rnab,ncb->rac", loc * colmask[None],
                                    row_k_m, preferred_element_type=loc.dtype)
                cur = loc[:, k]                              # [Nt_loc, tb, tb]
                upd = cur - deltas
                # TRSM: X L^T = C  ->  L X^T = C^T
                lkk_batch = jnp.broadcast_to(lkk_b, (nt_loc,) + lkk_b.shape)
                xt = jax.scipy.linalg.solve_triangular(
                    lkk_batch, jnp.swapaxes(upd, -1, -2), lower=True)
                x = jnp.swapaxes(xt, -1, -2)
                # only rows with global index m > k take the TRSM result
                gidx = dev + p * jnp.arange(nt_loc)
                take = (gidx > k)[:, None, None]
                newcol = jnp.where(take, x, cur)
                loc = loc.at[:, k].set(newcol)
                return loc

            local = jax.lax.fori_loop(0, nt, col_step, local)
            return local

        return shard_map(
            body, mesh=mesh,
            in_specs=P(axis), out_specs=P(axis), check_vma=False,
        )(tiles_sharded)

    with mesh:
        sharded = jax.device_put(
            tiles, jax.sharding.NamedSharding(mesh, P(axis)))
        out = factor(sharded)
    out = np.asarray(out, dtype=np.float64)[inv_perm]
    return np.tril(from_tiles(out))


def panel_broadcast_bytes(nt: int, tb: int, p: int, word: int = 8) -> int:
    """Analytic per-factorization collective volume: one row-k broadcast per
    step, each (k+1) tiles to (P-1) receivers (for the roofline model).

    The static multi-device schedule reproduces this number exactly:
    ``build_multidevice_schedule(nt, tb, p).bcast_bytes()`` (uniform-f64
    plans) sums the same tiles op by op."""
    total_tiles = sum(k + 1 for k in range(nt))
    return total_tiles * tb * tb * word * (p - 1)


def grid_broadcast_bytes(nt: int, tb: int, grid: tuple,
                         word: int = 8) -> int:
    """Analytic collective volume of the ``p x q`` 2D block-cyclic
    schedule (uniform word-size tiles): at step ``k`` the panel row
    ``(k, 0..k)`` goes to the ``p - 1`` other devices of grid column
    ``k % q``, and each finalized column tile ``(m, k)``, ``m > k``,
    goes to its ``q - 1`` grid-row peers.

    ``grid=(P, 1)`` reduces to :func:`panel_broadcast_bytes`; for a true
    2D factorization of ``P >= 2`` devices the total is strictly smaller
    (roughly ``(p + q - 2) / (P - 1)`` of the 1D volume, the classic
    O(sqrt(P)) communication scaling).  The static schedule reproduces
    this number exactly:
    ``build_multidevice_schedule(nt, tb, p*q, grid=grid).bcast_bytes()``.
    """
    p, q = grid
    panel_tiles = sum(k + 1 for k in range(nt))          # column-scoped
    column_tiles = sum(nt - 1 - k for k in range(nt))    # row-scoped
    return tb * tb * word * ((p - 1) * panel_tiles
                             + (q - 1) * column_tiles)


def modeled_scaling(nt: int, tb: int, ndevs=(1, 2, 4), policy: str = "v3",
                    hw_name: str = "gh200",
                    link_bw: float | None = None,
                    grid_of=None) -> list[dict]:
    """Fig. 9 scaling rows from the *same static schedules the executors
    replay* — an exact event simulation, not a side-channel estimate.

    For each device count, builds the block-cyclic multi-device schedule
    (1D tile-row ownership by default; ``grid_of`` maps a device count
    to an explicit ``(p, q)`` grid, e.g. ``{4: (2, 2)}``), runs
    :func:`~repro.core.analytics.simulate_multi` on the named hardware
    preset (``link_bw`` overrides the interconnect), and reports
    makespan, speedup/efficiency vs the 1-device schedule, and the
    broadcast volume."""
    from .analytics import HW, simulate_multi
    from .schedule import build_multidevice_schedule

    hw = HW[hw_name]
    grid_of = grid_of or {}
    m1 = build_multidevice_schedule(nt, tb, 1, policy)
    r1 = simulate_multi(m1, hw, link_bw=link_bw)
    t1 = r1.makespan
    rows = []
    for p in ndevs:
        if p == 1:
            msched, r = m1, r1
        else:
            msched = build_multidevice_schedule(nt, tb, p, policy,
                                                grid=grid_of.get(p))
            r = simulate_multi(msched, hw, link_bw=link_bw)
        rows.append({
            "ndev": p,
            "grid": list(msched.grid),
            "hw": hw_name,
            "policy": policy,
            "makespan": r.makespan,
            "tflops": r.tflops,
            "speedup": t1 / r.makespan,
            "efficiency": t1 / (p * r.makespan),
            "compute_efficiency": r.compute_efficiency,
            "bcast_bytes": msched.bcast_bytes(),
            "link_busy": r.link_busy,
        })
    return rows
