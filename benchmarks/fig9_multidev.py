"""Fig. 9: multi-device scaling of the block-cyclic Cholesky, 1D vs 2D.

Measured, two runtimes on forced host devices (subprocess; correctness
asserted against LAPACK):

* the *static-schedule executor* on 1/2/4 devices — per-device op
  streams replayed by ``make_multidevice_jax_executor`` through the
  public planner API (``CholeskyConfig(ndev=..., backend='jax')``),
  executed BCAST/RECV bytes cross-checked against the schedule; this is
  the run the modeled numbers below describe op for op.  At 4 devices
  both the paper's 1D tile-row layout and the 2D ``(2, 2)`` grid run,
  and their *executed* interconnect bytes are reported side by side
  (the 2D grid must move strictly less — the PR 5 acceptance bar,
  recorded in ``BENCH_fig9.json``);
* the shard_map einsum reference baseline (``distributed_cholesky``) on
  1/2/4/8 devices.

Modeled: event simulation of the same static op streams
(`build_multidevice_schedule` + `simulate_multi`) on the paper's
platforms — per-device H2D/D2H/compute engines plus the shared
interconnect carrying the scoped broadcasts.  The qualitative Fig. 9
claim is the interconnect story: the faster link (NVLink-C2C on GH200)
keeps parallel compute efficiency high where the PCIe-class platforms
drown in broadcast traffic — the 2D grid shrinks the broadcast itself,
and lookahead pipelining (PR 6) closes the 2D compute-bound gap by
overlapping the next panels with the trailing update, so the modeled
``(2, 2)`` geometry beats ``(4, 1)`` on *both* the link-bound and
compute-bound models (docs/multidevice.md walks through the geometry).

Every geometry x lookahead x hardware-preset efficiency lands in
``benchmarks/out/BENCH_fig9.json`` — written by :func:`run` itself, so
the record exists even outside the ``benchmarks.run`` driver — which is
the cross-PR trajectory for the 0.48 -> parity movement on gh200.
"""
import json
import os
import pathlib
import subprocess
import sys
import textwrap

from repro.core.analytics import HW, crosscheck_executed_volume, simulate_multi
from repro.core.distributed import (grid_broadcast_bytes, modeled_scaling,
                                    panel_broadcast_bytes)
from repro.core.schedule import build_multidevice_schedule

_REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]
_SRC = _REPO_ROOT / "src"
_OUT_JSON = _REPO_ROOT / "benchmarks" / "out" / "BENCH_fig9.json"


def _run_timed_raw(code: str, devices: int) -> str:
    env = dict(os.environ)
    # the children measure forced host devices by design: pin them to the
    # CPU so none reaches for an accelerator the parent may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.pathsep.join(
        [str(_SRC)] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=900, env=env,
                       cwd=str(_REPO_ROOT))
    assert p.returncode == 0, p.stderr[-2000:]
    return p.stdout


def _run_timed(code: str, devices: int) -> float:
    return float(_run_timed_raw(code, devices).split("TIME")[1])


def _measure(devices: int, n: int, tb: int) -> float:
    """Shard_map einsum reference baseline (core/distributed.py)."""
    return _run_timed(f"""
        import time, numpy as np, jax
        jax.config.update('jax_enable_x64', True)
        from repro.core.distributed import distributed_cholesky
        mesh = jax.make_mesh(({devices},), ('model',))
        rng = np.random.default_rng(0)
        x = rng.standard_normal(({n}, {n})); a = x @ x.T + {n}*np.eye({n})
        distributed_cholesky(a, {tb}, mesh)          # warm-up/compile
        t0 = time.time()
        L = distributed_cholesky(a, {tb}, mesh)
        dt = time.time() - t0
        err = np.abs(L - np.linalg.cholesky(a)).max()
        assert err < 1e-10, err
        print('TIME', dt)
    """, devices)


def _measure_static(devices: int, n: int, tb: int, grid=None,
                    lookahead=None) -> tuple[float, dict]:
    """Static-schedule executor through the planner API: per-device
    jitted op streams + device-to-device scoped broadcasts, executed
    transfer volume cross-checked against the schedule.  Returns
    ``(seconds, executed transfer stats)``."""
    out = _run_timed_raw(f"""
        import json, time, numpy as np, jax
        jax.config.update('jax_enable_x64', True)
        import repro
        from repro.core.analytics import crosscheck_executed_volume
        rng = np.random.default_rng(0)
        x = rng.standard_normal(({n}, {n})); a = x @ x.T + {n}*np.eye({n})
        cfg = repro.CholeskyConfig(tb={tb}, policy='v3', ndev={devices},
                                   grid={grid!r}, lookahead={lookahead!r},
                                   backend='jax' if {devices} > 1 else 'auto')
        solver = repro.plan({n}, cfg).compile()
        solver.factor(a)                             # warm-up/compile
        t0 = time.time()
        L = solver.factor(a)
        dt = time.time() - t0
        err = np.abs(L - np.linalg.cholesky(a)).max()
        assert err < 1e-10, err
        stats = {{}}
        if {devices} > 1:
            cc = crosscheck_executed_volume(solver.schedule,
                                            solver.transfer_stats())
            assert cc['match'], cc['mismatches']
            stats = solver.transfer_stats()
        print('TIME', dt)
        print('STATS', json.dumps(stats))
    """, devices)
    dt = float(out.split("TIME")[1].split("\n")[0])
    stats = json.loads(out.split("STATS")[1].strip())
    return dt, stats


def run(out):
    data = {}
    out("== Fig. 9: multi-device scaling (block-cyclic, 1D + 2D grids) ==")
    n, tb = 512, 32
    out(f"[measured, host devices] matrix {n}x{n}, tile {tb} "
        f"(CPU wall-clock; correctness asserted)")
    out("  static-schedule executor (per-device op streams, V3; "
        "executed bcast bytes == schedule):")
    data["measured_static"] = []
    for d in (1, 2, 4):
        dt, stats = _measure_static(d, n, tb)
        out(f"    {d} device(s): {dt*1e3:8.1f} ms")
        data["measured_static"].append(
            {"ndev": d, "seconds": dt, "executed": stats})
    out("  shard_map einsum reference baseline:")
    for d in (1, 2, 4, 8):
        dt = _measure(d, n, tb)
        out(f"    {d} device(s): {dt*1e3:8.1f} ms")

    # --- 1D vs 2D ownership at ndev=4, NT=8 (the acceptance geometry),
    # --- plus the pipelined (2, 2) at lookahead=1: executed == scheduled
    # --- == simulated bytes asserted for every case, lookahead included
    nt8 = 8
    tb8 = n // nt8
    out(f"[measured, 4 host devices] 1D (4,1) vs 2D (2,2) ownership, "
        f"n={n} tb={tb8} (NT={nt8}); executed == scheduled == simulated, "
        f"asserted:")
    grids = {}
    for grid, la in (((4, 1), 0), ((2, 2), 0), ((2, 2), 1)):
        dt, stats = _measure_static(4, n, tb8, grid=grid,
                                    lookahead=la or None)
        msched = build_multidevice_schedule(nt8, tb8, 4, "v3", grid=grid,
                                            lookahead=la)
        scheduled = msched.bcast_bytes()
        cc = crosscheck_executed_volume(msched, stats, hw=HW["a100-pcie"])
        assert cc["match"], (grid, la, cc["mismatches"])
        sims = {hw: simulate_multi(msched, HW[hw]).makespan
                for hw in ("a100-pcie", "gh200")}
        key = "x".join(map(str, grid)) + (f"_la{la}" if la else "")
        grids[key] = {
            "grid": list(grid), "lookahead": la, "seconds": dt,
            "scheduled_bcast_bytes": scheduled,
            "executed_bcast_bytes": stats["recv_bytes"],
            "simulated_link_bytes": cc["expected"]["simulated_link_bytes"],
            "executed": stats,
            "modeled_makespan_s": sims,
        }
        out(f"    grid {grid} la={la}: {dt*1e3:8.1f} ms   bcast "
            f"{scheduled/1e6:6.2f} MB scheduled == "
            f"{stats['recv_bytes']/1e6:6.2f} MB executed   "
            f"(modeled a100-pcie {sims['a100-pcie']*1e3:.2f} ms)")
    r1d, r2d = grids["4x1"], grids["2x2"]
    assert r2d["executed_bcast_bytes"] < r1d["executed_bcast_bytes"]
    assert r2d["scheduled_bcast_bytes"] < r1d["scheduled_bcast_bytes"]
    # the pipeline moves the same bytes as the plain 2D grid, earlier
    assert (grids["2x2_la1"]["executed_bcast_bytes"]
            == r2d["executed_bcast_bytes"])
    out(f"    => 2D moves {r2d['executed_bcast_bytes']/1e6:.2f} MB vs 1D "
        f"{r1d['executed_bcast_bytes']/1e6:.2f} MB over the interconnect "
        f"({r1d['executed_bcast_bytes']/r2d['executed_bcast_bytes']:.2f}x "
        f"less; O(sqrt P) ownership, docs/multidevice.md), and "
        f"lookahead=1 moves them earlier without adding any")
    data["ndev4_nt8_1d_vs_2d"] = grids

    nt, tbm = 32, 1024
    out(f"[modeled] static per-device op streams, f64 V3, "
        f"n={nt*tbm} tb={tbm} (simulate_multi; exact schedule replay), "
        f"every hardware preset x geometry x lookahead:")
    eff4 = {}
    data["modeled"] = {}
    for hw_name in sorted(HW):
        hw = HW[hw_name]
        out(f"  {hw_name} (link {hw.h2d_bw/1e9:.0f} GB/s):")
        rows = modeled_scaling(nt, tbm, ndevs=(1, 2, 4), hw_name=hw_name)
        t1 = rows[0]["makespan"]
        # per-geometry pipeline sweep at ndev=4, reusing the 1-device
        # baseline already in rows[0]: (4,1) la=0 duplicates rows[2] but
        # keeps the geometry record self-contained
        geometries = []
        for grid in ((4, 1), (2, 2)):
            for la in (0, 1, 2):
                m = build_multidevice_schedule(nt, tbm, 4, "v3", grid=grid,
                                               lookahead=la)
                r = simulate_multi(m, hw)
                geometries.append({
                    "ndev": 4, "grid": list(grid), "lookahead": la,
                    "hw": hw_name, "policy": "v3",
                    "makespan": r.makespan, "tflops": r.tflops,
                    "speedup": t1 / r.makespan,
                    "efficiency": t1 / (4 * r.makespan),
                    "compute_efficiency": r.compute_efficiency,
                    "bcast_bytes": m.bcast_bytes(),
                    "link_busy": r.link_busy,
                })
        data["modeled"][hw_name] = {"scaling": rows,
                                    "geometries": geometries}
        for row in rows:
            out(f"    {row['ndev']} device(s) {str(tuple(row['grid'])):7s}:"
                f" makespan {row['makespan']:7.3f}s"
                f"  {row['tflops']:6.1f} TFlop/s"
                f"  speedup {row['speedup']:4.2f}"
                f"  compute-eff {row['compute_efficiency']*100:5.1f}%"
                f"  bcast {row['bcast_bytes']/1e9:6.2f} GB")
        for row in geometries:
            out(f"    4 device(s) {str(tuple(row['grid'])):7s} la="
                f"{row['lookahead']}: makespan {row['makespan']:7.3f}s"
                f"  speedup {row['speedup']:4.2f}"
                f"  eff {row['efficiency']*100:5.1f}%"
                f"  bcast {row['bcast_bytes']/1e9:6.2f} GB")
        best = {g: min((r for r in geometries if tuple(r["grid"]) == g),
                       key=lambda r: r["makespan"])
                for g in ((4, 1), (2, 2))}
        eff4[hw_name] = best
        out(f"    best (2,2) {best[(2, 2)]['makespan']:.3f}s (la="
            f"{best[(2, 2)]['lookahead']}) vs best (4,1) "
            f"{best[(4, 1)]['makespan']:.3f}s (la="
            f"{best[(4, 1)]['lookahead']})")
    # the PR 6 acceptance bar: pipelined (2, 2) beats (4, 1) on BOTH the
    # link-bound and the compute-bound model (pre-lookahead, gh200 ran
    # (2, 2) at 0.48 efficiency vs (4, 1) at 0.74)
    data["win_2d"] = {}
    for hw_name in ("a100-pcie", "gh200"):
        b22, b41 = eff4[hw_name][(2, 2)], eff4[hw_name][(4, 1)]
        assert b22["makespan"] < b41["makespan"], (hw_name, b22, b41)
        data["win_2d"][hw_name] = {
            "best_2x2": b22, "best_4x1": b41,
            "speedup_2x2_over_4x1": b41["makespan"] / b22["makespan"],
        }
        out(f"  => {hw_name}: pipelined (2,2) beats (4,1) by "
            f"{b41['makespan'] / b22['makespan']:.2f}x "
            f"(la={b22['lookahead']})")
    out("  => the (2, 2) grid moves fewer broadcast bytes *and*, with "
        "lookahead pipelining the panel/broadcast critical path behind "
        "the other grid column's trailing update, now also wins makespan "
        "on the compute-bound model — the tuner's lookahead dimension "
        "scores this per hardware model (docs/multidevice.md)")

    out("[analytic] broadcast volume (matches the schedules exactly):")
    for p in (2, 4):
        out(f"  {p} device(s) 1D: "
            f"{panel_broadcast_bytes(nt, tbm, p)/1e9:.2f} GB")
    out(f"  4 device(s) (2,2): "
        f"{grid_broadcast_bytes(nt, tbm, (2, 2))/1e9:.2f} GB")
    out("")
    # always leave the machine-readable record behind, even when invoked
    # outside benchmarks.run (whose fuller record overwrites this one)
    _OUT_JSON.parent.mkdir(parents=True, exist_ok=True)
    with open(_OUT_JSON, "w") as f:
        json.dump({"bench": "fig9", "ok": True, "data": data}, f,
                  indent=1, sort_keys=True, default=str)
    out(f"wrote {_OUT_JSON}")
    return data


if __name__ == "__main__":
    run(print)
