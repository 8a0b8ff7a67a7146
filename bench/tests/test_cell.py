"""A cell's configuration: its device layout, the solver it builds, and a
rehearsal of a 2x2 grid configuration through ``run_cell`` on four CPU
devices."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from bench import cell as cells

ROOT = Path(__file__).resolve().parents[2]
GRID = "bench/tests/grid2x2-f32.json"     # a fixture, in no cell


def _spec(tmp_path, config_file, chips):
    """A copy of ``BENCHMARK.json`` whose f32 cell runs ``config_file`` on
    ``chips`` chips."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        if c["name"] == "matern24k-f32":
            c["file"] = config_file
    for w in spec["workloads"]:
        if w["name"] == "matern24k-f32.factor":
            w["chips"] = chips
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    return path


@pytest.mark.parametrize("config_file, chips, ndev", [
    ("bench/configs/matern24k-f32.json", 4, 1),
    (GRID, 1, 4),
])
def test_a_cell_whose_chips_differ_from_its_layout_is_refused(
        tmp_path, config_file, chips, ndev):
    with pytest.raises(ValueError, match=f"asks for {chips} chips.*"
                                         f"ndev={ndev}"):
        cells.load("matern24k-f32.factor", _spec(tmp_path, config_file,
                                                  chips))
    assert cells.load("matern24k-f32.factor", _spec(
        tmp_path, config_file, ndev))["chips"] == ndev


def test_layout_refuses_an_unknown_key():
    with pytest.raises(ValueError, match="layout keys"):
        cells.layout({"layout": {"ndev": 4, "grid": [2, 2], "devices": 4}})


def _as_before(config):
    """The ``CholeskyConfig`` a configuration built before it could state
    a layout."""
    import repro
    from repro.core.precision import EPS, LADDERS, PrecisionPlan
    p = config["precision"]
    ladder = LADDERS[p["ladder"]]
    classes = np.array([[ladder.index(c) for c in row]
                        for row in cells.class_map(config)], dtype=np.int8)
    eps = p["eps_target"] if p["kind"] == "map" else EPS[p["class"]]
    return repro.CholeskyConfig(tb=config["tb"], policy=config["policy"],
                                backend="jax",
                                plan=PrecisionPlan(classes, ladder, eps))


@pytest.mark.parametrize("name", ["matern24k-f32", "matern24k-mxp"])
def test_a_committed_configuration_builds_the_config_it_built(name):
    import dataclasses
    config = cells.load(f"{name}.factor")["config"]
    assert "layout" not in config and cells.layout(config) == {"ndev": 1}
    want = _as_before(config)
    got = cells.make_solver(config).config
    for f in dataclasses.fields(want):
        assert getattr(got, f.name) == getattr(want, f.name), f.name
    assert got == want


def test_the_grid_layout_reaches_the_solver():
    config = json.loads((ROOT / GRID).read_text())
    cfg = cells.solver_config(config)
    assert (cfg.ndev, cfg.grid) == (4, (2, 2))


REHEARSAL = """
    import json, sys
    sys.path[:0] = [{root!r}, {src!r}]
    from pathlib import Path
    from bench import cell as cells, run, work
    run.CACHE = Path({cache!r})      # a trace directory of its own
    table = work.peaks("TPU v5 lite")
    work.peaks = lambda kind: table
    cell = cells.load("matern24k-f32.factor", {spec!r})
    made = []
    make = cells.make_solver
    cells.make_solver = lambda c: made.append(make(c)) or made[-1]
    for trace in (False, True):
        result = run.run_cell(cell, 2**31 + 17, 0.3, trace,
                              require_tpu=False)
        result["transfers"] = made[-1].transfer_stats()
        print(json.dumps(result))
"""


def test_a_2x2_grid_configuration_runs_through_run_cell(tmp_path):
    """The fixture's 2x2 layout, untraced and traced, on four CPU devices:
    correct, through the multi-device executor, every share a share."""
    spec = _spec(tmp_path, GRID, 4)
    code = textwrap.dedent(REHEARSAL.format(
        root=str(ROOT), src=str(ROOT / "src"), spec=str(spec),
        cache=str(tmp_path / "cache")))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=600, env=env, cwd=tmp_path)
    assert proc.returncode == 0, proc.stderr[-3000:]
    plain, traced = [json.loads(line)
                     for line in proc.stdout.splitlines()[-2:]]
    shares = {m["name"] for m in cells.load("matern24k-f32.factor")[
        "per_layer"] if m["unit"] == "%"}
    for result in (plain, traced):
        assert result["correct"], result["checks"]
        assert result["failed"] == 0 and result["attempted"] > 0
        dev = result["device"]
        assert (dev["count"], dev["ndev"], dev["grid"]) == (4, 4, [2, 2])
        # the factor went through MultiDeviceJaxExecutor: tiles crossed
        # between devices
        assert result["transfers"]["bcast_bytes"] > 0
    assert {"factor_s", "setup_s"} <= set(plain["metrics"])
    got = traced["metrics"]
    assert shares <= set(got)
    for name in shares:
        assert 0 < got[name]["value"] <= 100, (name, got[name])
    assert 0 < traced["device"]["busy_s"] < traced["device"]["window_s"]
    assert got["host_s.factor"]["value"] > 0
