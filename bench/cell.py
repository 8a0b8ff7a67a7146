"""A cell of ``BENCHMARK.json``: its configuration, traffic, limits and
metrics, found by name, and the matrix and solver it runs."""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from bench import matern

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def _applies(metric: dict, name: str) -> bool:
    return "workloads" not in metric or name in metric["workloads"]


def load(name: str, spec_path: Path = ROOT / "BENCHMARK.json") -> dict:
    """The cell ``name`` with its configuration, traffic and limits read
    from their files, and the metrics ``BENCHMARK.json`` gives it.  A
    workload whose ``chips`` differ from its configuration's ``ndev`` is
    refused: the layout lives in the configuration alone."""
    spec = json.loads(Path(spec_path).read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((ROOT / cfg["file"]).read_text())
    ndev = layout(config)["ndev"]
    if w["chips"] != ndev:
        raise ValueError(f"workload {name!r} asks for {w['chips']} chips, "
                         f"its configuration {w['config']!r} lays the "
                         f"factor out over ndev={ndev}")
    return {
        "name": name,
        "chips": w["chips"],
        "config": config,
        "traffic": json.loads(
            (BENCH / "traffic" / f"{w['traffic']}.json").read_text()),
        "limits": json.loads(
            (BENCH / "limits" / f"{name}.json").read_text()),
        "end_to_end": [m for m in spec["end_to_end"] if _applies(m, name)],
        "per_layer": [m for m in spec["per_layer"] if _applies(m, name)],
    }


def layout(config: dict) -> dict:
    """The device layout the configuration states: ``ndev`` and, where
    given, ``grid`` (p, q); one device where the file has no ``layout``."""
    lay = config.get("layout", {"ndev": 1})
    unknown = set(lay) - {"ndev", "grid"}
    if unknown:
        raise ValueError(f"layout keys {sorted(unknown)}: it states ndev "
                         f"and grid")
    return lay


def build_matrix(config: dict, seed: int) -> np.ndarray:
    """The configuration's f64 host matrix for ``seed``."""
    m = config["matrix"]
    if m["kind"] != "matern":
        raise ValueError(f"matrix kind {m['kind']!r}")
    locs = matern.generate_locations(config["n"], seed % 2**64)
    return matern.matern_covariance(locs, m["sigma2"], m["beta"], m["nu"],
                                    m["nugget"])


def class_map(config: dict) -> list:
    """The ``nt x nt`` map of tile class names the configuration states."""
    nt = config["n"] // config["tb"]
    p = config["precision"]
    if p["kind"] == "uniform":
        return [[p["class"]] * nt for _ in range(nt)]
    classes = p["classes"]
    if (len(classes) != nt
            or any(len(row) != nt for row in classes)):
        raise ValueError(f"class map is not {nt} x {nt}")
    return [list(row) for row in classes]


def classes_as_run(config: dict) -> list:
    """The class map as the chip runs it: with x64 off the f64 class
    computes and is stored as f32."""
    x64 = config["x64"]
    return [[("f32" if c == "f64" and not x64 else c) for c in row]
            for row in class_map(config)]


def solver_config(config: dict):
    """The ``repro.CholeskyConfig`` the configuration states."""
    import repro
    from repro.core.precision import EPS, LADDERS, PrecisionPlan
    p = config["precision"]
    ladder = LADDERS[p["ladder"]]
    classes = np.array([[ladder.index(c) for c in row]
                        for row in class_map(config)], dtype=np.int8)
    eps = p["eps_target"] if p["kind"] == "map" else EPS[p["class"]]
    lay = layout(config)
    return repro.CholeskyConfig(
        tb=config["tb"], policy=config["policy"], backend="jax",
        plan=PrecisionPlan(classes, ladder, eps), ndev=lay["ndev"],
        grid=tuple(lay["grid"]) if "grid" in lay else None)


def make_solver(config: dict):
    """``repro.plan(n, cfg).compile()`` for the configuration."""
    import repro
    return repro.plan(config["n"], solver_config(config)).compile()
