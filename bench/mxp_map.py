"""Print the per-tile precision class map of a Matérn configuration.

    python bench/mxp_map.py bench/configs/matern24k-mxp.json

For each of the configuration's ``plan_seeds`` the program's own plan
(``CholeskyConfig.specialize``) is made for that seed's matrix; each
tile takes the highest class any of them gives it.  One
seed's plan does not fit another seed's matrix: the seed moves which
points share a Morton block, so a tile that holds far-apart points for
one seed holds neighbours for another.  The map is committed in the
configuration file, so every run compiles the same program whatever its
``--seed``.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import matern  # noqa: E402
from repro.core.api import CholeskyConfig  # noqa: E402
from repro.core.precision import LADDERS  # noqa: E402


def seed_plan(cfg: dict, seed: int) -> np.ndarray:
    """Class indices of the program's plan for the matrix of ``seed``:
    ``CholeskyConfig.specialize`` on that matrix."""
    m, p = cfg["matrix"], cfg["precision"]
    locs = matern.generate_locations(cfg["n"], seed)
    a = matern.matern_covariance(locs, m["sigma2"], m["beta"], m["nu"],
                                 m["nugget"])
    plan = CholeskyConfig(tb=cfg["tb"], eps_target=p["eps_target"],
                          ladder=p["ladder"]).specialize(a).plan
    return np.asarray(plan.classes)


def class_map(cfg: dict) -> dict:
    p = cfg["precision"]
    # index 0 is the highest class of the ladder
    classes = np.min([seed_plan(cfg, s) for s in p["plan_seeds"]], axis=0)
    ladder = LADDERS[p["ladder"]]
    names = [[ladder[c] for c in row] for row in classes]
    hist = {k: 0 for k in ladder}
    for i in range(len(names)):
        for j in range(i + 1):
            hist[names[i][j]] += 1
    return {"classes": names, "histogram_lower": hist}


if __name__ == "__main__":
    print(json.dumps(class_map(json.loads(Path(sys.argv[1]).read_text()))))
