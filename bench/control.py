"""Readings that set a cell's limit: the program's and its control's.

    python bench/control.py --workload <cell> --seeds 1,2,3 --control-seeds 1,2,3

For each seed of ``--seeds`` the program runs one step of the cell's
traffic (``READ_STEP``, with that step's nugget) on that seed's matrix
and is judged as ``run.py`` judges its last step; for each of
``--control-seeds`` the control is put in its place and judged the same
way.  One JSON line per reading; each solver is compiled once.

The control is the step below the precision the configuration states:

* uniform f32 tiles, whose tile products run at ``Precision.HIGHEST``:
  the plain blocked reference (``bench/reference.Solver``) with its
  trailing updates in three bfloat16 passes, which is ``Precision.HIGH``;
* a per-tile class map: the program itself with every tile one class
  lower on its ladder (the lowest class stays).
"""
from __future__ import annotations

import argparse
import copy
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import cell as cells  # noqa: E402
from bench import loop, reference, run  # noqa: E402


def demoted(config: dict) -> dict:
    """The configuration with every tile one class lower on its ladder."""
    from repro.core.precision import LADDERS
    ladder = LADDERS[config["precision"]["ladder"]]
    out = copy.deepcopy(config)
    out["precision"]["classes"] = [
        [ladder[min(ladder.index(c) + 1, len(ladder) - 1)] for c in row]
        for row in cells.class_map(config)]
    return out


# the step that ends a window of four factors, the window's usual count
READ_STEP = 3


def reading(solver, config, traffic, seed) -> float:
    """The number ``run.py`` compares, for the solver in the program's
    place: step ``READ_STEP`` of the seed's traffic on the seed's matrix,
    judged as a run judges its last step.  A step that fails reads
    infinity."""
    a = cells.build_matrix(config, seed)
    d = loop.Driver(solver, a, config, traffic, seed)
    ok = d.step(READ_STEP)
    err = loop.judge(a, d.finish())
    return err if ok else float("inf")


def control_solver(config):
    """The control, in the program's place."""
    p = config["precision"]
    if p["kind"] == "map":
        return cells.make_solver(demoted(config))
    if p["class"] != "f32":
        raise ValueError(f"no control for uniform {p['class']} tiles")
    return reference.Solver(config["tb"], reference.dot_high)


def readings(cell: dict, seeds, control_seeds):
    """``("program" | "control", seed, backward error)`` for every seed."""
    import jax
    config, traffic = cell["config"], cell["traffic"]
    jax.config.update("jax_enable_x64", bool(config["x64"]))
    for who, make, ss in (("program", cells.make_solver, seeds),
                          ("control", control_solver, control_seeds)):
        if not ss:
            continue
        solver = make(config)
        for s in ss:
            yield who, s, reading(solver, config, traffic, s)
        del solver
        gc.collect()


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]
    import jax
    run.use_compile_cache()
    if jax.devices()[0].platform != "tpu":
        sys.exit(f"no TPU: JAX sees {jax.devices()[0].platform}")
    cell = cells.load(args.workload)
    t0 = time.perf_counter()
    for who, s, err in readings(cell, seeds, control_seeds):
        print(json.dumps({"workload": args.workload, "who": who, "seed": s,
                          "backward_error": err,
                          "t": time.perf_counter() - t0}), flush=True)


if __name__ == "__main__":
    main()
