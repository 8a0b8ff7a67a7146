"""The control reads worse than the program on every seed.

``bench/control.py`` runs this at the cells' own size on the chip, where
its readings set each limit (PERF.md gives them).  Here it runs at a
size a test run holds, on the CPU.
"""
import pytest

from bench import control

SEEDS = [5, 6, 2**31 + 7]


@pytest.mark.parametrize("config", ["matern24k-f32", "matern24k-mxp"])
def test_control_reads_worse_than_the_program(cell_of, config):
    cell = cell_of(config, 2048, 256)
    got = {"program": [], "control": []}
    for who, _, err in control.readings(cell, SEEDS, SEEDS):
        got[who].append(err)
    assert len(got["program"]) == len(got["control"]) == len(SEEDS)
    assert min(got["control"]) > 2 * max(got["program"]), got


def test_the_mixed_precision_control_demotes_every_tile_once(cell_of):
    cfg = cell_of("matern24k-mxp", 2048, 256)["config"]
    lower = control.demoted(cfg)["precision"]["classes"]
    step = {"f64": "f32", "f32": "bf16", "bf16": "f8e4m3",
            "f8e4m3": "f8e4m3"}
    assert lower == [[step[c] for c in row]
                     for row in cfg["precision"]["classes"]]
