"""A run with its timed path broken underneath comes out not correct.

The harness runs here past its look for a chip, on a cell cut to a small
n, with the full-size cell's limit.  Each fault is planted in the
program's ``OOCSolver.factor``, on the tile store the timed call leaves,
or in ``OOCSolver.solve``, on the answer it returns.  A one-chip cell has
no exchange between chips to leave out.
"""
import numpy as np
import pytest

from bench import run
from repro.core.api import OOCSolver
from repro.core.tiling import to_tiles


def _plant(monkeypatch, fault):
    real = OOCSolver.factor

    def factor(self, a, materialize=True, trace=None):
        if fault == "previous store kept" and self._tiles is not None:
            return None         # a factor that does nothing after the first
        out = real(self, a, materialize=materialize, trace=trace)
        tiles = self._tiles
        nt = tiles.shape[0]
        given = to_tiles(np.asarray(a, dtype=np.float64), self.config.tb)
        if fault == "input returned as the factor":
            self._tiles = given
        elif fault == "half left out":
            tiles[:, nt // 2:] = given[:, nt // 2:]
        elif fault == "answer altered":
            tiles[nt - 1, nt - 1, -1, 0] += 1.0
        elif fault == "not positive definite":
            tiles[nt - 1, nt - 1, -1, -1] = -1.0
        elif fault == "not finite":
            tiles[nt - 1, nt - 1, -1, -1] = np.nan
        return out

    monkeypatch.setattr(OOCSolver, "factor", factor)


@pytest.mark.parametrize("config", ["matern24k-f32", "matern24k-mxp"])
@pytest.mark.parametrize("fault", [None, "previous store kept",
                                   "input returned as the factor",
                                   "half left out", "answer altered",
                                   "not positive definite", "not finite"])
def test_a_fault_comes_out_not_correct(monkeypatch, cell_of, config, fault):
    cell = cell_of(config, 1024, 256)
    if fault:
        _plant(monkeypatch, fault)
    result = run.run_cell(cell, 2**31 + 3, 0.1, False, require_tpu=False)
    check = result["checks"]["backward_error"]
    assert result["correct"] is (fault is None), check
    assert result["attempted"] >= 1
    assert result["failed"] == (0 if fault is None
                                else result["attempted"]
                                if fault in ("not positive definite",
                                             "not finite") else 1)
