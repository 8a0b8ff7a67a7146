"""The traffic generator: what a traffic file's parameters make it do."""
import copy

import numpy as np
import pytest

from bench import checks, loop, run
from repro.core.api import OOCSolver

MLE_STEP = [{"op": "factor"}, {"op": "logdet"}, {"op": "solve", "rhs": 2}]


def _traffic(cell, **kw):
    t = copy.deepcopy(cell["traffic"])
    t.update(kw)
    return t


def test_the_committed_mix_is_valid(cell_of):
    loop.validate(cell_of("matern24k-f32", 1024, 256)["traffic"])


@pytest.mark.parametrize("change", [
    {"step": []}, {"step": [{"op": "refactor"}]},
    {"step": [{"op": "solve"}]}, {"loop": "batched"},
    {"loop": "open"}, {"clients": 2},
    {"theta": {"nugget_scale": [1.0, 1.0, 2.0], "warmup_nugget_scale": 3}},
    {"theta": {"nugget_scale": [1.0, 2.0, 1.0], "warmup_nugget_scale": 3}},
    {"theta": {"nugget_scale": [1.0], "warmup_nugget_scale": 3}},
    {"theta": {"nugget_scale": [1.0, 2.0], "warmup_nugget_scale": 2.0}},
    {"metrics": {"factor_s": "mean"}}, {"check_rhs": 0},
])
def test_a_mix_it_cannot_drive_is_refused(cell_of, change):
    with pytest.raises(ValueError):
        loop.validate(_traffic(cell_of("matern24k-f32", 1024, 256),
                               **change))


def test_every_step_moves_the_nugget_and_warm_up_uses_none_of_them(
        cell_of):
    cell = cell_of("matern24k-f32", 64, 32)
    a = np.eye(64)
    runs = [loop.Driver(None, a, cell["config"], cell["traffic"], seed)
            for seed in (5, 5, 2**31 + 11)]
    seq = [[d.shift(k) for k in range(12)] for d in runs]
    assert seq[0] == seq[1]
    for s, d in zip(seq, runs):
        assert all(x != y for x, y in zip(s, s[1:]))
        assert d.shift(-1) not in s
        assert sorted(set(s)) == sorted(
            d.unit * (c - 1) for c in cell["traffic"]["theta"]
            ["nugget_scale"])


def test_backward_error_with_a_shift_is_that_of_the_shifted_matrix():
    rng = np.random.default_rng(3)
    g = rng.standard_normal((300, 300))
    a = g @ g.T / 300
    x, b = rng.standard_normal((300, 2)), rng.standard_normal((300, 2))
    assert checks.backward_error(a, x, b, shift=0.7) == pytest.approx(
        checks.backward_error(a + 0.7 * np.eye(300), x, b), rel=1e-12)


def test_an_mle_step_mix_judges_its_solves(cell_of):
    cell = cell_of("matern24k-mxp", 1024, 256)
    cell["traffic"] = _traffic(cell, step=MLE_STEP)
    result = run.run_cell(cell, 2**31 + 9, 0.2, False, require_tpu=False)
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {"factor_s", "peak_hbm_gb",
                                      "setup_s"}


def test_an_open_loop_of_solves_reports_its_latency(cell_of):
    cell = cell_of("matern24k-f32", 1024, 256)
    cell["traffic"] = _traffic(
        cell, step=[{"op": "solve", "rhs": 1}], loop="open",
        rate_per_s=40.0, metrics={"solve_p95_ms": "latency_p95_ms",
                                  "solves_per_s": "steps_per_s"})
    cell["end_to_end"] = [{"name": "solve_p95_ms", "unit": "ms"},
                          {"name": "solves_per_s", "unit": "1/s"},
                          {"name": "setup_s", "unit": "s"}]
    result = run.run_cell(cell, 77, 0.5, False, require_tpu=False)
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 5
    assert 0 < result["metrics"]["solve_p95_ms"]["value"] < 500
    assert result["metrics"]["solves_per_s"]["value"] < 100


def test_a_solve_whose_answer_is_altered_comes_out_not_correct(
        monkeypatch, cell_of):
    real = OOCSolver.solve

    def solve(self, b):
        x = real(self, b)
        x[0] += 1.0
        return x

    monkeypatch.setattr(OOCSolver, "solve", solve)
    cell = cell_of("matern24k-f32", 1024, 256)
    cell["traffic"] = _traffic(cell, step=MLE_STEP)
    result = run.run_cell(cell, 2**31 + 9, 0.2, False, require_tpu=False)
    assert result["correct"] is False
