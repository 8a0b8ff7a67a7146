"""Matérn covariance assembly (geo/matern.py)."""
import numpy as np
import pytest

from repro.geo import matern
from repro.geo.matern import BETA_MEDIUM, generate_locations, matern_covariance


def _dense_formula(locs, sigma2, beta, nu, nugget):
    """The single-shot construction over an ``n x n x 2`` difference
    array, kept as the reference for the row-blocked build."""
    d = np.sqrt(((locs[:, None, :] - locs[None, :, :]) ** 2).sum(-1))
    h = d / beta
    if nu == 0.5:
        c = np.exp(-h)
    elif nu == 1.5:
        s = np.sqrt(3.0) * h
        c = (1.0 + s) * np.exp(-s)
    elif nu == 2.5:
        s = np.sqrt(5.0) * h
        c = (1.0 + s + s * s / 3.0) * np.exp(-s)
    else:
        from scipy.special import kv, gamma
        hp = np.where(h == 0.0, 1.0, h)
        c = (2.0 ** (1.0 - nu) / gamma(nu)) * (hp ** nu) * kv(nu, hp)
        c = np.where(h == 0.0, 1.0, c)
    cov = sigma2 * c
    cov[np.diag_indices_from(cov)] += nugget * sigma2
    return cov


@pytest.mark.parametrize("nu", [0.5, 1.5, 2.5, 1.0])
def test_row_blocked_build_equals_dense_formula(monkeypatch, nu):
    """Blocking changes no arithmetic: the result is bitwise the dense
    formula, with blocks that split rows unevenly (7 rows of 150)."""
    locs = generate_locations(150, seed=4)
    monkeypatch.setattr(matern, "_BLOCK_ELEMS", 7 * 150)
    got = matern_covariance(locs, sigma2=1.3, beta=BETA_MEDIUM, nu=nu,
                            nugget=1e-3)
    want = _dense_formula(locs, 1.3, BETA_MEDIUM, nu, 1e-3)
    np.testing.assert_array_equal(got, want)
