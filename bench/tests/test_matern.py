"""The benchmark's Matérn copy equals ``repro.geo.matern`` value for value."""
import numpy as np
import pytest

from bench import matern
from repro.geo import matern as program


@pytest.mark.parametrize("n,seed", [(300, 0), (1000, 7), (777, 2**31 + 5)])
@pytest.mark.parametrize("nu", [0.5, 1.5, 2.5])
def test_copy_matches_program(n, seed, nu):
    locs = matern.generate_locations(n, seed)
    assert np.array_equal(locs, program.generate_locations(n, seed=seed))
    a = matern.matern_covariance(locs, 1.0, 0.078809, nu, 0.1, threads=4)
    b = program.matern_covariance(locs, sigma2=1.0, beta=0.078809, nu=nu,
                                  nugget=0.1)
    assert np.array_equal(a, b)
