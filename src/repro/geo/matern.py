"""Matérn covariance construction (paper §III-D, Eq. 2).

C(h; θ) = σ²/(2^{ν−1}Γ(ν)) (h/a)^ν K_ν(h/a),   θ = (σ², a, ν)

The paper's experiments use ν = 0.5 (exponential kernel) with spatial
range β ∈ {0.02627, 0.078809, 0.210158} for weak/medium/strong correlation.
Covariance assembly is a host-side data-generation step (float64, SciPy
Bessel for general ν, closed forms for ν ∈ {1/2, 3/2, 5/2}); the
factorization of the resulting Σ is the device workload.
"""
from __future__ import annotations

import numpy as np

# paper's three correlation regimes (β = spatial range a)
BETA_WEAK = 0.02627
BETA_MEDIUM = 0.078809
BETA_STRONG = 0.210158

# entries per row block of matern_covariance (32 MiB of f64 per temporary)
_BLOCK_ELEMS = 1 << 22


def _morton_key(pts: np.ndarray, bits: int = 16) -> np.ndarray:
    """Z-order (Morton) key per point — ExaGeoStat orders locations this way
    so that covariance tiles correspond to spatial blocks and off-diagonal
    tile norms decay (that decay is what the MxP criterion harvests)."""
    q = np.clip((pts * (2**bits - 1)).astype(np.uint64), 0, 2**bits - 1)

    def spread(x):
        x = x.astype(np.uint64)
        x = (x | (x << np.uint64(16))) & np.uint64(0x0000FFFF0000FFFF)
        x = (x | (x << np.uint64(8))) & np.uint64(0x00FF00FF00FF00FF)
        x = (x | (x << np.uint64(4))) & np.uint64(0x0F0F0F0F0F0F0F0F)
        x = (x | (x << np.uint64(2))) & np.uint64(0x3333333333333333)
        x = (x | (x << np.uint64(1))) & np.uint64(0x5555555555555555)
        return x

    return spread(q[:, 0]) | (spread(q[:, 1]) << np.uint64(1))


def generate_locations(n: int, seed: int = 0) -> np.ndarray:
    """Irregular locations on the unit square, Morton-ordered
    (ExaGeoStat-style jittered grid + space-filling-curve ordering)."""
    rng = np.random.default_rng(seed)
    side = int(np.ceil(np.sqrt(n)))
    xs, ys = np.meshgrid(np.arange(side), np.arange(side))
    pts = np.stack([xs.ravel(), ys.ravel()], axis=1).astype(np.float64)
    pts += rng.uniform(-0.4, 0.4, size=pts.shape)
    pts = (pts - pts.min(0)) / (pts.max(0) - pts.min(0))
    idx = rng.permutation(pts.shape[0])[:n]
    pts = pts[idx]
    order = np.argsort(_morton_key(pts))
    return pts[order]


def _matern_kernel(h: np.ndarray, nu: float) -> np.ndarray:
    """Unit-variance Matérn correlation at scaled distances ``h = d / a``."""
    if nu == 0.5:
        return np.exp(-h)
    if nu == 1.5:
        s = np.sqrt(3.0) * h
        return (1.0 + s) * np.exp(-s)
    if nu == 2.5:
        s = np.sqrt(5.0) * h
        return (1.0 + s + s * s / 3.0) * np.exp(-s)
    from scipy.special import kv, gamma
    hp = np.where(h == 0.0, 1.0, h)
    c = (2.0 ** (1.0 - nu) / gamma(nu)) * (hp ** nu) * kv(nu, hp)
    return np.where(h == 0.0, 1.0, c)


def matern_covariance(locs: np.ndarray, sigma2: float = 1.0,
                      beta: float = BETA_MEDIUM, nu: float = 0.5,
                      nugget: float = 1e-6) -> np.ndarray:
    """Dense Matérn covariance matrix Σ_θ over the given locations.

    Built in row blocks of about ``_BLOCK_ELEMS`` entries, so host memory
    peaks at the ``n x n`` result plus one block's temporaries (a single
    ``n x n x 2`` difference array is 17 GB at ``n = 32768``).
    """
    n = locs.shape[0]
    cov = np.empty((n, n), dtype=np.float64)
    rows = max(1, _BLOCK_ELEMS // max(n, 1))
    for r0 in range(0, n, rows):
        blk = locs[r0:r0 + rows]
        d = np.sqrt(((blk[:, None, :] - locs[None, :, :]) ** 2).sum(-1))
        cov[r0:r0 + rows] = sigma2 * _matern_kernel(d / beta, nu)
    cov[np.diag_indices_from(cov)] += nugget * sigma2
    return cov
