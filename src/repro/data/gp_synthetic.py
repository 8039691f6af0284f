"""Synthetic GP regression datasets — paper §3, Eq. 21.

    y = sum_{i=1..p} cos(x_i) + nu,   nu ~ N(0, sigma_n^2)

The paper's bash script generates these with increasing n and p at fixed
N = 10^4; ``make_gp_dataset`` is the same generator as a pure function
(deterministic in seed), used by the tests, the serving loops and the
examples.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

__all__ = ["make_clustered_dataset", "make_gp_dataset"]


def make_gp_dataset(
    N: int,
    p: int,
    *,
    noise: float = 0.05,
    lo: float = -1.0,
    hi: float = 1.0,
    seed: int = 0,
    test_frac: float = 0.1,
):
    """Returns (X, y, Xs, ys): train/test splits of the Eq. 21 function."""
    rng = np.random.default_rng(seed)
    n_test = max(1, int(N * test_frac))
    X_all = rng.uniform(lo, hi, size=(N + n_test, p)).astype(np.float32)
    f = np.sum(np.cos(X_all), axis=1)
    y_all = (f + noise * rng.standard_normal(N + n_test)).astype(np.float32)
    X, Xs = X_all[:N], X_all[N:]
    y, ys = y_all[:N], y_all[N:]
    return jnp.asarray(X), jnp.asarray(y), jnp.asarray(Xs), jnp.asarray(ys)


def make_clustered_dataset(
    N: int,
    *,
    n_clusters: int = 12,
    spread: float = 0.35,
    extent: float = 4.0,
    length_scale: float = 0.3,
    n_bumps: int = 60,
    noise: float = 0.05,
    seed: int = 0,
    test_frac: float = 0.1,
):
    """Clustered 2-D spatial regression — the regime Vecchia is built for.

    Inputs are drawn around ``n_clusters`` random centers on the wide
    ``[-extent, extent]^2`` domain (Gaussian spread per cluster), so the
    data has LOCAL structure with big empty gaps between clusters — global
    basis expansions must spend capacity on the gaps while nearest-neighbor
    conditioning does not.  Targets come from a fixed sum of ``n_bumps``
    random short-length-scale SE bumps (an explicit sample-path surrogate:
    smooth, stationary-ish, and O(N * n_bumps) to evaluate, so it scales to
    N = 10^4+ without any O(N^3) GP sampling) plus observation noise.

    Test points are drawn around the SAME centers (interpolation within
    clusters, the spatial-statistics task), deterministic in ``seed``.
    Returns ``(X, y, Xs, ys)`` like :func:`make_gp_dataset`.
    """
    rng = np.random.default_rng(seed)
    n_test = max(1, int(N * test_frac))
    n_all = N + n_test
    centers = rng.uniform(-extent, extent, size=(n_clusters, 2))
    which = rng.integers(0, n_clusters, size=n_all)
    X_all = (
        centers[which] + spread * rng.standard_normal((n_all, 2))
    ).astype(np.float32)
    # fixed random bump field: f(x) = sum_j a_j exp(-|x - c_j|^2 / (2 l^2))
    bump_c = rng.uniform(-extent - 1.0, extent + 1.0, size=(n_bumps, 2))
    bump_a = rng.standard_normal(n_bumps)
    d2 = np.sum(
        (X_all[:, None, :] - bump_c[None, :, :]) ** 2, axis=-1
    )
    f = (np.exp(-d2 / (2.0 * length_scale**2)) @ bump_a).astype(np.float32)
    y_all = (f + noise * rng.standard_normal(n_all)).astype(np.float32)
    X, Xs = X_all[:N], X_all[N:]
    y, ys = y_all[:N], y_all[N:]
    return jnp.asarray(X), jnp.asarray(y), jnp.asarray(Xs), jnp.asarray(ys)
