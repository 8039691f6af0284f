"""Pure-jnp oracles for the Pallas kernels (independent implementations).

Deliberately written in the most direct/naive jnp form — no scans, no
blocking — so kernel bugs cannot hide behind shared code.  Tests assert
allclose(kernel, ref) across shape/dtype sweeps.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "ref_phi", "ref_rff_phi", "ref_scaled_gram", "ref_diag_quad",
    "ref_fused_fit_moments",
    "one_hot_selection", "phi_consts",
]


def phi_consts(eps: jax.Array, rho: jax.Array) -> jax.Array:
    """(p, 3) table of [beta, delta2, z_scale=rho*beta] per input dimension."""
    beta = (1.0 + (2.0 * eps / rho) ** 2) ** 0.25
    delta2 = 0.5 * rho**2 * (beta**2 - 1.0)
    return jnp.stack([beta, delta2, rho * beta], axis=-1).astype(jnp.float32)


def one_hot_selection(idx: np.ndarray, n_max: int) -> np.ndarray:
    """(p*n_max, M) one-hot matrix S with S[j*n_max + d, m] = [idx[m, j] == d]."""
    M, p = idx.shape
    S = np.zeros((p * n_max, M), np.float32)
    for j in range(p):
        S[j * n_max + idx[:, j], np.arange(M)] = 1.0
    return S


def ref_phi(Xt: jax.Array, consts: jax.Array, S: jax.Array, n_max: int) -> jax.Array:
    """Oracle for hermite_phi_kernel: (p, N), (p, 3), (p*n_max, M) -> (N, M)."""
    p, N = Xt.shape
    out = jnp.ones((N, S.shape[1]), jnp.float32)
    for j in range(p):
        beta, delta2, zscale = consts[j, 0], consts[j, 1], consts[j, 2]
        x = Xt[j]
        z = zscale * x
        psis = [jnp.sqrt(beta) * jnp.ones_like(z)]
        if n_max > 1:
            psis.append(z * jnp.sqrt(2.0) * psis[0])
        for i in range(2, n_max):
            psis.append(
                z * jnp.sqrt(2.0 / i) * psis[-1] - jnp.sqrt((i - 1.0) / i) * psis[-2]
            )
        feats = jnp.stack(psis, axis=-1) * jnp.exp(-delta2 * x * x)[:, None]  # (N, n_max)
        out = out * (feats @ S[j * n_max : (j + 1) * n_max])
    return out


def ref_rff_phi(Xt: jax.Array, consts, table: jax.Array, n_max: int = 0) -> jax.Array:
    """Oracle for the RFF tile function (kernels/rff_phi.rff_tile):
    (p, N) inputs and the (p+1, M) [W; phase] table -> cos(X W + phase).
    ``consts`` and ``n_max`` keep ref_phi's signature and are unused."""
    p = Xt.shape[0]
    return jnp.cos(Xt.T @ table[:p] + table[p])


def ref_scaled_gram(Phi: jax.Array, d: jax.Array, sig2) -> jax.Array:
    """Oracle for scaled_gram_kernel: I + D (Phi^T Phi) D / sig2."""
    M = Phi.shape[1]
    d = d.reshape(-1)
    G = Phi.astype(jnp.float32).T @ Phi.astype(jnp.float32)
    return jnp.eye(M, dtype=jnp.float32) + d[:, None] * G * d[None, :] / sig2


def ref_fused_fit_moments(X, y, consts, S, d, sig2, n_max: int, scale=True,
                          phi=ref_phi):
    """Oracle for the fit's moments: materializes the whole Phi (which
    the kernels never hold), then reduces.  ``phi`` is the feature oracle
    (ref_phi for Hermite-Mercer, ref_rff_phi for RFF).  Returns (B, b) or
    (G, b)."""
    Phi = phi(X.T.astype(jnp.float32), consts, S, n_max)
    b = Phi.T @ y.astype(jnp.float32)
    if not scale:
        return Phi.T @ Phi, b
    return ref_scaled_gram(Phi, d, sig2), b


def ref_diag_quad(A: jax.Array, C: jax.Array) -> jax.Array:
    """Oracle for diag_quad_kernel: diag(A C A^T), shape (N,)."""
    A = A.astype(jnp.float32)
    return jnp.einsum("nk,kl,nl->n", A, C.astype(jnp.float32), A)
