"""Plain (exact) Gaussian-process regression — paper Eqs. 3-4.

This is the O(N^3) baseline FAGP is measured against (the comparison the
Joukov-Kulic formulation, and hence the paper, is built on).  Zero-mean GP
with a choice of reference kernel — the ARD SE kernel (default, the
paper's) or the ARD Matern-5/2 kernel (the exact form the ``rff_matern52``
expansion approximates; same eps parametrization, see
``mercer.k_matern52_ard``).  Cholesky solve of (K + sigma^2 I).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from .mercer import SEKernelParams, k_matern52_ard, k_se_ard

__all__ = ["ExactGPState", "KERNELS", "fit", "predict", "mean_var", "nlml"]

# exact reference kernels by name; the KernelExpansion instances point at
# these via ``exact_kernel`` so the parity tests share one oracle table
KERNELS = {"se": k_se_ard, "matern52": k_matern52_ard}

# the float32 reference: its products run at full f32 precision on every
# backend (a TPU's default for an f32 matmul is lower)
_HIGHEST = jax.lax.Precision.HIGHEST


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ExactGPState:
    X: jax.Array          # (N, p) train inputs
    chol: jax.Array       # (N, N) lower Cholesky of K + sigma^2 I
    alpha: jax.Array      # (N,)   (K + sigma^2 I)^{-1} y
    params: SEKernelParams
    kernel: str = dataclasses.field(
        default="se", metadata=dict(static=True)
    )


@partial(jax.jit, static_argnames=("kernel",))
def fit(X: jax.Array, y: jax.Array, params: SEKernelParams,
        kernel: str = "se") -> ExactGPState:
    N = X.shape[0]
    K = KERNELS[kernel](X, X, params.eps)
    Ky = K + (params.noise**2) * jnp.eye(N, dtype=K.dtype)
    chol = jnp.linalg.cholesky(Ky)
    alpha = jax.scipy.linalg.cho_solve((chol, True), y)
    return ExactGPState(X=X, chol=chol, alpha=alpha, params=params,
                        kernel=kernel)


@jax.jit
def predict(state: ExactGPState, Xs: jax.Array):
    """Posterior mean (N*,) and covariance (N*, N*) at test inputs Xs."""
    k = KERNELS[state.kernel]
    Ks = k(Xs, state.X, state.params.eps)                 # (N*, N)
    mu = jnp.matmul(Ks, state.alpha, precision=_HIGHEST)   # Eq. 3, m = 0
    V = jax.scipy.linalg.solve_triangular(state.chol, Ks.T, lower=True)  # (N, N*)
    Kss = k(Xs, Xs, state.params.eps)
    cov = Kss - jnp.matmul(V.T, V, precision=_HIGHEST)     # Eq. 4
    return mu, cov


@jax.jit
def mean_var(state: ExactGPState, Xs: jax.Array):
    """Posterior mean (N*,) and marginal variance (N*,) — the diagonal of
    :func:`predict`'s covariance without forming the N* x N* matrix.  Both
    reference kernels are unit-variance, so the prior diagonal is 1."""
    k = KERNELS[state.kernel]
    Ks = k(Xs, state.X, state.params.eps)                  # (N*, N)
    mu = jnp.matmul(Ks, state.alpha, precision=_HIGHEST)
    V = jax.scipy.linalg.solve_triangular(state.chol, Ks.T, lower=True)
    var = jnp.maximum(1.0 - jnp.sum(V * V, axis=0), 0.0)
    return mu, var


@partial(jax.jit, static_argnames=("kernel",))
def nlml(X: jax.Array, y: jax.Array, params: SEKernelParams,
         kernel: str = "se") -> jax.Array:
    """Exact negative log marginal likelihood (for hyperparameter baselines)."""
    N = X.shape[0]
    K = KERNELS[kernel](X, X, params.eps)
    Ky = K + (params.noise**2) * jnp.eye(N, dtype=K.dtype)
    chol = jnp.linalg.cholesky(Ky)
    alpha = jax.scipy.linalg.cho_solve((chol, True), y)
    return (
        0.5 * jnp.dot(y, alpha)
        + jnp.sum(jnp.log(jnp.diagonal(chol)))
        + 0.5 * N * jnp.log(2.0 * jnp.pi)
    )
