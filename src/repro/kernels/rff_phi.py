"""Pallas tile builder for random-Fourier-feature (RFF) expansions.

The RFF feature map of a stationary kernel k with spectral measure S(w) is

    phi_m(x) = cos(w_r x + phase_m),   m = 0..2R-1,
    r = m mod R,  phase_m = 0 for the cos half, -pi/2 for the sin half
    (cos(z - pi/2) = sin(z)),  lambda_m = 1/R,

so that Phi diag(lambda) Phi^T is the Monte-Carlo estimate
(1/R) sum_r [cos(w_r x)cos(w_r x') + sin(w_r x)sin(w_r x')] -> k(x, x').

Tile contract (see kernels/hermite_phi.py): the per-column table stacks the
scaled frequency matrix W (p, M) over the phase row (1, M), giving a
(p+1, M) table blocked along the feature axis; the global ``consts`` table
is unused (a (1, 1) placeholder keeps the shared kernel signature).  One
(TK, TM) tile of Phi is then a single MXU contraction xt^T @ W_block plus a
VPU cosine — O(p) VMEM state per column, no N x M intermediate anywhere,
which lets the feature kernel build the fit's row chunks of Phi and the
bank's fused kernel (phi_gram) build tiles in VMEM for RFF fits alike.

The frequencies themselves are *data* (they carry the lengthscale scaling
sqrt(2) * eps, differentiable for NLML learning) and are built outside the
kernel by the RFF ``KernelExpansion`` (core/expansions.py) from the base
draws stored in ``GPSpec.omega``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["rff_tile", "rff_consts_placeholder"]


def rff_consts_placeholder() -> jax.Array:
    """RFF needs no global constant table; this keeps the kernel signature
    shared with the Hermite tile (consts is replicated to every tile)."""
    return jnp.zeros((1, 1), jnp.float32)


def rff_tile(xt, consts, table, *, p: int, n_max: int):
    """One (TK, TM) tile of the RFF Phi from in-VMEM values.

    xt: (p, TK) input rows for this tile; consts: unused placeholder;
    table: (p + 1, TM) block of [W; phase] — W rows are the sqrt(2)*eps-
    scaled spectral frequencies for these feature columns.  ``n_max`` is
    part of the shared tile signature and unused here (no recurrence).
    """
    w = table[:p, :]                                    # (p, TM)
    phase = table[p : p + 1, :]                         # (1, TM)
    z = jax.lax.dot_general(
        xt, w, (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )                                                   # (TK, TM)
    return jnp.cos(z + phase)
