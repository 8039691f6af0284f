"""Pallas TPU kernel: diagonal of a quadratic form, var_i = a_i^T C a_i.

Used for FAGP predictive variances: var = diag((Phi* D) B^{-1} (Phi* D)^T).
The paper's CUDA code materializes the full N* x N* covariance and reads its
diagonal; this kernel never forms the off-diagonal entries — an O(N*) output
instead of O(N*^2) memory — while streaming C in (TK, TL) tiles.

Grid: (N/TN, M/TK, M/TL), output block (1, TN) revisited across (k, l):
    out[i] += rowsum( (A_ik @ C_kl) * A_il )
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["diag_quad_kernel"]


def _diag_quad_body(a1_ref, c_ref, a2_ref, o_ref):
    k, l = pl.program_id(1), pl.program_id(2)

    @pl.when((k == 0) & (l == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    t = jnp.dot(a1_ref[...], c_ref[...], precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
    o_ref[...] += jnp.sum(t * a2_ref[...], axis=1)[None, :]


def diag_quad_kernel(
    A: jax.Array,         # (N, M)
    C: jax.Array,         # (M, M)
    *,
    block_n: int = 256,
    block_m: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Raw pallas_call; returns (1, N). Requires N % block_n == M % block_m == 0."""
    N, M = A.shape
    grid = (N // block_n, M // block_m, M // block_m)
    return pl.pallas_call(
        _diag_quad_body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_m), lambda i, k, l: (i, k)),
            pl.BlockSpec((block_m, block_m), lambda i, k, l: (k, l)),
            pl.BlockSpec((block_n, block_m), lambda i, k, l: (i, l)),
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda i, k, l: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, N), jnp.float32),
        interpret=interpret,
    )(A, C, A)
