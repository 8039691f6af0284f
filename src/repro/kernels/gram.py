"""Pallas TPU kernel: fused scaled Gram matrix  B = I + D (Phi^T Phi) D / sig2.

The paper's hot loop computes Phi^T Sigma_n^{-1} Phi with a cuBLAS GEMM and
then adds Lambda^{-1} in a second pass.  Here the Gram contraction, the
symmetric sqrt(lambda) scaling, the 1/sigma^2 noise scaling, and the unit
diagonal are fused into one kernel: Phi is read from HBM exactly once and
the (M, M) output is written exactly once.

Grid: (M/TI, M/TJ, N/TK) with the K (row/N) axis innermost ("arbitrary"),
accumulating into the output block across K steps — the canonical Pallas
matmul revisiting pattern.  f32 accumulation regardless of input dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["scaled_gram_kernel"]


def _gram_body(phi_i_ref, phi_j_ref, di_ref, dj_ref, sig2_ref, o_ref, *, nk: int):
    # program_id must be read outside pl.when branches (the interpret-mode
    # HLO path cannot substitute it inside cond sub-jaxprs)
    i, j = pl.program_id(0), pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # (TI, TJ) += Phi_k_i^T @ Phi_k_j   (f32 accumulation on the MXU)
    o_ref[...] += jax.lax.dot_general(
        phi_i_ref[...], phi_j_ref[...], (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == nk - 1)
    def _epilogue():
        ti, tj = o_ref.shape
        di = di_ref[0, :][:, None]                     # (TI, 1)
        dj = dj_ref[0, :][None, :]                     # (1, TJ)
        acc = o_ref[...] * (di * dj / sig2_ref[0, 0])
        rows = i * ti + jax.lax.broadcasted_iota(jnp.int32, (ti, tj), 0)
        cols = j * tj + jax.lax.broadcasted_iota(jnp.int32, (ti, tj), 1)
        o_ref[...] = acc + jnp.where(rows == cols, 1.0, 0.0).astype(acc.dtype)


def scaled_gram_kernel(
    Phi: jax.Array,       # (N, M)
    d: jax.Array,         # (1, M)  sqrt(lambda) scaling
    sig2: jax.Array,      # (1, 1)  noise variance
    *,
    block_m: int = 256,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Raw pallas_call. Requires M % block_m == 0 and N % block_k == 0."""
    N, M = Phi.shape
    nk = N // block_k
    grid = (M // block_m, M // block_m, nk)
    return pl.pallas_call(
        functools.partial(_gram_body, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_k, block_m), lambda i, j, k: (k, i)),
            pl.BlockSpec((block_k, block_m), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, block_m), lambda i, j, k: (0, i)),
            pl.BlockSpec((1, block_m), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, block_m), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, M), jnp.float32),
        interpret=interpret,
    )(Phi, Phi, d, d, sig2)
