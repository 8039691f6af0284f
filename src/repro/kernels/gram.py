"""Pallas TPU kernel: the fit's Gram moments from a feature matrix in HBM.

    G = Phi^T diag(m)^2 Phi,   b = Phi^T diag(m) y
    B = I + D G D / sig2        (scale=True; D = diag(sqrt(lambda)))

The paper's hot loop computes Phi^T Sigma_n^{-1} Phi with a cuBLAS GEMM and
then adds Lambda^{-1} in a second pass.  Here the Gram contraction, the
moment vector b, the row mask m, the symmetric sqrt(lambda) scaling, the
1/sigma^2 noise scaling and the unit diagonal are fused into one kernel:
Phi is read from HBM once per output block row and column, and the (M, M)
output is written once.

Grid: (M/TI, M/TJ, N/TK) with the K (row) axis innermost, accumulating
into the output block across K steps — the canonical Pallas matmul
revisiting pattern, f32 accumulation at ``Precision.HIGHEST`` regardless
of input dtype.  b (1, TI) accumulates only on the j == 0 face, so each
row tile of Phi contributes to it once.  The mask multiplies both Gram
factors and b's; rows with m = 0 (padding, excluded shard rows) drop out.

Row chunks: given ``acc = (G0, b0)`` the kernel starts from those moments
instead of zero and writes in place (the outputs alias them), so a caller
can stream the rows through the kernel one chunk at a time
(``ops.fused_fit_moments``).  The scaling epilogue belongs to the last
chunk's call only; ``scale=False`` returns the raw moments.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["gram_moments_kernel"]


def _gram_body(phi_i_ref, phi_j_ref, y_ref, mask_ref, di_ref, dj_ref,
               sig2_ref, *refs, nk: int, scale: bool, accumulate: bool):
    g0_ref, b0_ref, o_ref, b_ref = refs if accumulate else (None, None, *refs)
    # program_id must be read outside pl.when branches (the interpret-mode
    # HLO path cannot substitute it inside cond sub-jaxprs)
    i, j = pl.program_id(0), pl.program_id(1)
    k = pl.program_id(2)

    mask = mask_ref[0, :][None, :]                     # (1, TK)
    phi_i = phi_i_ref[...] * mask.T                    # f32, whatever Phi is
    phi_j = phi_j_ref[...] * mask.T

    @pl.when(k == 0)
    def _init():
        o_ref[...] = g0_ref[...] if accumulate else jnp.zeros_like(o_ref)

    # (TI, TJ) += Phi_k_i^T @ Phi_k_j   (f32 accumulation on the MXU)
    o_ref[...] += jax.lax.dot_general(
        phi_i, phi_j, (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )

    @pl.when((j == 0) & (k == 0))
    def _init_b():
        b_ref[...] = b0_ref[...] if accumulate else jnp.zeros_like(b_ref)

    @pl.when(j == 0)
    def _acc_b():
        # (1, TI) += y_k @ (m * Phi)_k_i  (y already zero-padded past N)
        b_ref[...] += jax.lax.dot_general(
            y_ref[...], phi_i, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )

    if scale:
        @pl.when(k == nk - 1)
        def _epilogue():
            ti, tj = o_ref.shape
            di = di_ref[0, :][:, None]                 # (TI, 1)
            dj = dj_ref[0, :][None, :]                 # (1, TJ)
            acc = o_ref[...] * (di * dj / sig2_ref[0, 0])
            rows = i * ti + jax.lax.broadcasted_iota(jnp.int32, (ti, tj), 0)
            cols = j * tj + jax.lax.broadcasted_iota(jnp.int32, (ti, tj), 1)
            o_ref[...] = acc + jnp.where(rows == cols, 1.0, 0.0).astype(acc.dtype)


def gram_moments_kernel(
    Phi: jax.Array,       # (N, M)
    y: jax.Array,         # (1, N)  targets, zero-padded past the true N
    mask: jax.Array,      # (1, N)  row weights: 1.0 valid, 0.0 excluded
    d: jax.Array,         # (1, M)  sqrt(lambda) scaling; unused unless scale
    sig2: jax.Array,      # (1, 1)  noise variance; unused unless scale
    acc: tuple[jax.Array, jax.Array] | None = None,  # (G0 (M, M), b0 (1, M))
    *,
    block_m: int = 256,
    block_k: int = 512,
    scale: bool = True,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Raw pallas_call; returns (B or G (M, M), b (1, M)), starting from
    ``acc`` when given (and overwriting it in place).  Requires
    M % block_m == 0 and N % block_k == 0."""
    N, M = Phi.shape
    nk = N // block_k
    accumulate = acc is not None
    row = lambda i, j, k: (0, k)
    in_specs = [
        pl.BlockSpec((block_k, block_m), lambda i, j, k: (k, i)),
        pl.BlockSpec((block_k, block_m), lambda i, j, k: (k, j)),
        pl.BlockSpec((1, block_k), row),
        pl.BlockSpec((1, block_k), row),
        pl.BlockSpec((1, block_m), lambda i, j, k: (0, i)),
        pl.BlockSpec((1, block_m), lambda i, j, k: (0, j)),
        pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
    ]
    out_specs = [
        pl.BlockSpec((block_m, block_m), lambda i, j, k: (i, j)),
        pl.BlockSpec((1, block_m), lambda i, j, k: (0, i)),
    ]
    return pl.pallas_call(
        functools.partial(_gram_body, nk=nk, scale=scale,
                          accumulate=accumulate),
        grid=(M // block_m, M // block_m, nk),
        in_specs=in_specs + (out_specs if accumulate else []),
        out_specs=out_specs,
        out_shape=[
            jax.ShapeDtypeStruct((M, M), jnp.float32),
            jax.ShapeDtypeStruct((1, M), jnp.float32),
        ],
        input_output_aliases={7: 0, 8: 1} if accumulate else {},
        interpret=interpret,
    )(Phi, Phi, y, mask, d, d, sig2, *(acc or ()))

