"""Pallas TPU kernel: a GP bank's fit moments with no feature matrix in HBM.

``bank_phi_gram_kernel`` walks the slots of a GP bank on a leading grid
axis — grid (B, M/TI, M/TJ, N/TK), K innermost — so B independent small
datasets produce B Gram/moment pairs in ONE kernel launch:

    G_s = Phi_s^T Phi_s   (B, M, M),     b_s = Phi_s^T y_s   (B, 1, M)

Each slot's (p, TK) X tile regenerates its (TK, TI) and (TK, TJ) tiles of
Phi in VMEM through the expansion's tile function (``tile_fn`` —
hermite_phi.phi_tile for the Hermite-Mercer expansion, rff_phi.rff_tile
for the random-Fourier families), contracts them on the MXU and discards
them, so B separate N x M feature matrices never exist anywhere.  The B
block (TI, TJ) accumulates across K (canonical revisiting matmul); the b
block (1, TI) only on the j == 0 face, so each row tile contributes once.
Per-slot row masks make ragged per-tenant N a masking detail rather than a
shape change (phi(0) != 0, so zero-padding X alone would corrupt the Gram).

The price is recompute: each X tile's features are rebuilt 2 * M/TI times,
and the Hermite tile's one-hot "gather" is MXU work (a contraction only
n_max deep still fills the MXU's depth).  Bank tenants have M <= 256, one
output block, so a tile is rebuilt at most twice; keeping a Phi per tenant
instead would multiply memory by the bank size.  The single-model fit,
whose M runs to 10^4 and more, builds each tile once instead: the features
kernel writes a row chunk of Phi and the Gram kernel contracts it
(``ops.fused_fit_moments``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .hermite_phi import phi_tile

__all__ = ["bank_phi_gram_kernel"]


def _bank_phi_gram_body(
    xt_ref, consts_ref, si_ref, sj_ref, y_ref, mask_ref, o_ref, b_ref,
    *, p: int, n_max: int, tile_fn,
):
    j, k = pl.program_id(2), pl.program_id(3)

    mask = mask_ref[0, 0, :][None, :]                  # (1, TK)
    xt = xt_ref[0]                                     # (p, TK) this slot's rows
    phi_i = tile_fn(xt, consts_ref[...], si_ref[...],
                    p=p, n_max=n_max) * mask.T
    phi_j = tile_fn(xt, consts_ref[...], sj_ref[...],
                    p=p, n_max=n_max) * mask.T

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jax.lax.dot_general(
        phi_i, phi_j, (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )[None]

    @pl.when((j == 0) & (k == 0))
    def _init_b():
        b_ref[...] = jnp.zeros_like(b_ref)

    @pl.when(j == 0)
    def _acc_b():
        # (1, TI) += (mask * y)_k @ Phi_k_i — y is masked as well as Phi so
        # a non-binary mask weights b exactly like the jnp scan path
        # (_block_scan_moments masks both factors); for the binary
        # row-validity masks the bank emits, the two are identical
        b_ref[...] += jax.lax.dot_general(
            y_ref[0] * mask, phi_i, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )[None]


def bank_phi_gram_kernel(
    Xt: jax.Array,        # (B, p, N) per-slot transposed inputs, f32
    consts: jax.Array,    # small global table (shared spec)
    S: jax.Array,         # (K, M) per-column table (shared spec)
    y: jax.Array,         # (B, 1, N) per-slot targets, zero-padded
    mask: jax.Array,      # (B, 1, N) per-slot row validity (ragged N)
    *,
    n_max: int,
    block_m: int = 256,
    block_k: int = 256,
    interpret: bool = False,
    tile_fn=phi_tile,
):
    """Raw pallas_call for a whole bank: returns the *unscaled* moments
    (G (B, M, M), b (B, 1, M)) — G_s = Phi_s^T Phi_s, b_s = Phi_s^T y_s —
    in one launch.  The scaled system B = I + D G D / sig2 is assembled
    outside (its one home, ``fagp._assemble_scaled_system``, vmapped over
    slots).  Requires N % block_k == 0 and M % block_m == 0
    (ops.bank_fused_fit_moments pads)."""
    nbank, p, N = Xt.shape
    M = S.shape[1]
    grid = (nbank, M // block_m, M // block_m, N // block_k)
    return pl.pallas_call(
        functools.partial(_bank_phi_gram_body, p=p, n_max=n_max,
                          tile_fn=tile_fn),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, p, block_k), lambda s, i, j, k: (s, 0, k)),
            pl.BlockSpec(consts.shape, lambda s, i, j, k: (0, 0)),
            pl.BlockSpec((S.shape[0], block_m), lambda s, i, j, k: (0, i)),
            pl.BlockSpec((S.shape[0], block_m), lambda s, i, j, k: (0, j)),
            pl.BlockSpec((1, 1, block_k), lambda s, i, j, k: (s, 0, k)),
            pl.BlockSpec((1, 1, block_k), lambda s, i, j, k: (s, 0, k)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_m, block_m), lambda s, i, j, k: (s, i, j)),
            pl.BlockSpec((1, 1, block_m), lambda s, i, j, k: (s, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nbank, M, M), jnp.float32),
            jax.ShapeDtypeStruct((nbank, 1, M), jnp.float32),
        ],
        interpret=interpret,
    )(Xt, consts, S, S, y, mask)
