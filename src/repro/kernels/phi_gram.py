"""Pallas TPU kernel: streaming fused fit — Phi is never written to HBM.

The materialized fit path (hermite_phi -> scaled_gram) makes two HBM passes
and parks an N x M intermediate in HBM between them — exactly the memory
wall the paper's decomposed kernel is supposed to avoid (the M x M system
is small; the N x M feature matrix is not).  This kernel fuses feature
construction INTO the Gram accumulation: each (TK, TI) / (TK, TJ) tile of
Phi is regenerated in VMEM from the corresponding (p, TK) tile of X via the
expansion's tile builder (``tile_fn`` — hermite_phi.phi_tile for the
Hermite-Mercer expansion, rff_phi.rff_tile for the random-Fourier
families), contracted on the MXU, and discarded.  HBM traffic: read X and y once, write B (M x M) and
b (M) once.  Peak live memory is O(M^2) in N — the same asymptotic as the
jnp scan path, but in one fused pass.

The trade is recompute for bandwidth: each X tile's features are rebuilt
2 * M/TI times (once per output block row/column).  The tile builder is
O(p * n_max) VPU work per element (Hermite) or one (TK, p) x (p, TM)
contraction plus a cosine (RFF) vs the O(TI) MXU work of the Gram
contraction it feeds, so for M >= ~256 the MXU stays the bottleneck.

Outputs (one fused pallas_call):
    B = I + D (Phi^T Phi) D / sig2    (M, M)   [or plain G when scale=False]
    b = Phi^T y                        (1, M)

Grid: (M/TI, M/TJ, N/TK), K innermost.  The B block (TI, TJ) accumulates
across K (canonical revisiting matmul); the b block (1, TI) accumulates
only on the j == 0 face so each row tile of Phi contributes exactly once.
Padded rows are masked inside the kernel (phi(0) != 0, so zero-padding X
alone would corrupt the Gram).

Bank variant (``bank_phi_gram_kernel``): one extra *leading* grid axis
walks the slots of a GP bank — grid (B, M/TI, M/TJ, N/TK) — so B
independent small datasets produce B Gram/moment pairs in ONE kernel
launch.  Each slot's (p, TK) X tile regenerates its own Phi tiles in VMEM
exactly as the single-model kernel does (any tile_fn); at no point do B
separate N x M feature matrices exist anywhere.  Per-slot row masks make ragged
per-tenant N a masking detail rather than a shape change.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .hermite_phi import phi_tile

__all__ = ["phi_gram_kernel", "bank_phi_gram_kernel"]


def _phi_gram_body(
    xt_ref, consts_ref, si_ref, sj_ref, di_ref, dj_ref, sig2_ref, y_ref,
    mask_ref, o_ref, b_ref, *, p: int, n_max: int, nk: int, scale: bool,
    tile_fn,
):
    i, j = pl.program_id(0), pl.program_id(1)
    k = pl.program_id(2)

    mask = mask_ref[0, :][None, :]                     # (1, TK)
    # (TK, TI) and (TK, TJ) tiles of Phi, built in VMEM and discarded
    phi_i = tile_fn(xt_ref[...], consts_ref[...], si_ref[...],
                    p=p, n_max=n_max) * mask.T
    phi_j = tile_fn(xt_ref[...], consts_ref[...], sj_ref[...],
                    p=p, n_max=n_max) * mask.T

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jax.lax.dot_general(
        phi_i, phi_j, (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )

    @pl.when((j == 0) & (k == 0))
    def _init_b():
        b_ref[...] = jnp.zeros_like(b_ref)

    @pl.when(j == 0)
    def _acc_b():
        # (1, TI) += y_k @ Phi_k_i  (y already zero-padded past N)
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


def phi_gram_kernel(
    Xt: jax.Array,        # (p, N) transposed inputs, f32
    consts: jax.Array,    # small global table (Hermite: (p, 3))
    S: jax.Array,         # (K, M) per-column table (Hermite: one-hot), f32
    d: jax.Array,         # (1, M)  sqrt(lambda) scaling
    sig2: jax.Array,      # (1, 1)  noise variance
    y: jax.Array,         # (1, N)  targets, zero-padded past the true N
    mask: jax.Array,      # (1, N)  1.0 on valid rows, 0.0 on padding
    *,
    n_max: int,
    block_m: int = 256,
    block_k: int = 256,
    scale: bool = True,
    interpret: bool = False,
    tile_fn=phi_tile,
):
    """Raw pallas_call; returns (B (M, M), b (1, M)).  Requires
    N % block_k == 0 and M % block_m == 0 (ops.fused_fit_moments pads).
    Generic over the expansion's ``tile_fn`` (see kernels/hermite_phi)."""
    p, N = Xt.shape
    M = S.shape[1]
    nk = N // block_k
    grid = (M // block_m, M // block_m, nk)
    return pl.pallas_call(
        functools.partial(
            _phi_gram_body, p=p, n_max=n_max, nk=nk, scale=scale,
            tile_fn=tile_fn,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((p, block_k), lambda i, j, k: (0, k)),
            pl.BlockSpec(consts.shape, lambda i, j, k: (0, 0)),
            pl.BlockSpec((S.shape[0], block_m), lambda i, j, k: (0, i)),
            pl.BlockSpec((S.shape[0], block_m), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, block_m), lambda i, j, k: (0, i)),
            pl.BlockSpec((1, block_m), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
            pl.BlockSpec((1, block_k), lambda i, j, k: (0, k)),
            pl.BlockSpec((1, block_k), lambda i, j, k: (0, k)),
        ],
        out_specs=[
            pl.BlockSpec((block_m, block_m), lambda i, j, k: (i, j)),
            pl.BlockSpec((1, block_m), lambda i, j, k: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, M), jnp.float32),
            jax.ShapeDtypeStruct((1, M), jnp.float32),
        ],
        interpret=interpret,
    )(Xt, consts, S, S, d, d, sig2, y, mask)


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
