"""Pallas TPU kernel: fused expansion feature construction.

Computes Phi_(X) — the N x M feature matrix of a kernel expansion — in a
single HBM pass: read X once (N x p), write Phi once (N x M), with the
per-tile feature construction fused in VMEM.  Historically this module was
Hermite-only (paper Eq. 19); the kernel is now generic over a *tile
builder* ``tile_fn(xt, consts, table, *, p, n_max) -> (TN, TM)`` so every
registered ``KernelExpansion`` (Hermite-Mercer, RFF-SE, RFF-Matern) runs
through the same grid/BlockSpec machinery:

* ``consts``: a small global table replicated to every tile (Hermite: the
  (p, 3) [beta, delta2, rho*beta] rows; RFF: unused placeholder).
* ``table``: a (K, M) per-column table blocked along the feature axis
  (Hermite: the (p*n_max, M) one-hot selection S; RFF: stacked scaled
  frequencies + phase rows — see ``kernels.rff_phi``).

TPU adaptation of the paper's CUDA eigenfunction evaluation:

* The CUDA code evaluates eigenfunctions with one thread per (sample, index)
  pair.  On TPU we tile (rows x features) into VMEM blocks and express the
  *gather* `feats[:, idx[m, j]]` as a small one-hot **matmul**
  `feats @ S_j` — dynamic gathers are VPU-hostile, while an
  (TN, n_max) @ (n_max, TM) contraction runs on the MXU.  n_max <= 64, so
  the extra FLOPs are negligible next to the saved HBM traffic of a
  materialized (N, p, n_max) intermediate.
* The Hermite recurrence is unrolled at trace time (n_max is static), in
  its gamma-scaled form.  The recurrence itself lives in ONE place —
  ``core.mercer.hermite_psi_rows`` — shared with the jnp reference path
  (``mercer.eigenfunctions_1d``), so the two implementations cannot drift.

Grid: (N/TN, M/TM).  Block shapes: X^T (p, TN) [X stored transposed so the
lane dimension is the 128-aligned row axis], table (K, TM), out (TN, TM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.mercer import hermite_psi_rows

__all__ = ["hermite_phi_kernel", "hermite_phi", "phi_tile"]


def phi_tile(xt, consts, s, *, p: int, n_max: int):
    """One (TN, TM) tile of the Hermite-Mercer Phi from in-VMEM values.

    xt: (p, TN) input rows for this tile; consts: (p, 3); s: (p*n_max, TM)
    one-hot selection.  Shared by hermite_phi_kernel and the streaming
    fused-fit kernel (phi_gram), which generates these tiles on the fly
    instead of materializing Phi in HBM.  The scaled recurrence is
    ``core.mercer.hermite_psi_rows`` — its one home.
    """
    out = None
    for j in range(p):
        beta = consts[j, 0]
        delta2 = consts[j, 1]
        zscale = consts[j, 2]
        xj = xt[j, :][None, :]                          # (1, TN)
        z = zscale * xj
        env = jnp.exp(-delta2 * xj * xj)                # (1, TN)

        rows = hermite_psi_rows(z, beta, n_max)         # n_max x (1, TN)
        feats = jnp.concatenate(rows, axis=0) * env     # (n_max, TN)

        s_j = s[j * n_max : (j + 1) * n_max, :]         # (n_max, TM) one-hot
        # (TN, TM) <- feats^T @ S_j  : MXU-friendly "gather"
        sel = jax.lax.dot_general(
            feats, s_j, (((0,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
        out = sel if out is None else out * sel
    return out


def _phi_body(xt_ref, consts_ref, s_ref, o_ref, *, p: int, n_max: int,
              tile_fn):
    """One (TN, TM) output tile of Phi."""
    out = tile_fn(xt_ref[...], consts_ref[...], s_ref[...], p=p, n_max=n_max)
    o_ref[...] = out.astype(o_ref.dtype)


def hermite_phi_kernel(
    Xt: jax.Array,        # (p, N) transposed inputs, f32
    consts: jax.Array,    # small global table (Hermite: (p, 3))
    S: jax.Array,         # (K, M) per-column table (Hermite: one-hot)
    *,
    n_max: int,
    block_n: int = 256,
    block_m: int = 256,
    out_dtype=jnp.float32,
    interpret: bool = False,
    tile_fn=phi_tile,
) -> jax.Array:
    """Raw pallas_call, generic over the expansion's ``tile_fn``.  Requires
    N % block_n == 0 and M % block_m == 0 (ops.expansion_phi pads/unpads)."""
    p, N = Xt.shape
    M = S.shape[1]
    grid = (N // block_n, M // block_m)
    return pl.pallas_call(
        functools.partial(_phi_body, p=p, n_max=n_max, tile_fn=tile_fn),
        grid=grid,
        in_specs=[
            pl.BlockSpec((p, block_n), lambda i, j: (0, i)),
            pl.BlockSpec(consts.shape, lambda i, j: (0, 0)),
            pl.BlockSpec((S.shape[0], block_m), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_n, block_m), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((N, M), out_dtype),
        interpret=interpret,
    )(Xt, consts, S)
