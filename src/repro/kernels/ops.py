"""Jitted public wrappers around the Pallas kernels.

Handles: padding to tile multiples, transposition to the kernel layouts,
interpret-mode resolution (CPU -> interpret=True so the kernel body runs in
Python; TPU -> compiled; any other backend raises), and jnp fallbacks for
tiny shapes where kernel tiling overhead is not worth it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from .diag_quad import diag_quad_kernel
from .gram import scaled_gram_kernel
from .hermite_phi import hermite_phi_kernel, phi_tile
from .phi_gram import bank_phi_gram_kernel, phi_gram_kernel

__all__ = [
    "expansion_phi", "hermite_phi", "scaled_gram", "diag_quad",
    "fused_fit_moments", "bank_fused_fit_moments", "resolve_interpret",
]


def resolve_interpret(interpret: bool | None) -> bool:
    """interpret=None -> compiled kernels on TPU, interpret mode on CPU.

    Any other backend raises: the kernels are written for the TPU, and
    running them interpreted there would hide the device behind a slow
    emulation with no error."""
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas kernels run compiled on 'tpu' and interpreted on "
        f"'cpu'; the default backend is {backend!r} (use backend='jnp')"
    )


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(
    jax.jit,
    static_argnames=("n_max", "block_n", "block_m", "interpret", "tile_fn"),
)
def expansion_phi(
    X: jax.Array,            # (N, p)
    consts: jax.Array,       # small global table (Hermite: (p, 3))
    S: jax.Array,            # (K, M) per-column table (Hermite: one-hot)
    *,
    n_max: int,
    block_n: int = 256,
    block_m: int = 256,
    interpret: bool | None = None,
    tile_fn=phi_tile,
) -> jax.Array:
    """Phi_(X): (N, M) expansion feature matrix via the fused Pallas kernel,
    generic over the expansion's ``tile_fn`` (a module-level function so the
    jit cache stays keyed on stable identities).

    Padded feature columns may hold garbage for non-Hermite tiles (an RFF
    column with a zero table row is cos(0) = 1, not 0) — they are sliced
    away here before anything downstream can read them."""
    N, _ = X.shape
    M = S.shape[1]
    interp = resolve_interpret(interpret)
    block_n = min(block_n, max(8, 1 << (N - 1).bit_length()))
    block_m = min(block_m, max(128, 1 << (M - 1).bit_length()))
    Xt = _pad_to(X.T.astype(jnp.float32), 1, block_n)
    Sp = _pad_to(S.astype(jnp.float32), 1, block_m)
    out = hermite_phi_kernel(
        Xt, consts, Sp, n_max=n_max, block_n=block_n, block_m=block_m,
        interpret=interp, tile_fn=tile_fn,
    )
    return out[:N, :M]


def hermite_phi(
    X: jax.Array,            # (N, p)
    consts: jax.Array,       # (p, 3) from ref.phi_consts
    S: jax.Array,            # (p*n_max, M) one-hot from ref.one_hot_selection
    *,
    n_max: int,
    block_n: int = 256,
    block_m: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """Phi_(X) for the Hermite-Mercer expansion (the historical name; now a
    thin wrapper over the generic :func:`expansion_phi`)."""
    return expansion_phi(
        X, consts, S, n_max=n_max, block_n=block_n, block_m=block_m,
        interpret=interpret, tile_fn=phi_tile,
    )


@functools.partial(
    jax.jit,
    static_argnames=("n_max", "block_m", "block_k", "scale", "interpret",
                     "tile_fn"),
)
def fused_fit_moments(
    X: jax.Array,            # (N, p)
    y: jax.Array,            # (N,)
    consts: jax.Array,       # small global table (Hermite: (p, 3))
    S: jax.Array,            # (K, M) per-column table (Hermite: one-hot)
    sqrtlam: jax.Array,      # (M,)  ignored when scale=False
    sig2: jax.Array,         # scalar; ignored when scale=False
    mask: jax.Array | None = None,  # (N,) row validity; None = all valid
    *,
    n_max: int,
    block_m: int = 256,
    block_k: int = 256,
    scale: bool = True,
    interpret: bool | None = None,
    tile_fn=phi_tile,
) -> tuple[jax.Array, jax.Array]:
    """Streaming fused fit statistics: Phi is generated tile-by-tile inside
    the Gram contraction and never written to HBM (kernels/phi_gram),
    generic over the expansion's ``tile_fn``.

    scale=True  -> (B, b) with B = I + D Phi^T Phi D / sig2  (the fit solve)
    scale=False -> (G, b) with G = Phi^T Phi  (raw moments, e.g. for the
                   distributed per-shard partial sums that are psum'd first)

    ``mask`` excludes rows (e.g. shard padding) from both statistics.
    """
    N, p = X.shape
    M = S.shape[1]
    interp = resolve_interpret(interpret)
    block_k = min(block_k, max(8, 1 << (N - 1).bit_length()))
    block_m = min(block_m, max(128, 1 << (M - 1).bit_length()))
    Xt = _pad_to(X.T.astype(jnp.float32), 1, block_k)
    Sp = _pad_to(S.astype(jnp.float32), 1, block_m)
    d = _pad_to(sqrtlam.reshape(1, -1).astype(jnp.float32), 1, block_m)
    yp = _pad_to(y.reshape(1, -1).astype(jnp.float32), 1, block_k)
    if mask is None:
        mask = jnp.ones((1, N), jnp.float32)
    else:
        mask = mask.reshape(1, -1).astype(jnp.float32)
    mask = _pad_to(mask, 1, block_k)
    B, b = phi_gram_kernel(
        Xt, consts, Sp, d, jnp.asarray(sig2, jnp.float32).reshape(1, 1),
        yp, mask, n_max=n_max, block_m=block_m, block_k=block_k,
        scale=scale, interpret=interp, tile_fn=tile_fn,
    )
    # padded feature columns are garbage in general (zero for the Hermite
    # one-hot, cos(0)=1 for RFF) but live entirely in rows/cols >= M of the
    # outputs; the slice below removes every trace of them
    return B[:M, :M], b[0, :M]


@functools.partial(
    jax.jit,
    static_argnames=("n_max", "block_m", "block_k", "interpret", "tile_fn"),
)
def bank_fused_fit_moments(
    Xb: jax.Array,           # (B, N, p) per-slot inputs (N = padded row cap)
    yb: jax.Array,           # (B, N)    per-slot targets
    consts: jax.Array,       # small global table (shared spec)
    S: jax.Array,            # (K, M) per-column table (shared spec)
    mask: jax.Array | None = None,  # (B, N) per-slot row validity (ragged N)
    *,
    n_max: int,
    block_m: int = 256,
    block_k: int = 256,
    interpret: bool | None = None,
    tile_fn=phi_tile,
) -> tuple[jax.Array, jax.Array]:
    """Raw fit moments for a whole bank of B independent GPs in ONE kernel
    launch: G (B, M, M) with G_s = Phi_s^T Phi_s and b (B, M) with
    b_s = Phi_s^T y_s.  The bank axis is a leading grid dimension of the
    streaming fused kernel (kernels/phi_gram.bank_phi_gram_kernel), so the
    Hermite-feature tiles of different slots are generated in VMEM one tile
    at a time — B separate N x M Phi matrices never exist in HBM.

    ``mask`` rows with 0.0 are excluded from both statistics, which is how
    ragged per-tenant N is expressed on a fixed (B, N, p) stack.
    """
    nbank, N, p = Xb.shape
    M = S.shape[1]
    interp = resolve_interpret(interpret)
    block_k = min(block_k, max(8, 1 << (N - 1).bit_length()))
    block_m = min(block_m, max(128, 1 << (M - 1).bit_length()))
    Xt = _pad_to(jnp.swapaxes(Xb, 1, 2).astype(jnp.float32), 2, block_k)
    Sp = _pad_to(S.astype(jnp.float32), 1, block_m)
    yp = _pad_to(yb.reshape(nbank, 1, N).astype(jnp.float32), 2, block_k)
    if mask is None:
        mask = jnp.ones((nbank, 1, N), jnp.float32)
    else:
        mask = mask.reshape(nbank, 1, N).astype(jnp.float32)
    mask = _pad_to(mask, 2, block_k)
    G, b = bank_phi_gram_kernel(
        Xt, consts, Sp, yp, mask, n_max=n_max, block_m=block_m,
        block_k=block_k, interpret=interp, tile_fn=tile_fn,
    )
    # padded feature columns only touch rows/cols >= M; sliced away here
    return G[:, :M, :M], b[:, 0, :M]


@functools.partial(jax.jit, static_argnames=("block_m", "block_k", "interpret"))
def scaled_gram(
    Phi: jax.Array,          # (N, M)
    sqrtlam: jax.Array,      # (M,)
    sig2: jax.Array,         # scalar
    *,
    block_m: int = 256,
    block_k: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """B = I + D Phi^T Phi D / sig2 in one fused HBM pass over Phi."""
    N, M = Phi.shape
    interp = resolve_interpret(interpret)
    block_m = min(block_m, max(128, 1 << (M - 1).bit_length()))
    block_k = min(block_k, max(8, 1 << (N - 1).bit_length()))
    # zero-padding rows of Phi adds nothing to the Gram sum; zero-padded
    # columns of d produce identity rows/cols that are sliced away.
    Phip = _pad_to(_pad_to(Phi, 0, block_k), 1, block_m)
    d = _pad_to(sqrtlam.reshape(1, -1).astype(jnp.float32), 1, block_m)
    out = scaled_gram_kernel(
        Phip, d, jnp.asarray(sig2, jnp.float32).reshape(1, 1),
        block_m=block_m, block_k=block_k, interpret=interp,
    )
    return out[:M, :M]


@functools.partial(jax.jit, static_argnames=("block_n", "block_m", "interpret"))
def diag_quad(
    A: jax.Array,            # (N, M)
    C: jax.Array,            # (M, M)
    *,
    block_n: int = 256,
    block_m: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """diag(A C A^T): (N,) predictive variances without the N x N matrix."""
    N, M = A.shape
    interp = resolve_interpret(interpret)
    block_n = min(block_n, max(8, 1 << (N - 1).bit_length()))
    block_m = min(block_m, max(128, 1 << (M - 1).bit_length()))
    Ap = _pad_to(_pad_to(A, 0, block_n), 1, block_m)
    Cp = _pad_to(_pad_to(C, 0, block_m), 1, block_m)
    out = diag_quad_kernel(
        Ap, Cp, block_n=block_n, block_m=block_m, interpret=interp
    )
    return out[0, :N]
