"""Blocked k-nearest-neighbor search — the Vecchia conditioning-set builder.

The Vecchia approximation (``core/vecchia.py``) needs, for every query (or
every training row), the indices of its k nearest training points.  The
naive route materializes the full Q x N pairwise-distance matrix — exactly
the N-sized intermediate this repo's streaming paths exist to avoid.  Here
the queries are processed in blocks of ``block_q`` (``lax.map``) and,
inside each query block, the training set streams through in blocks of
``block_t`` (``lax.scan``) while a running top-k of squared distances is
merged with ``jax.lax.top_k`` on the concatenated ``(block_q, k +
block_t)`` candidate set.  Peak live memory is O(block_q * (k + block_t))
— never Q x N — pinned by a jaxpr sweep in tests/test_vecchia.py exactly
like the streaming-fit memory claims.

``ordered_topk`` adds the Vecchia ordering constraint: row i may only
condition on rows j < i (so the product of conditionals telescopes to the
exact joint at full conditioning sets).  Rows with fewer than k admissible
candidates come back with +inf distance in the spare slots; the caller
masks on finiteness.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

__all__ = ["knn_search", "ordered_topk", "sq_dists"]


def sq_dists(Xq: jax.Array, Xt: jax.Array) -> jax.Array:
    """Squared euclidean distances (Bq, Bt) between two point blocks."""
    q2 = jnp.sum(Xq * Xq, axis=1)[:, None]
    t2 = jnp.sum(Xt * Xt, axis=1)[None, :]
    cross = jnp.matmul(Xq, Xt.T, precision=jax.lax.Precision.HIGHEST)
    return jnp.maximum(q2 + t2 - 2.0 * cross, 0.0)


def _train_blocks(Xt: jax.Array, block_t: int):
    """Pad the training set to a whole number of blocks; returns
    (Xtb (nblk, block_t, p), jb (nblk, block_t) global row indices)."""
    N = Xt.shape[0]
    nblk = max(1, -(-N // block_t))
    pad = nblk * block_t - N
    Xtp = jnp.pad(Xt, ((0, pad), (0, 0)))
    jb = jnp.arange(nblk * block_t, dtype=jnp.int32)
    return Xtp.reshape(nblk, block_t, -1), jb.reshape(nblk, block_t)


def _scan_topk(Xq, Xtb, jb, k: int, n_train: int, iq=None):
    """Streamed top-k over pre-blocked training data for ONE query block.

    Xq (Bq, p); Xtb (nblk, Bt, p); jb (nblk, Bt) global training indices
    (padding rows have jb >= n_train and are never selected).  ``iq``
    (Bq,) global query row indices, if given, restricts candidates to
    j < iq — the Vecchia ordered-conditioning constraint.  Returns
    (dists (Bq, k) ascending, idx (Bq, k)); inadmissible slots hold +inf.
    """
    Bq = Xq.shape[0]
    init = (
        jnp.full((Bq, k), jnp.inf, Xq.dtype),
        jnp.zeros((Bq, k), jnp.int32),
    )

    def step(carry, blk):
        best_d, best_i = carry
        Xt_i, j_i = blk
        d = sq_dists(Xq, Xt_i)                                # (Bq, Bt)
        bad = j_i[None, :] >= n_train
        if iq is not None:
            bad = bad | (j_i[None, :] >= iq[:, None])
        d = jnp.where(bad, jnp.inf, d)
        cand_d = jnp.concatenate([best_d, d], axis=1)         # (Bq, k+Bt)
        cand_i = jnp.concatenate(
            [best_i, jnp.broadcast_to(j_i[None, :], d.shape)], axis=1
        )
        neg, pos = jax.lax.top_k(-cand_d, k)
        return (-neg, jnp.take_along_axis(cand_i, pos, axis=1)), None

    (best_d, best_i), _ = jax.lax.scan(step, init, (Xtb, jb))
    return best_d, best_i


def _query_blocks(Xq: jax.Array, block_q: int):
    Q = Xq.shape[0]
    nblk = max(1, -(-Q // block_q))
    pad = nblk * block_q - Q
    return jnp.pad(Xq, ((0, pad), (0, 0))).reshape(nblk, block_q, -1)


@partial(jax.jit, static_argnames=("k", "block_q", "block_t"))
def knn_search(Xq: jax.Array, Xt: jax.Array, k: int, *,
               block_q: int = 128, block_t: int = 512):
    """For each query row, the k nearest training rows.

    Returns (dists (Q, k), idx (Q, k)): squared distances ascending and the
    matching global training indices.  No Q x N distance matrix is ever
    formed (see module docstring).
    """
    Q, N = Xq.shape[0], Xt.shape[0]
    if k < 1 or k > N:
        raise ValueError(f"knn_search needs 1 <= k <= N={N}, got k={k}")
    block_q = max(1, min(block_q, Q))
    block_t = max(1, min(block_t, N))
    Xtb, jb = _train_blocks(Xt, block_t)
    d, i = jax.lax.map(
        lambda Xqi: _scan_topk(Xqi, Xtb, jb, k, N), _query_blocks(Xq, block_q)
    )
    return d.reshape(-1, k)[:Q], i.reshape(-1, k)[:Q]


@partial(jax.jit, static_argnames=("k", "block_q", "block_t"))
def ordered_topk(X: jax.Array, k: int, *,
                 block_q: int = 128, block_t: int = 512):
    """Vecchia conditioning sets under the natural ordering: for each row
    i, the (up to) k nearest rows among j < i.

    Returns (idx (N, k), mask (N, k) float32): ``mask[i, s] == 1`` marks a
    valid neighbor; rows i < k have spare slots masked 0 (their index is
    clamped to 0 so gathers stay in bounds).
    """
    N = X.shape[0]
    if k < 1 or k > N:
        raise ValueError(f"ordered_topk needs 1 <= k <= N={N}, got k={k}")
    block_q = max(1, min(block_q, N))
    block_t = max(1, min(block_t, N))
    Xtb, jb = _train_blocks(X, block_t)
    Xqb = _query_blocks(X, block_q)
    nqb = Xqb.shape[0]
    iqb = jnp.arange(nqb * block_q, dtype=jnp.int32).reshape(nqb, block_q)
    d, i = jax.lax.map(
        lambda args: _scan_topk(args[0], Xtb, jb, k, N, iq=args[1]),
        (Xqb, iqb),
    )
    d = d.reshape(-1, k)[:N]
    i = i.reshape(-1, k)[:N]
    mask = jnp.isfinite(d)
    return jnp.where(mask, i, 0), mask.astype(X.dtype)
