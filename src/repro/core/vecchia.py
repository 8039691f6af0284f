"""Vecchia nearest-neighbor conditioning — the sibling approximation.

Where FAGP (the paper's technique) replaces the N x N kernel inverse by a
GLOBAL low-rank feature system, the Vecchia approximation is LOCAL: the
joint density is factorized along the data ordering and each conditional
is truncated to the k nearest preceding points,

    p(y) ~= prod_i p(y_i | y_{c(i)}),   c(i) = k nearest rows among j < i,

and prediction conditions each query on its k nearest training points.
Every solve is a k x k Cholesky — batched over rows as B x k x k lanes
(the same small-solve batching the bank and the hyperopt lane engine
exploit) — so cost is O(N k^3) with NO N x N (or Q x N) intermediate: the
conditioning sets come from the blocked streaming top-k in
``repro.kernels.knn`` (pinned by a jaxpr sweep in tests/test_vecchia.py).
This is the regime decomposed-kernel expansions handle worst — large,
clustered, short-lengthscale spatial data — and the reason ROADMAP item 3
wants it as a sibling family behind the facade rather than a fourth
expansion: its state is the raw data, not a feature-space factorization.

The family plugs in through ``core.approximation``: ``spec =
GPSpec.create_vecchia(eps, noise, kernel="se"|"matern52", neighbors=k)``
and every ``GP`` call dispatches here by ``spec.approximation``.  The
kernel oracles are the exact reference kernels (``exact_gp.KERNELS`` — the
same table the expansion parity tests trust), so as k -> N both prediction
and the ordered-factorization NLML converge to ``exact_gp`` (exactly, at
full conditioning sets: the product of conditionals telescopes to the
joint).  Declared capabilities: fit / mean_var / update / nlml.  Refused
(structured ``UnsupportedError``): ``predict`` (full Q x Q posterior
covariance — the cross-query terms need a joint conditioning set),
``optimize`` and bank admission.

Layering note: this module must not import ``fagp`` at module scope (fagp
imports it at its bottom to register the family); the spec compatibility
helpers are pulled lazily inside ``with_spec``.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from .approximation import (
    Approximation,
    UnsupportedError,
    register_approximation,
)
from .exact_gp import KERNELS
from repro.kernels import knn

__all__ = ["VecchiaApproximation", "VecchiaState"]

_BLOCK_Q = 128  # query rows per batched-Cholesky lane block


def _block_q(k: int) -> int:
    """Query-block size: bounded lane memory (block_q * k^2 floats)."""
    return int(max(1, min(_BLOCK_Q, (1 << 21) // max(1, k * k))))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class VecchiaState:
    """A fitted Vecchia session.  The "factorization" IS the training data:
    conditioning sets and k x k solves are rebuilt per query batch, so
    ``update`` is an exact concatenation (no approximation drift) and the
    checkpoint leaves are simply (X, y)."""

    X: jax.Array                     # (N, p) training inputs
    y: jax.Array                     # (N,) or (N, T) training targets
    spec: Optional[Any] = None       # baked GPSpec (approximation="vecchia")

    @property
    def n_train(self) -> int:
        return self.X.shape[0]

    @property
    def n_tasks(self) -> int:
        return 1 if self.y.ndim == 1 else self.y.shape[1]

    @property
    def n_features(self) -> int:
        raise UnsupportedError(
            "approximation 'vecchia' does not support 'n_features': the "
            "state is the raw data, not a feature-space factorization",
            layer="approximation", capability="n_features", spec=self.spec,
        )

    def with_spec(self, spec=None, **overrides) -> "VecchiaState":
        """Same contract as :meth:`FAGPState.with_spec`: execution knobs
        (block_rows, backend) may change at serve time; structure
        (approximation, kernel, neighbors) and hyperparameters are frozen
        — refit instead (for Vecchia a refit is O(1) anyway)."""
        from . import fagp  # lazy: no module-scope fagp import here

        if spec is None:
            if self.spec is None:
                raise ValueError(
                    "state has no baked spec to override; pass a full "
                    "GPSpec: state.with_spec(spec)"
                )
            spec = dataclasses.replace(self.spec, **overrides)
        elif overrides:
            raise TypeError(
                "pass either a full spec or keyword overrides, not both"
            )
        if self.spec is not None:
            for f in fagp._STRUCTURAL_FIELDS:
                if getattr(spec, f) != getattr(self.spec, f):
                    raise ValueError(
                        f"spec/state mismatch: state was fitted with "
                        f"{self.spec.describe()} but the new spec has "
                        f"{f}={getattr(spec, f)!r}; structural choices are "
                        f"frozen into the session — refit instead"
                    )
            for f in fagp._HYPER_FIELDS:
                if not fagp._leaf_equal(
                    getattr(spec, f), getattr(self.spec, f)
                ):
                    raise ValueError(
                        f"with_spec: spec/state mismatch: {f} differs from "
                        f"the value this state was fitted with; refit "
                        f"instead"
                    )
        VECCHIA.validate(spec)
        return dataclasses.replace(self, spec=spec)


# ---------------------------------------------------------------------------
# Batched conditioning math.  Every helper below takes gathered neighbor
# blocks and runs B x k x k Cholesky lanes (one jnp.linalg.cholesky over a
# leading batch axis — the lane idiom of bank/gp_hyperopt).
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("kernel", "k", "block_q", "block_t"))
def _mean_var(X, y2, Xs, eps, noise, *, kernel, k, block_q, block_t):
    """Posterior mean (Q, T) and latent marginal variance (Q,): each query
    conditions on its k nearest training rows.  Both reference kernels are
    unit-variance, so k(x, x) = 1."""
    kf = KERNELS[kernel]
    sig2 = noise**2
    Q = Xs.shape[0]
    _, idx = knn.knn_search(Xs, X, k, block_q=block_q, block_t=block_t)

    nblk = max(1, -(-Q // block_q))
    pad = nblk * block_q - Q
    Xsb = jnp.pad(Xs, ((0, pad), (0, 0))).reshape(nblk, block_q, -1)
    idxb = jnp.pad(idx, ((0, pad), (0, 0))).reshape(nblk, block_q, k)
    eye = jnp.eye(k, dtype=X.dtype)[None]

    def blk(args):
        Xq, nb = args
        Xn = X[nb]                                             # (B, k, p)
        yn = y2[nb]                                            # (B, k, T)
        Knn = jax.vmap(lambda Z: kf(Z, Z, eps))(Xn)
        ks = jax.vmap(lambda xq, Z: kf(xq[None, :], Z, eps)[0])(Xq, Xn)
        L = jnp.linalg.cholesky(Knn + sig2 * eye)
        alpha = jax.vmap(
            lambda Lc, bc: jax.scipy.linalg.cho_solve((Lc, True), bc)
        )(L, yn)
        mu = jnp.einsum("bk,bkt->bt", ks, alpha,
                        precision=jax.lax.Precision.HIGHEST)
        w = jax.vmap(
            lambda Lc, c: jax.scipy.linalg.solve_triangular(
                Lc, c, lower=True
            )
        )(L, ks)
        var = jnp.maximum(1.0 - jnp.sum(w * w, axis=1), 0.0)
        return mu, var

    mu, var = jax.lax.map(blk, (Xsb, idxb))
    return (
        mu.reshape(-1, y2.shape[1])[:Q],
        var.reshape(-1)[:Q],
    )


@partial(jax.jit, static_argnames=("kernel", "k", "block_q", "block_t"))
def _nlml(X, y2, eps, noise, *, kernel, k, block_q, block_t):
    """Ordered-factorization NLML: sum_i -log N(y_i; mu_i, var_i) with
    (mu_i, var_i) the conditional of y_i given its (up to) k nearest
    PRECEDING rows.  At k >= N-1 the conditionals telescope to the exact
    joint, so this equals ``exact_gp.nlml`` (tests pin it).  Rows with
    fewer than k admissible neighbors (i < k) get identity-filled masked
    slots — mathematically absent, numerically inert."""
    kf = KERNELS[kernel]
    sig2 = noise**2
    N = X.shape[0]
    T = y2.shape[1]
    nbr, m = knn.ordered_topk(X, k, block_q=block_q, block_t=block_t)

    nblk = max(1, -(-N // block_q))
    pad = nblk * block_q - N
    Xb = jnp.pad(X, ((0, pad), (0, 0))).reshape(nblk, block_q, -1)
    yb = jnp.pad(y2, ((0, pad), (0, 0))).reshape(nblk, block_q, T)
    nb_ = jnp.pad(nbr, ((0, pad), (0, 0))).reshape(nblk, block_q, k)
    mb = jnp.pad(m, ((0, pad), (0, 0))).reshape(nblk, block_q, k)
    rv = jnp.pad(jnp.ones((N,), X.dtype), (0, pad)).reshape(nblk, block_q)
    eye = jnp.eye(k, dtype=X.dtype)[None]

    def blk(args):
        Xi, yi, nb, mi, rvi = args
        Xc = X[nb]                                             # (B, k, p)
        yc = y2[nb]                                            # (B, k, T)
        Kcc = jax.vmap(lambda Z: kf(Z, Z, eps))(Xc)
        ks = jax.vmap(lambda xq, Z: kf(xq[None, :], Z, eps)[0])(Xi, Xc)
        mm = mi[:, :, None] * mi[:, None, :]                   # (B, k, k)
        A = mm * (Kcc + sig2 * eye) + (1.0 - mm) * eye
        c = mi * ks                                            # (B, k)
        L = jnp.linalg.cholesky(A)
        alpha = jax.vmap(
            lambda Lc, bc: jax.scipy.linalg.cho_solve((Lc, True), bc)
        )(L, mi[:, :, None] * yc)
        mu = jnp.einsum("bk,bkt->bt", c, alpha,                # (B, T)
                        precision=jax.lax.Precision.HIGHEST)
        w = jax.vmap(
            lambda Lc, cc: jax.scipy.linalg.solve_triangular(
                Lc, cc, lower=True
            )
        )(L, c)
        var = 1.0 + sig2 - jnp.sum(w * w, axis=1)              # (B,)
        resid = yi - mu
        nll = 0.5 * (
            T * jnp.log(2.0 * jnp.pi * var)
            + jnp.sum(resid * resid, axis=1) / var
        )
        return jnp.sum(nll * rvi)

    return jnp.sum(jax.lax.map(blk, (Xb, yb, nb_, mb, rv)))


# ---------------------------------------------------------------------------
# The registered family
# ---------------------------------------------------------------------------


def _as_2d(y: jax.Array) -> jax.Array:
    return y if y.ndim == 2 else y[:, None]


class VecchiaApproximation(Approximation):
    """``spec.approximation == "vecchia"``: nearest-neighbor conditioning
    with ``spec.kernel`` in {'se', 'matern52'} (the exact reference
    oracles) and ``spec.neighbors`` = k."""

    name = "vecchia"
    capabilities = frozenset({"fit", "mean_var", "update", "nlml"})
    state_type = VecchiaState

    # -- spec validation ----------------------------------------------------

    def validate(self, spec) -> None:
        if spec.kernel not in KERNELS:
            raise ValueError(
                f"vecchia kernel must be one of {sorted(KERNELS)}, got "
                f"{spec.kernel!r}"
            )
        if spec.neighbors is None or int(spec.neighbors) < 1:
            raise ValueError(
                f"vecchia needs neighbors >= 1 (the conditioning-set size "
                f"k), got {spec.neighbors!r}"
            )
        if spec.omega is not None:
            raise ValueError(
                "vecchia takes no spectral draws (omega); it evaluates the "
                "exact kernel on k-neighbor sets"
            )

    # -- blocking knobs -----------------------------------------------------

    @staticmethod
    def _blocks(spec, n_train: int) -> tuple:
        k = int(spec.neighbors)
        return _block_q(k), max(1, min(int(spec.block_rows), n_train))

    # -- facade operations --------------------------------------------------

    def fit(self, X, y, spec) -> VecchiaState:
        X = jnp.asarray(X)
        y = jnp.asarray(y)
        if X.ndim != 2:
            raise ValueError(f"X must be (N, p), got shape {X.shape}")
        if spec.p != X.shape[1]:
            raise ValueError(
                f"spec/input mismatch: {spec.describe()} was built for "
                f"p={spec.p} input dimensions but the data has "
                f"p={X.shape[1]}"
            )
        if y.shape[0] != X.shape[0]:
            raise ValueError(
                f"X has {X.shape[0]} rows but y has {y.shape[0]}"
            )
        if int(spec.neighbors) > X.shape[0]:
            raise ValueError(
                f"vecchia neighbors={int(spec.neighbors)} exceeds the "
                f"training-set size N={X.shape[0]}; choose k <= N"
            )
        return VecchiaState(X=X, y=y, spec=spec)

    def mean_var(self, state: VecchiaState, Xs):
        spec = state.spec
        k = int(spec.neighbors)
        bq, bt = self._blocks(spec, state.n_train)
        mu, var = _mean_var(
            state.X, _as_2d(state.y), jnp.asarray(Xs), spec.eps, spec.noise,
            kernel=spec.kernel, k=k, block_q=bq, block_t=bt,
        )
        return (mu[:, 0] if state.y.ndim == 1 else mu), var

    def update(self, state: VecchiaState, X_new, y_new) -> VecchiaState:
        X_new = jnp.asarray(X_new)
        y_new = jnp.asarray(y_new)
        if y_new.ndim != state.y.ndim or (
            y_new.ndim == 2 and y_new.shape[1] != state.y.shape[1]
        ):
            raise ValueError(
                f"update task mismatch: state holds {state.n_tasks} "
                f"task(s) but y_new has shape {y_new.shape}"
            )
        return dataclasses.replace(
            state,
            X=jnp.concatenate([state.X, X_new], axis=0),
            y=jnp.concatenate([state.y, y_new], axis=0),
        )

    def nlml(self, X, y, spec, *, mask=None):
        if mask is not None:
            raise UnsupportedError(
                f"approximation 'vecchia' does not support 'nlml_mask' for "
                f"{spec.describe()}: the ordered factorization has no "
                f"masked-row form yet",
                layer="approximation", capability="nlml_mask", spec=spec,
            )
        X = jnp.asarray(X)
        y = jnp.asarray(y)
        k = min(int(spec.neighbors), X.shape[0])
        bq, bt = self._blocks(spec, X.shape[0])
        return _nlml(
            X, _as_2d(y), spec.eps, spec.noise,
            kernel=spec.kernel, k=k, block_q=bq, block_t=bt,
        )

    # -- checkpoint hooks ---------------------------------------------------

    def ckpt_leaf_names(self) -> tuple:
        return ("X", "y")

    def ckpt_leaves(self, state: VecchiaState) -> dict:
        return {"X": state.X, "y": state.y}

    def ckpt_meta(self, state: VecchiaState) -> dict:
        return {"N": int(state.n_train), "n_tasks": int(state.n_tasks)}

    def ckpt_rebuild(self, spec, leaves: dict, train) -> VecchiaState:
        return VecchiaState(X=leaves["X"], y=leaves["y"], spec=spec)


VECCHIA = VecchiaApproximation()
register_approximation(VECCHIA)
