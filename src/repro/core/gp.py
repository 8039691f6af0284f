"""`GP` — one facade over the whole predictive-posterior pipeline.

The paper's contribution is a single pipeline (Eqs. 8-12) run end-to-end on
an accelerator; this module exposes it as a single self-describing session
object instead of six free functions that each re-take configuration:

    from repro.core.gp import GP, GPSpec

    spec = GPSpec.create(n=8, eps=[0.8, 0.8], noise=0.05)
    gp = GP.fit(X, y, spec)              # spec baked into the session
    mu, cov = gp.predict(Xs)             # nothing re-passed
    mu, var = gp.mean_var(Xs)            # serving path (marginal variance)
    gp = gp.update(X_new, y_new)         # rank-k ingest, no refit
    loss = gp.nlml(X, y)                 # NLML under the session's spec
    gp = GP.optimize(X, y, spec)         # gradient NLML hyperparameter fit

    gp.with_spec(backend="pallas")       # serve-time backend swap (validated)

`GP` is an immutable pytree wrapping a fitted state; every method returns
results or a new `GP`.  Multi-output targets ``y`` of shape ``(N, T)``
share one factorization with per-task mean weights — ``predict``/
``mean_var`` then return ``(N*, T)`` means and a shared variance.
`serve_gp`, `core.distributed` and the chip benchmark (`bench/`) all
speak this one interface.

TWO things are pluggable behind the facade, at different layers:

* the APPROXIMATION FAMILY (``spec.approximation``, a registered
  :class:`~repro.core.approximation.Approximation`): every method below
  dispatches through the family's protocol adapter.  ``"fagp"`` (default)
  is the paper's decomposed-kernel technique with its expansion/backend
  machinery; ``"vecchia"`` (``core.vecchia``) is nearest-neighbor
  conditioning for the clustered-spatial regime —

      spec = GPSpec.create_vecchia([2.0, 2.0], 0.1, kernel="matern52",
                                   neighbors=32)
      gp = GP.fit(X, y, spec)          # same calls, different family
      mu, var = gp.mean_var(Xs)

  A family declares capability flags; calling a method it does not
  implement (e.g. ``predict``/``optimize`` on vecchia) raises the
  structured :class:`~repro.core.approximation.UnsupportedError` at the
  facade boundary, before any compute.

* within the FAGP family, the KERNEL EXPANSION (``spec.expansion`` names a
  registered :class:`~repro.core.expansions.KernelExpansion`): the same
  facade serves the paper's Hermite-Mercer eigen-expansion (default) and
  the random-Fourier families —

      spec = GPSpec.create_rff([0.8, 0.8], kernel="matern52",
                               num_features=256, seed=0)
      gp = GP.fit(X, y, spec)          # same calls, different kernel

``GP.optimize`` learns RFF lengthscales exactly like Mercer ones (the
spectral draws are data leaves on the spec; eps scales them inside the
feature map).  The split ``(params, cfg)`` call shapes were deprecated for
two releases and now raise TypeError (README §Migration).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax

from ..obs.trace import span
from . import fagp  # noqa: F401  (fagp registers both families on import)
from .approximation import (
    Approximation,
    UnsupportedError,
    get_approximation,
    require_capability,
)
from .fagp import FAGPState, GPSpec

__all__ = ["GP", "GPSpec", "Approximation", "UnsupportedError"]


def _approx_for(spec: Optional[GPSpec]) -> Approximation:
    if spec is None:
        raise ValueError(
            "state has no baked GPSpec; attach one with "
            "state.with_spec(spec) first"
        )
    return get_approximation(spec.approximation)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class GP:
    """A fitted GP session: the state (with its spec baked in) plus methods.

    ``state`` is whatever the spec's approximation family fits —
    :class:`~repro.core.fagp.FAGPState` for ``"fagp"``,
    :class:`~repro.core.vecchia.VecchiaState` for ``"vecchia"``.  Construct
    with :meth:`fit`, :meth:`optimize`, or :meth:`from_state`; the default
    constructor is for internal use.
    """

    state: Any

    # -- constructors -------------------------------------------------------

    @classmethod
    def fit(cls, X: jax.Array, y: jax.Array, spec: GPSpec) -> "GP":
        """Fit the posterior; y is (N,) or (N, T) for T tasks sharing one
        factorization.  The spec is baked into the session."""
        with span("gp.fit"):
            ap = _approx_for(spec)
            require_capability(ap, "fit", spec)
            return cls(state=ap.fit(X, y, spec))

    @classmethod
    def from_state(cls, state) -> "GP":
        """Wrap an existing fitted state (e.g. from ``fit_distributed``)."""
        if state.spec is None:
            raise ValueError(
                "state has no baked GPSpec; attach one with "
                "state.with_spec(spec) before wrapping it in a GP"
            )
        return cls(state=state)

    @classmethod
    def optimize(
        cls,
        X: jax.Array,
        y: jax.Array,
        spec: GPSpec,
        *,
        steps: int = 100,
        lr: float = 5e-2,
        restarts: int = 1,
        tol: Optional[float] = None,
        jitter: float = 0.3,
        seed: int = 0,
        callback: Optional[Callable[[int, float, GPSpec], None]] = None,
    ) -> "GP":
        """Gradient-based NLML hyperparameter learning (the paper's declared
        future work), then fit at the learned hyperparameters.

        Minimizes ``nlml(X, y, spec)/N`` over (eps, rho, noise) in log space
        with AdamW on the fleet lane engine (``repro.optim.gp_hyperopt`` —
        the same engine ``GPBank.optimize`` runs for whole tenant fleets):
        ``restarts`` lanes start from log-space jittered inits (restart 0
        is always the unperturbed spec) and are stepped together in ONE
        compiled executable, the best lane by final NLML wins, and ``tol``
        freezes converged lanes early.  The moment accumulation inside the
        objective streams through the backend registry, so optimization
        never materializes the N x M feature matrix on either backend.

        ``callback(step, nlml_per_row, current_spec)`` is invoked every 10%
        of the run with the currently-best lane's loss and hyperparameters.

        Families that do not declare the ``optimize`` capability (vecchia,
        for now) refuse here with a structured ``UnsupportedError``.
        """
        ap = _approx_for(spec)
        require_capability(ap, "optimize", spec)
        return cls(state=ap.optimize(
            X, y, spec, steps=steps, lr=lr, restarts=restarts, tol=tol,
            jitter=jitter, seed=seed, callback=callback,
        ))

    # -- introspection ------------------------------------------------------

    @property
    def spec(self) -> GPSpec:
        return self.state.spec

    @property
    def approximation(self) -> Approximation:
        """The session's registered approximation family."""
        return _approx_for(self.spec)

    @property
    def n_features(self) -> int:
        """M, the number of Mercer features (size of the fitted system);
        FAGP-family sessions only."""
        return self.state.n_features

    @property
    def n_tasks(self) -> int:
        return self.state.n_tasks

    # -- the pipeline -------------------------------------------------------

    def predict(self, Xs: jax.Array, *, mode: str = "fused"):
        """Posterior mean and full covariance at Xs (paper Eqs. 11-12)."""
        ap = self.approximation
        require_capability(ap, "predict", self.spec)
        return ap.predict(self.state, Xs, mode=mode)

    def mean_var(self, Xs: jax.Array):
        """Posterior mean and marginal variance — the serving path."""
        with span("gp.mean_var"):
            ap = self.approximation
            require_capability(ap, "mean_var", self.spec)
            return ap.mean_var(self.state, Xs)

    def update(self, X_new: jax.Array, y_new: jax.Array) -> "GP":
        """Absorb new observations (FAGP: rank-k Cholesky update; vecchia:
        exact concatenation into the conditioning pool)."""
        ap = self.approximation
        require_capability(ap, "update", self.spec)
        return GP(state=ap.update(self.state, X_new, y_new))

    def nlml(self, X: jax.Array, y: jax.Array):
        """NLML of (X, y) under this session's spec."""
        ap = self.approximation
        require_capability(ap, "nlml", self.spec)
        return ap.nlml(X, y, self.spec)

    def with_spec(self, spec: Optional[GPSpec] = None, **overrides) -> "GP":
        """Serve-time escape hatch: swap execution knobs (backend,
        block_rows); structural changes are rejected (see
        :meth:`FAGPState.with_spec`)."""
        return GP(state=self.state.with_spec(spec, **overrides))

    # -- durability ----------------------------------------------------------

    def save(self, ckpt_dir, *, step: Optional[int] = None) -> int:
        """Serialize this session under ``ckpt_dir`` (versioned: each save
        lands as ``step_<version>``; ``step=None`` auto-increments).  The
        manifest records the spec's structure — approximation family,
        expansion, truncation, an omega hash — so :meth:`load` round-trips
        bit-exactly and a restore into an incompatible spec raises.
        Returns the version written."""
        from repro.checkpoint import gpstate

        return gpstate.save_state(ckpt_dir, self.state, step=step)

    @classmethod
    def load(cls, ckpt_dir, *, step: Optional[int] = None,
             spec: Optional[GPSpec] = None) -> "GP":
        """Restore a session saved by :meth:`save` (``step=None`` loads the
        newest version).  The spec is rebuilt from the checkpoint itself —
        hyperparameter leaves, omega draws, approximation tag and all
        (manifests from before the approximation protocol load as
        ``"fagp"``).  Passing ``spec`` validates the checkpoint against it
        (structure AND hyperparameters, like ``with_spec``) and raises on
        mismatch."""
        from repro.checkpoint import gpstate

        _, state, _ = gpstate.load_state(
            ckpt_dir, step=step, like_spec=spec, require_hypers_match=True,
        )
        return cls(state=state)
